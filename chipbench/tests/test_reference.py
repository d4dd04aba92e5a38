"""The plain references against the served program at a reduced size on
the CPU in float32: prefill a prefix, then decode through the cache, and
compare every position's logits with the reference's full forward."""
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import dims as D
from chipbench import spec, weights
from repro.configs import base
from repro.models.model import build

CONFIGS = sorted(p.stem for p in (spec.HERE / "configs").glob("*.json"))


def _conf(name):
    return json.loads((spec.HERE / "configs" / f"{name}.json").read_text())


def _small(name):
    conf = _conf(name)
    cfg = base.reduced(D.arch_of(conf))
    return conf, cfg, D.dims_of(cfg, conf["model"])


def _served_logits(model, params, tokens, k):
    """Logits at positions k-1 .. S-2 as the engine produces them."""
    s = tokens.shape[1]
    logits, cache = jax.jit(model.prefill, static_argnames="s_max")(
        params, {"tokens": tokens[:, :k]}, s_max=s)
    out = [logits]
    decode = jax.jit(model.decode_step)
    for p in range(k, s - 1):
        logits, cache = decode(params, cache, tokens[:, p],
                               jnp.asarray(p, jnp.int32))
        out.append(logits)
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_served_program(name):
    conf, cfg, dims = _small(name)
    model = build(cfg, compute_dtype=jnp.float32)
    params = weights.make(model.param_specs(), seed=2**31 + 7)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab, (2, 20)), jnp.int32)
    k = 6
    got = np.asarray(_served_logits(model, params, tokens, k))[..., :cfg.vocab]
    mod = importlib.import_module(f"chipbench.reference.{conf['reference']}")
    want = np.asarray(mod.forward(params, tokens, dims))[:, k - 1:-1]
    scale = want.std(axis=-1, keepdims=True)
    err = np.abs(got - want) / scale
    assert err.max() < 1e-3, err.max()


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_control_precision_moves_the_logits(name, quant):
    conf, cfg, dims = _small(name)
    model = build(cfg, compute_dtype=jnp.float32)
    params = weights.make(model.param_specs(), seed=3)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        1, cfg.vocab, (1, 16)), jnp.int32)
    mod = importlib.import_module(f"chipbench.reference.{conf['reference']}")
    full = np.asarray(mod.forward(params, tokens, dims))
    low = np.asarray(mod.forward(params, tokens, dims, quant))
    rel = np.abs(full - low).max() / full.std()
    assert 1e-4 < rel < 0.5, rel


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_states_the_program_sizes_and_its_cuts(name):
    conf = _conf(name)
    assert conf["model"] == D.dims_of(D.arch_of(conf), conf["model"])
    # every key that differs from the published configuration is listed
    # in ``reduced``, and no other
    pub = conf["published"]
    assert set(pub) == set(conf["model"])
    assert sorted(k for k in pub if pub[k] != conf["model"][k]) == \
        sorted(conf["reduced"])
    entry = {c["name"]: c for c in spec.benchmark()["configs"]}.get(name)
    if entry is not None:
        assert entry["reduced"] == conf["reduced"]
        assert entry["source"] == conf["source"]
