"""Hymba as published, at the reduced size on the CPU in float32: the
served program (prefill, then decode through the cache) and the engine's
crash and recovery against the plain reference (``hymba_reference.py``),
and the reference's own windowed mask.

The reduced config keeps every mechanism: 4 meta tokens, a window of 8,
full attention in the first, a middle and the last layer, KV pairs and a
KV triple, expand 2 and value heads twice the q/k head size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hymba_reference as ref
from repro.configs import base, registry
from repro.models import backbone
from repro.models.model import build
from repro.serve.engine import EngineConfig, ServingEngine

CFG = base.reduced(registry.get("hymba-1.5b"))
S = 30                 # tokens: past window + meta (12) more than twice


def _params(model, seed=0):
    """Weights with every vector (norm gains, biases) moved off its init,
    so a gain, bias or norm the program skips changes the logits.  Layer
    leaves carry a leading repeat axis: their vectors are 2-D."""
    params = model.init_params(jax.random.PRNGKey(seed))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
    out = []
    for (path, x), k in zip(flat, keys):
        vector = x.ndim <= (2 if path[0].key == "blocks" else 1)
        out.append(x + 0.1 * jax.random.normal(k, x.shape) if vector else x)
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def model_params():
    model = build(CFG, compute_dtype=jnp.float32)
    return model, _params(model)


def _tokens(b=2, s=S, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, CFG.vocab, (b, s)), jnp.int32)


def test_reduced_config_holds_every_mechanism():
    kinds = [t.partition(":")[2] for t in CFG.layer_pattern]
    full = [i for i, k in enumerate(kinds) if k == "full"]
    assert full[0] == 0 and full[-1] == CFG.n_layers - 1 and len(full) == 3
    groups = {}
    for i, src in enumerate(CFG.kv_source):
        groups.setdefault(src, []).append(i)
    sizes = sorted(len(g) for g in groups.values() if
                   kinds[g[0]] == "local")
    assert 2 in sizes and 3 in sizes
    assert CFG.meta_tokens and CFG.window < S
    assert CFG.d_inner == 2 * CFG.d_model
    assert CFG.v_head_dim == 2 * CFG.resolved_head_dim


def test_ssm_offsets_start_at_mambas_initialisation():
    """``a_log``, ``dt_bias`` and ``d_skip`` are offsets: ``init_params``
    starts every layer's at 0, where A = -(1..N), dt runs log-spaced from
    1e-3 to 0.1 over the channels and D = 1."""
    params = build(CFG).init_params(jax.random.PRNGKey(0))
    layers = list(backbone.layer_params(CFG, params))
    assert len(layers) == CFG.n_layers
    for p in layers:
        for name in backbone.SSM_OFFSETS:
            np.testing.assert_array_equal(p["mamba"][name], 0.0)
        a_log, dt_bias, d = backbone.ssm_coefficients(CFG, p["mamba"])
        np.testing.assert_allclose(
            -np.exp(a_log), -np.tile(np.arange(1.0, CFG.ssm_state + 1),
                                     (CFG.d_inner, 1)), rtol=1e-6)
        dt = np.log(np.asarray(jax.nn.softplus(dt_bias), np.float64))
        np.testing.assert_allclose(
            dt, np.linspace(np.log(1e-3), np.log(0.1), CFG.d_inner),
            atol=1e-5)
        np.testing.assert_array_equal(d, 1.0)


@pytest.mark.parametrize("k", [0, 5, 17])
def test_prefill_then_decode_matches_the_reference(model_params, k):
    """Prefill the first k tokens (none: the meta tokens alone), then
    decode every later one through the cache, past window + meta.  The
    logits at each position match the reference's full forward within
    1e-3 of the row's standard deviation: both are float32 at the highest
    precision, and what differs is the order of summation (blockwise
    softmax, the chunked scan) over 14 layers, ~1e-6 of a row's spread;
    a wrong mask, a skipped norm or a state off by one position moves
    the logits by ~1e-1 or more."""
    model, params = model_params
    tokens = _tokens()
    logits, cache = jax.jit(model.prefill, static_argnames="s_max")(
        params, {"tokens": tokens[:, :k]}, s_max=S)
    got = [logits]
    decode = jax.jit(model.decode_step)
    for p in range(k, S - 1):
        logits, cache = decode(params, cache, tokens[:, p],
                               jnp.asarray(p, jnp.int32))
        got.append(logits)
    got = np.asarray(jnp.stack(got, 1))[..., :CFG.vocab]
    want = np.asarray(ref.forward(CFG, params, tokens))
    want = want[:, max(k - 1, 0):-1]
    if k == 0:          # the meta-only prefill predicts no token
        got = got[:, 1:]
    err = np.abs(got - want) / want.std(-1, keepdims=True)
    assert err.max() < 1e-3, err.max()


def test_engine_crash_recovery_serves_the_uncrashed_twins_logits(
        model_params, tmp_path):
    """Prompts longer than window + meta, a crash mid-decode: recovery's
    grouped re-prefill rebuilds full KV, windowed rings and SSM/conv state,
    and every later step's logits equal the twin's (float32, the same
    programs: 1e-5)."""
    model, params = model_params
    ec = EngineConfig(max_batch=3, s_max=40, max_requests=16)
    prompts = [np.asarray(_tokens(1, n, seed=n)[0], np.int64)
               for n in (14, 21, 14)]

    def fresh(name):
        eng = ServingEngine(model, params, ec,
                            arena_path=str(tmp_path / name))
        for rid, p in enumerate(prompts):
            eng.add_request(rid + 1, p)
        return eng

    twin, eng = fresh("twin"), fresh("crashed")
    for _ in range(4):
        assert eng.step() == twin.step()
    eng.crash()
    eng.recover()
    assert eng.last_recovery.stage("engine").detail["prefill_groups"] == 2
    for _ in range(6):
        assert eng.step() == twin.step()
        for rid, logits in twin.last_logits.items():
            np.testing.assert_allclose(np.asarray(eng.last_logits[rid]),
                                       np.asarray(logits),
                                       rtol=1e-5, atol=1e-5)


def test_reference_window_ignores_far_tokens_but_never_meta(model_params):
    """In a windowed layer of the reference, the last position's output
    does not move when a token a window or more back changes, and moves
    when a meta token does."""
    _, params = model_params
    p = next(lay for tag, lay in zip(CFG.layer_pattern,
                                     backbone.layer_params(CFG, params))
             if tag.endswith(":local"))["attn"]
    s, m, w = 24, CFG.meta_tokens, CFG.window
    u = jax.random.normal(jax.random.PRNGKey(5), (1, s, CFG.d_model))

    def last(u):
        out, _ = ref._attention(CFG, p, u, None, w)
        return np.asarray(out[0, -1])

    base_out = last(u)
    far = s - 1 - w                     # just outside the window
    assert far >= m
    np.testing.assert_array_equal(last(u.at[0, far].add(1.0)), base_out)
    assert np.abs(last(u.at[0, far + 1].add(1.0)) - base_out).max() > 1e-3
    assert np.abs(last(u.at[0, m - 1].add(1.0)) - base_out).max() > 1e-3
    mask = np.asarray(ref.attention_mask(s, w, m))
    assert mask[-1, :m].all() and not mask[-1, m:far + 1].any()
