"""Model.compute_params: the matmul weights cast to the compute dtype once.

The forward casts every leaf of ``backbone.MATMUL_WEIGHTS`` to the compute
dtype on each read, so the cast tree must give the f32 tree's logits bit
for bit; a leaf the forward reads in f32 that the table named would break
that in its architecture.  The serving engine holds the cast tree across a
crash and recovery."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base, registry
from repro.models import backbone
from repro.models import layers
from repro.models.model import build
from repro.serve.engine import EngineConfig, ServingEngine


def _batch(cfg, b, s):
    k = jax.random.PRNGKey(3)
    batch = {"tokens": jax.random.randint(k, (b, s), 0, cfg.vocab)}
    if cfg.family == "audio":
        batch["frames"] = 0.02 * jax.random.normal(
            k, (b, cfg.encoder_seq, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        batch["context"] = 0.02 * jax.random.normal(
            k, (b, cfg.context_seq, cfg.d_model), jnp.float32)
    return batch


def _named(path) -> bool:
    return getattr(path[-1], "key", None) in backbone.MATMUL_WEIGHTS


@pytest.mark.parametrize("arch", list(registry.ARCHS))
def test_cast_tree_gives_the_f32_trees_logits(arch, monkeypatch):
    cfg = base.reduced(registry.get(arch))
    if cfg.moe is not None:
        # XLA's CPU backend has no bf16 x bf16 -> f32 dot with batch
        # dimensions (the expert einsums); with the row-parallel outputs
        # in the compute dtype the MoE forward runs here
        monkeypatch.setitem(layers.LOWP_ROW_REDUCE, "on", True)
    model = build(cfg, compute_dtype=jnp.bfloat16)
    params = model.init_params(jax.random.PRNGKey(0))
    # init leaves the norm gains, biases and gates at exact values (0, 1,
    # -2) that a bf16 cast would keep: move every vector off them, so a
    # leaf the forward reads in f32 changes the logits if the table names it
    noise = jax.random.split(jax.random.PRNGKey(1), len(jax.tree.leaves(params)))
    params = jax.tree.unflatten(jax.tree.structure(params), [
        x + 0.1 * jax.random.normal(k, x.shape, x.dtype) if x.ndim <= 1 else x
        for x, k in zip(jax.tree.leaves(params), noise)])
    cast, stats = model.compute_params(params)

    # the table's leaves are bf16 now; every other leaf is the f32 object
    flat = jax.tree_util.tree_leaves_with_path(params)
    flat_cast = jax.tree_util.tree_leaves_with_path(cast)
    assert [p for p, _ in flat] == [p for p, _ in flat_cast]
    named = 0
    for (path, x), (_, y) in zip(flat, flat_cast):
        if _named(path):
            named += 1
            assert y.dtype == jnp.bfloat16, path
            np.testing.assert_array_equal(np.asarray(y),
                                          np.asarray(x.astype(jnp.bfloat16)))
        else:
            assert y is x and y.dtype == x.dtype, path
    assert named > 0
    total = sum(y.nbytes for _, y in flat_cast)
    assert stats["cast_bytes"] + stats["kept_bytes"] == total
    assert stats["cast_bytes"] == sum(y.nbytes for p, y in flat_cast
                                      if _named(p))

    batch = _batch(cfg, 2, 12)
    prefill = jax.jit(lambda p, b: model.prefill(p, b, s_max=16))
    decode = jax.jit(model.decode_step)
    # the caches too: a recurrent state is kept in f32, where a change
    # that bf16 logits round away still shows
    want = prefill(params, batch)
    got = prefill(cast, batch)
    jax.tree.map(np.testing.assert_array_equal, got, want)
    tok = jnp.argmax(want[0], -1).astype(jnp.int32)
    for step in range(2):
        pos = jnp.asarray(12 + step, jnp.int32)
        want = decode(params, want[1], tok, pos)
        got = decode(cast, got[1], tok, pos)
        jax.tree.map(np.testing.assert_array_equal, got, want)
        tok = jnp.argmax(want[0], -1).astype(jnp.int32)


def test_cast_is_the_identity_at_the_params_own_dtype():
    model = build(base.reduced(registry.get("phi3-medium-14b")),
                  compute_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    cast, stats = model.compute_params(params)
    for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(cast)):
        assert y is x
    assert stats == {"cast_bytes": 0,
                     "kept_bytes": sum(x.nbytes
                                       for x in jax.tree.leaves(params))}


def test_engine_serves_and_recovers_from_the_cast_tree(tmp_path,
                                                       monkeypatch):
    """A bf16 engine built from f32 weights holds the cast tree, keeps it
    through crash() and recover(), and serves the tokens of an engine
    that never crashed."""
    model = build(base.reduced(registry.get("llama3.2-3b")),
                  compute_dtype=jnp.bfloat16)
    params = model.init_params(jax.random.PRNGKey(0))
    casts = []
    orig = type(model).compute_params

    def counted(self, p):
        casts.append(1)
        return orig(self, p)

    monkeypatch.setattr(type(model), "compute_params", counted)
    ec = EngineConfig(max_batch=2, s_max=24, max_requests=16)

    def fresh(name):
        eng = ServingEngine(model, params, ec,
                            arena_path=str(tmp_path / name))
        eng.add_request(101, np.array([1, 2, 3, 4], np.int64))
        eng.add_request(202, np.array([9, 8, 7], np.int64))
        return eng

    twin = fresh("twin")
    for _ in range(6):
        twin.step()
    ref = [twin.step() for _ in range(3)]

    eng = fresh("arena")
    held = eng.params
    assert len(casts) == 2
    for path, x in jax.tree_util.tree_leaves_with_path(held):
        assert x.dtype == (jnp.bfloat16 if _named(path) else jnp.float32)
    for _ in range(6):
        eng.step()
    eng.crash()
    assert eng.params is held
    eng.recover()
    assert eng.params is held and len(casts) == 2
    assert [eng.step() for _ in range(3)] == ref
