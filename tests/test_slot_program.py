"""The engine's one program a slot (``ServingEngine._decode_slot``)
against the three dispatches it replaced, written out here as the
reference: an eager per-leaf extract of the slot's rows, a jitted B=1
``decode_step``, an eager per-leaf re-seat.  On the CPU in float32 the
logits and every cache leaf are equal bit for bit, other slots' rows are
untouched, and one compile serves every slot and position."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base, registry
from repro.models.model import build
from repro.serve.engine import EngineConfig, ServingEngine, _map_slot

ARCHS = ["llama3.2-3b", "hymba-1.5b"]
MAX_BATCH = 4
# prompt lengths a slot: hymba's reduced window is 8 with 4 meta tokens,
# so the longer prompts wrap its rings
PROMPTS = (3, 14, 7, 21)


@pytest.fixture(scope="module", params=ARCHS)
def engine(request, tmp_path_factory):
    """One engine an architecture, shared by the module's tests: each
    reads the state it starts from, so their order does not matter."""
    model = build(base.reduced(registry.get(request.param)),
                  compute_dtype=jnp.float32)
    eng = ServingEngine(model, model.init_params(jax.random.PRNGKey(0)),
                        EngineConfig(max_batch=MAX_BATCH, s_max=32,
                                     max_requests=16),
                        arena_path=str(tmp_path_factory.mktemp("a") / "a"))
    rng = np.random.default_rng(0)
    for rid, n in enumerate(PROMPTS):
        eng.add_request(rid + 1, rng.integers(1, model.cfg.vocab, n))
    return eng


def _three_dispatches(model, params, cache, slot, token, p):
    """The old path: extract, decode at B=1, re-seat, each on its own."""
    one = _map_slot(cache, cache,
                    lambda full, _, ax: jax.lax.dynamic_slice_in_dim(
                        full, slot, 1, axis=ax))
    logits, one = _decode(model)(params, one, jnp.asarray([token], jnp.int32),
                                 jnp.asarray(p, jnp.int32))
    cache = _map_slot(cache, one,
                      lambda full, o, ax: jax.lax.dynamic_update_slice_in_dim(
                          full, o.astype(full.dtype), slot, axis=ax))
    return logits[0], cache


@functools.lru_cache(maxsize=None)
def _decode(model):
    return jax.jit(model.decode_step)


def _other_rows(cache, slot):
    """Every leaf with ``slot``'s rows taken out along its batch axis."""
    keep = np.asarray([s for s in range(MAX_BATCH) if s != slot])
    return _map_slot(cache, cache,
                     lambda full, _, ax: np.take(np.asarray(full), keep, ax))


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("slot", [0, 1, MAX_BATCH - 1])
def test_slot_program_equals_extract_decode_reseat(engine, slot):
    model = engine.model
    # two decodes, the second at the next position on the first's cache
    for k in range(2):
        p = int(engine.pos[slot]) - 1 + k
        token = 5 + 3 * slot + k
        before = engine.cache
        want_logits, want_cache = _three_dispatches(
            model, engine.params, before, slot, token, p)
        logits = engine._decode_slot(slot, token, p)
        assert logits.shape == (model.cfg.vocab,)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(want_logits))
        _assert_trees_equal(engine.cache, want_cache)
        _assert_trees_equal(_other_rows(engine.cache, slot),
                            _other_rows(before, slot))


def test_one_compile_serves_every_slot_and_position(engine):
    for _ in range(2):
        out = engine.step()
        assert sorted(out) == list(range(1, MAX_BATCH + 1))
    assert len(set(int(p) for p in engine.pos)) == MAX_BATCH
    assert engine._slot_decode._cache_size() == 1
