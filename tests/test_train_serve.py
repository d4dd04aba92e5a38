"""Integration: trainer crash/resume bit-consistency; serving engine
crash/recover determinism; paged allocator; data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base, registry
from repro.core import policy as pol
from repro.data.pipeline import Pipeline
from repro.models.model import build
from repro.optim.adamw import AdamWConfig
from repro.serve.engine import EngineConfig, ServingEngine
from repro.serve.kvcache import PagedAllocator, PagedConfig
from repro.train.trainer import Trainer, TrainerConfig


@pytest.fixture(scope="module")
def small_model():
    cfg = base.reduced(registry.get("llama3.2-3b"))
    return build(cfg, compute_dtype=jnp.float32)


def test_trainer_crash_resume_bit_consistent(tmp_path, small_model):
    tc = TrainerConfig(steps=8, ckpt_every=4, ckpt_dir=str(tmp_path / "a"),
                       policy=pol.PARTLY_PERSISTENT, global_batch=4,
                       seq_len=32, async_ckpt=False)
    tr = Trainer(small_model, AdamWConfig(), tc)
    tr.init()
    tr.run(6)
    tr.crash()
    step = tr.resume()
    assert step == 4
    tr.run(2)
    crash_losses = {m["step"]: m["loss"] for m in tr.metrics_log}

    tc2 = TrainerConfig(steps=8, ckpt_every=0, ckpt_dir=str(tmp_path / "b"),
                        policy=pol.PARTLY_PERSISTENT, global_batch=4,
                        seq_len=32)
    tr2 = Trainer(small_model, AdamWConfig(), tc2)
    tr2.init()
    tr2.run(6)
    ref = {m["step"]: m["loss"] for m in tr2.metrics_log}
    for s in (4, 5):
        assert abs(crash_losses[s] - ref[s]) < 1e-5, s


def test_trainer_drop_policy_resumes_with_divergence(tmp_path, small_model):
    """partly+drop restores params exactly but re-warms moments — the
    documented approximation; training continues finitely."""
    tc = TrainerConfig(steps=6, ckpt_every=3, ckpt_dir=str(tmp_path),
                       policy=pol.PARTLY_DROP, global_batch=4, seq_len=32,
                       async_ckpt=False)
    tr = Trainer(small_model, AdamWConfig(), tc)
    tr.init()
    tr.run(4)
    tr.crash()
    assert tr.resume() == 3
    assert float(jnp.sum(jnp.abs(jax.tree.leaves(tr.state.mu)[0]))) == 0.0
    tr.run(2)
    assert np.isfinite(tr.metrics_log[-1]["loss"])


def test_pipeline_determinism_and_cursor():
    cfg = registry.get("llama3.2-3b")
    p1 = Pipeline(cfg, 4, 16, seed=3)
    b_a = p1.batch_at(5)
    p2 = Pipeline(cfg, 4, 16, seed=3)
    p2.reconstruct_cursor(3, 5)
    b_b = p2.batch_at(5)
    np.testing.assert_array_equal(b_a["tokens"], b_b["tokens"])
    # tokens in range, labels shifted
    assert b_a["tokens"].max() < cfg.vocab
    b_c = Pipeline(cfg, 4, 16, seed=4).batch_at(5)
    assert (b_a["tokens"] != b_c["tokens"]).any()


def test_serving_crash_recover_determinism(tmp_path, small_model):
    """Tokens generated after crash+recover must equal the same steps of
    an uninterrupted twin run (greedy decode is deterministic)."""
    params = small_model.init_params(jax.random.PRNGKey(0))
    ec = EngineConfig(max_batch=2, s_max=24, max_requests=16)

    def fresh(name):
        eng = ServingEngine(small_model, params, ec,
                            arena_path=str(tmp_path / name))
        eng.add_request(101, np.array([1, 2, 3, 4], np.int64))
        eng.add_request(202, np.array([9, 8, 7], np.int64))
        return eng

    twin = fresh("twin")
    for _ in range(6):
        twin.step()
    ref = [twin.step() for _ in range(3)]

    eng = fresh("arena")
    for _ in range(6):
        eng.step()
    eng.crash()
    dt = eng.recover()
    assert dt >= 0
    got = [eng.step() for _ in range(3)]
    assert ref == got


@pytest.mark.parametrize("arch", ["llama3.2-3b", "hymba-1.5b"])
def test_serving_one_token_prompt_recovers(tmp_path, arch):
    """A one-token log has an empty prefix to prefill: admission and
    recovery seat the zero state, and the recovered logits equal an
    uninterrupted twin's (KV and recurrent state alike)."""
    model = build(base.reduced(registry.get(arch)), compute_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(1))
    ec = EngineConfig(max_batch=2, s_max=16, max_requests=8)

    def fresh(name):
        eng = ServingEngine(model, params, ec,
                            arena_path=str(tmp_path / name))
        eng.add_request(7, np.array([5], np.int64))
        return eng

    twin = fresh("twin")
    eng = fresh("arena")
    eng.crash()                                  # before any step
    eng.recover()
    for _ in range(2):
        assert eng.step() == twin.step()
        np.testing.assert_allclose(np.asarray(eng.last_logits[7]),
                                   np.asarray(twin.last_logits[7]),
                                   rtol=1e-5, atol=1e-5)
    eng.crash()                                  # log of 3 tokens now
    eng.recover()
    assert eng.step() == twin.step()
    np.testing.assert_allclose(np.asarray(eng.last_logits[7]),
                               np.asarray(twin.last_logits[7]),
                               rtol=1e-5, atol=1e-5)


def test_paged_allocator_lru_and_recover(tmp_path):
    pa = PagedAllocator(PagedConfig(n_pages=16, page_tokens=4),
                        path=str(tmp_path / "pg"))
    pa.alloc(1, 6)
    pa.alloc(2, 6)
    assert len(pa.pages_free) == 4
    # exhaustion triggers LRU eviction of request 1's oldest pages
    pa.alloc(3, 8)
    assert (pa.owner == 3).sum() == 8
    owner_before = pa.owner.copy()
    free_before = sorted(pa.pages_free)
    pa.arena.commit()
    pa.arena.crash()
    sec = pa.recover()
    assert sec >= 0
    np.testing.assert_array_equal(pa.owner, owner_before)
    assert sorted(pa.pages_free) == free_before
    pa.free_request(3)
    assert (pa.owner == 3).sum() == 0


def test_sample_index_recover(tmp_path):
    from repro.data.index import SampleIndex
    idx = SampleIndex(str(tmp_path / "idx"), 4096)
    ids = np.arange(1000, dtype=np.int64)
    idx.add(ids, ids % 7, ids * 64, np.full(1000, 64, np.int64))
    idx.arena.crash()
    sec = idx.recover()
    assert sec >= 0
    ok, shard, off, ln = idx.lookup(ids[::13])
    assert ok.all()
    np.testing.assert_array_equal(shard, (ids[::13]) % 7)
    np.testing.assert_array_equal(off, ids[::13] * 64)
