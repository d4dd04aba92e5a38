"""Concurrent staged recovery + crash-point fuzzing (DESIGN.md §6,
"Concurrent recovery & admission").

Four invariant families:

* crash-point fuzzing: a mixed DLL/B+Tree/Hashmap arena is crashed at
  EVERY epoch boundary (power-loss and torn data/metadata flavors) and
  recovered with both serial and concurrent managers — the last
  committed generation must survive either way;
* double failure: recovery itself is interrupted (a second crash fires
  right after the k-th stage completes, for every k, while sibling
  stages may still be running in pool threads) and then recovery runs
  again — reconstructors are pure and recovery writes nothing
  persistent, so recover-crash-recover must land on the committed
  state bit-exactly;
* determinism: recover(concurrency=4) and recover(concurrency=1)
  produce bit-identical arenas + volatile redundancy and equivalent
  RecoveryReports (modulo timing fields);
* early admission: the serving engine's slot-readiness bitmap admits
  each prefill group as it lands (decode serves ready slots while
  other slots are still recovering), and ckpt background warmup takes
  APPROXIMABLE re-warming off the restore critical path without
  changing the restored state.
"""
import threading

import numpy as np
import pytest

from repro.core.arena import open_arena
from repro.core.recovery import RecoveryManager
from repro.pstruct.bptree import BPTree
from repro.pstruct.dll import DoublyLinkedList
from repro.pstruct.hashmap import Hashmap

MODES = ("partly", "full")
# every invariant here is independent of the shard count (DESIGN.md §7),
# so the arena tests run on one shard and on four
SHARDS = [1, 4]


# ---------------------------------------------------------------- helpers


def _mixed_arena(mode, n_shards):
    layout = {}
    layout.update(DoublyLinkedList.layout(256, mode, name="dll"))
    layout.update(BPTree.layout(256, 1024, mode, name="bt"))
    layout.update(Hashmap.layout(512, mode, name="hm"))
    a = open_arena(None, layout, n_shards=n_shards)
    return (a, DoublyLinkedList(a, 256, mode, name="dll"),
            BPTree(a, 256, 1024, mode, name="bt"),
            Hashmap(a, 512, mode, name="hm"))


def _pmem_image(a) -> np.ndarray:
    """Every persistent byte of the arena, shard files concatenated."""
    if hasattr(a, "shards"):
        return np.concatenate([np.asarray(sh._mm) for sh in a.shards]
                              + [np.asarray(a._man)])
    return np.asarray(a._mm).copy()


def _script(n_ops, seed=0):
    """Mixed append/insert workload over fresh keys (torn-epoch-safe —
    nothing rewrites committed persistent rows destructively except the
    B+Tree, whose documented asymmetry the sweep accounts for)."""
    rng = np.random.default_rng(seed)
    ops, key = [], 0
    for i in range(n_ops):
        m = int(rng.integers(2, 7))
        vals = rng.integers(0, 1 << 30, (m, 7)).astype(np.int64)
        keys = np.arange(key, key + m, dtype=np.int64)
        key += m
        ops.append(("dll" if i % 3 == 0 else ("bt" if i % 3 == 1 else "hm"),
                    keys, vals))
    return ops


def _apply(d, t, h, op):
    kind, keys, vals = op
    if kind == "dll":
        d.append_batch(vals)
    elif kind == "bt":
        t.insert_batch(keys, vals)
    else:
        h.insert_batch(keys, vals)


def _manager(a, d, t, h):
    mgr = RecoveryManager(a)
    mgr.add("dll", "pstruct.dll", d)
    mgr.add("bt", "pstruct.bptree", t)
    mgr.add("hm", "pstruct.hashmap", h)
    return mgr


def _fingerprint(a, d, t, h):
    """Everything recovery is supposed to rebuild, bit-exactly: region
    volatile copies + every piece of volatile redundancy."""
    fp = {f"region:{name}": r.vol.copy() for name, r in a.regions.items()}
    fp["dll.prev"] = d.prev.copy()
    fp["dll.free"] = np.sort(np.asarray(d._free, np.int64))
    fp["dll.order"] = d.order().copy()
    fp["hm.n_buckets"] = h.n_buckets
    fp["hm.buckets"] = h.buckets.copy()
    fp["hm.chain"] = h.chain.copy()
    fp["hm.hashes"] = h.hashes.copy()
    fp["bt.leaf_prev"] = t.leaf_prev.copy()
    fp["bt.free_nodes"] = np.sort(np.asarray(t._free_nodes, np.int64))
    fp["bt.free_recs"] = np.sort(np.asarray(t._free_recs, np.int64))
    return fp


def _assert_fp_equal(got, want):
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def _strip_timing(report):
    """Report equivalence view: everything but the timing fields."""
    out = []
    for st in report.stages:
        detail = {k: v for k, v in st.detail.items()
                  if not k.endswith("_s") and k not in ("seconds",)}
        out.append((st.name, detail))
    return {"valid": report.valid, "generation": report.generation,
            "stages": out}


# ----------------------------------------------- boundary-sweep fuzzing


@pytest.mark.parametrize("n_shards", SHARDS)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("torn", [False, True])
@pytest.mark.parametrize("concurrency", [1, 4])
def test_crash_fuzz_every_boundary(mode, torn, concurrency, n_shards):
    """For every epoch boundary b, crash inside op b+1 (power loss
    mid-epoch, or torn: data half flushed but not metadata), recover
    with the given concurrency, and require the committed generation's
    fingerprint for the count-bounded structures (B+Tree rows follow
    the documented in-place asymmetry, asserted via find_batch)."""
    ops = _script(8, seed=3)
    n = len(ops)
    for boundary in range(n):
        a, d, t, h = _mixed_arena(mode, n_shards)
        bt_keys = []
        for i in range(boundary + 1):
            _apply(d, t, h, ops[i])
            if ops[i][0] == "bt":
                bt_keys.extend(ops[i][1].tolist())
            a.commit()
        dll_order = d.to_list().copy()
        dll_data = d.data[dll_order].copy()
        hm_size = h.size
        bt_vals = t.find_batch(np.asarray(bt_keys, np.int64))[1].copy() \
            if bt_keys else None
        gen0 = a.generation
        if boundary + 1 < n:
            with a.epoch():
                _apply(d, t, h, ops[boundary + 1])
                if torn:
                    a.writeset.flush(include_meta=False)
                a.crash()
        else:
            a.crash()
        report = _manager(a, d, t, h).recover(concurrency=concurrency)
        assert report.valid and report.generation == gen0
        np.testing.assert_array_equal(d.to_list(), dll_order)
        np.testing.assert_array_equal(d.data[dll_order], dll_data)
        assert h.size == hm_size
        if bt_keys:
            ok, got = t.find_batch(np.asarray(bt_keys, np.int64))
            assert ok.all()
            np.testing.assert_array_equal(got, bt_vals)


# --------------------------------------------- double-failure fuzzing


@pytest.mark.parametrize("n_shards", SHARDS)
@pytest.mark.parametrize("torn", [False, True])
@pytest.mark.parametrize("concurrency", [1, 4])
@pytest.mark.parametrize("crash_after_stage", [0, 1, 2, 3])
def test_double_failure_mid_stage(torn, concurrency, crash_after_stage,
                                  n_shards):
    """Recovery is itself crashed: a listener injects arena.crash() the
    moment the k-th stage report lands (stage 0 = reopen) — under
    concurrency>1 sibling stages of the same level are mid-flight in
    other threads when the rug is pulled.  The interrupted pass may
    raise or produce garbage volatile state; it must never touch
    persistent bytes, so a second, uninterrupted recovery lands on the
    committed fingerprint."""
    a, d, t, h = _mixed_arena("partly", n_shards)
    for op in _script(6, seed=11):
        _apply(d, t, h, op)
        a.commit()
    # the first failure: crash mid-op (optionally torn)
    with a.epoch():
        _apply(d, t, h, _script(1, seed=99)[0])
        if torn:
            a.writeset.flush(include_meta=False)
        a.crash()
    # reference: what one uninterrupted recovery of this image rebuilds
    pmem0 = _pmem_image(a)
    _manager(a, d, t, h).recover()
    np.testing.assert_array_equal(_pmem_image(a), pmem0)   # recovery persists nothing
    want = _fingerprint(a, d, t, h)

    # the fuzzed run: recover again, crashing mid-recovery after stage k
    a.crash()
    seen = []

    def bomb(st):
        seen.append(st.name)
        if len(seen) == crash_after_stage + 1:
            a.crash()

    try:
        _manager(a, d, t, h).recover(concurrency=concurrency,
                                     on_stage=bomb)
    except Exception:
        pass          # garbage volatile state may fail loudly — allowed
    np.testing.assert_array_equal(_pmem_image(a), pmem0)   # still nothing persisted
    report = _manager(a, d, t, h).recover(concurrency=concurrency)
    assert report.valid
    _assert_fp_equal(_fingerprint(a, d, t, h), want)
    np.testing.assert_array_equal(_pmem_image(a), pmem0)


# ------------------------------------------------- report truthfulness


@pytest.mark.parametrize("n_shards", SHARDS)
def test_report_valid_true_only_after_commit(rng, n_shards):
    a, d, t, h = _mixed_arena("partly", n_shards)
    d.append_batch(rng.integers(0, 9, (4, 7)))
    a.crash()                                  # commit() never ran
    rep = _manager(a, d, t, h).recover(concurrency=4)
    assert not rep.valid
    d.append_batch(rng.integers(0, 9, (4, 7)))
    a.commit()
    a.crash()
    rep = _manager(a, d, t, h).recover(concurrency=4)
    assert rep.valid and rep.generation == 1


@pytest.mark.parametrize("n_shards", SHARDS)
def test_report_valid_false_after_invalidate(rng, n_shards):
    a, d, t, h = _mixed_arena("partly", n_shards)
    d.append_batch(rng.integers(0, 9, (4, 7)))
    a.commit()
    a.invalidate()
    a.crash()
    rep = _manager(a, d, t, h).recover()
    assert not rep.valid


# ------------------------------------------------------- determinism


@pytest.mark.parametrize("n_shards", SHARDS)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", [0, 7])
def test_concurrent_recovery_bit_identical_to_serial(mode, seed, n_shards):
    """recover(concurrency=4) == recover(concurrency=1): bit-identical
    arenas + volatile redundancy, equivalent reports modulo timing."""
    a, d, t, h = _mixed_arena(mode, n_shards)
    for op in _script(9, seed=seed):
        _apply(d, t, h, op)
        a.commit()
    a.crash()
    rep1 = _manager(a, d, t, h).recover(concurrency=1)
    fp1 = _fingerprint(a, d, t, h)
    a.crash()
    rep4 = _manager(a, d, t, h).recover(concurrency=4)
    fp4 = _fingerprint(a, d, t, h)
    _assert_fp_equal(fp4, fp1)
    assert _strip_timing(rep4) == _strip_timing(rep1)
    assert rep4.concurrency == 4 and rep1.concurrency == 1


# -------------------------------------------------- callbacks + timing


@pytest.mark.parametrize("n_shards", SHARDS)
def test_stage_callbacks_fire_once_per_stage_any_thread(rng, n_shards):
    a, d, t, h = _mixed_arena("partly", n_shards)
    for op in _script(5, seed=2):
        _apply(d, t, h, op)
        a.commit()
    a.crash()
    mgr = _manager(a, d, t, h)
    from_listener, from_on_stage = [], []
    mgr.add_listener(lambda st: from_listener.append(st.name))
    rep = mgr.recover(concurrency=4,
                      on_stage=lambda st: from_on_stage.append(st.name))
    # every stage (incl. reopen) lands exactly once in each callback;
    # completion order is the pool's business, the SET is the contract
    assert sorted(from_listener) == sorted(s.name for s in rep.stages)
    assert sorted(from_on_stage) == sorted(from_listener)
    # the report itself stays in deterministic level-major order
    assert [s.name for s in rep.stages] == ["reopen", "dll", "bt", "hm"]


@pytest.mark.parametrize("n_shards", SHARDS)
def test_report_carries_wall_critical_path_and_sum(rng, n_shards):
    a, d, t, h = _mixed_arena("partly", n_shards)
    for op in _script(5, seed=4):
        _apply(d, t, h, op)
        a.commit()
    a.crash()
    rep = _manager(a, d, t, h).recover(concurrency=2)
    # three stages on one level: critical path = reopen + slowest stage
    assert rep.critical_path_ms <= rep.total_ms + 1e-6
    assert rep.critical_path_ms <= rep.wall_ms + 0.5  # measurement slack
    assert rep.wall_ms > 0 and rep.total_ms > 0
    d_dict = rep.as_dict()
    for key in ("wall_ms", "critical_path_ms", "total_ms", "concurrency"):
        assert key in d_dict
    for st in rep.stages:
        assert st.t_end >= st.t_start >= 0.0


def test_critical_path_follows_dependency_chain():
    """A linear dependency chain's critical path is the full stage sum;
    adding an independent stage leaves the chain's path dominant."""
    from repro.core import reconstruct

    if "test.sleepy" not in reconstruct.names():
        @reconstruct.register("test.sleepy")
        def _sleepy(secs):
            import time as _t
            _t.sleep(secs)
            return {}

    mgr = RecoveryManager()
    mgr.add("a", "test.sleepy", 0.02)
    mgr.add("b", "test.sleepy", 0.02, depends=("a",))
    mgr.add("lone", "test.sleepy", 0.001)
    rep = mgr.recover(reopen=False, concurrency=4)
    assert rep.critical_path_seconds >= 0.04 - 1e-3
    assert rep.critical_path_seconds <= rep.total_seconds + 1e-3
    assert [lvl for lvl in mgr.levels()] == [["a", "lone"], ["b"]]


def test_concurrent_scheduler_runs_each_stage_exactly_once():
    """Regression: a stage future can complete before its done-callback
    attaches, running finished() INLINE in the submitting thread — mid
    initial-submission-loop that can drop a LATER stage's dependency
    counter to zero and submit it early, and the loop's own
    remaining==0 check then submitted it AGAIN.  The duplicate
    completion double-decremented its dependents' counters, so a stage
    could start before a sibling dependency finished (observed as the
    engine stage racing the journal replay).  Instant stages maximize
    the inline-callback window; every stage must run exactly once and
    only after its declared dependencies."""
    from repro.core import reconstruct

    if "test.counted" not in reconstruct.names():
        @reconstruct.register("test.counted")
        def _counted(state):
            key, deps, runs, done, lock = state
            with lock:
                missing = [d for d in deps if d not in done]
                assert not missing, f"{key} ran before {missing}"
                runs[key] = runs.get(key, 0) + 1
                done.add(key)
            return {}

    for _ in range(60):
        runs: dict = {}
        done: set = set()
        lock = threading.Lock()

        def st(key, *deps):
            return (key, deps, runs, done, lock)

        mgr = RecoveryManager()
        mgr.add("a", "test.counted", st("a"))
        mgr.add("b", "test.counted", st("b", "a"), depends=("a",))
        mgr.add("c", "test.counted", st("c"))
        mgr.add("d", "test.counted", st("d", "b", "c"),
                depends=("b", "c"))
        mgr.recover(reopen=False, concurrency=2)
        assert runs == {"a": 1, "b": 1, "c": 1, "d": 1}


# ------------------------------------------- engine early admission


@pytest.mark.parametrize("concurrency", [1, 4])
def test_engine_admits_slots_per_prefill_group(tmp_path, concurrency):
    import jax
    import jax.numpy as jnp

    from repro.configs import base, registry
    from repro.models.model import build
    from repro.serve.engine import EngineConfig, ServingEngine

    model = build(base.reduced(registry.get("llama3.2-3b")),
                  compute_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params,
                        EngineConfig(max_batch=3, s_max=16,
                                     max_requests=16),
                        arena_path=str(tmp_path / "a"))
    eng.add_request(7, np.array([1, 2, 3], np.int64))       # plen 3
    eng.add_request(8, np.array([4, 5, 6, 9, 2], np.int64))  # plen 5
    eng.step()
    eng.crash()
    assert not eng.slot_ready.any()
    events = []
    lock = threading.Lock()

    def on_ready(slots, tlen, admitted_s):
        with lock:
            events.append((sorted(int(s) for s in slots), tlen,
                           eng.slot_ready.copy()))

    eng.on_slot_ready = on_ready
    eng.recover(concurrency=concurrency)
    eng.on_slot_ready = None
    # two distinct prompt lengths -> two admission events
    assert len(events) == 2
    assert {e[1] for e in events} == {4, 6}    # tlen = plen + 1 step
    for slots, _tlen, bitmap in events:
        assert bitmap[slots].all()             # admitted when it fired
    # the unoccupied slot was admitted by the scan, before any prefill
    assert all(e[2][2] for e in events)
    assert eng.slot_ready.all()
    rep = eng.last_recovery
    det = rep.stage("engine").detail
    assert det["prefill_groups"] == 2
    assert 0 < det["first_admission_s"] <= det["last_admission_s"]


def test_engine_step_and_seating_respect_readiness(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.configs import base, registry
    from repro.models.model import build
    from repro.serve.engine import EngineConfig, ServingEngine

    model = build(base.reduced(registry.get("llama3.2-3b")),
                  compute_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params,
                        EngineConfig(max_batch=2, s_max=16,
                                     max_requests=16),
                        arena_path=str(tmp_path / "a"))
    eng.add_request(7, np.array([1, 2, 3], np.int64))
    eng.add_request(8, np.array([4, 5, 6, 9], np.int64))
    eng.step()
    eng.crash()
    stepped = []

    def on_ready(slots, tlen, admitted_s):
        if not stepped:
            # mid-recovery: only the admitted group's slot decodes; the
            # other active slot is skipped, and with every slot busy a
            # new request cannot be seated yet
            out = eng.step()
            stepped.append((sorted(out), eng.slot_ready.copy()))
            with pytest.raises(RuntimeError, match="no free slots"):
                eng.add_request(99, np.array([1], np.int64))

    eng.on_slot_ready = on_ready
    eng.recover()               # serial: callbacks run between groups
    eng.on_slot_ready = None
    assert len(stepped) == 1
    first_rids, bitmap = stepped[0]
    assert len(first_rids) == 1 and int(bitmap.sum()) == 1
    # fully recovered: both slots serve again
    out = eng.step()
    assert sorted(out) == [7, 8]


# --------------------------------------------- ckpt background warmup


def _tiny_train_state():
    import jax
    import jax.numpy as jnp

    from repro.train.state import new_state

    k = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(k, (32, 16)), "b": jnp.zeros((16,))}
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    return new_state(params, mu, nu, seed=7)


def test_ckpt_background_warmup_matches_inline(tmp_path):
    import jax

    from repro.ckpt.manager import CheckpointManager
    from repro.core import policy as pol

    st = _tiny_train_state()
    spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st)
    mgr = CheckpointManager(str(tmp_path), pol.PARTLY_DROP)
    mgr.save(st)
    inline = mgr.restore(spec)
    bg = mgr.restore(spec, warmup="background")
    bg = mgr.finish_warmup(bg)
    for a, b in zip(jax.tree.leaves(inline), jax.tree.leaves(bg)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ckpt_background_warmup_reports_stage(tmp_path):
    import jax

    from repro.ckpt.manager import CheckpointManager
    from repro.core import policy as pol

    st = _tiny_train_state()
    spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st)
    mgr = CheckpointManager(str(tmp_path), pol.PARTLY_DROP)
    mgr.save(st)
    got = mgr.restore(spec, warmup="background")
    mgr.wait_warmup()
    rep = mgr.last_recovery
    warm = rep.stage("warmup_approximable")
    assert warm is not None and warm.detail["background"]
    assert warm.detail["leaves"] == 4          # mu/nu x {w, b}
    assert warm.seconds >= 0
    # the placeholder state is already usable (host zeros for moments)
    assert float(np.sum(np.abs(np.asarray(got.mu["w"])))) == 0.0
    mgr.finish_warmup(got)


def test_ckpt_unclaimed_warmup_refuses_next_restore(tmp_path):
    """Splicing restore B's warm leaves into restore A's state would be
    silent corruption — the manager refuses the second restore until
    the first warmup is claimed."""
    import jax

    from repro.ckpt.manager import CheckpointManager
    from repro.core import policy as pol

    st = _tiny_train_state()
    spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st)
    mgr = CheckpointManager(str(tmp_path), pol.PARTLY_DROP)
    mgr.save(st)
    got = mgr.restore(spec, warmup="background")
    with pytest.raises(RuntimeError, match="unclaimed background warmup"):
        mgr.restore(spec)
    got = mgr.finish_warmup(got)          # claim it
    mgr.restore(spec)                     # now fine
    assert got.step is not None


def test_ckpt_warmup_thread_failure_surfaces(tmp_path, monkeypatch):
    """A failure inside the warmup thread must re-raise at the join
    point, not die silently in a daemon thread."""
    import jax

    from repro.ckpt import manager as M
    from repro.core import policy as pol

    st = _tiny_train_state()
    spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st)
    mgr = M.CheckpointManager(str(tmp_path), pol.PARTLY_DROP)
    mgr.save(st)

    real = M.jnp.asarray

    def boom(x, *a, **k):
        # fail only in the warmup worker — restore's main-thread
        # device placement stays real
        if threading.current_thread() is not threading.main_thread():
            raise ValueError("synthetic warmup failure")
        return real(x, *a, **k)

    monkeypatch.setattr(M.jnp, "asarray", boom)
    got = mgr.restore(spec, warmup="background")
    with pytest.raises(ValueError, match="synthetic warmup"):
        mgr.finish_warmup(got)
    monkeypatch.undo()
    # the error is consumed; the manager is reusable afterwards
    mgr.restore(spec)
    assert got.step is not None


# ------------------- duplicate-admission oracle (DESIGN.md §11)
#
# The request journal's exactly-once contract, fuzzed the same way the
# structures are: crash at EVERY epoch boundary (power-loss and torn),
# recover, then replay the ENTIRE workload through the journal's
# duplicate check — completed requests must be refused, interrupted
# ones must retry, and the final effect-set must equal a twin run that
# never crashed.  Swept over one shard and four.

from repro.serve.feature_store import FeatureConfig, FeatureStore  # noqa: E402
from repro.serve.journal import (ST_DONE, ST_NEVER,  # noqa: E402
                                 ST_RETRY, DuplicateRequestError)


def _fs_cfg(n_shards):
    return FeatureConfig(n_keys=64, dim=3, n_samples=512,
                         n_shards=n_shards, journal=True)


def _fs_script(n_ops, seed=0):
    rng = np.random.default_rng(seed)
    ops = []
    for rid in range(n_ops):
        m = int(rng.integers(1, 6))
        keys = rng.choice(64, size=m, replace=False).astype(np.int64)
        deltas = rng.integers(-9, 10, (m, 3)).astype(np.int64)
        ops.append((rid, keys, deltas))
    return ops


def _fs_effects(fs):
    return {"vectors": fs.lookup(np.arange(fs.cfg.n_keys)).copy(),
            "counts": fs.counts.copy(),
            "next_sample": fs.next_sample,
            "classify": dict(fs.journal.classify())}


def _fs_assert_effects(fs, want):
    got = _fs_effects(fs)
    assert got["classify"] == want["classify"]
    assert got["next_sample"] == want["next_sample"]
    np.testing.assert_array_equal(got["counts"], want["counts"])
    np.testing.assert_array_equal(got["vectors"], want["vectors"])


def _fs_twin(n_shards, ops):
    """Uninterrupted twin run: the expected effect-set, plus the journal
    overhead bound (<= 1 extra flushed line per epoch)."""
    fs = FeatureStore(_fs_cfg(n_shards))
    s0 = fs.arena.stats.snapshot()
    for op in ops:
        assert fs.apply(*op)
    d = fs.arena.stats.delta(s0)
    assert 0 < d.journal_lines <= d.epochs
    return _fs_effects(fs)


@pytest.mark.parametrize("n_shards", SHARDS)
@pytest.mark.parametrize("torn", [False, True])
def test_journal_exactly_once_every_boundary(n_shards, torn):
    ops = _fs_script(6, seed=13)
    want = _fs_twin(n_shards, ops)
    last = len(ops) if not torn else len(ops) - 1
    for boundary in range(last + 1):
        fs = FeatureStore(_fs_cfg(n_shards))
        for op in ops[:boundary]:
            assert fs.apply(*op)
        if torn and boundary < len(ops):
            # crash inside op `boundary`: data phase durable, commit not
            assert fs.apply(*ops[boundary], _torn_crash=True) is False
        else:
            fs.crash()                       # power loss between epochs
        rep = fs.recover(concurrency=2)
        # a report is valid only once a generation has committed; at
        # boundary 0 the image is legitimately pre-first-commit
        assert rep.valid == (boundary > 0)
        # classification: exactly the committed prefix is completed; the
        # crashed op left no committed trace
        assert fs.journal.classify() == \
            {rid: ST_DONE for rid, _, _ in ops[:boundary]}
        if boundary < len(ops):
            assert fs.journal.state_of(ops[boundary][0]) == ST_NEVER
        # the oracle: replay the WHOLE workload; completed requests are
        # refused, the rest apply exactly once
        for i, op in enumerate(ops):
            assert fs.apply(*op) == (i >= boundary), (boundary, i)
        _fs_assert_effects(fs, want)


@pytest.mark.parametrize("n_shards", SHARDS)
@pytest.mark.parametrize("crash_after_stage", [0, 1, 2, 3, 4])
def test_journal_oracle_survives_double_failure(n_shards,
                                                crash_after_stage):
    """Crash the journal's own recovery after every stage (reopen, emb,
    samples, journal, store — possibly while siblings run in pool
    threads), recover again, and the replay oracle must still land on
    the twin effect-set with zero duplicate admissions."""
    ops = _fs_script(5, seed=21)
    want = _fs_twin(n_shards, ops)
    fs = FeatureStore(_fs_cfg(n_shards))
    for op in ops[:3]:
        assert fs.apply(*op)
    assert fs.apply(*ops[3], _torn_crash=True) is False
    seen = []

    def bomb(st):
        seen.append(st.name)
        if len(seen) == crash_after_stage + 1:
            fs.arena.crash()

    try:
        fs.recover(concurrency=2, on_stage=bomb)
    except Exception:
        pass      # garbage volatile state may fail loudly — allowed
    rep = fs.recover(concurrency=2)
    assert rep.valid
    for i, op in enumerate(ops):
        assert fs.apply(*op) == (i >= 3)
    _fs_assert_effects(fs, want)


def test_engine_journal_refuses_duplicate_admission(tmp_path):
    """Engine-level exactly-once: after crash+recover the journal
    classifies a finished request completed and an in-flight one
    must-retry; re-admitting EITHER raises, the freed slot seats a
    fresh rid, and decode resumes."""
    import jax
    import jax.numpy as jnp

    from repro.configs import base, registry
    from repro.models.model import build
    from repro.serve.engine import EngineConfig, ServingEngine

    model = build(base.reduced(registry.get("llama3.2-3b")),
                  compute_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params,
                        EngineConfig(max_batch=2, s_max=16,
                                     max_requests=16, journal=True),
                        arena_path=str(tmp_path / "a"))
    eng.add_request(7, np.array([1, 2, 3, 4], np.int64))
    eng.add_request(8, np.array([5, 6], np.int64))
    for _ in range(2):
        eng.step()
    assert eng.finish_request(7) == 6          # 4 prompt + 2 decoded
    with pytest.raises(KeyError):
        eng.finish_request(7)                  # already finished
    eng.crash()
    eng.recover(concurrency=2)
    rep = eng.last_recovery
    assert rep.valid
    assert rep.stage("journal").detail["must_retry"] == 1
    assert eng.journal.state_of(7) == ST_DONE
    assert eng.journal.state_of(8) == ST_RETRY
    for rid in (7, 8):
        with pytest.raises(DuplicateRequestError):
            eng.add_request(rid, np.array([9], np.int64))
    eng.add_request(9, np.array([9, 9], np.int64))  # freed slot reused
    out = eng.step()
    assert sorted(out) == [8, 9]
