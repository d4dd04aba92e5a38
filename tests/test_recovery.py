"""Unified recovery subsystem tests (core/recovery.py, DESIGN.md §6).

* chain primitives: chain_order/chain_lengths/chain_walk vs scalar-walk
  oracles, stale-count bounding, cycle detection;
* RecoveryManager: dependency ordering, validity check, staged timing;
* torn-epoch recovery: a mixed DLL/B+Tree/Hashmap workload sharing one
  arena is crashed at EVERY epoch boundary (extends test_writeset.py's
  single-structure crash test to all structures via the manager) —
  power-loss mid-epoch must recover the last committed generation
  byte-exactly for every structure; a crash at the data/metadata barrier
  must recover it for the count-bounded structures (DLL, Hashmap) and a
  valid superset state for the in-place-rewriting B+Tree.
"""

import numpy as np
import pytest

from repro.core import reconstruct
from repro.core.arena import open_arena
from repro.core.recovery import (NULL, RecoveryManager, RecoveryReport,
                                 chain_lengths, chain_order, chain_walk)
from repro.pstruct.bptree import BPTree
from repro.pstruct.dll import DoublyLinkedList
from repro.pstruct.hashmap import Hashmap

MODES = ("partly", "full")


# ------------------------------------------------------- chain primitives


def _scalar_order(nxt, head, count):
    """The seed's sequential NEXT walk — oracle and bench baseline."""
    out = np.empty(count, np.int64)
    cur = head
    for i in range(count):
        out[i] = cur
        cur = int(nxt[cur])
    return out


def _random_chain(n, n_live, seed=0):
    rng = np.random.default_rng(seed)
    live = rng.permutation(n)[:n_live]
    nxt = np.full(n, NULL, np.int64)
    nxt[live[:-1]] = live[1:]
    return nxt, live


@pytest.mark.parametrize("n,n_live", [(16, 16), (300, 211), (4096, 1000)])
def test_chain_order_matches_scalar_walk(n, n_live):
    nxt, live = _random_chain(n, n_live, seed=n)
    head = int(live[0])
    want = _scalar_order(nxt, head, n_live)
    np.testing.assert_array_equal(chain_order(nxt, head, n_live), want)
    # count=None derives the length by pointer doubling
    np.testing.assert_array_equal(chain_order(nxt, head), want)
    np.testing.assert_array_equal(want, live)


def test_chain_order_stale_count_bounds_walk():
    """A committed count smaller than the volatile chain length walks only
    the committed prefix — the torn-epoch recovery guarantee."""
    nxt, live = _random_chain(64, 40, seed=9)
    got = chain_order(nxt, int(live[0]), 25)
    np.testing.assert_array_equal(got, live[:25])


def test_chain_lengths_multi_head():
    nxt, live = _random_chain(128, 70, seed=3)
    heads = np.array([live[0], live[10], live[69], NULL], np.int64)
    got = chain_lengths(nxt, heads)
    np.testing.assert_array_equal(got, [70, 60, 1, 0])


def test_chain_lengths_oob_head_is_empty_chain():
    """Heads outside [0, n) terminate like NULL — the module-wide OOB
    contract (a bucket head flushed past the fresh-water mark)."""
    nxt = np.full(8, NULL, np.int64)
    got = chain_lengths(nxt, np.array([0, 8, 100, NULL], np.int64))
    np.testing.assert_array_equal(got, [1, 0, 0, 0])


def test_chain_order_overlong_count_raises():
    """An explicit count past the chain end must fail loudly, not wrap
    NULL around as a numpy negative index."""
    nxt, live = _random_chain(32, 10, seed=4)
    with pytest.raises(ValueError, match="count exceeds"):
        chain_order(nxt, int(live[0]), 11)


def test_chain_lengths_detects_cycle():
    nxt = np.array([1, 2, 3, 1], np.int64)   # 1 -> 2 -> 3 -> 1
    with pytest.raises(RuntimeError, match="cycle"):
        chain_lengths(nxt, np.array([0]))


def test_chain_walk_materializes_all_chains():
    # two disjoint chains of different lengths + an empty head
    nxt = np.full(16, NULL, np.int64)
    nxt[[2, 5]] = [5, 7]              # 2 -> 5 -> 7
    nxt[3] = 9                        # 3 -> 9
    members = chain_walk(nxt, np.array([2, 3, NULL], np.int64))
    assert members.shape == (3, 3)
    np.testing.assert_array_equal(members[0], [2, 5, 7])
    np.testing.assert_array_equal(members[1], [3, 9, NULL])
    np.testing.assert_array_equal(members[2], [NULL, NULL, NULL])


# -------------------------------------- contraction list ranking (§8)


@pytest.mark.parametrize("method", ["double", "contract"])
@pytest.mark.parametrize("k", [2, 3, 7, 32, 1000])
def test_chain_order_method_parity(method, k):
    """contraction == doubling == scalar walk, any sampling stride —
    including k larger than the whole table (spine = heads only)."""
    nxt, live = _random_chain(300, 211, seed=k)
    head = int(live[0])
    want = _scalar_order(nxt, head, 211)
    np.testing.assert_array_equal(
        chain_order(nxt, head, 211, method=method, k=k), want)
    np.testing.assert_array_equal(
        chain_order(nxt, head, method=method, k=k), want)
    np.testing.assert_array_equal(
        chain_lengths(nxt, np.array([head, NULL]), method=method, k=k),
        [211, 0])


@pytest.mark.parametrize("method", ["double", "contract"])
def test_mid_chain_cycle_detected(method):
    """A cycle reachable only MID-chain (the head itself is not on it)
    must raise in both strategies: 0 -> 1 -> 2 -> 3 -> 1."""
    nxt = np.array([1, 2, 3, 1], np.int64)
    with pytest.raises(RuntimeError, match="cycle"):
        chain_order(nxt, 0, method=method)
    with pytest.raises(RuntimeError, match="cycle"):
        chain_lengths(nxt, np.array([0]), method=method)
    with pytest.raises(RuntimeError, match="cycle"):
        chain_walk(nxt, np.array([0]), method=method)


def test_mid_chain_spine_free_cycle_poisons_contract():
    """A mid-chain cycle that contains NO spine node (all its ids are
    off the k-stride) can't surface as a contracted-chain cycle — the
    local walk must poison the stuck segment instead of spinning, and
    the poisoned weight must still read as "cycle"."""
    nxt = np.full(64, NULL, np.int64)
    nxt[0] = 33                       # head 0 (spine) into the cycle:
    nxt[33], nxt[34], nxt[35] = 34, 35, 33   # 33/34/35 are all % 32 != 0
    with pytest.raises(RuntimeError, match="cycle"):
        chain_order(nxt, 0, method="contract", k=32)
    with pytest.raises(RuntimeError, match="cycle"):
        chain_lengths(nxt, np.array([0]), method="contract", k=32)


@pytest.mark.parametrize("method", ["double", "contract"])
def test_mid_chain_cycle_beyond_committed_count_recovers_prefix(method):
    """Torn-epoch shape: the committed prefix is a valid chain; a torn
    NEXT beyond it loops back.  An explicit committed count must bound
    the walk to the prefix WITHOUT tripping cycle detection — the
    stale-count recovery guarantee, preserved by the contraction path
    (only segments whose start lands inside [0, count) are expanded)."""
    nxt = np.array([1, 2, 3, 4, 2, NULL], np.int64)   # 4 -> 2 re-enters
    for count in (1, 2, 3):
        np.testing.assert_array_equal(
            chain_order(nxt, 0, count, method=method, k=2),
            [0, 1, 2][:count])


@pytest.mark.parametrize("k", [2, 4, 32])
def test_chain_walk_contract_matches_level_sync(k):
    rng = np.random.default_rng(k)
    nxt = np.full(400, NULL, np.int64)
    heads = []
    free = rng.permutation(400)
    at = 0
    for ln in (1, 7, 40, 113):        # four disjoint chains
        ids = free[at:at + ln]
        at += ln
        nxt[ids[:-1]] = ids[1:]
        heads.append(int(ids[0]))
    heads.append(NULL)
    heads.append(999)                 # OOB head: empty row
    want = chain_walk(nxt, np.asarray(heads), method="double")
    got = chain_walk(nxt, np.asarray(heads), method="contract", k=k)
    np.testing.assert_array_equal(got, want)


def test_chain_walk_auto_escalates_only_on_long_chains():
    """chain_walk "auto" on a big table must not pay contraction's
    O(n) passes for short chains (the hashmap-unlink hot path) but
    must still rank a proven-long chain correctly after escalating."""
    from repro.core.recovery import CONTRACT_MIN_N, _WALK_ESCALATE_ROUNDS
    n = CONTRACT_MIN_N
    nxt = np.full(n, NULL, np.int64)
    rng = np.random.default_rng(0)
    ids = rng.permutation(n)[:_WALK_ESCALATE_ROUNDS * 3]
    nxt[ids[:-1]] = ids[1:]          # one long chain, escalates
    short = np.asarray([int(ids[-1]), NULL])   # plus a length-1 chain
    long_heads = np.asarray([int(ids[0])])
    want = chain_walk(nxt, long_heads, method="contract")
    got = chain_walk(nxt, long_heads, method="auto")
    np.testing.assert_array_equal(got, want)
    # short chains resolve within the escalation budget (level-sync)
    np.testing.assert_array_equal(
        chain_walk(nxt, short, method="auto"),
        [[int(ids[-1])], [NULL]])
    with pytest.raises(ValueError, match="unknown chain method"):
        chain_walk(nxt, short, method="levelsync")


def test_chain_method_heuristic_and_override():
    from repro.core.recovery import (CONTRACT_MIN_COUNT, CONTRACT_MIN_N,
                                     chain_method)
    assert chain_method(CONTRACT_MIN_N - 1) == "double"
    assert chain_method(CONTRACT_MIN_N) == "contract"
    # tiny explicit counts stay on the doubling tables
    assert chain_method(CONTRACT_MIN_N, CONTRACT_MIN_COUNT - 1) == "double"
    assert chain_method(16, method="contract") == "contract"
    with pytest.raises(ValueError, match="unknown chain method"):
        chain_method(16, method="scalar")


# ------------------------------------------------------- RecoveryManager


def test_manager_orders_by_dependency_and_times_stages(rng):
    a = open_arena(None, DoublyLinkedList.layout(64, "partly"))
    d = DoublyLinkedList(a, 64, "partly")
    d.append_batch(rng.integers(0, 9, (10, 7)))
    a.commit()
    a.crash()

    ran = []

    @reconstruct.register("test.probe")
    def _probe(tag):
        ran.append(tag)
        return {"tag": tag}

    mgr = RecoveryManager(a)
    # registered out of order: declared dependencies must win
    mgr.add("late", "test.probe", "late", depends=("dll", "early"))
    mgr.add("early", "test.probe", "early")
    mgr.add("dll", "pstruct.dll", d, depends=("early",))
    assert mgr.order() == ["early", "dll", "late"]
    report = mgr.recover()
    assert ran == ["early", "late"]
    assert d.count == 10
    # staged report: reopen + one stage per recoverable, all timed
    assert [s.name for s in report.stages] == ["reopen", "early", "dll",
                                               "late"]
    assert all(s.seconds >= 0 for s in report.stages)
    assert report.stage("dll").detail["count"] == 10
    assert report.valid and report.generation == 1


def test_manager_reports_committed_generation_across_processes(tmp_path,
                                                               rng):
    """The report's generation comes from the persisted header, so a
    recovery in a fresh process (in-memory counter back at 0) still
    names the committed generation it restored."""
    path = str(tmp_path / "arena")
    a = open_arena(path, DoublyLinkedList.layout(32, "partly"))
    d = DoublyLinkedList(a, 32, "partly")
    for _ in range(3):
        d.append_batch(rng.integers(0, 9, (2, 7)))
        a.commit()
    a.close()
    a2 = open_arena(path, DoublyLinkedList.layout(32, "partly"))
    d2 = DoublyLinkedList(a2, 32, "partly")
    mgr = RecoveryManager(a2)
    mgr.add("dll", "pstruct.dll", d2)
    report = mgr.recover()
    assert report.valid and report.generation == 3
    assert a2.generation == 3              # reopen re-anchors the counter
    assert d2.count == 6


def test_manager_rejects_unknown_and_cyclic_dependencies():
    mgr = RecoveryManager()
    with pytest.raises(KeyError):
        mgr.add("x", "no.such.reconstructor", None)
    mgr.add("a", "rng", 0, depends=("b",))
    with pytest.raises(KeyError):
        mgr.order()                       # b unregistered
    mgr.add("b", "rng", 0, depends=("a",))
    with pytest.raises(ValueError, match="cycle"):
        mgr.order()


def test_manager_reports_uncommitted_arena_invalid(rng):
    a = open_arena(None, DoublyLinkedList.layout(32, "partly"))
    d = DoublyLinkedList(a, 32, "partly")
    d.append_batch(rng.integers(0, 9, (4, 7)))
    a.crash()                              # commit() never ran
    mgr = RecoveryManager(a)
    mgr.add("dll", "pstruct.dll", d)
    report = mgr.recover()
    # epoch flushes are durable (the structure recovers), but the
    # arena-level validity flag — checked once, by the manager — records
    # that no commit sealed them
    assert not report.valid
    assert d.count == 4


# --------------------------------------------------- torn-epoch recovery


def _mixed_arena(mode, n_shards):
    layout = {}
    layout.update(DoublyLinkedList.layout(256, mode, name="dll"))
    layout.update(BPTree.layout(256, 1024, mode, name="bt"))
    layout.update(Hashmap.layout(512, mode, name="hm"))
    a = open_arena(None, layout, n_shards=n_shards)
    return (a, DoublyLinkedList(a, 256, mode, name="dll"),
            BPTree(a, 256, 1024, mode, name="bt"),
            Hashmap(a, 512, mode, name="hm"))


def _script(n_ops, seed=0):
    """Mixed append/insert workload over fresh keys (torn-epoch-safe ops:
    nothing rewrites committed persistent rows destructively)."""
    rng = np.random.default_rng(seed)
    ops = []
    key = 0
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


def _state(d, t, h, bt_keys, hm_keys):
    order = d.to_list()
    ok_b, got_b = t.find_batch(np.asarray(bt_keys, np.int64)) \
        if bt_keys else (np.ones(0, bool), np.zeros((0, 7), np.int64))
    ok_h, got_h = h.find_batch(np.asarray(hm_keys, np.int64)) \
        if hm_keys else (np.ones(0, bool), np.zeros((0, 7), np.int64))
    return {"dll_order": order.copy(), "dll_data": d.data[order].copy(),
            "bt_count": t.header.vol[0, 3], "bt_ok": ok_b.copy(),
            "bt_vals": got_b.copy(), "hm_size": h.size,
            "hm_ok": ok_h.copy(), "hm_vals": got_h.copy()}


def _recover_all(a, d, t, h):
    mgr = RecoveryManager(a)
    mgr.add("dll", "pstruct.dll", d)
    mgr.add("bt", "pstruct.bptree", t)
    mgr.add("hm", "pstruct.hashmap", h)
    return mgr.recover()


# the torn-epoch sweep runs on one shard and on a sharded substrate
# (DESIGN.md §7)
@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("torn", [False, True])
def test_crash_at_every_epoch_boundary_recovers_committed_state(mode, torn,
                                                                n_shards):
    """Replay a 12-op mixed workload; for every boundary b, crash during
    op b+1 — either before anything flushed (torn=False: power loss
    mid-epoch) or after the data half flushed but not the metadata half
    (torn=True) — recover via the manager, and compare against the state
    captured at boundary b."""
    ops = _script(12)
    n = len(ops)
    for boundary in range(n):
        a, d, t, h = _mixed_arena(mode, n_shards)
        bt_keys, hm_keys = [], []
        snap = None
        for i in range(boundary + 1):
            _apply(d, t, h, ops[i])
            kind, keys, _ = ops[i]
            (bt_keys if kind == "bt" else hm_keys if kind == "hm"
             else []).extend(keys.tolist())
            a.commit()
        snap = _state(d, t, h, bt_keys, hm_keys)
        gen0 = a.generation
        # crash inside the NEXT op's epoch
        if boundary + 1 < n:
            with a.epoch():
                _apply(d, t, h, ops[boundary + 1])
                if torn:
                    a.writeset.flush(include_meta=False)
                a.crash()
        else:
            a.crash()
        report = _recover_all(a, d, t, h)
        assert report.valid and a.generation == gen0
        got = _state(d, t, h, bt_keys, hm_keys)
        # DLL + Hashmap: the committed COUNT / fresh-water mark bounds
        # the recovered state in both crash flavors — byte-exact last
        # committed generation even when the torn op touched them.
        np.testing.assert_array_equal(got["dll_order"], snap["dll_order"])
        np.testing.assert_array_equal(got["dll_data"], snap["dll_data"])
        assert got["hm_size"] == snap["hm_size"]
        assert got["hm_ok"].all() and snap["hm_ok"].all()
        np.testing.assert_array_equal(got["hm_vals"], snap["hm_vals"])
        bt_torn = (torn and boundary + 1 < n
                   and ops[boundary + 1][0] == "bt")
        if bt_torn:
            # the torn epoch's data half rewrote committed leaf rows in
            # place — the documented B+Tree asymmetry: keys still found
            # must carry committed values, strict equality is not owed
            found = got["bt_ok"]
            np.testing.assert_array_equal(got["bt_vals"][found],
                                          snap["bt_vals"][found])
        else:
            t.check_invariants()
            assert got["bt_ok"].all()
            np.testing.assert_array_equal(got["bt_vals"], snap["bt_vals"])
            assert got["bt_count"] == snap["bt_count"]


@pytest.mark.parametrize("n_shards", [1, 4])
def test_torn_bptree_leaf_rewrite_is_visible_but_durable(rng, n_shards):
    """Documents the asymmetry the boundary sweep allows for: a B+Tree
    insert rewrites committed leaf rows in place, so the data half of a
    torn epoch IS reachable after recovery — committed keys stay durable
    with committed values, but the torn keys surface and the committed
    COUNT goes stale (which is why check_invariants is not owed here,
    unlike the count-bounded DLL/Hashmap)."""
    a, d, t, h = _mixed_arena("partly", n_shards)
    keys = np.arange(40, dtype=np.int64)
    vals = rng.integers(0, 9, (40, 7)).astype(np.int64)
    t.insert_batch(keys, vals)
    a.commit()
    torn_keys = np.arange(40, 45, dtype=np.int64)
    with a.epoch():
        t.insert_batch(torn_keys,
                       rng.integers(0, 9, (5, 7)).astype(np.int64))
        a.writeset.flush(include_meta=False)
        a.crash()
    _recover_all(a, d, t, h)
    ok, got = t.find_batch(keys)
    assert ok.all()
    np.testing.assert_array_equal(got, vals)
    ok_torn, _ = t.find_batch(torn_keys)
    assert ok_torn.all()                       # torn rewrite surfaced
    assert int(t.header.vol[0, 3]) == 40       # committed COUNT is stale


# ------------------------------------------------ serving recovery report


def test_engine_recovery_report_has_dependency_ordered_stages(tmp_path):
    import jax.numpy as jnp

    from repro.configs import base, registry
    from repro.models.model import build
    from repro.serve.engine import EngineConfig, ServingEngine
    import jax

    model = build(base.reduced(registry.get("llama3.2-3b")),
                  compute_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, EngineConfig(max_batch=2, s_max=16,
                                                    max_requests=16),
                        arena_path=str(tmp_path / "a"))
    eng.add_request(7, np.array([1, 2, 3], np.int64))
    eng.add_request(8, np.array([4, 5, 6], np.int64))   # same prompt length
    eng.step()
    eng.crash()
    dt = eng.recover()
    assert dt >= 0
    rep = eng.last_recovery
    names = [s.name for s in rep.stages]
    expect = {"reopen", "req_table", "lru", "pages", "engine"}
    if eng.journal is not None:          # REPRO_JOURNAL-dependent stage
        expect.add("journal")
    assert set(names) == expect
    # reopen is the prologue; the engine stage depends on everything else
    assert names[0] == "reopen" and names[-1] == "engine"
    assert rep.stage("engine").detail["requests"] == 2
    # equal-length prompts re-prefill as ONE batched group
    assert rep.stage("engine").detail["prefill_groups"] == 1
    assert rep.total_seconds >= rep.seconds("engine")


def test_paged_allocator_recovery_report(tmp_path):
    from repro.serve.kvcache import PagedAllocator, PagedConfig
    pa = PagedAllocator(PagedConfig(n_pages=16, page_tokens=4),
                        path=str(tmp_path / "pg"))
    pa.alloc(1, 5)
    pa.arena.commit()
    pa.arena.crash()
    sec = pa.recover()
    assert sec >= 0
    rep = pa.last_recovery
    assert [s.name for s in rep.stages] == ["reopen", "lru", "pages"]
    assert rep.stage("pages").detail["pages_live"] == 5
    assert rep.stage("pages").detail["pages_free"] == 11
