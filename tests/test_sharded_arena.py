"""Sharded persistent arenas (core/arena.py ShardedArena, DESIGN.md §7).

Invariant families:

* routers partition rows exactly; every router round-trips through
  epoch-mark -> commit -> crash -> reopen (serial and pooled);
* the aggregate line/dedup accounting of a sharded arena is IDENTICAL
  to the single arena's for the same op trace (the medium-independent
  metric must not depend on how the substrate is partitioned);
* shard-count invariance: recovering the same op trace under
  n_shards in {1, 3, 4} yields bit-identical structure fingerprints;
* manifest-last commit protocol: a crash in the inter-shard commit
  window (after shard k of N committed, before the manifest) recovers
  the last generation ALL shards agree on — swept over every k;
* the data-before-metadata barrier is GLOBAL across shards: a torn
  flush never exposes a header on one shard ahead of another shard's
  data;
* the dependency-counter scheduler starts a stage the moment its own
  deps land (no level barrier), reports ready_at / queue_wait, and
  splits a sharded arena's reopen into per-region load stages;
* the serving engine stripes its token slab across shards and re-admits
  traffic per (shard, prompt-length) group.
"""
import os

import numpy as np
import pytest

from repro.core import reconstruct
from repro.core.arena import (Arena, ShardedArena, open_arena, route_rows,
                              router_block)
from repro.core.recovery import RecoveryManager
from repro.pstruct.bptree import BPTree
from repro.pstruct.dll import DoublyLinkedList
from repro.pstruct.hashmap import Hashmap

ROUTERS = (("seg", 4), ("seg", 64), ("hash",), ("hash", 8), ("range",),
           ("shard", 2), None)


# ------------------------------------------------------------- routers


@pytest.mark.parametrize("router", ROUTERS)
@pytest.mark.parametrize("n_shards", [2, 3, 4])
def test_router_partitions_rows_exactly(router, n_shards):
    shard_of = route_rows(router, 103, n_shards)
    assert shard_of.shape == (103,)
    assert ((shard_of >= 0) & (shard_of < n_shards)).all()
    # block-granular routers are constant within each block
    blk = router_block(router)
    if blk:
        for b in range(103 // blk):
            assert len(set(shard_of[b * blk:(b + 1) * blk])) == 1


@pytest.mark.parametrize("router", ROUTERS)
def test_roundtrip_epoch_commit_crash_reopen(router):
    a = open_arena(None, {"r": (np.int64, (103, 8), router),
                          "r.header": (np.int64, (1, 8))}, n_shards=3)
    r, hdr = a.regions["r"], a.regions["r.header"]
    data = np.random.default_rng(0).integers(0, 99, (103, 8))
    r.vol[:] = data
    hdr.vol[0, 0] = 42
    with a.epoch():
        r.mark_rows(np.arange(103))
        hdr.mark_rows(np.array([0]))
    a.commit()
    a.crash()
    assert (r.vol == 0).all()
    a.reopen()
    np.testing.assert_array_equal(r.vol, data)
    assert hdr.vol[0, 0] == 42
    assert a.header_valid() and a.header_generation() == 1
    # pooled reopen is bit-identical
    a.crash()
    a.reopen(concurrency=3)
    np.testing.assert_array_equal(r.vol, data)


def test_local_global_maps_are_bijective():
    a = open_arena(None, {"r": (np.int64, (257, 8), ("hash", 4))},
                   n_shards=4)
    r = a.regions["r"]
    seen = np.zeros(257, bool)
    for s, sl in enumerate(r.slices):
        if sl is None:
            continue
        assert (r.shard_of[sl._gidx] == s).all()
        assert (r.local_of[sl._gidx] == np.arange(sl._gidx.size)).all()
        assert not seen[sl._gidx].any()
        seen[sl._gidx] = True
    assert seen.all()


# --------------------------------------------------------- accounting


def test_aggregate_accounting_matches_single_arena():
    """Same op trace, same exact line/dedup numbers — sharding changes
    WHERE bytes land, never how many lines the medium is charged."""
    stats = {}
    for ns in (1, 4):
        rng = np.random.default_rng(11)        # identical trace per config
        a = open_arena(None, BPTree.layout(256, 1024), n_shards=ns)
        t = BPTree(a, 256, 1024)
        keys = rng.permutation(500).astype(np.int64)
        vals = rng.integers(0, 1 << 30, (500, 7)).astype(np.int64)
        for i in range(0, 500, 97):
            t.insert_batch(keys[i:i + 97], vals[i:i + 97])
        t.delete_batch(keys[:100])
        a.commit()
        s = a.stats
        stats[ns] = (s.lines, s.bytes, s.saved_lines, s.dedup_rows,
                     s.epochs)
    assert stats[1] == stats[4], stats


def test_per_shard_stats_sum_to_aggregate(rng):
    a = open_arena(None, DoublyLinkedList.layout(256), n_shards=3)
    d = DoublyLinkedList(a, 256)
    # 200 rows = 4 segment blocks of 64 -> shards 0, 1, 2, 0
    d.append_batch(rng.integers(0, 9, (200, 7)))
    a.commit()
    agg = a.stats
    per = a.shard_stats()
    assert agg.lines == sum(s.lines for s in per)
    assert agg.bytes == sum(s.bytes for s in per)
    assert all(s.lines > 0 for s in per)   # every shard took flushes


# ------------------------------------------- shard-count invariance


def _mixed(n_shards, mode="partly"):
    layout = {}
    layout.update(DoublyLinkedList.layout(256, mode, name="dll"))
    layout.update(BPTree.layout(256, 1024, mode, name="bt"))
    layout.update(Hashmap.layout(512, mode, name="hm"))
    a = open_arena(None, layout, n_shards=n_shards)
    return (a, DoublyLinkedList(a, 256, mode, name="dll"),
            BPTree(a, 256, 1024, mode, name="bt"),
            Hashmap(a, 512, mode, name="hm"))


def _trace(a, d, t, h, n_ops=9, seed=7):
    rng = np.random.default_rng(seed)
    key = 0
    for i in range(n_ops):
        m = int(rng.integers(2, 7))
        vals = rng.integers(0, 1 << 30, (m, 7)).astype(np.int64)
        keys = np.arange(key, key + m, dtype=np.int64)
        key += m
        if i % 3 == 0:
            d.append_batch(vals)
        elif i % 3 == 1:
            t.insert_batch(keys, vals)
        else:
            h.insert_batch(keys, vals)
        a.commit()


def _recover(a, d, t, h, concurrency=1):
    mgr = RecoveryManager(a)
    mgr.add("dll", "pstruct.dll", d, regions=("dll.nodes", "dll.header"))
    mgr.add("bt", "pstruct.bptree", t,
            regions=("bt.nodes", "bt.records", "bt.header"))
    mgr.add("hm", "pstruct.hashmap", h,
            regions=("hm.entries", "hm.header"))
    return mgr.recover(concurrency=concurrency)


def _fingerprint(a, d, t, h):
    fp = {f"region:{nm}": r.vol.copy() for nm, r in a.regions.items()}
    fp["dll.prev"] = d.prev.copy()
    fp["dll.order"] = d.order().copy()
    fp["dll.free"] = np.sort(np.asarray(d._free, np.int64))
    fp["hm.n_buckets"] = h.n_buckets
    fp["hm.buckets"] = h.buckets.copy()
    fp["hm.chain"] = h.chain.copy()
    fp["bt.leaf_prev"] = t.leaf_prev.copy()
    fp["bt.free_nodes"] = np.sort(np.asarray(t._free_nodes, np.int64))
    return fp


@pytest.mark.parametrize("mode", ["partly", "full"])
def test_shard_count_invariant_fingerprints(mode):
    """Recovering the same committed op trace under n_shards in
    {1, 3, 4} yields bit-identical structure fingerprints — the shard
    substrate must be invisible above the region API."""
    fps = {}
    for ns in (1, 3, 4):
        a, d, t, h = _mixed(ns, mode)
        _trace(a, d, t, h)
        a.crash()
        rep = _recover(a, d, t, h, concurrency=2 if ns > 1 else 1)
        assert rep.valid and rep.generation == 9
        fps[ns] = _fingerprint(a, d, t, h)
    for ns in (3, 4):
        assert fps[ns].keys() == fps[1].keys()
        for k in fps[1]:
            np.testing.assert_array_equal(fps[ns][k], fps[1][k],
                                          err_msg=f"n_shards={ns}: {k}")


# --------------------------------------- inter-shard commit window


@pytest.mark.parametrize("crash_after_shard", [0, 1, 2, 3])
def test_intershard_commit_window_recovers_agreed_generation(
        crash_after_shard):
    """The crash-point fuzzer's new sweep axis: power fails AFTER shard
    k of 4 committed generation g+1 but BEFORE the manifest.  The
    manifest still names g — the generation all shards agree on — and
    recovery must land exactly where a plain flushed-but-uncommitted
    crash lands (the epoch data is durable either way; only the
    generation seal differs)."""
    def build():
        a, d, t, h = _mixed(4)
        _trace(a, d, t, h, n_ops=6)
        # one more op whose COMMIT is the thing that fails
        d.append_batch(np.ones((3, 7), np.int64))
        return a, d, t, h

    # reference: epoch flushed (epoch close), commit never ran
    a0, d0, t0, h0 = build()
    gen0 = a0.header_generation()
    a0.crash()
    _recover(a0, d0, t0, h0)
    want = _fingerprint(a0, d0, t0, h0)

    a, d, t, h = build()
    a.commit(_crash_after_shard=crash_after_shard)   # powers off mid-commit
    rep = _recover(a, d, t, h)
    # shards 0..k sit at gen+1; the manifest — written LAST — still
    # seals the generation every shard reached
    assert rep.generation == gen0 == 6
    assert rep.valid
    got = _fingerprint(a, d, t, h)
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    # the substrate is not wedged: the next commit seals a new
    # generation on every shard and the manifest
    a.commit()
    assert a.header_generation() == 7 and a.header_valid()


@pytest.mark.parametrize("crash_after_shard", range(4))
def test_commit_window_sweep(crash_after_shard):
    """The inter-shard commit-window sweep: ``crash_after_shard=k``
    powers off after shard k's header flipped but before the manifest.
    The manifest names the generation all shards agree on and recovery
    lands where a flushed-but-uncommitted crash lands."""
    def build():
        a, d, t, h = _mixed(4)
        _trace(a, d, t, h, n_ops=6)
        d.append_batch(np.ones((3, 7), np.int64))
        return a, d, t, h

    a0, d0, t0, h0 = build()
    gen0 = a0.header_generation()
    a0.crash()
    _recover(a0, d0, t0, h0)
    want = _fingerprint(a0, d0, t0, h0)

    a, d, t, h = build()
    a.commit(_crash_after_shard=crash_after_shard)
    rep = _recover(a, d, t, h)
    assert rep.valid and rep.generation == gen0 == 6
    got = _fingerprint(a, d, t, h)
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    # not wedged: the next commit seals gen 7 everywhere
    a.commit()
    assert a.header_generation() == 7 and a.header_valid()


def test_manifest_is_written_last_on_disk(tmp_path):
    path = str(tmp_path / "arena")
    a = open_arena(path, DoublyLinkedList.layout(128), n_shards=3)
    d = DoublyLinkedList(a, 128)
    d.append_batch(np.arange(21, dtype=np.int64).reshape(3, 7))
    a.commit()
    for k in range(3):
        assert os.path.exists(f"{path}.s{k}")
    assert os.path.exists(path + ".manifest")
    a.close()
    # fresh-process open: committed generation + data come back
    a2 = open_arena(path, DoublyLinkedList.layout(128), n_shards=3)
    d2 = DoublyLinkedList(a2, 128)
    rep = RecoveryManager(a2).add("dll", "pstruct.dll", d2).recover()
    assert rep.valid and rep.generation == 1
    assert d2.count == 3


def test_reopening_with_wrong_shard_count_fails_loudly(tmp_path):
    """The manifest records n_shards precisely so a mis-configured
    fresh-process open cannot silently map the wrong number of backing
    files and 'recover' garbage."""
    path = str(tmp_path / "arena")
    a = open_arena(path, DoublyLinkedList.layout(128), n_shards=2)
    a.commit()
    a.close()
    with pytest.raises(ValueError, match="2 shards, opened with 4"):
        open_arena(path, DoublyLinkedList.layout(128), n_shards=4)


def test_shard_header_ahead_of_manifest_is_still_valid():
    """Shards ahead of the manifest (gen+1 committed, manifest at gen)
    are torn territory the structures bound away — validity only
    requires every shard to have REACHED the manifest generation."""
    a, d, t, h = _mixed(2)
    _trace(a, d, t, h, n_ops=4)
    a.commit(_crash_after_shard=0)
    assert a.header_valid()
    # but a shard BEHIND the manifest is corruption
    a.shards[1].generation = 0
    a.shards[1]._write_header(valid=True)
    assert not a.header_valid()


# ----------------------------- global data-before-metadata barrier


def test_data_before_metadata_barrier_is_global():
    """Data region pinned to shard 1, header pinned to shard 0: a torn
    flush (include_meta=False) must persist shard 1's data and drop
    shard 0's header mark — the barrier orders PHASES across all
    shards, not per shard."""
    a = open_arena(None, {"r": (np.int64, (64, 8), ("shard", 1)),
                          "r.header": (np.int64, (1, 8), ("shard", 0))},
                   n_shards=2)
    r, hdr = a.regions["r"], a.regions["r.header"]
    with a.epoch():
        r.vol[5] = 7
        r.mark_rows(np.array([5]))
        hdr.vol[0, 0] = 99
        hdr.mark_rows(np.array([0]))
        a.writeset.flush(include_meta=False)
        assert not a.writeset
        a.crash()
    a.reopen()
    assert r.vol[5, 0] == 7          # data half landed (shard 1)
    assert hdr.vol[0, 0] == 0        # metadata half was dropped (shard 0)


# --------------------------- dependency-counter scheduler + ready_at


def test_scheduler_has_no_level_barrier():
    """A fast chain must race ahead of a slow sibling: `child` depends
    only on `fast`, so under the counter scheduler it starts while
    `slow` (same level as `fast`) is still running — the level-barrier
    implementation would have gated it on slow's end."""
    if "test.sleepy" not in reconstruct.names():
        @reconstruct.register("test.sleepy")
        def _sleepy(secs):
            import time as _t
            _t.sleep(secs)
            return {}

    mgr = RecoveryManager()
    mgr.add("slow", "test.sleepy", 0.25)
    mgr.add("fast", "test.sleepy", 0.01)
    mgr.add("child", "test.sleepy", 0.01, depends=("fast",))
    rep = mgr.recover(reopen=False, concurrency=3)
    slow, child = rep.stage("slow"), rep.stage("child")
    assert child.t_start < slow.t_end - 0.05
    assert child.ready_at >= rep.stage("fast").t_end - 1e-6
    assert [s.name for s in rep.stages] == ["slow", "fast", "child"]


def test_stage_reports_expose_ready_at_and_queue_wait(rng):
    a, d, t, h = _mixed(3)
    _trace(a, d, t, h, n_ops=5)
    a.crash()
    rep = _recover(a, d, t, h, concurrency=2)
    names = [s.name for s in rep.stages]
    # sharded arena + declared regions => per-region load stages for
    # the BULK regions (>= 64 KiB; smaller ones load in the reopen
    # prologue), biggest first, between reopen and the rebuilds
    assert names[0] == "reopen"
    loads = [n for n in names if n.startswith("load:")]
    assert set(loads) == {"load:bt.nodes", "load:bt.records"}
    assert names[-3:] == ["dll", "bt", "hm"]
    for s in rep.stages:
        assert s.t_start >= s.ready_at >= 0.0
        dd = s.as_dict()
        assert "ready_at" in dd and "queue_wait" in dd
        assert dd["queue_wait"] >= 0.0


def test_same_named_regions_across_arenas_all_reload(rng):
    """Two sharded arenas in one manager, both holding a region named
    'dll.nodes' big enough to become a load stage: the stage must reload
    BOTH arenas' regions (neither may be left zeroed by the reopen
    exclusion)."""
    arenas, dlls = [], []
    for k in range(2):
        a = open_arena(None, DoublyLinkedList.layout(2048), n_shards=2)
        d = DoublyLinkedList(a, 2048)
        d.append_batch(rng.integers(1, 9, (64 * (k + 1), 7)))
        a.commit()
        arenas.append(a)
        dlls.append(d)
    for a in arenas:
        a.crash()
    mgr = RecoveryManager(*arenas)
    mgr.add("d0", "pstruct.dll", dlls[0],
            regions=("dll.nodes", "dll.header"))
    mgr.add("d1", "pstruct.dll", dlls[1],
            regions=("dll.nodes", "dll.header"))
    rep = mgr.recover(concurrency=2)
    assert "load:dll.nodes" in [s.name for s in rep.stages]
    assert dlls[0].count == 64 and dlls[1].count == 128
    assert (dlls[0].data[dlls[0].to_list()] != 0).all()
    assert (dlls[1].data[dlls[1].to_list()] != 0).all()


def test_serial_and_concurrent_sharded_recovery_bit_identical():
    a, d, t, h = _mixed(4)
    _trace(a, d, t, h)
    a.crash()
    _recover(a, d, t, h, concurrency=1)
    fp1 = _fingerprint(a, d, t, h)
    a.crash()
    _recover(a, d, t, h, concurrency=4)
    fp4 = _fingerprint(a, d, t, h)
    for k in fp1:
        np.testing.assert_array_equal(fp4[k], fp1[k], err_msg=k)


# ------------------------------------------------- serving engine


def test_engine_stripes_tokens_and_admits_per_shard_group(tmp_path):
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
                                     max_requests=16, n_shards=2),
                        arena_path=str(tmp_path / "a"))
    assert isinstance(eng.arena, ShardedArena)
    # slot-per-shard striping of the token slab
    np.testing.assert_array_equal(
        eng.arena.region_shards("tokens", np.array([0, 1])), [0, 1])
    eng.add_request(7, np.array([1, 2, 3], np.int64))
    eng.add_request(8, np.array([4, 5, 6], np.int64))   # same prompt len
    out0 = dict(eng.step())
    eng.crash()
    eng.recover()
    det = eng.last_recovery.stage("engine").detail
    # same length, DIFFERENT token-log shards: admission goes per
    # shard-group, so two groups (a single arena would batch them once)
    assert det["prefill_groups"] == 2
    assert det["shard_groups"] == 2
    # greedy decode stays bit-checkable across the sharded substrate
    assert sorted(out0) == [7, 8]
    out1 = dict(eng.step())
    assert sorted(out1) == [7, 8]


def test_single_shard_sharded_arena_matches_plain(rng):
    """ShardedArena(n_shards=1) behaves like the plain Arena (the
    open_arena fast path) for the same trace — belt and braces for the
    degenerate configuration."""
    a1 = open_arena(None, DoublyLinkedList.layout(128), n_shards=1)
    assert isinstance(a1, Arena)
    sh = ShardedArena(None, n_shards=1)
    for name, spec in DoublyLinkedList.layout(128).items():
        sh.region(name, spec[0], spec[1],
                  router=spec[2] if len(spec) > 2 else None)
    sh.finalize()
    d1 = DoublyLinkedList(a1, 128)
    d2 = DoublyLinkedList(sh, 128)
    vals = rng.integers(0, 9, (20, 7))
    d1.append_batch(vals)
    d2.append_batch(vals)
    a1.commit()
    sh.commit()
    assert a1.stats.lines == sh.stats.lines
    a1.crash(), sh.crash()
    a1.reopen(), sh.reopen()
    d1.reconstruct(), d2.reconstruct()
    np.testing.assert_array_equal(d1.to_list(), d2.to_list())
