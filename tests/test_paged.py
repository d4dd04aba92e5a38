"""Paged regions & block cache (core/paging.py, DESIGN.md §12).

Invariant families:

* region selection: only data regions bigger than one block page fault
  through the cache — headers, order snapshots, and journal rings stay
  resident; paging is strictly volatile-side, so a paged arena's
  persistent files are BYTE-identical to an unpaged arena's for the
  same op trace (sharded and single);
* LRU discipline: clean blocks evict at the budget, dirty blocks are
  pinned until the write-set drain (the epoch flush IS the write-back
  path) — an all-pinned cache goes over budget rather than drop the
  only copy of unflushed rows;
* crash contract: a crashed paged region reads ZEROS (never stale
  committed bytes) until reopen/load re-authorizes faulting;
* eviction + write-back under crash sweeps: forced post-commit drops
  (``drop_clean``) and organic tiny-cache eviction never change what
  recovery reconstructs — fingerprints match the unpaged reference at
  every epoch boundary;
* the spill fallback (full-``.vol`` consumers) is correct, counted,
  and exits paged mode until the next load;
* recovery reports per-stage ``block_faults`` on paged arenas.
"""
import numpy as np
import pytest

from repro.core.arena import open_arena
from repro.core.paging import BlockCache, PagedRegion, PagedShardedRegion
from repro.core.recovery import RecoveryManager
from repro.pstruct.dll import DoublyLinkedList

def _paged_kw(cache_blocks=4, block_bytes=512):
    return dict(paged=True, block_bytes=block_bytes,
                cache_blocks=cache_blocks)


# --------------------------------------------------- region selection


def test_eligibility_and_roundtrip():
    a = open_arena(None, {"r": (np.int64, (64, 8)),
                          "r.header": (np.int64, (1, 8)),
                          "r.snapring": (np.int64, (64, 8)),
                          "jr.jrnl": (np.int64, (64, 8)),
                          "tiny": (np.int64, (4, 8))}, **_paged_kw())
    r = a.regions["r"]
    assert isinstance(r, PagedRegion) and r.is_paged
    # headers / snapshots / journal rings / sub-block regions stay
    # resident no matter their size
    for name in ("r.header", "r.snapring", "jr.jrnl", "tiny"):
        assert not getattr(a.regions[name], "is_paged", False), name
    data = np.arange(64 * 8, dtype=np.int64).reshape(64, 8)
    r.write_rows(np.arange(64), data)
    np.testing.assert_array_equal(r.read_rows(np.arange(64)), data)
    assert r.read_one(13, 5) == data[13, 5]
    np.testing.assert_array_equal(r.read_at(np.array([3, 60]), 2),
                                  data[[3, 60], 2])
    np.testing.assert_array_equal(r.read_col(1), data[:, 1])
    assert a.cache.faults == r.total_blocks   # 64 rows / 8 per block
    assert a.cache.hits > 0


def test_scattered_reads_cross_blocks():
    a = open_arena(None, {"r": (np.int64, (200, 8))}, **_paged_kw(64))
    r = a.regions["r"]
    data = np.random.default_rng(0).integers(0, 99, (200, 8))
    r.write_rows(np.arange(200), data)
    rng = np.random.default_rng(1)
    for _ in range(5):
        rows = rng.integers(0, 200, 37)
        np.testing.assert_array_equal(r.read_rows(rows), data[rows])
        np.testing.assert_array_equal(r.read_at(rows, slice(2, 5)),
                                      data[rows, 2:5])
    assert r.read_rows(np.empty(0, np.int64)).shape == (0, 8)


# ------------------------------------------------- pinning & eviction


def test_dirty_blocks_pinned_until_flush():
    a = open_arena(None, {"r": (np.int64, (64, 8))},
                   **_paged_kw(cache_blocks=1))
    r = a.regions["r"]
    cache = a.cache
    r.write_rows(np.array([0]), np.arange(8))    # block 0 dirty
    r.write_rows(np.array([8]), np.arange(8))    # block 1 dirty
    # both dirty -> neither evictable -> cache rides over budget
    assert cache.over_budget >= 1
    assert cache.resident_bytes > cache.capacity_bytes
    assert r._block_pinned(0) and r._block_pinned(1)
    with a.epoch():
        r.mark_rows(np.array([0, 8]))
    # drained -> unpinned -> free drops
    assert not r._block_pinned(0) and not r._block_pinned(1)
    dropped = cache.drop_clean()
    assert dropped == 2 and cache.resident_bytes == 0
    # refault reads back the flushed values
    np.testing.assert_array_equal(r.read_rows(np.array([0, 8])),
                                  np.broadcast_to(np.arange(8), (2, 8)))


def test_clean_blocks_evict_at_budget():
    a = open_arena(None, {"r": (np.int64, (64, 8))},
                   **_paged_kw(cache_blocks=2))
    r = a.regions["r"]
    data = np.random.default_rng(2).integers(0, 99, (64, 8))
    r.write_rows(np.arange(64), data)
    with a.epoch():
        r.mark_rows(np.arange(64))
    a.commit()
    a.cache.drop_clean()
    base = a.cache.evictions
    over0 = a.cache.over_budget   # the pinned bulk write above rode
    for bid in range(r.total_blocks):           # sequential sweep
        r.read_one(bid * r._block_rows, 0)
    assert a.cache.evictions > base
    assert a.cache.resident_bytes <= a.cache.capacity_bytes
    assert a.cache.over_budget == over0   # clean sweep never over-rides
    np.testing.assert_array_equal(r.read_rows(np.arange(64)), data)


# ------------------------------------------------------ crash contract


def test_crashed_region_reads_zeros_until_reopen(tmp_path):
    a = open_arena(str(tmp_path / "a"), {"r": (np.int64, (64, 8))},
                   **_paged_kw())
    r = a.regions["r"]
    data = np.random.default_rng(3).integers(1, 99, (64, 8))
    r.write_rows(np.arange(64), data)
    with a.epoch():
        r.mark_rows(np.arange(64))
    a.commit()
    a.crash()
    # volatile state is GONE: reads must NOT resurrect committed bytes
    assert (r.read_rows(np.arange(64)) == 0).all()
    assert (r.vol == 0).all()                   # spill path also zeros
    a.reopen()
    np.testing.assert_array_equal(r.read_rows(np.arange(64)), data)


# ------------------------------------------------------ spill fallback


def test_spill_fallback_roundtrip(tmp_path):
    a = open_arena(str(tmp_path / "a"), {"r": (np.int64, (64, 8))},
                   **_paged_kw())
    r = a.regions["r"]
    data = np.random.default_rng(4).integers(0, 99, (64, 8))
    r.write_rows(np.arange(32), data[:32])      # dirty resident rows
    full = r.vol                                # full-array consumer
    assert a.cache.spills == 1
    assert not r.paged_active
    np.testing.assert_array_equal(full[:32], data[:32])
    # post-spill the region behaves like an unpaged one until reload
    r.vol[32:] = data[32:]
    with a.epoch():
        r.mark_rows(np.arange(64))
    a.commit()
    a.crash()
    a.reopen()                                  # load() re-enters paging
    assert r.paged_active
    np.testing.assert_array_equal(r.read_rows(np.arange(64)), data)


# ------------------------------- paged/unpaged parity & byte identity


def _dll_trace(a, d, n_epochs, crash_tail=False):
    """Deterministic append/delete trace, one commit per epoch; with
    ``crash_tail`` adds uncommitted work that a crash must discard."""
    rng = np.random.default_rng(7)
    live = []
    for e in range(n_epochs):
        ids = d.append_batch(rng.integers(0, 99, (7, 7)))
        live.extend(int(i) for i in ids)
        if e % 2 and len(live) > 6:
            dead = [live.pop(0) for _ in range(3)]
            d.delete_batch(np.asarray(dead, np.int64))
        a.commit()
    if crash_tail:
        d.append_batch(rng.integers(0, 99, (3, 7)))


def _dll_fingerprint(d):
    order = d.to_list()
    return order.copy(), d.data[order].copy()


@pytest.mark.parametrize("n_shards", [1, 3])
def test_persistent_files_bit_identical_paged_vs_unpaged(tmp_path, n_shards):
    """Paging is volatile-only: the same op trace must land the same
    bytes in every backing file (shards + manifest)."""
    blobs = {}
    for paged in (False, True):
        root = tmp_path / f"paged{int(paged)}"
        root.mkdir()
        ap = str(root / "a")
        a = open_arena(ap, DoublyLinkedList.layout(256, "partly"),
                       n_shards=n_shards,
                       **(_paged_kw() if paged else {"paged": False}))
        d = DoublyLinkedList(a, 256, "partly")
        _dll_trace(a, d, 6)
        files = {p.name: p.read_bytes() for p in sorted(root.iterdir())
                 if not p.name.endswith(".layout")}
        blobs[paged] = files
    assert blobs[False].keys() == blobs[True].keys()
    for name in blobs[False]:
        assert blobs[False][name] == blobs[True][name], \
            f"{name} diverged under paging"


def test_evict_then_crash_sweep_every_epoch_boundary(tmp_path):
    """At every epoch boundary: commit, force-drop every clean block,
    run an uncommitted tail, crash.  Recovery must reconstruct the
    boundary's committed state bit-identically to an unpaged reference
    crashed at the same point."""
    for k in range(1, 6):
        fps = {}
        for paged in (False, True):
            ap = str(tmp_path / f"{k}.{int(paged)}")
            a = open_arena(ap, DoublyLinkedList.layout(96, "partly"),
                           **(_paged_kw(cache_blocks=3) if paged
                              else {"paged": False}))
            d = DoublyLinkedList(a, 96, "partly")
            _dll_trace(a, d, k, crash_tail=True)
            if paged:
                assert a.cache.drop_clean() > 0
            a.crash()
            a.reopen()
            d.reconstruct()
            fps[paged] = _dll_fingerprint(d)
        np.testing.assert_array_equal(fps[False][0], fps[True][0])
        np.testing.assert_array_equal(fps[False][1], fps[True][1])


def test_organic_eviction_crash_recovery(tmp_path):
    """A cache far smaller than the working set evicts continuously
    during the trace (no forced drops); recovery is still exact."""
    ap = str(tmp_path / "a")
    a = open_arena(ap, DoublyLinkedList.layout(96, "partly"),
                   **_paged_kw(cache_blocks=2))
    d = DoublyLinkedList(a, 96, "partly")
    _dll_trace(a, d, 8, crash_tail=True)
    assert a.cache.evictions > 0, "cache never evicted — not exercised"
    fp0 = None
    a.crash()
    a.reopen()
    d.reconstruct()
    fp0 = _dll_fingerprint(d)
    # unpaged reference
    a2 = open_arena(str(tmp_path / "b"),
                    DoublyLinkedList.layout(96, "partly"), paged=False)
    d2 = DoublyLinkedList(a2, 96, "partly")
    _dll_trace(a2, d2, 8, crash_tail=True)
    a2.crash()
    a2.reopen()
    d2.reconstruct()
    fp1 = _dll_fingerprint(d2)
    np.testing.assert_array_equal(fp0[0], fp1[0])
    np.testing.assert_array_equal(fp0[1], fp1[1])


# ----------------------------------------------------- sharded paging


@pytest.mark.parametrize("router", [("seg", 8), ("hash",), ("range",)])
def test_sharded_paged_roundtrip(router):
    a = open_arena(None, {"r": (np.int64, (103, 8), router),
                          "r.header": (np.int64, (1, 8))},
                   n_shards=3, **_paged_kw())
    r = a.regions["r"]
    assert isinstance(r, PagedShardedRegion)
    data = np.random.default_rng(5).integers(0, 99, (103, 8))
    r.write_rows(np.arange(103), data)
    a.regions["r.header"].vol[0, 0] = 42
    with a.epoch():
        r.mark_rows(np.arange(103))
        a.regions["r.header"].mark_rows(np.array([0]))
    a.commit()
    a.crash()
    assert (r.read_rows(np.arange(103)) == 0).all()
    a.reopen()
    np.testing.assert_array_equal(r.read_rows(np.arange(103)), data)
    assert a.regions["r.header"].vol[0, 0] == 42


# ------------------------------------------------- recovery reporting


def test_recovery_report_carries_block_faults(tmp_path):
    a = open_arena(str(tmp_path / "a"),
                   DoublyLinkedList.layout(96, "partly"), **_paged_kw())
    d = DoublyLinkedList(a, 96, "partly")
    _dll_trace(a, d, 4)
    a.crash()
    rep = RecoveryManager(a).add("dll", "pstruct.dll", d).recover()
    st = {s.name: s.detail for s in rep.stages}
    assert "block_faults" in st["dll"]
    # lazy load: the reconstructor faults blocks, the reset doesn't
    assert st["dll"]["block_faults"] > 0
    assert a.cache.faults >= st["dll"]["block_faults"]
    np.testing.assert_array_equal(*(_dll_fingerprint(d)[0],
                                    d.to_list()))


def test_cache_counters_consistent():
    c = BlockCache(block_bytes=512, cache_blocks=2)
    assert c.capacity_bytes == 1024
    c.reset_peak()
    assert c.peak_resident_bytes == c.resident_bytes == 0
