"""Paged regions: larger-than-RAM arenas behind the Region API.

DESIGN.md §12.  A ``ShardedRegion``/``Region`` materializes one
full-shape volatile array, capping arena capacity at host RAM and
forcing ``load()`` to read 100% of the persistent bytes after a crash.
The paged backend replaces that array with a pool of fixed-size row
blocks (default 4 KiB — the same granularity as the sharded
block-copy load fast path) faulted in on demand through a per-arena
LRU ``BlockCache``:

* a FAULT assembles the block from its home slots in persistent memory,
  so a refaulted clean block is bit-identical to the volatile view it
  replaces;
* a CLEAN block is therefore pure cache: eviction is a free drop;
* a DIRTY block (unflushed ``write_*`` rows) is PINNED — it holds the
  only copy of those rows, and mid-epoch home write-back would tear
  the committed generation's data-before-metadata invariant.  Dirty
  blocks write back exclusively through the existing write-set drain
  (``_note_flushed``), i.e. the epoch flush IS the write-back path, so
  commit semantics are unchanged;
* recovery's ``load:`` stages become lazy block-pool resets; the
  reconstructors fault exactly the blocks they touch, so recovery cost
  tracks the working set, not the arena size (the OID/node-cache
  indirection the ROADMAP item names).

Consumers that still grab the full ``.vol`` array trigger a one-shot
SPILL: the region materializes (home + dirty resident rows)
and leaves paged mode until the next ``load()``/crash.  Counted in
``BlockCache.spills`` — correctness fallback, not a fast path.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, Optional

import numpy as np

from repro.core.arena import (CorruptLineError, Region, ShardedRegion,
                              sidecar_checksums)


class BlockCache:
    """Per-arena LRU over (region, block id) with dirty-block pinning.

    ``cache_blocks * block_bytes`` is the residency budget; admission
    past it evicts clean unpinned blocks from the LRU end.  When every
    resident block is pinned the cache stays over budget (counted in
    ``over_budget``) rather than evict un-written-back state.  All
    block operations run under one reentrant lock — concurrent
    recovery stages fault safely, and the only lock ordering is
    cache.lock -> arena fence lock (never the reverse)."""

    def __init__(self, block_bytes: int = 4096, cache_blocks: int = 1024):
        self.block_bytes = int(block_bytes)
        self.cache_blocks = int(cache_blocks)
        self.capacity_bytes = self.block_bytes * self.cache_blocks
        self.lock = threading.RLock()
        self._lru: "OrderedDict" = OrderedDict()  # (name, bid) -> region
        self.faults = 0
        self.hits = 0
        self.evictions = 0
        self.spills = 0
        self.over_budget = 0
        self.resident_bytes = 0
        self.peak_resident_bytes = 0

    # All methods assume self.lock is held by the calling accessor.
    def hit(self, region, bid: int) -> None:
        self.hits += 1
        self._lru.move_to_end((region.name, bid))

    def admit(self, region, bid: int, nbytes: int) -> None:
        self.faults += 1
        key = (region.name, bid)
        self._lru[key] = region
        self.resident_bytes += nbytes
        # peak includes the admit-then-evict transient — that memory
        # really coexists, and the SLO slack covers it
        if self.resident_bytes > self.peak_resident_bytes:
            self.peak_resident_bytes = self.resident_bytes
        self._evict_to_budget(protect=key)

    def forget(self, region, bid: int, nbytes: int) -> None:
        self._lru.pop((region.name, bid), None)
        self.resident_bytes -= nbytes

    def _evict_to_budget(self, protect=None) -> None:
        # `protect` is the block being admitted right now: its caller
        # holds a reference and is about to read/write it, so it must
        # survive its own admission even while still clean
        while self.resident_bytes > self.capacity_bytes:
            victim = None
            for (name, bid), region in self._lru.items():
                if (name, bid) == protect:
                    continue
                if not region._block_pinned(bid):
                    victim = (region, bid)
                    break
            if victim is None:
                self.over_budget += 1
                return
            victim[0]._drop_block(victim[1])
            self.evictions += 1

    def drop_clean(self) -> int:
        """Evict EVERY clean unpinned block (memory-pressure hook; the
        crash-sweep tests use it to force post-flush refaults).
        Returns the number of blocks dropped."""
        with self.lock:
            victims = [(region, bid)
                       for (name, bid), region in self._lru.items()
                       if not region._block_pinned(bid)]
            for region, bid in victims:
                region._drop_block(bid)
                self.evictions += 1
            return len(victims)

    def reset_peak(self) -> None:
        """Re-anchor the peak to current residency — phase-scoped peak
        measurement (the --paged-slo gate resets between build and
        recover)."""
        with self.lock:
            self.peak_resident_bytes = self.resident_bytes


class _BlockPool:
    """Demand-faulted block pool shared by PagedRegion and
    PagedShardedRegion.  Subclasses provide ``_assemble(lo, hi)`` (the
    fault read) and ``_integ_ref(lo, hi)`` (the sidecar checksums the
    fault verifies against)."""

    is_paged = True

    def _init_vol(self) -> None:
        self._cache: BlockCache = self.arena.cache
        self._block_rows = max(1, self._cache.block_bytes //
                               max(self.rowbytes, 1))
        self._n_blocks = -(-self.shape[0] // self._block_rows)
        self._resident: Dict[int, np.ndarray] = {}
        # one dirty bit per ROW (1 B/row bookkeeping — 1/64 of the 64 B
        # row data, like the DLL's volatile PREV redundancy): dirty-row
        # marking and the drain's unpin are single vectorized scatters
        # instead of per-block mask loops.  Invariant: a set bit's block
        # is resident (writes fault it in; eviction refuses pinned
        # blocks), so dropping a block never orphans dirty bits.
        self._dirty_rows = np.zeros(self.shape[0], bool)
        self._spill: Optional[np.ndarray] = None
        # crash() disarms faulting: volatile state is GONE, and reads
        # must see zeros (the unpaged contract) until reopen/load
        # re-authorizes reading the persistent bytes
        self._armed = True

    # -- pool state --------------------------------------------------------
    @property
    def paged_active(self) -> bool:
        """False once a full-``.vol`` consumer forced a spill."""
        return self._spill is None

    @property
    def total_blocks(self) -> int:
        return self._n_blocks

    @property
    def vol(self):
        # full-array access: correctness fallback for unconverted
        # consumers — materializes once and leaves paged mode
        if self._spill is None:
            self._materialize_spill()
        return self._spill

    @vol.setter
    def vol(self, value) -> None:
        self._spill = value

    def _reset_blocks(self, armed: bool = True) -> None:
        with self._cache.lock:
            self._drop_all()
            self._spill = None
            self._armed = armed

    def _drop_all(self) -> None:
        for bid in list(self._resident):
            self._cache.forget(self, bid, self._resident[bid].nbytes)
        self._resident.clear()
        self._dirty_rows[:] = False

    def _block_pinned(self, bid: int) -> bool:
        lo = bid * self._block_rows
        return bool(self._dirty_rows[lo:lo + self._block_rows].any())

    def _drop_block(self, bid: int) -> None:
        blk = self._resident.pop(bid, None)
        if blk is None:
            return
        self._cache.forget(self, bid, blk.nbytes)

    def _get_block(self, bid: int) -> np.ndarray:
        blk = self._resident.get(bid)
        if blk is not None:
            self._cache.hit(self, bid)
            return blk
        lo = bid * self._block_rows
        hi = min(lo + self._block_rows, self.shape[0])
        blk = (self._assemble(lo, hi) if self._armed
               else np.zeros((hi - lo,) + self.shape[1:], self.dtype))
        if self._armed:
            # fault-path verification (DESIGN.md §13): a corrupt block
            # is rejected BEFORE admission, so no consumer ever reads
            # silently-rotted bytes through the cache
            self._verify_block(blk, lo, hi)
        self._resident[bid] = blk
        self._cache.admit(self, bid, blk.nbytes)
        return blk

    def _verify_block(self, blk: np.ndarray, lo: int, hi: int) -> None:
        """Check an assembled block against its sidecar checksums.
        Data rows and their sidecar lines always move in the same flush
        phase, so every flushed row has a matching reference and
        never-flushed rows carry the 0 sentinel and are skipped."""
        sc = self._integ
        if sc is None:
            return
        ref = self._integ_ref(lo, hi)
        live = ref != 0
        if not live.any():
            return
        ck = sidecar_checksums(blk, sc.shape[1])
        bad = live & (ck != ref)
        if bad.any():
            rows = lo + np.nonzero(bad.any(axis=1))[0]
            raise CorruptLineError(self.name, rows,
                                   detail="paged fault verification")

    def _blk_loop(self, rows: np.ndarray):
        """Group `rows` by block; yield (bid, block, local rows within
        the block, positions into `rows`) per touched block."""
        bids = rows // self._block_rows
        order = np.argsort(bids, kind="stable")
        sbids = bids[order]
        srows = rows[order]
        cuts = np.nonzero(np.diff(sbids))[0] + 1
        starts = np.concatenate(([0], cuts))
        ends = np.concatenate((cuts, [sbids.size]))
        for a, b in zip(starts, ends):
            bid = int(sbids[a])
            yield (bid, self._get_block(bid),
                   srows[a:b] - bid * self._block_rows, order[a:b])

    def _empty_at(self, col, n: int) -> np.ndarray:
        probe = np.empty((0,) + self.shape[1:], self.dtype)[:, col]
        return np.empty((n,) + probe.shape[1:], self.dtype)

    def _flat_gather(self, rows: np.ndarray):
        """(stacked, flat) such that ``stacked[flat]`` is the rows' data.
        One fancy index over a concatenation of the touched blocks
        instead of a per-block Python loop — the write-set drain gathers
        thousands of scattered rows per epoch, and per-block loop
        overhead would tax the flush path the --paged-parity gate
        bounds.  Assumes the cache lock is held."""
        bids = rows // self._block_rows
        ub, inv = np.unique(bids, return_inverse=True)
        blocks = [self._get_block(int(b)) for b in ub]
        if len(blocks) == 1:
            return blocks[0], rows - ub[0] * self._block_rows
        offs = np.zeros(len(blocks), np.int64)
        np.cumsum([b.shape[0] for b in blocks[:-1]], out=offs[1:])
        stacked = np.concatenate(blocks)
        return stacked, offs[inv] + (rows - ub[inv] * self._block_rows)

    # -- row accessors (the _RowAccess API, block-routed) ------------------
    def read_rows(self, rows: np.ndarray) -> np.ndarray:
        rows = np.asarray(rows, np.int64)
        if self._spill is not None:
            return self._spill[rows]
        if rows.size == 0:
            return np.empty((0,) + self.shape[1:], self.dtype)
        with self._cache.lock:
            stacked, flat = self._flat_gather(rows)
            return stacked[flat]

    def read_at(self, rows: np.ndarray, col) -> np.ndarray:
        rows = np.asarray(rows, np.int64)
        if self._spill is not None:
            return self._spill[rows, col]
        if rows.size == 0:
            return self._empty_at(col, 0)
        with self._cache.lock:
            stacked, flat = self._flat_gather(rows)
            return stacked[flat, col]

    def read_one(self, row: int, col: int) -> int:
        if self._spill is not None:
            return int(self._spill[row, col])
        with self._cache.lock:
            bid, off = divmod(int(row), self._block_rows)
            return int(self._get_block(bid)[off, col])

    def read_col(self, col) -> np.ndarray:
        # whole-column read: faults every block THROUGH the cache, so
        # residency stays bounded — the full-recovery fallback path
        if self._spill is not None:
            return self._spill[:, col]
        return self.read_at(np.arange(self.shape[0], dtype=np.int64), col)

    def write_rows(self, rows: np.ndarray, vals) -> None:
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return
        if self._spill is not None:
            self._spill[rows] = vals
            return
        v = np.broadcast_to(np.asarray(vals, self.dtype),
                            (rows.size,) + self.shape[1:])
        with self._cache.lock:
            # dirty bits BEFORE the block loop: each admission inside
            # the loop may evict, and an already-written block of THIS
            # call must be pinned by then or its writes vanish
            self._dirty_rows[rows] = True
            for bid, blk, local, pos in self._blk_loop(rows):
                blk[local] = v[pos]

    def write_at(self, rows: np.ndarray, col, vals) -> None:
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return
        if self._spill is not None:
            self._spill[rows, col] = vals
            return
        shape = self._empty_at(col, rows.size).shape
        v = np.broadcast_to(np.asarray(vals, self.dtype), shape)
        with self._cache.lock:
            self._dirty_rows[rows] = True     # pin before any admission
            for bid, blk, local, pos in self._blk_loop(rows):
                blk[local, col] = v[pos]

    # -- write-back bookkeeping --------------------------------------------
    def _note_flushed(self, rows: np.ndarray) -> None:
        """Rows persisted home by the write-set drain (refault-visible):
        clear their dirty bits so their blocks become evictable."""
        if self._spill is not None:
            return
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return
        with self._cache.lock:
            self._dirty_rows[rows] = False

    def _note_persisted(self, rows: np.ndarray) -> None:
        """Direct (epoch-less) persist wrote these rows home — as
        durable as a flush."""
        self._note_flushed(rows)

    def _note_persisted_range(self, lo: int, hi: int) -> None:
        self._note_persisted(np.arange(lo, hi, dtype=np.int64))

    # -- flush-source gathers ----------------------------------------------
    def _gather(self, rows: np.ndarray) -> np.ndarray:
        return self.read_rows(rows)

    def _gather_range(self, lo: int, hi: int) -> np.ndarray:
        return self.read_rows(np.arange(lo, hi, dtype=np.int64))

    def _pack_source(self, rows: np.ndarray):
        g = self.read_rows(rows)
        return g, np.arange(rows.size, dtype=np.int64)

    # -- spill fallback ----------------------------------------------------
    def _materialize_spill(self) -> None:
        with self._cache.lock:
            if self._spill is not None:
                return
            full = (self._assemble(0, self.shape[0]) if self._armed
                    else np.zeros(self.shape, self.dtype))
            # clean resident blocks are value-equal to the assembly;
            # only dirty rows hold newer (unflushed) state
            for r in np.nonzero(self._dirty_rows)[0]:
                bid, off = divmod(int(r), self._block_rows)
                full[r] = self._resident[bid][off]
            self._cache.spills += 1
            self._drop_all()
            self._spill = full


class PagedRegion(_BlockPool, Region):
    """Single-arena paged region: blocks assemble from this arena's
    home slots."""

    def _assemble(self, lo: int, hi: int) -> np.ndarray:
        blk = np.array(self._pview()[lo:hi])
        self.arena.synth_read(blk.nbytes)
        return blk

    def _integ_ref(self, lo: int, hi: int) -> np.ndarray:
        """Sidecar checksums for rows [lo, hi) (sidecars are never
        paged, so this is a plain persistent read)."""
        return np.array(self._integ._pview()[lo:hi])

    def load(self) -> None:
        """Lazy reload: drop every block.  The post-crash working set
        faults back in on demand — recovery reads what it touches."""
        self._reset_blocks()

    def _crash_reset(self) -> None:
        self._reset_blocks(armed=False)


class PagedShardedRegion(_BlockPool, ShardedRegion):
    """Sharded paged region: ONE block pool at the sharded level (the
    cache replaces the one full-shape volatile image); each fault
    gathers its rows from the owning shards' slices."""

    def _assemble(self, lo: int, hi: int) -> np.ndarray:
        blk = np.empty((hi - lo,) + self.shape[1:], self.dtype)
        grows = np.arange(lo, hi, dtype=np.int64)
        sh = self.shard_of[grows]
        for s in np.unique(sh):
            pos = np.nonzero(sh == s)[0]
            sl = self.slices[s]
            lr = self.local_of[grows[pos]]
            blk[pos] = sl._pview()[lr]
            self.arena.shards[s].synth_read(int(pos.size) * self.rowbytes)
        return blk

    def _integ_ref(self, lo: int, hi: int) -> np.ndarray:
        sc = self._integ
        ref = np.empty((hi - lo,) + sc.shape[1:], sc.dtype)
        grows = np.arange(lo, hi, dtype=np.int64)
        sh = sc.shard_of[grows]
        for s in np.unique(sh):
            pos = np.nonzero(sh == s)[0]
            sl = sc.slices[s]
            lr = sc.local_of[grows[pos]]
            ref[pos] = sl._pview()[lr]
        return ref

    # slice gathers / notes route here with GLOBAL row ids
    def _vol_rows(self, grows: np.ndarray) -> np.ndarray:
        return self.read_rows(grows)

    def _pack_source_global(self, grows: np.ndarray):
        g = self.read_rows(grows)
        return g, np.arange(grows.size, dtype=np.int64)

    def _note_flushed_global(self, grows: np.ndarray) -> None:
        self._note_flushed(grows)

    def _note_persisted_global(self, grows: np.ndarray) -> None:
        self._note_persisted(grows)

    def load(self, concurrency: int = 1) -> None:
        self._reset_blocks()

    def load_shard(self, s: int) -> None:
        # reload == discard volatile and defer to faults; idempotent
        # across the per-shard loop callers drive
        self._reset_blocks()

    def _crash_reset(self) -> None:
        self._reset_blocks(armed=False)
