"""Partly-persistent doubly linked list (paper §IV-C).

Array-backed (indices as pointers) so operations vectorize over batches —
the TPU-framework adaptation of the paper's single-threaded op loop
(DESIGN.md §2): framework call sites (the paged-KV LRU/free list) naturally
operate on batches of pages.

Layout mirrors the paper's Listing 1 exactly at the flush-unit level:

* partly persistent: one 64 B row per node = DATA (7 x i64 = 56 B) + NEXT
  (8 B).  PREV is volatile only.  Appending a node flushes 1 line.
* fully persistent: one 128 B row per node = DATA + NEXT + PREV + pad
  (the paper's 64-aligned struct with prev spilling to a second line).
  Appending flushes 2 lines, plus the successor's prev line on links.

Volatile redundancy (all DERIVABLE): PREV array, TAIL, free-slot list, and
an order ring (the list order materialized for O(1) batched head pops —
the LRU eviction path).

Reconstruction (paper §IV-C3, parallelized per §V-F's suggestion): binary
lifting over NEXT — jump tables next^(2^k); node-at-position for all
positions computed vectorized in O(N log N); PREV by one scatter; TAIL =
last; free slots = complement.  This is the TPU/vector-native equivalent of
the paper's sequential forward walk.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.core import reconstruct as rec
from repro.core.arena import (Arena, CorruptLineError, FlushStats,
                              SNAP_SLOTS, SNAP_WORDS, snap_record_pack,
                              snap_record_parse, snapshot_enabled)
from repro.core.recovery import ChainSnapshot, chain_method, chain_order

NULL = -1
DATA_WORDS = 7

# Sharded-arena routing (DESIGN.md §7): node rows stripe block-cyclically
# in segments of 64 — appends fill a segment on one shard then roll to
# the next, so a batch's flush fans out across shard files while rows
# within a segment still coalesce lines.
SHARD_SEG = 64

# header slots
H_FLAG, H_HEAD, H_COUNT, H_TAIL, H_FREE_HEAD, H_FRESH = range(6)


class DoublyLinkedList:
    """mode: "partly" | "full"."""

    def __init__(self, arena: Arena, capacity: int, mode: str = "partly",
                 name: str = "dll", chain_method: str = "auto",
                 snapshot: Optional[bool] = None):
        assert mode in ("partly", "full")
        self.mode = mode
        self.capacity = capacity
        # chain-ranking strategy for every NEXT-chain walk (to_list and
        # the recovery reconstructor): "auto" flips from pointer
        # doubling to contraction list ranking at the cache crossover
        # (core.recovery.chain_method, DESIGN.md §8)
        self.chain_method = chain_method
        self.arena = arena
        row = 8 if mode == "partly" else 16
        self._row = row
        self.nodes = arena.regions.get(f"{name}.nodes") or arena.region(
            f"{name}.nodes", np.int64, (capacity, row),
            router=("seg", SHARD_SEG))
        self.header = arena.regions.get(f"{name}.header") or arena.region(
            f"{name}.header", np.int64, (1, 8))
        # volatile redundancy
        self.prev = np.full(capacity, NULL, np.int64)
        self._free: list[int] = []
        self._ring = np.empty(capacity * 2, np.int64)  # order ring
        self._r0 = 0
        self._r1 = 0
        # incremental order snapshots (DESIGN.md §10): a persisted mirror
        # of the order ring plus a 4-slot sealed-record ring, appended to
        # by a commit-time provider.  Degrades to OFF when the arena's
        # layout was finalized without the snapshot regions (an older
        # image, or REPRO_SNAPSHOT=0 at creation).
        snap_on = snapshot_enabled(snapshot)
        self.snapring = arena.regions.get(f"{name}.snapring")
        self.snaprec = arena.regions.get(f"{name}.snaprec")
        if snap_on and self.snapring is None and not arena._layout_final:
            self.snapring = arena.region(f"{name}.snapring", np.int64,
                                         (capacity * 2,),
                                         router=("seg", SHARD_SEG))
            self.snaprec = arena.region(f"{name}.snaprec", np.int64,
                                        (SNAP_SLOTS, SNAP_WORDS))
        self.snapshot = snap_on and self.snapring is not None
        if self.snapshot:
            self._snap_dirty = np.zeros(capacity * 2, bool)
            self._snap_seq = 0
            self._snap_resync = True   # first drain mirrors the window
            self._snap_last = None     # (r0, r1, count) at last emit
            arena.add_snapshot_provider(self._snap_emit)

    @staticmethod
    def layout(capacity: int, mode: str = "partly", name: str = "dll",
               snapshot: Optional[bool] = None):
        row = 8 if mode == "partly" else 16
        out = {f"{name}.nodes": (np.int64, (capacity, row),
                                 ("seg", SHARD_SEG)),
               f"{name}.header": (np.int64, (1, 8))}
        if snapshot_enabled(snapshot):
            out[f"{name}.snapring"] = (np.int64, (capacity * 2,),
                                       ("seg", SHARD_SEG))
            out[f"{name}.snaprec"] = (np.int64, (SNAP_SLOTS, SNAP_WORDS))
        return out

    # ------------- views over the node rows -------------
    @property
    def data(self) -> np.ndarray:
        # full-array view — on a paged arena this SPILLS the region;
        # batch consumers should use data_rows()
        return self.nodes.vol[:, :DATA_WORDS]

    @property
    def next(self) -> np.ndarray:
        return self.nodes.vol[:, DATA_WORDS]

    def data_rows(self, ids: np.ndarray) -> np.ndarray:
        """DATA words of the given node ids — block-routed on a paged
        arena (the ``.data`` property would materialize the region)."""
        return np.asarray(self.nodes.read_at(np.asarray(ids, np.int64),
                                             slice(0, DATA_WORDS)))

    def _next_col(self) -> np.ndarray:
        """NEXT column for a full chain walk: a paged nodes region reads
        the column through the block cache (residency stays bounded by
        eviction); resident regions return the live view."""
        n = self.nodes
        if getattr(n, "paged_active", False):
            return np.asarray(n.read_col(DATA_WORDS))
        return n.vol[:, DATA_WORDS]

    @property
    def head(self) -> int:
        return int(self.header.vol[0, H_HEAD])

    @property
    def tail(self) -> int:
        return int(self.header.vol[0, H_TAIL])

    @property
    def count(self) -> int:
        return int(self.header.vol[0, H_COUNT])

    # ------------- allocation -------------
    def _alloc(self, m: int) -> np.ndarray:
        ids = []
        take = min(len(self._free), m)
        if take:
            ids.extend(self._free[-take:])
            del self._free[-take:]
        fresh_needed = m - take
        fresh0 = int(self.header.vol[0, H_FRESH])
        if fresh_needed:
            if fresh0 + fresh_needed > self.capacity:
                raise MemoryError("dll arena exhausted")
            ids.extend(range(fresh0, fresh0 + fresh_needed))
            self.header.vol[0, H_FRESH] = fresh0 + fresh_needed
        return np.asarray(ids, np.int64)

    # ------------- operations -------------
    def append_batch(self, values: np.ndarray) -> np.ndarray:
        """Append m nodes at the tail.  values: (m, 7) int64.  Returns ids."""
        with self.arena.epoch():
            return self._append_batch(values)

    def _append_batch(self, values: np.ndarray) -> np.ndarray:
        m = len(values)
        fresh0 = int(self.header.vol[0, H_FRESH])
        ids = self._alloc(m)
        hv = self.header.vol[0]
        self.nodes.write_at(ids, slice(0, DATA_WORDS), values)
        # chain: old_tail -> ids[0] -> ids[1] ... -> NULL
        self.nodes.write_at(ids[:-1], DATA_WORDS, ids[1:])
        self.nodes.write_at(ids[-1:], DATA_WORDS, NULL)
        self.prev[ids[1:]] = ids[:-1]
        old_tail = int(hv[H_TAIL]) if hv[H_COUNT] > 0 else NULL
        if old_tail != NULL:
            self.nodes.write_at(np.asarray([old_tail]), DATA_WORDS, ids[0])
            self.prev[ids[0]] = old_tail
        else:
            hv[H_HEAD] = ids[0]
            self.prev[ids[0]] = NULL
        hv[H_TAIL] = ids[-1]
        hv[H_COUNT] += m
        hv[H_FLAG] = 1
        if self.mode == "full":
            self.nodes.write_at(ids[1:], DATA_WORDS + 1, ids[:-1])
            self.nodes.write_at(ids[:1], DATA_WORDS + 1, old_tail)
        # ring
        n = len(ids)
        if self._r1 + n > self._ring.size:
            self._compact_ring()
        self._ring[self._r1:self._r1 + n] = ids
        self._r1 += n
        if self.snapshot:
            self._snap_dirty[self._r1 - n:self._r1] = True
        # ---- mark dirty (flushed once at epoch close) ----
        # fresh-range ids, then free-list reuses plus the old tail's
        # pointer rewrite: two marks (the write set's marks /
        # saved_lines counters count them apart)
        new = ids[ids >= fresh0]
        if new.size:
            self.nodes.mark_rows(new)
        reused = ids[ids < fresh0]
        dirty = reused if old_tail == NULL \
            else np.concatenate([[old_tail], reused])
        if dirty.size:
            self.nodes.mark_rows(dirty)
        self.header.mark_rows(np.array([0]))
        return ids

    def pop_front_batch(self, m: int) -> np.ndarray:
        """Remove the m oldest nodes (LRU eviction).  Returns their ids."""
        with self.arena.epoch():
            return self._pop_front_batch(m)

    def _pop_front_batch(self, m: int) -> np.ndarray:
        hv = self.header.vol[0]
        m = min(m, int(hv[H_COUNT]))
        if m == 0:
            return np.empty(0, np.int64)
        ids = self._ring_pop(m)
        new_head = self.nodes.read_one(int(ids[-1]), DATA_WORDS)
        hv[H_HEAD] = new_head
        hv[H_COUNT] -= m
        if new_head == NULL:
            hv[H_TAIL] = NULL
        else:
            self.prev[new_head] = NULL
        self._free.extend(ids.tolist())
        # partly: only the header changes persistently (the popped rows are
        # unreachable from HEAD, so their bytes are dead — zero row flushes).
        if self.mode == "full":
            # fully persistent must clear new_head's prev line
            if new_head != NULL:
                self.nodes.write_at(np.asarray([new_head]),
                                    DATA_WORDS + 1, NULL)
                self.nodes.mark_rows(np.array([new_head]))
        self.header.mark_rows(np.array([0]))
        return ids

    def delete_batch(self, ids: np.ndarray) -> None:
        """Unlink an arbitrary batch of node ids (vectorized rounds: each
        round unlinks ids whose predecessor is not itself being deleted).
        All rounds share one epoch: a predecessor rewritten in several
        rounds flushes once."""
        with self.arena.epoch():
            self._delete_batch(np.asarray(ids, np.int64))

    def _delete_batch(self, ids: np.ndarray) -> None:
        pending = set(ids.tolist())
        hv = self.header.vol[0]
        while pending:
            arr = np.fromiter(pending, np.int64)
            pred = self.prev[arr]
            ready = ~np.isin(pred, arr)
            batch = arr[ready]
            if batch.size == 0:  # adjacent chain; peel one end
                batch = arr[:1]
            nxt = np.asarray(self.nodes.read_at(batch, DATA_WORDS))
            prv = self.prev[batch]
            # batched column writes: within a round each node has a
            # DISTINCT predecessor and successor (a list node has one of
            # each, and nodes whose predecessor is also being deleted
            # wait for a later round), so the scatters are conflict-free
            link = prv != NULL
            if link.any():
                self.nodes.write_at(prv[link], DATA_WORDS, nxt[link])
            for i in np.nonzero(~link)[0]:
                hv[H_HEAD] = nxt[i]
            has_nx = nxt != NULL
            if has_nx.any():
                self.prev[nxt[has_nx]] = prv[has_nx]
                if self.mode == "full":
                    self.nodes.write_at(nxt[has_nx], DATA_WORDS + 1,
                                        prv[has_nx])
            for i in np.nonzero(~has_nx)[0]:
                hv[H_TAIL] = prv[i]
            dirty = [prv[link]]
            if self.mode == "full":
                dirty.append(nxt[has_nx])
            dirty = np.concatenate(dirty)
            hv[H_COUNT] -= batch.size
            self._free.extend(batch.tolist())
            pending.difference_update(batch.tolist())
            if dirty.size:
                self.nodes.mark_rows(dirty)
        self.header.mark_rows(np.array([0]))
        self._ring_invalidate(ids)

    # ------------- ring helpers -------------
    def _compact_ring(self) -> None:
        live = self._ring[self._r0:self._r1]
        self._ring[: live.size] = live
        self._r0, self._r1 = 0, live.size
        if self.snapshot:
            # every slot moved: the persisted mirror diverges wholesale
            self._snap_resync = True

    def _ring_pop(self, m: int) -> np.ndarray:
        out = np.empty(m, np.int64)
        got = 0
        while got < m:
            cand = self._ring[self._r0]
            self._r0 += 1
            if cand >= 0:
                out[got] = cand
                got += 1
        return out

    def _ring_invalidate(self, ids: np.ndarray) -> None:
        window = self._ring[self._r0:self._r1]
        mask = np.isin(window, ids)
        window[mask] = NULL
        if self.snapshot:
            self._snap_dirty[self._r0 + np.nonzero(mask)[0]] = True

    # ------------- traversal / verification -------------
    def to_list(self) -> np.ndarray:
        """Materialize list order from NEXT (the shared chain_order
        primitive — doubling or contraction per ``chain_method``, never
        a scalar walk)."""
        return chain_order(self._next_col(), self.head, self.count,
                           method=self.chain_method)

    def order(self) -> np.ndarray:
        """List order materialized from the volatile ring (no chain
        traversal at all): appends push at the back, pops consume the
        front, deletes punch NULL holes — the surviving window IS the
        list order.  Recovery consumers (the paged-KV allocator) read
        this right after reconstruction."""
        window = self._ring[self._r0:self._r1]
        return window[window != NULL].copy()

    # ------------- incremental order snapshots (DESIGN.md §10) -------
    def _snap_emit(self):
        """Commit-time snapshot provider: mirror the ring slots dirtied
        since the last commit and seal one record line naming the window
        and the generation this commit targets.  Slots never move
        between compactions (appends write fresh slots, deletes punch
        NULLs in place, pops only advance the record's r0), so the
        per-commit delta is a few lines regardless of list size.

        Idempotent: a flush with nothing newly dirty and an unchanged
        window emits nothing, so the writeset can drain providers at
        every epoch flush (not just commits) without a commit's own
        flush adding bytes beyond the preceding epoch's — the
        inter-shard commit-window byte-identity invariant."""
        out = []
        if self._snap_resync:
            self._snap_dirty[:] = False
            self._snap_dirty[self._r0:self._r1] = True
            self._snap_resync = False
        dirty = np.nonzero(self._snap_dirty)[0]
        state = (self._r0, self._r1, int(self.header.vol[0, H_COUNT]))
        if not dirty.size and state == self._snap_last:
            return out
        self._snap_last = state
        if dirty.size:
            self.snapring.vol[dirty] = self._ring[dirty]
            out.append((self.snapring, dirty))
            self._snap_dirty[:] = False
        seq = self._snap_seq
        self._snap_seq += 1
        slot = seq % SNAP_SLOTS
        self.snaprec.vol[slot] = snap_record_pack(
            self.arena.generation + 1, seq, self._r0, self._r1,
            int(self.header.vol[0, H_COUNT]))
        out.append((self.snaprec, np.asarray([slot], np.int64)))
        return out

    # ------------- crash / reconstruction -------------
    def reconstruct(self) -> None:
        """Rebuild all volatile redundancy from persistent fields only
        (paper §IV-C3).  Thin shim over the registered pure reconstructor
        — recovery paths route through core.recovery.RecoveryManager,
        which loads the regions once and times the stage."""
        self.header.load()
        self.nodes.load()
        if self.snapshot:
            self.snapring.load()
            self.snaprec.load()
        rec.get("pstruct.dll")(self)

    def flush_stats(self) -> FlushStats:
        return self.arena.stats


def _snap_records(snaprec) -> list:
    """Intact records in the persisted record ring, any order."""
    return [r for r in (snap_record_parse(snaprec.vol[s])
                        for s in range(SNAP_SLOTS)) if r is not None]


def _snap_resume(d) -> None:
    """Post-recovery provider state: resume the record sequence past
    every intact slot (so newest-by-seq selection keeps working across
    restarts) and re-mirror the whole window at the next commit (the
    rebuilt ring starts at slot 0, wherever the mirror's window was)."""
    recs = _snap_records(d.snaprec)
    d._snap_seq = (max(r[1] for r in recs) + 1) if recs else 0
    d._snap_dirty[:] = False
    d._snap_resync = True
    d._snap_last = None


def _snap_candidate(d, count: int) -> Optional[ChainSnapshot]:
    """Candidate order from the newest intact record whose generation is
    committed: the persisted window's live slots, plus a bounded local
    walk along NEXT past the snapshot tail (the suffix of appends the
    record predates), minus any front overhang (pops since the record).
    Every failure mode returns None — chain_order's verify-always pass
    is what makes adoption safe, this only has to be cheap."""
    committed = d.arena.header_generation()
    best = None
    for r in _snap_records(d.snaprec):
        if r[0] > committed:        # sealed by a generation that never
            continue                # committed (crash inside the window)
        if best is None or r[1] > best[1]:
            best = r
    if best is None:
        return None
    _, _, r0, r1, _, _ = best
    if not (0 <= r0 <= r1 <= d.snapring.shape[0]):
        return None
    window = d.snapring.vol[r0:r1]
    base = window[window != NULL]
    if base.size == 0 or ((base < 0) | (base >= d.capacity)).any():
        return None
    if getattr(d.nodes, "paged_active", False):
        # bounded scalar suffix walk: fault only the blocks it steps on
        def read_next(cur: int) -> int:
            return d.nodes.read_one(cur, DATA_WORDS)
    else:
        nxt = d.next

        def read_next(cur: int) -> int:
            return int(nxt[cur])
    suffix = []
    cur = int(base[-1])
    while len(suffix) < count:
        nx = read_next(cur)
        if nx < 0 or nx >= d.capacity:
            break
        suffix.append(nx)
        cur = nx
    cand = np.concatenate([base, np.asarray(suffix, np.int64)]) \
        if suffix else np.asarray(base, np.int64)
    if cand.size < count:
        return None
    return ChainSnapshot(cand[cand.size - count:], replayed=len(suffix))


def _gather_verify(nodes, head: int, count: int, cand: np.ndarray,
                   n: int) -> bool:
    """Exact mirror of recovery._snapshot_verify, but gathering NEXT of
    only the candidate rows through the block cache — the verify that
    makes snapshot adoption safe costs O(working set) faults instead of
    a full-column read on a paged arena."""
    if count is None or cand.size != count:
        return False
    if int(cand[0]) != int(head):
        return False
    if ((cand < 0) | (cand >= n)).any():
        return False
    if count > 1 and not np.array_equal(
            np.asarray(nodes.read_at(cand[:-1], DATA_WORDS)), cand[1:]):
        return False
    return True


def _salvage_bad_rows(arena, region) -> np.ndarray:
    """Rows of a structure's primary region failing their sidecar
    checksums (empty when the arena carries no integrity layer) —
    the shared salvage-mode probe (DESIGN.md §13)."""
    if not getattr(arena, "integrity", False):
        return np.empty(0, np.int64)
    return arena.verify_region(region)


@rec.register("pstruct.dll")
def _reconstruct_dll(d: "DoublyLinkedList") -> dict:
    """Pure rebuild of the DLL's volatile redundancy from its (already
    loaded) persistent fields: PREV by one scatter off the vectorized
    chain order, TAIL = last, free slots = complement, order ring =
    chain order (paper §IV-C3, parallelized per §V-F)."""
    hv = d.header.vol[0]
    if hv[H_FLAG] != 1:
        # Flag bit unset: nothing was ever flushed — recover as empty
        # (the paper's "safely initialized" check, §IV-C3).
        hv[:] = 0
        hv[H_HEAD] = NULL
        hv[H_TAIL] = NULL
    count = int(hv[H_COUNT])
    head = int(hv[H_HEAD])
    d.prev = np.full(d.capacity, NULL, np.int64)
    snap_on = getattr(d, "snapshot", False)
    if count == 0:
        hv[H_TAIL] = NULL
        hv[H_FRESH] = 0
        d._free = []
        d._r0 = d._r1 = 0
        if snap_on:
            _snap_resume(d)
        return {"mode": d.mode, "count": 0}
    # The committed COUNT bounds the walk: rows appended by a torn epoch
    # (data flushed, header not) stay unreachable.
    method = getattr(d, "chain_method", "auto")
    salvage = getattr(d.arena, "_salvage", False)
    bad = _salvage_bad_rows(d.arena, d.nodes) if salvage \
        else np.empty(0, np.int64)
    dropped = 0
    if bad.size:
        # salvage walk (DESIGN.md §13): corrupt rows terminate the
        # chain — the recovered list is the maximal committed prefix
        # whose every node verifies.  Reads the committed persistent
        # image directly (never through the block cache, whose fault
        # verification would reject whole blocks a corrupt neighbor
        # shares with healthy prefix rows).
        nxt = np.asarray(d.arena._pimage(d.nodes))[:, DATA_WORDS]
        badset = set(bad.tolist())
        seen: set = set()
        prefix: list[int] = []
        cur = head
        while (len(prefix) < count and 0 <= cur < d.capacity
               and cur not in badset and cur not in seen):
            prefix.append(cur)
            seen.add(cur)
            cur = int(nxt[cur])
        order = np.asarray(prefix, np.int64)
        dropped = count - int(order.size)
        snap = None
        if order.size == 0:
            hv[:] = 0
            hv[H_HEAD] = NULL
            hv[H_TAIL] = NULL
            d._free = []
            d._r0 = d._r1 = 0
            if snap_on:
                _snap_resume(d)
            return {"mode": d.mode, "count": 0, "quarantined": True,
                    "quarantined_rows": dropped}
        count = int(order.size)
        hv[H_COUNT] = count
    else:
        snap = _snap_candidate(d, count) if snap_on else None
        if getattr(d.nodes, "paged_active", False) and snap is not None \
                and _gather_verify(d.nodes, head, count, snap.candidate,
                                   d.capacity):
            # paged fast path: adopt the verified snapshot WITHOUT
            # touching the full NEXT column — recovery faults only the
            # candidate rows' blocks, so its cost tracks the working set
            snap.outcome = "snapshot"
            order = snap.candidate.astype(np.int64, copy=True)
        else:
            try:
                order = chain_order(d._next_col(), head, count,
                                    method=method, snapshot=snap)
            except (RuntimeError, ValueError) as e:
                if salvage:
                    # structurally impossible chain (cycle / short walk)
                    # with no sidecar to localize it: the whole
                    # structure is untrusted
                    raise CorruptLineError(
                        d.nodes.name, np.empty(0, np.int64),
                        detail=f"chain rebuild: {e}") from e
                raise
    d.prev[order[1:]] = order[:-1]
    hv[H_TAIL] = order[-1]
    live = np.zeros(d.capacity, bool)
    live[order] = True
    # quarantined rows are neither live nor reusable: keeping them out
    # of the free list stops a later insert from resurrecting rot
    if bad.size:
        live[bad[bad < d.capacity]] = True
    # Fresh-water mark: everything at/above the max live id is fresh.
    fresh = int(order.max()) + 1
    hv[H_FRESH] = fresh
    free = np.nonzero(~live[:fresh])[0]
    d._free = free.tolist()
    d._ring = np.empty(d.capacity * 2, np.int64)
    d._ring[:count] = order
    d._r0, d._r1 = 0, count
    if d.mode == "full":
        # pure-reconstructor PREV rebuild stays UNMARKED (derivable);
        # on a paged arena these rows pin their blocks dirty until a
        # later epoch flushes them — the documented full-mode cost
        d.nodes.write_at(order[1:], DATA_WORDS + 1, order[:-1])
        d.nodes.write_at(order[:1], DATA_WORDS + 1, NULL)
    detail = {"mode": d.mode, "count": count,
              "chain": chain_method(d.capacity, count, method)}
    if dropped:
        detail.update(degraded=True, quarantined_rows=dropped,
                      chain="salvage")
    if snap_on:
        # outcome: "snapshot" (seeded, suffix-only replay) or the full
        # fallback rank the verify pass forced; replayed = rows walked
        detail["chain"] = snap.outcome if snap is not None \
            else detail["chain"]
        detail["replayed"] = snap.replayed if snap is not None \
            and snap.outcome == "snapshot" else count
        _snap_resume(d)
    return detail


def order_from_next(nxt: np.ndarray, head: int, count: int) -> np.ndarray:
    """Back-compat alias for the shared primitive (core.recovery)."""
    return chain_order(nxt, head, count)
