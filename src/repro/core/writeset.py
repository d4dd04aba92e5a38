"""Write-set / epoch-flush layer (paper §V-E, MOD-style minimal ordering).

Structures no longer flush rows as they touch them.  Instead each logical
operation opens an *epoch* (``Arena.epoch()``); every mutation marks its
dirty rows into the arena's :class:`WriteSet`; when the outermost epoch
closes (or ``Arena.commit`` runs) the write set flushes ONCE:

* rows marked several times within the epoch are deduplicated;
* adjacent dirty rows coalesce into distinct 64 B lines exactly once
  across the whole operation — not once per ``persist_rows`` call;
* data regions flush before metadata (header) regions, extending the
  arena's data-before-metadata commit ordering into the epoch itself: a
  crash mid-epoch leaves the previous header state reachable;
* large row gathers can route through the Pallas ``pack_flush`` kernel
  (tile-aligned staging buffer) when the arena enables it.

Accounting: :class:`~repro.core.arena.FlushStats` gains per-epoch dedup
counters.  ``saved_lines`` is the difference between what per-call
accounting *would* have charged (one distinct-line count per mark, the
pre-refactor behaviour) and what the batched epoch flush actually
charged — the paper's redundant-flush overhead, measured directly.

``DigestWriteSet`` is the file-granularity sibling used by
``ckpt/manager.py``: leaves whose content digest is unchanged since the
last flush are dropped from the write set ("don't persist what didn't
change"), unifying the checkpoint manager's incremental mode with the
row-granularity tracker here.

``ShardedWriteSet`` coordinates one WriteSet per arena shard
(DESIGN.md §7): an epoch close flushes every shard's DATA regions in
the shard pool, barriers, then flushes every shard's METADATA regions —
the data-before-metadata ordering is global across shards, so a
structure whose header landed on shard 0 can never expose rows that a
slower shard 3 hadn't flushed yet.  Per-shard line/dedup accounting
stays in each shard's FlushStats and rolls up through
``ShardedArena.stats``.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["WriteSet", "ShardedWriteSet", "DigestWriteSet"]


class WriteSet:
    """Per-arena dirty-row tracker with epoch-batched flushing."""

    def __init__(self, arena):
        self.arena = arena
        # region name -> list of (unique rows, per-call line cost)
        self._pending: Dict[str, List[Tuple[np.ndarray, int]]] = {}

    # ------------------------------------------------------------- mark
    def mark(self, region, rows: np.ndarray) -> None:
        """Record dirty rows of `region`; flushed at epoch close."""
        rows = np.unique(np.asarray(rows, np.int64))
        if rows.size == 0:
            return
        if getattr(region, "snap", False) or getattr(region, "jrnl", False):
            # snapshot and journal regions stay out of the mark/saved/
            # dedup ledger — their lines land in FlushStats.snapshot_lines
            # / journal_lines at drain
            self._pending.setdefault(region.name, []).append((rows, 0))
            return
        would = self.arena._rows_line_count(region.offset, region.rowbytes,
                                            rows)
        self._pending.setdefault(region.name, []).append((rows, would))
        self.arena.stats.marks += 1

    def __bool__(self) -> bool:
        return bool(self._pending)

    def discard(self) -> None:
        """Drop all pending marks without flushing (crash simulation)."""
        self._pending.clear()

    # ------------------------------------------------------------ flush
    def flush(self, include_meta: bool = True) -> None:
        """Flush all pending marks: dedup rows, account distinct lines
        once, copy volatile -> persistent.  Data regions first, then
        metadata regions (headers); ``include_meta=False`` flushes only
        the data half and DROPS the metadata marks — the crash-injection
        point used by recovery tests."""
        self._drain_snapshots()
        if not self._pending:
            return
        flushed = self.flush_phase(meta=False)
        if include_meta:
            flushed = self.flush_phase(meta=True) or flushed
        else:
            self._pending.clear()   # crash point: metadata marks are lost
        if flushed:
            self.arena.stats.epochs += 1

    def _drain_snapshots(self) -> None:
        """Ask each registered order-snapshot provider for its dirty
        snapshot rows at EVERY flush, so a mid-commit crash leaves
        byte-identical snapshot regions to a flushed-but-uncommitted
        crash (the inter-shard commit-window invariant).  Providers are
        idempotent — a flush with nothing newly dirty emits nothing —
        and a record sealed at a non-commit flush names a generation
        that may never commit; recovery's ``gen <= committed`` guard
        plus verify-always adoption makes that harmless (DESIGN.md
        §10)."""
        arena = self.arena
        if not arena._snap_providers:
            return
        for prov in arena._snap_providers:
            for region, rows in prov():
                self.mark(region, rows)

    def flush_phase(self, meta: bool) -> bool:
        """Flush only the data half (``meta=False``) or only the
        metadata half (``meta=True``) of the pending marks, leaving the
        other half pending.  The two-phase split is what lets
        ShardedWriteSet barrier ALL shards' data ahead of ANY shard's
        metadata.  Returns whether anything flushed; the caller owns the
        ``epochs`` counter."""
        arena = self.arena
        names = [n for n in self._pending if arena.regions[n].meta == meta]
        names.sort(key=lambda n: arena.regions[n].offset)
        flushed_any = False
        with arena.stall_scope():
            flushed_any = self._flush_names(names, arena)
        if flushed_any:
            arena._fence()      # one ordering point per barrier phase
        return flushed_any

    def _flush_names(self, names, arena) -> bool:
        flushed_any = False
        for name in names:
            region = arena.regions[name]
            marks = self._pending.pop(name)
            rows = np.unique(np.concatenate([r for r, _ in marks]))
            would_lines = sum(w for _, w in marks)
            marked_rows = sum(r.size for r, _ in marks)
            g = self._copy_rows(region, rows)
            # the drain IS where checksums ride the write set: data rows
            # and their sidecar lines move in the same phase, same fence
            arena._integrity_home(region, rows, data=g)
            if region.snap or region.jrnl:
                arena._account_rows(region.offset, region.rowbytes, rows,
                                    snap=region.snap, jrnl=region.jrnl)
                flushed_any = True
                continue
            before = arena.stats.lines
            arena._account_rows(region.offset, region.rowbytes, rows)
            actual = arena.stats.lines - before
            arena.stats.saved_lines += max(0, would_lines - actual)
            arena.stats.dedup_rows += marked_rows - rows.size
            flushed_any = True
        return flushed_any

    def _copy_rows(self, region, rows: np.ndarray) -> np.ndarray:
        pv = region._pview()
        if (self.arena.pack_flush_rows
                and rows.size >= self.arena.pack_flush_rows):
            vol, vrows = region._pack_source(rows)
            g = _pack_gather(vol, vrows)
        else:
            g = region._gather(rows)
        pv[rows] = g
        # the epoch drain IS the dirty-block write-back path: the rows
        # are home now, so a paged region may unpin their blocks
        region._note_flushed(rows)
        # returned so the integrity sidecar reuses the gather
        return g


class ShardedWriteSet:
    """Cross-shard epoch coordinator.

    Marks are buffered GLOBALLY per region — one cheap append per
    ``mark_rows`` call, exactly like the single-arena tracker — and the
    row->shard split happens ONCE per epoch at flush time, not once per
    mark (a B+Tree batch marks dozens of row sets per op; splitting
    each of them per shard would multiply the bookkeeping by the shard
    count).  The flush fans per-shard copy+account work out on the
    arena's shard pool in two phases: every shard's DATA regions land
    before ANY shard's metadata — the data-before-metadata barrier is
    global, so a header on shard 0 can never expose rows a slower shard
    3 hadn't flushed."""

    def __init__(self, arena):
        self.arena = arena
        # region name -> [row arrays, would_lines, marked]
        self._pending: Dict[str, list] = {}

    def mark(self, region, rows: np.ndarray) -> None:
        rows = np.unique(np.asarray(rows, np.int64))
        if rows.size == 0:
            return
        # the per-call counterfactual (what one accounting call per mark
        # would have charged) is computed on the GLOBAL rows with the
        # ONE shared counting rule — identical to the single-arena
        # bookkeeping, O(1) for line-aligned rows.  (For rows that are
        # line-aligned — every current region — the flushed-lines total
        # is shard-count-invariant too; sub-line rows split across
        # shards legitimately charge a shared line once PER FILE.)
        if getattr(region, "snap", False) or getattr(region, "jrnl", False):
            ent = self._pending.get(region.name)
            if ent is None:
                ent = self._pending[region.name] = [[], 0, 0]
            ent[0].append(rows)
            return
        from repro.core.arena import Arena
        would = Arena._rows_line_count(0, region.rowbytes, rows)
        ent = self._pending.get(region.name)
        if ent is None:
            ent = self._pending[region.name] = [[], 0, 0]
        ent[0].append(rows)
        ent[1] += would
        ent[2] += rows.size
        self.arena._local_stats.marks += 1

    def __bool__(self) -> bool:
        return bool(self._pending)

    def discard(self) -> None:
        self._pending.clear()

    def _drain_snapshots(self) -> None:
        arena = self.arena
        if not arena._snap_providers:
            return
        for prov in arena._snap_providers:
            for region, rows in prov():
                self.mark(region, rows)

    def flush(self, include_meta: bool = True) -> None:
        self._drain_snapshots()
        if not self._pending:
            return
        arena = self.arena
        flushed = self._flush_phase(meta=False)
        if include_meta:
            flushed = self._flush_phase(meta=True) or flushed
        else:
            self._pending.clear()   # crash point: metadata marks are lost
        if flushed:
            arena._local_stats.epochs += 1

    def flush_phase(self, meta: bool) -> bool:
        return self._flush_phase(meta)

    def _flush_phase(self, meta: bool) -> bool:
        arena = self.arena
        names = [n for n in self._pending
                 if arena.regions[n].meta == meta]
        names.sort(key=lambda n: n)
        if not names:
            return False
        # split each region's deduplicated rows per shard ONCE, then fan
        # the copy + per-shard line accounting out on the shard pool
        work: Dict[int, list] = {}      # shard -> [(slice, local rows)]
        region_rows = []
        for name in names:
            region = arena.regions[name]
            arrs, would, marked = self._pending.pop(name)
            rows = np.unique(np.concatenate(arrs)) if len(arrs) > 1 \
                else arrs[0]
            if not (region.snap or region.jrnl):
                # snap/jrnl lines stay off the ledger
                region_rows.append((region, rows, would, marked))
            for sl, local in region._split(rows):
                work.setdefault(sl.arena_index, []).append((sl, local))

        actual = {}                     # shard -> lines flushed there

        def flush_shard(s: int) -> None:
            shard = arena.shards[s]
            before = shard.stats.lines
            with shard.stall_scope():
                for sl, local in work[s]:
                    g = self._copy_rows(sl, local)
                    shard._account_rows(sl.offset, sl.rowbytes, local,
                                        snap=sl.snap, jrnl=sl.jrnl)
                    # per-shard sidecar write: a row's checksum shares
                    # its shard (same router), phase, and fence
                    shard._integrity_home(sl, local, data=g)
            actual[s] = shard.stats.lines - before

        shards = sorted(work)
        if len(shards) > 1:
            list(arena.pool().map(flush_shard, shards))
        else:
            flush_shard(shards[0])
        # region-level dedup/saved accounting against the global
        # counterfactual (rolls up through ShardedArena.stats)
        total_actual = sum(actual.values())
        would_total = sum(w for _, _, w, _ in region_rows)
        arena._local_stats.saved_lines += max(0, would_total - total_actual)
        arena._local_stats.dedup_rows += sum(
            m - r.size for _, r, _, m in region_rows)
        arena._fence()          # the global cross-shard ordering point
        return True

    def _copy_rows(self, sl, rows: np.ndarray) -> np.ndarray:
        pv = sl._pview()
        if (self.arena.pack_flush_rows
                and rows.size >= self.arena.pack_flush_rows):
            vol, vrows = sl._pack_source(rows)
            g = _pack_gather(vol, vrows)
        else:
            g = sl._gather(rows)
        pv[rows] = g
        # write-back point for paged parents (slice forwards globally)
        sl._note_flushed(rows)
        return g


def _pack_gather(vol: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Gather dirty rows through the Pallas pack kernel (tile-aligned
    staging buffer — the §V-E flush-unit path).  Rows are bit-cast to
    uint32 words so 64-bit payloads survive jax's default 32-bit mode."""
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    words = vol.reshape(vol.shape[0], -1).view(np.uint32)
    packed = kops.pack_rows(jnp.asarray(words), jnp.asarray(rows, jnp.int32))
    return np.ascontiguousarray(np.asarray(packed)).view(vol.dtype).reshape(
        (rows.size,) + vol.shape[1:])


class DigestWriteSet:
    """Content-digest dirty tracking for file-per-leaf persistence.

    ``dirty(key, digest, present)`` returns True when the leaf must be
    rewritten (digest changed, or the backing file is missing) and
    records the new digest; unchanged leaves are counted as deduplicated
    writes, mirroring ``WriteSet``'s row dedup at file granularity."""

    def __init__(self):
        self._digests: Dict[str, str] = {}
        self.skipped = 0
        self.written = 0

    def dirty(self, key: str, digest: str, present: bool = True) -> bool:
        clean = present and self._digests.get(key) == digest
        self._digests[key] = digest
        if clean:
            self.skipped += 1
            return False
        self.written += 1
        return True

    def note(self, key: str, digest: str) -> None:
        """Record a write that happens regardless of digest (callers not
        running in incremental mode), keeping the counters truthful."""
        self._digests[key] = digest
        self.written += 1
