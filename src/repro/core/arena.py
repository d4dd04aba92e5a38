"""Persistent arena: the framework's "persistent memory".

The paper operates data structures in volatile memory and treats each
explicit flush as a checkpoint of the *essential* fields into persistent
memory (Optane, mmap'd with MAP_SYNC).  Our TPU-cluster analogue (DESIGN.md
§2) is a host-side file-backed arena:

* every region has a VOLATILE numpy array (the working copy — the "DRAM/HBM"
  side) and a PERSISTENT np.memmap view of a backing file;
* ``persist_rows`` / ``persist_range`` copy selected rows from volatile to
  persistent and account the cost in *flush units* — 64-byte "cache lines"
  by default, with adjacent dirty lines coalesced, exactly mirroring the
  paper's clwb accounting (§V-E: unaligned/partial-line flushes re-fetch
  whole lines, so cost is counted in whole lines touched);
* a commit protocol orders data before metadata: ``commit()`` flushes the
  backing file and only then sets the header's valid flag (the paper's
  "flag bit" + NVTree-style manifest-last ordering);
* ``reopen()`` simulates the post-crash restart: all volatile state is
  discarded and regions are reloaded from the file;
* structures mark dirty rows (``Region.mark_rows``) into the arena's
  write set inside an ``Arena.epoch()``; the epoch exit flushes once —
  rows deduplicated, lines coalesced across the whole operation, data
  regions before header regions (core/writeset.py, DESIGN.md §2).

Byte/line counters are exact and medium-independent; wall-clock cost on this
CPU host is the real memcpy+write cost, which scales linearly in flushed
bytes (reproducing Fig 1's linearity).  An optional synthetic per-line
latency models Optane-like flush stalls for experiments that want the
paper's regime explicitly.

``ShardedArena`` (DESIGN.md §7) partitions the substrate into N
independent shards — each a full ``Arena`` with its own backing file,
write set, flush stats, and data-before-metadata commit header — behind
the SAME region/epoch/commit API, so every structure runs unchanged on
any shard count.  A ``ShardedRegion`` keeps ONE full-shape volatile
array (structures index it with global row ids exactly as before) while
its persistent bytes are split across shards by a pure row->shard
router (block-cyclic segments, hashed rows, or contiguous ranges).  A
tiny manifest commits LAST: the cross-shard generation is the one ALL
shards agree on, so a crash between shard commits recovers the previous
manifest generation.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import struct
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core.writeset import ShardedWriteSet, WriteSet

LINE = 64                 # flush granularity (bytes) — paper's cache line
MEDIA_GRAIN = 256         # DCPMM internal granularity (§IV-D bucket sizing)

_MAGIC = b"RPRA"
_HDR_FMT = "<4sQQ?7x"     # magic, n_regions, generation, valid flag
_MAN_MAGIC = b"RPRM"
_MAN_FMT = "<4sQQ?7x"     # magic, n_shards, generation, valid flag


# ======================================================================
# Integrity taxonomy (DESIGN.md §13)
# ======================================================================


class IntegrityError(RuntimeError):
    """Base of the media-fault taxonomy: persistent bytes failed a trust
    check that power loss alone cannot produce (checksum mismatch, shard
    file gone, manifest/header magic garbage)."""


class CorruptLineError(IntegrityError):
    """Committed persistent line(s) fail their sidecar checksum."""

    def __init__(self, region: str, rows, detail: str = ""):
        self.region = region
        self.rows = np.atleast_1d(np.asarray(rows, np.int64))
        msg = (f"corrupt line(s) in region {region!r}, "
               f"rows {self.rows[:8].tolist()}"
               + (f" (+{self.rows.size - 8} more)"
                  if self.rows.size > 8 else ""))
        super().__init__(msg + (f": {detail}" if detail else ""))


class ShardLossError(IntegrityError):
    """A shard backing file is missing, truncated, or behind the
    committed manifest generation — whole-device loss, not a torn
    commit (torn commits leave shards AHEAD of the manifest)."""


class ManifestError(IntegrityError):
    """The arena commit header or the sharded manifest — the trust
    anchors everything else hangs off — carry garbage magic/fields."""


class QuarantinedError(RuntimeError):
    """A request touched keys salvage recovery quarantined: refusing is
    the contract — serving reconstructed garbage is not (DESIGN.md §13)."""


@dataclass
class FlushStats:
    lines: int = 0
    bytes: int = 0
    calls: int = 0
    fence_ns: int = 0      # synthetic latency accumulated (if enabled)
    fences: int = 0        # ordering points paid (barrier phases + commit
                           # seals in barrier mode; ONE flip in shadow mode)
    # epoch-flush (write-set) counters — DESIGN.md §2
    epochs: int = 0        # batched epoch flushes performed
    marks: int = 0         # mark_rows calls absorbed by the write set
    dedup_rows: int = 0    # row marks dropped as duplicates within an epoch
    saved_lines: int = 0   # lines one accounting call PER MARK would have
                           # charged minus lines the epoch flush charged
    snapshot_lines: int = 0  # order-snapshot lines (DESIGN.md §10) — kept
                             # OUT of `lines`/`saved_lines` so partly-vs-
                             # full accounting stays comparable across PRs
    journal_lines: int = 0   # request-journal ring lines (DESIGN.md §11) —
                             # same separation: journal-off data accounting
                             # is bit-identical to journal-on
    integrity_lines: int = 0  # checksum-sidecar lines (DESIGN.md §13) —
                              # integrity-off accounting stays bit-identical

    def snapshot(self) -> "FlushStats":
        return dataclasses.replace(self)

    def delta(self, since: "FlushStats") -> "FlushStats":
        return FlushStats(*(getattr(self, f.name) - getattr(since, f.name)
                            for f in dataclasses.fields(self)))


class _RowAccess:
    """Row accessors shared by Region and ShardedRegion.

    Structures read/write volatile rows through these instead of direct
    ``.vol`` fancy indexing; here they are thin views over the
    full-shape volatile array (zero behavior change), while the paged
    variants (core/paging.py, DESIGN.md §12) override them to route
    through the per-arena block cache without ever materializing the
    full array.  ``col`` may be an int or a slice over the trailing
    dimension."""

    is_paged = False

    def read_rows(self, rows: np.ndarray) -> np.ndarray:
        return self.vol[np.asarray(rows, np.int64)]

    def read_at(self, rows: np.ndarray, col) -> np.ndarray:
        return self.vol[np.asarray(rows, np.int64), col]

    def read_one(self, row: int, col: int) -> int:
        return int(self.vol[row, col])

    def read_col(self, col) -> np.ndarray:
        return self.vol[:, col]

    def write_rows(self, rows: np.ndarray, vals) -> None:
        self.vol[np.asarray(rows, np.int64)] = vals

    def write_at(self, rows: np.ndarray, col, vals) -> None:
        self.vol[np.asarray(rows, np.int64), col] = vals

    # -- paging hooks (no-ops on resident regions) ------------------------
    def _note_flushed(self, rows: np.ndarray) -> None:
        """Rows just copied volatile->persistent through the write-set:
        a paged region clears their dirty bits (unpinning clean blocks
        for eviction); resident regions need no bookkeeping."""

    def _note_persisted(self, rows: np.ndarray) -> None:
        """Rows just written home by a DIRECT (epoch-less) persist call
        — the paged override additionally keeps shadow-masked rows
        dirty, since a refault would overlay the stale mirror."""

    def _note_persisted_range(self, lo: int, hi: int) -> None:
        pass


class Region(_RowAccess):
    """A named, row-structured persistent region."""

    def __init__(self, arena: "Arena", name: str, dtype, shape: Tuple[int, ...],
                 offset: int, meta: Optional[bool] = None):
        self.arena = arena
        self.name = name
        self.dtype = np.dtype(dtype)
        self.shape = tuple(shape)
        self.offset = offset
        # Order-snapshot regions (DESIGN.md §10) are derivable-redundancy
        # mirrors: their flush lines are accounted separately
        # (FlushStats.snapshot_lines) and they ride the metadata phase so
        # a torn data-phase crash never leaves half a snapshot behind the
        # committed header.
        self.snap = ".snap" in name
        # Request-journal regions (DESIGN.md §11): the append ring is a
        # data-phase region (entries become visible only through the
        # committed head counter on a metadata line) whose lines are
        # accounted in FlushStats.journal_lines.
        self.jrnl = ".jrnl" in name
        # Integrity-sidecar regions (DESIGN.md §13): per-line checksums
        # of a data region, written by the SAME drain that moves the
        # data rows (never marked by structures), accounted in
        # FlushStats.integrity_lines.
        self.integ = name.endswith(".integ")
        self._integ: Optional["Region"] = None   # my sidecar, if covered
        # Metadata regions (structure headers) flush AFTER data regions
        # within an epoch — data-before-metadata ordering (DESIGN.md §2).
        self.meta = (name.endswith("header") or self.snap) \
            if meta is None else meta
        self.rowbytes = int(self.dtype.itemsize * np.prod(shape[1:], dtype=np.int64)) \
            if len(shape) > 1 else self.dtype.itemsize
        self.nbytes = self.rowbytes * shape[0]
        self._init_vol()

    def _init_vol(self) -> None:
        # Volatile working copy.  PagedRegion overrides this with a
        # demand-faulted block pool (DESIGN.md §12).
        self.vol = np.zeros(self.shape, self.dtype)

    def _crash_reset(self) -> None:
        """Discard volatile state on a simulated power loss."""
        self.vol = np.zeros(self.shape, self.dtype)

    # -- persistence ------------------------------------------------------
    def _pview(self) -> np.ndarray:
        mm = self.arena._mm
        flat = np.frombuffer(mm, dtype=np.uint8,
                             count=self.nbytes, offset=self.offset)
        return flat.view(self.dtype).reshape(self.shape)

    def _gather(self, rows: np.ndarray) -> np.ndarray:
        """Volatile source rows for a flush — overridden by shard slices,
        whose volatile state lives in the parent ShardedRegion."""
        return self.vol[rows]

    def _gather_range(self, lo: int, hi: int) -> np.ndarray:
        return self.vol[lo:hi]

    def _pack_source(self, rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(full volatile array, row ids into it) for the pack_flush
        kernel gather path."""
        return self.vol, rows

    def persist_rows(self, rows: np.ndarray) -> None:
        """Flush the given row indices (volatile -> persistent) NOW, with
        per-call line accounting.  Structure code should prefer
        ``mark_rows`` so flushes batch per epoch."""
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return
        rows = np.unique(rows)
        pv = self._pview()
        pv[rows] = self._gather(rows)
        self.arena._account_rows(self.offset, self.rowbytes, rows,
                                 snap=self.snap, jrnl=self.jrnl,
                                 integ=self.integ)
        self._note_persisted(rows)
        self.arena._integrity_home(self, rows)

    def mark_rows(self, rows: np.ndarray, fresh: bool = False) -> None:
        """Add rows to the arena's write set (flushed once, deduplicated,
        when the enclosing epoch closes).  Outside any epoch this
        degrades to an immediate ``persist_rows`` — per-op call sites
        behave identically either way.  ``fresh=True`` asserts the rows
        were never reachable from any committed generation (fresh-range
        allocations above the committed high-water mark), so a shadow
        drain may write them home in place instead of through the
        remap; barrier mode ignores the hint."""
        if self.arena._epoch_depth > 0:
            self.arena.writeset.mark(self, np.asarray(rows, np.int64),
                                     fresh=fresh)
        else:
            self.persist_rows(rows)

    def mark_range(self, lo: int, hi: int, fresh: bool = False) -> None:
        if hi > lo:
            self.mark_rows(np.arange(lo, hi, dtype=np.int64), fresh=fresh)

    def persist_range(self, lo: int, hi: int) -> None:
        if hi <= lo:
            return
        pv = self._pview()
        pv[lo:hi] = self._gather_range(lo, hi)
        self.arena._account_range(self.offset + lo * self.rowbytes,
                                  (hi - lo) * self.rowbytes,
                                  snap=self.snap, jrnl=self.jrnl,
                                  integ=self.integ)
        self._note_persisted_range(lo, hi)
        self.arena._integrity_home(self, np.arange(lo, hi, dtype=np.int64))

    def persist_all(self) -> None:
        self.persist_range(0, self.shape[0])

    def load(self) -> None:
        """Reload volatile copy from persistent memory (post-crash).
        Pays the synthetic media read latency when the arena models one
        — the recovery-side mirror of the flush stall."""
        self.vol = np.array(self._pview())
        self.arena._shadow_overlay(self)
        self.arena.synth_read(self.nbytes)


class Arena:
    """File-backed persistent arena with flush accounting."""

    def __init__(self, path: Optional[str], synth_line_ns: float = 0.0,
                 pack_flush_rows: int = 0, commit_mode: str = "barrier",
                 synth_fence_ns: float = 0.0, paged: Optional[bool] = None,
                 block_bytes: int = 4096, cache_blocks: int = 1024,
                 integrity: Optional[bool] = None):
        assert commit_mode in ("barrier", "shadow")
        self.path = path
        self.regions: Dict[str, Region] = {}
        self.stats = FlushStats()
        # Integrity sidecars (DESIGN.md §13): finalize() appends a
        # per-line checksum region per covered data region, written by
        # the epoch drain itself.  Integrity-off layouts and accounting
        # are bit-identical to the pre-integrity substrate.
        self.integrity = integrity_enabled(integrity)
        # Paged-region backend (DESIGN.md §12): eligible data regions
        # fault fixed-size blocks through a per-arena LRU cache instead
        # of materializing a full-shape volatile array.  Strictly
        # volatile-side — persistent layouts are bit-identical either way.
        self.paged = paged_enabled(paged)
        self.block_bytes = int(block_bytes)
        self.cache_blocks = int(cache_blocks)
        self.cache = None
        if self.paged:
            from repro.core.paging import BlockCache
            self.cache = BlockCache(self.block_bytes, self.cache_blocks)
        self.synth_line_ns = synth_line_ns
        self.commit_mode = commit_mode
        self.synth_fence_ns = synth_fence_ns
        # >0: epoch flushes of at least this many rows gather through the
        # Pallas pack_flush kernel (tile-aligned staging buffer).
        self.pack_flush_rows = pack_flush_rows
        # Sharded parents set this: synthetic flush stalls then sleep
        # (GIL-released — stalls of sibling shards overlap in the flush
        # pool) instead of spinning.  A lone arena always spins: exact,
        # and nothing could overlap with it anyway.
        self.synth_sleep = False
        self._defer = False
        self._defer_ns = 0
        # concurrent per-region load stages may synth_read the same
        # shard from several scheduler threads; the fence accumulator is
        # the one counter they share
        self._fence_lock = threading.Lock()
        self.writeset = WriteSet(self)
        self._epoch_depth = 0
        self._layout_final = False
        # order-snapshot providers (DESIGN.md §10): callables returning
        # [(region, rows), ...] drained by the write set ONLY inside a
        # commit (never by mid-epoch flushes), so snapshot bytes always
        # ride the commit protocol of whichever mode is active
        self._snap_providers: List = []
        self._mm: Optional[np.memmap] = None
        self._cursor = 4096  # header page
        self._meta: Dict[str, dict] = {}
        self.generation = 0
        # shadow-commit state (DESIGN.md §9) — all volatile; the
        # persistent side (one meta line, two remap-entry banks, and a
        # per-region mirror per bank) is laid out by finalize() after
        # the last region
        self._region_ids: Dict[str, int] = {}
        self._shadow_meta_off = 0
        self._shadow_ent_off = [0, 0]
        self._shadow_cap = 0
        self._shadow_masks = ({}, {})   # bank -> {region name: bool mask}
        self._shadow_counts = [0, 0]
        self._shadow_collapsed = [True, True]
        self._shadow_auth_bank = 0

    # -- epochs -----------------------------------------------------------
    @contextlib.contextmanager
    def epoch(self):
        """One logical operation: ``mark_rows`` calls inside the block
        accumulate in the write set; the outermost epoch exit flushes
        them once (rows deduplicated, lines coalesced across the op,
        data regions before metadata regions)."""
        self._epoch_depth += 1
        try:
            yield self
        finally:
            self._epoch_depth -= 1
            if self._epoch_depth == 0:
                self.writeset.flush()

    # -- layout -----------------------------------------------------------
    def region(self, name: str, dtype, shape: Tuple[int, ...],
               meta: Optional[bool] = None, router=None,
               _cls=Region, **_slice_kw) -> Region:
        """``router`` (a row->shard routing spec) is accepted for layout
        compatibility with ShardedArena and ignored here: a single arena
        IS one shard."""
        assert not self._layout_final, "layout already finalized"
        assert name not in self.regions
        # Row-align every region to LINE so a row flush never straddles an
        # unrelated region (paper: __attribute__((aligned(64)))).
        self._cursor = _align(self._cursor, LINE)
        cls = _cls
        if cls is Region and self.cache is not None and _paged_eligible(
                name, meta, dtype, shape, self.block_bytes):
            from repro.core.paging import PagedRegion
            cls = PagedRegion
        r = cls(self, name, dtype, shape, self._cursor, meta=meta,
                **_slice_kw)
        self._cursor += _align(r.nbytes, LINE)
        self.regions[name] = r
        self._region_ids[name] = len(self._region_ids)
        self._meta[name] = {"dtype": np.dtype(dtype).str,
                            "shape": list(shape), "offset": r.offset}
        return r

    def region_shards(self, name: str, rows: np.ndarray) -> np.ndarray:
        """Shard id of each row of region `name` — all zeros for a plain
        arena (callers group work per shard without caring which arena
        flavor they hold)."""
        return np.zeros(len(np.atleast_1d(rows)), np.int64)

    def finalize(self) -> None:
        assert not self._layout_final
        if self.integrity:
            self._integrity_layout()
        self._layout_final = True
        if self.commit_mode == "shadow":
            self._shadow_layout()
        total = _align(self._cursor, 4096)
        if self.path is None:
            self._mm = np.zeros(total, np.uint8)  # in-memory (tests)
        else:
            create = not os.path.exists(self.path)
            if create:
                with open(self.path, "wb") as f:
                    f.truncate(total)
            elif os.path.getsize(self.path) < total:
                # an existing-but-short backing file is media loss, not
                # a layout bug.  np.memmap in r+ mode would silently
                # re-extend it with zeros — zeros that also wipe the
                # integrity sidecars back to the never-written sentinel,
                # making the loss invisible to scrub — so the size check
                # must happen BEFORE mapping.
                raise ShardLossError(
                    f"backing file {self.path!r} truncated: "
                    f"{os.path.getsize(self.path)} < {total} bytes")
            try:
                self._mm = np.memmap(self.path, dtype=np.uint8, mode="r+",
                                     shape=(total,))
            except (ValueError, OSError) as e:
                raise ShardLossError(
                    f"backing file {self.path!r} unmappable at "
                    f"{total} bytes: {e}") from e
            if create:
                self._write_header(valid=False)
        # sidecar layout description (tiny, metadata-only)
        if self.path is not None:
            with open(self.path + ".layout", "w") as f:
                json.dump(self._meta, f)

    # -- order snapshots (DESIGN.md §10) -----------------------------------
    def add_snapshot_provider(self, fn) -> None:
        """Register an order-snapshot provider: a callable returning
        ``[(region, rows), ...]`` of snapshot-region rows to persist.
        Drained by the write set exactly once per commit, inside the
        active commit protocol."""
        self._snap_providers.append(fn)

    # -- integrity sidecars (DESIGN.md §13) --------------------------------
    def _integrity_layout(self) -> None:
        """Append one checksum sidecar per covered data region: int64
        rows of shape (rows, chunks) where each word checksums one 64 B
        line of the source row (whole row when rows are sub-line).
        Appending AFTER every declared region keeps integrity-on
        layouts a pure suffix of integrity-off ones — existing region
        offsets never move."""
        for name, r in list(self.regions.items()):
            if r.meta or r.snap or r.jrnl or r.integ or r.rowbytes % 8:
                continue
            if getattr(r, "_parent", None) is not None:
                continue            # shard slices: the parent covers them
            sc = self.region(name + ".integ", np.int64,
                             (r.shape[0], _integ_chunks(r.rowbytes)),
                             meta=False)
            r._integ = sc

    def _integrity_home(self, region, rows: np.ndarray,
                        data: Optional[np.ndarray] = None) -> None:
        """Recompute + persist `rows`' sidecar checksums IN PLACE — the
        companion of every home write of the data rows themselves
        (barrier drains, fresh shadow rows, direct persists), so data
        and checksums always move in the same flush phase and a torn
        crash can never split them.  ``data``, when the caller already
        gathered the rows (the epoch drain always has), skips a second
        gather."""
        sc = region._integ
        if sc is None or rows.size == 0:
            return
        if data is None:
            data = region._gather(rows)
        ck = sidecar_checksums(data, sc.shape[1])
        sc.write_rows(rows, ck)
        sc._pview()[rows] = ck
        self._account_rows(sc.offset, sc.rowbytes, rows, integ=True)

    def verify_header(self) -> None:
        """Raise ManifestError when the commit header's magic is neither
        ours nor the all-zero never-committed state — field corruption
        power loss cannot produce (the header is one atomic line)."""
        raw = bytes(self._mm[:4])
        if raw not in (_MAGIC, b"\x00\x00\x00\x00"):
            raise ManifestError(
                f"arena {self.path!r} header magic {raw!r} corrupt")

    def _pimage(self, region) -> np.ndarray:
        """The COMMITTED persistent image of a region: home bytes plus
        the authoritative shadow bank's overlay.  A pure read — scrub
        and salvage never write persistent state."""
        img = np.array(region._pview())
        if self.commit_mode == "shadow":
            mask = self._shadow_masks[self._shadow_auth_bank].get(
                region.name)
            if mask is not None and mask.any():
                rows = np.nonzero(mask)[0]
                img[rows] = self._shadow_mirror(
                    region, self._shadow_auth_bank)[rows]
        return img

    def verify_region(self, region) -> np.ndarray:
        """Row indices of `region` whose committed persistent bytes fail
        their sidecar checksums (empty = clean).  Reads the persistent
        image only — in-flight volatile writes and pending epoch marks
        are invisible to it, and rows whose lines were never flushed
        carry the 0 \"no checksum\" sentinel and are skipped — so scrub
        under traffic cannot false-positive (DESIGN.md §13)."""
        if isinstance(region, str):
            region = self.regions[region]
        sc = region._integ
        if sc is None:
            return np.empty(0, np.int64)
        ck = sidecar_checksums(self._pimage(region), sc.shape[1])
        ref = self._pimage(sc)
        bad = (ref != 0) & (ck != ref)
        self.synth_read(region.nbytes + sc.nbytes)
        return np.nonzero(bad.any(axis=1))[0]

    def scrub(self, raise_on_error: bool = False
              ) -> Dict[str, np.ndarray]:
        """Verify every covered region against its sidecar; returns
        {region name: bad rows} for the regions that fail (empty dict =
        media clean).  Read-only and crash-safe at any instant."""
        bad: Dict[str, np.ndarray] = {}
        for name, r in self.regions.items():
            if r._integ is None:
                continue
            rows = self.verify_region(r)
            if rows.size:
                bad[name] = rows
        if bad and raise_on_error:
            name, rows = next(iter(bad.items()))
            raise CorruptLineError(name, rows,
                                   detail=f"scrub: {len(bad)} region(s)")
        return bad

    # -- header / commit protocol -----------------------------------------
    def _write_header(self, valid: bool) -> None:
        hdr = struct.pack(_HDR_FMT, _MAGIC, len(self.regions),
                          self.generation, valid)
        self._mm[: len(hdr)] = np.frombuffer(hdr, np.uint8)

    def header_valid(self) -> bool:
        raw = bytes(self._mm[: struct.calcsize(_HDR_FMT)])
        magic, _, gen, valid = struct.unpack(_HDR_FMT, raw)
        return magic == _MAGIC and bool(valid)

    def header_generation(self) -> int:
        """Committed generation as persisted in the header — unlike the
        in-memory ``generation`` counter, this survives a fresh-process
        reopen."""
        raw = bytes(self._mm[: struct.calcsize(_HDR_FMT)])
        magic, _, gen, _ = struct.unpack(_HDR_FMT, raw)
        return int(gen) if magic == _MAGIC else 0

    def commit(self) -> None:
        """Data-before-metadata ordering: drain the write set, flush file
        contents, then set the valid flag (the paper's initialization
        flag bit).  Inside an epoch this flushes the pending marks first,
        so a commit never orders the flag ahead of its data.  In shadow
        mode the whole protocol collapses to ONE ordering point — see
        ``_commit_shadow``.  Spanned as ``persist.commit``
        (``repro.obs``)."""
        with obs.span("persist.commit"):
            self._commit()

    def _commit(self) -> None:
        if self.commit_mode == "shadow":
            self._commit_shadow()
            return
        self.writeset.flush()
        if isinstance(self._mm, np.memmap):
            self._mm.flush()
        self._fence()
        self.generation += 1
        self._write_header(valid=True)
        if isinstance(self._mm, np.memmap):
            self._mm.flush()
        self.stats.calls += 1

    def invalidate(self) -> None:
        self._write_header(valid=False)

    def _fence(self) -> None:
        """One ordering point (sfence + drain of outstanding flushes):
        counted per mode so the barrier-vs-shadow comparison is visible
        in the stats artifact, and paid synthetically when
        ``synth_fence_ns`` models the stall."""
        self.stats.fences += 1
        if self.synth_fence_ns:
            self._stall(int(self.synth_fence_ns))

    # -- shadow commit protocol (DESIGN.md §9) ------------------------------
    def _shadow_layout(self) -> None:
        """Persistent shadow areas, appended after the last region: one
        meta line holding each remap bank's sealed entry count, two
        remap-entry banks (one per generation parity: the epoch
        targeting generation T writes bank T%2, so a torn flip never
        touches the committed bank), and a per-region mirror per bank
        whose slot index IS the row index — duplicate rewrites of a row
        are idempotent by construction, and the remap entry is just
        (region id, row)."""
        cur = _align(self._cursor, LINE)
        self._shadow_meta_off = cur
        cur += LINE
        self._shadow_cap = max(1, sum(r.shape[0]
                                      for r in self.regions.values()))
        for b in (0, 1):
            self._shadow_ent_off[b] = cur
            cur += _align(self._shadow_cap * 16, LINE)
        for r in self.regions.values():
            r._shadow_off = {}
            for b in (0, 1):
                r._shadow_off[b] = cur
                cur += _align(r.nbytes, LINE)
        self._cursor = cur

    def _shadow_target_bank(self) -> int:
        return (self.generation + 1) % 2

    def _shadow_mirror(self, region: "Region", bank: int) -> np.ndarray:
        flat = np.frombuffer(self._mm, dtype=np.uint8, count=region.nbytes,
                             offset=region._shadow_off[bank])
        return flat.view(region.dtype).reshape(region.shape)

    def _shadow_entries(self, bank: int) -> np.ndarray:
        flat = np.frombuffer(self._mm, dtype=np.uint8,
                             count=self._shadow_cap * 16,
                             offset=self._shadow_ent_off[bank])
        return flat.view(np.int64).reshape(self._shadow_cap, 2)

    def _shadow_meta_view(self) -> np.ndarray:
        flat = np.frombuffer(self._mm, dtype=np.uint8, count=LINE,
                             offset=self._shadow_meta_off)
        return flat.view(np.int64)

    def _shadow_write(self, region: "Region", rows: np.ndarray) -> None:
        """Route a rewrite through the remap: the new row versions land
        in the target bank's mirror (slot = row) and first-touch rows
        append a (region, row) remap entry.  Committed home rows are
        never rewritten before the flip, so the drain needs no ordering
        against the metadata that references them."""
        b = self._shadow_target_bank()
        mask = self._shadow_masks[b].get(region.name)
        if mask is None:
            mask = self._shadow_masks[b][region.name] = \
                np.zeros(region.shape[0], bool)
        new = rows[~mask[rows]]
        mask[rows] = True
        self._shadow_mirror(region, b)[rows] = region._gather(rows)
        self._account_rows(region._shadow_off[b], region.rowbytes, rows,
                           snap=region.snap, jrnl=region.jrnl,
                           integ=region.integ)
        if new.size:
            cnt = self._shadow_counts[b]
            ents = self._shadow_entries(b)
            ents[cnt:cnt + new.size, 0] = self._region_ids[region.name]
            ents[cnt:cnt + new.size, 1] = new
            self._account_range(self._shadow_ent_off[b] + cnt * 16,
                                int(new.size) * 16, snap=region.snap,
                                jrnl=region.jrnl, integ=region.integ)
            self._shadow_counts[b] = cnt + int(new.size)
        # The rows' volatile values are now captured persistently in the
        # target-bank mirror, which a paged refault overlays — so their
        # dirty bits may clear (clean blocks become evictable).
        region._note_flushed(rows)
        # cascade: the rows' checksums route through the SAME bank, so a
        # discarded target bank drops data and checksums together
        sc = region._integ
        if sc is not None:
            sc.write_rows(rows, sidecar_checksums(region._gather(rows),
                                                  sc.shape[1]))
            self._shadow_write(sc, rows)

    def _shadow_collapse(self, limit: Optional[int] = None) -> bool:
        """Fold the committed bank's shadow rows into their home slots —
        the stale-row reclamation, deferred into the next drain instead
        of blocking the commit that created them.  The copy is
        value-identical to what recovery would overlay, so a crash at
        ANY instant during it (the double-failure window) changes
        nothing the committed generation can observe.  ``limit`` bounds
        the number of regions folded (crash-injection hook); returns
        whether the bank fully collapsed."""
        b = self.generation % 2
        if self._shadow_collapsed[b]:
            return True
        done = True
        for i, name in enumerate(sorted(self._shadow_masks[b])):
            if limit is not None and i >= limit:
                done = False
                break
            rows = np.nonzero(self._shadow_masks[b][name])[0]
            if rows.size == 0:
                continue
            region = self.regions[name]
            region._pview()[rows] = self._shadow_mirror(region, b)[rows]
            self._account_rows(region.offset, region.rowbytes, rows,
                               snap=region.snap, jrnl=region.jrnl,
                               integ=region.integ)
        if done:
            self._shadow_collapsed[b] = True
        return done

    def _shadow_seal(self) -> None:
        """Persist the target bank's entry count.  Safe before the
        flip: the bank is dead weight until the generation pointer
        selects it, and the committed bank's count slot is untouched."""
        b = self._shadow_target_bank()
        self._shadow_meta_view()[b] = self._shadow_counts[b]
        self._account_range(self._shadow_meta_off + b * 8, 8)

    def _shadow_retire(self) -> None:
        """Post-flip bookkeeping: the previous bank's entries are dead
        (their rows were folded home before the flip); the newly
        committed bank awaits its fold at the next drain."""
        live = self.generation % 2
        dead = 1 - live
        self._shadow_masks[dead].clear()
        self._shadow_counts[dead] = 0
        self._shadow_collapsed[dead] = True
        self._shadow_collapsed[live] = self._shadow_counts[live] == 0
        self._shadow_auth_bank = live

    def _commit_shadow(self) -> None:
        """Shadow commit: fold the previous epoch's shadow rows home,
        drain the write set straight through — fresh rows in place,
        rewrites into the target bank — seal the target bank's count,
        then pay the ONE ordering point and flip the generation
        pointer.  The flip atomically reassigns bank authority; a torn
        flip leaves the committed bank (untouched since its own seal)
        authoritative, and the orphaned target bank is discarded by
        never being selected."""
        self._shadow_collapse()
        self.writeset.flush()
        self._shadow_seal()
        if isinstance(self._mm, np.memmap):
            self._mm.flush()
        self._fence()                      # the single ordering point
        self.generation += 1
        self._write_header(valid=True)
        if isinstance(self._mm, np.memmap):
            self._mm.flush()
        self.stats.calls += 1
        self._shadow_retire()

    def _shadow_discard(self) -> None:
        """Volatile shadow bookkeeping dies with a crash; ``reopen``
        re-parses it from the committed bank."""
        for m in self._shadow_masks:
            m.clear()
        self._shadow_counts = [0, 0]
        self._shadow_collapsed = [True, True]

    def _shadow_parse(self, authority_gen: Optional[int] = None) -> None:
        """Post-crash: rebuild the volatile remap masks from the bank
        the COMMITTED generation pointer selects — for a shard that is
        the manifest generation, which may trail the shard's own header
        if the flip tore between shards.  Entries in the other bank (a
        torn flip's orphans) are never selected and are overwritten
        when that bank is next targeted."""
        if self.commit_mode != "shadow":
            return
        gen = self.header_generation() if authority_gen is None \
            else authority_gen
        b = gen % 2
        cnt = int(self._shadow_meta_view()[b])
        ents = np.array(self._shadow_entries(b)[:cnt])
        masks: Dict[str, np.ndarray] = {}
        names = list(self.regions)
        for rid in (np.unique(ents[:, 0]) if cnt else ()):
            name = names[int(rid)]
            mask = np.zeros(self.regions[name].shape[0], bool)
            mask[ents[ents[:, 0] == rid, 1]] = True
            masks[name] = mask
        self._shadow_masks = (masks, {}) if b == 0 else ({}, masks)
        self._shadow_counts = [cnt, 0] if b == 0 else [0, cnt]
        self._shadow_collapsed = [True, True]
        self._shadow_collapsed[b] = cnt == 0
        self._shadow_auth_bank = b
        # re-anchor bank targeting to the COMMITTED generation: a shard
        # whose header flipped ahead of a torn manifest write must aim
        # its next drain at the bank the manifest's parity dooms, not
        # keep writing into the bank recovery just selected
        self.generation = gen

    def _shadow_overlay(self, region: "Region",
                        vol: Optional[np.ndarray] = None,
                        gidx: Optional[np.ndarray] = None) -> None:
        """Apply the authoritative bank's shadow rows over a freshly
        loaded volatile copy — recovery-side only, and VOLATILE-only:
        recovery persists nothing (the fold happens lazily at the next
        drain, preserving reconstructor purity)."""
        if self.commit_mode != "shadow":
            return
        mask = self._shadow_masks[self._shadow_auth_bank].get(region.name)
        if mask is None:
            return
        rows = np.nonzero(mask)[0]
        if rows.size == 0:
            return
        m = self._shadow_mirror(region, self._shadow_auth_bank)
        if vol is None:
            region.vol[rows] = m[rows]
        else:
            vol[gidx[rows]] = m[rows]
        self.synth_read(int(rows.size) * region.rowbytes)

    # -- crash simulation ---------------------------------------------------
    def crash(self) -> None:
        """Discard all volatile state (keep the backing file).  Pending
        write-set marks die with the volatile state — power loss loses
        un-flushed rows; it must never flush zeroed volatile copies over
        committed data when a wrapping epoch unwinds."""
        self.writeset.discard()
        self._shadow_discard()
        for r in self.regions.values():
            r._crash_reset()

    def reopen(self) -> None:
        """Reload every region's volatile copy from persistent memory,
        and re-anchor the in-memory generation counter to the committed
        one (a fresh process starts at 0 otherwise).  Shadow mode first
        re-parses the committed bank's remap so each load overlays the
        flipped-in row versions."""
        self._shadow_parse()
        for r in self.regions.values():
            r.load()
        self.generation = max(self.generation, self.header_generation())

    # -- accounting ---------------------------------------------------------
    def _account_range(self, byte_off: int, nbytes: int,
                       snap: bool = False, jrnl: bool = False,
                       integ: bool = False) -> None:
        lo = (byte_off // LINE) * LINE
        hi = _align(byte_off + nbytes, LINE)
        lines = (hi - lo) // LINE
        if snap:
            # snapshot overhead is real media traffic (it pays the synth
            # stall) but lands in its own counter so data-line accounting
            # stays bit-comparable to snapshot-off runs
            self.stats.snapshot_lines += lines
            self._synth(lines)
            return
        if jrnl:
            # journal rings get the same treatment (DESIGN.md §11)
            self.stats.journal_lines += lines
            self._synth(lines)
            return
        if integ:
            # checksum sidecars too (DESIGN.md §13)
            self.stats.integrity_lines += lines
            self._synth(lines)
            return
        self.stats.lines += lines
        self.stats.bytes += nbytes
        self.stats.calls += 1
        self._synth(lines)

    @staticmethod
    def _rows_line_count(base: int, rowbytes: int, rows: np.ndarray) -> int:
        """Distinct 64 B lines touched by flushing `rows` (sorted unique)."""
        if rowbytes % LINE == 0 and base % LINE == 0:
            # aligned rows: rows * rowbytes/LINE lines, coalescing irrelevant
            return int(rows.size) * (rowbytes // LINE)
        if rowbytes and LINE % rowbytes == 0 and base % LINE == 0:
            # sub-line rows that tile lines exactly (the checksum
            # sidecars: 8/16/32 B rows) — sorted-unique rows sharing a
            # line are adjacent, so distinct lines = breaks + 1
            per = LINE // rowbytes
            if rows.size == 0:
                return 0
            return int(np.count_nonzero(np.diff(rows // per))) + 1
        # exact distinct-line count over sorted row intervals (adjacent
        # rows may share a line — the Fig-12 unaligned-flush effect)
        starts = (base + rows * rowbytes) // LINE
        ends = (base + (rows + 1) * rowbytes - 1) // LINE
        starts = np.maximum(starts,
                            np.concatenate(([-1], ends[:-1])) + 1)
        return int(np.sum(np.maximum(0, ends - starts + 1)))

    def _account_rows(self, base: int, rowbytes: int, rows: np.ndarray,
                      snap: bool = False, jrnl: bool = False,
                      integ: bool = False) -> None:
        lines = self._rows_line_count(base, rowbytes, rows)
        if snap:
            self.stats.snapshot_lines += lines
            self._synth(lines)
            return
        if jrnl:
            self.stats.journal_lines += lines
            self._synth(lines)
            return
        if integ:
            self.stats.integrity_lines += lines
            self._synth(lines)
            return
        self.stats.lines += lines
        self.stats.bytes += int(rows.size) * rowbytes
        self.stats.calls += 1
        self._synth(lines)

    def _synth(self, lines: int) -> None:
        if self.synth_line_ns:
            self._stall(int(lines * self.synth_line_ns))

    def synth_read(self, nbytes: int) -> None:
        """Synthetic media READ latency for a reload of `nbytes` —
        the §V-F mirror of the write-side flush stall, at DCPMM media
        granularity (256 B grains).  Zero-cost unless the arena was
        opened with ``synth_line_ns`` (the same knob as the write side:
        one medium, one latency model)."""
        if self.synth_line_ns:
            grains = (nbytes + MEDIA_GRAIN - 1) // MEDIA_GRAIN
            self._stall(int(grains * self.synth_line_ns))

    @contextlib.contextmanager
    def stall_scope(self):
        """Aggregate synthetic stalls issued inside the block into ONE
        stall paid at exit — a flush that touches several regions fences
        once per drain, not once per region.  The accounting
        (``fence_ns``) is unchanged; only the pay-out coalesces, which
        is what keeps the sleep-based stall's timer slack from being
        charged per region."""
        self._defer_ns = 0
        self._defer = True
        try:
            yield
        finally:
            self._defer = False
            ns, self._defer_ns = self._defer_ns, 0
            if ns:
                self._pay(ns)

    def _stall(self, ns: int) -> None:
        with self._fence_lock:
            self.stats.fence_ns += ns
        if self._defer:
            self._defer_ns += ns
            return
        self._pay(ns)

    def _pay(self, ns: int) -> None:
        if self.synth_sleep and ns >= 200_000:
            # big stalls sleep so concurrent shard flushes/reloads
            # overlap them; sub-200µs stalls stay on the exact spin (the
            # host timer's wakeup slack would swamp them)
            time.sleep(ns * 1e-9)
            return
        t0 = time.perf_counter_ns()
        while time.perf_counter_ns() - t0 < ns:
            pass

    def close(self) -> None:
        if isinstance(self._mm, np.memmap):
            self._mm.flush()
        self._mm = None


def _align(x: int, a: int) -> int:
    return ((x + a - 1) // a) * a


# ======================================================================
# Sharded arenas (DESIGN.md §7)
# ======================================================================


def _splitmix64(x: np.ndarray) -> np.ndarray:
    # 0-d arrays route through numpy's *scalar* ufunc paths, which WARN
    # on the intended uint64 wraparound; compute 1-D (a view) and
    # restore the shape so >=1-d callers pay nothing
    x = np.asarray(x).astype(np.uint64, copy=False)
    shape = x.shape
    x = x.reshape(-1)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return (x ^ (x >> np.uint64(31))).reshape(shape)


# ======================================================================
# Incremental order snapshots — record format (DESIGN.md §10)
# ======================================================================

SNAP_MAGIC = 0x50414E53          # "SNAP" little-endian
SNAP_SLOTS = 4                   # record-ring slots; one 64 B line each
SNAP_WORDS = 8                   # int64 words per record (= one line)


def snapshot_enabled(flag: Optional[bool] = None) -> bool:
    """Resolve a structure's ``snapshot=`` ctor arg: an explicit flag
    wins; ``None`` defers to the ``REPRO_SNAPSHOT`` env axis (default
    on).  Snapshot-off layouts and accounting are bit-identical to the
    pre-snapshot substrate."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("REPRO_SNAPSHOT", "1") != "0"


def journal_enabled(flag: Optional[bool] = None) -> bool:
    """Resolve a structure's ``journal=`` ctor arg: an explicit flag
    wins; ``None`` defers to the ``REPRO_JOURNAL`` env axis (default
    on).  Journal-off layouts and accounting are bit-identical to the
    pre-journal substrate (DESIGN.md §11)."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("REPRO_JOURNAL", "1") != "0"


def paged_enabled(flag: Optional[bool] = None) -> bool:
    """Resolve an arena's ``paged=`` ctor arg: an explicit flag wins;
    ``None`` defers to the ``REPRO_PAGED`` env axis (default OFF — the
    resident volatile array is the baseline).  Paging is strictly
    volatile-side, so persistent layouts are bit-identical either way
    (DESIGN.md §12)."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("REPRO_PAGED", "0") != "0"


def integrity_enabled(flag: Optional[bool] = None) -> bool:
    """Resolve an arena's ``integrity=`` ctor arg: an explicit flag
    wins; ``None`` defers to the ``REPRO_INTEGRITY`` env axis (default
    ON).  Integrity-off layouts and flush accounting are bit-identical
    to the pre-integrity substrate (DESIGN.md §13)."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("REPRO_INTEGRITY", "1") != "0"


def _paged_eligible(name: str, meta: Optional[bool], dtype, shape,
                    block_bytes: int) -> bool:
    """Data regions bigger than one block page; headers, order
    snapshots, and journal rings stay resident — they are tiny, hot on
    every epoch, and recovery reads them in full anyway.  Computed from
    the layout spec BEFORE construction so an ineligible huge region is
    never allocated twice."""
    snap = ".snap" in name
    jrnl = ".jrnl" in name
    integ = name.endswith(".integ")
    m = (name.endswith("header") or snap) if meta is None else meta
    rowbytes = int(np.dtype(dtype).itemsize *
                   np.prod(shape[1:], dtype=np.int64)) \
        if len(shape) > 1 else np.dtype(dtype).itemsize
    return (not (m or snap or jrnl or integ)
            and rowbytes * shape[0] > block_bytes)


_POS_KEYS: Dict[int, np.ndarray] = {}


def _pos_keys(n: int) -> np.ndarray:
    """``n`` distinct odd 64-bit multipliers, one per word position —
    splitmix64 of the position, forced odd so each per-word multiply is
    a bijection mod 2**64."""
    k = _POS_KEYS.get(n)
    if k is None:
        k = _splitmix64(np.arange(1, n + 1, dtype=np.uint64)) \
            | np.uint64(1)
        _POS_KEYS[n] = k
    return k


def mix_checksums(words: np.ndarray) -> np.ndarray:
    """THE checksum of the substrate (DESIGN.md §10/§11/§13): each word
    multiplied by a distinct odd position key (a bijection mod 2**64,
    so any change to any word changes its term), xor-folded over the
    trailing axis, splitmix64-finalized for avalanche.  ``(..., k)``
    integer words -> ``(...)`` int64.  One vectorized helper serves
    snapshot records, journal slots, and the integrity sidecar — a torn
    or bit-rotted line fails it with overwhelming probability, and the
    per-position keys catch the word swaps plain xor would miss.  The
    multilinear shape keeps the hot path at one multiply per word: this
    runs inside every epoch drain, where its cost is bounded against
    the flush itself (the --integrity-overhead gate)."""
    w = np.asarray(words)
    # int64 -> uint64 is a bit-reinterpretation: view when contiguous
    # (the drain's gathered rows always are) instead of copying
    if w.dtype == np.int64 and w.flags.c_contiguous:
        w = w.view(np.uint64)
    elif w.dtype != np.uint64:
        w = w.astype(np.uint64)
    shape = w.shape[:-1]
    w = np.atleast_2d(w)          # 1-D input: keep off scalar ufunc paths
    k = _pos_keys(w.shape[-1])
    # unrolled fold: ufunc .reduce over a short trailing axis is the
    # slowest op on the drain's hot path, and k is <= 8 for every
    # caller (one line = 8 words)
    acc = w[..., 0] * k[0]
    for j in range(1, w.shape[-1]):
        acc = acc ^ (w[..., j] * k[j])
    return _splitmix64(acc).astype(np.int64).reshape(shape)


def _integ_chunks(rowbytes: int) -> int:
    """Checksum words per sidecar row: one per 64 B line of the source
    row, or one for the whole row when rows are sub-line."""
    return rowbytes // LINE if rowbytes % LINE == 0 and rowbytes else 1


def sidecar_checksums(arr: np.ndarray, chunks: int) -> np.ndarray:
    """Per-line checksums of gathered rows: ``(m, ...)`` rows of any
    8-byte-divisible dtype -> ``(m, chunks)`` int64, one word per 64 B
    line (per whole row for sub-line rows).  0 is reserved as the
    sidecar's \"never checksummed\" sentinel, so a computed 0 nudges
    to 1."""
    m = arr.shape[0]
    w = np.ascontiguousarray(arr).reshape(m, -1).view(np.uint64)
    ck = mix_checksums(w.reshape(m, chunks, -1))
    ck[ck == 0] = 1
    return ck


def snap_checksum(rec: np.ndarray) -> int:
    """Mix-then-xor checksum over the first 7 words of a snapshot
    record.  A torn 64 B record line (the only partial-write unit the
    substrate can produce) fails this with overwhelming probability, so
    recovery can reject it without any ordering requirement between the
    record and the ring rows it describes."""
    return int(mix_checksums(np.asarray(rec, np.int64)[:7]))


def snap_record_pack(gen: int, seq: int, a: int, b: int, c: int,
                     d: int = 0) -> np.ndarray:
    """Sealed snapshot record: ``[magic, gen, seq, a, b, c, d, cksum]``
    — exactly one flush line.  ``gen`` is the generation the enclosing
    commit is sealing; ``seq`` picks the record-ring slot (seq %
    SNAP_SLOTS) so a torn append can only damage the slot it targets,
    never the previously sealed records."""
    rec = np.array([SNAP_MAGIC, gen, seq, a, b, c, d, 0], np.int64)
    rec[7] = snap_checksum(rec)
    return rec


def snap_record_parse(rec: np.ndarray) -> Optional[Tuple[int, ...]]:
    """``(gen, seq, a, b, c, d)`` if the record line is intact, else
    ``None`` (torn append, never-written slot, or foreign bytes)."""
    rec = np.asarray(rec, np.int64).ravel()
    if rec.size != SNAP_WORDS or int(rec[0]) != SNAP_MAGIC:
        return None
    if int(rec[7]) != snap_checksum(rec):
        return None
    return tuple(int(x) for x in rec[1:7])


def route_rows(router, n_rows: int, n_shards: int, rr_hint: int = 0
               ) -> np.ndarray:
    """Pure row->shard map for one region.  Routers are functions of the
    ROW INDEX only (never of row contents): reading a row back after a
    crash must not require the row to know where it lives.

    * ``("seg", B)``   — block-cyclic: segment ``row // B`` on shard
      ``(row // B) % n_shards`` (DLL segments, B+Tree leaf ranges);
    * ``("hash", B)``  — splitmix64(row // B) % n_shards (hashmap slab
      segments — the paper's bucket-hash scatter, decoupled from insert
      order; B defaults to 64 rows, so routing stays segment-granular
      and loads take the ~4 KiB block-copy fast path);
    * ``("range",)``   — contiguous equal split;
    * ``("shard", k)`` — pin the whole region to shard k;
    * ``None``         — small regions (headers) pin to shard
      ``rr_hint % n_shards`` (round-robin by creation order, so distinct
      structures' headers spread across shards); larger ones default to
      ~4 KiB block-cyclic segments.
    """
    rows = np.arange(n_rows, dtype=np.int64)
    if n_shards == 1:
        return np.zeros(n_rows, np.int32)
    router = normalize_router(router, n_rows, n_shards, rr_hint)
    kind = router[0]
    if kind == "seg":
        return ((rows // int(router[1])) % n_shards).astype(np.int32)
    if kind == "hash":
        blk = int(router[1]) if len(router) > 1 else 64
        return (_splitmix64(rows // blk) %
                np.uint64(n_shards)).astype(np.int32)
    if kind == "range":
        return np.minimum(rows * n_shards // max(n_rows, 1),
                          n_shards - 1).astype(np.int32)
    if kind == "shard":
        return np.full(n_rows, int(router[1]) % n_shards, np.int32)
    raise ValueError(f"unknown router {router!r}")


def normalize_router(router, n_rows: int, n_shards: int,
                     rr_hint: int = 0):
    """Resolve the ``None`` default to a concrete router — the ONE place
    the defaulting policy lives (route_rows and ShardedRegion both
    consume it)."""
    if router is not None:
        return router
    if n_rows <= 4 * n_shards:
        return ("shard", rr_hint)
    return ("seg", 64)


def router_block(router) -> int:
    """Segment size of a block-granular router (seg/hash), else 0 — the
    load fast path keys off this."""
    if router is None:
        return 0
    if router[0] == "seg":
        return int(router[1])
    if router[0] == "hash":
        return int(router[1]) if len(router) > 1 else 64
    return 0


class _ShardSlice(Region):
    """Per-shard persistent slice of a ShardedRegion.

    Local rows pack the parent's assigned global rows in ascending
    global order; all volatile state lives ONLY in the parent's
    full-shape array — a slice is pure persistence plumbing, so a crash
    has exactly one volatile image to discard."""

    def __init__(self, arena, name, dtype, shape, offset, meta=None,
                 parent=None, gidx=None, arena_index=0):
        super().__init__(arena, name, dtype, shape, offset, meta=meta)
        self.vol = None                 # no independent volatile copy
        self._parent = parent
        self._gidx = gidx               # local row -> global row
        self.arena_index = arena_index  # which shard holds this slice

    def _gather(self, rows: np.ndarray) -> np.ndarray:
        return self._parent._vol_rows(self._gidx[rows])

    def _gather_range(self, lo: int, hi: int) -> np.ndarray:
        return self._parent._vol_rows(self._gidx[lo:hi])

    def write_rows(self, rows: np.ndarray, vals) -> None:
        # sidecar cascades write slice-local rows; the one volatile copy
        # lives in the parent, global-indexed
        self._parent.write_rows(self._gidx[np.asarray(rows, np.int64)],
                                vals)

    def _pack_source(self, rows: np.ndarray):
        return self._parent._pack_source_global(self._gidx[rows])

    def _note_flushed(self, rows: np.ndarray) -> None:
        self._parent._note_flushed_global(self._gidx[rows])

    def _note_persisted(self, rows: np.ndarray) -> None:
        self._parent._note_persisted_global(self._gidx[rows])

    def _note_persisted_range(self, lo: int, hi: int) -> None:
        self._parent._note_persisted_global(self._gidx[lo:hi])

    def _crash_reset(self) -> None:
        pass                            # no volatile state of its own

    def load(self) -> None:
        self._parent.vol[self._gidx] = self._pview()
        self.arena._shadow_overlay(self, vol=self._parent.vol,
                                   gidx=self._gidx)


class ShardedRegion(_RowAccess):
    """Facade with the exact Region API structures use (``vol`` /
    ``mark_rows`` / ``mark_range`` / ``persist_*`` / ``load``), backed
    by per-shard slices.  Marks and flushes partition by the router;
    per-shard line/dedup accounting lands in each shard's FlushStats and
    rolls up through ``ShardedArena.stats``."""

    def __init__(self, arena: "ShardedArena", name: str, dtype,
                 shape: Tuple[int, ...], meta: Optional[bool] = None,
                 router=None, rr_hint: int = 0):
        self.arena = arena
        self.name = name
        self.dtype = np.dtype(dtype)
        self.shape = tuple(shape)
        self.snap = ".snap" in name
        self.jrnl = ".jrnl" in name
        self.integ = name.endswith(".integ")
        self._integ: Optional["ShardedRegion"] = None
        self.meta = (name.endswith("header") or self.snap) \
            if meta is None else meta
        self.rowbytes = int(self.dtype.itemsize *
                            np.prod(shape[1:], dtype=np.int64)) \
            if len(shape) > 1 else self.dtype.itemsize
        self.nbytes = self.rowbytes * shape[0]
        self._init_vol()
        n = self.shape[0]
        self.router = router = normalize_router(router, n, arena.n_shards,
                                                rr_hint)
        self.shard_of = route_rows(router, n, arena.n_shards, rr_hint)
        self.local_of = np.zeros(n, np.int64)
        # block-granular routers (seg/hash) load through a block-level
        # copy: per-shard FULL-block ids over the (nb, B, ...) view
        self._blk = router_block(router)
        nb = (n // self._blk) if self._blk else 0
        self._blocks: List[Optional[np.ndarray]] = []
        self.slices: List[Optional[_ShardSlice]] = []
        for s, shard in enumerate(arena.shards):
            gidx = np.nonzero(self.shard_of == s)[0]
            self.local_of[gidx] = np.arange(gidx.size)
            self._blocks.append(
                np.nonzero(self.shard_of[:nb * self._blk:self._blk] == s)[0]
                if self._blk else None)
            if gidx.size == 0:
                self.slices.append(None)
                continue
            sl = shard.region(name, dtype, (int(gidx.size),) + self.shape[1:],
                              meta=self.meta, _cls=_ShardSlice,
                              parent=self, gidx=gidx, arena_index=s)
            self.slices.append(sl)

    def _init_vol(self) -> None:
        self.vol = np.zeros(self.shape, self.dtype)

    def _crash_reset(self) -> None:
        # the volatile buffer is a LONG-LIVED arena: zero in place so
        # the post-crash reload writes warm pages
        self.vol.fill(0)

    # -- slice plumbing: slices hold no volatile state, so their gathers
    # and paging notes route through the parent with GLOBAL row ids ------
    def _vol_rows(self, grows: np.ndarray) -> np.ndarray:
        return self.vol[grows]

    def _pack_source_global(self, grows: np.ndarray):
        return self.vol, grows

    def _note_flushed_global(self, grows: np.ndarray) -> None:
        pass

    def _note_persisted_global(self, grows: np.ndarray) -> None:
        pass

    # -- shard partitioning ------------------------------------------------
    def _split(self, rows: np.ndarray):
        """Yield (slice, local_rows) per shard holding any of `rows`."""
        shards = self.shard_of[rows]
        for s in np.unique(shards):
            sel = rows[shards == s]
            yield self.slices[s], self.local_of[sel]

    # -- Region API --------------------------------------------------------
    def mark_rows(self, rows: np.ndarray, fresh: bool = False) -> None:
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return
        if self.arena._epoch_depth > 0:
            # buffered globally; the row->shard split happens once per
            # epoch at flush (ShardedWriteSet.mark documents why)
            self.arena.writeset.mark(self, rows, fresh=fresh)
        else:
            self.persist_rows(rows)

    def mark_range(self, lo: int, hi: int, fresh: bool = False) -> None:
        if hi > lo:
            self.mark_rows(np.arange(lo, hi, dtype=np.int64), fresh=fresh)

    def persist_rows(self, rows: np.ndarray) -> None:
        rows = np.asarray(rows, np.int64)
        if rows.size == 0:
            return
        for sl, local in self._split(np.unique(rows)):
            sl.persist_rows(local)

    def persist_range(self, lo: int, hi: int) -> None:
        if hi > lo:
            self.persist_rows(np.arange(lo, hi, dtype=np.int64))

    def persist_all(self) -> None:
        self.persist_range(0, self.shape[0])

    def load(self, concurrency: int = 1) -> None:
        """Reload all shards' rows.  ``concurrency>1`` fans the per-shard
        block copies out on the arena's shard pool: post-crash loads
        write cold pages, and page faults parallelize even where pure
        memcpy bandwidth would not."""
        if concurrency > 1 and self.arena.n_shards > 1:
            list(self.arena.pool().map(self.load_shard,
                                       range(self.arena.n_shards)))
        else:
            for s in range(self.arena.n_shards):
                self.load_shard(s)

    def load_shard(self, s: int) -> None:
        """Reload this region's shard-s rows into the shared volatile
        array.  Block-granular routers (seg/hash) copy whole segments
        through a (blocks, B, ...) view — ~5x the throughput of a
        row-wise scatter, and a C-level copy that releases the GIL, so
        the pooled sharded reopen is actually parallel."""
        sl = self.slices[s]
        if sl is None:
            return
        pv = sl._pview()
        if self._blk:
            B = self._blk
            nb = self.shape[0] // B            # full blocks
            bs = self._blocks[s]
            nfull = bs.size
            if nfull:
                self.vol[:nb * B].reshape((nb, B) + self.shape[1:])[bs] = \
                    pv[:nfull * B].reshape((nfull, B) + self.shape[1:])
            if pv.shape[0] > nfull * B:        # global tail block is ours
                self.vol[nb * B:] = pv[nfull * B:]
        else:
            self.vol[sl._gidx] = pv
        sl.arena._shadow_overlay(sl, vol=self.vol, gidx=sl._gidx)
        # per-shard media read stall — sleeps in the shard pool, so N
        # shards' reload stalls overlap instead of summing
        sl.arena.synth_read(sl.nbytes)


class ShardedArena:
    """N independent arena shards behind the single-arena API, plus a
    manifest that makes the cross-shard generation atomic.

    Commit protocol (manifest-last, the NVTree ordering lifted one
    level):  1. drain every shard's write set — ALL shards' data
    regions, then all shards' metadata regions (the data-before-metadata
    barrier is global, not per shard);  2. commit each shard (flush
    file, bump its header generation, set its valid flag);  3. write the
    manifest.  A crash between shard commits leaves the manifest at the
    previous generation — exactly the generation every shard agrees on,
    which is what recovery reports.
    """

    def __init__(self, path: Optional[str], n_shards: int = 2,
                 synth_line_ns: float = 0.0, pack_flush_rows: int = 0,
                 commit_mode: str = "barrier", synth_fence_ns: float = 0.0,
                 paged: Optional[bool] = None, block_bytes: int = 4096,
                 cache_blocks: int = 1024,
                 integrity: Optional[bool] = None):
        assert n_shards >= 1
        assert commit_mode in ("barrier", "shadow")
        self.path = path
        self.n_shards = int(n_shards)
        # sidecars are declared at the SHARDED level (same router as
        # their source region, so a row's checksum lives on the row's
        # shard); shard sub-arenas must not re-derive their own
        self.integrity = integrity_enabled(integrity)
        # shard sub-arenas are pure persistence backends — the ONE block
        # cache (like the one volatile image it replaces) lives at the
        # sharded level, so shards are always opened unpaged
        self.shards = [Arena(f"{path}.s{k}" if path else None,
                             synth_line_ns, pack_flush_rows,
                             commit_mode=commit_mode, paged=False,
                             integrity=False)
                       for k in range(self.n_shards)]
        self.paged = paged_enabled(paged)
        self.block_bytes = int(block_bytes)
        self.cache_blocks = int(cache_blocks)
        self.cache = None
        if self.paged:
            from repro.core.paging import BlockCache
            self.cache = BlockCache(self.block_bytes, self.cache_blocks)
        for sh in self.shards:
            sh.synth_sleep = True
        self.synth_line_ns = synth_line_ns
        self.pack_flush_rows = pack_flush_rows
        self.commit_mode = commit_mode
        # the fence is a GLOBAL ordering point, so its synthetic stall
        # lives at the sharded level, never per shard
        self.synth_fence_ns = synth_fence_ns
        self.regions: Dict[str, ShardedRegion] = {}
        self.writeset = ShardedWriteSet(self)
        self.generation = 0
        self._epoch_depth = 0
        self._layout_final = False
        self._snap_providers: List = []
        self._local_stats = FlushStats()
        self._man: Optional[np.ndarray] = None
        self._rr = 0
        self._pool: Optional[ThreadPoolExecutor] = None

    # -- stats -------------------------------------------------------------
    @property
    def stats(self) -> FlushStats:
        """Aggregate of every shard's per-shard accounting (plus the
        manifest-level commit calls) — same FlushStats shape callers
        snapshot()/delta() on a plain arena."""
        out = self._local_stats.snapshot()
        for sh in self.shards:
            for f in dataclasses.fields(FlushStats):
                setattr(out, f.name,
                        getattr(out, f.name) + getattr(sh.stats, f.name))
        return out

    def shard_stats(self) -> List[FlushStats]:
        return [sh.stats.snapshot() for sh in self.shards]

    # -- epochs ------------------------------------------------------------
    @contextlib.contextmanager
    def epoch(self):
        self._epoch_depth += 1
        try:
            yield self
        finally:
            self._epoch_depth -= 1
            if self._epoch_depth == 0:
                self.writeset.flush()

    # -- layout ------------------------------------------------------------
    def region(self, name: str, dtype, shape: Tuple[int, ...],
               meta: Optional[bool] = None, router=None) -> ShardedRegion:
        assert not self._layout_final, "layout already finalized"
        assert name not in self.regions
        cls = ShardedRegion
        if self.cache is not None and _paged_eligible(
                name, meta, dtype, shape, self.block_bytes):
            from repro.core.paging import PagedShardedRegion
            cls = PagedShardedRegion
        r = cls(self, name, dtype, shape, meta=meta,
                router=router, rr_hint=self._rr)
        self._rr += 1
        self.regions[name] = r
        return r

    def region_shards(self, name: str, rows: np.ndarray) -> np.ndarray:
        return self.regions[name].shard_of[
            np.asarray(np.atleast_1d(rows), np.int64)].astype(np.int64)

    def finalize(self) -> None:
        assert not self._layout_final
        if self.integrity:
            self._integrity_layout()
        self._layout_final = True
        for sh in self.shards:
            sh.finalize()
        if self.path is None:
            self._man = np.zeros(64, np.uint8)
        else:
            mp = self.path + ".manifest"
            create = not os.path.exists(mp)
            if create:
                with open(mp, "wb") as f:
                    f.truncate(64)
            self._man = np.memmap(mp, dtype=np.uint8, mode="r+",
                                  shape=(64,))
            if create:
                self._write_manifest(valid=False)
            else:
                # the manifest records the shard count precisely so a
                # mis-configured reopen fails loudly instead of mapping
                # the wrong number of backing files
                raw = bytes(self._man[: struct.calcsize(_MAN_FMT)])
                magic, man_shards, man_gen, man_valid = \
                    struct.unpack(_MAN_FMT, raw)
                if magic == _MAN_MAGIC and man_shards != self.n_shards:
                    raise ValueError(
                        f"arena at {self.path!r} was committed with "
                        f"{man_shards} shards, opened with "
                        f"{self.n_shards}")
                if magic == _MAN_MAGIC and man_valid and man_gen > 0:
                    # a valid manifest promises every shard reached at
                    # least its generation (shards can only be AHEAD
                    # across a torn commit).  A shard behind it — or
                    # zeroed because the file vanished and was recreated
                    # above — is media loss, not power loss.
                    for k, sh in enumerate(self.shards):
                        if not (sh.header_valid()
                                and sh.header_generation() >= man_gen):
                            raise ShardLossError(
                                f"shard {k} ({sh.path!r}) lost or behind "
                                f"manifest generation {man_gen}")

    def _integrity_layout(self) -> None:
        """Sharded sidecars: one per covered region, SAME router as the
        source — a row and its checksum always commit through the same
        shard's header, so the cross-shard atomicity argument (manifest-
        last) covers them as a pair."""
        for name, r in list(self.regions.items()):
            if r.meta or r.snap or r.jrnl or r.integ or r.rowbytes % 8:
                continue
            sc = self.region(name + ".integ", np.int64,
                             (r.shape[0], _integ_chunks(r.rowbytes)),
                             meta=False, router=r.router)
            r._integ = sc
            for s in range(self.n_shards):
                if r.slices[s] is not None:
                    r.slices[s]._integ = sc.slices[s]

    def verify_header(self) -> None:
        """ManifestError on garbage manifest magic; delegate per-shard
        header checks to each shard."""
        raw = bytes(self._man[:4])
        if raw not in (_MAN_MAGIC, b"\x00\x00\x00\x00"):
            raise ManifestError(
                f"arena {self.path!r} manifest magic {raw!r} corrupt")
        for sh in self.shards:
            sh.verify_header()

    def _pimage(self, region: "ShardedRegion") -> np.ndarray:
        """Committed persistent image assembled across shards (home
        bytes + each shard's authoritative bank overlay) — pure read."""
        img = np.zeros(region.shape, region.dtype)
        for sl in region.slices:
            if sl is None:
                continue
            img[sl._gidx] = sl._pview()
            sh = sl.arena
            if sh.commit_mode == "shadow":
                mask = sh._shadow_masks[sh._shadow_auth_bank].get(sl.name)
                if mask is not None and mask.any():
                    rows = np.nonzero(mask)[0]
                    img[sl._gidx[rows]] = sh._shadow_mirror(
                        sl, sh._shadow_auth_bank)[rows]
        return img

    def verify_region(self, region) -> np.ndarray:
        if isinstance(region, str):
            region = self.regions[region]
        sc = region._integ
        if sc is None:
            return np.empty(0, np.int64)
        ck = sidecar_checksums(self._pimage(region), sc.shape[1])
        ref = self._pimage(sc)
        bad = (ref != 0) & (ck != ref)
        for sh in self.shards:
            sh.synth_read((region.nbytes + sc.nbytes) // self.n_shards)
        return np.nonzero(bad.any(axis=1))[0]

    def scrub(self, raise_on_error: bool = False
              ) -> Dict[str, np.ndarray]:
        bad: Dict[str, np.ndarray] = {}
        for name, r in self.regions.items():
            if r._integ is None:
                continue
            rows = self.verify_region(r)
            if rows.size:
                bad[name] = rows
        if bad and raise_on_error:
            name, rows = next(iter(bad.items()))
            raise CorruptLineError(name, rows,
                                   detail=f"scrub: {len(bad)} region(s)")
        return bad

    # -- order snapshots (DESIGN.md §10) -----------------------------------
    def add_snapshot_provider(self, fn) -> None:
        self._snap_providers.append(fn)

    # -- manifest / commit protocol ----------------------------------------
    def _write_manifest(self, valid: bool) -> None:
        man = struct.pack(_MAN_FMT, _MAN_MAGIC, self.n_shards,
                          self.generation, valid)
        self._man[: len(man)] = np.frombuffer(man, np.uint8)
        if isinstance(self._man, np.memmap):
            self._man.flush()

    def header_generation(self) -> int:
        raw = bytes(self._man[: struct.calcsize(_MAN_FMT)])
        magic, _, gen, _ = struct.unpack(_MAN_FMT, raw)
        return int(gen) if magic == _MAN_MAGIC else 0

    def header_valid(self) -> bool:
        raw = bytes(self._man[: struct.calcsize(_MAN_FMT)])
        magic, _, gen, valid = struct.unpack(_MAN_FMT, raw)
        if magic != _MAN_MAGIC or not valid:
            return False
        # the manifest seals generation `gen`; every shard must have
        # reached at least that far (shards ahead are torn territory the
        # structures' count-bounded recovery already handles)
        return all(sh.header_valid() and sh.header_generation() >= gen
                   for sh in self.shards)

    def _fence(self) -> None:
        """The global ordering point — one per barrier phase plus one
        per commit seal in barrier mode, exactly ONE per shadow commit."""
        self._local_stats.fences += 1
        if self.synth_fence_ns:
            ns = int(self.synth_fence_ns)
            self._local_stats.fence_ns += ns
            t0 = time.perf_counter_ns()
            while time.perf_counter_ns() - t0 < ns:
                pass

    def commit(self, _crash_after_shard: Optional[int] = None) -> None:
        """Drain write sets (global data-before-metadata in barrier
        mode), commit each shard, manifest LAST.  ``_crash_after_shard=k``
        is the crash-injection hook for the inter-shard commit window:
        shards 0..k commit, then power fails before the manifest — the
        fuzzer's sweep point (tests/test_sharded_arena.py).

        Shadow mode: fold every shard's previous bank home and drain the
        write set in one pooled phase (no cross-shard barrier), seal
        each shard's target bank, pay the SINGLE ordering point, then
        flip every shard's header and write the manifest last — the
        existing cross-shard atomicity protocol carries over unchanged.
        ``_crash_after_shard=-1`` crashes after the seals but before any
        flip (the torn-flip window's leading edge).  Spanned as
        ``persist.commit`` (``repro.obs``)."""
        with obs.span("persist.commit"):
            self._commit(_crash_after_shard)

    def _commit(self, _crash_after_shard: Optional[int]) -> None:
        if self.commit_mode == "shadow":
            if self.n_shards > 1:
                list(self.pool().map(lambda sh: sh._shadow_collapse(),
                                     self.shards))
            else:
                self.shards[0]._shadow_collapse()
            self.writeset.flush()
            for sh in self.shards:
                sh._shadow_seal()
                if isinstance(sh._mm, np.memmap):
                    sh._mm.flush()
            if _crash_after_shard is not None and _crash_after_shard < 0:
                self.crash()
                return
            self._fence()                  # the single ordering point
        else:
            self.writeset.flush()
            self._fence()
        tgt = self.generation + 1
        for k, sh in enumerate(self.shards):
            if isinstance(sh._mm, np.memmap):
                sh._mm.flush()
            sh.generation = tgt
            sh._write_header(valid=True)
            if isinstance(sh._mm, np.memmap):
                sh._mm.flush()
            if _crash_after_shard is not None and k == _crash_after_shard:
                self.crash()
                return
        self.generation = tgt
        self._write_manifest(valid=True)
        self._local_stats.calls += 1
        if self.commit_mode == "shadow":
            for sh in self.shards:
                sh._shadow_retire()

    def invalidate(self) -> None:
        self._write_manifest(valid=False)

    # -- crash simulation ---------------------------------------------------
    def crash(self) -> None:
        """Discard every shard's pending marks and the one volatile image
        per region (slices carry none).  The volatile buffer is a
        LONG-LIVED arena: it zeroes in place instead of reallocating, so
        the post-crash reload writes warm pages — allocator churn and
        page faults stay out of the recovery-critical path."""
        self.writeset.discard()
        for sh in self.shards:
            sh._shadow_discard()
        for r in self.regions.values():
            r._crash_reset()

    def reopen(self, concurrency: int = 1,
               exclude: Tuple[str, ...] = ()) -> None:
        """Reload every region's volatile copy from the shard files —
        per shard, in the flush pool when ``concurrency>1`` (the loads
        are big GIL-releasing copies, so N shards reopen in parallel) —
        then re-anchor the generation to the manifest's.  ``exclude``
        names regions the caller will load itself (RecoveryManager's
        per-region load stages)."""
        # shadow bank authority is the MANIFEST generation: a shard whose
        # header flipped ahead of a torn manifest write must still
        # overlay the manifest generation's bank (intact by parity, and
        # value-identical to its own already-folded home rows)
        man_gen = self.header_generation()
        for sh in self.shards:
            sh._shadow_parse(authority_gen=man_gen)
        regions = [r for n, r in self.regions.items() if n not in exclude]
        # paged regions reload lazily: one cheap block-pool reset, and
        # the post-crash working set faults in on demand
        for r in regions:
            if r.is_paged:
                r.load()
        regions = [r for r in regions if not r.is_paged]

        def load_shard(s: int) -> None:
            # one aggregated media stall per shard, not one per region
            with self.shards[s].stall_scope():
                for r in regions:
                    r.load_shard(s)

        if concurrency > 1 and self.n_shards > 1:
            list(self.pool().map(load_shard, range(self.n_shards)))
        else:
            for s in range(self.n_shards):
                load_shard(s)
        self.generation = max(self.generation, self.header_generation())

    # -- pool ---------------------------------------------------------------
    def pool(self) -> ThreadPoolExecutor:
        """Shared shard-flush/reopen pool.  Sized to the shard count, not
        the core count: flush stalls sleep (I/O-like), so more waiters
        than cores still overlap."""
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.n_shards,
                thread_name_prefix="arena-shard")
        return self._pool

    def close(self) -> None:
        for sh in self.shards:
            sh.close()
        if isinstance(self._man, np.memmap):
            self._man.flush()
        self._man = None
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None


def open_arena(path: Optional[str], layout: Dict[str, Tuple],
               n_shards: int = 1, **kw):
    """Create/open an arena with the given layout.  Layout values are
    ``(dtype, shape)`` or ``(dtype, shape, router)`` — the router steers
    rows across shards when ``n_shards > 1`` (route_rows documents the
    specs).  ``n_shards=1`` returns the plain single Arena: byte- and
    accounting-identical to the pre-sharding path."""
    a = Arena(path, **kw) if n_shards == 1 else \
        ShardedArena(path, n_shards=n_shards, **kw)
    for name, spec in layout.items():
        dtype, shape = spec[0], spec[1]
        router = spec[2] if len(spec) > 2 else None
        a.region(name, dtype, shape, router=router)
    a.finalize()
    return a
