"""Media-fault injection harness (DESIGN.md §13).

The integrity layer's whole claim — every single-line corruption in
committed territory is *detected or harmless* — is only as strong as
the injector behind the sweep.  These helpers corrupt the committed
image of a row at its home slot in the owning shard's mapping, where
post-crash recovery reads it.

Faults by taxonomy (core.arena error types):

* ``flip_bits`` / ``stuck_line``   -> ``CorruptLineError`` territory:
  in-place byte rot inside a committed row's line(s), visible to
  ``Arena.scrub()`` and the paged fault path;
* ``truncate_shard`` / ``remove_shard`` -> ``ShardLossError``
  territory: whole-file media loss, detected at fresh-process open
  (use BETWEEN arena generations — the helpers operate on the backing
  files, never through a live mapping);
* ``corrupt_header`` / ``corrupt_manifest`` -> ``ManifestError``
  territory: scribbled commit-pointer magic, detected by
  ``verify_header()`` in the recovery prologue.

Everything returns enough to assert precision (which bytes changed),
and ``flip_bits`` is an involution — inject twice to undo.
"""
from __future__ import annotations

import os
from typing import Tuple

import numpy as np

from repro.core.arena import LINE, Arena, ShardedArena

__all__ = [
    "flip_bits", "stuck_line", "truncate_shard", "remove_shard",
    "corrupt_header", "corrupt_manifest", "committed_row_offset",
]


def committed_row_offset(arena, region, row: int) -> Tuple[Arena, int, int]:
    """(owning plain arena, byte offset of the row's committed image in
    that arena's mapping, rowbytes).  Resolves sharded regions to the
    owning shard — valid before or after a crash."""
    if isinstance(region, str):
        region = arena.regions[region]
    if isinstance(arena, ShardedArena):
        s = int(region.shard_of[row])
        return committed_row_offset(arena.shards[s], region.slices[s],
                                    int(region.local_of[row]))
    return arena, region.offset + row * region.rowbytes, region.rowbytes


def flip_bits(arena, region, row: int, byte: int = 0,
              mask: int = 0x01) -> int:
    """XOR ``mask`` into one byte of the committed image of
    ``(region, row)`` — the single-bit-rot injection.  Returns the
    absolute byte offset that changed (inject again to undo)."""
    a, off, rb = committed_row_offset(arena, region, row)
    assert 0 <= byte < rb
    a._mm[off + byte] ^= np.uint8(mask)
    if isinstance(a._mm, np.memmap):
        a._mm.flush()
    return off + byte


def stuck_line(arena, region, row: int, line: int = 0,
               value: int = 0xFF) -> Tuple[int, int]:
    """Overwrite one 64 B line of the committed row image with a
    stuck-at pattern (a failed-cell fault).  Clamped to the row so the
    injection stays a SINGLE-row corruption; returns the [lo, hi) byte
    range overwritten."""
    a, off, rb = committed_row_offset(arena, region, row)
    lo = off + line * LINE
    hi = min(off + rb, lo + LINE)
    assert lo < hi, "line index beyond the row"
    a._mm[lo:hi] = np.uint8(value)
    if isinstance(a._mm, np.memmap):
        a._mm.flush()
    return lo, hi


def _shard_path(arena, shard: int) -> str:
    if isinstance(arena, str):
        return f"{arena}.s{shard}"
    assert arena.path is not None, "file faults need a file-backed arena"
    if isinstance(arena, ShardedArena):
        return arena.shards[shard].path
    return arena.path


def truncate_shard(arena, shard: int = 0, nbytes: int = 0) -> str:
    """Truncate a shard's backing file to ``nbytes`` — partial media
    loss.  File-level: use between process generations (after a crash,
    before the fresh open that raises ``ShardLossError``)."""
    path = _shard_path(arena, shard)
    with open(path, "r+b") as f:
        f.truncate(nbytes)
    return path


def remove_shard(arena, shard: int = 0) -> str:
    """Delete a shard's backing file outright — total media loss of
    one shard.  File-level, like ``truncate_shard``."""
    path = _shard_path(arena, shard)
    os.remove(path)
    return path


def corrupt_header(arena, shard: int = 0) -> None:
    """Scribble a commit header's magic word (plain arena, or one shard
    of a sharded one) — ``verify_header()`` raises ``ManifestError``."""
    a = arena.shards[shard] if isinstance(arena, ShardedArena) else arena
    a._mm[:4] = np.frombuffer(b"ROT!", np.uint8)
    if isinstance(a._mm, np.memmap):
        a._mm.flush()


def corrupt_manifest(arena) -> None:
    """Scribble a sharded arena's manifest magic — the cross-shard
    commit pointer itself is the corrupted medium."""
    assert isinstance(arena, ShardedArena)
    arena._man[:4] = np.frombuffer(b"ROT!", np.uint8)
    if isinstance(arena._man, np.memmap):
        arena._man.flush()
