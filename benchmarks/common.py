"""Shared benchmark plumbing.

Scaling note (documented in EXPERIMENTS.md): the paper initializes 200M
entries and applies 100M ops on a 96-core Optane machine.  This harness
runs the same *workload shapes* scaled down (default 200k init / 100k ops)
on the CPU host.  Two metrics are reported per cell:

* exact flush accounting (lines / bytes) — medium-independent, directly
  comparable to the paper's flush-count reasoning;
* wall time with a synthetic per-line flush latency (default 250 ns,
  ~Optane clwb+fence cost) so the fully/partly *time* ratios reproduce
  the paper's regime (flush-dominated DLL, mixed B+Tree/hashmap).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core.arena import open_arena
from repro.pstruct.bptree import BPTree
from repro.pstruct.dll import DoublyLinkedList
from repro.pstruct.hashmap import Hashmap

MODES = ("full", "partly")
SYNTH_LINE_NS = 250.0     # emulated clwb+fence cost per 64B line
# Ops are applied in vectorized batches (the TPU-framework adaptation of
# the paper's single-op loop).  64 keeps flush patterns close to per-op
# (inner-node / chain-pointer rewrites are not over-amortized) while
# letting numpy vectorize the traversals.
BATCH = 64


def arena_fields(a=None, **over) -> Dict:
    """Substrate fields stamped on EVERY bench row (shard count,
    persisted arena bytes) so rows from different configurations stay
    self-describing in the JSON artifacts.  Rows with no arena behind
    them (raw chain primitives, the ckpt restore) stamp the working-set
    bytes instead."""
    f = {"n_shards": 1, "arena_bytes": 0,
         "block_bytes": 0, "cache_blocks": 0, "peak_resident_bytes": 0,
         "integrity": False, "integrity_lines": 0}
    if a is not None:
        f = {"n_shards": int(getattr(a, "n_shards", 1)),
             "arena_bytes": int(sum(r.nbytes for r in a.regions.values())),
             "block_bytes": 0, "cache_blocks": 0, "peak_resident_bytes": 0,
             # checksum-sidecar accounting (DESIGN.md §13) rides on every
             # row so integrity-on and -off artifacts stay distinguishable
             "integrity": bool(getattr(a, "integrity", False)),
             "integrity_lines": int(a.stats.integrity_lines)}
        # paged arenas (DESIGN.md §12) additionally stamp the block-cache
        # geometry and the high-water resident footprint, so paged rows
        # carry their memory budget next to their timings
        cache = getattr(a, "cache", None)
        if cache is not None:
            f.update(block_bytes=int(cache.block_bytes),
                     cache_blocks=int(cache.cache_blocks),
                     peak_resident_bytes=int(cache.peak_resident_bytes))
    f.update(over)
    return f


@dataclasses.dataclass
class Cell:
    structure: str
    mode: str
    workload: str
    n_ops: int
    wall_s: float
    flush_s: float
    lines: int
    bytes: int
    saved_lines: int = 0   # epoch write-set dedup vs per-call accounting
    dedup_rows: int = 0    # duplicate row marks absorbed per epoch

    @property
    def flush_frac(self) -> float:
        return self.flush_s / self.wall_s if self.wall_s else 0.0


def make_structure(kind: str, mode: str, capacity: int,
                   synth_line_ns: float = SYNTH_LINE_NS,
                   integrity: Optional[bool] = None):
    if kind == "dll":
        a = open_arena(None, DoublyLinkedList.layout(capacity, mode),
                       synth_line_ns=synth_line_ns, integrity=integrity)
        return a, DoublyLinkedList(a, capacity, mode)
    if kind == "bptree":
        a = open_arena(None, BPTree.layout(max(64, capacity // 4),
                                           capacity, mode),
                       synth_line_ns=synth_line_ns, integrity=integrity)
        return a, BPTree(a, max(64, capacity // 4), capacity, mode)
    if kind == "hashmap":
        a = open_arena(None, Hashmap.layout(capacity, mode),
                       synth_line_ns=synth_line_ns, integrity=integrity)
        return a, Hashmap(a, capacity, mode)
    raise ValueError(kind)


def run_workload(kind: str, mode: str, workload: str, n_init: int,
                 n_ops: int, seed: int = 0,
                 synth_line_ns: float = SYNTH_LINE_NS) -> Cell:
    """workload: insert | delete | mixed_1_1 | mixed_2_1 | mixed_4_1."""
    rng = np.random.default_rng(seed)
    capacity = n_init + n_ops + 1024
    a, s = make_structure(kind, mode, capacity, synth_line_ns)

    keyspace = rng.permutation(capacity * 2).astype(np.int64)
    init_keys = keyspace[:n_init]
    new_keys = keyspace[n_init:n_init + n_ops]
    vals = rng.integers(0, 1 << 40, (max(n_init, n_ops), 7)).astype(np.int64)

    # ---- init (not timed) ----
    if kind == "dll":
        for i in range(0, n_init, 4096):
            s.append_batch(vals[i:min(i + 4096, n_init)])
    else:
        for i in range(0, n_init, 4096):
            s.insert_batch(init_keys[i:i + 4096], vals[i:i + 4096])
    a.commit()
    base_stats = a.stats.snapshot()

    # ---- timed ops ----
    if workload == "insert":
        ratio = (1, 0)
    elif workload == "delete":
        ratio = (0, 1)
    else:
        k = int(workload.split("_")[1])
        ratio = (k, 1)

    ins_ptr = del_ptr = 0
    t0 = time.perf_counter()
    done = 0
    while done < n_ops:
        for _ in range(ratio[0]):
            if done >= n_ops:
                break
            m = min(BATCH, n_ops - done)
            if kind == "dll":
                s.append_batch(vals[(ins_ptr % n_ops):(ins_ptr % n_ops) + m]
                               if (ins_ptr % n_ops) + m <= n_ops
                               else vals[:m])
            else:
                ks = new_keys[ins_ptr:ins_ptr + m]
                s.insert_batch(ks, vals[:len(ks)])
            ins_ptr += m
            done += m
        for _ in range(ratio[1]):
            if done >= n_ops:
                break
            m = min(BATCH, n_ops - done)
            if kind == "dll":
                s.pop_front_batch(m)
            elif kind == "bptree":
                ks = init_keys[del_ptr:del_ptr + m]
                if len(ks) == 0:
                    ks = new_keys[del_ptr - n_init:del_ptr - n_init + m]
                s.delete_batch(ks)
            else:
                ks = init_keys[del_ptr:del_ptr + m]
                if len(ks) == 0:
                    ks = new_keys[del_ptr - n_init:del_ptr - n_init + m]
                s.remove_batch(ks)
            del_ptr += m
            done += m
    wall = time.perf_counter() - t0
    d = a.stats.delta(base_stats)
    return Cell(kind, mode, workload, n_ops, wall,
                d.fence_ns * 1e-9, d.lines, d.bytes,
                saved_lines=d.saved_lines, dedup_rows=d.dedup_rows)


def fmt_table(rows: List[Dict], cols: List[str]) -> str:
    widths = [max(len(c), *(len(str(r[c])) for r in rows)) for c in cols]
    out = [" | ".join(c.ljust(w) for c, w in zip(cols, widths))]
    out.append("-|-".join("-" * w for w in widths))
    for r in rows:
        out.append(" | ".join(str(r[c]).ljust(w)
                              for c, w in zip(cols, widths)))
    return "\n".join(out)


def speedup(t_full: float, t_partly: float) -> str:
    return f"{(t_full / t_partly - 1) * 100:+.1f}%"
