"""flush_batching — per-call vs epoch-batched flush line accounting.

The write-set layer (repro.core.writeset, DESIGN.md §2) dedups dirty rows
and coalesces adjacent lines once per *epoch* instead of once per
``persist_rows`` call.  This micro-bench quantifies the saving on the
paper's workloads, at three batching granularities:

* ``per_call``  — one accounting call per mark (the write set's
  would-be counter).  An upper bound on pre-writeset cost: structures
  that already batched an op's dirty rows per region (B+Tree) sat at
  the per_op level, while multi-round paths (DLL delete) really did
  flush per call;
* ``per_op``    — one epoch per structure operation (the default after
  the refactor: every ``insert_batch``/``delete_batch`` is an epoch).
  This measured row is the honest pre-writeset baseline for B+Tree;
* ``per_group`` — one epoch wrapped around GROUP consecutive ops (the
  serving pattern: kvcache.alloc spans evict+append+commit).
  ``save_vs_per_op`` compares against the measured per_op row.

The ``n_shards`` sweep (DESIGN.md §7) measures FLUSH-EPOCH THROUGHPUT
of the sharded arena on the same mixed B+Tree workload: ops accumulate
marks in the epoch (untimed — structure CPU is not the flush path),
then the timed section is exactly the epoch drain + commit.  The sweep
runs in the stall-dominated regime (synthetic per-line latency at 4x
the 250 ns base so the flush stall stays above this host's timer
wakeup slack): a single arena pays the whole stall serially, N shards
pay 1/N each, overlapped in the flush pool — the medium-independent
line/dedup accounting is asserted IDENTICAL across shard counts.

Emits BENCH_flush.json next to the repo root (CI artifact).

Run: ``PYTHONPATH=src python -m benchmarks.flush_batching [--quick]``
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import numpy as np

from benchmarks.common import arena_fields, make_structure
from repro.core.arena import open_arena
from repro.pstruct.bptree import BPTree
from repro.pstruct.dll import DoublyLinkedList

GROUP = 8  # ops fused per outer epoch in the per_group variant
SHARD_COUNTS = (1, 2, 4, 8)


def _bptree_mixed(n_init: int, n_ops: int, batch: int, group: int,
                  seed: int = 0) -> Dict:
    """Mixed 1:1 insert/delete on the partly-persistent B+Tree."""
    rng = np.random.default_rng(seed)
    capacity = n_init + n_ops + 1024
    a, t = make_structure("bptree", "partly", capacity, synth_line_ns=0)
    keyspace = rng.permutation(capacity * 2).astype(np.int64)
    init_keys = keyspace[:n_init]
    new_keys = keyspace[n_init:n_init + n_ops]
    vals = rng.integers(0, 1 << 40, (max(n_init, n_ops), 7)).astype(np.int64)
    for i in range(0, n_init, 4096):
        t.insert_batch(init_keys[i:i + 4096], vals[i:i + 4096])
    a.commit()
    base = a.stats.snapshot()

    ops = []
    done = ins = rm = 0
    while done < n_ops:
        m = min(batch, n_ops - done)
        ops.append(("ins", new_keys[ins:ins + m], vals[:m]))
        ins += m
        done += m
        if done >= n_ops:
            break
        m = min(batch, n_ops - done)
        ops.append(("del", init_keys[rm:rm + m], None))
        rm += m
        done += m

    for g in range(0, len(ops), group):
        chunk = ops[g:g + group]
        if group > 1:
            with a.epoch():
                _apply(t, chunk)
            a.commit()
        else:
            _apply(t, chunk)
            a.commit()
    d = a.stats.delta(base)
    return {"lines": d.lines, "saved_lines": d.saved_lines,
            "snapshot_lines": d.snapshot_lines,
            "dedup_rows": d.dedup_rows, "epochs": d.epochs,
            "fences": d.fences,
            "per_call_lines": d.lines + d.saved_lines,
            **arena_fields(a)}


def _apply(t, chunk) -> None:
    for op, ks, vs in chunk:
        if op == "ins":
            t.insert_batch(ks, vs)
        else:
            t.delete_batch(ks)


def _dll_delete(n_init: int, n_ops: int, batch: int, seed: int = 0) -> Dict:
    """Scattered DLL deletes: the multi-round unlink marked the same
    predecessor rows and the header once per round pre-refactor — the
    per-op epoch already dedups those."""
    rng = np.random.default_rng(seed)
    a, d = make_structure("dll", "partly", n_init + 64, synth_line_ns=0)
    vals = rng.integers(0, 1 << 40, (n_init, 7)).astype(np.int64)
    for i in range(0, n_init, 4096):
        d.append_batch(vals[i:i + 4096])
    a.commit()
    base = a.stats.snapshot()
    ids = rng.permutation(n_init)[:n_ops].astype(np.int64)
    for i in range(0, n_ops, batch):
        d.delete_batch(ids[i:i + batch])
        a.commit()
    dd = a.stats.delta(base)
    # snapshot_lines (DLL order snapshots, DESIGN.md §10) reported
    # SEPARATELY: lines/saved_lines stay bit-comparable to the
    # pre-snapshot artifacts
    return {"lines": dd.lines, "saved_lines": dd.saved_lines,
            "snapshot_lines": dd.snapshot_lines,
            "dedup_rows": dd.dedup_rows, "epochs": dd.epochs,
            "fences": dd.fences,
            "per_call_lines": dd.lines + dd.saved_lines,
            **arena_fields(a)}


def _sharded_flush(n_shards: int, n_init: int, n_ops: int, batch: int,
                   group: int, synth_ns: float, seed: int = 0) -> Dict:
    """Mixed 1:1 insert/delete B+Tree on an ``n_shards`` arena; returns
    the flush-phase wall (epoch drains + commits only) and the exact
    line accounting.  ``n_shards=1`` is the plain single Arena — the
    pre-sharding baseline, spin-exact stalls and all."""
    rng = np.random.default_rng(seed)
    capacity = n_init + n_ops + 1024
    layout = BPTree.layout(max(64, capacity // 4), capacity, "partly")
    a = open_arena(None, layout, n_shards=n_shards, synth_line_ns=synth_ns)
    t = BPTree(a, max(64, capacity // 4), capacity, "partly")
    keyspace = rng.permutation(capacity * 2).astype(np.int64)
    init_keys = keyspace[:n_init]
    new_keys = keyspace[n_init:n_init + n_ops]
    vals = rng.integers(0, 1 << 40, (max(n_init, n_ops), 7)).astype(np.int64)
    for i in range(0, n_init, 4096):
        t.insert_batch(init_keys[i:i + 4096], vals[i:i + 4096])
    a.commit()
    base = a.stats.snapshot()
    ops = []
    done = ins = rm = 0
    while done < n_ops:
        m = min(batch, n_ops - done)
        ops.append(("ins", new_keys[ins:ins + m], vals[:m]))
        ins += m
        done += m
        if done >= n_ops:
            break
        m = min(batch, n_ops - done)
        ops.append(("del", init_keys[rm:rm + m], None))
        rm += m
        done += m
    flush_wall = 0.0
    for g in range(0, len(ops), group):
        # marks accumulate inside the epoch untimed (structure CPU is
        # not the flush path); the timed section is the drain + commit
        a._epoch_depth += 1
        _apply(t, ops[g:g + group])
        a._epoch_depth -= 1
        t0 = time.perf_counter()
        a.writeset.flush()
        a.commit()
        flush_wall += time.perf_counter() - t0
    d = a.stats.delta(base)
    a.close()    # release the shard pool + memmap handles per sweep point
    return {**arena_fields(a),
            "flush_wall_s": round(flush_wall, 6),
            "lines": d.lines, "saved_lines": d.saved_lines,
            "snapshot_lines": d.snapshot_lines,
            "dedup_rows": d.dedup_rows, "epochs": d.epochs,
            "fences": d.fences,
            "lines_per_s": int(d.lines / max(flush_wall, 1e-9))}


def sharded_sweep(n_init: int, n_ops: int, batch: int = 256,
                  group: int = 32, synth_ns: float = 1000.0,
                  repeats: int = 2) -> List[Dict]:
    """Flush-epoch throughput vs shard count, interleaved best-of-N (the
    noise filter every bench here uses on this shared host).

    ``synth_ns`` scales the per-line stall so stall-per-epoch lands in
    the several-ms range where this host's sleep wakeup slack (~1 ms)
    cannot mask the overlap; the line counts stay exact at any scale."""
    best: Dict[int, Dict] = {}
    for _ in range(repeats):
        for ns in SHARD_COUNTS:
            r = _sharded_flush(ns, n_init, n_ops, batch, group, synth_ns)
            if ns not in best or r["flush_wall_s"] < best[ns]["flush_wall_s"]:
                best[ns] = r
    rows = [best[ns] for ns in SHARD_COUNTS]
    base = rows[0]
    for r in rows:
        r["x_vs_1shard"] = round(base["flush_wall_s"]
                                 / max(r["flush_wall_s"], 1e-9), 2)
        # the medium-independent accounting must not depend on sharding
        assert (r["lines"], r["saved_lines"], r["dedup_rows"]) == \
            (base["lines"], base["saved_lines"], base["dedup_rows"]), rows
    return rows


def paged_parity(n_init: int, n_ops: int, batch: int = 256,
                 group: int = 16, synth_ns: float = 4000.0,
                 repeats: int = 3) -> Dict:
    """The ``--paged-parity`` gate (DESIGN.md §12): the paged backend
    must not tax the flush path when the working set fits the block
    cache.  Scattered DLL deletes (the fully block-routed structure),
    same seed, paged vs unpaged: the write-set drain gathers rows
    through the block cache instead of slicing the volatile array, and
    with ZERO evictions (cache-fitting) the line/dedup/fence accounting
    must be bit-identical and flush lines/s within 5%.  Stall-dominated
    regime (``synth_ns`` per line) — scaled, like the sharded sweep's
    stall, until the modeled latency clears this host's per-epoch
    Python overhead; the medium-independent counts stay exact at any
    scale."""
    def one(paged: bool) -> Dict:
        rng = np.random.default_rng(0)
        cap = n_init + 64
        layout = DoublyLinkedList.layout(cap, "partly")
        a = open_arena(None, layout, synth_line_ns=synth_ns, paged=paged,
                       block_bytes=4096,
                       cache_blocks=(cap * 64) // 4096 + 16)
        d = DoublyLinkedList(a, cap, "partly")
        vals = rng.integers(0, 1 << 40, (n_init, 7)).astype(np.int64)
        for i in range(0, n_init, 4096):
            d.append_batch(vals[i:i + 4096])
        a.commit()
        ids = rng.permutation(n_init)[:n_ops].astype(np.int64)
        base = a.stats.snapshot()
        flush_wall = 0.0
        for g in range(0, n_ops, batch * group):
            a._epoch_depth += 1
            for i in range(g, min(g + batch * group, n_ops), batch):
                d.delete_batch(ids[i:i + batch])
            a._epoch_depth -= 1
            t0 = time.perf_counter()
            a.writeset.flush()
            a.commit()
            flush_wall += time.perf_counter() - t0
        st = a.stats.delta(base)
        cache = getattr(a, "cache", None)
        row = {**arena_fields(a), "paged": paged,
               "flush_wall_s": round(flush_wall, 6),
               "lines": st.lines, "saved_lines": st.saved_lines,
               "snapshot_lines": st.snapshot_lines,
               "dedup_rows": st.dedup_rows, "epochs": st.epochs,
               "fences": st.fences,
               "evictions": int(cache.evictions) if cache else 0,
               "spills": int(cache.spills) if cache else 0,
               "lines_per_s": int(st.lines / max(flush_wall, 1e-9))}
        a.close()
        return row

    best: Dict[bool, Dict] = {}
    for _ in range(repeats):
        for paged in (False, True):
            r = one(paged)
            if (paged not in best
                    or r["flush_wall_s"] < best[paged]["flush_wall_s"]):
                best[paged] = r
    up, pg = best[False], best[True]
    return {"workload": "dll scattered deletes, stall-dominated, "
                        "working set fits the block cache",
            "synth_line_ns": synth_ns,
            "rows": [up, pg],
            "lines_per_s_ratio": round(
                pg["lines_per_s"] / max(up["lines_per_s"], 1), 3)}


def run(n_init: int = 20000, n_ops: int = 20000,
        batch: int = 64) -> List[Dict]:
    rows = []
    for label, group in (("bptree_mixed/per_op", 1),
                         (f"bptree_mixed/per_{GROUP}_ops", GROUP)):
        r = _bptree_mixed(n_init, n_ops, batch, group)
        r["grouping"] = label
        rows.append(r)
    # honest baseline for the grouped variant: the MEASURED per-op run
    # (one flush per region per op — the pre-writeset behaviour), not the
    # per-mark reconstruction, which double-counts rows a single op marks
    # from several sub-steps.
    per_op_lines = rows[0]["lines"]
    rows[1]["save_vs_per_op"] = (
        f"{100 * (per_op_lines - rows[1]['lines']) / max(per_op_lines, 1):.1f}%")
    rows[0]["save_vs_per_op"] = "0.0%"
    r = _dll_delete(n_init, min(n_ops, n_init // 2), batch)
    r["grouping"] = "dll_delete/per_op"
    # pre-refactor DLL delete_batch flushed each unlink round separately,
    # so the per-mark baseline IS its per-call behaviour.
    r["save_vs_per_op"] = (
        f"{100 * r['saved_lines'] / max(r['per_call_lines'], 1):.1f}%")
    rows.append(r)
    for r in rows:
        save = r["per_call_lines"] - r["lines"]
        r["save_vs_per_call"] = f"{100 * save / max(r['per_call_lines'], 1):.1f}%"
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--paged-parity", action="store_true",
                    help="run ONLY the paged-vs-unpaged flush parity "
                         "gate: with the working set inside the block "
                         "cache, line accounting must be bit-identical "
                         "and paged flush lines/s within 5% "
                         "(DESIGN.md §12); merges a paged_parity "
                         "section into --out")
    ap.add_argument("--out", default="BENCH_flush.json")
    args = ap.parse_args()
    if args.paged_parity:
        pp = paged_parity(*( (4000, 4096) if args.quick
                             else (12000, 8192) ))
        for r in pp["rows"]:
            print(f"  paged={'on' if r['paged'] else 'off':>3}: wall "
                  f"{r['flush_wall_s']}s, {r['lines']} lines, "
                  f"{r['lines_per_s']} lines/s, "
                  f"evictions={r['evictions']} spills={r['spills']}")
        print(f"paged/unpaged flush throughput: "
              f"{pp['lines_per_s_ratio']}x (gate >= 0.95)")
        try:
            with open(args.out) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
        data["paged_parity"] = pp
        with open(args.out, "w") as f:
            json.dump(data, f, indent=1)
        print(f"-> {args.out}")
        up, pg = pp["rows"]
        # the cache-fitting premise: no eviction, no spill on the paged
        # side, so every drain gather hits resident blocks
        assert pg["evictions"] == 0 and pg["spills"] == 0, pg
        # medium-independent accounting must not see the backend at all
        for k in ("lines", "saved_lines", "snapshot_lines", "dedup_rows",
                  "epochs", "fences"):
            assert up[k] == pg[k], (k, up, pg)
        # ... and the stall-dominated flush wall must stay within 5%
        if not args.quick:
            assert pp["lines_per_s_ratio"] >= 0.95, pp
        return 0
    n_init, n_ops = (4000, 4000) if args.quick else (20000, 20000)
    rows = run(n_init, n_ops)
    from benchmarks.common import fmt_table
    cols = ["grouping", "per_call_lines", "lines", "saved_lines",
            "snapshot_lines", "save_vs_per_op", "save_vs_per_call",
            "dedup_rows", "epochs", "fences"]
    print(fmt_table(rows, cols))

    # quick mode shrinks the op count, so it raises the per-line stall
    # to keep stall-per-epoch in the slack-dominating range
    synth_ns = 4000.0 if args.quick else 1000.0
    if args.quick:
        shard_rows = sharded_sweep(4000, 8192, batch=256, group=16,
                                   synth_ns=synth_ns, repeats=2)
    else:
        shard_rows = sharded_sweep(n_init, 32768, batch=256, group=32,
                                   synth_ns=synth_ns, repeats=2)
    print(fmt_table(shard_rows, ["n_shards", "flush_wall_s", "lines",
                                 "lines_per_s", "x_vs_1shard", "epochs",
                                 "fences"]))

    with open(args.out, "w") as f:
        json.dump({"workload": "bptree mixed 1:1 insert/delete",
                   "n_init": n_init, "n_ops": n_ops, "rows": rows,
                   "sharded_sweep": {
                       "workload": "bptree mixed 1:1, flush-phase wall "
                                   "(epoch drain + commit), stall-"
                                   "dominated regime",
                       "synth_line_ns": synth_ns,
                       "rows": shard_rows}}, f, indent=1)
    print(f"-> {args.out}")
    # epoch batching must never regress per-call accounting, and the
    # grouped B+Tree mixed workload + DLL deletes must beat it outright
    assert all(r["lines"] <= r["per_call_lines"] for r in rows), rows
    assert any(r["lines"] < r["per_call_lines"] for r in rows), rows
    # sharded flush throughput: never below the single-arena baseline
    # (the CI regression gate), and >= 1.3x at 4 shards in full mode
    x4 = next(r["x_vs_1shard"] for r in shard_rows if r["n_shards"] == 4)
    assert x4 >= 1.0, shard_rows
    if not args.quick:
        assert x4 >= 1.3, shard_rows
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
