"""Readings that set a cell's ``logit_gap`` limit, taken on the chip in
one process: for each seed, a whole run of the cell (its own window,
load and sample) that also reads the gap of the reference computed at
the control precision on the same prompts and served tokens.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        --control int8 fp8 --seeds 11 12 13 ...

One JSON line per seed on stdout: ``logit_gap`` is the program's reading,
``control_gap`` the control's, ``correct`` the program's verdict and
``control_correct`` the verdict with the control's gap in the program's
place, both under the cell's current limits (the control must come out
false).  Benchmark runs never compute the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", choices=("int8", "fp8"), nargs="+",
                    required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench import run as R
    from chipbench import spec

    bench = spec.benchmark()
    cell = spec.cell(args.workload)
    devices = R.require_chip(int(cell["chips"]))
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = R.run(cell, seed, args.seconds, False, devices, bench,
                    t_start=t0, controls=tuple(args.control))
        c = res["compared"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control": args.control,
            "logit_gap": c["logit_gap"]["value"],
            "correct": res["correct"],
            "control_gap": res["control_gap"],
            "control_correct": res["control_correct"],
            "lost_tokens": c["lost_tokens"]["value"],
            "tokens_compared": c["tokens_compared"]["value"],
            "metrics": res["metrics"], "seconds": time.perf_counter() - t0}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
