"""Run one benchmark cell once on the chip and print one result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``workloads/<cell>.json``) names a configuration
(``configs/``), a traffic mix (``traffic/``) and its chips.  The run
makes the weights from the seed on the device, opens a ``ServingEngine``
over fresh arenas, warms every program the mix uses, then drives the
engine's own entry points (``add_request``, ``step``,
``finish_request``, ``crash``, ``recover``) from closed-loop clients for
``--seconds``.  After the window it reads the device's peak memory, frees
the engine and decides ``correct`` against the plain reference.  With
``--trace 1`` the window runs under the JAX profiler and the line carries
the cell's per-layer metrics instead of its end-to-end ones.  Every
metric is computed by ``metrics/<name>.py``.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def require_chip(count: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: JAX found no TPU (platform "
              f"{devices[0].platform!r}); there is no CPU fallback",
              file=sys.stderr)
        raise SystemExit(3)
    if len(devices) < count:
        print(f"chipbench: the cell needs {count} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(3)
    return devices[:count]


class Run:
    """Everything one run recorded, for the metric readers."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def window_tokens(self):
        """(request, index, time) of every token served in the window."""
        lo, hi = self.rec.window
        return [(r, i, t) for r in self.requests for i, t in
                enumerate(r.times) if lo <= t <= hi]

    def window_steps(self):
        lo, hi = self.rec.window
        return [s for s in self.clients.steps if s[0] >= lo and s[1] <= hi]

    def window_crashes(self):
        lo, hi = self.rec.window
        return [c for c in self.clients.crashes
                if lo <= c["t0"] and "first_token" in c
                and c["first_token"] <= hi]


def flush_lines(eng) -> int:
    """Lines of every kind both arenas have flushed."""
    total = 0
    for arena in (eng.arena, eng.paging.arena):
        s = arena.stats
        total += (s.lines + s.snapshot_lines + s.journal_lines
                  + s.integrity_lines)
    return total


def run(cell: dict, seed: int, seconds: float, trace: bool, devices,
        bench: dict, *, arch=None, t_start: float = T_START,
        controls=()) -> dict:
    """One run of ``cell``; returns the result line as a dict.  ``arch``
    replaces the registry's configuration (the tests run a reduced one
    on the CPU).  ``controls`` (``int8``, ``fp8``) also reads the gap of
    the reference computed at each precision, as ``control_gap`` in the
    result, and the verdict with that gap in the program's place, as
    ``control_correct`` (``calibrate.py``; never in a benchmark run)."""
    import jax
    import jax.numpy as jnp

    from chipbench import check, loadgen, spec, weights
    from chipbench import dims as D
    from chipbench import trace as T
    from chipbench.peaks import peaks
    from chipbench.spans import Recorder
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import build
    from repro.serve.engine import EngineConfig, ServingEngine

    conf, mix_json = cell["config"], cell["traffic"]
    arch = arch or D.arch_of(conf)
    dims = D.dims_of(arch, conf["model"])
    if dims != conf["model"]:
        raise SystemExit(f"chipbench: configs/{conf['name']}.json no longer"
                         f" states the program's sizes: {dims}")
    kind = devices[0].device_kind
    chip = peaks(kind) if devices[0].platform == "tpu" else None
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    rec = Recorder()
    jax.monitoring.register_event_duration_secs_listener(rec)
    workdir = Path(tempfile.mkdtemp(prefix="chipbench_"))
    try:
        model = build(arch, compute_dtype=jnp.dtype(conf["compute_dtype"]))
        params = weights.make(model.param_specs(), seed)
        jax.block_until_ready(params)
        eng = ServingEngine(model, params,
                            EngineConfig(**conf["engine"]),
                            arena_path=str(workdir / "arena"))
        mix = loadgen.Mix.from_json(mix_json)
        s_max = conf["engine"]["s_max"]
        if mix.longest() > s_max:
            raise SystemExit(f"chipbench: mix {mix_json['name']} needs "
                             f"{mix.longest()} tokens, s_max is {s_max}")
        reqs = loadgen.Requests(mix, seed, arch.vocab)
        clients = loadgen.Clients(eng, mix, reqs, rec)
        for arena in (eng.arena, eng.paging.arena):
            rec.wrap(arena, "commit", "arena.commit")
        with rec.span("setup.warm_up"):
            clients.warm_up()
            jax.block_until_ready(eng.cache)
        compiles_setup = len(rec.compiles)
        lines0 = flush_lines(eng)
        trace_dir = workdir / "trace"
        if trace:
            T.start(trace_dir)
        t_open = time.perf_counter()
        setup_s = t_open - t_start
        with rec.span("window"):
            clients.run(t_open + seconds)
            jax.block_until_ready(eng.cache)
        t_close = time.perf_counter()
        rec.window = (t_open, t_close)
        reduction = (T.stop(trace_dir, set(rec.spans) | {"window"})
                     if trace else None)
        lines1 = flush_lines(eng)
        mem = [d.memory_stats() or {} for d in devices]
        peak_bytes = max(m.get("peak_bytes_in_use", 0) for m in mem)
        clients.read_back_live()
        window_compiles = rec.window_compiles()
        outside_recover = [c for c in window_compiles
                           if "engine.recover" not in c[3]]
        # free the program's state before the reference runs
        eng.crash()
        del eng, clients.eng
        gc.collect()

        sampled = check.sample(reqs.all(), seed)
        with rec.span("check.reference"):
            worst, worst_ctl, compared = check.gaps(
                params, conf["model"], conf["reference"], sampled, s_max,
                controls)
        limits = cell.get("limits", {})
        numbers = [
            ("lost_tokens", clients.readback_errors, 0, "max"),
            ("logit_gap", worst, limits.get("logit_gap", 0.0), "max"),
            ("tokens_compared", compared, 1, "min"),
        ]
        correct = check.verdict(numbers)
        # each control put in the program's place, under the same limits
        control_correct = {
            c: check.verdict([(n, worst_ctl[c] if n == "logit_gap" else v,
                               lim, k) for n, v, lim, k in numbers])
            for c in controls}

        the_run = Run(cell=cell, config=conf, dims=conf["model"], mix=mix,
                      rec=rec, clients=clients, requests=reqs.all(),
                      setup_s=setup_s, window_s=t_close - t_open,
                      flush_lines=lines1 - lines0, trace=reduction,
                      peaks=chip, device_kind=kind, seed=seed)
        e2e = spec.end_to_end_metrics(bench, cell["name"])
        wanted = (spec.per_layer_metrics(bench, cell["name"],
                                         {m["name"] for m in e2e})
                  if trace else e2e)
        metrics = {}
        for m in wanted:
            value = spec.reader(m["name"])(the_run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        lo, hi = rec.window
        attempted = sum(1 for r in reqs.all() if r.sent is not None and (
            r.sent >= lo or any(lo <= t <= hi for t in r.times)))
        log = print
        log(f"[chipbench] {cell['name']} seed {seed}: setup {setup_s} s "
            f"({compiles_setup} compiles), window {t_close - t_open} s, "
            f"{len(the_run.window_steps())} steps, "
            f"{len(the_run.window_tokens())} tokens, "
            f"{len(the_run.window_crashes())} crashes", flush=True)
        log(f"[chipbench] compiles in the window: {len(window_compiles)} "
            f"({len(outside_recover)} outside recover()): "
            f"{[(c[2], c[1]) for c in window_compiles]}", flush=True)
        for name, values in _timings(the_run).items():
            log(f"[chipbench] timing {name}: n={len(values)} "
                f"p50={_pct(values, 50)} p95={_pct(values, 95)}", flush=True)
        log(f"[chipbench] reference: {len(sampled)} requests, {compared} "
            f"served tokens compared, longest "
            f"{max((len(r.tokens) for r in sampled), default=0)}", flush=True)
        result = {
            "correct": correct,
            "attempted": attempted,
            "failed": len(clients.failed),
            "metrics": metrics,
            "device": {"platform": devices[0].platform, "kind": kind,
                       "count": len(devices),
                       "memory_peak_bytes": peak_bytes},
        }
        if controls:
            result["control_gap"] = worst_ctl
            result["control_correct"] = control_correct
        if reduction is not None:
            result["device"]["busy_s"] = reduction.busy_s()
            result["device"]["window_s"] = reduction.window_s()
            result["breakdown"] = reduction.breakdown()
        result["compared"] = {n: {"value": v, "limit": lim,
                                  "bound": "at most" if k == "max"
                                  else "at least"}
                              for n, v, lim, k in numbers}
        for n, v, lim, k in numbers:
            print(f"compared {n}: {v} (limit: {'at most' if k == 'max' else 'at least'} {lim})",
                  file=sys.stderr, flush=True)
        return result
    finally:
        jax.monitoring.unregister_event_duration_listener(rec)
        shutil.rmtree(workdir, ignore_errors=True)


def _pct(values, q):
    import numpy as np

    return float(np.percentile(values, q)) if values else None


def _timings(r: Run) -> dict:
    """Every timing the run took, with its sample count (printed on
    earlier lines; the metrics pick theirs)."""
    lo, _ = r.rec.window
    gaps, ttft = [], []
    for req in r.requests:
        ts = [t for t in req.times if lo <= t <= r.rec.window[1]]
        gaps += [b - a for a, b in zip(ts, ts[1:])]
        if req.sent is not None and req.sent >= lo and req.times:
            ttft.append(req.times[0] - req.sent)
    return {"step_s": [b - a for a, b, _ in r.window_steps()],
            "itl_s": gaps, "ttft_s": ttft,
            "ttft_after_crash_s": [c["ttft_s"] for c in r.window_crashes()]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    from chipbench import spec

    bench = spec.benchmark()
    cell = spec.cell(args.workload)
    devices = require_chip(int(cell["chips"]))
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                 bench)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
