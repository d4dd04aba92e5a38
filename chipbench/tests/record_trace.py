"""Record the small device trace that ``test_trace.py`` reduces.

    python3 chipbench/tests/record_trace.py <out.xplane.pb>   # on a TPU

Host spans named as the harness names them, each around jitted
work with known sizes, and an idle gap inside a span of its own; then a
dump of the trace's planes and lines so the reduction can be checked by
eye.
"""
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: no TPU")
    mm = jax.jit(lambda a, b: a @ b)
    add = jax.jit(lambda a: a + 1.0)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready((mm(a, a), add(a)))
    out = Path(sys.argv[1])
    out.parent.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory() as td:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(td, profiler_options=opts)
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("engine.step"):
                for _ in range(3):
                    jax.block_until_ready(mm(a, a))
            with jax.profiler.TraceAnnotation("engine.add_request"):
                jax.block_until_ready(add(a))
                time.sleep(0.05)
                jax.block_until_ready(add(a))
            time.sleep(0.02)
        jax.profiler.stop_trace()
        path = glob.glob(f"{td}/plugins/profile/*/*.xplane.pb")[0]
        shutil.copy(path, out)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(out))
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:6]:
                print("    ", repr(ev.name), ev.start_ns, ev.duration_ns,
                      [(k, v) for k, v in ev.stats][:6])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
