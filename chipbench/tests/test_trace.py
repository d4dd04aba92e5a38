"""The trace reduction on a small trace recorded once on a TPU v5e
(``record_trace.py``): a ``window`` span holding an ``engine.step`` span
around three 2048² bf16 matmuls and an ``engine.add_request`` span around
two element-wise adds with a 50 ms sleep between them.

The numbers below were read off the trace's events by hand: the device's
clock runs ~1 ms behind the host's here, so the first matmul lands before
the window opens and the second straddles its start."""
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data" / "small.xplane.pb"
SPANS = {"window", "engine.step", "engine.add_request"}


@pytest.fixture(scope="module")
def red():
    return trace.load(DATA, SPANS)


def test_window_and_busy_time(red):
    assert red.window == (42_743_120, 196_760_970)
    # second matmul clipped to the window (36,370 ns), third matmul's ops,
    # and the two adds
    assert red.busy_s() == pytest.approx(178_923e-9, abs=1e-9)
    assert red.busy_s() < red.window_s()


def test_programs_attributed_to_host_spans(red):
    assert trace.program("jit_decode_step(17)") == "jit_decode_step"
    step = red.spans("engine.step")
    admit = red.spans("engine.add_request")
    assert len(step) == len(admit) == 1
    # by start time: the third matmul and the first add in the step span,
    # the second add in the admission span
    assert len(red.programs("jit__lambda", within=step)) == 2
    assert len(red.programs("jit__lambda", within=admit)) == 1
    assert len(red.programs("jit__lambda")) == 3


def test_breakdown_names_gaps_by_the_open_span(red):
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    name, secs = b["idle_gaps"][0]
    # the sleep between the two adds
    assert name == "engine.add_request" and secs > 0.05
    assert b["device_ops"][0][0] == "jit__lambda/%fusion"
    gaps = red.idle_gaps()
    assert sum(b - a for a, b in gaps) / 1e9 + red.busy_s() == \
        pytest.approx(red.window_s(), rel=1e-9)
