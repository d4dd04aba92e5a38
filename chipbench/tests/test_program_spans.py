"""The readers of the program's own spans (``program_spans.py`` and the
metrics built on it): the two-anchor clock map and the rebuild's device
time on synthetic spans, and the step metrics on reduced traced runs on
the CPU, where they must add up to the mean step."""
import pytest

from chipbench import program_spans as P
from chipbench import run as R
from chipbench import spans, spec
from chipbench.trace import Reduction
from chipbench.tests.harness_util import run_small
from repro import obs

STEP_MS = ("engine.sync_ms_per_step", "engine.dispatch_ms_per_step",
           "persist.record_ms_per_step", "engine.self_ms_per_step")


class FakeRec:
    def __init__(self, window):
        self.window = window
        self.spans = {"window": [window]}


class FakeRun:
    def __init__(self, host_window, trace):
        self.rec = FakeRec(host_window)
        self.trace = trace


def test_clock_map_is_anchored_at_both_ends_of_the_window():
    # the trace's window runs 1 % slower than the host's, from 5 s on
    trace = Reduction([], {"window": [(5_000_000_000, 7_020_000_000)]})
    run = FakeRun((100.0, 102.0), trace)
    to_trace = P.to_trace_clock(run)
    assert to_trace(100.0) == 5_000_000_000
    assert to_trace(102.0) == pytest.approx(7_020_000_000, abs=1)
    assert to_trace(101.0) == pytest.approx(6_010_000_000, abs=1)
    assert P.to_trace_clock(FakeRun((100.0, 102.0), None)) is None
    assert P.to_trace_clock(FakeRun((100.0, 102.0),
                                    Reduction([], {}))) is None


def test_rebuild_device_time_runs_from_the_span_to_the_next_decode(
        monkeypatch):
    ms = 1_000_000
    modules = [("jit_decode_step", 10 * ms, 20 * ms),
               # first crash at 100 ms: two prefills and a scatter
               ("jit_prefill", 101 * ms, 131 * ms),
               ("jit_prefill", 131 * ms, 171 * ms),
               ("jit_scatter", 171 * ms, 176 * ms),
               ("jit_decode_step", 180 * ms, 200 * ms),
               ("jit_argmax", 200 * ms, 201 * ms),
               # second crash at 300 ms: one prefill
               ("jit_prefill", 302 * ms, 352 * ms),
               ("jit_decode_step", 360 * ms, 380 * ms)]
    trace = Reduction([("/device:TPU:0", [], modules)],
                      {"window": [(0, 1000 * ms)]})
    run = FakeRun((50.0, 51.0), trace)       # 1 ms of host = 1 ms of trace
    recs = [obs.Span("recover.engine", 50.1, 50.102, None, None, 1),
            obs.Span("recover.engine", 50.3, 50.301, None, None, 1),
            obs.Span("serve.step", 50.2, 50.25, None, None, 1)]
    monkeypatch.setattr(P, "in_window", lambda r: recs)
    read = spec.reader("recovery.rebuild_device_ms")
    assert read(run) == pytest.approx((75 + 50) / 2)
    monkeypatch.setattr(P, "in_window", lambda r: recs[2:])
    assert read(run) is None


def test_every_reader_is_silent_without_program_spans(monkeypatch):
    trace = Reduction([], {"window": [(0, 10)]})
    run = FakeRun((1.0, 2.0), trace)
    monkeypatch.setattr(P, "in_window", lambda r: [])
    for name in STEP_MS + ("engine.host_syncs_per_step",
                           "recovery.rebuild_device_ms"):
        assert spec.reader(name)(run) is None


@pytest.fixture
def kept(monkeypatch):
    """The Recorder and the Run of the next ``run.run`` call."""
    made = {}

    class KeptRecorder(spans.Recorder):
        def __init__(self):
            super().__init__()
            made["rec"] = self

    class KeptRun(R.Run):
        def __init__(self, **kw):
            super().__init__(**kw)
            made["run"] = self

    monkeypatch.setattr(spans, "Recorder", KeptRecorder)
    monkeypatch.setattr(R, "Run", KeptRun)
    return made


def test_step_metrics_add_up_to_the_step_on_a_traced_run(kept):
    obs.clear()
    res = run_small("phi3-chat", trace=True)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    run = kept["run"]
    found = P.steps(run)
    assert found
    mean_step_ms = sum(s.t1 - s.t0 for s, _ in found) / len(found) * 1e3
    assert sum(m[k] for k in STEP_MS) == pytest.approx(mean_step_ms,
                                                       rel=0.01)
    assert all(m[k] > 0 for k in STEP_MS)
    # one sync a served token: the steps' active slots
    active = [n for _, _, n in run.window_steps()]
    assert len(active) == len(found)
    assert m["engine.host_syncs_per_step"] == sum(active) / len(active)
    # no device plane on the CPU
    assert "recovery.rebuild_device_ms" not in m


def test_program_spans_are_disjoint_from_the_benchmarks(kept):
    obs.clear()
    run_small("phi3-crash", trace=True, seconds=2.0)
    run = kept["run"]
    names = {s.name for s in P.in_window(run)}
    assert {"serve.step", "persist.commit", "recover.engine"} <= names
    assert all(n.startswith(obs.PREFIXES) for n in names)
    assert not names & (set(kept["rec"].spans) | {"window"})
    # every crash's rebuild starts inside the window, on the trace's clock
    to_trace = P.to_trace_clock(run)
    w0, w1 = run.trace.window
    starts = [to_trace(s.t0) for s in P.in_window(run)
              if s.name == "recover.engine"]
    assert len(starts) >= len(run.window_crashes()) > 0
    assert all(w0 <= t <= w1 for t in starts)


def test_an_untraced_run_records_no_program_span(kept):
    obs.clear()
    run_small("phi3-crash", trace=False, seconds=2.0)
    assert obs.recorded() == []
