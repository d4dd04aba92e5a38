"""The program's own spans (``repro.obs``): nothing is recorded while no
profiler runs; under the profiler a serving step and a recovery record
the spans the benchmark's readers count, with their parents, rids and
the ``RecoveryReport``'s own intervals."""
import collections
import contextlib
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import base, registry
from repro.models.model import build
from repro.serve.engine import EngineConfig, ServingEngine

SLOT_SPANS = ("serve.slot.extract", "serve.slot.decode",
              "serve.slot.reseat", "serve.sync")


@pytest.fixture(scope="module")
def model_params():
    model = build(base.reduced(registry.get("llama3.2-3b")),
                  compute_dtype=jnp.float32)
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(model_params, tmp_path, n_active: int, max_batch: int = 4):
    model, params = model_params
    eng = ServingEngine(model, params,
                        EngineConfig(max_batch=max_batch, s_max=16,
                                     max_requests=16),
                        arena_path=str(tmp_path / "a"))
    # two prompt lengths, so recovery re-prefills in two groups
    for k in range(n_active):
        eng.add_request(10 + k, np.arange(1, 4 + 2 * (k % 2), dtype=np.int64))
    return eng


@contextlib.contextmanager
def _profiling(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def test_no_profiler_records_nothing(model_params, tmp_path):
    eng = _engine(model_params, tmp_path, 3)
    obs.clear()
    assert obs.span("serve.step") is obs.span("persist.commit")
    eng.step()
    eng.finish_request(10)
    eng.crash()
    eng.recover(concurrency=2)
    eng.step()
    assert obs.recorded() == []


@pytest.mark.parametrize("n_active", [1, 3])
def test_step_records_each_slots_spans_under_the_step(model_params,
                                                      tmp_path, n_active):
    eng = _engine(model_params, tmp_path, n_active)
    obs.clear()
    with _profiling(tmp_path):
        out = eng.step()
    spans = obs.recorded()
    by_name = collections.defaultdict(list)
    for s in spans:
        by_name[s.name].append(s)
    (step,) = by_name.pop("serve.step")
    assert step.parent is None and step.rid is None
    rids = sorted(out)
    assert len(rids) == n_active
    for name in SLOT_SPANS:
        assert sorted(s.rid for s in by_name.pop(name)) == rids
    # the last token's read, then the appends, per slot
    assert sorted(s.rid for s in by_name.pop("persist.record")) == \
        sorted(rids * 2)
    (commit,) = by_name.pop("persist.commit")
    assert commit.rid is None
    assert not by_name
    for s in spans:
        if s is not step:
            assert s.parent == "serve.step" and s.thread == step.thread
            assert step.t0 <= s.t0 <= s.t1 <= step.t1
    children = sorted((s.t0, s.t1) for s in spans if s is not step)
    assert all(a[1] <= b[0] for a, b in zip(children, children[1:]))


@pytest.mark.parametrize("concurrency", [1, 2])
def test_recover_records_one_span_per_report_stage(model_params, tmp_path,
                                                   concurrency):
    eng = _engine(model_params, tmp_path, 3)
    eng.step()
    eng.crash()
    obs.clear()
    with _profiling(tmp_path):
        eng.recover(concurrency=concurrency)
    rep = eng.last_recovery
    assert rep.stage("engine").detail["prefill_groups"] == 2
    spans = [s for s in obs.recorded() if s.name.startswith("recover.")
             and s.name != "recover.prefill_group"]
    assert sorted(s.name for s in spans) == \
        sorted("recover." + st.name for st in rep.stages)
    for s in spans:
        st = rep.stage(s.name[len("recover."):])
        assert s.t1 - s.t0 == pytest.approx(st.t_end - st.t_start,
                                            abs=1e-9)
        assert st.seconds <= st.t_end - st.t_start + 1e-9
    # the stages' offsets from one start, as the report has them
    t_all = spans[0].t0 - rep.stage(spans[0].name[8:]).t_start
    for s in spans:
        assert s.t0 - t_all == pytest.approx(
            rep.stage(s.name[8:]).t_start, abs=1e-9)
    if concurrency > 1:
        # pool threads keep stacks of their own
        assert all(s.parent is None for s in spans)


def test_every_name_has_a_program_prefix_and_reaches_the_trace(
        model_params, tmp_path):
    eng = _engine(model_params, tmp_path, 2)
    obs.clear()
    with _profiling(tmp_path):
        eng.add_request(30, np.array([5, 6, 7], np.int64))
        eng.step()
        eng.finish_request(10)
        eng.crash()
        eng.recover(concurrency=2)
        eng.step()
    names = {s.name for s in obs.recorded()}
    assert {"serve.step", "persist.commit", "recover.engine"} <= names
    assert all(n.startswith(obs.PREFIXES) for n in names)
    (path,) = glob.glob(f"{tmp_path}/trace/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    events = {ev.name for plane in data.planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events}
    assert names <= events


def test_each_prefill_group_is_a_span_under_the_engine_stage(model_params,
                                                             tmp_path):
    eng = _engine(model_params, tmp_path, 3)
    eng.step()
    eng.crash()
    obs.clear()
    with _profiling(tmp_path):
        eng.recover()
    detail = eng.last_recovery.stage("engine").detail
    spans = obs.recorded()
    (stage,) = [s for s in spans if s.name == "recover.engine"]
    groups = [s for s in spans if s.name == "recover.prefill_group"]
    assert len(groups) == detail["prefill_groups"] == 2
    for g in groups:
        assert g.parent == "recover.engine" and g.thread == stage.thread
        assert stage.t0 <= g.t0 <= g.t1 <= stage.t1


@pytest.mark.parametrize("arch", ["llama3.2-3b", "hymba-1.5b"])
def test_rebuild_positions_count_every_group_and_the_meta_tokens(tmp_path,
                                                                 arch):
    cfg = base.reduced(registry.get(arch))
    model = build(cfg, compute_dtype=jnp.float32)
    eng = _engine((model, model.init_params(jax.random.PRNGKey(0))),
                  tmp_path, 3)
    eng.step()
    eng.crash()
    eng.recover()
    detail = eng.last_recovery.stage("engine").detail
    # logs of 4, 6, 4 tokens: prefixes of 3 and 5 re-prefilled
    assert sorted(detail["rebuild_groups"]) == [[3, 2], [5, 1]]
    m = cfg.meta_tokens
    assert (m > 0) == (arch == "hymba-1.5b")
    assert detail["rebuild_positions"] == 2 * (3 + m) + (5 + m)
