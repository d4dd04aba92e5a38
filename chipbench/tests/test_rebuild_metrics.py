"""The readers of what the program reports about recovery's rebuild
(``recovery.rebuild_positions``, ``recovery.rebuild_mfu``), on a reduced
traced phi3-crash run and on hymba-1.5b's sizes, and the hybrid layer's
counts (``counts/hybrid.py``) worked by hand."""
import json

import jax
import numpy as np
import pytest

from chipbench import flops, spec
from chipbench.counts import hybrid
from chipbench.tests.harness_util import run_small
from chipbench.tests.test_program_spans import kept  # noqa: F401
from repro import obs
from repro.configs import registry
from repro.models import backbone as B

V5E_PEAK = {"bf16_flops_per_s": 197e12}


def _hand_prefill_flops(dims, tokens):
    """Every position from the first meta token on, the head once."""
    meta = dims.get("meta_tokens", 0)
    head = 2 * dims["d_model"] * dims["vocab"]
    return (sum(flops.token_flops(dims, p) for p in range(-meta, tokens))
            - (tokens + meta - 1) * head)


def _with_device_ms(monkeypatch, ms):
    real = spec.reader
    monkeypatch.setattr(spec, "reader", lambda name: (
        (lambda r: ms) if name == "recovery.rebuild_device_ms"
        else real(name)))
    return real


def test_rebuild_readers_on_a_traced_run(kept, monkeypatch):  # noqa: F811
    obs.clear()
    res = run_small("phi3-crash", trace=True)
    run = kept["run"]
    crashes = run.window_crashes()
    assert crashes
    groups = [c["report"].stage("engine").detail["rebuild_groups"]
              for c in crashes]
    # logs of prompt + 3 tokens: two sessions each re-prefill 10 and 18
    # tokens (the last logged token is decoded, not prefilled)
    assert sorted(groups[0]) == [[10, 2], [18, 2]]
    want = [sum(n * t for t, n in g) for g in groups]
    got = res["metrics"]["recovery.rebuild_positions"]["value"]
    assert got == sum(want) / len(want)
    # no device plane on the CPU: the rebuild's device time, and so its
    # share of the peak, are not read
    assert "recovery.rebuild_mfu" not in res["metrics"]
    run.peaks = V5E_PEAK
    real = _with_device_ms(monkeypatch, 2.0)
    per_crash = sum(n * _hand_prefill_flops(run.dims, t)
                    for t, n in groups[0])
    assert real("recovery.rebuild_mfu")(run) == pytest.approx(
        100 * per_crash / (2e-3 * 197e12))


class _Report:
    def __init__(self, detail):
        self.detail = detail

    def stage(self, name):
        return self if name == "engine" else None


class _Run:
    def __init__(self, detail, dims=None):
        self.peaks = V5E_PEAK
        self.dims = dims or {"meta_tokens": 0}
        self.crash = {"report": _Report(detail)}

    def window_crashes(self):
        return [self.crash]


def test_rebuild_readers_are_silent_for_a_program_without_the_details():
    old = _Run({"requests": 16, "prefill_groups": 2})
    for name in ("recovery.rebuild_positions", "recovery.rebuild_mfu"):
        assert spec.reader(name)(old) is None


def _dims():
    return json.loads((spec.HERE / "configs" / "hymba-1.5b.json")
                      .read_text())["model"]


def test_rebuild_mfu_counts_the_meta_positions(monkeypatch):
    """hymba's rebuild of 8 sessions of 1,029 and 8 of 1,797 logged
    tokens: 128 meta positions before each, attended as the program
    attends them; a log of no tokens still prefills the meta tokens."""
    d = _dims()
    groups = [[1029, 8], [1797, 8], [0, 1]]
    run = _Run({"rebuild_groups": groups,
                "rebuild_positions": 8 * 1157 + 8 * 1925 + 128}, d)
    assert spec.reader("recovery.rebuild_positions")(run) == 24784
    real = _with_device_ms(monkeypatch, 1000.0)
    per_crash = sum(n * _hand_prefill_flops(d, t) for t, n in groups)
    assert real("recovery.rebuild_mfu")(run) == pytest.approx(
        100 * per_crash / 197e12)


def test_hybrid_counts_worked_by_hand():
    d = _dims()
    ssm = 1600 * 6400 + 3200 * (100 + 32) + 100 * 3200
    rest = 3200 * 1600 + 3 * 1600 * 5504         # out_proj, MLP
    q = 1600 * 25 * 64
    kv = 1600 * 5 * (64 + 128)
    assert flops.layer_weights(d, "hybrid:full") == q + kv + ssm + rest
    assert flops.layer_weights(d, "hybrid:local") == q + kv + ssm + rest
    assert flops.layer_weights(d, "hybrid:local:shared") == q + ssm + rest
    # 17 KV-holding layers (3 full, 14 windowed), 15 that share
    assert flops.matmul_weights(d) == 32 * (q + ssm + rest) + 17 * kv + \
        1600 * 32001
    # keys seen: meta always; full every earlier token; windowed the last
    # 1024; a meta token (pos < 0) the meta tokens up to itself
    for tag, pos, seen in (("hybrid:full", 1791, 1792 + 128),
                           ("hybrid:local", 1791, 1024 + 128),
                           ("hybrid:local:shared", 500, 501 + 128),
                           ("hybrid:full", -128, 1),
                           ("hybrid:local", -1, 128)):
        assert hybrid.attended(d, tag, pos) == seen
    at = 2 * 25 * (64 + 128)
    assert flops.token_flops(d, 1791) == 2 * flops.matmul_weights(d) + \
        at * (3 * (1792 + 128) + 29 * (1024 + 128))
    state = 1600 * 2 * 16 * 4 + 3 * 3200 * 2     # SSM f32, conv tail bf16
    assert flops.state_bytes(d, 1791) == 32 * state + 2 * 5 * 192 * (
        3 * (1792 + 128) + 14 * (1024 + 128))


def test_hybrid_weight_count_is_the_programs():
    """Every parameter but the vocabulary padding and the meta tokens
    (which only a prefill reads)."""
    cfg = registry.get("hymba-1.5b")
    total = sum(int(np.prod(s.shape))
                for s in jax.tree.leaves(B.param_specs(cfg)))
    pad = (cfg.vocab_padded - cfg.vocab) * cfg.d_model
    assert flops.all_weights(_dims()) == total - pad - 128 * 1600
