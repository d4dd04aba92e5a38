"""Whole runs of each cell at a reduced size on the CPU: the clients, the
engine's entry points, the read-back and the reference comparison."""
import pytest

from chipbench import spec
from chipbench.tests.harness_util import CELLS, run_small


@pytest.mark.parametrize("name", CELLS)
def test_small_run_is_correct_and_reports_its_metrics(name):
    res = run_small(name)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0
    assert res["compared"]["tokens_compared"]["value"] > 0
    bench = spec.benchmark()
    if name in {w["name"] for w in bench["workloads"]}:
        want = {m["name"] for m in spec.end_to_end_metrics(bench, name)}
        assert set(res["metrics"]) == want, res["metrics"]
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "compared"
