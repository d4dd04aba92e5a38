"""The control at a size a test run can hold: a whole run of each cell,
cut to a reduced depth but 256 wide with an 8,192-token vocabulary so
that near-ties occur, computed in bfloat16 as on the chip, also reads the
gap of the reference at the cell's control precision
(``configs/<name>.json`` ``control``) on the same served tokens, and the
verdict with that gap in the program's place.

The limit here is this size's own: over seeds 9-12 the program read
0.000-0.0205 and int8 0.0489-0.0831 (CPU), so 0.03 lies between them.
The cells' limits come from the chip readings at the published widths
in PERF.md."""
import dataclasses

import jax
import pytest

from chipbench import dims as D
from chipbench import run as R
from chipbench import spec
from chipbench.tests.harness_util import CELLS, small_cell

LIMIT = 0.03


@pytest.mark.parametrize("name", CELLS)
def test_control_in_the_programs_place_is_not_correct(name):
    cell, arch = small_cell(name, limit=LIMIT)
    arch = dataclasses.replace(arch, d_model=256, head_dim=64,
                               d_ff=512 if arch.d_ff else 0, vocab=8192)
    cell["config"]["model"] = D.dims_of(arch, cell["config"]["model"])
    cell["config"]["compute_dtype"] = "bfloat16"
    quant = cell["config"]["control"]
    res = R.run(cell, 9, 6.0, False, jax.devices()[:1], spec.benchmark(),
                arch=arch, controls=(quant,))
    gap = res["compared"]["logit_gap"]["value"]
    assert res["compared"]["lost_tokens"]["value"] == 0
    assert res["correct"], res["compared"]
    assert res["control_gap"][quant] > LIMIT > gap, (res["control_gap"], gap)
    assert res["control_correct"] == {quant: False}
