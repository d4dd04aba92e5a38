"""FLOP and byte counts against numbers worked by hand from the
configurations' sizes, and the peak table."""
import json

import pytest

from chipbench import flops, peaks, spec


def _dims(name):
    return json.loads((spec.HERE / "configs" / f"{name}.json").read_text())[
        "model"]


def test_phi3_medium_counts():
    d = _dims("phi3-medium-14b")
    attn = 5120 * 40 * 128 + 2 * 5120 * 10 * 128 + 40 * 128 * 5120
    mlp = 3 * 5120 * 17920
    assert flops.layer_weights(d, "dense") == attn + mlp == 340_787_200
    assert flops.matmul_weights(d) == 5 * 340_787_200 + 5120 * 32064
    # a token at position 500 attends to 501 keys in all 5 layers
    assert flops.token_flops(d, 500) == 2 * 1_868_103_680 + \
        5 * 4 * 40 * 128 * 501
    # K and V of 10 heads of 128 in bf16, per attended position
    assert flops.state_bytes(d, 500) == 5 * 2 * 10 * 128 * 2 * 501 \
        == 12_825_600


def test_step_bytes_read_every_weight_once_in_bf16():
    d = _dims("phi3-medium-14b")
    # the program's parameter count less its vocabulary padding: 5 layers
    # with two norm gains each, embedding and head, final norm
    params = 5 * (340_787_200 + 2 * 5120) + 2 * 5120 * 32064 + 5120
    assert flops.all_weights(d) == params == 2_032_327_680
    assert flops.step_bytes(d, []) == 2 * params
    assert flops.step_bytes(d, [5, 7]) == 2 * params + \
        flops.state_bytes(d, 5) + flops.state_bytes(d, 7)


def test_peaks_refuse_an_unknown_chip():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
