"""chip_smoke.py on the CPU: it refuses to run, and its phases pass at
the reduced size (the chip run is the same code at published widths)."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_serve_crash_recover_matches_twin(smoke, tmp_path):
    params = smoke.serve_and_recover(
        full_size=False, seed=0, workdir=tmp_path,
        compiles=smoke.CompileLog(), prompt_lens=(12, 20, 12, 20), s_max=64)
    assert params["blocks"]["pos0"]["mlp"]["w_up"].ndim == 3


def test_compare_to_twin_rejects_a_shifted_state(smoke):
    """A recovered state that differs beyond rounding fails the check."""
    import numpy as np
    from repro.launch.serve import StepResult

    rng = np.random.default_rng(0)
    ref = rng.normal(size=256).astype(np.float32)
    twin = [StepResult({1: int(ref.argmax())}, {1: ref}, 0.0)]
    close = [StepResult({1: int(ref.argmax())}, {1: ref * (1 + 2 ** -8)},
                        0.0)]
    smoke.compare_to_twin(close, twin, 256, crash_at=0)
    off = ref + 0.2 * rng.normal(size=256).astype(np.float32)
    with pytest.raises(AssertionError, match="logits differ"):
        smoke.compare_to_twin([StepResult({1: int(off.argmax())}, {1: off},
                                          0.0)], twin, 256, crash_at=0)


def test_reshard_phase_on_four_cpu_devices(tmp_path):
    """The --chips 4 phase on four virtual CPU devices; a child process,
    because the device count is fixed when JAX starts."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import sys, jax; from pathlib import Path; "
            f"sys.path.insert(0, {str(ROOT)!r}); import chip_smoke; "
            "chip_smoke.reshard_phase(jax.devices(), Path(sys.argv[1])); "
            "print('{\"reshard\": \"ok\"}')")
    out = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {
        "reshard": "ok"}
    assert "restored onto 2x2 reversed" in out.stdout
