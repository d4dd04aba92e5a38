"""A benchmark cell cut to a size the CPU runs in seconds: the reduced
configuration, 4 slots of 64 tokens, short prompts and outputs."""
import copy

import jax

from chipbench import dims as D
from chipbench import spec
from repro.configs import base

SMALL_TRAFFIC = {
    "chat": {"prompt_tokens": {"8": 0.5, "16": 0.5},
             "output_tokens": [4, 12], "clients": 4},
    "crash": {"prompt_tokens": {"8": 0.5, "16": 0.5},
              "output_tokens": [6, 6], "crash": {"after_tokens": 3},
              "clients": 4},
}


CELLS = sorted(p.stem for p in (spec.HERE / "workloads").glob("*.json"))


def small_cell(name: str, limit: float = 0.05):
    cell = copy.deepcopy(spec.cell(name))
    arch = base.reduced(D.arch_of(cell["config"]))
    cell["config"]["model"] = D.dims_of(arch, cell["config"]["model"])
    cell["config"]["compute_dtype"] = "float32"
    cell["config"]["engine"] = {"max_batch": 4, "s_max": 64,
                                "max_requests": 256}
    cell["traffic"].update(SMALL_TRAFFIC[cell["traffic"]["name"]])
    cell["limits"] = {"logit_gap": limit}
    return cell, arch


def run_small(name: str, seed: int = 5, seconds: float = 3.0,
              trace: bool = False, limit: float = 0.05):
    from chipbench import run as R

    cell, arch = small_cell(name, limit)
    return R.run(cell, seed, seconds, trace, jax.devices()[:1],
                 spec.benchmark(), arch=arch)
