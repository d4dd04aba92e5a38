"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Boots the ServingEngine (paged-KV DLL allocator + request hashmap, both
partly persistent), serves batched greedy decode for synthetic requests,
then demonstrates the crash/recover path: all device + volatile host
state is dropped and rebuilt from the persistent arena (token log replay
re-prefills every live request).

The pieces are functions so that other programs (``chip_smoke.py``) run
this same path: ``build_model`` -> ``open_engine`` -> ``admit`` ->
``serve_steps`` -> ``crash_and_recover`` -> ``serve_steps``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base, registry
from repro.core.recovery import RecoveryReport
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model, build
from repro.serve.engine import EngineConfig, ServingEngine

FIRST_RID = 100


def compute_dtype():
    """float32 on the CPU, bf16 on an accelerator; parameters stay f32."""
    return jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16


def build_model(arch: str, *, full_size: bool,
                seed: int = 0) -> Tuple[Model, dict]:
    """The registry config at its published widths (``full_size``) or its
    ``base.reduced`` CPU preset, with random float32 weights from
    ``seed``."""
    cfg = registry.get(arch)
    if not full_size:
        cfg = base.reduced(cfg)
    model = build(cfg, compute_dtype=compute_dtype())
    return model, model.init_params(jax.random.PRNGKey(seed))


def open_engine(model: Model, params, arena_path: str, *, max_batch: int,
                s_max: int) -> ServingEngine:
    return ServingEngine(model, params,
                         EngineConfig(max_batch=max_batch, s_max=s_max,
                                      max_requests=4 * max_batch),
                         arena_path=arena_path)


def make_prompts(vocab: int, lengths: Sequence[int],
                 seed: int) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, int(n)).astype(np.int64)
            for n in lengths]


def admit(eng: ServingEngine, prompts: Sequence[np.ndarray]) -> List[int]:
    """Admit one request per prompt (rids from FIRST_RID); returns rids."""
    rids = []
    for i, prompt in enumerate(prompts):
        eng.add_request(FIRST_RID + i, prompt)
        rids.append(FIRST_RID + i)
    return rids


@dataclasses.dataclass
class StepResult:
    tokens: Dict[int, int]          # rid -> greedy token
    logits: Dict[int, jax.Array]    # rid -> logits the token came from
    seconds: float                  # host clock, ends in block_until_ready


def serve_steps(eng: ServingEngine, steps: int) -> List[StepResult]:
    """Run ``steps`` decode steps, each timed until the device is done."""
    out = []
    for _ in range(steps):
        t0 = time.perf_counter()
        tokens = eng.step()
        jax.block_until_ready((eng.cache, eng.last_logits))
        out.append(StepResult(tokens, dict(eng.last_logits),
                              time.perf_counter() - t0))
    return out


def crash_and_recover(eng: ServingEngine) -> RecoveryReport:
    """Drop all device and volatile host state, rebuild it from the
    arena, and return the staged recovery report."""
    eng.crash()
    eng.recover()
    return eng.last_recovery


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(registry.ARCHS))
    ap.add_argument("--full-size", action="store_true",
                    help="published widths instead of the reduced preset")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--s-max", type=int, default=64)
    ap.add_argument("--arena", default="/tmp/repro_serve_arena")
    ap.add_argument("--crash", action="store_true",
                    help="crash mid-serve and recover")
    args = ap.parse_args(argv)

    enable_compile_cache()
    model, params = build_model(args.arch, full_size=args.full_size)
    eng = open_engine(model, params, args.arena, max_batch=args.requests,
                      s_max=args.s_max)
    lengths = np.random.default_rng(0).integers(3, 9, args.requests)
    prompts = make_prompts(model.cfg.vocab, lengths, seed=0)
    for rid, prompt in zip(admit(eng, prompts), prompts):
        print(f"[serve] request {rid}: prompt={prompt.tolist()}")

    for step, res in enumerate(serve_steps(eng, args.steps // 2)):
        print(f"[serve] step {step}: {res.tokens}")

    if args.crash:
        print("[serve] CRASH — dropping device caches + volatile tables")
        rep = crash_and_recover(eng)
        print(f"[serve] recovered in {rep.total_seconds:.3f}s (hashmap "
              f"reconstructed, LRU chain rebuilt, KV re-prefilled from "
              f"token log)")

    for step, res in enumerate(serve_steps(eng, args.steps - args.steps // 2),
                               start=args.steps // 2):
        print(f"[serve] step {step}: {res.tokens}")
    print(f"[serve] flush stats: {eng.arena.stats}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
