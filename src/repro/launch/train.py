"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the Trainer end to end on the local device(s) with the configured
persistence policy, crash-sim hooks, and respawn-from-checkpoint —
the single-host harness for the fault-tolerance contract.  On real
hardware the same entry point runs per host under the cluster scheduler
(jax.distributed.initialize is a no-op on one process).

Fault-tolerance loop: the trainer runs in incarnations.  If a step
exceeds the straggler deadline or the process is told to crash (test
hook), the incarnation ends and the next one restores from the latest
valid checkpoint and continues — the paper's crash/reconstruct contract
at trainer scale.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.configs import base, registry
from repro.core import policy as pol
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig

POLICIES = {
    "full": pol.FULLY_PERSISTENT,
    "partly": pol.PARTLY_PERSISTENT,
    "partly-q8": pol.PARTLY_Q8,
    "partly-drop": pol.PARTLY_DROP,
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(registry.ARCHS))
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="use the reduced same-family config (CPU)")
    ap.add_argument("--full-size", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--policy", default="partly", choices=list(POLICIES))
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline-s", type=float, default=0.0)
    ap.add_argument("--crash-at-step", type=int, default=-1,
                    help="inject a crash after this step (fault-tolerance "
                         "demo); the launcher respawns from checkpoint")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = base.reduced(cfg)
    model = build(cfg, compute_dtype=jnp.float32
                  if jax.default_backend() == "cpu" else jnp.bfloat16)
    tc = TrainerConfig(
        steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, policy=POLICIES[args.policy],
        seed=args.seed, global_batch=args.global_batch,
        seq_len=args.seq_len, microbatches=args.microbatches,
        deadline_s=args.deadline_s)
    trainer = Trainer(model, AdamWConfig(), tc)

    if args.resume and trainer.ckpt.valid():
        step = trainer.resume()
        print(f"[train] resumed incarnation at step {step}")
    else:
        trainer.init()
        print(f"[train] fresh start: {cfg.name} ({args.policy} persistence)")

    start = int(jax.device_get(trainer.state.step))
    end = args.steps
    while start < end:
        run_until = min(end, args.crash_at_step) \
            if start <= args.crash_at_step < end else end
        trainer.run(run_until - start)
        start = int(jax.device_get(trainer.state.step))
        if start == args.crash_at_step:
            print(f"[train] CRASH injected at step {start}; respawning...")
            trainer.crash()
            resumed = trainer.resume()
            print(f"[train] incarnation 2 restored at step {resumed} "
                  f"(reconstructed pipeline cursor + rng)")
            start = resumed
            args.crash_at_step = -1

    last = trainer.metrics_log[-1]
    rep = trainer.ckpt.last_report
    print(json.dumps({
        "final_step": last["step"], "final_loss": round(last["loss"], 4),
        "ckpt_bytes_written": rep.bytes_written if rep else 0,
        "ckpt_bytes_skipped_derivable":
            rep.bytes_skipped_derivable if rep else 0,
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
