"""Smoke run of the serving main path on one TPU chip.

    python3 chip_smoke.py             # one chip: serve, crash, recover, kernels
    python3 chip_smoke.py --chips 4   # four chips: checkpoint re-shard only

One chip: hymba-1.5b at its published widths (random weights from
``--seed``) is served through ``repro.launch.serve``'s functions by a
``ServingEngine`` over fresh arenas: four requests of two prompt lengths,
8 decode steps, ``crash()`` + ``recover()``, 8 more steps.  An
uninterrupted twin engine with the same parameters runs the same 16 steps,
and the recovered engine's logits must match it.  A kernel phase then runs
the write-set pack kernel and the checkpoint quantizer compiled for the
chip on a real-width hymba leaf against ``kernels/ref.py``.

Four chips: a reduced hymba train state is saved from a 4-device mesh
and restored onto a different 4-device layout and onto one device; both
restores must equal what was saved bit for bit, on the shardings asked for.

The script refuses to run without a TPU.  Progress goes to earlier lines;
the last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "hymba-1.5b"
MAX_BATCH = 4
S_MAX = 1024
# two lengths: prefill compiles once per length, not once per request
PROMPT_LENS = (128, 256, 128, 256)
STEPS = 8                      # decode steps before and after the crash
# Post-recovery logits are compared with the twin's by their RMS
# difference.  bf16 keeps 8 significant bits (unit roundoff 2**-8), and
# re-prefill rounds in a different order than incremental decode in each
# of the 32 layers, so the difference grows like sqrt(32) * 2**-8 ~ 2.2%
# of the logits' RMS; 2**-4 allows ~3x that.  A device state rebuilt from
# the wrong tokens or positions differs by more than 10%.
LOGIT_RTOL = 2.0 ** -4
# One logit can stray ~4x past the RMS difference (the largest of 32001),
# and the top two may stray in opposite directions: below this margin the
# twin's argmax is a rounding tie and the token may legitimately differ.
MARGIN_RTOL = 4 * LOGIT_RTOL
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileLog:
    """Backend compile seconds per (phase, program), from JAX's
    monitoring events."""

    def __init__(self):
        self.phase = "setup"
        self.secs = collections.defaultdict(list)

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.secs[(self.phase, kw.get("fun_name", "?"))].append(duration)

    def report(self) -> None:
        for (phase, name), secs in sorted(self.secs.items(),
                                          key=lambda kv: -sum(kv[1])):
            log(f"compile {phase}/{name}: {len(secs)} program(s), "
                f"{sum(secs)} s")


def require_tpu(count: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); there is no CPU fallback")
    if len(devices) < count:
        sys.exit(f"chip_smoke: {count} TPU chips needed, "
                 f"{len(devices)} found")
    return devices


# ------------------------------------------------------------- serving

def _real(logits, vocab: int) -> np.ndarray:
    """Host copy of the logits over the real (unpadded) vocabulary."""
    return np.asarray(logits, np.float32)[:vocab]


def compare_to_twin(got, ref, vocab: int, crash_at: int) -> None:
    """``got``/``ref``: per-step ``StepResult`` lists of the recovered
    engine and its uninterrupted twin.  Every step's logits must agree
    within LOGIT_RTOL, and tokens where the twin's top-2 margin is no
    rounding tie; a rid whose token legitimately differs has diverged and
    is compared no further."""
    diverged = set()
    worst = worst_before = 0.0
    for t, (g, r) in enumerate(zip(got, ref)):
        assert set(g.logits) == set(r.logits), (t, g.logits, r.logits)
        for rid in sorted(r.logits):
            if rid in diverged:
                continue
            a, b = _real(g.logits[rid], vocab), _real(r.logits[rid], vocab)
            assert np.isfinite(a).all() and np.isfinite(b).all(), (t, rid)
            rms = float(np.sqrt(np.mean(b * b)))
            rel = float(np.sqrt(np.mean((a - b) ** 2))) / rms
            top2 = np.sort(b)[-2:]
            margin = float(top2[1] - top2[0]) / rms
            if t < crash_at:
                worst_before = max(worst_before, rel)
            else:
                worst = max(worst, rel)
                log(f"step {t} rid {rid}: rms diff {rel} of logit rms "
                    f"{rms}, twin top-2 margin {margin}")
            if rel > LOGIT_RTOL:
                raise AssertionError(
                    f"step {t} rid {rid}: logits differ from the twin by "
                    f"{rel} of their rms (limit {LOGIT_RTOL})")
            if g.tokens[rid] != r.tokens[rid]:
                if margin > MARGIN_RTOL:
                    raise AssertionError(
                        f"step {t} rid {rid}: token {g.tokens[rid]} != "
                        f"twin {r.tokens[rid]} at margin {margin}")
                log(f"step {t} rid {rid}: rounding tie (margin {margin}),"
                    f" streams diverge here")
                diverged.add(rid)
    log(f"logits: worst rms diff {worst_before} before the crash, {worst} "
        f"after it (limit {LOGIT_RTOL}); diverged at ties: "
        f"{sorted(diverged)}")


def check_report(report, n_requests: int) -> None:
    names = [st.name for st in report.stages]
    for need in ("req_table", "lru", "pages", "engine"):
        assert need in names, (need, names)
    assert report.valid and not report.quarantined and not report.degraded, \
        report.as_dict()
    assert not any(st.quarantined or st.degraded for st in report.stages)
    assert report.stage("engine").detail["requests"] == n_requests


def _time_calls(obj, name: str) -> list:
    """Wrap ``obj.name`` to append each call's seconds to the returned
    list (the host-side persistence share of a decode step)."""
    fn, secs = getattr(obj, name), []

    def timed(*args, **kw):
        t0 = time.perf_counter()
        try:
            return fn(*args, **kw)
        finally:
            secs.append(time.perf_counter() - t0)

    setattr(obj, name, timed)
    return secs


def serve_and_recover(*, full_size: bool, seed: int, workdir: Path,
                      compiles: CompileLog,
                      prompt_lens=PROMPT_LENS, s_max: int = S_MAX):
    """The one-chip serving phase; returns the model and its parameters."""
    from repro.launch import serve

    compiles.phase = "init"
    t0 = time.perf_counter()
    model, params = serve.build_model(ARCH, full_size=full_size, seed=seed)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"{model.cfg.name}: d_model {model.cfg.d_model}, "
        f"{model.cfg.n_layers} layers, {n_params} f32 params, compute "
        f"{jnp.dtype(model.compute_dtype).name}, built in "
        f"{time.perf_counter() - t0} s")

    prompts = serve.make_prompts(model.cfg.vocab, prompt_lens, seed)
    eng = serve.open_engine(model, params, str(workdir / "served"),
                            max_batch=MAX_BATCH, s_max=s_max)
    compiles.phase = "admit"
    t0 = time.perf_counter()
    serve.admit(eng, prompts)
    jax.block_until_ready(eng.cache)
    log(f"admitted {len(prompts)} requests (prompt lengths "
        f"{list(prompt_lens)}) in {time.perf_counter() - t0} s")

    compiles.phase = "decode"
    got = serve.serve_steps(eng, STEPS)
    compiles.phase = "recover"
    report = serve.crash_and_recover(eng)
    log(f"recovery: {report.total_seconds} s wall")
    for st in report.stages:
        log(f"  stage {st.name}: {st.seconds} s {st.detail}")
    check_report(report, len(prompts))
    compiles.phase = "decode"
    got += serve.serve_steps(eng, STEPS)
    log(f"decode step seconds: {[r.seconds for r in got]}")
    log(f"arena stats: {eng.arena.stats}")

    compiles.phase = "twin"
    twin = serve.open_engine(model, params, str(workdir / "twin"),
                             max_batch=MAX_BATCH, s_max=s_max)
    serve.admit(twin, prompts)
    commit_secs = _time_calls(twin.arena, "commit")
    ref = serve.serve_steps(twin, 2 * STEPS)
    log(f"twin decode step seconds: {[r.seconds for r in ref]}")
    log(f"  of which arena commit seconds: {commit_secs}")
    decode = jax.jit(model.decode_step)
    args = (params, model.init_cache(1, s_max), jnp.zeros((1,), jnp.int32),
            jnp.asarray(s_max // 2, jnp.int32))
    jax.block_until_ready(decode(*args))
    secs = []
    for _ in range(4):
        t0 = time.perf_counter()
        jax.block_until_ready(decode(*args))
        secs.append(time.perf_counter() - t0)
    log(f"  one B=1 decode_step program alone (a step runs {MAX_BATCH}): "
        f"{secs} s")
    compare_to_twin(got, ref, model.cfg.vocab, crash_at=STEPS)
    return model, params


# ------------------------------------------------------------- kernels

def _custom_call_compiled(fn, *args) -> bool:
    return "tpu_custom_call" in fn.lower(*args).compile().as_text()


def ffn_leaf(cfg, params):
    """The first layer's MLP up-projection, (d_model, d_ff)."""
    from repro.models import backbone

    return next(backbone.layer_params(cfg, params))["mlp"]["w_up"]


def kernel_phase(cfg, params, seed: int) -> None:
    """The checkpoint quantizer on a real-width hymba FFN leaf and the
    write-set pack/scatter kernels, compiled for the chip, against the
    pure-jnp references."""
    from repro.kernels import ops, ref

    leaf = ffn_leaf(cfg, params)                             # (1600, 5504)
    assert _custom_call_compiled(ops.quantize_leaf, leaf)
    dequant = jax.jit(ops.dequantize_leaf, static_argnums=(2, 3))
    q, s = ops.quantize_leaf(leaf)
    assert _custom_call_compiled(dequant, q, s, leaf.shape, leaf.dtype)
    back = np.asarray(dequant(q, s, leaf.shape, leaf.dtype))
    rows = ops.as_rows(leaf)[0]
    qr, sr = (np.asarray(a) for a in ref.quantize_blockwise_ref(rows))
    q, s = np.asarray(q), np.asarray(s)
    # the scale is the same f32 absmax / 127 on both sides
    np.testing.assert_allclose(s, sr, rtol=1e-6, atol=0)
    # x / scale may round differently in the last ulp, which moves a value
    # sitting on a .5 boundary by one quantization step
    dq = np.abs(q.astype(np.int32) - qr.astype(np.int32))
    assert dq.max() <= 1, dq.max()
    np.testing.assert_array_equal(
        np.asarray(ref.dequantize_blockwise_ref(q, s)).reshape(-1)
        [:leaf.size].reshape(leaf.shape), back)
    err = np.abs(np.asarray(leaf) - back).reshape(-1)
    step = np.repeat(s.reshape(-1), 256)[:leaf.size]
    assert (err <= 0.5 * step * (1 + 2.0 ** -10)).all()
    log(f"quantize_leaf/dequantize_leaf {tuple(leaf.shape)}: compiled "
        f"kernels, {int((dq > 0).sum())} of {q.size} codes off the "
        f"reference by one, max round-trip error {float(err.max())}")

    rng = np.random.default_rng(seed)
    words = jnp.asarray(rng.integers(0, 2 ** 32, (4096, 128), np.uint32))
    idx = rng.permutation(4096)[:512].astype(np.int32)
    idx[::7] = -1
    idx = jnp.asarray(idx)
    assert _custom_call_compiled(ops.pack_rows, words, idx)
    packed = ops.pack_rows(words, idx)
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(ref.pack_rows_ref(words, idx)))
    assert _custom_call_compiled(ops.scatter_rows, words, packed, idx)
    np.testing.assert_array_equal(
        np.asarray(ops.scatter_rows(words, packed, idx)),
        np.asarray(ref.scatter_rows_ref(words, packed, idx)))
    log("pack_rows/scatter_rows (4096, 128) uint32: compiled kernels, "
        "equal to the reference")


# ------------------------------------------------------ four-chip phase

def _layout(mesh, spec_tree, axes):
    """NamedShardings that split, for each mesh axis in ``axes`` order,
    the last not-yet-split dim the axis size divides."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def leaf(s):
        entries = [None] * len(s.shape)
        for ax in axes:
            for i in reversed(range(len(s.shape))):
                if entries[i] is None and s.shape[i] % mesh.shape[ax] == 0:
                    entries[i] = ax
                    break
        return NamedSharding(mesh, P(*entries))

    return jax.tree.map(leaf, spec_tree)


def reshard_phase(devices, workdir: Path) -> None:
    """Save a train state sharded over a 4-device mesh; restore it onto
    a different 4-device layout and onto one device, bit-exactly."""
    from jax.sharding import Mesh

    from repro.configs import base, registry
    from repro.launch.serve import compute_dtype
    from repro.models.model import build
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig

    model = build(base.reduced(registry.get(ARCH)),
                  compute_dtype=compute_dtype())
    trainer = Trainer(model, AdamWConfig(), TrainerConfig(
        steps=2, ckpt_every=0, ckpt_dir=str(workdir / "ckpt"),
        global_batch=4, seq_len=32, async_ckpt=False))
    trainer.init()
    spec = trainer.state_spec()
    mesh_a = Mesh(np.array(devices[:4]), ("data",))
    trainer.state = jax.device_put(trainer.state,
                                   _layout(mesh_a, spec, ("data",)))
    trainer.run(2)
    saved = trainer.state
    spans = [len(x.sharding.device_set) for x in jax.tree.leaves(saved)]
    assert max(spans) == 4, spans
    trainer.ckpt.save(saved)
    want = jax.tree.leaves(jax.tree.map(np.asarray, saved))
    log(f"saved step {int(saved.step)} from a 4-device mesh: "
        f"{sum(s == 4 for s in spans)} of {len(spans)} leaves span 4 "
        f"devices, {trainer.ckpt.last_report.bytes_written} bytes")

    mesh_b = Mesh(np.array(devices[:4])[::-1].reshape(2, 2), ("x", "y"))
    mesh_1 = Mesh(np.array(devices[:1]), ("data",))
    for name, target in (("2x2 reversed", _layout(mesh_b, spec, ("y", "x"))),
                         ("1 device", _layout(mesh_1, spec, ("data",)))):
        got = trainer.ckpt.restore(spec, target)
        n_split = 0
        for g, w, sh in zip(jax.tree.leaves(got), want,
                            jax.tree.leaves(target)):
            assert g.sharding.is_equivalent_to(sh, g.ndim), (g.sharding, sh)
            assert g.sharding.device_set == sh.device_set
            for shard in g.addressable_shards:
                assert shard.data.shape == sh.shard_shape(g.shape)
            n_split += len({s.index for s in g.addressable_shards}) > 1
            np.testing.assert_array_equal(np.asarray(g), w)
        log(f"restored onto {name}: {len(want)} leaves bit-equal, "
            f"{n_split} split across devices as asked")


# ---------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"devices: {len(devices)} x {devices[0].device_kind}; compile "
        f"cache {enable_compile_cache()}")
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
            if args.chips == 4:
                reshard_phase(devices, Path(td))
            else:
                model, params = serve_and_recover(
                    full_size=True, seed=args.seed, workdir=Path(td),
                    compiles=compiles)
                compiles.phase = "kernels"
                kernel_phase(model.cfg, params, args.seed)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    compiles.report()
    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
