"""Checkpoint manager: persistence policies applied to TrainState.

The paper's discipline, end to end:

* plan: classify every leaf (core.policy) — ESSENTIAL / DERIVABLE /
  APPROXIMABLE — and compute the flush plan (bytes to persist).
* flush: device->host gather of persisted leaves, optional int8
  block-quantization of APPROXIMABLE leaves (kernels.quant_pack), one file
  per leaf shard, written by a background thread (async checkpointing —
  compute/persist overlap).
* commit protocol: leaf files are fully written and fsync'd BEFORE the
  manifest is atomically renamed into place (manifest-last = the paper's
  flag bit; a crash mid-write leaves the previous checkpoint valid).
* restore: read manifest, load+dequantize persisted leaves, RECONSTRUCT
  every DERIVABLE leaf (rng, pipeline cursor, schedule) via
  core.reconstruct, re-warm dropped moments, and device_put with the
  *target* mesh's shardings — restoring onto a different mesh (elastic
  scaling) is the same code path.  ``restore(warmup="background")``
  takes APPROXIMABLE re-warming off the restore critical path: the
  returned state carries cheap host placeholders for dropped moments
  while a background thread materializes the device arrays;
  ``finish_warmup(state)`` joins and swaps them in, and the warmup time
  lands in the RecoveryReport as its own §V-F-style stage
  ("warmup_approximable") next to the reconstruction times.
* incremental mode (beyond paper): leaves whose content digest is unchanged
  since the previous checkpoint are skipped ("don't persist what didn't
  change") — frozen embeddings/stubs cost zero bytes per step.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import policy as pol
from repro.core import reconstruct as rec
from repro.core.recovery import RecoveryReport
from repro.core.writeset import DigestWriteSet
from repro.kernels import ops as kops
from repro.train.state import TrainState

PyTree = Any


@dataclasses.dataclass
class SaveReport:
    step: int
    bytes_written: int
    bytes_skipped_derivable: int
    bytes_skipped_unchanged: int
    n_leaves_written: int
    seconds: float
    quantized: bool


def _leaf_file(path_str: str) -> str:
    h = hashlib.md5(path_str.encode()).hexdigest()[:16]
    return f"leaf_{h}.npz"


class CheckpointManager:
    def __init__(self, directory: str, policy: pol.PersistPolicy,
                 incremental: bool = False, use_pack_kernel: bool = False):
        self.dir = directory
        self.policy = policy
        self.incremental = incremental
        self.use_pack_kernel = use_pack_kernel
        os.makedirs(directory, exist_ok=True)
        self._writer: Optional[threading.Thread] = None
        # Leaf-granularity write set: digests decide which leaves are
        # dirty this epoch ("don't persist what didn't change") — same
        # discipline as the arena's row write set (DESIGN.md §2).
        self._writeset = DigestWriteSet()
        self.last_report: Optional[SaveReport] = None
        # restore() reports through the same per-stage format as every
        # other recovery path (core.recovery.RecoveryReport)
        self.last_recovery: Optional[RecoveryReport] = None
        # background APPROXIMABLE warmup (restore(warmup="background"))
        self._warmer: Optional[threading.Thread] = None
        self._warm_result: Dict[int, Any] = {}
        self._warm_error: Optional[BaseException] = None

    # ------------------------------------------------------------------ save
    def save(self, state: TrainState, blocking: bool = True) -> SaveReport:
        t0 = time.perf_counter()
        self.wait()
        sd = state.as_dict()
        plans = pol.plan(sd, self.policy)
        leaves = {pol.path_str(p): l for p, l in
                  jax.tree_util.tree_flatten_with_path(sd)[0]}

        to_write: Dict[str, Tuple[np.ndarray, dict]] = {}
        bytes_written = 0
        bytes_skipped_deriv = 0
        bytes_skipped_unchanged = 0
        quantized_any = False
        manifest: Dict[str, Any] = {"step": int(jax.device_get(state.step)),
                                    "policy": self.policy.name,
                                    "approx": self.policy.approx,
                                    "leaves": {}}

        for p in plans:
            leaf = leaves[p.path]
            raw_bytes = int(np.prod(p.shape or (1,))) * np.dtype(p.dtype).itemsize
            if not p.persisted:
                bytes_skipped_deriv += raw_bytes
                continue
            entry = {"shape": list(p.shape), "dtype": str(np.dtype(p.dtype)),
                     "kind": p.kind.value, "file": _leaf_file(p.path),
                     "quantized": False}
            if p.quantized and np.issubdtype(np.dtype(p.dtype), np.floating):
                q, s = kops.quantize_leaf(leaf)
                host = {"q": np.asarray(q), "s": np.asarray(s)}
                entry["quantized"] = True
                quantized_any = True
                nbytes = host["q"].nbytes + host["s"].nbytes
            else:
                host = {"x": np.asarray(jax.device_get(leaf))}
                nbytes = host["x"].nbytes
            digest = hashlib.md5(
                b"".join(v.tobytes() for v in host.values())).hexdigest()
            entry["digest"] = digest
            if self.incremental:
                present = os.path.exists(
                    os.path.join(self.dir, entry["file"]))
                if not self._writeset.dirty(p.path, digest, present):
                    bytes_skipped_unchanged += nbytes
                    manifest["leaves"][p.path] = entry
                    continue
            else:
                self._writeset.note(p.path, digest)
            to_write[p.path] = (host, entry)
            manifest["leaves"][p.path] = entry
            bytes_written += nbytes

        def write():
            for path, (host, entry) in to_write.items():
                fp = os.path.join(self.dir, entry["file"])
                with open(fp + ".tmp", "wb") as f:
                    np.savez(f, **host)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(fp + ".tmp", fp)
            # manifest-last commit (the paper's flag bit)
            mtmp = os.path.join(self.dir, "manifest.json.tmp")
            with open(mtmp, "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(mtmp, os.path.join(self.dir, "manifest.json"))

        if blocking:
            write()
        else:
            self._writer = threading.Thread(target=write, daemon=True)
            self._writer.start()

        report = SaveReport(
            step=manifest["step"], bytes_written=bytes_written,
            bytes_skipped_derivable=bytes_skipped_deriv,
            bytes_skipped_unchanged=bytes_skipped_unchanged,
            n_leaves_written=len(to_write),
            seconds=time.perf_counter() - t0, quantized=quantized_any)
        self.last_report = report
        return report

    def wait(self) -> None:
        if self._writer is not None:
            self._writer.join()
            self._writer = None

    # --------------------------------------------------------------- restore
    def valid(self) -> bool:
        return os.path.exists(os.path.join(self.dir, "manifest.json"))

    def restore(self, state_spec: TrainState,
                shardings: Optional[PyTree] = None,
                warmup: str = "inline") -> TrainState:
        """state_spec: a TrainState of ShapeDtypeStructs (or arrays) giving
        the target structure; shardings: matching NamedSharding pytree (or
        None for single-device).  DERIVABLE leaves are reconstructed, not
        read.

        warmup: "inline" re-warms APPROXIMABLE leaves on the restore
        critical path (the seed behavior); "background" returns host
        placeholders for them immediately and materializes the device
        arrays in a background thread — call ``finish_warmup(state)`` to
        join and swap them in.  The warmup stage is timed into the
        report either way (detail ``background=True`` marks the
        off-critical-path variant)."""
        assert warmup in ("inline", "background")
        self.wait()
        self.wait_warmup()
        if self._warm_result:
            # splicing THIS restore's indices into a state produced by a
            # previous one would corrupt it silently — refuse loudly
            raise RuntimeError(
                "unclaimed background warmup from a previous restore — "
                "call finish_warmup(state) on that state first")
        t_all = time.perf_counter()
        report = RecoveryReport()
        t0 = time.perf_counter()
        with open(os.path.join(self.dir, "manifest.json")) as f:
            manifest = json.load(f)
        step = manifest["step"]
        report.add("manifest", time.perf_counter() - t0, step=step)
        report.generation = step
        sd = state_spec._asdict()
        flat, treedef = jax.tree_util.tree_flatten_with_path(sd)
        # flatten the shardings in sd's own (dict, key-sorted) order: a
        # TrainState flattens in field order and would pair them with
        # the wrong leaves
        if shardings is None:
            sflat = [None] * len(flat)
        else:
            if isinstance(shardings, TrainState):
                shardings = shardings._asdict()
            sflat = treedef.flatten_up_to(shardings)
        seed = None
        # first pass: essential scalars we need for reconstruction
        for pth, spec in flat:
            if pol.path_str(pth) == "data_seed":
                ent = manifest["leaves"].get("data_seed")
                if ent is not None:
                    seed = int(self._load_leaf(ent, (), np.int32))
        if seed is None:
            seed = 0

        out = []
        times = {"load_persisted": 0.0, "reconstruct_derivable": 0.0,
                 "rewarm_approximable": 0.0, "device_put": 0.0}
        counts = {k: 0 for k in times}
        deferred: Dict[int, Tuple[Tuple[int, ...], Any, Any]] = {}
        for i, ((pth, spec), shard) in enumerate(zip(flat, sflat)):
            pstr = pol.path_str(pth)
            kind = pol.classify(pth, self.policy.rules)
            ent = manifest["leaves"].get(pstr)
            shape = tuple(getattr(spec, "shape", ()))
            dtype = getattr(spec, "dtype", np.float32)
            t0 = time.perf_counter()
            if ent is not None:
                arr = self._load_leaf(ent, shape, dtype)
                stage = "load_persisted"
            elif kind == pol.Kind.DERIVABLE:
                arr = self._reconstruct_leaf(pstr, seed, step, shape, dtype)
                stage = "reconstruct_derivable"
            elif kind == pol.Kind.APPROXIMABLE:
                # drop policy: re-warm from zeros (bias correction restarts
                # cleanly because update() corrects with the global step)
                arr = np.zeros(shape, dtype)
                stage = "rewarm_approximable"
                if warmup == "background":
                    # hand back the host placeholder now; the device
                    # array materializes off the critical path
                    deferred[i] = (shape, dtype, shard)
                    times[stage] += time.perf_counter() - t0
                    counts[stage] += 1
                    out.append(arr)
                    continue
            else:
                raise KeyError(f"essential leaf {pstr} missing from checkpoint")
            times[stage] += time.perf_counter() - t0
            counts[stage] += 1
            t0 = time.perf_counter()
            if shard is not None:
                arr = jax.device_put(arr, shard)
            else:
                arr = jnp.asarray(arr)
            times["device_put"] += time.perf_counter() - t0
            counts["device_put"] += 1
            out.append(arr)
        for stage, secs in times.items():
            report.add(stage, secs, leaves=counts[stage],
                       background=(stage == "rewarm_approximable"
                                   and warmup == "background"))
        report.total_seconds = time.perf_counter() - t_all
        self.last_recovery = report
        if deferred:
            self._start_warmup(report, deferred, t_all)
        sd_new = jax.tree.unflatten(treedef, out)
        return TrainState(**sd_new)

    # ------------------------------------------- background warmup stage
    def _start_warmup(self, report: RecoveryReport,
                      deferred: Dict[int, Tuple], t_anchor: float) -> None:
        self._warm_result = {}
        self._warm_error = None

        def warm():
            try:
                t0 = time.perf_counter()
                warmed: Dict[int, Any] = {}
                for idx, (shape, dtype, shard) in deferred.items():
                    arr = np.zeros(shape, dtype)
                    warmed[idx] = (jax.device_put(arr, shard)
                                   if shard is not None
                                   else jnp.asarray(arr))
                secs = time.perf_counter() - t0
                st = report.add("warmup_approximable", secs,
                                leaves=len(warmed), background=True)
                st.t_start = t0 - t_anchor
                st.t_end = st.t_start + secs
                self._warm_result = warmed
            except BaseException as e:   # surfaced by wait_warmup()
                self._warm_error = e

        self._warmer = threading.Thread(target=warm, daemon=True)
        self._warmer.start()

    def wait_warmup(self) -> None:
        """Join the background warmup thread; a failure inside it (a
        device_put OOM, a sharding mismatch) re-raises HERE rather than
        dying silently in the daemon thread."""
        if self._warmer is not None:
            self._warmer.join()
            self._warmer = None
        err, self._warm_error = self._warm_error, None
        if err is not None:
            raise err

    def finish_warmup(self, state: TrainState) -> TrainState:
        """Join the background warmup thread and swap the warmed device
        arrays into the restored state (leaf order matches restore's
        flatten order).  A no-op for inline restores."""
        self.wait_warmup()
        if not self._warm_result:
            return state
        leaves, treedef = jax.tree_util.tree_flatten(state.as_dict())
        for idx, arr in self._warm_result.items():
            leaves[idx] = arr
        self._warm_result = {}
        return TrainState(**jax.tree_util.tree_unflatten(treedef, leaves))

    def _load_leaf(self, entry: dict, shape, dtype) -> np.ndarray:
        with np.load(os.path.join(self.dir, entry["file"])) as z:
            if entry.get("quantized"):
                q, s = z["q"], z["s"]
                return np.asarray(kops.dequantize_leaf(
                    jnp.asarray(q), jnp.asarray(s), tuple(entry["shape"]),
                    np.dtype(entry["dtype"])))
            return z["x"].reshape(shape).astype(dtype, copy=False)

    def _reconstruct_leaf(self, pstr: str, seed: int, step: int, shape,
                          dtype) -> np.ndarray:
        if pstr == "rng":
            key, _ = rec.run("rng", seed, step)
            return np.asarray(key)
        # unknown derivable leaves default to zeros (caches, cursors held
        # host-side are rebuilt by their owners)
        return np.zeros(shape, dtype)
