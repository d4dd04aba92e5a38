"""Model: config -> callable train/prefill/decode programs.

All stacks run as ``lax.scan`` over superblocks (see backbone.py).  The LM
loss is computed in *sequence chunks* so the (B, chunk, V) logits tensor —
not (B, S, V) — is the live working set (V is up to 262k).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import backbone as B
from repro.models.layers import rms_norm

Array = jax.Array
PyTree = Any


@functools.partial(jax.jit, static_argnums=1)
def _cast(leaves, dtype):
    return [x.astype(dtype) for x in leaves]


def _mask_padded_vocab(logits: Array, vocab: int) -> Array:
    vp = logits.shape[-1]
    if vp == vocab:
        return logits
    ids = lax.iota(jnp.int32, vp)
    return jnp.where(ids < vocab, logits, jnp.finfo(logits.dtype).min)


class Model:
    def __init__(self, cfg: ArchConfig, compute_dtype=jnp.bfloat16,
                 loss_chunk: int = 512):
        self.cfg = cfg
        self.compute_dtype = compute_dtype
        self.loss_chunk = loss_chunk

    # ---------------- parameters ----------------
    def param_specs(self) -> PyTree:
        return B.param_specs(self.cfg)

    def init_params(self, rng: jax.Array) -> PyTree:
        return B.init_params(self.cfg, rng)

    def compute_params(self, params: PyTree) -> Tuple[PyTree, Dict[str, int]]:
        """``params`` with every matmul weight (``backbone.MATMUL_WEIGHTS``)
        cast to the compute dtype in one jitted call, and the bytes of the
        result that were cast (``cast_bytes``) and kept as they were
        (``kept_bytes``).  Every other leaf, and a weight already in the
        compute dtype, is the same array object.  The forward casts those
        weights to the compute dtype on every read, so it computes the
        same logits from either tree; from this one it skips reading the
        f32 weights and writing their cast in each program call."""
        dt = jnp.dtype(self.compute_dtype)
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves = [x for _, x in flat]
        picked = [i for i, (path, x) in enumerate(flat)
                  if getattr(path[-1], "key", None) in B.MATMUL_WEIGHTS
                  and x.dtype != dt]
        if picked:
            cast = _cast([leaves[i] for i in picked], dt)
            for i, y in zip(picked, cast):
                leaves[i] = y
        cast_bytes = sum(leaves[i].nbytes for i in picked)
        total = sum(x.nbytes for x in leaves)
        return (jax.tree.unflatten(treedef, leaves),
                {"cast_bytes": cast_bytes, "kept_bytes": total - cast_bytes})

    def cache_specs(self, batch: int, s_max: int) -> PyTree:
        return B.cache_specs(self.cfg, batch, s_max, self.compute_dtype)

    def init_cache(self, batch: int, s_max: int) -> PyTree:
        return B.init_cache(self.cfg, batch, s_max, self.compute_dtype)

    # ---------------- batch specs ----------------
    def batch_spec(self, batch: int, seq: int) -> Dict[str, jax.ShapeDtypeStruct]:
        cfg = self.cfg
        spec = {
            "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
            "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        }
        if cfg.family == "audio":
            spec["frames"] = jax.ShapeDtypeStruct(
                (batch, cfg.encoder_seq, cfg.d_model), self.compute_dtype)
        if cfg.family == "vlm":
            spec["context"] = jax.ShapeDtypeStruct(
                (batch, cfg.context_seq, cfg.d_model), self.compute_dtype)
        return spec

    # ---------------- forward pieces ----------------
    def _embed(self, params: PyTree, tokens: Array) -> Array:
        from repro.dist.mesh import constrain_activations

        e = params["embed"]
        x = jnp.take(e, tokens, axis=0).astype(self.compute_dtype)
        return constrain_activations(x)

    def _context(self, params: PyTree, batch: Dict[str, Array],
                 mode: str) -> Optional[Array]:
        cfg = self.cfg
        if cfg.family == "vlm":
            return batch["context"].astype(self.compute_dtype)
        if cfg.family == "audio" and mode != "decode":
            return self._encode(params, batch["frames"])
        return None

    def _encode(self, params: PyTree, frames: Array) -> Array:
        """Whisper-style encoder over precomputed frame embeddings (stub
        frontend)."""
        cfg = self.cfg
        x = frames.astype(self.compute_dtype)
        blocks = params["enc_blocks"]["pos0"]

        def body(carry, bp):
            y, _ = B.apply_layer(cfg, "dense:bidir", bp, carry, mode="train")
            return y, None

        body = self._maybe_remat_scan_body(body, "train")
        x, _ = lax.scan(body, x, blocks)
        return rms_norm(x, params["enc_final_norm"], cfg.norm_eps)

    def _maybe_remat_scan_body(self, body, mode):
        if mode != "train":
            return body
        pol = B.REMAT["policy"]
        if pol == "none":
            return body
        if pol == "dots":
            return jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return jax.checkpoint(body)

    def _stack(self, params: PyTree, x: Array, ctx: Optional[Array],
               mode: str, cache: Optional[PyTree] = None,
               pos: Optional[Array] = None,
               s_max: Optional[int] = None) -> Tuple[Array, Optional[PyTree]]:
        cfg = self.cfg
        pattern, n_super, rem = cfg.pattern_plan()
        new_cache: Dict[str, Any] = {}

        if n_super:
            if mode == "train":
                def body(carry, bp):
                    y = carry
                    for i, tag in enumerate(pattern):
                        y, _ = B.apply_layer(cfg, tag, bp[f"pos{i}"], y,
                                             mode="train", ctx=ctx)
                    return y, None
                body = self._maybe_remat_scan_body(body, mode)
                x, _ = lax.scan(body, x, params["blocks"])
            elif mode == "prefill":
                def body(carry, bp):
                    y = carry
                    caches = {}
                    for i, tag in enumerate(pattern):
                        y, c = B.apply_layer(cfg, tag, bp[f"pos{i}"], y,
                                             mode="prefill", ctx=ctx,
                                             s_max=s_max)
                        caches[f"pos{i}"] = c
                    return y, caches
                x, blk_caches = lax.scan(body, x, params["blocks"])
                new_cache["blocks"] = blk_caches
            else:  # decode
                def body(carry, xs):
                    bp, bc = xs
                    y = carry
                    caches = {}
                    for i, tag in enumerate(pattern):
                        y, c = B.apply_layer(cfg, tag, bp[f"pos{i}"], y,
                                             mode="decode",
                                             cache=bc[f"pos{i}"], pos=pos)
                        caches[f"pos{i}"] = c
                    return y, caches
                x, blk_caches = lax.scan(body, x,
                                         (params["blocks"], cache["blocks"]))
                new_cache["blocks"] = blk_caches

        if rem:
            rem_caches = {}
            for i, tag in enumerate(rem):
                rp = params["rem"][f"rem{i}"]
                if mode == "decode":
                    x, c = B.apply_layer(cfg, tag, rp, x, mode="decode",
                                         cache=cache["rem"][f"rem{i}"],
                                         pos=pos)
                else:
                    x, c = B.apply_layer(cfg, tag, rp, x, mode=mode, ctx=ctx,
                                         s_max=s_max)
                rem_caches[f"rem{i}"] = c
            if mode == "prefill" or mode == "decode":
                new_cache["rem"] = rem_caches

        return x, (new_cache if new_cache else None)

    def _head(self, params: PyTree, x: Array) -> Array:
        """x: (..., d) -> logits (..., Vp) f32."""
        cfg = self.cfg
        if cfg.tie_embeddings:
            w = params["embed"].astype(self.compute_dtype)  # (Vp, d)
            logits = jnp.einsum("...d,vd->...v", x, w,
                                preferred_element_type=jnp.float32)
        else:
            w = params["lm_head"].astype(self.compute_dtype)
            logits = jnp.einsum("...d,dv->...v", x, w,
                                preferred_element_type=jnp.float32)
        if cfg.final_softcap:
            logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
        return logits

    # ---------------- public programs ----------------
    def loss(self, params: PyTree, batch: Dict[str, Array]) -> Array:
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        ctx = self._context(params, batch, "train")
        x = self._embed(params, tokens)
        x, _ = self._stack(params, x, ctx, "train")
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)

        b, s, d = x.shape
        chunk = min(self.loss_chunk, s)
        if s % chunk:
            chunk = s
        n_chunks = s // chunk

        def ce_chunk(x_c, y_c):
            logits = self._head(params, x_c)
            logits = _mask_padded_vocab(logits, cfg.vocab)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, y_c[..., None],
                                       axis=-1)[..., 0]
            return jnp.sum(lse - gold)

        if n_chunks == 1:
            total = ce_chunk(x, labels)
        else:
            xs = (x.reshape(b, n_chunks, chunk, d).swapaxes(0, 1),
                  labels.reshape(b, n_chunks, chunk).swapaxes(0, 1))

            def body(acc, xs_c):
                x_c, y_c = xs_c
                return acc + ce_chunk(x_c, y_c), None

            # Remat each chunk: backward recomputes the (B, chunk, V) logits
            # from x_c (one matmul) instead of saving them per chunk — at
            # V=128k..262k the saved logits would dominate HBM.
            body = jax.checkpoint(body)
            total, _ = lax.scan(body, jnp.zeros((), jnp.float32), xs)
        return total / (b * s)

    def prefill(self, params: PyTree, batch: Dict[str, Array],
                s_max: Optional[int] = None) -> Tuple[Array, PyTree]:
        """s_max: decode-cache capacity to allocate (>= tokens.shape[1];
        defaults to the prompt length)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        ctx = self._context(params, batch, "prefill")
        x = self._embed(params, tokens)
        x, kv = self._stack(params, x, ctx, "prefill", s_max=s_max)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._head(params, x[:, -1])
        return _mask_padded_vocab(logits, cfg.vocab), kv

    def decode_step(self, params: PyTree, cache: PyTree, tokens: Array,
                    pos: Array) -> Tuple[Array, PyTree]:
        """tokens: (B,) int32; pos: scalar int32 (position being written)."""
        cfg = self.cfg
        x = self._embed(params, tokens[:, None])
        x, kv = self._stack(params, x, None, "decode", cache=cache, pos=pos)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._head(params, x[:, 0])
        return _mask_padded_vocab(logits, cfg.vocab), kv


def build(cfg: ArchConfig, **kw) -> Model:
    return Model(cfg, **kw)
