"""Core transformer layers: norms, RoPE, gated MLPs, blockwise attention.

Attention design notes (TPU adaptation):

* Prefill/train attention is *blockwise* with an online-softmax scan over KV
  blocks (the splash-attention pattern): memory is O(q_block * kv_block)
  instead of O(S^2).
* The causal schedule is **statically triangular**: a Python loop over query
  blocks, each scanning only its KV prefix.  This keeps compiled HLO FLOPs
  equal to the true triangular cost (no 2x masked-waste), which matters for
  honest roofline accounting at 32k prefill.
* Sliding-window layers slice the banded KV range per query block with a
  *static* slice (python ints), so local attention costs O(S*W) exactly.
* Decode uses direct softmax over the cache; sliding-window decode uses a
  ring buffer whose absolute slot positions are derived from `pos` (no
  stored position tensor needed).

All matmuls accumulate in f32 (`preferred_element_type`), activations are
bf16 by default.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: Array, gamma: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def layer_norm(x: Array, gamma: Array, beta: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> Array:
    """(head_dim//2,) inverse frequencies, f32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotate-half RoPE.  x: (..., S, ..., head_dim) with positions (..., S)
    broadcastable against x's sequence axis; here we require
    x: (B, S, N, D) [or (B, S, N, G, D)] and positions: (S,) or (B, S)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                     # (d/2,)
    pos = positions.astype(jnp.float32)
    ang = pos[..., None] * inv                     # (..., S, d/2)
    # Broadcast angles over head axes between S and D.
    extra = x.ndim - ang.ndim - 1
    for _ in range(extra):
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": functools.partial(jax.nn.gelu, approximate=True)}[name]


def gated_mlp(x: Array, w_gate: Array, w_up: Array, w_down: Array, act: str) -> Array:
    """x: (..., d).  w_gate/w_up: (d, f); w_down: (f, d)."""
    dt = x.dtype
    g = jnp.einsum("...d,df->...f", x, w_gate.astype(dt),
                   preferred_element_type=_row_reduce_dtype(dt))
    u = jnp.einsum("...d,df->...f", x, w_up.astype(dt),
                   preferred_element_type=_row_reduce_dtype(dt))
    h = (act_fn(act)(g) * u).astype(dt)
    return jnp.einsum("...f,fd->...d", h, w_down.astype(dt),
                      preferred_element_type=_row_reduce_dtype(dt)
                      ).astype(dt)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

# §Perf: emit row-parallel matmul outputs (attn O-projection, MLP down-
# projection, MoE down-projection) at the compute dtype instead of f32.
# GSPMD inserts the cross-shard partial-sum all-reduce directly on the dot
# output, so a bf16 output halves the dominant TP activation-reduce bytes
# (gemma3 train: 37.6 s -> ~19 s collective).  MXU accumulation is f32
# internally either way; the cross-device add happens in bf16 (standard
# Megatron practice).  Off by default (bitwise-f32 baseline); enabled by
# launch/specs.build_cell for distributed cells.
LOWP_ROW_REDUCE = {"on": False}


def _row_reduce_dtype(dt):
    return dt if LOWP_ROW_REDUCE["on"] else jnp.float32


def _softcap(scores: Array, cap: float) -> Array:
    if cap and cap > 0.0:
        return cap * jnp.tanh(scores / cap)
    return scores


@dataclasses.dataclass(frozen=True)
class AttnParams:
    """Weight bundle for one attention mixer (arrays may be batched by a
    leading superblock dim before being sliced by scan)."""
    wq: Array        # (d, H, Dh)
    wk: Optional[Array]   # (d, K, Dh); None: the layer projects only Q
    wv: Optional[Array]   # (d, K, Dv)
    wo: Array        # (H, Dv, d)
    q_norm: Optional[Array] = None   # (Dh,) gemma3 qk-norm
    k_norm: Optional[Array] = None


def project_qkv(x: Array, p: AttnParams, n_kv: int, *, positions: Array,
                theta: float, qk_norm_eps: float = 1e-6,
                use_rope: bool = True) -> Tuple[Array, Array, Array]:
    """x: (B, S, d) -> q: (B, S, K, G, Dh); k: (B, S, K, Dh), v: (B, S, K,
    Dv), or None for both when ``p.wk`` is None."""
    dt = x.dtype
    pref = _row_reduce_dtype(dt)
    q = jnp.einsum("bsd,dhe->bshe", x, p.wq.astype(dt),
                   preferred_element_type=pref).astype(dt)
    k = v = None
    if p.wk is not None:
        k = jnp.einsum("bsd,dke->bske", x, p.wk.astype(dt),
                       preferred_element_type=pref).astype(dt)
        v = jnp.einsum("bsd,dke->bske", x, p.wv.astype(dt),
                       preferred_element_type=pref).astype(dt)
    if p.q_norm is not None:
        q = rms_norm(q, p.q_norm, qk_norm_eps)
        k = rms_norm(k, p.k_norm, qk_norm_eps)
    if use_rope:
        q = apply_rope(q, positions, theta)
        if k is not None:
            k = apply_rope(k, positions, theta)
    b, s, h, e = q.shape
    g = h // n_kv
    q = q.reshape(b, s, n_kv, g, e)
    return q, k, v


def _online_softmax_block(carry, q, k_blk, v_blk, mask, softcap):
    """One KV block of streaming attention.

    q: (B, K, G, Sq, Dh); k_blk/v_blk: (B, Skv, K, Dh);
    mask: (Sq, Skv) or None (True = attend); carry: (m, l, acc).
    """
    m_prev, l_prev, acc_prev = carry
    s = jnp.einsum("bkgqd,bjkd->bkgqj", q, k_blk,
                   preferred_element_type=jnp.float32)
    s = _softcap(s, softcap)
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    # Re-scale previous accumulator.
    scale = jnp.exp(m_prev - m_new)
    # Guard fully-masked blocks: exp(NEG_INF - NEG_INF) would be 1.
    p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new[..., None]), 0.0)
    l_new = l_prev * scale + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgqj,bjkd->bkgqd", p.astype(v_blk.dtype), v_blk,
                    preferred_element_type=jnp.float32)
    acc_new = acc_prev * scale[..., None] + pv
    return m_new, l_new, acc_new


def _finish(m, l, acc, dtype):
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(dtype)  # (B, K, G, Sq, Dh)


def blockwise_attention(q: Array, k: Array, v: Array, *, causal: bool,
                        window: int = 0, softcap: float = 0.0,
                        q_block: int = 1024, kv_block: int = 1024,
                        scale: Optional[float] = None,
                        n_meta: int = 0) -> Array:
    """Streaming-softmax attention.

    q: (B, S, K, G, Dh); k: (B, Skv, K, Dh); v: (B, Skv, K, Dv).  Returns
    (B, S, K, G, Dv).

    causal=True  -> static triangular schedule over query blocks (the last
                    one may be shorter).
    window>0     -> additionally banded: query block i only reads the KV
                    slice [i*qb - window, (i+1)*qb)  (static slice).
    n_meta>0     -> the first n_meta keys are seen by every query despite
                    the window (hymba's meta tokens): a block whose band
                    starts past them reads them first.
    causal=False -> full bidirectional / cross attention.
    A KV span longer than kv_block that it does not divide is read as its
    whole blocks, then the rest.
    """
    b, s, n_kv, g, dh = q.shape
    dv = v.shape[-1]
    skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    q = (q * scale).astype(q.dtype)

    qb = min(q_block, s)
    # (B, K, G, S, Dh) layout for the inner loops.
    qt = q.transpose(0, 2, 3, 1, 4)

    def attend(carry, q_i, q_pos0, lo, hi):
        """Fold keys [lo, hi) into the online-softmax carry (a fresh one
        when ``carry`` is None)."""
        nq = q_i.shape[3]
        k_i = lax.slice_in_dim(k, lo, hi, axis=1)
        v_i = lax.slice_in_dim(v, lo, hi, axis=1)
        if carry is None:
            carry = (jnp.full((b, n_kv, g, nq), NEG_INF, jnp.float32),
                     jnp.zeros((b, n_kv, g, nq), jnp.float32),
                     jnp.zeros((b, n_kv, g, nq, dv), jnp.float32))

        def mask_of(k0, nk):
            """Mask of the queries over keys k0 .. k0 + nk - 1."""
            if not (causal or window):
                return None
            qpos = q_pos0 + jnp.arange(nq)
            kpos = k0 + jnp.arange(nk)
            m = jnp.ones((nq, nk), bool)
            if causal:
                m &= qpos[:, None] >= kpos[None, :]
            if window:
                near = qpos[:, None] - kpos[None, :] < window
                if n_meta:
                    near |= kpos[None, :] < n_meta
                m &= near
            return m

        span = hi - lo
        kb = min(kv_block, span)
        n_kb, tail = divmod(span, kb)
        done = 0
        if n_kb > 1:
            whole = n_kb * kb
            ks, vs = ((t if not tail else lax.slice_in_dim(t, 0, whole, axis=1))
                      for t in (k_i, v_i))
            ks = ks.reshape(b, n_kb, kb, n_kv, dh).transpose(1, 0, 2, 3, 4)
            vs = vs.reshape(b, n_kb, kb, n_kv, dv).transpose(1, 0, 2, 3, 4)
            jidx = jnp.arange(n_kb)

            def body(carry, xs):
                k_blk, v_blk, j = xs
                return _online_softmax_block(carry, q_i, k_blk, v_blk,
                                             mask_of(lo + j * kb, kb),
                                             softcap), None

            # Flash-attention-style backward: remat the KV-block body so the
            # (B, K, G, Sq, Skv) probability matrix and mask are NOT saved
            # as per-iteration scan residuals (25 GiB/layer at 4k train
            # otherwise) — backward recomputes them from the saved k/v
            # blocks.
            body = jax.checkpoint(body)
            carry, _ = lax.scan(body, carry, (ks, vs, jidx))
            done = whole
        for a, z in ((done, span - tail), (span - tail, span)):
            if z > a:
                kk, vv = ((t if (a, z) == (0, span)
                           else lax.slice_in_dim(t, a, z, axis=1))
                          for t in (k_i, v_i))
                carry = _online_softmax_block(carry, q_i, kk, vv,
                                              mask_of(lo + a, z - a),
                                              softcap)
        return carry

    out_blocks = []
    for q_pos0 in range(0, s, qb):
        q_i = lax.slice_in_dim(qt, q_pos0, min(q_pos0 + qb, s), axis=3)
        nq = q_i.shape[3]
        if causal:
            lo = max(0, q_pos0 - window + 1) if window else 0
            lo = (lo // kv_block) * kv_block
            hi = min(skv, q_pos0 + nq)
        else:
            lo, hi = 0, skv
        carry = None
        if n_meta and lo > 0:
            if lo <= n_meta:
                lo = 0
            else:
                carry = attend(carry, q_i, q_pos0, 0, n_meta)
        carry = attend(carry, q_i, q_pos0, lo, hi)
        out_blocks.append(_finish(*carry, q.dtype))
    out = (jnp.concatenate(out_blocks, axis=3) if len(out_blocks) > 1
           else out_blocks[0])
    return out.transpose(0, 3, 1, 2, 4)  # (B, S, K, G, Dv)


def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     kv_positions: Array, pos: Array, *, window: int = 0,
                     softcap: float = 0.0,
                     scale: Optional[float] = None,
                     n_meta: int = 0) -> Array:
    """Single-step attention against a cache.

    q: (B, 1, K, G, Dh); k_cache: (B, C, K, Dh); v_cache: (B, C, K, Dv);
    kv_positions: (C,) absolute position held by each cache slot (−1 empty);
    pos: scalar current position.  Window masking uses absolute positions;
    the first ``n_meta`` positions are inside every window.
    """
    b, _, n_kv, g, dh = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    qs = (q[:, 0] * scale)  # (B, K, G, Dh)
    s = jnp.einsum("bkgd,bjkd->bkgj", qs, k_cache,
                   preferred_element_type=jnp.float32)
    s = _softcap(s, softcap)
    valid = (kv_positions >= 0) & (kv_positions <= pos)
    if window:
        near = kv_positions > pos - window
        if n_meta:
            near |= kv_positions < n_meta
        valid &= near
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgj,bjkd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out[:, None].astype(q.dtype)  # (B, 1, K, G, Dh)


def attn_out(attended: Array, wo: Array) -> Array:
    """attended: (B, S, K, G, Dh); wo: (H, Dh, d) -> (B, S, d)."""
    b, s, n_kv, g, dh = attended.shape
    a = attended.reshape(b, s, n_kv * g, dh)
    return jnp.einsum("bshe,hed->bsd", a, wo.astype(a.dtype),
                      preferred_element_type=_row_reduce_dtype(a.dtype)
                      ).astype(a.dtype)


# ---------------------------------------------------------------------------
# Ring-buffer cache helpers (sliding-window decode)
# ---------------------------------------------------------------------------


def ring_slot_positions(pos: Array, cap: int, n_meta: int = 0) -> Array:
    """Absolute position stored in each ring slot after writing `pos` at
    slot pos % cap.  Slot w holds the largest p <= pos with p % cap == w
    (or -1 if none).  With ``n_meta`` pinned slots, slot w < n_meta holds
    position w and the rest are a ring of cap - n_meta over the positions
    from n_meta on."""
    if n_meta:
        ring = ring_slot_positions(pos - n_meta, cap - n_meta)
        return jnp.concatenate([jnp.arange(n_meta),
                                jnp.where(ring >= 0, ring + n_meta, -1)])
    slots = jnp.arange(cap)
    p = pos - ((pos - slots) % cap)
    return jnp.where(p >= 0, p, -1)


def ring_write(cache: Array, value: Array, pos: Array, cap: int,
               n_meta: int = 0) -> Array:
    """cache: (B, cap, ...); value: (B, 1, ...) written at slot pos % cap,
    or past ``n_meta`` pinned slots at n_meta + (pos - n_meta) % (cap -
    n_meta) (``pos`` >= n_meta)."""
    if n_meta:
        slot = (n_meta + (pos - n_meta) % (cap - n_meta)).astype(jnp.int32)
    else:
        slot = (pos % cap).astype(jnp.int32)
    return lax.dynamic_update_slice_in_dim(cache, value.astype(cache.dtype),
                                           slot, axis=1)
