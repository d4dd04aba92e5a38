"""flash_attention — blockwise online-softmax attention Pallas kernel.

The §Roofline analysis shows XLA-materialized attention dominates the
memory term of every 4k-train / 32k-prefill cell: the (Sq, Skv) score
tensor round-trips HBM several times per layer.  This kernel is the
TPU-native fix — the splash-attention pattern with the score block living
entirely in VMEM:

* grid = (B*K*G, Sq/bq, Skv/bk); the KV axis is the MINOR (fastest) grid
  dim, so the (m, l, acc) accumulators for one q-block stay resident in
  VMEM scratch across the KV sweep (TPU grid order guarantees sequential
  minor-axis execution).
* causal masking via block-level iota compare; fully-masked blocks are
  skipped by the index-map returning the same block (the compiler still
  executes them, but the mask zeroes contributions — the static
  triangular schedule of the XLA path is traded for grid regularity).
* accumulation f32; q/k/v bf16 or f32; out dtype = q dtype.

HBM traffic per layer becomes q + k + v + o (+ tiny m/l), matching the
roofline model's "kernel-adjusted" memory term.  Validated in
interpret mode against ref.flash_attention_ref on shape/dtype sweeps
(tests/test_kernels.py); TPU compilation path is pl.pallas_call with the
same BlockSpecs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, sq: int, skv: int, bq: int, bk: int,
                  scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)                  # (bk, d)
    s = q @ k.T                                       # (bq, bk)
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    m_prev = m_ref[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
    scale_prev = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * scale_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * scale_prev + p @ v
    m_ref[...] = m_new

    @pl.when(kj == (skv // bk) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, scale=None,
                    interpret: bool) -> jax.Array:
    """q: (H, Sq, D); k, v: (H, Skv, D) — call via vmap/reshape for batch.

    Returns (H, Sq, D) in q's dtype.  Sq % block_q == Skv % block_k == 0.
    """
    h, sq, d = q.shape
    skv = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    grid = (h, sq // bq, skv // bk)
    kernel = functools.partial(_flash_kernel, causal=causal, sq=sq,
                               skv=skv, bq=bq, bk=bk, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda hh, qi, kj: (hh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda hh, qi, kj: (hh, kj, 0)),
            pl.BlockSpec((1, bk, d), lambda hh, qi, kj: (hh, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda hh, qi, kj: (hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum l
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
