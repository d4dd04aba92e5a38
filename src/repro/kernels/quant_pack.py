"""quant_pack — fused blockwise int8 quantize + pack Pallas kernel.

Beyond-paper persistence path for APPROXIMABLE leaves (Adam moments):
persist 1 byte/elem + one f32 scale per 256-element group instead of 4
bytes/elem — a ~3.9x reduction in flushed bytes (EXPERIMENTS.md §Perf).
Also usable as the in-memory moment representation (8-bit Adam) for the
llama4-400b memory budget (DESIGN.md §5).

Tiling: grid over N / bn row blocks; each block spans the full width D,
so the (bn, D / G) scales block covers its array's whole last dim (the TPU
lowering refuses a (bn, 1) block there).  Inside the block a static loop
over the D / G groups (G = group = 256, two lane tiles) computes each
per-row absmax -> scale column and its (bn, G) quantized payload.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

GROUP = 256


def _quant_kernel(x_ref, q_ref, s_ref):
    for g in range(x_ref.shape[1] // GROUP):
        cols = slice(g * GROUP, (g + 1) * GROUP)
        x = x_ref[:, cols].astype(jnp.float32)        # (bn, G)
        absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        scale = jnp.maximum(absmax, 1e-12) / 127.0
        q_ref[:, cols] = jnp.clip(jnp.round(x / scale), -127,
                                  127).astype(jnp.int8)
        s_ref[:, g:g + 1] = scale


def _row_block(n: int, block_n: int) -> int:
    bn = min(block_n, n)
    while n % bn:
        bn //= 2
    return bn


def quantize_blockwise(x: jax.Array, *, block_n: int = 64,
                       interpret: bool):
    """x: (N, D) float -> (q (N, D) int8, scales (N, D // GROUP) f32).

    D must be a multiple of GROUP; N a multiple of 8 (ops.py pads).
    """
    n, d = x.shape
    assert d % GROUP == 0 and n % 8 == 0, (n, d)
    bn = _row_block(n, block_n)
    return pl.pallas_call(
        _quant_kernel,
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, d // GROUP), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.int8),
            jax.ShapeDtypeStruct((n, d // GROUP), jnp.float32),
        ],
        interpret=interpret,
    )(x)


def _dequant_kernel(q_ref, s_ref, x_ref):
    for g in range(q_ref.shape[1] // GROUP):
        cols = slice(g * GROUP, (g + 1) * GROUP)
        x_ref[:, cols] = q_ref[:, cols].astype(jnp.float32) * s_ref[:, g:g + 1]


def dequantize_blockwise(q: jax.Array, scales: jax.Array, *,
                         block_n: int = 64, dtype=jnp.float32,
                         interpret: bool) -> jax.Array:
    n, d = q.shape
    assert d % GROUP == 0 and scales.shape == (n, d // GROUP)
    bn = _row_block(n, block_n)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((bn, d // GROUP), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(q, scales)
    return out.astype(dtype)
