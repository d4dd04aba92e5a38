"""pack_flush — selective-field gather/pack Pallas kernel.

THE paper hot spot, TPU-adapted: checkpointing persists only the essential
rows/fields of device-resident state.  The flush path gathers the dirty row
set into a contiguous, tile-aligned staging buffer (which is then DMA'd to
host and written by the async checkpoint writer).  This is the cache-line
analogue from §V-E: the staging buffer is laid out in (8, 128) VMEM tiles,
so a flush unit never straddles tiles — packing *unaligned* field slices
would re-read tiles exactly like unaligned clwb re-fetches lines (we expose
that contrast in benchmarks/fig12_alignment).

Kernel shape: out[i, :] = src[idx[i], :] for i < n_valid (rows whose
idx == -1 are zero-filled).  The row index list is scalar-prefetched
(pltpu.PrefetchScalarGridSpec) so BlockSpec index_maps can steer the input
block choice — the idiomatic TPU dynamic-gather pattern.

Rows move as (N, 1, D) views with a squeezed leading block dim: the TPU
lowering requires a block's last two dims to be (8, 128) multiples or the
full array dims, which a (1, bd) block of an (N, D) array is not, while the
(1, bd) tail of an (N, 1, D) array is.

scatter_unpack (restore path) is the exact inverse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUB = 8  # f32 sublane


def _gather_kernel(idx_ref, src_ref, out_ref):
    """One grid step packs one output row-block from a dynamic source row.

    grid = (n_out, D // bd); blocks: src (1, bd) selected by idx, out (1, bd)
    (the squeezed (None, 1, bd) view of the (N, 1, D) arrays).
    """
    i = pl.program_id(0)
    valid = idx_ref[i] >= 0
    row = src_ref[...]
    out_ref[...] = jnp.where(valid, row, jnp.zeros_like(row))


def pack_rows(src: jax.Array, idx: jax.Array, *, block_d: int = 512,
              interpret: bool) -> jax.Array:
    """Gather rows of `src` (N, D) at `idx` (M,) into a packed (M, D) buffer.

    idx entries of -1 produce zero rows.  D must be a multiple of 128; the
    wrapper in ops.py pads as needed.
    """
    n, d = src.shape
    m = idx.shape[0]
    bd = min(block_d, d)
    assert d % bd == 0 and bd % LANE == 0, (d, bd)

    grid = (m, d // bd)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, bd),
                         lambda i, j, idx_ref: (jnp.maximum(idx_ref[i], 0),
                                                0, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, bd),
                               lambda i, j, idx_ref: (i, 0, j)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, d), src.dtype),
        interpret=interpret,
    )(idx, src[:, None, :])
    return out[:, 0, :]


def _scatter_kernel(inv_ref, packed_ref, dst_ref, out_ref):
    """Inverse of pack: for dst row r, out[r] = packed[inv[r]] if a packed
    row maps here (inv[r] >= 0) else dst[r].

    grid = (n, D // bd).  Every output block is written exactly once, so no
    aliasing is needed; the packed input block is steered dynamically by
    the scalar-prefetched inverse map.
    """
    r = pl.program_id(0)
    valid = inv_ref[r] >= 0
    out_ref[...] = jnp.where(valid, packed_ref[...], dst_ref[...])


def scatter_rows(dst: jax.Array, packed: jax.Array, idx: jax.Array, *,
                 block_d: int = 512, interpret: bool) -> jax.Array:
    """Functional dst.at[idx[i]].set(packed[i]) for idx[i] >= 0 (restore).

    The (N,) inverse map (dst row -> packed row or -1) is computed with one
    jnp scatter in the wrapper; the kernel then writes every dst row block
    exactly once.
    """
    n, d = dst.shape
    m = idx.shape[0]
    bd = min(block_d, d)
    assert d % bd == 0 and bd % LANE == 0

    valid = idx >= 0
    oob = jnp.where(valid, idx, n)  # invalid rows -> out of bounds, dropped
    inv = jnp.full((n,), -1, jnp.int32).at[oob].set(
        jnp.arange(m, dtype=jnp.int32), mode="drop")

    grid = (n, d // bd)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, bd),
                         lambda r, j, inv_ref: (jnp.maximum(inv_ref[r], 0),
                                                0, j)),
            pl.BlockSpec((None, 1, bd), lambda r, j, inv_ref: (r, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, bd),
                               lambda r, j, inv_ref: (r, 0, j)),
    )
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), dst.dtype),
        interpret=interpret,
    )(inv, packed[:, None, :], dst[:, None, :])
    return out[:, 0, :]
