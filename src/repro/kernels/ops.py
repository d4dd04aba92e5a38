"""jit'd public wrappers around the Pallas kernels.

Handles: lane padding (last dim to 128/256 multiples), flattening arbitrary
pytree leaves to (N, D) row form, backend selection (compiled on TPU,
interpreted on CPU, refused elsewhere), and the leaf-level
quantized-persist API used by the checkpoint manager.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import pack_flush, quant_pack, hash_probe
from repro.kernels.quant_pack import GROUP


def _interpret() -> bool:
    """Interpret the kernels on the CPU, compile them on a TPU; any other
    backend has no kernel path and is refused rather than interpreted."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"no Pallas kernel path for backend {backend!r}")
    return backend == "cpu"


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------- pack / scatter ----------------

@functools.partial(jax.jit, static_argnames=("block_d",))
def pack_rows(src: jax.Array, idx: jax.Array, block_d: int = 512) -> jax.Array:
    """Gather dirty rows into a contiguous flush buffer (tile-aligned)."""
    d0 = src.shape[1]
    srcp = _pad_to(src, 128, 1)
    bd = min(block_d, srcp.shape[1])
    while srcp.shape[1] % bd:
        bd //= 2
    out = pack_flush.pack_rows(srcp, idx, block_d=bd, interpret=_interpret())
    return out[:, :d0]


@functools.partial(jax.jit, static_argnames=("block_d",))
def scatter_rows(dst: jax.Array, packed: jax.Array, idx: jax.Array,
                 block_d: int = 512) -> jax.Array:
    d0 = dst.shape[1]
    dstp = _pad_to(dst, 128, 1)
    packedp = _pad_to(packed, 128, 1)
    bd = min(block_d, dstp.shape[1])
    while dstp.shape[1] % bd:
        bd //= 2
    out = pack_flush.scatter_rows(dstp, packedp, idx, block_d=bd,
                                  interpret=_interpret())
    return out[:, :d0]


# ---------------- quantize / dequantize ----------------

def as_rows(x: jax.Array) -> Tuple[jax.Array, Tuple[int, ...], int]:
    """Flatten any leaf to (N, GROUP*k) rows, padding the tail."""
    flat = x.reshape(-1)
    n_el = flat.shape[0]
    width = GROUP * max(1, min(16, (n_el + GROUP - 1) // GROUP))
    rows = -(-n_el // width)
    rows8 = -(-rows // 8) * 8
    padded = jnp.zeros((rows8 * width,), flat.dtype).at[:n_el].set(flat)
    return padded.reshape(rows8, width), x.shape, n_el


@jax.jit
def quantize_leaf(x: jax.Array):
    """Any-shaped float leaf -> (q int8 rows, scales, meta) for persist."""
    rows, shape, n_el = as_rows(x)
    q, s = quant_pack.quantize_blockwise(rows, interpret=_interpret())
    return q, s


def dequantize_leaf(q: jax.Array, s: jax.Array, shape, dtype) -> jax.Array:
    rows = quant_pack.dequantize_blockwise(q, s, interpret=_interpret())
    n_el = int(np.prod(shape)) if shape else 1
    return rows.reshape(-1)[:n_el].reshape(shape).astype(dtype)


# ---------------- hash probe ----------------

@jax.jit
def hash_lookup(keys_table: jax.Array, queries: jax.Array) -> jax.Array:
    """keys_table: (n_buckets, 128) int32; queries (Q,) int32.
    Returns global slot ids (-1 absent)."""
    nb = keys_table.shape[0]
    h = hash32(queries)
    bid = (h % jnp.uint32(nb)).astype(jnp.int32)
    return hash_probe.probe(keys_table, queries, bid, interpret=_interpret())


def hash32(x: jax.Array) -> jax.Array:
    u = x.astype(jnp.uint32)
    u = (u ^ (u >> 16)) * jnp.uint32(0x7FEB352D)
    u = (u ^ (u >> 15)) * jnp.uint32(0x846CA68B)
    return u ^ (u >> 16)
