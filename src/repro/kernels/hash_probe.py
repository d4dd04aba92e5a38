"""hash_probe — batched bucketized hash-table probe Pallas kernel.

Device-side analogue of the paper's hashmap FIND/INSERT chain walk, used by
the serving engine for batched request/session lookups and embedding-dedup.
TPU adaptation (DESIGN.md §2): pointer-chasing chains don't vectorize, so
the device table is *bucketized* — each bucket is a 128-wide lane row that
is compared in one VPU op.  hash -> bucket id is computed in the ops.py
wrapper; the scalar-prefetched bucket ids steer the BlockSpec index_map
(same dynamic-gather pattern as pack_flush).

Kernel: for query q with bucket b = bucket_of(q):
    slot  = first lane j with keys[b, j] == q   (or -1)
Returns the global slot id b * BUCKET + j so callers can gather values.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BUCKET = 128  # lanes


def _probe_kernel(bid_ref, q_ref, keys_ref, out_ref):
    i = pl.program_id(0)
    q = q_ref[...]                        # (1, 1)
    row = keys_ref[...]                   # (1, BUCKET)
    hit = row == q
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BUCKET), 1)
    slot = jnp.min(jnp.where(hit, lane, BUCKET), axis=1, keepdims=True)
    found = slot < BUCKET
    gslot = bid_ref[i] * BUCKET + slot
    out_ref[...] = jnp.where(found, gslot, -1).astype(jnp.int32)


def probe(keys_table: jax.Array, queries: jax.Array, bucket_ids: jax.Array,
          *, interpret: bool) -> jax.Array:
    """keys_table: (n_buckets, BUCKET) int32/int64-as-2xi32 packed keys;
    queries: (Q,) same dtype; bucket_ids: (Q,) int32.
    Returns (Q,) int32 global slot ids (-1 = absent)."""
    nb, bw = keys_table.shape
    assert bw == BUCKET
    q = queries.shape[0]
    grid = (q,)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, bid_ref: (i, 0)),
            pl.BlockSpec((1, BUCKET), lambda i, bid_ref: (bid_ref[i], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i, bid_ref: (i, 0)),
    )
    out = pl.pallas_call(
        _probe_kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((q, 1), jnp.int32),
        interpret=interpret,
    )(bucket_ids, queries[:, None], keys_table)
    return out[:, 0]
