"""chain_order — chain-reconstruction Pallas kernels (doubling +
contraction list ranking).

Device-side variant of the recovery layer's shared chain primitives
(core/recovery.py).  Two paths behind ``chain_order_device(method=)``:

* DOUBLING — one `jump_double` call advances every node's jump pointer
  by its own current distance (jump' = jump[jump], NULL-absorbing) and
  accumulates the hop count, so log2(N) rounds resolve the order/length
  of a NULL-terminated chain — the §V-F reconstruction walk at hardware
  speed instead of Python-loop speed.
* CONTRACTION (DESIGN.md §8) — sample every k-th row as a spine node
  (deterministic ``id % k == 0``, so membership is arithmetic — no
  lookup table on device), local-walk the spine segments with
  `gather_next` rounds (total gathers O(N): lanes retire as segments
  close), rank the ~N/k contracted chain with the SAME `jump_double`
  tables — now an in-cache working set — and expand ranks back through
  a second pass of `gather_next` rounds.  This is what keeps 10**6+
  chain recovery off the jump-table cache cliff; ``method="auto"``
  defers to the shared `core.recovery.chain_method` heuristic.

TPU adaptation (same dynamic-gather pattern as pack_flush/hash_probe):
pointer chasing doesn't vectorize as lane ops, so the per-node gathers
``jump[jump[i]]`` / ``nxt[cur[i]]`` are steered by the
*scalar-prefetched* pointer array in the BlockSpec index_map; the kernel
bodies only mask the NULL-absorbed lanes.

Sharded arenas (DESIGN.md §7) add a ``segments`` offset argument: a
sharded region's NEXT column arrives as N per-shard views concatenated
shard-major (what a recovery DMA reads straight out of the shard files,
no host re-gather), while pointer VALUES stay global row ids.  With the
block-cyclic segment router the packed position of global id g is
closed-form — ``packed_positions`` — so the doubling rounds steer their
gathers through the per-shard segments directly: pass
``segments=<shard row offsets>, seg_rows=<router segment size>`` and
the primitives accept the packed layout, returning global ids.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.recovery import CONTRACT_K, ChainSnapshot, chain_method

NULL = -1

# pallas_call round-trips issued by this module (interpret or compiled):
# the contraction fusion's whole point is shrinking this, so benchmarks
# snapshot it around a run instead of guessing from wall time
KERNEL_CALLS = 0


def packed_positions(ids, seg_rows: int, segments):
    """Position of each global row id in a shard-major packed array.

    ``segments`` — (n_shards + 1,) row offsets of each shard's span in
    the packed array (``segments[s]`` = rows held by shards < s); shard
    of a global id under the block-cyclic router is
    ``(id // seg_rows) % n_shards`` and its local rank is
    ``(id // (seg_rows * n_shards)) * seg_rows + id % seg_rows`` —
    exact even when the last block is partial, because earlier blocks of
    a shard are always full.  Works on numpy and jax arrays alike.
    Negative ids (NULL) map to NULL."""
    n_shards = len(segments) - 1
    seg = ids // seg_rows
    shard = seg % n_shards
    local = (ids // (seg_rows * n_shards)) * seg_rows + ids % seg_rows
    if isinstance(ids, np.ndarray):
        base = np.asarray(segments)[np.maximum(shard, 0)]
        return np.where(ids >= 0, base + local, NULL)
    base = jnp.asarray(segments)[jnp.maximum(shard, 0)]
    return jnp.where(ids >= 0, base + local, NULL)


def _double_kernel(jmp_ref, jump_at_ref, cnt_at_ref, cnt_ref,
                   jump_out, cnt_out):
    """One doubling round for node i = program_id(0).

    jump_at/cnt_at blocks are steered to row jump[i] (clamped to 0 when
    absorbed); cnt block is row i.  Invariant maintained: after k rounds
    jump[i] = node min(2^k, L(i)) hops after i, cnt[i] = min(2^k, L(i)).
    """
    i = pl.program_id(0)
    live = jmp_ref[i] >= 0
    jump_out[...] = jnp.where(live, jump_at_ref[...], NULL)
    cnt_out[...] = cnt_ref[...] + jnp.where(live, cnt_at_ref[...], 0)


def jump_double(jump: jax.Array, cnt: jax.Array, *,
                segments: Optional[np.ndarray] = None,
                seg_rows: int = 0,
                interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """jump, cnt: (N,) int32.  Returns (jump', cnt') after one doubling
    round: jump'[i] = jump[jump[i]] (NULL absorbing), cnt'[i] = cnt[i] +
    cnt[jump[i]] for live lanes.  Out-of-range pointers terminate like
    NULL (the shared torn-epoch contract of core.recovery.jump_tables):
    sanitized here, so every round's output is in-range-or-NULL.

    With ``segments``/``seg_rows`` the arrays are shard-major packed
    (per-shard views of a sharded region, concatenated) while pointer
    VALUES are global ids: the steering array handed to the scalar
    prefetcher is the pointers' packed POSITION (closed-form translate),
    so each gather lands inside the right shard's segment."""
    n = jump.shape[0]
    jump = jnp.where((jump >= 0) & (jump < n), jump, NULL)
    if segments is not None:
        steer = packed_positions(jump, seg_rows, segments).astype(jnp.int32)
    else:
        steer = jump
    grid = (n,)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1),
                         lambda i, p_ref: (jnp.maximum(p_ref[i], 0), 0)),
            pl.BlockSpec((1, 1),
                         lambda i, p_ref: (jnp.maximum(p_ref[i], 0), 0)),
            pl.BlockSpec((1, 1), lambda i, p_ref: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i, p_ref: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, p_ref: (i, 0)),
        ],
    )
    global KERNEL_CALLS
    KERNEL_CALLS += 1
    j2, c2 = pl.pallas_call(
        _double_kernel,
        grid_spec=spec,
        out_shape=(jax.ShapeDtypeStruct((n, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n, 1), jnp.int32)),
        interpret=interpret,
    )(steer, jump[:, None], cnt[:, None], cnt[:, None])
    return j2[:, 0], c2[:, 0]


def _gather_kernel(steer_ref, val_at_ref, out):
    """One chain hop for lane i = program_id(0): the val block is
    steered to row steer[i] (clamped to 0 when the lane is retired);
    the body only masks retired lanes to NULL."""
    i = pl.program_id(0)
    live = steer_ref[i] >= 0
    out[...] = jnp.where(live, val_at_ref[...], NULL)


def gather_next(nxt: jax.Array, ids, *,
                segments: Optional[np.ndarray] = None,
                seg_rows: int = 0,
                interpret: bool) -> jax.Array:
    """One contraction hop for a batch of lanes: out[i] = nxt[ids[i]]
    (NULL lanes stay NULL; out-of-range ids terminate, the shared
    torn-epoch contract).  ``nxt`` is the sanitized (n,) int32 pointer
    column — shard-major packed when ``segments``/``seg_rows`` are
    given, in which case the scalar-prefetched steering is the ids'
    packed POSITION while ids and gathered values stay global.  This is
    the kernel the contraction local-walk and expand rounds ride: the
    same prefetch-steered dynamic gather as `jump_double`, minus the
    count lane."""
    n = nxt.shape[0]
    if isinstance(ids, np.ndarray):
        # range-check at the caller's full width BEFORE the int32
        # narrowing: a torn 2**32+3 must terminate, not alias node 3
        # (jnp.asarray would truncate it silently under 32-bit jax)
        ids = np.where((ids >= 0) & (ids < n), ids, NULL).astype(np.int32)
    ids = jnp.asarray(ids, jnp.int32)
    ids = jnp.where((ids >= 0) & (ids < n), ids, NULL)
    if segments is not None:
        steer = packed_positions(ids, seg_rows, segments).astype(jnp.int32)
    else:
        steer = ids
    grid = (ids.shape[0],)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1),
                         lambda i, p_ref: (jnp.maximum(p_ref[i], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i, p_ref: (i, 0)),
    )
    global KERNEL_CALLS
    KERNEL_CALLS += 1
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((ids.shape[0], 1), jnp.int32),
        interpret=interpret,
    )(steer, nxt[:, None])
    return out[:, 0]


def walk_segments(nxt: jax.Array, starts, *, k: int, head: int,
                  n_mult: int, promoted: bool,
                  segments: Optional[np.ndarray] = None,
                  seg_rows: int = 0, budget: int = 64,
                  interpret: bool
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Walk every lane's chain segment toward its next spine node in ONE
    ``pallas_call``: an in-kernel ``fori_loop`` takes up to ``budget``
    hops per lane (lanes freeze the step they arrive at a spine node or
    the chain ends), replacing the one-host-roundtrip-per-hop
    `gather_next` cascade of the contraction local walk.  The whole
    (sanitized) pointer column rides in as a single block and each hop
    is a dynamic in-kernel load — spine membership stays the arithmetic
    ``id % k == 0`` test (plus the promoted head), so no lookup table
    crosses the host boundary either.

    Returns ``(cur, sp, w)`` per lane: final global id (NULL once the
    chain ended), arrival spine index (NULL if still walking or the
    chain ended), and hops taken this call.  A lane with ``cur >= 0``
    and ``sp == NULL`` ran out of budget — feed ``cur`` back in to
    continue (weights accumulate at the caller).

    ``segments``/``seg_rows``: shard-major packed layout; the packed
    position of each hop's global pointer is the same closed form as
    `packed_positions`, evaluated in-kernel."""
    n = nxt.shape[0]
    starts = jnp.asarray(starts, jnp.int32)
    if segments is not None:
        segs = jnp.asarray(np.asarray(segments), jnp.int32)
        n_shards = len(segments) - 1
    else:
        segs = jnp.zeros(1, jnp.int32)
        n_shards = 1
    sr = max(int(seg_rows), 1)
    kk, hd, nm = int(k), int(head), int(n_mult)

    def kern(start_ref, seg_ref, nxt_ref, cur_out, sp_out, w_out):
        i = pl.program_id(0)

        def pos(c):
            if n_shards == 1:
                return c
            shard = (c // sr) % n_shards
            local = (c // (sr * n_shards)) * sr + c % sr
            return seg_ref[shard] + local

        def spidx(c):
            sp = jnp.where(c % kk == 0, c // kk, NULL)
            if promoted:
                sp = jnp.where(c == hd, nm, sp)
            return sp

        def hop(_, st):
            cur, w, sp, done = st
            nv = nxt_ref[pl.ds(pos(jnp.maximum(cur, 0)), 1), :][0, 0]
            live = jnp.logical_not(done)
            cur2 = jnp.where(live, nv, cur)
            w2 = jnp.where(live, w + 1, w)
            spv = spidx(cur2)
            arrived = live & (cur2 >= 0) & (spv >= 0)
            sp2 = jnp.where(arrived, spv, sp)
            done2 = done | (live & ((cur2 < 0) | arrived))
            return cur2, w2, sp2, done2

        g = start_ref[i]
        cur, w, sp, _ = jax.lax.fori_loop(
            0, budget, hop,
            (g, jnp.int32(0), jnp.int32(NULL), g < 0))
        cur_out[...] = jnp.full((1, 1), cur, jnp.int32)
        sp_out[...] = jnp.full((1, 1), sp, jnp.int32)
        w_out[...] = jnp.full((1, 1), w, jnp.int32)

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(starts.shape[0],),
        in_specs=[pl.BlockSpec((n, 1), lambda i, s_ref, g_ref: (0, 0))],
        out_specs=[pl.BlockSpec((1, 1), lambda i, s_ref, g_ref: (i, 0)),
                   pl.BlockSpec((1, 1), lambda i, s_ref, g_ref: (i, 0)),
                   pl.BlockSpec((1, 1), lambda i, s_ref, g_ref: (i, 0))],
    )
    global KERNEL_CALLS
    KERNEL_CALLS += 1
    c2, sp, w = pl.pallas_call(
        kern,
        grid_spec=spec,
        out_shape=(jax.ShapeDtypeStruct((starts.shape[0], 1), jnp.int32),
                   jax.ShapeDtypeStruct((starts.shape[0], 1), jnp.int32),
                   jax.ShapeDtypeStruct((starts.shape[0], 1), jnp.int32)),
        interpret=interpret,
    )(starts, segs, nxt[:, None])
    return c2[:, 0], sp[:, 0], w[:, 0]


def expand_segments(nxt: jax.Array, starts, posn, rem, count: int, *,
                    segments: Optional[np.ndarray] = None,
                    seg_rows: int = 0,
                    interpret: bool) -> np.ndarray:
    """Emit every node of the used contraction segments into the final
    order array in ONE ``pallas_call``: lane i walks ``rem[i]`` hops
    from ``starts[i]``, storing each visited global id at
    ``out[posn[i] + t]`` — the whole (count,) order block persists
    across the sequential grid (every step maps block (0, 0)), so the
    lanes' disjoint runs land in a single kernel instead of one
    host-roundtripped gather per hop.  Retired steps re-store the
    lane's own first slot with its own first value, so no mask is
    needed and no other lane's run is disturbed."""
    n = nxt.shape[0]
    starts = jnp.asarray(starts, jnp.int32)
    posn = jnp.asarray(posn, jnp.int32)
    rem_np = np.asarray(rem, np.int64)
    remj = jnp.asarray(rem_np, jnp.int32)
    L = int(starts.shape[0])
    max_rem = int(rem_np.max()) if L else 0
    if segments is not None:
        segs = jnp.asarray(np.asarray(segments), jnp.int32)
        n_shards = len(segments) - 1
    else:
        segs = jnp.zeros(1, jnp.int32)
        n_shards = 1
    sr = max(int(seg_rows), 1)

    def kern(start_ref, pos_ref, rem_ref, seg_ref, nxt_ref, out_ref):
        i = pl.program_id(0)

        def pos(c):
            if n_shards == 1:
                return c
            shard = (c // sr) % n_shards
            local = (c // (sr * n_shards)) * sr + c % sr
            return seg_ref[shard] + local

        g0 = start_ref[i]
        p0 = pos_ref[i]
        r = rem_ref[i]

        def hop(t, st):
            cur, p = st
            live = t < r
            out_ref[pl.ds(jnp.where(live, p, p0), 1), :] = jnp.full(
                (1, 1), jnp.where(live, cur, g0), jnp.int32)
            nv = nxt_ref[pl.ds(pos(jnp.maximum(cur, 0)), 1), :][0, 0]
            return jnp.where(t + 1 < r, nv, cur), p + 1

        jax.lax.fori_loop(0, max_rem, hop, (g0, p0))

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(L,),
        in_specs=[pl.BlockSpec((n, 1), lambda i, *_: (0, 0))],
        out_specs=pl.BlockSpec((count, 1), lambda i, *_: (0, 0)),
    )
    global KERNEL_CALLS
    KERNEL_CALLS += 1
    out = pl.pallas_call(
        kern,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((count, 1), jnp.int32),
        interpret=interpret,
    )(starts, posn, remj, segs, nxt[:, None])
    return np.asarray(out[:, 0], np.int64)


def chain_tables_device(nxt: np.ndarray, bits: int, *,
                        segments: Optional[np.ndarray] = None,
                        seg_rows: int = 0,
                        interpret: bool
                        ) -> Tuple[List[np.ndarray], np.ndarray]:
    """Binary-lifting tables via the kernel: returns ([jump^(2^k) for
    k < bits], counts) with counts[i] = min(2^bits, chain length from i).

    ``segments``/``seg_rows``: `nxt` is shard-major packed (see module
    docstring); tables then hold GLOBAL ids at PACKED positions."""
    # sanitize at full width BEFORE the int32 narrowing: a torn 64-bit
    # pointer like 2**32+3 would otherwise wrap to a valid-looking 3
    # instead of terminating the chain (the module-wide OOB contract)
    nxt = np.asarray(nxt)
    n = nxt.shape[0]
    jump = jnp.asarray(np.where((nxt >= 0) & (nxt < n), nxt, NULL),
                       jnp.int32)
    cnt = jnp.ones(nxt.shape[0], jnp.int32)
    tables = [np.asarray(jump, np.int64)]
    for _ in range(bits - 1):
        jump, cnt = jump_double(jump, cnt, segments=segments,
                                seg_rows=seg_rows, interpret=interpret)
        tables.append(np.asarray(jump, np.int64))
    # one more round so counts saturate past 2^(bits-1)-long chains
    _, cnt = jump_double(jump, cnt, segments=segments, seg_rows=seg_rows,
                         interpret=interpret)
    return tables, np.asarray(cnt, np.int64)


def _snapshot_verify_device(nxt: np.ndarray, head: int, cand: np.ndarray,
                            segments, seg_rows: int,
                            interpret: bool) -> bool:
    """Verify an order-snapshot candidate (DESIGN.md §10) with ONE
    `gather_next` round: succ[i] = nxt[cand[i]] must equal cand[i+1]
    for every internal link and NULL at the last element (the chain
    must END there — that completeness check replaces the host
    primitive's explicit count comparison, so the device path needs no
    O(N) table build to adopt a snapshot).  NEXT is a function of node
    id, so a candidate that passes IS the chain order from `head` —
    duplicates would force nxt[cand[-1]] to be both NULL and a live
    successor."""
    n = np.asarray(nxt).shape[0]
    if cand.size == 0 or cand[0] != head:
        return False
    if ((cand < 0) | (cand >= n)).any():
        return False
    sane = np.where((np.asarray(nxt) >= 0) & (np.asarray(nxt) < n),
                    np.asarray(nxt), NULL)
    succ = np.asarray(gather_next(jnp.asarray(sane, jnp.int32), cand,
                                  segments=segments, seg_rows=seg_rows,
                                  interpret=interpret), np.int64)
    if succ[-1] != NULL:
        return False                 # chain continues past the candidate
    return bool(np.array_equal(succ[:-1], cand[1:]))


def chain_order_device(nxt: np.ndarray, head: int, *,
                       segments: Optional[np.ndarray] = None,
                       seg_rows: int = 0,
                       method: str = "auto",
                       k: int = 0,
                       fuse: bool = True,
                       snapshot: Optional[ChainSnapshot] = None,
                       interpret: bool) -> np.ndarray:
    """Full device-built chain order.  ``method`` — "double" (the
    doubling rounds run in the Pallas kernel; the final node-at-position
    extraction is a cheap O(count log count) gather off the returned
    tables), "contract" (the contraction list ranking: `gather_next`
    local-walk rounds, `jump_double` rank over the ~n/k contracted
    chain, `gather_next` expand rounds), or "auto" — the SAME heuristic
    as the host primitive (`core.recovery.chain_method`), so host and
    device flip strategies at the same size.  A head outside [0, n) is
    a terminated chain (empty order) — the same OOB contract as the
    host primitive.

    ``segments``/``seg_rows`` accept the shard-major packed NEXT column
    of a sharded region (the per-shard persistent views, concatenated —
    no host re-gather); `head` and the returned order are global ids
    either way, on both methods (the contraction rank runs in
    spine-index space, which is layout-free).

    ``snapshot``: an order-snapshot candidate (core.recovery
    .ChainSnapshot, DESIGN.md §10).  Verified with one `gather_next`
    round; on success the candidate is returned directly (outcome
    "snapshot") and the ranking is skipped entirely — on mismatch the
    full device ranking runs (outcome = the ranking method, replayed =
    full chain length), the same contract as the host primitive."""
    n = nxt.shape[0]
    if head < 0 or head >= n:
        return np.empty(0, np.int64)
    if snapshot is not None:
        cand = np.asarray(snapshot.candidate, np.int64).ravel()
        if _snapshot_verify_device(nxt, head, cand, segments, seg_rows,
                                   interpret):
            snapshot.outcome = "snapshot"
            return cand.copy()
        snapshot.outcome = chain_method(n, None, method)
        order = chain_order_device(nxt, head, segments=segments,
                                   seg_rows=seg_rows, method=method, k=k,
                                   fuse=fuse, interpret=interpret)
        snapshot.replayed = int(order.size)
        return order
    if chain_method(n, None, method) == "contract":
        return _order_device_contract(nxt, head, k or CONTRACT_K,
                                      segments, seg_rows, interpret,
                                      fuse=fuse)

    def pos_of(ids):
        if segments is None:
            return ids
        return packed_positions(ids, seg_rows, segments)

    bits = max(1, int(n).bit_length())
    tables, cnt = chain_tables_device(nxt, bits, segments=segments,
                                      seg_rows=seg_rows,
                                      interpret=interpret)
    count = int(cnt[pos_of(np.asarray([head], np.int64))[0]])
    if count > n:
        raise RuntimeError("cycle in chain")
    pos = np.arange(count)
    cur = np.full(count, head, np.int64)
    for b in range(len(tables)):
        m = (pos >> b) & 1 == 1
        if m.any():
            cur[m] = tables[b][pos_of(cur[m])]
    return cur


def _order_device_contract(nxt: np.ndarray, head: int, k: int,
                           segments: Optional[np.ndarray],
                           seg_rows: int,
                           interpret: bool,
                           fuse: bool = True) -> np.ndarray:
    """Contraction list ranking with every chain hop in a Pallas
    kernel; the host orchestrates lane bookkeeping between rounds, the
    established chain_tables_device split.

    ``fuse=True`` (default) runs the local walk through `walk_segments`
    — one ``pallas_call`` covers up to ``budget`` hops for every lane,
    so the typical segment (~k hops) resolves in a single round trip
    instead of one per hop; ``fuse=False`` keeps the per-hop
    `gather_next` cascade (the recovery_bench baseline rows).

    Spine membership is pure arithmetic (``id % k == 0``, plus the one
    promoted head), so the local walk needs no spine-position table:
    the contracted index of global id g is ``g // k`` for sampled rows
    and ``ceil(n/k)`` for the promoted head."""
    # sanitize at 64-bit BEFORE the int32 narrowing (module-wide OOB
    # contract, same as chain_tables_device)
    nxt = np.asarray(nxt)
    n = nxt.shape[0]
    jnxt = jnp.asarray(np.where((nxt >= 0) & (nxt < n), nxt, NULL),
                       jnp.int32)
    n_mult = (n + k - 1) // k            # sampled spine rows
    promoted = head % k != 0
    spine = np.arange(0, n, k, dtype=np.int64)
    if promoted:
        spine = np.concatenate([spine, [head]])
    S = spine.size

    def spine_idx(ids):                  # global id -> spine index
        out = np.where(ids % k == 0, ids // k, NULL)
        if promoted:
            out = np.where(ids == head, n_mult, out)
        return out.astype(np.int64)

    cnext = np.full(S, NULL, np.int64)
    if fuse:
        # ---- local walk, fused: one walk_segments call covers up to
        # `budget` hops for every live lane; lanes that exhaust the
        # budget (segment longer than budget) feed their cursor back in
        # and weights accumulate — typically ONE round trip total
        w = np.zeros(S, np.int64)
        lanes = np.arange(S)
        cur = spine
        budget = max(2 * k, 64)
        hops = 0
        while lanes.size and hops <= n:
            c2, sp, wd = walk_segments(
                jnxt, cur, k=k, head=head, n_mult=n_mult,
                promoted=promoted, segments=segments, seg_rows=seg_rows,
                budget=budget, interpret=interpret)
            c2 = np.asarray(c2, np.int64)
            sp = np.asarray(sp, np.int64)
            w[lanes] += np.asarray(wd, np.int64)
            arrived = sp >= 0
            if arrived.any():
                cnext[lanes[arrived]] = sp[arrived]
            alive = (c2 >= 0) & ~arrived
            lanes = lanes[alive]
            cur = c2[alive]
            hops += budget
        if lanes.size:                   # spine-free cycle: poison
            w[lanes] = n + 1
        w = np.maximum(w, 1)
    else:
        # ---- local walk, per-hop baseline: one gather_next round per
        # segment hop, lanes retired (and compacted away) as they reach
        # the next spine node
        w = np.ones(S, np.int64)
        lanes = np.arange(S)
        cur = np.asarray(gather_next(jnxt, spine, segments=segments,
                                     seg_rows=seg_rows,
                                     interpret=interpret), np.int64)
        for _ in range(n + 1):
            if not lanes.size:
                break
            sp = np.where(cur >= 0, spine_idx(np.maximum(cur, 0)), NULL)
            arrived = sp >= 0
            if arrived.any():
                cnext[lanes[arrived]] = sp[arrived]
            keep = (cur >= 0) & ~arrived
            lanes = lanes[keep]
            if lanes.size:
                w[lanes] += 1
                cur = np.asarray(gather_next(jnxt, cur[keep],
                                             segments=segments,
                                             seg_rows=seg_rows,
                                             interpret=interpret),
                                 np.int64)
        if lanes.size:                   # spine-free cycle: poison
            w[lanes] = n + 1

    # ---- rank the contracted chain with the existing doubling tables
    # (spine-index space: dense, layout-free, in-cache) — weights seed
    # the count lane, so counts come out as global hop totals
    hpos = n_mult if promoted else head // k
    bits = max(1, int(S).bit_length())
    jq = jnp.asarray(cnext, jnp.int32)
    cw = jnp.asarray(np.minimum(w, n + 1), jnp.int32)
    tables = [np.asarray(jq, np.int64)]
    for _ in range(bits):
        jq, cw = jump_double(jq, cw, interpret=interpret)
        tables.append(np.asarray(jq, np.int64))
    if int(np.asarray(jq)[hpos]) != NULL:
        raise RuntimeError("cycle in chain")   # cycle through spine nodes
    count = int(np.asarray(cw)[hpos])
    if count > n:
        raise RuntimeError("cycle in chain")   # poisoned spine-free cycle
    # contracted position walk off the tables (host, like the doubling
    # path's extraction), then exclusive-cumsum weights -> global starts
    cap = min(count, S)
    posq = np.arange(cap)
    curq = np.full(cap, hpos, np.int64)
    dead = np.zeros(cap, bool)
    for b in range(len(tables)):
        m = ((posq >> b) & 1 == 1) & ~dead
        if m.any():
            curq[m] = tables[b][curq[m]]
            dead |= curq == NULL
    wq = np.where(dead, 0, w[np.where(dead, 0, curq)])
    g = np.concatenate([[0], np.cumsum(wq)[:-1]])
    use = ~dead & (g < count)

    # ---- expand: re-walk only the used segments, emitting into out
    cur = spine[curq[use]]
    posn = g[use]
    rem = np.minimum(wq[use], count - posn)
    if fuse:
        # all runs land in one emitting pallas_call (the same fusion as
        # the local walk, plus in-kernel stores at each lane's offsets)
        if cur.size == 0:
            return np.empty(count, np.int64)
        return expand_segments(jnxt, cur, posn, rem, count,
                               segments=segments, seg_rows=seg_rows,
                               interpret=interpret)
    out = np.empty(count, np.int64)
    while cur.size:
        out[posn] = cur
        rem -= 1
        kp = rem > 0
        if not kp.any():
            break
        cur = np.asarray(gather_next(jnxt, cur[kp], segments=segments,
                                     seg_rows=seg_rows,
                                     interpret=interpret), np.int64)
        posn = posn[kp] + 1
        rem = rem[kp]
    return out
