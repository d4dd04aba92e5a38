"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Sweeps shapes/dtypes per kernel and asserts allclose against ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (chain_order, hash_probe, ops, pack_flush,
                           quant_pack, ref)

KEY = jax.random.PRNGKey(0)


def test_interpret_only_on_cpu(monkeypatch):
    """Kernels interpret on the CPU and compile on a TPU; any other
    backend is refused instead of silently interpreted."""
    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()


# ---------------------------------------------------------------- pack

@pytest.mark.parametrize("n,d", [(8, 128), (64, 256), (33, 384), (128, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_pack_rows_sweep(n, d, dtype):
    src = (jax.random.normal(KEY, (n, d)) * 10).astype(dtype)
    idx = jnp.asarray(
        np.random.default_rng(1).choice(n + 1, size=min(n, 16)) - 1,
        jnp.int32)  # includes -1 sentinels
    got = pack_flush.pack_rows(src, idx, interpret=True)
    want = ref.pack_rows_ref(src, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,d", [(16, 128), (64, 512), (40, 896)])
def test_scatter_rows_roundtrip(n, d):
    src = jax.random.normal(KEY, (n, d))
    m = n // 2
    idx = jnp.asarray(np.random.default_rng(2).choice(n, m, replace=False),
                      jnp.int32)
    packed = pack_flush.pack_rows(src, idx, block_d=128, interpret=True)
    dst = jnp.zeros((n, d))
    got = pack_flush.scatter_rows(dst, packed, idx, block_d=128,
                                  interpret=True)
    want = ref.scatter_rows_ref(dst, packed, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # scatter(pack(x)) restores exactly the selected rows
    np.testing.assert_array_equal(np.asarray(got[idx]), np.asarray(src[idx]))


def test_pack_unaligned_width_via_ops():
    """ops.pack_rows pads non-128-multiple widths (the Fig-12 alignment
    path) and unpads the result."""
    src = jax.random.normal(KEY, (32, 300))
    idx = jnp.array([3, 1, -1, 31], jnp.int32)
    got = ops.pack_rows(src, idx)
    want = ref.pack_rows_ref(src, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------ quantize

@pytest.mark.parametrize("n,d", [(8, 256), (64, 512), (16, 2048)])
@pytest.mark.parametrize("scale", [1e-3, 1.0, 1e3])
def test_quantize_blockwise_sweep(n, d, scale):
    x = jax.random.normal(KEY, (n, d)) * scale
    q, s = quant_pack.quantize_blockwise(x, interpret=True)
    qr, sr = ref.quantize_blockwise_ref(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    # dequant error bound: |x - dq| <= scale_per_group (1/127 of absmax)
    dq = quant_pack.dequantize_blockwise(q, s, interpret=True)
    err = np.abs(np.asarray(x) - np.asarray(dq))
    bound = np.repeat(np.asarray(s), quant_pack.GROUP, axis=1) * 0.5001
    assert (err <= bound + 1e-9).all()


def test_quantize_leaf_any_shape():
    for shape in [(7,), (3, 5), (2, 3, 4, 5), ()]:
        x = jax.random.normal(KEY, shape) * 3
        q, s = ops.quantize_leaf(x)
        back = ops.dequantize_leaf(q, s, x.shape, x.dtype)
        assert back.shape == x.shape
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   atol=0.05 * max(1.0, float(jnp.max(jnp.abs(x)) if x.size else 0.0)))


# ---------------------------------------------------------- hash probe

def test_hash_probe_matches_ref():
    nb = 64
    rng = np.random.default_rng(3)
    table = np.full((nb, hash_probe.BUCKET), -1, np.int32)
    keys = rng.choice(100000, 500, replace=False).astype(np.int32)
    # place each key in its hash bucket (first free lane)
    for k in keys:
        b = int(np.asarray(ops.hash32(jnp.asarray([k]))[0]) % nb)
        lane = int(np.argmax(table[b] == -1))
        table[b, lane] = k
    tbl = jnp.asarray(table)
    queries = jnp.asarray(np.concatenate([keys[:64],
                                          keys[:32] + 1000000]), jnp.int32)
    h = ops.hash32(queries)
    bids = (h % jnp.uint32(nb)).astype(jnp.int32)
    got = hash_probe.probe(tbl, queries, bids, interpret=True)
    want = ref.probe_ref(tbl, queries, bids)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # present keys found, absent -> -1
    assert (np.asarray(got[:64]) >= 0).all()
    assert (np.asarray(got[64:]) == -1).all()


def test_hash_lookup_end_to_end():
    nb = 32
    keys = jnp.arange(100, 150, dtype=jnp.int32)
    table = np.full((nb, hash_probe.BUCKET), -1, np.int32)
    for k in np.asarray(keys):
        b = int(np.asarray(ops.hash32(jnp.asarray([k]))[0]) % nb)
        table[b, np.argmax(table[b] == -1)] = k
    got = ops.hash_lookup(jnp.asarray(table),
                          jnp.array([100, 149, 999], jnp.int32))
    g = np.asarray(got)
    assert g[0] >= 0 and g[1] >= 0 and g[2] == -1


# ----------------------------------------------------- chain order (§V-F)

@pytest.mark.parametrize("n", [8, 61, 256])
def test_jump_double_matches_ref(n):
    rng = np.random.default_rng(5)
    perm = rng.permutation(n)
    nxt = np.full(n, -1, np.int32)
    nxt[perm[:-1]] = perm[1:]
    jump = jnp.asarray(nxt)
    cnt = jnp.ones(n, jnp.int32)
    for _ in range(3):   # stays an oracle match through several rounds
        gj, gc = chain_order.jump_double(jump, cnt, interpret=True)
        wj, wc = ref.jump_double_ref(jump, cnt)
        np.testing.assert_array_equal(np.asarray(gj), np.asarray(wj))
        np.testing.assert_array_equal(np.asarray(gc), np.asarray(wc))
        jump, cnt = gj, gc


def test_chain_order_device_matches_numpy_primitive():
    from repro.core.recovery import chain_order as chain_order_np
    rng = np.random.default_rng(6)
    n = 128
    perm = rng.permutation(n)
    live = perm[:97]                       # chain covers a strict subset
    nxt = np.full(n, -1, np.int64)
    nxt[live[:-1]] = live[1:]
    head = int(live[0])
    got = chain_order.chain_order_device(nxt, head, interpret=True)
    want = chain_order_np(nxt, head)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, live)


def test_chain_order_device_detects_cycle():
    nxt = np.array([1, 2, 0, -1], np.int64)
    with pytest.raises(RuntimeError, match="cycle"):
        chain_order.chain_order_device(nxt, 0, interpret=True)


def test_chain_order_device_treats_oob_pointer_as_terminator():
    """Torn-epoch contract parity with the numpy primitive: a pointer
    flushed past the committed fresh-water mark ends the chain."""
    from repro.core.recovery import chain_order as chain_order_np
    nxt = np.array([1, 8, -1, -1], np.int64)     # 8 is out of range (n=4)
    got = chain_order.chain_order_device(nxt, 0, interpret=True)
    np.testing.assert_array_equal(got, [0, 1])
    np.testing.assert_array_equal(got, chain_order_np(nxt, 0))


@pytest.mark.parametrize("n,B,N", [(203, 8, 3), (256, 64, 4), (40, 16, 4)])
def test_chain_order_device_segments_matches_global(n, B, N):
    """The sharded-arena path (DESIGN.md §7): the NEXT column arrives as
    per-shard views concatenated shard-major (`segments` offsets), with
    pointer values still global — the kernel's steering translate must
    reproduce the global-array order exactly."""
    from repro.core.recovery import chain_order as chain_order_np
    rng = np.random.default_rng(n)
    perm = rng.permutation(n)
    nxt = np.full(n, -1, np.int64)
    nxt[perm[:-1]] = perm[1:]
    head = int(perm[0])
    shard_of = (np.arange(n) // B) % N
    segments = np.zeros(N + 1, np.int64)
    packed = np.empty(n, np.int64)
    off = 0
    for s in range(N):
        gidx = np.nonzero(shard_of == s)[0]
        packed[off:off + gidx.size] = nxt[gidx]
        segments[s] = off
        off += gidx.size
    segments[N] = off
    # the closed-form translate IS the packing
    pp = chain_order.packed_positions(np.arange(n, dtype=np.int64), B,
                                      segments)
    np.testing.assert_array_equal(packed[pp], nxt)
    got = chain_order.chain_order_device(packed, head, segments=segments,
                                         seg_rows=B, interpret=True)
    np.testing.assert_array_equal(got, chain_order_np(nxt, head))


def test_chain_order_device_segments_from_sharded_dll():
    """End to end: a sharded arena's per-shard persistent NEXT views,
    concatenated WITHOUT any host re-gather, recover the DLL order the
    host primitive computes from the global volatile array."""
    from repro.core.arena import open_arena
    from repro.pstruct import dll as DL

    a = open_arena(None, DL.DoublyLinkedList.layout(256), n_shards=4)
    d = DL.DoublyLinkedList(a, 256)
    rng = np.random.default_rng(3)
    ids = d.append_batch(rng.integers(0, 9, (180, 7)).astype(np.int64))
    d.delete_batch(ids[30:60])
    a.commit()
    region = a.regions["dll.nodes"]
    packed = np.concatenate([
        sl._pview()[:, DL.DATA_WORDS] for sl in region.slices
        if sl is not None])
    segments = np.cumsum([0] + [0 if sl is None else sl.shape[0]
                                for sl in region.slices])
    got = chain_order.chain_order_device(
        packed, d.head, segments=segments, seg_rows=DL.SHARD_SEG,
        interpret=True)
    np.testing.assert_array_equal(got, d.to_list())
    # the contraction path must agree bit-for-bit on the SAME packed
    # layout (acceptance: sharded packed layout included), fused
    # walk/expand kernels and the per-hop cascade alike
    for fuse in (False, True):
        got_c = chain_order.chain_order_device(
            packed, d.head, segments=segments, seg_rows=DL.SHARD_SEG,
            method="contract", k=16, fuse=fuse, interpret=True)
        np.testing.assert_array_equal(got_c, d.to_list())


# ------------------------- contraction list ranking, device (§8)


@pytest.mark.parametrize("fuse", [False, True])
@pytest.mark.parametrize("k", [4, 32])
def test_chain_order_device_contract_matches_host(k, fuse):
    from repro.core.recovery import chain_order as chain_order_np
    rng = np.random.default_rng(7)
    n = 96
    perm = rng.permutation(n)
    live = perm[:71]
    nxt = np.full(n, -1, np.int64)
    nxt[live[:-1]] = live[1:]
    head = int(live[0])
    got = chain_order.chain_order_device(nxt, head, method="contract",
                                         k=k, fuse=fuse, interpret=True)
    np.testing.assert_array_equal(got, chain_order_np(nxt, head))
    np.testing.assert_array_equal(got, live)


def test_contract_fused_saves_round_trips():
    """The fused walk/expand kernels must resolve the same order in
    strictly fewer pallas_call round trips than the per-hop cascade —
    the deterministic quantity the fusion exists to shrink."""
    rng = np.random.default_rng(11)
    n = 512
    perm = rng.permutation(n)
    nxt = np.full(n, -1, np.int64)
    nxt[perm[:-1]] = perm[1:]
    calls = {}
    for fuse in (False, True):
        chain_order.KERNEL_CALLS = 0
        got = chain_order.chain_order_device(
            nxt, int(perm[0]), method="contract", k=8, fuse=fuse,
            interpret=True)
        np.testing.assert_array_equal(got, perm)
        calls[fuse] = chain_order.KERNEL_CALLS
    assert calls[True] < calls[False], calls


@pytest.mark.parametrize("method", ["double", "contract"])
def test_chain_order_device_mid_chain_cycle(method):
    """A cycle reachable only MID-chain (head not on it) raises on both
    device strategies: 0 -> 1 -> 2 -> 3 -> 1."""
    nxt = np.array([1, 2, 3, 1], np.int64)
    with pytest.raises(RuntimeError, match="cycle"):
        chain_order.chain_order_device(nxt, 0, method=method, k=2,
                                       interpret=True)


@pytest.mark.parametrize("fuse", [False, True])
def test_chain_order_device_contract_spine_free_cycle(fuse):
    """A mid-chain cycle containing no sampled spine node: the device
    local walk must poison the stuck segment (not spin) and still
    surface "cycle"."""
    nxt = np.full(16, -1, np.int64)
    nxt[0] = 9
    nxt[9], nxt[10], nxt[11] = 10, 11, 9     # 9/10/11 all % 8 != 0
    with pytest.raises(RuntimeError, match="cycle"):
        chain_order.chain_order_device(nxt, 0, method="contract", k=8,
                                       fuse=fuse, interpret=True)


@pytest.mark.parametrize("fuse", [False, True])
def test_chain_order_device_contract_oob_and_empty(fuse):
    from repro.core.recovery import chain_order as chain_order_np
    nxt = np.array([1, 8, -1, -1], np.int64)     # 8 OOB terminates
    got = chain_order.chain_order_device(nxt, 0, method="contract", k=2,
                                         fuse=fuse, interpret=True)
    np.testing.assert_array_equal(got, chain_order_np(nxt, 0))
    assert chain_order.chain_order_device(
        nxt, -1, method="contract", k=2, fuse=fuse,
        interpret=True).size == 0
    assert chain_order.chain_order_device(
        nxt, 99, method="contract", k=2, fuse=fuse,
        interpret=True).size == 0


# --------------------------------------- chain primitive edge cases


def test_chain_empty_chain_everywhere():
    """NULL head / empty table: every primitive returns empty, never
    indexes."""
    from repro.core import recovery as R
    nxt = np.full(4, -1, np.int64)
    assert R.chain_order(nxt, R.NULL).size == 0
    assert R.chain_order(nxt, R.NULL, 0).size == 0
    assert chain_order.chain_order_device(nxt, -1, interpret=True).size == 0
    empty = np.empty(0, np.int64)
    assert R.chain_lengths(empty, empty).size == 0
    assert R.chain_walk(nxt, empty).shape == (0, 0)


def test_chain_single_node():
    from repro.core import recovery as R
    nxt = np.array([-1], np.int64)
    np.testing.assert_array_equal(R.chain_order(nxt, 0), [0])
    np.testing.assert_array_equal(R.chain_order(nxt, 0, 1), [0])
    np.testing.assert_array_equal(
        chain_order.chain_order_device(nxt, 0, interpret=True), [0])
    np.testing.assert_array_equal(R.chain_lengths(nxt, np.array([0])), [1])
    np.testing.assert_array_equal(R.chain_walk(nxt, np.array([0])),
                                  [[0]])


def test_chain_self_loop_guard():
    """A self-loop (nxt[i] == i, the smallest cycle) must fail loudly in
    every primitive, host and device."""
    from repro.core import recovery as R
    nxt = np.array([-1, 1, -1], np.int64)        # node 1 points at itself
    with pytest.raises(RuntimeError, match="cycle"):
        R.chain_order(nxt, 1)
    with pytest.raises(RuntimeError, match="cycle"):
        R.chain_lengths(nxt, np.array([1]))
    with pytest.raises(RuntimeError, match="cycle"):
        R.chain_walk(nxt, np.array([1]))
    with pytest.raises(RuntimeError, match="cycle"):
        chain_order.chain_order_device(nxt, 1, interpret=True)


@pytest.mark.parametrize("bad", [2 ** 31 - 1, 2 ** 31, 2 ** 31 + 5,
                                 2 ** 32 + 3, -(2 ** 31)])
def test_chain_int32_overflow_adjacent_pointers_terminate(bad):
    """Torn 64-bit pointers adjacent to the int32 boundary must behave
    as terminators, not wrap through the int32 working arrays into
    valid-looking node ids (2**32+3 would alias node 3)."""
    from repro.core import recovery as R
    nxt = np.array([1, 2, bad, -1, -1], np.int64)   # 0 -> 1 -> 2 -> X
    np.testing.assert_array_equal(R.chain_order(nxt, 0), [0, 1, 2])
    np.testing.assert_array_equal(
        chain_order.chain_order_device(nxt, 0, interpret=True), [0, 1, 2])
    np.testing.assert_array_equal(R.chain_lengths(nxt, np.array([0])), [3])
    np.testing.assert_array_equal(
        R.chain_walk(nxt, np.array([0], np.int64))[0], [0, 1, 2])
    # an overflow-adjacent HEAD is an already-terminated chain
    assert R.chain_lengths(nxt, np.array([bad]))[0] == 0


def test_chain_order_oob_head_is_empty():
    """Heads outside [0, n): the DLL header's HEAD field flushed by a
    torn epoch into uncommitted territory — empty chain, not a fault,
    in all four primitives (host + device)."""
    from repro.core import recovery as R
    nxt = np.array([1, -1], np.int64)
    for head in (5, 2 ** 31, 2 ** 40):
        assert R.chain_walk(nxt, np.array([head], np.int64))[0].size \
            == R.chain_lengths(nxt, np.array([head]))[0] == 0
        assert R.chain_order(nxt, head).size == 0
        assert chain_order.chain_order_device(
            nxt, head, interpret=True).size == 0


# ------------------------------------------------------- flash attention

@pytest.mark.parametrize("h,sq,skv,d,bq,bk,causal", [
    (2, 256, 256, 64, 128, 128, True),
    (3, 128, 128, 128, 64, 32, True),
    (1, 256, 512, 64, 128, 128, False),
    (4, 64, 64, 32, 64, 64, True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(h, sq, skv, d, bq, bk, causal, dtype):
    from repro.kernels.flash_attention import flash_attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (h, sq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (h, skv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (h, skv, d)).astype(dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_matches_model_blockwise():
    """The Pallas kernel and the model's XLA blockwise path agree."""
    from repro.kernels.flash_attention import flash_attention
    from repro.models import layers as L
    b, s, nk, g, dh = 1, 128, 2, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, nk, g, dh))
    k = jax.random.normal(ks[1], (b, s, nk, dh))
    v = jax.random.normal(ks[2], (b, s, nk, dh))
    want = L.blockwise_attention(q, k, v, causal=True, q_block=64,
                                 kv_block=64)
    # kernel layout: fold (B,K,G) into H; repeat K/V per query group
    qh = q.transpose(0, 2, 3, 1, 4).reshape(b * nk * g, s, dh)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1
                    ).reshape(b * nk * g, s, dh)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1
                    ).reshape(b * nk * g, s, dh)
    got = flash_attention(qh, kh, vh, causal=True, block_q=64, block_k=64,
                          interpret=True)
    got = got.reshape(b, nk, g, s, dh).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)
