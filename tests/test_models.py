"""Model-layer unit tests: attention variants, RoPE, ring cache, loss
chunking, MoE dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.models import layers as L
from repro.models import moe as M

KEY = jax.random.PRNGKey(0)


def naive_attention(q, k, v, causal=True, window=0, softcap=0.0, n_meta=0):
    """O(S^2) reference: q (B,S,K,G,D); k,v (B,S,K,D); the first n_meta
    keys are inside every window."""
    b, s, nk, g, d = q.shape
    qf = q.astype(jnp.float32) / jnp.sqrt(d)
    s_ = jnp.einsum("bqkgd,bjkd->bkgqj", qf, k.astype(jnp.float32))
    if softcap:
        s_ = softcap * jnp.tanh(s_ / softcap)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= (qpos - kpos < window) | (kpos < n_meta)
    s_ = jnp.where(mask[None, None, None], s_, -1e30)
    p = jax.nn.softmax(s_, -1)
    out = jnp.einsum("bkgqj,bjkd->bkgqd", p, v.astype(jnp.float32))
    return out.transpose(0, 3, 1, 2, 4)


def rand_qkv(b=2, s=64, nk=2, g=2, d=16):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, nk, g, d))
    k = jax.random.normal(ks[1], (b, s, nk, d))
    v = jax.random.normal(ks[2], (b, s, nk, d))
    return q, k, v


@pytest.mark.parametrize("qb,kb", [(64, 64), (16, 16), (32, 8), (16, 64)])
def test_blockwise_attention_matches_naive(qb, kb):
    q, k, v = rand_qkv()
    got = L.blockwise_attention(q, k, v, causal=True, q_block=qb,
                                kv_block=kb)
    want = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [8, 16, 40])
def test_sliding_window_matches_naive(window):
    q, k, v = rand_qkv()
    got = L.blockwise_attention(q, k, v, causal=True, window=window,
                                q_block=16, kv_block=16)
    want = naive_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("s,window,n_meta", [(64, 8, 4), (60, 8, 4),
                                             (60, 20, 24), (45, 0, 0)])
def test_meta_window_and_ragged_blocks_match_naive(s, window, n_meta):
    """Meta keys inside every window (a band past them reads them first),
    a ragged last query block and ragged KV spans, and value heads wider
    than q/k heads."""
    q, k, _ = rand_qkv(s=s)
    v = jax.random.normal(KEY, (2, s, 2, 32))
    got = L.blockwise_attention(q, k, v, causal=True, window=window,
                                q_block=16, kv_block=16, n_meta=n_meta)
    want = naive_attention(q, k, v, causal=True, window=window,
                           n_meta=n_meta)
    assert got.shape == (2, s, 2, 2, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_over_a_ring_with_meta_slots():
    """A ring of 8 behind 4 pinned meta slots, written position by
    position past its wrap, attends as the last row of windowed attention
    with the meta tokens always seen."""
    s, m, w = 30, 4, 8
    q, k, v = rand_qkv(s=s)
    want = naive_attention(q, k, v, causal=True, window=w, n_meta=m)
    kc = jnp.zeros((2, m + w, 2, 16))
    vc = jnp.zeros((2, m + w, 2, 16))
    for p in range(s):
        pos = jnp.asarray(p)
        if p < m:
            kc, vc = kc.at[:, p].set(k[:, p]), vc.at[:, p].set(v[:, p])
        else:
            kc = L.ring_write(kc, k[:, p:p + 1], pos, m + w, m)
            vc = L.ring_write(vc, v[:, p:p + 1], pos, m + w, m)
        if p < m + w:
            continue
        kv_pos = L.ring_slot_positions(pos, m + w, m)
        assert sorted(np.asarray(kv_pos).tolist()) == \
            list(range(m)) + list(range(p - w + 1, p + 1))
        got = L.decode_attention(q[:, p:p + 1], kc, vc, kv_pos, pos,
                                 window=w, n_meta=m)
        np.testing.assert_allclose(np.asarray(got[:, 0]),
                                   np.asarray(want[:, p]),
                                   atol=2e-5, rtol=2e-5)


def test_ssm_scan_pads_a_ragged_last_chunk():
    """A sequence the chunk does not divide gives the one-chunk scan's
    outputs and final state (the padded steps leave the state as it
    was)."""
    from repro.models import ssm as S

    b, s, c, n = 2, 50, 8, 4
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, s, c))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, c)))
    a_log = jax.random.normal(ks[2], (c, n))
    bm, cm = (jax.random.normal(k, (b, s, n)) for k in ks[3:5])
    h0 = jax.random.normal(ks[5], (b, c, n))
    d = jnp.ones((c,))
    y1, h1 = S.ssm_scan(x, dt, a_log, bm, cm, d, h0, chunk=16)
    y2, h2 = S.ssm_scan(x, dt, a_log, bm, cm, d, h0, chunk=s)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5,
                               atol=1e-5)


def test_softcap_matches_naive():
    q, k, v = rand_qkv()
    got = L.blockwise_attention(q, k, v, causal=True, softcap=30.0,
                                q_block=16, kv_block=16)
    want = naive_attention(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_bidirectional_attention():
    q, k, v = rand_qkv()
    got = L.blockwise_attention(q, k, v, causal=False, q_block=16,
                                kv_block=16)
    want = naive_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_matches_last_row():
    """decode at position s-1 == last row of full attention."""
    q, k, v = rand_qkv(s=32)
    full = naive_attention(q, k, v, causal=True)
    kv_pos = jnp.arange(32)
    got = L.decode_attention(q[:, -1:], k, v, kv_pos,
                             jnp.asarray(31))
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(full[:, -1]), atol=2e-5, rtol=2e-5)


def test_ring_buffer_positions():
    cap = 8
    for pos in [0, 3, 7, 8, 13, 100]:
        slots = np.asarray(L.ring_slot_positions(jnp.asarray(pos), cap))
        for w, p in enumerate(slots):
            if p >= 0:
                assert p % cap == w and p <= pos
                assert p + cap > pos  # the newest value for that slot


def test_rope_rotation_invariant():
    """RoPE preserves norms and relative-position inner products."""
    x = jax.random.normal(KEY, (1, 8, 2, 16))
    pos = jnp.arange(8)
    r = L.apply_rope(x, pos, 10000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(r), axis=-1),
                               rtol=1e-5)
    # q.k after rope depends only on relative distance
    q = jnp.ones((1, 8, 1, 16))
    k = jnp.ones((1, 8, 1, 16))
    qr = L.apply_rope(q, pos, 10000.0)
    kr = L.apply_rope(k, pos, 10000.0)
    dots = np.einsum("bqhd,bkhd->qk", np.asarray(qr), np.asarray(kr))
    d1 = np.diag(dots, k=1)
    np.testing.assert_allclose(d1, d1[0] * np.ones_like(d1), rtol=1e-5)


def test_moe_capacity_and_combine():
    cfg = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0,
                    router_group=16)
    d, g, b = 8, 16, 2
    ks = jax.random.split(KEY, 5)
    p = M.MoEParams(
        router=jax.random.normal(ks[0], (d, 4)),
        w_gate=0.1 * jax.random.normal(ks[1], (4, d, 16)),
        w_up=0.1 * jax.random.normal(ks[2], (4, d, 16)),
        w_down=0.1 * jax.random.normal(ks[3], (4, 16, d)),
    )
    x = jax.random.normal(ks[4], (b, g, d))
    y = M.moe_ffn(x, p, cfg, "silu")
    assert y.shape == x.shape
    # with huge capacity, no token dropped: output == dense mixture ref
    logits = jnp.einsum("bgd,de->bge", x, p.router)
    top_w, top_e = jax.lax.top_k(logits, 2)
    top_w = jax.nn.softmax(top_w, -1)
    def ffn_e(xv, e):
        h = jax.nn.silu(xv @ p.w_gate[e]) * (xv @ p.w_up[e])
        return h @ p.w_down[e]
    want = np.zeros((b, g, d), np.float32)
    for bi in range(b):
        for gi in range(g):
            for kk in range(2):
                e = int(top_e[bi, gi, kk])
                want[bi, gi] += float(top_w[bi, gi, kk]) * np.asarray(
                    ffn_e(x[bi, gi], e))
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4, rtol=1e-4)


def test_moe_capacity_drops_overflow():
    """top_k tokens beyond expert capacity are dropped (contribute 0)."""
    cfg = MoEConfig(n_experts=2, top_k=1, capacity_factor=0.5,
                    router_group=8)
    d = 4
    # router forces every token to expert 0; capacity = 8*1*0.5/2 = 2
    p = M.MoEParams(
        router=jnp.stack([jnp.ones(d), -jnp.ones(d)], 1),
        w_gate=jnp.ones((2, d, 8)), w_up=jnp.ones((2, d, 8)),
        w_down=jnp.ones((2, 8, d)),
    )
    x = jnp.abs(jax.random.normal(KEY, (1, 8, d))) + 0.1
    y = M.moe_ffn(x, p, cfg, "silu")
    contributed = (np.abs(np.asarray(y[0])) > 1e-9).any(1)
    assert contributed.sum() == 2  # exactly `capacity` tokens got output


def test_loss_chunking_equivalence():
    from repro.configs import base, registry
    from repro.models.model import build
    cfg = base.reduced(registry.get("llama3.2-3b"))
    m1 = build(cfg, compute_dtype=jnp.float32, loss_chunk=4)
    m2 = build(cfg, compute_dtype=jnp.float32, loss_chunk=1 << 20)
    params = m1.init_params(KEY)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    l1 = m1.loss(params, batch)
    l2 = m2.loss(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_slstm_custom_vjp_matches_autodiff():
    """The sLSTM custom VJP (one post-scan recurrent-weight contraction,
    §Perf) must match plain autodiff of the per-step cell."""
    from repro.models import xlstm as X
    B, S, H, Dh = 2, 10, 3, 8
    ks = jax.random.split(KEY, 2)
    pre = 0.5 * jax.random.normal(ks[0], (B, S, 4, H, Dh))
    r = 0.3 * jax.random.normal(ks[1], (4, H, Dh, Dh))
    st0 = X.slstm_init_state(B, H, Dh)

    def ref_scan(pre, r):
        def body(st, pre_t):
            st2 = X._slstm_cell(st, pre_t, r)
            return st2, st2.h
        sf, hs = jax.lax.scan(body, st0, pre.swapaxes(0, 1))
        return hs.swapaxes(0, 1), sf

    def loss_ref(pre, r):
        hs, sf = ref_scan(pre, r)
        return jnp.sum(jnp.sin(hs)) + jnp.sum(sf.c * 0.3)

    def loss_new(pre, r):
        hs, sf = X.slstm_scan(pre, r, st0)
        return jnp.sum(jnp.sin(hs)) + jnp.sum(sf.c * 0.3)

    l1 = loss_ref(pre, r)
    l2 = loss_new(pre, r)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    g1 = jax.grad(loss_ref, (0, 1))(pre, r)
    g2 = jax.grad(loss_new, (0, 1))(pre, r)
    for a, b in zip(g1, g2):
        rel = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9))
        assert rel < 5e-3, rel  # drec stacked bf16 => small quantization
