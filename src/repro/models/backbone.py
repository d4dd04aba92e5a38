"""Backbone assembly: layer-type dispatch, superblock scan, Model API.

The stack is organized as ``n_super`` repetitions of the config's
``layer_pattern`` ("superblock") plus an unrolled remainder.  Superblock
parameters are stacked on a leading axis and consumed by one ``lax.scan``,
so HLO size is O(|pattern|), not O(n_layers) — a 62-layer gemma3 compiles
the same superblock body as a 6-layer toy.  Per-position layer types inside
the pattern are *static* (no runtime branching ⇒ exact cost_analysis FLOPs).

Modes:
  train   — full-sequence forward, no caches, remat-wrapped superblocks
  prefill — full-sequence forward, emits decode caches
  decode  — single-token step consuming/updating caches (scan carries the
            token activation; caches stream through scan xs/ys)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X

Array = jax.Array
PyTree = Any

# Remat policy applied to the superblock body in train mode.  "none" saves
# everything (no recompute), "full" saves nothing (max recompute, min HBM),
# "dots" saves matmul outputs with no batch dims.
REMAT = {"policy": "full"}


def _remat_wrap(fn):
    pol = REMAT["policy"]
    if pol == "none":
        return fn
    if pol == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def parse_tag(tag: str) -> Tuple[str, str]:
    base, _, var = tag.partition(":")
    return base, (var or "full")


# ---------------------------------------------------------------------------
# Parameter shape construction
# ---------------------------------------------------------------------------


def _attn_shapes(cfg: ArchConfig, cross: bool = False) -> Dict[str, Tuple[int, ...]]:
    d, h, k, e = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    out = {"wq": (d, h, e), "wk": (d, k, e), "wv": (d, k, e), "wo": (h, e, d)}
    if cfg.qk_norm and not cross:
        out["q_norm"] = (e,)
        out["k_norm"] = (e,)
    return out


def _mlp_shapes(d: int, f: int) -> Dict[str, Tuple[int, ...]]:
    return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def _moe_shapes(cfg: ArchConfig) -> Dict[str, Tuple[int, ...]]:
    mc = cfg.moe
    d = cfg.d_model
    f = mc.expert_d_ff or cfg.d_ff
    out = {
        "router": (d, mc.n_experts),
        "w_gate": (mc.n_experts, d, f),
        "w_up": (mc.n_experts, d, f),
        "w_down": (mc.n_experts, f, d),
    }
    if mc.shared_expert:
        out.update({"s_gate": (d, f), "s_up": (d, f), "s_down": (f, d)})
    return out


def _mamba_shapes(cfg: ArchConfig) -> Dict[str, Tuple[int, ...]]:
    d = cfg.d_model
    di = cfg.ssm.expand * d
    n = cfg.ssm.state_dim
    dt_rank = max(1, d // 16)
    return {
        "in_proj": (d, 2 * di),
        "conv": (di, cfg.ssm.conv_width),
        "x_proj": (di, dt_rank + 2 * n),
        "dt_w": (dt_rank, di),
        "dt_bias": (di,),
        "a_log": (di, n),
        "d_skip": (di,),
    }


def layer_shapes(cfg: ArchConfig, tag: str) -> Dict[str, Any]:
    base, var = parse_tag(tag)
    d = cfg.d_model
    sh: Dict[str, Any] = {"ln1": (d,)}
    if base in ("dense", "attn", "moe"):
        if var == "cross" and cfg.family == "vlm":
            sh["xattn"] = _attn_shapes(cfg, cross=True)
            sh["xgate"] = ()
        else:
            sh["attn"] = _attn_shapes(cfg)
            if var == "cross":              # audio: self + cross
                sh["ln_x"] = (d,)
                sh["xattn"] = _attn_shapes(cfg, cross=True)
        sh["ln2"] = (d,)
        if base == "moe":
            sh["moe"] = _moe_shapes(cfg)
        else:
            sh["mlp"] = _mlp_shapes(d, cfg.d_ff)
    elif base == "hybrid":
        di = cfg.ssm.expand * d
        sh["attn"] = _attn_shapes(cfg)
        sh["mamba"] = _mamba_shapes(cfg)
        sh["norm_attn"] = (cfg.n_heads * cfg.resolved_head_dim,)
        sh["norm_mamba"] = (di,)
        sh["ln2"] = (d,)
        sh["mlp"] = _mlp_shapes(d, cfg.d_ff)
        # wo lives in sh["attn"]; hybrid projects the *combined* stream:
        sh["attn"] = {k: v for k, v in sh["attn"].items() if k != "wo"}
        sh["wo"] = (cfg.n_heads * cfg.resolved_head_dim, d)
        sh["w_mamba_out"] = (di, d)
    elif base == "mlstm":
        h = cfg.n_heads
        dv = cfg.resolved_head_dim
        dk = max(dv // 2, 8)
        sh.update({
            "wq": (d, h, dk), "wk": (d, h, dk), "wv": (d, h, dv),
            "w_if": (d, 2, h), "b_if": (2, h), "w_og": (d, h, dv),
            "out_norm": (h * dv,), "wo": (h, dv, d),
        })
    elif base == "slstm":
        h = cfg.n_heads
        dh = cfg.d_model // cfg.n_heads
        fx = int((cfg.xlstm.proj_factor if cfg.xlstm else 2.0) * d)
        sh.update({
            "w_in": (d, 4, h, dh), "b_in": (4, h, dh), "r": (4, h, dh, dh),
            "out_norm": (d,), "wo": (d, d), "ln2": (d,),
            "mlp": _mlp_shapes(d, fx),
        })
    else:
        raise ValueError(f"unknown layer tag {tag}")
    return sh


# Leaf names the forward reads only through ``.astype(compute_dtype)``, as an
# operand of a dot or a gather, wherever they appear in the tree: a serving
# copy may hold them in the compute dtype (``Model.compute_params``).  Every
# other leaf is read in f32 and keeps its own dtype: the norm gains, the MoE
# router, the SSM's conv/dt_w/dt_bias/a_log/d_skip, the biases b_if and b_in,
# xgate and the sLSTM recurrence r.  tests/test_compute_params.py holds every
# registry architecture to this table.
MATMUL_WEIGHTS = frozenset({
    "embed", "lm_head",
    "wq", "wk", "wv", "wo",                       # attention, xattn, xLSTM
    "w_gate", "w_up", "w_down",                   # dense MLP and MoE experts
    "s_gate", "s_up", "s_down",                   # MoE shared expert
    "in_proj", "x_proj", "w_mamba_out",           # hybrid's SSM branch
    "w_if", "w_og", "w_in",                       # mLSTM gates, sLSTM input
})


def _leaf_specs(tree, prefix_dims=()):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(tuple(prefix_dims) + tuple(s), jnp.float32),
        tree, is_leaf=lambda x: isinstance(x, tuple))


def param_specs(cfg: ArchConfig) -> PyTree:
    pattern, n_super, rem = cfg.pattern_plan()
    p: Dict[str, Any] = {
        "embed": jax.ShapeDtypeStruct((cfg.vocab_padded, cfg.d_model), jnp.float32),
        "final_norm": jax.ShapeDtypeStruct((cfg.d_model,), jnp.float32),
    }
    if n_super:
        p["blocks"] = {
            f"pos{i}": _leaf_specs(layer_shapes(cfg, t), (n_super,))
            for i, t in enumerate(pattern)
        }
    if rem:
        p["rem"] = {
            f"rem{i}": _leaf_specs(layer_shapes(cfg, t))
            for i, t in enumerate(rem)
        }
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab_padded), jnp.float32)
    if cfg.encoder_layers:
        p["enc_blocks"] = {
            "pos0": _leaf_specs(layer_shapes(cfg, "dense:bidir"),
                                (cfg.encoder_layers,))
        }
        p["enc_final_norm"] = jax.ShapeDtypeStruct((cfg.d_model,), jnp.float32)
    return p


def init_params(cfg: ArchConfig, rng: jax.Array) -> PyTree:
    """Materialize real parameters (smoke tests / examples only)."""
    specs = param_specs(cfg)
    leaves, treedef = jax.tree.flatten(specs)
    rngs = jax.random.split(rng, len(leaves))
    out = []
    for r, s in zip(rngs, leaves):
        fan_in = s.shape[0] if len(s.shape) > 1 else max(s.shape[-1], 1)
        scale = 0.02 if len(s.shape) <= 1 else min(0.02, (1.0 / fan_in) ** 0.5)
        if len(s.shape) == 0 or (len(s.shape) >= 1 and s.shape == ()):
            out.append(jnp.zeros(s.shape, s.dtype))
        elif len(s.shape) == 1:
            out.append(jnp.zeros(s.shape, s.dtype))  # norms/bias start at 0
        else:
            out.append(scale * jax.random.normal(r, s.shape, s.dtype))
    params = jax.tree.unflatten(treedef, out)
    params = _fix_special_inits(cfg, params)
    return params


def _fix_special_inits(cfg: ArchConfig, params: PyTree) -> PyTree:
    """SSM a_log / dt_bias need structured init for stability."""
    def fix(path, x):
        keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        if "a_log" in keys:
            n = x.shape[-1]
            base = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
            return jnp.broadcast_to(base, x.shape)
        if "dt_bias" in keys:
            return jnp.full(x.shape, -2.0, x.dtype)  # softplus -> small dt
        if "d_skip" in keys:
            return jnp.ones(x.shape, x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(fix, params)


# ---------------------------------------------------------------------------
# Cache shape construction (decode)
# ---------------------------------------------------------------------------


def _cache_shapes(cfg: ArchConfig, tag: str, batch: int, s_max: int,
                  dtype) -> Dict[str, jax.ShapeDtypeStruct]:
    base, var = parse_tag(tag)
    k, e = cfg.n_kv_heads, cfg.resolved_head_dim
    sh: Dict[str, Any] = {}

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt)

    if base in ("dense", "attn", "moe", "hybrid"):
        if var == "cross" and cfg.family == "vlm":
            ctx = cfg.context_seq
            sh["xk"] = sds((batch, ctx, k, e))
            sh["xv"] = sds((batch, ctx, k, e))
        else:
            cap = min(cfg.window, s_max) if var == "local" else s_max
            sh["k"] = sds((batch, cap, k, e))
            sh["v"] = sds((batch, cap, k, e))
            if var == "cross":   # audio self+cross
                sh["xk"] = sds((batch, cfg.encoder_seq, k, e))
                sh["xv"] = sds((batch, cfg.encoder_seq, k, e))
    if base == "hybrid":
        di = cfg.ssm.expand * cfg.d_model
        sh["ssm"] = sds((batch, di, cfg.ssm.state_dim), jnp.float32)
        sh["conv"] = sds((batch, cfg.ssm.conv_width - 1, di))
    if base == "mlstm":
        h, dv = cfg.n_heads, cfg.resolved_head_dim
        dk = max(dv // 2, 8)
        sh["c"] = sds((batch, h, dk, dv), jnp.float32)
        sh["n"] = sds((batch, h, dk), jnp.float32)
        sh["m"] = sds((batch, h), jnp.float32)
    if base == "slstm":
        h = cfg.n_heads
        dh = cfg.d_model // cfg.n_heads
        for name in ("c", "n", "h", "m"):
            sh[name] = sds((batch, h, dh), jnp.float32)
    return sh


def cache_specs(cfg: ArchConfig, batch: int, s_max: int,
                dtype=jnp.bfloat16) -> PyTree:
    pattern, n_super, rem = cfg.pattern_plan()
    out: Dict[str, Any] = {}
    if n_super:
        out["blocks"] = {
            f"pos{i}": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n_super,) + s.shape, s.dtype),
                _cache_shapes(cfg, t, batch, s_max, dtype))
            for i, t in enumerate(pattern)
        }
    if rem:
        out["rem"] = {
            f"rem{i}": _cache_shapes(cfg, t, batch, s_max, dtype)
            for i, t in enumerate(rem)
        }
    return out


def init_cache(cfg: ArchConfig, batch: int, s_max: int,
               dtype=jnp.bfloat16) -> PyTree:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_specs(cfg, batch, s_max, dtype))


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------


def _attn_params(p: Dict[str, Array]) -> L.AttnParams:
    return L.AttnParams(wq=p["wq"], wk=p["wk"], wv=p["wv"],
                        wo=p.get("wo"), q_norm=p.get("q_norm"),
                        k_norm=p.get("k_norm"))


def _self_attention_seq(cfg: ArchConfig, p, x, positions, *, causal, window):
    from repro.dist import mesh as dmesh

    sp = dmesh.seq_parallel_on()
    if sp:
        x = dmesh.seq_parallel(x, "q")          # (B, S/16, d) per device
    q, k, v = L.project_qkv(x, _attn_params(p), cfg.n_kv_heads,
                            positions=positions, theta=cfg.rope_theta)
    if sp:
        # causal attention needs the full KV prefix: gather K/V over the
        # model axis, keep Q sequence-sharded (one q-block => the score
        # tensor stays (B, K, G, S/16, S) per device).
        k = dmesh.seq_parallel(k, "kv")
        v = dmesh.seq_parallel(v, "kv")
    att = L.blockwise_attention(q, k, v, causal=causal, window=window,
                                softcap=cfg.attn_softcap,
                                q_block=(x.shape[1] if sp else 1024))
    return att, k, v


def _cross_attention_seq(cfg: ArchConfig, p, x, ctx):
    dt = x.dtype
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    b, s, h, e = q.shape
    q = q.reshape(b, s, cfg.n_kv_heads, h // cfg.n_kv_heads, e)
    xk = jnp.einsum("bsd,dke->bske", ctx.astype(dt), p["wk"].astype(dt),
                    preferred_element_type=jnp.float32).astype(dt)
    xv = jnp.einsum("bsd,dke->bske", ctx.astype(dt), p["wv"].astype(dt),
                    preferred_element_type=jnp.float32).astype(dt)
    att = L.blockwise_attention(q, xk, xv, causal=False)
    return att, xk, xv


def _mamba_seq(cfg: ArchConfig, p, x, conv_tail, state0):
    """x: (B, S, d) -> (y (B,S,di->d is caller's job: returns (B,S,di)),
    new_conv_tail, new_state)."""
    di = cfg.ssm.expand * cfg.d_model
    n = cfg.ssm.state_dim
    dt_rank = max(1, cfg.d_model // 16)
    dt_ = x.dtype
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(dt_),
                    preferred_element_type=jnp.float32).astype(dt_)
    xs, z = jnp.split(xz, 2, axis=-1)
    xc, new_tail = S.depthwise_conv(xs, p["conv"], conv_tail)
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(dt_)
    proj = jnp.einsum("bsc,ce->bse", xc, p["x_proj"].astype(dt_),
                      preferred_element_type=jnp.float32)
    dt_low, bmat, cmat = jnp.split(proj, [dt_rank, dt_rank + n], axis=-1)
    dt_full = jax.nn.softplus(
        jnp.einsum("bsr,rc->bsc", dt_low, p["dt_w"].astype(jnp.float32))
        + p["dt_bias"].astype(jnp.float32))
    y, state = S.ssm_scan(xc, dt_full.astype(dt_), p["a_log"], bmat, cmat,
                          p["d_skip"], state0)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(dt_)
    return y, new_tail, state


def _mamba_step(cfg: ArchConfig, p, x_t, conv_tail, state):
    """x_t: (B, 1, d).  Single decode step."""
    n = cfg.ssm.state_dim
    dt_rank = max(1, cfg.d_model // 16)
    dt_ = x_t.dtype
    xz = jnp.einsum("bsd,de->bse", x_t, p["in_proj"].astype(dt_),
                    preferred_element_type=jnp.float32).astype(dt_)
    xs, z = jnp.split(xz, 2, axis=-1)
    # conv over (tail ++ x)
    full = jnp.concatenate([conv_tail, xs], axis=1)       # (B, cw, di)
    w = p["conv"].astype(jnp.float32)
    xc = jnp.sum(full.astype(jnp.float32) * w.T[None], axis=1, keepdims=True)
    xc = jax.nn.silu(xc).astype(dt_)
    new_tail = full[:, 1:]
    proj = jnp.einsum("bsc,ce->bse", xc, p["x_proj"].astype(dt_),
                      preferred_element_type=jnp.float32)
    dt_low, bmat, cmat = jnp.split(proj, [dt_rank, dt_rank + n], axis=-1)
    dt_full = jax.nn.softplus(
        jnp.einsum("bsr,rc->bsc", dt_low, p["dt_w"].astype(jnp.float32))
        + p["dt_bias"].astype(jnp.float32))
    y, state = S.ssm_step(xc[:, 0], dt_full[:, 0].astype(dt_), p["a_log"],
                          bmat[:, 0], cmat[:, 0], p["d_skip"], state)
    y = y[:, None] * jax.nn.silu(z.astype(jnp.float32)).astype(dt_)
    return y, new_tail, state


def _mlstm_proj(cfg, p, x):
    dt = x.dtype
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"].astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    gates = jnp.einsum("bsd,dgh->bsgh", x, p["w_if"].astype(dt),
                       preferred_element_type=jnp.float32) + p["b_if"].astype(jnp.float32)
    og = jnp.einsum("bsd,dhe->bshe", x, p["w_og"].astype(dt),
                    preferred_element_type=jnp.float32)
    return q, k, v, gates[:, :, 0], gates[:, :, 1], og


def _seat_cache(k_all: Array, cap_total: int) -> Array:
    """Place the tail of prefill K/V (B, S, ...) into a fresh ring/linear
    cache of capacity cap_total, at the slots decode will expect
    (slot = abs_pos % cap_total)."""
    b, s = k_all.shape[:2]
    t = min(cap_total, s)
    tail = k_all[:, s - t:]
    slots = np.arange(s - t, s) % cap_total
    out = jnp.zeros((b, cap_total) + k_all.shape[2:], k_all.dtype)
    return out.at[:, slots].set(tail)


def apply_layer(cfg: ArchConfig, tag: str, p: Dict[str, Any], x: Array, *,
                mode: str, ctx: Optional[Array] = None,
                cache: Optional[Dict[str, Array]] = None,
                pos: Optional[Array] = None,
                s_max: Optional[int] = None) -> Tuple[Array, Optional[Dict]]:
    """Apply one layer.  Returns (x, new_cache)."""
    base, var = parse_tag(tag)
    b, s, d = x.shape
    s_max = s_max or s
    new_cache: Dict[str, Array] = {}
    rms = functools.partial(L.rms_norm, eps=cfg.norm_eps)

    if base in ("dense", "attn", "moe"):
        # ---- mixer ----
        if var == "cross" and cfg.family == "vlm":
            y = rms(x, p["ln1"])
            if mode == "decode":
                q = jnp.einsum("bsd,dhe->bshe", y, p["xattn"]["wq"].astype(y.dtype),
                               preferred_element_type=jnp.float32).astype(y.dtype)
                q = q.reshape(b, s, cfg.n_kv_heads, cfg.q_group, -1)
                ctx_pos = jnp.arange(cache["xk"].shape[1])
                att = L.decode_attention(q, cache["xk"], cache["xv"], ctx_pos,
                                         jnp.array(1 << 30))
                new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
            else:
                att, xk, xv = _cross_attention_seq(cfg, p["xattn"], y, ctx)
                if mode == "prefill":
                    new_cache["xk"], new_cache["xv"] = xk, xv
            att = L.attn_out(att, p["xattn"]["wo"])
            x = x + jnp.tanh(p["xgate"].astype(jnp.float32)).astype(x.dtype) * att
        else:
            y = rms(x, p["ln1"])
            window = cfg.window if var == "local" else 0
            causal = var != "bidir"
            if mode == "decode":
                cap = cache["k"].shape[1]
                positions = pos[None] if pos.ndim == 0 else pos
                q, k_new, v_new = L.project_qkv(
                    y, _attn_params(p["attn"]), cfg.n_kv_heads,
                    positions=positions, theta=cfg.rope_theta)
                k_c = L.ring_write(cache["k"], k_new, pos, cap)
                v_c = L.ring_write(cache["v"], v_new, pos, cap)
                kv_pos = L.ring_slot_positions(pos, cap)
                att = L.decode_attention(q, k_c, v_c, kv_pos, pos,
                                         window=window,
                                         softcap=cfg.attn_softcap)
                new_cache["k"], new_cache["v"] = k_c, v_c
            else:
                positions = jnp.arange(s)
                att, k_all, v_all = _self_attention_seq(
                    cfg, p["attn"], y, positions, causal=causal, window=window)
                if mode == "prefill":
                    cap = min(cfg.window, s_max) if var == "local" else s_max
                    new_cache["k"] = _seat_cache(k_all, cap)
                    new_cache["v"] = _seat_cache(v_all, cap)
            att = L.attn_out(att, p["attn"]["wo"])
            if mode != "decode":
                from repro.dist import mesh as dmesh
                att = dmesh.seq_parallel(att, "res")
            x = x + att
            if var == "cross":           # audio decoder: self + cross
                y2 = rms(x, p["ln_x"])
                if mode == "decode":
                    q = jnp.einsum("bsd,dhe->bshe", y2,
                                   p["xattn"]["wq"].astype(y2.dtype),
                                   preferred_element_type=jnp.float32).astype(y2.dtype)
                    q = q.reshape(b, s, cfg.n_kv_heads, cfg.q_group, -1)
                    ctx_pos = jnp.arange(cache["xk"].shape[1])
                    att2 = L.decode_attention(q, cache["xk"], cache["xv"],
                                              ctx_pos, jnp.array(1 << 30))
                    new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
                else:
                    att2, xk, xv = _cross_attention_seq(cfg, p["xattn"], y2, ctx)
                    if mode == "prefill":
                        new_cache["xk"], new_cache["xv"] = xk, xv
                x = x + L.attn_out(att2, p["xattn"]["wo"])
        # ---- ffn ----
        y = rms(x, p["ln2"])
        if base == "moe":
            mc = cfg.moe
            mp = M.MoEParams(router=p["moe"]["router"], w_gate=p["moe"]["w_gate"],
                             w_up=p["moe"]["w_up"], w_down=p["moe"]["w_down"],
                             s_gate=p["moe"].get("s_gate"),
                             s_up=p["moe"].get("s_up"),
                             s_down=p["moe"].get("s_down"))
            x = x + M.moe_ffn(y, mp, mc, cfg.act)
        else:
            x = x + L.gated_mlp(y, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                                p["mlp"]["w_down"], cfg.act)
        return x, (new_cache or None)

    if base == "hybrid":
        di = cfg.ssm.expand * d
        y = rms(x, p["ln1"])
        window = cfg.window if var == "local" else 0
        ap = _attn_params(p["attn"])
        if mode == "decode":
            cap = cache["k"].shape[1]
            positions = pos[None] if pos.ndim == 0 else pos
            q, k_new, v_new = L.project_qkv(y, ap, cfg.n_kv_heads,
                                            positions=positions,
                                            theta=cfg.rope_theta)
            k_c = L.ring_write(cache["k"], k_new, pos, cap)
            v_c = L.ring_write(cache["v"], v_new, pos, cap)
            kv_pos = L.ring_slot_positions(pos, cap)
            att = L.decode_attention(q, k_c, v_c, kv_pos, pos, window=window)
            new_cache["k"], new_cache["v"] = k_c, v_c
            m_out, new_tail, new_state = _mamba_step(cfg, p["mamba"], y,
                                                     cache["conv"],
                                                     cache["ssm"])
            new_cache["conv"], new_cache["ssm"] = new_tail, new_state
        else:
            from repro.dist import mesh as dmesh
            positions = jnp.arange(s)
            # Sequence-parallel attention branch (25H/5kv can't shard the
            # 16-way model axis); the mamba branch keeps batch-sharded y —
            # its d_inner is already model-parallel.
            y_att = dmesh.seq_parallel(y, "q")
            q, k_all, v_all = L.project_qkv(y_att, ap, cfg.n_kv_heads,
                                            positions=positions,
                                            theta=cfg.rope_theta)
            k_all = dmesh.seq_parallel(k_all, "kv")
            v_all = dmesh.seq_parallel(v_all, "kv")
            att = L.blockwise_attention(
                q, k_all, v_all, causal=True, window=window,
                q_block=(s if dmesh.seq_parallel_on() else 1024))
            state0 = jnp.zeros((b, di, cfg.ssm.state_dim), jnp.float32)
            m_out, new_tail, new_state = _mamba_seq(cfg, p["mamba"], y, None,
                                                    state0)
            if mode == "prefill":
                cap = min(cfg.window, s_max) if var == "local" else s_max
                new_cache["k"] = _seat_cache(k_all, cap)
                new_cache["v"] = _seat_cache(v_all, cap)
                new_cache["conv"], new_cache["ssm"] = new_tail, new_state
        a_flat = att.reshape(b, s, -1)
        a_mix = rms(a_flat, p["norm_attn"]) @ p["wo"].astype(x.dtype)
        if mode != "decode":
            from repro.dist import mesh as dmesh
            a_mix = dmesh.seq_parallel(a_mix, "res")
        mix = (a_mix
               + rms(m_out, p["norm_mamba"]) @ p["w_mamba_out"].astype(x.dtype))
        x = x + 0.5 * mix
        y = rms(x, p["ln2"])
        x = x + L.gated_mlp(y, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                            p["mlp"]["w_down"], cfg.act)
        return x, (new_cache or None)

    if base == "mlstm":
        y = rms(x, p["ln1"])
        q, k, v, i_pre, f_pre, og = _mlstm_proj(cfg, p, y)
        if mode == "decode":
            st = X.MLSTMState(cache["c"], cache["n"], cache["m"])
            yc, st2 = X.mlstm_step(q[:, 0], k[:, 0], v[:, 0],
                                   i_pre[:, 0], f_pre[:, 0], st)
            yc = yc[:, None]
            new_cache = {"c": st2.c, "n": st2.n, "m": st2.m}
        else:
            hh, dv = cfg.n_heads, cfg.resolved_head_dim
            dk = max(dv // 2, 8)
            st = X.mlstm_init_state(b, hh, dk, dv)
            chunk = cfg.xlstm.chunk if cfg.xlstm else 256
            yc, st2 = X.mlstm_chunkwise(q, k, v, i_pre, f_pre, st, chunk=chunk)
            if mode == "prefill":
                new_cache = {"c": st2.c, "n": st2.n, "m": st2.m}
        yc = yc * jax.nn.sigmoid(og).astype(yc.dtype)
        flat = yc.reshape(b, s, -1)
        flat = rms(flat, p["out_norm"])
        out = jnp.einsum("bshe,hed->bsd",
                         flat.reshape(b, s, cfg.n_heads, -1),
                         p["wo"].astype(x.dtype),
                         preferred_element_type=jnp.float32).astype(x.dtype)
        return x + out, (new_cache or None)

    if base == "slstm":
        y = rms(x, p["ln1"])
        pre = (jnp.einsum("bsd,dghe->bsghe", y, p["w_in"].astype(y.dtype),
                          preferred_element_type=jnp.float32)
               + p["b_in"].astype(jnp.float32)).astype(y.dtype)
        if mode == "decode":
            st = X.SLSTMState(cache["c"], cache["n"], cache["h"], cache["m"])
            h_out, st2 = X.slstm_step(pre[:, 0], p["r"], st)
            h_out = h_out[:, None]
            new_cache = {"c": st2.c, "n": st2.n, "h": st2.h, "m": st2.m}
        else:
            hh = cfg.n_heads
            dh = cfg.d_model // hh
            st = X.slstm_init_state(b, hh, dh)
            h_out, st2 = X.slstm_scan(pre, p["r"], st)
            if mode == "prefill":
                new_cache = {"c": st2.c, "n": st2.n, "h": st2.h, "m": st2.m}
        flat = h_out.reshape(b, s, d).astype(x.dtype)
        flat = rms(flat, p["out_norm"])
        x = x + (flat @ p["wo"].astype(x.dtype)).astype(x.dtype)
        y = rms(x, p["ln2"])
        x = x + L.gated_mlp(y, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                            p["mlp"]["w_down"], cfg.act)
        return x, (new_cache or None)

    raise ValueError(f"unknown layer base {base}")
