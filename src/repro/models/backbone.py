"""Backbone assembly: layer-type dispatch, superblock scan, Model API.

The stack is organized as ``n_super`` repetitions of the config's
``layer_pattern`` ("superblock") plus an unrolled remainder.  Superblock
parameters are stacked on a leading axis and consumed by one ``lax.scan``,
so HLO size is O(|pattern|), not O(n_layers) — a 62-layer gemma3 compiles
the same superblock body as a 6-layer toy.  Per-position layer types inside
the pattern are *static* (no runtime branching ⇒ exact cost_analysis FLOPs).
A stack with KV-sharing groups (hymba) is instead a sequence of runs, each
one group repeated and scanned (``ArchConfig.stack_runs``, ``segments``).

Modes:
  train   — full-sequence forward, no caches, remat-wrapped superblocks
  prefill — full-sequence forward, emits decode caches
  decode  — single-token step consuming/updating caches (scan carries the
            token activation; caches stream through scan xs/ys)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import SHARED_KV, ArchConfig, shares_kv
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
    """(layer base, attention variant); a ``SHARED_KV`` suffix is not part
    of the variant (``shares_kv`` reads it)."""
    base, _, var = tag.removesuffix(SHARED_KV).partition(":")
    return base, (var or "full")


def segments(cfg: ArchConfig):
    """Where each part of the stack sits in the parameter and cache trees,
    in layer order: ``(keys, period, repeats)``.  A part with repeats > 0
    holds ``{"pos<i>": leaves with a leading repeats axis}`` and is scanned;
    one with repeats 0 is a single layer's leaves.  A KV-sharing stack is
    its ``stack_runs`` at ``blocks/run<r>``; any other is ``layer_pattern``
    tiled at ``blocks``, then the remainder at ``rem/rem<j>``."""
    runs = cfg.stack_runs()
    if runs:
        return [(("blocks", f"run{r}"), period, n)
                for r, (period, n) in enumerate(runs)]
    pattern, n_super, rem = cfg.pattern_plan()
    out = [(("blocks",), pattern, n_super)] if n_super else []
    return out + [(("rem", f"rem{j}"), (t,), 0) for j, t in enumerate(rem)]


def layer_params(cfg: ArchConfig, params) -> Iterator[Dict[str, Any]]:
    """Each layer's weights in layer order, from either layout of
    ``segments``."""
    for keys, period, n in segments(cfg):
        part = subtree(params, keys)
        if not n:
            yield part
            continue
        for k in range(n):
            for i in range(len(period)):
                yield jax.tree.map(lambda a, k=k: a[k], part[f"pos{i}"])


def subtree(tree, keys):
    for k in keys:
        tree = tree[k]
    return tree


def put(tree: Dict[str, Any], keys, value) -> None:
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


def _build(cfg: ArchConfig, layer_tree) -> Dict[str, Any]:
    """The stack's tree of ``layer_tree(tag, repeats)`` per layer."""
    out: Dict[str, Any] = {}
    for keys, period, n in segments(cfg):
        if n:
            put(out, keys, {f"pos{i}": layer_tree(t, n)
                            for i, t in enumerate(period)})
        else:
            put(out, keys, layer_tree(period[0], 0))
    return out


# ---------------------------------------------------------------------------
# Parameter shape construction
# ---------------------------------------------------------------------------


def _attn_shapes(cfg: ArchConfig, cross: bool = False) -> Dict[str, Tuple[int, ...]]:
    d, h, k, e = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ev = cfg.v_head_dim
    out = {"wq": (d, h, e), "wk": (d, k, e), "wv": (d, k, ev), "wo": (h, ev, d)}
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
    d, di, n, r = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    return {
        "in_proj": (d, 2 * di),           # the SSM input x and its gate z
        "conv": (di, cfg.conv_width),
        "conv_bias": (di,),
        "x_proj": (di, r + 2 * n),        # dt's low-rank input, B, C
        "dt_norm": (r,),
        "b_norm": (n,),
        "c_norm": (n,),
        "dt_w": (r, di),
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
        di = cfg.d_inner
        att = cfg.n_heads * cfg.v_head_dim
        if att != di:
            raise ValueError(f"{cfg.name}: the attention heads' output "
                             f"({att}) must match d_inner ({di})")
        # a SHARED_KV layer projects only Q; each stream gets its own norm
        # and one out_proj takes their mean
        keep = ("wq",) if shares_kv(tag) else ("wq", "wk", "wv")
        sh["attn"] = {k: v for k, v in _attn_shapes(cfg).items() if k in keep}
        sh["mamba"] = _mamba_shapes(cfg)
        sh["norm_attn"] = (att,)
        sh["norm_mamba"] = (di,)
        sh["out_proj"] = (di, d)
        sh["ln2"] = (d,)
        sh["mlp"] = _mlp_shapes(d, cfg.d_ff)
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
# router, the SSM's conv/conv_bias/dt_w/dt_bias/a_log/d_skip and inner norms,
# the biases b_if and b_in, xgate and the sLSTM recurrence r.  tests/test_compute_params.py holds every
# registry architecture to this table.
MATMUL_WEIGHTS = frozenset({
    "embed", "lm_head",
    "wq", "wk", "wv", "wo",                       # attention, xattn, xLSTM
    "w_gate", "w_up", "w_down",                   # dense MLP and MoE experts
    "s_gate", "s_up", "s_down",                   # MoE shared expert
    "in_proj", "x_proj", "out_proj",              # hybrid's SSM branch
    "meta",                                       # hymba's meta tokens
    "w_if", "w_og", "w_in",                       # mLSTM gates, sLSTM input
})


def _leaf_specs(tree, prefix_dims=()):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(tuple(prefix_dims) + tuple(s), jnp.float32),
        tree, is_leaf=lambda x: isinstance(x, tuple))


def param_specs(cfg: ArchConfig) -> PyTree:
    p: Dict[str, Any] = {
        "embed": jax.ShapeDtypeStruct((cfg.vocab_padded, cfg.d_model), jnp.float32),
        "final_norm": jax.ShapeDtypeStruct((cfg.d_model,), jnp.float32),
    }
    p.update(_build(cfg, lambda t, n: _leaf_specs(layer_shapes(cfg, t),
                                                  (n,) if n else ())))
    if cfg.meta_tokens:
        p["meta"] = jax.ShapeDtypeStruct((cfg.meta_tokens, cfg.d_model),
                                         jnp.float32)
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
    """The SSM's ``a_log``, ``dt_bias`` and ``d_skip`` are offsets from
    Mamba's initialisation (``ssm_coefficients``): they start at 0."""
    def fix(path, x):
        if getattr(path[-1], "key", None) in SSM_OFFSETS:
            return jnp.zeros_like(x)
        return x
    return jax.tree_util.tree_map_with_path(fix, params)


# ---------------------------------------------------------------------------
# Cache shape construction (decode)
# ---------------------------------------------------------------------------


def kv_capacity(cfg: ArchConfig, var: str, s_max: int) -> int:
    """Cache positions of a layer serving up to ``s_max`` tokens: a ring of
    the window for ``local`` layers, every position otherwise, and in both
    the meta tokens' pinned slots first."""
    return cfg.meta_tokens + (min(cfg.window, s_max) if var == "local"
                              else s_max)


def _cache_shapes(cfg: ArchConfig, tag: str, batch: int, s_max: int,
                  dtype) -> Dict[str, jax.ShapeDtypeStruct]:
    base, var = parse_tag(tag)
    k, e = cfg.n_kv_heads, cfg.resolved_head_dim
    ev = cfg.v_head_dim
    sh: Dict[str, Any] = {}

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt)

    if base in ("dense", "attn", "moe"):
        if var == "cross" and cfg.family == "vlm":
            ctx = cfg.context_seq
            sh["xk"] = sds((batch, ctx, k, e))
            sh["xv"] = sds((batch, ctx, k, e))
        else:
            cap = kv_capacity(cfg, var, s_max)
            sh["k"] = sds((batch, cap, k, e))
            sh["v"] = sds((batch, cap, k, e))
            if var == "cross":   # audio self+cross
                sh["xk"] = sds((batch, cfg.encoder_seq, k, e))
                sh["xv"] = sds((batch, cfg.encoder_seq, k, e))
    if base == "hybrid":
        # one KV cache per sharing group, held by its first layer; SSM and
        # conv state in every layer
        if not shares_kv(tag):
            cap = kv_capacity(cfg, var, s_max)
            sh["k"] = sds((batch, cap, k, e))
            sh["v"] = sds((batch, cap, k, ev))
        di = cfg.d_inner
        sh["ssm"] = sds((batch, di, cfg.ssm_state), jnp.float32)
        sh["conv"] = sds((batch, cfg.conv_width - 1, di))
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
    """``s_max`` counts tokens; a layer's capacity adds the meta slots."""
    def layer_tree(tag, n):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(((n,) if n else ()) + s.shape,
                                           s.dtype),
            _cache_shapes(cfg, tag, batch, s_max, dtype))

    return _build(cfg, layer_tree)


def init_cache(cfg: ArchConfig, batch: int, s_max: int,
               dtype=jnp.bfloat16) -> PyTree:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_specs(cfg, batch, s_max, dtype))


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------


def _attn_params(p: Dict[str, Array]) -> L.AttnParams:
    return L.AttnParams(wq=p["wq"], wk=p.get("wk"), wv=p.get("wv"),
                        wo=p.get("wo"), q_norm=p.get("q_norm"),
                        k_norm=p.get("k_norm"))


def _self_attention_seq(cfg: ArchConfig, p, x, positions, *, causal, window,
                        kv=None, block: int = 1024):
    """Prefill/train self attention.  ``kv``: another layer's (k, v) to
    attend over (a ``SHARED_KV`` layer projects only Q).  Returns the
    attended values and the (k, v) attended over."""
    from repro.dist import mesh as dmesh

    sp = dmesh.seq_parallel_on()
    if sp:
        x = dmesh.seq_parallel(x, "q")          # (B, S/16, d) per device
    q, k, v = L.project_qkv(x, _attn_params(p), cfg.n_kv_heads,
                            positions=positions, theta=cfg.rope_theta)
    if kv is not None:
        k, v = kv
    elif sp:
        # causal attention needs the full KV prefix: gather K/V over the
        # model axis, keep Q sequence-sharded (one q-block => the score
        # tensor stays (B, K, G, S/16, S) per device).
        k = dmesh.seq_parallel(k, "kv")
        v = dmesh.seq_parallel(v, "kv")
    att = L.blockwise_attention(q, k, v, causal=causal, window=window,
                                softcap=cfg.attn_softcap,
                                q_block=(x.shape[1] if sp else block),
                                kv_block=block, n_meta=cfg.meta_tokens)
    return att, k, v


def _self_attention_step(cfg: ArchConfig, p, y, k_cache, v_cache, pos, *,
                         window, write: bool = True):
    """Decode self attention of one token at ``pos`` against a ring cache
    (meta slots pinned first).  ``write=False`` reads another layer's
    cache, already holding this position, and projects only Q.  Returns
    the attended values and the caches."""
    cap = k_cache.shape[1]
    positions = pos[None] if pos.ndim == 0 else pos
    q, k_new, v_new = L.project_qkv(y, _attn_params(p), cfg.n_kv_heads,
                                    positions=positions,
                                    theta=cfg.rope_theta)
    m = cfg.meta_tokens
    if write:
        k_cache = L.ring_write(k_cache, k_new, pos, cap, m)
        v_cache = L.ring_write(v_cache, v_new, pos, cap, m)
    kv_pos = L.ring_slot_positions(pos, cap, m)
    att = L.decode_attention(q, k_cache, v_cache, kv_pos, pos, window=window,
                             softcap=cfg.attn_softcap, n_meta=m)
    return att, k_cache, v_cache


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


# The SSM's A, dt bias and D are stored as offsets from Mamba's
# initialisation (arXiv:2312.00752 §3.6), as norm gains are stored as gamma
# for the gain 1 + gamma: zero offsets are a model at that initialisation.
SSM_OFFSETS = frozenset({"a_log", "dt_bias", "d_skip"})
DT_INIT = (1e-3, 0.1)     # dt at zero offset: log-spaced over the channels


def ssm_coefficients(cfg: ArchConfig, p):
    """(log of -A (d_inner, N), dt's bias (d_inner,), D (d_inner,)) in f32
    from the stored offsets: S4D-real A = -(1..N), dt's bias the inverse
    softplus of ``DT_INIT`` log-spaced over the channels, D = 1."""
    f32 = jnp.float32
    a0 = jnp.log(jnp.arange(1, cfg.ssm_state + 1, dtype=f32))
    dt0 = jnp.exp(jnp.linspace(np.log(DT_INIT[0]), np.log(DT_INIT[1]),
                               cfg.d_inner, dtype=f32))
    b0 = dt0 + jnp.log(-jnp.expm1(-dt0))
    return (p["a_log"].astype(f32) + a0, p["dt_bias"].astype(f32) + b0,
            1.0 + p["d_skip"].astype(f32))


def _ssm_inputs(cfg: ArchConfig, p, xc, dt_bias):
    """dt (softplus, f32), B and C of the selective scan from the post-conv
    activations ``xc``; dt's low-rank input, B and C are RMS-normed."""
    r, n = cfg.dt_rank, cfg.ssm_state
    proj = jnp.einsum("bsc,ce->bse", xc, p["x_proj"].astype(xc.dtype),
                      preferred_element_type=jnp.float32)
    dt_low, bmat, cmat = jnp.split(proj, [r, r + n], axis=-1)
    dt_low = L.rms_norm(dt_low, p["dt_norm"], cfg.norm_eps)
    bmat = L.rms_norm(bmat, p["b_norm"], cfg.norm_eps)
    cmat = L.rms_norm(cmat, p["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rc->bsc", dt_low, p["dt_w"].astype(jnp.float32))
        + dt_bias)
    return dt, bmat, cmat


def _mamba_seq(cfg: ArchConfig, p, x):
    """x: (B, S, d) -> (y (B, S, d_inner) gated by silu(z), conv tail,
    final SSM state), from the zero state."""
    dt_ = x.dtype
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(dt_),
                    preferred_element_type=jnp.float32).astype(dt_)
    xs, z = jnp.split(xz, 2, axis=-1)
    xc, new_tail = S.depthwise_conv(xs, p["conv"], p["conv_bias"])
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(dt_)
    a_log, dt_bias, d_skip = ssm_coefficients(cfg, p)
    dt, bmat, cmat = _ssm_inputs(cfg, p, xc, dt_bias)
    state0 = jnp.zeros((x.shape[0], cfg.d_inner, cfg.ssm_state), jnp.float32)
    y, state = S.ssm_scan(xc, dt, a_log, bmat, cmat, d_skip, state0)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(dt_)
    return y, new_tail, state


def _mamba_step(cfg: ArchConfig, p, x_t, conv_tail, state):
    """x_t: (B, 1, d).  Single decode step."""
    dt_ = x_t.dtype
    xz = jnp.einsum("bsd,de->bse", x_t, p["in_proj"].astype(dt_),
                    preferred_element_type=jnp.float32).astype(dt_)
    xs, z = jnp.split(xz, 2, axis=-1)
    # conv over (tail ++ x)
    full = jnp.concatenate([conv_tail, xs], axis=1)       # (B, cw, di)
    w = p["conv"].astype(jnp.float32)
    xc = (jnp.sum(full.astype(jnp.float32) * w.T[None], axis=1, keepdims=True)
          + p["conv_bias"].astype(jnp.float32))
    xc = jax.nn.silu(xc).astype(dt_)
    new_tail = full[:, 1:]
    a_log, dt_bias, d_skip = ssm_coefficients(cfg, p)
    dt, bmat, cmat = _ssm_inputs(cfg, p, xc, dt_bias)
    y, state = S.ssm_step(xc[:, 0], dt[:, 0], a_log, bmat[:, 0],
                          cmat[:, 0], d_skip, state)
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


def _seat_cache(k_all: Array, cap_total: int, n_meta: int = 0) -> Array:
    """Place the tail of prefill K/V (B, S, ...) into a fresh ring/linear
    cache of capacity cap_total, at the slots decode will expect
    (``L.ring_write``: slot = abs_pos % cap_total, or past ``n_meta``
    pinned slots that hold the first n_meta positions)."""
    if n_meta:
        return jnp.concatenate(
            [k_all[:, :n_meta],
             _seat_cache(k_all[:, n_meta:], cap_total - n_meta)], axis=1)
    b, s = k_all.shape[:2]
    t = min(cap_total, s)
    tail = k_all[:, s - t:]
    slots = np.arange(s - t, s) % cap_total
    out = jnp.zeros((b, cap_total) + k_all.shape[2:], k_all.dtype)
    return out.at[:, slots].set(tail)


def apply_period(cfg: ArchConfig, period, bp: Dict[str, Any], x: Array, *,
                 mode: str, ctx: Optional[Array] = None,
                 caches: Optional[Dict[str, Any]] = None,
                 pos: Optional[Array] = None,
                 s_max: Optional[int] = None) -> Tuple[Array, Dict]:
    """Apply a period's layers (``bp``/``caches`` keyed ``pos<i>``) in
    order; a ``SHARED_KV`` layer attends over the keys and values of the
    layer before it that holds them.  Returns (x, new caches)."""
    out: Dict[str, Any] = {}
    kv = None
    for i, tag in enumerate(period):
        c = caches[f"pos{i}"] if caches is not None else None
        if parse_tag(tag)[0] == "hybrid":
            x, out[f"pos{i}"], kv = hybrid_layer(
                cfg, tag, bp[f"pos{i}"], x, mode=mode, cache=c, pos=pos,
                s_max=s_max, kv=kv)
        else:
            x, out[f"pos{i}"] = apply_layer(cfg, tag, bp[f"pos{i}"], x,
                                            mode=mode, ctx=ctx, cache=c,
                                            pos=pos, s_max=s_max)
    return x, out


def hybrid_layer(cfg: ArchConfig, tag: str, p: Dict[str, Any], x: Array, *,
                 mode: str, cache: Optional[Dict[str, Array]] = None,
                 pos: Optional[Array] = None, s_max: Optional[int] = None,
                 kv=None) -> Tuple[Array, Optional[Dict], Any]:
    """Hymba's layer: attention heads and SSM heads side by side on one
    normed input; each stream gets its own RMSNorm, their mean goes
    through one shared ``out_proj`` into the residual; then the MLP.

    ``kv``: the (k, v) of the layer before this one in its group (the
    updated cache in decode, every position's in prefill/train), read by a
    ``SHARED_KV`` layer.  Returns (x, new_cache, kv for the next layer)."""
    _, var = parse_tag(tag)
    shared = shares_kv(tag)
    b, s, _ = x.shape
    window = cfg.window if var == "local" else 0
    y = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    new_cache: Dict[str, Array] = {}
    if mode == "decode":
        k_c, v_c = kv if shared else (cache["k"], cache["v"])
        att, k_c, v_c = _self_attention_step(cfg, p["attn"], y, k_c, v_c,
                                             pos, window=window,
                                             write=not shared)
        if not shared:
            new_cache["k"], new_cache["v"] = k_c, v_c
        kv = (k_c, v_c)
        m_out, new_cache["conv"], new_cache["ssm"] = _mamba_step(
            cfg, p["mamba"], y, cache["conv"], cache["ssm"])
    else:
        # q/kv blocks of 512 keep a B=8 re-prefill of ~2k positions'
        # scores near 200 MB a block
        att, k_all, v_all = _self_attention_seq(
            cfg, p["attn"], y, jnp.arange(s), causal=True, window=window,
            kv=kv if shared else None, block=512)
        kv = (k_all, v_all)
        m_out, tail, state = _mamba_seq(cfg, p["mamba"], y)
        if mode == "prefill":
            if not shared:
                cap = kv_capacity(cfg, var, s_max)
                new_cache["k"] = _seat_cache(k_all, cap, cfg.meta_tokens)
                new_cache["v"] = _seat_cache(v_all, cap, cfg.meta_tokens)
            new_cache["conv"], new_cache["ssm"] = tail, state
    a_flat = att.reshape(b, s, -1)
    if mode != "decode":
        from repro.dist import mesh as dmesh
        a_flat = dmesh.seq_parallel(a_flat, "res")
    mix = 0.5 * (L.rms_norm(a_flat, p["norm_attn"], cfg.norm_eps)
                 .astype(jnp.float32)
                 + L.rms_norm(m_out, p["norm_mamba"], cfg.norm_eps)
                 .astype(jnp.float32))
    x = x + jnp.einsum("bsc,cd->bsd", mix.astype(x.dtype),
                       p["out_proj"].astype(x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    y = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + L.gated_mlp(y, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                        p["mlp"]["w_down"], cfg.act)
    return x, (new_cache or None), kv


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
                att, new_cache["k"], new_cache["v"] = _self_attention_step(
                    cfg, p["attn"], y, cache["k"], cache["v"], pos,
                    window=window)
            else:
                positions = jnp.arange(s)
                att, k_all, v_all = _self_attention_seq(
                    cfg, p["attn"], y, positions, causal=causal, window=window)
                if mode == "prefill":
                    cap = kv_capacity(cfg, var, s_max)
                    new_cache["k"] = _seat_cache(k_all, cap, cfg.meta_tokens)
                    new_cache["v"] = _seat_cache(v_all, cap, cfg.meta_tokens)
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
