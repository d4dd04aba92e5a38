"""Plain float32 forward pass of Hymba-1.5B (arXiv:2411.13676; sizes from
the published ``config.json`` of nvidia/Hymba-1.5B-Base).

Every sequence is the 128 meta tokens, then the tokens.  A layer, on x:

* ``u = RMSNorm(x)``;
* attention heads: ``q = u Wq``, ``k = u Wk``, ``v = u Wv`` (q/k heads of
  64, value heads of 128; a layer of a ``kv_reuse_group`` after its first
  projects only q and attends over the first's k and v: ``kv_source``),
  rotate-half RoPE on q and k at absolute positions (meta included),
  ``softmax(q k^T / 8)`` under a causal mask, query head j reading KV head
  j // 5.  A windowed layer (all but ``global_attn_idx`` 0, 15, 31) also
  masks every key 1024 or more positions back, except the meta tokens;
* SSM heads (Mamba-1, ``mamba_inner_layernorms``): ``[x_s, z] = u W_in``;
  ``x_c = silu(causal depthwise conv of x_s + bias)``; ``[dt_l, B, C] =
  x_c W_x``, each RMS-normed; ``dt = softplus(dt_l W_dt + b_dt)``; ``h_t =
  exp(dt_t A) h_{t-1} + dt_t x_t B_t`` with ``A = -exp(A_log)``, a
  sequential scan over positions; ``y_t = C_t h_t + D x_t``; ``y = y *
  silu(z)``;
* ``x = x + ((RMSNorm(att) + RMSNorm(y)) / 2) W_out``: each stream its own
  norm, one shared out_proj;
* ``x = x + SwiGLU(RMSNorm(x))``.

A final RMSNorm and the tied embedding as the head; no biases but the
conv's and dt's.

Departures of the served program from the published model, none of them
a change of what is computed at these sizes:

* ``in_proj`` is held split: the SSM's x and gate z in ``mamba.in_proj``,
  q, k, v in ``attn.wq``/``wk``/``wv``;
* norm gains are stored as ``gamma`` with the gain ``1 + gamma``;
* ``A_log``, dt's bias and ``D`` are stored as offsets from Mamba's
  initialisation (arXiv:2312.00752 §3.6): ``a_log`` + log(1..16),
  ``dt_bias`` + the inverse softplus of dt log-spaced over [1e-3, 0.1]
  across the channels, and 1 + ``d_skip``;
* the embedding is padded to 32256 rows (logits over the real 32001);
* the published RoPE scaling (``ntk`` past 2048 positions) is not applied:
  no position here passes 1932.

Everything is computed in float32 at ``Precision.HIGHEST``: one jitted
program per kind of layer, called layer by layer over the whole sequence;
attention takes its queries in blocks so that a block's scores fit beside
the weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.common import HIGHEST, linear, rms_norm, swiglu

Q_BLOCK = 256


def _rope(x, theta):
    """Rotate-half rotary embedding at positions 0 .. S-1; x: (B, S, H, D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, u, kv, dims, window, quant):
    b, s, d = u.shape
    h, kh = dims["n_heads"], dims["n_kv_heads"]
    e, ev = dims["head_dim"], dims["v_head_dim"]
    q = _rope(linear(u, p["wq"].reshape(d, h * e), quant).reshape(b, s, h, e),
              dims["rope_theta"]) / math.sqrt(e)
    if kv is None:
        k = linear(u, p["wk"].reshape(d, kh * e), quant).reshape(b, s, kh, e)
        v = linear(u, p["wv"].reshape(d, kh * ev), quant).reshape(b, s, kh,
                                                                  ev)
        kv = (_rope(k, dims["rope_theta"]), v)
    k, v = (jnp.repeat(t, h // kh, axis=2) for t in kv)
    m = dims["meta_tokens"]
    out = []
    for lo in range(0, s, Q_BLOCK):
        qb = q[:, lo:lo + Q_BLOCK]
        sc = jnp.einsum("bqhe,bkhe->bhqk", qb, k, precision=HIGHEST)
        i = jnp.arange(lo, lo + qb.shape[1])[:, None]
        j = jnp.arange(s)[None, :]
        see = j <= i
        if window:
            see &= (i - j < window) | (j < m)
        sc = jnp.where(see[None, None], sc, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(sc, -1), v,
                              precision=HIGHEST))
    return jnp.concatenate(out, axis=1).reshape(b, s, h * ev), kv


def _ssm(p, u, dims, quant):
    r, n, eps = dims["dt_rank"], dims["ssm_state"], dims["norm_eps"]
    xs, z = jnp.split(linear(u, p["in_proj"], quant), 2, axis=-1)
    cw, s = dims["conv_width"], xs.shape[1]
    xp = jnp.pad(xs, ((0, 0), (cw - 1, 0), (0, 0)))
    xc = jax.nn.silu(p["conv_bias"] + sum(xp[:, i:i + s] * p["conv"][:, i]
                                          for i in range(cw)))
    dt_l, bm, cm = jnp.split(linear(xc, p["x_proj"], quant), [r, r + n],
                             axis=-1)
    # dt_w is served in float32: the control rounds only the matrices the
    # program serves in its compute dtype
    # a_log, dt_bias and d_skip are offsets from Mamba's initialisation
    dt0 = jnp.exp(jnp.linspace(math.log(1e-3), math.log(0.1),
                               dims["d_inner"]))
    dt = jax.nn.softplus(linear(rms_norm(dt_l, p["dt_norm"], eps), p["dt_w"])
                         + p["dt_bias"] + dt0 + jnp.log(-jnp.expm1(-dt0)))
    bm, cm = rms_norm(bm, p["b_norm"], eps), rms_norm(cm, p["c_norm"], eps)
    a = -jnp.exp(p["a_log"] + jnp.log(jnp.arange(1.0, n + 1)))  # (d_inner, N)

    def step(hs, t):
        x_t, dt_t, b_t, c_t = t
        hs = jnp.exp(dt_t[..., None] * a) * hs \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return hs, jnp.einsum("bcn,bn->bc", hs, c_t, precision=HIGHEST)

    h0 = jnp.zeros((u.shape[0], dims["d_inner"], n), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(t.swapaxes(0, 1)
                                        for t in (xc, dt, bm, cm)))
    return (y.swapaxes(0, 1) + xc * (1.0 + p["d_skip"])) * jax.nn.silu(z)


@functools.partial(jax.jit, static_argnames=("dims", "window", "quant"))
def _layer(p, x, kv, *, dims, window, quant):
    dims = dict(dims)
    eps = dims["norm_eps"]
    u = rms_norm(x, p["ln1"], eps)
    att, kv = _attention(p["attn"], u, kv, dims, window, quant)
    y = _ssm(p["mamba"], u, dims, quant)
    mix = 0.5 * (rms_norm(att, p["norm_attn"], eps)
                 + rms_norm(y, p["norm_mamba"], eps))
    x = x + linear(mix, p["out_proj"], quant)
    g = rms_norm(x, p["ln2"], eps)
    x = x + swiglu(g, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                   p["mlp"]["w_down"], quant)
    return x, kv


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _logits(params, x, *, dims, quant):
    dims = dict(dims)
    h = rms_norm(x[:, dims["meta_tokens"]:], params["final_norm"],
                 dims["norm_eps"])
    return linear(h, params["embed"][:dims["vocab"]].T, quant)


def _key(dims: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in dims.items()))


def _layers(params):
    """Each layer's weights in order: the program stacks runs of KV groups
    at ``blocks/run<r>/pos<i>`` over the run's repeats."""
    blocks = params["blocks"]
    for r in range(len(blocks)):
        run = blocks[f"run{r}"]
        for k in range(jax.tree.leaves(run)[0].shape[0]):
            for i in range(len(run)):
                yield jax.tree.map(lambda a, k=k: a[k], run[f"pos{i}"])


def forward(params, tokens, dims: dict, quant=None):
    """Logits (B, S, vocab) float32 of every position of ``tokens``
    (B, S) int32, from the meta tokens and the whole sequence at once."""
    key = _key(dims)
    x = params["embed"][tokens].astype(jnp.float32)
    meta = params["meta"].astype(jnp.float32)
    x = jnp.concatenate(
        [jnp.broadcast_to(meta[None], (x.shape[0],) + meta.shape), x], 1)
    kv = None
    for i, p in enumerate(_layers(params)):
        tag = dims["layer_pattern"][i]
        shared = dims["kv_source"][i] != i
        window = dims["window"] if tag.split(":")[1] == "local" else 0
        x, kv = _layer(p, x, kv if shared else None, dims=key,
                       window=window, quant=quant)
    return _logits(params, x, dims=key, quant=quant)
