"""Plain float32 forward pass of Hymba (arXiv:2411.13676; sizes from the
published ``config.json`` of nvidia/Hymba-1.5B-Base), for the tests: the
whole sequence at once, no cache, no chunking, no kernel, every matrix
product at ``Precision.HIGHEST``.

Every sequence is the meta tokens, then the tokens.  A layer, on x:

* ``u = RMSNorm(x)``;
* attention heads: ``q = u Wq``, ``k = u Wk``, ``v = u Wv`` (a layer of a
  KV-sharing group after its first projects only q and uses the first's k
  and v), rotate-half RoPE on q and k at absolute positions (meta
  included), ``softmax(q k^T / sqrt(head_dim))`` under a causal mask; a
  windowed layer also masks every key ``window`` or more positions back
  except the meta tokens.  Query head j reads KV head j // (H / K);
* SSM heads: ``[x_s, z] = u W_in``; ``x_c = silu(causal depthwise conv of
  x_s + bias)``; ``[dt_l, B, C] = x_c W_x``, each RMS-normed; ``dt =
  softplus(dt_l W_dt + b_dt)``; ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t
  B_t`` with ``A = -exp(A_log)``, run position by position; ``y_t = C_t
  h_t + D x_t``; ``y = y * silu(z)``;
* ``x = x + ((RMSNorm(att) + RMSNorm(y)) / 2) W_out``;
* ``x = x + SwiGLU(RMSNorm(x))``.

A final RMSNorm and the tied embedding as the head.  Departures of the
program from the published model, none a change of what is computed:
``in_proj`` is held as the SSM's ``mamba.in_proj`` (x_s, z) and the
attention's ``wq``/``wk``/``wv``; norm gains are stored as ``gamma`` and
applied as ``1 + gamma``; ``A_log``, dt's bias and ``D`` are stored as
offsets from Mamba's initialisation (``_ssm_coefficients``); the
embedding is padded to a multiple of 256
rows (logits are read over the real vocabulary).  Assumed: a key is in
the window when the query is fewer than ``window`` positions after it.
"""
import math

import jax
import jax.numpy as jnp

from repro.models import backbone

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + gamma)


def _rope(x, theta):
    """x: (B, S, H, D) at positions 0 .. S-1."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_mask(s, window, n_meta):
    """(S, S) True where query i sees key j."""
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = j <= i
    if window:
        mask &= (i - j < window) | (j < n_meta)
    return mask


def _attention(cfg, p, u, kv, window):
    b, s, _ = u.shape
    h, kh = cfg.n_heads, cfg.n_kv_heads
    e, ev = cfg.resolved_head_dim, cfg.v_head_dim
    q = _rope(_mm(u, p["wq"].reshape(-1, h * e)).reshape(b, s, h, e),
              cfg.rope_theta)
    if kv is None:
        k = _rope(_mm(u, p["wk"].reshape(-1, kh * e)).reshape(b, s, kh, e),
                  cfg.rope_theta)
        v = _mm(u, p["wv"].reshape(-1, kh * ev)).reshape(b, s, kh, ev)
        kv = (k, v)
    k, v = (jnp.repeat(t, h // kh, axis=2) for t in kv)
    sc = jnp.einsum("bqhe,bkhe->bhqk", q, k, precision=HIGHEST) / e ** 0.5
    sc = jnp.where(attention_mask(s, window, cfg.meta_tokens)[None, None],
                   sc, -jnp.inf)
    out = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(sc, -1), v,
                     precision=HIGHEST)
    return out.reshape(b, s, h * ev), kv


def _ssm_coefficients(p, di, n):
    """log(-A), dt's bias and D from the stored offsets from Mamba's
    initialisation: A = -(1..N), dt log-spaced over [1e-3, 0.1] across the
    channels (its bias the inverse softplus), D = 1."""
    dt0 = jnp.exp(jnp.linspace(math.log(1e-3), math.log(0.1), di))
    return (p["a_log"] + jnp.log(jnp.arange(1.0, n + 1)),
            p["dt_bias"] + dt0 + jnp.log(-jnp.expm1(-dt0)),
            1.0 + p["d_skip"])


def _ssm(cfg, p, u):
    di, n, r = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    xs, z = jnp.split(_mm(u, p["in_proj"]), 2, axis=-1)
    cw = p["conv"].shape[1]
    xp = jnp.pad(xs, ((0, 0), (cw - 1, 0), (0, 0)))
    s = xs.shape[1]
    xc = p["conv_bias"] + sum(xp[:, i:i + s] * p["conv"][:, i]
                              for i in range(cw))
    xc = jax.nn.silu(xc)
    dt_l, bm, cm = jnp.split(_mm(xc, p["x_proj"]), [r, r + n], axis=-1)
    eps = cfg.norm_eps
    a_log, dt_bias, d_skip = _ssm_coefficients(p, di, n)
    dt = jax.nn.softplus(_mm(_rms(dt_l, p["dt_norm"], eps), p["dt_w"])
                         + dt_bias)
    bm, cm = _rms(bm, p["b_norm"], eps), _rms(cm, p["c_norm"], eps)
    a = -jnp.exp(a_log)                               # (di, n)

    def step(hs, t):
        x_t, dt_t, b_t, c_t = t                       # (B, di), (B, n)
        hs = jnp.exp(dt_t[..., None] * a) * hs \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return hs, jnp.einsum("bcn,bn->bc", hs, c_t, precision=HIGHEST)

    h0 = jnp.zeros((u.shape[0], di, n), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(
        t.swapaxes(0, 1) for t in (xc, dt, bm, cm)))
    y = y.swapaxes(0, 1) + xc * d_skip
    return y * jax.nn.silu(z)


def forward(cfg, params, tokens):
    """Logits (B, S, vocab) of every token position of ``tokens`` (B, S)
    under the ``ArchConfig`` ``cfg``."""
    with jax.default_matmul_precision("highest"):
        m, eps = cfg.meta_tokens, cfg.norm_eps
        x = params["embed"][tokens]
        x = jnp.concatenate([jnp.broadcast_to(
            params["meta"][None], (x.shape[0],) + params["meta"].shape), x],
            axis=1)
        kv = None
        for tag, p in zip(cfg.layer_pattern,
                          backbone.layer_params(cfg, params)):
            u = _rms(x, p["ln1"], eps)
            shared = tag.endswith(":shared")
            window = cfg.window if ":local" in tag else 0
            att, kv = _attention(cfg, p["attn"], u, kv if shared else None,
                                 window)
            y = _ssm(cfg, p["mamba"], u)
            mix = 0.5 * (_rms(att, p["norm_attn"], eps)
                         + _rms(y, p["norm_mamba"], eps))
            x = x + _mm(mix, p["out_proj"])
            g = _rms(x, p["ln2"], eps)
            x = x + _mm(jax.nn.silu(_mm(g, p["mlp"]["w_gate"]))
                        * _mm(g, p["mlp"]["w_up"]), p["mlp"]["w_down"])
        x = _rms(x[:, m:], params["final_norm"], eps)
        return _mm(x, params["embed"].T)[..., :cfg.vocab]
