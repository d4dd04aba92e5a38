"""Plain float32 forward pass of Phi-3-medium (arXiv:2404.14219; sizes
from the published ``config.json`` of microsoft/Phi-3-medium-4k-instruct).

A pre-norm decoder: RMSNorm, grouped-query attention (40 query heads over
10 key/value heads of 128) with rotate-half rotary embeddings, a residual
add, RMSNorm, a SwiGLU MLP, a residual add; a final RMSNorm and an untied
LM head.  No biases.

Departures of the served program from the published model, none of them
a change of what is computed at these sizes:

* fused ``qkv_proj`` and ``gate_up_proj`` are held as separate matrices;
* norm gains are stored as ``gamma`` with the gain ``1 + gamma`` (the
  published ``weight`` is that gain);
* the published 2,047-token sliding window is not applied: no sequence
  here is longer than 1,024 tokens, so it never binds.

Everything is computed in float32 at ``Precision.HIGHEST`` with a full
(non-cached) causal forward over the whole sequence, one jitted program
per layer, called layer by layer; attention takes its queries in blocks
so that the scores of a block of rows fit beside the weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.common import (HIGHEST, layer_params, linear,
                                        rms_norm, swiglu)

Q_BLOCK = 256


def _rope(x, theta):
    """Rotate-half rotary embedding; x: (B, S, H, D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, y, dims, quant):
    b, s, d = y.shape
    h, kv, e = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    q = linear(y, p["wq"].reshape(d, h * e), quant)
    k = linear(y, p["wk"].reshape(d, kv * e), quant)
    v = linear(y, p["wv"].reshape(d, kv * e), quant)
    q = _rope(q.reshape(b, s, h, e), dims["rope_theta"]) / math.sqrt(e)
    k = _rope(k.reshape(b, s, kv, e), dims["rope_theta"])
    v = v.reshape(b, s, kv, e)
    # query head j reads key/value head j // (h // kv)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    out = []
    for lo in range(0, s, Q_BLOCK):
        qb = q[:, lo:lo + Q_BLOCK]
        sc = jnp.einsum("bqhe,bkhe->bhqk", qb, k, precision=HIGHEST)
        i = jnp.arange(lo, lo + qb.shape[1])[:, None]
        j = jnp.arange(s)[None, :]
        sc = jnp.where((j <= i)[None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.einsum("bhqk,bkhe->bqhe", pr, v, precision=HIGHEST))
    out = jnp.concatenate(out, axis=1).reshape(b, s, h * e)
    return linear(out, p["wo"].reshape(h * e, d), quant)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(p, x, *, dims, quant):
    dims = dict(dims)
    eps = dims["norm_eps"]
    x = x + _attention(p["attn"], rms_norm(x, p["ln1"], eps), dims, quant)
    y = rms_norm(x, p["ln2"], eps)
    return x + swiglu(y, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                      p["mlp"]["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _logits(params, x, *, dims, quant):
    dims = dict(dims)
    h = rms_norm(x, params["final_norm"], dims["norm_eps"])
    return linear(h, params["lm_head"][:, :dims["vocab"]], quant)


def _key(dims: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in dims.items()))


def forward(params, tokens, dims: dict, quant=None):
    """Logits (B, S, vocab) float32 of every position of ``tokens``
    (B, S) int32, from the whole sequence at once."""
    key = _key(dims)
    period = len(dims["layer_pattern"])
    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(dims["n_layers"]):
        x = _layer(layer_params(params, i, period), x, dims=key, quant=quant)
    return _logits(params, x, dims=key, quant=quant)
