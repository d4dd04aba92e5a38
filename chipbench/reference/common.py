"""Pieces shared by the plain references: float32 at the highest matmul
precision, with an optional lower-precision ``quant`` mode used only by
the correctness control (``int8``: symmetric per-row activations and
per-column weights, the w8a8 step a later change might take; ``fp8``:
the same scaling into float8 e4m3)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_QMAX = {"int8": 127.0, "fp8": 448.0}


def _fake_quant(x, axis: int, quant: str):
    """Round ``x`` to ``quant`` with one absmax scale per slice along
    ``axis`` (the contracting axis), and return it in float32."""
    qmax = _QMAX[quant]
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax
    scale = jnp.where(scale > 0, scale, 1.0)
    y = x / scale
    if quant == "int8":
        y = jnp.clip(jnp.round(y), -qmax, qmax)
    else:
        y = y.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return y * scale


def linear(x, w, quant=None):
    """x: (..., K) @ w: (K, N) in float32 (``quant`` rounds both inputs
    first)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant is not None:
        x = _fake_quant(x, -1, quant)
        w = _fake_quant(w, 0, quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, gamma, eps):
    """RMSNorm with a zero-centred gain, ``x / rms(x) * (1 + gamma)``."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gamma)


def swiglu(x, w_gate, w_up, w_down, quant=None):
    g = linear(x, w_gate, quant)
    u = linear(x, w_up, quant)
    return linear(jax.nn.silu(g) * u, w_down, quant)


def layer_params(params, i: int, period: int):
    """Layer ``i``'s weights from the stacked superblock layout: layers
    ``[0, n_super * period)`` sit in ``blocks["pos<i % period>"][i //
    period]``, the rest in ``rem["rem<j>"]``."""
    blocks = params.get("blocks")
    n_super = 0
    if blocks:
        n_super = jax.tree.leaves(blocks["pos0"])[0].shape[0]
    if i < n_super * period:
        return jax.tree.map(lambda a: a[i // period],
                            blocks[f"pos{i % period}"])
    return params["rem"][f"rem{i - n_super * period}"]


def head_stats(logits, tokens, other=None):
    """Per position ``t`` of ``logits`` (B, S, V) float32 over the real
    vocabulary: the gap by which ``tokens[:, t]`` lies below the row's
    best, in units of the row's standard deviation, and the same gap of
    the token that ``other`` (B, S, V) puts first (``None`` when not
    given)."""
    best = jnp.max(logits, axis=-1)
    std = jnp.std(logits, axis=-1)
    got = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    gap = (best - got) / std
    if other is None:
        return gap, None
    pick = jnp.argmax(other, axis=-1)
    alt = jnp.take_along_axis(logits, pick[..., None], axis=-1)[..., 0]
    return gap, (best - alt) / std
