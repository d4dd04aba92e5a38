"""Selective state-space (Mamba-style) sequence mixer.

Used by the hymba hybrid layers (parallel attention + mamba heads).

Prefill/train path: *chunked* associative scan — a sequential `lax.scan`
over chunks of the sequence, with a parallel `lax.associative_scan` inside
each chunk.  A fully parallel associative scan over the whole sequence
would materialize (B, S, d_inner, N) decay/state tensors (terabytes at
train_4k); chunking bounds live memory to (B, chunk, d_inner, N) while
keeping log-depth parallelism inside the chunk.

Decode path: single-step recurrence on the carried (B, d_inner, N) state
plus a (B, conv_w-1, d_inner) convolution tail.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array


def _ssm_combine(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a1 * a2, a2 * b1 + b2


def depthwise_conv(x: Array, w: Array, bias: Array) -> Tuple[Array, Array]:
    """Causal depthwise conv1d with a bias, over a fresh sequence.

    x: (B, S, C); w: (C, K); bias: (C,).  Returns (y, the last K-1 inputs:
    the decode tail).
    """
    b, s, c = x.shape
    k = w.shape[1]
    tail = jnp.zeros((b, k - 1, c), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)            # (B, S+K-1, C)
    y = jnp.broadcast_to(bias.astype(jnp.float32), (b, s, c))
    for i in range(k):
        y = y + xp[:, i:i + s].astype(jnp.float32) * w[:, i].astype(jnp.float32)
    new_tail = xp[:, s:]                                # last K-1 inputs
    return y.astype(x.dtype), new_tail


def ssm_scan(x_in: Array, dt: Array, a_log: Array, bmat: Array, cmat: Array,
             d_skip: Array, state0: Array, *, chunk: int = 128
             ) -> Tuple[Array, Array]:
    """Selective scan.

    x_in:  (B, S, C)   post-conv activations (C = d_inner)
    dt:    (B, S, C)   positive step sizes (softplus already applied)
    a_log: (C, N)      log of -A (A = -exp(a_log))
    bmat:  (B, S, N)   input->state projection coefficients
    cmat:  (B, S, N)   state->output coefficients
    d_skip:(C,)        skip connection
    state0:(B, C, N)   initial state
    Returns (y (B, S, C) f32->x dtype, final_state (B, C, N) f32).

    A sequence longer than ``chunk`` that it does not divide is padded at
    the end with steps of dt = 0, which leave the state as it was.
    """
    b, s, c = x_in.shape
    n = a_log.shape[1]
    ch = min(chunk, s)
    pad = -s % ch
    if pad:
        x_in, dt, bmat, cmat = (
            jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
            for t in (x_in, dt, bmat, cmat))
    n_chunks = (s + pad) // ch

    a = -jnp.exp(a_log.astype(jnp.float32))            # (C, N), negative

    def per_chunk(state, xs):
        xc, dtc, bc, cc = xs                           # (B, ch, ...)
        dtc = dtc.astype(jnp.float32)
        decay = jnp.exp(dtc[..., None] * a)            # (B, ch, C, N)
        inp = (dtc * xc.astype(jnp.float32))[..., None] * bc[:, :, None, :].astype(jnp.float32)
        # Parallel scan inside the chunk (time axis = 1).
        dec_s, inp_s = lax.associative_scan(_ssm_combine, (decay, inp), axis=1)
        # Fold in the carried state.
        states = dec_s * state[:, None] + inp_s        # (B, ch, C, N)
        y = jnp.einsum("btcn,btn->btc", states, cc.astype(jnp.float32))
        y = y + xc.astype(jnp.float32) * d_skip.astype(jnp.float32)
        return states[:, -1], y

    if n_chunks > 1:
        xs = tuple(
            t.reshape(b, n_chunks, ch, *t.shape[2:]).swapaxes(0, 1)
            for t in (x_in, dt, bmat, cmat))
        # Remat the chunk: the (B, ch, C, N) decay/state tensors (~5 x
        # 210 MB per chunk at hymba train_4k) are recomputed in backward
        # instead of stacked as residuals (§Perf, same policy as
        # blockwise_attention / mlstm_chunkwise).
        state_f, ys = lax.scan(jax.checkpoint(per_chunk),
                               state0.astype(jnp.float32), xs)
        y = ys.swapaxes(0, 1).reshape(b, s + pad, c)[:, :s]
    else:
        state_f, y = per_chunk(state0.astype(jnp.float32), (x_in, dt, bmat, cmat))
    return y.astype(x_in.dtype), state_f


def ssm_step(x_t: Array, dt_t: Array, a_log: Array, b_t: Array, c_t: Array,
             d_skip: Array, state: Array) -> Tuple[Array, Array]:
    """One decode step.  x_t/dt_t: (B, C); b_t/c_t: (B, N); state: (B, C, N)."""
    a = -jnp.exp(a_log.astype(jnp.float32))
    dtf = dt_t.astype(jnp.float32)
    decay = jnp.exp(dtf[..., None] * a)                # (B, C, N)
    inp = (dtf * x_t.astype(jnp.float32))[..., None] * b_t[:, None, :].astype(jnp.float32)
    new_state = decay * state.astype(jnp.float32) + inp
    y = jnp.einsum("bcn,bn->bc", new_state, c_t.astype(jnp.float32))
    y = y + x_t.astype(jnp.float32) * d_skip.astype(jnp.float32)
    return y.astype(x_t.dtype), new_state
