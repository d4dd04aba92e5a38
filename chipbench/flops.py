"""Operations and bytes one decode step needs, from a configuration's
sizes (``configs/<name>.json`` ``model``) alone.

FLOPs of a token are those of the model's matrix products at its
position: 2 per weight of every projection (the LM head over the real
vocabulary included; the embedding lookup is a gather and counts none),
plus attention's scores and weighted sum over the keys the token sees.
Element-wise work (norms, gates, recurrences) is left out.

Bytes of a step are what it must read at the compute dtype: every weight
once per step (2 bytes each in bfloat16, whatever the batch), and each
active session's decode state once.  They are a floor that batching or
bf16 weights can reach, not what today's program moves.

What one layer needs comes from ``counts/<kind>.py`` for its kind (the
``layer_pattern`` tag before ``:``), so a configuration with a new kind
of layer adds a file.
"""
from __future__ import annotations

import importlib

BF16 = 2


def _kind(tag: str):
    return importlib.import_module(f"chipbench.counts.{tag.split(':')[0]}")


def _period_counts(dims):
    pattern = dims["layer_pattern"]
    counts = {}
    for i in range(dims["n_layers"]):
        tag = pattern[i % len(pattern)]
        counts[tag] = counts.get(tag, 0) + 1
    return counts


def layer_weights(dims: dict, tag: str) -> int:
    """Parameters of one layer of kind ``tag`` that enter a matrix
    product."""
    return _kind(tag).matmul_weights(dims, tag)


def matmul_weights(dims: dict) -> int:
    """Weights entering a matrix product for one token, LM head over the
    real vocabulary included."""
    total = sum(c * layer_weights(dims, t)
                for t, c in _period_counts(dims).items())
    return total + dims["d_model"] * dims["vocab"]


def all_weights(dims: dict) -> int:
    """Every parameter a decode step reads: the matrix weights, the
    embedding table when it is not the head, the norm gains and small
    vectors, and the final norm."""
    d = dims["d_model"]
    total = matmul_weights(dims)
    if not dims["tie_embeddings"]:
        total += d * dims["vocab"]                     # embedding rows
    total += sum(c * _kind(t).small_weights(dims, t)
                 for t, c in _period_counts(dims).items())
    return total + d


def token_flops(dims: dict, pos: int) -> float:
    """FLOPs of one token decoded at position ``pos`` (0-based)."""
    return 2.0 * matmul_weights(dims) + sum(
        c * _kind(t).attn_flops(dims, t, pos)
        for t, c in _period_counts(dims).items())


def state_bytes(dims: dict, pos: int) -> int:
    """Decode state one session at position ``pos`` must read."""
    return sum(c * _kind(t).state_bytes(dims, t, pos)
               for t, c in _period_counts(dims).items())


def step_bytes(dims: dict, positions) -> int:
    """Bytes one decode step needs for sessions at ``positions``."""
    return all_weights(dims) * BF16 + sum(state_bytes(dims, p)
                                          for p in positions)
