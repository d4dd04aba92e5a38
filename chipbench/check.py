"""The comparison that decides ``correct``.

* Persistence: every token acknowledged before a crash, and every token
  of the sessions live when the window closes, is read back from the
  engine's request table and token log (``Clients.read_back``).  Limit 0.
* Model programs and recovery: a sample of the requests served, drawn
  from the seed and holding the longest, is run through the plain
  float32 reference over its prompt and served tokens.  For each served
  token, the gap by which the reference's logit of that token lies below
  the reference's best, in units of the standard deviation of that row
  of logits.  The largest gap is compared with the cell's limit.  Tokens
  served after a crash come from the recovered state, so a wrong
  recovery shows here.

The reference runs after the window, once the engine is freed, in blocks
of ``ROWS`` sequences padded to ``s_max`` (causal, so padding after a
sequence changes none of its positions).
"""
from __future__ import annotations

import importlib
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.common import head_stats

ROWS = 4
MIN_TOKENS = 256
MAX_REQUESTS = 24


def sample(requests, seed: int) -> list:
    """Served requests drawn from the seed until they hold
    ``MIN_TOKENS`` served tokens (or ``MAX_REQUESTS`` requests), the one
    with the most served tokens always among them."""
    served = [r for r in requests if r.tokens]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.tokens), -r.rid))
    rest = [r for r in served if r is not longest]
    order = np.random.default_rng([seed, 0xC0FFEE]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= MIN_TOKENS or len(out) >= MAX_REQUESTS:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def _batch(reqs, s_max: int):
    toks = np.zeros((ROWS, s_max), np.int32)
    mask = np.zeros((ROWS, s_max), bool)      # position t predicts t + 1
    nxt = np.zeros((ROWS, s_max), np.int32)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int64)])
        toks[i, :len(seq)] = seq
        p = len(r.prompt)
        nxt[i, p - 1:len(seq) - 1] = seq[p:]
        mask[i, p - 1:len(seq) - 1] = True
    return jnp.asarray(toks), jnp.asarray(nxt), mask


def gaps(params, dims: dict, reference: str, reqs: list, s_max: int,
         controls=()):
    """Largest gap of the served tokens, the largest gap of the tokens
    the reference computed at each precision of ``controls`` puts first
    (a dict), and the number of served tokens compared."""
    ref = importlib.import_module(f"chipbench.reference.{reference}")
    worst, n = 0.0, 0
    worst_ctl = {c: 0.0 for c in controls}
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(reqs), ROWS):
            toks, nxt, mask = _batch(reqs[lo:lo + ROWS], s_max)
            logits = ref.forward(params, toks, dims)
            gap, _ = head_stats(logits, nxt)
            gap = np.asarray(gap)[mask]
            n += gap.size
            worst = max(worst, float(gap.max()))
            for c in controls:
                _, alt = head_stats(logits, nxt,
                                    ref.forward(params, toks, dims, c))
                worst_ctl[c] = max(worst_ctl[c],
                                   float(np.asarray(alt)[mask].max()))
            del logits
    return worst, worst_ctl, n


def verdict(numbers: List[tuple]) -> bool:
    """``numbers``: (name, value, limit, kind) with kind ``max`` (value
    at most limit) or ``min`` (value at least limit)."""
    return all((v <= lim) if kind == "max" else (v >= lim)
               for _, v, lim, kind in numbers)
