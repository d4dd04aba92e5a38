"""The one traffic generator, and the closed-loop clients that drive the
serving engine through its own entry points.

A mix (``traffic/<name>.json``) gives the number of clients, the share of
each prompt length, the range of output lengths, whether the first
requests start part-way through their outputs (``stagger``), and an
optional crash rule.  Request ``k`` of every client forms block ``k``;
each block holds the mix's exact shares of prompt lengths and evenly
spaced output lengths, shuffled by the seed.  So every seed sends the
same sizes in another order, and the seed changes the tokens and the
order, not the work.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Mix:
    clients: int
    prompt_tokens: Dict[int, float]
    output_tokens: tuple
    stagger: bool
    crash_after: Optional[int]

    @classmethod
    def from_json(cls, d: dict) -> "Mix":
        crash = d.get("crash") or {}
        return cls(int(d["clients"]),
                   {int(k): float(v) for k, v in d["prompt_tokens"].items()},
                   tuple(int(x) for x in d["output_tokens"]),
                   bool(d.get("stagger", False)),
                   crash.get("after_tokens"))

    def longest(self) -> int:
        return max(self.prompt_tokens) + self.output_tokens[1]


def _shares(weights: Dict[int, float], n: int) -> List[int]:
    """n values with the weights' exact shares (largest remainder)."""
    keys = sorted(weights)
    total = sum(weights.values())
    raw = [weights[k] / total * n for k in keys]
    counts = [int(r) for r in raw]
    order = sorted(range(len(keys)), key=lambda i: counts[i] - raw[i])
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return [k for k, c in zip(keys, counts) for _ in range(c)]


@dataclasses.dataclass
class Request:
    rid: int
    client: int
    prompt: np.ndarray
    out_len: int
    sent: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    crashes: int = 0
    done: bool = False


class Requests:
    """Request ``k`` of client ``c``, generated block by block from the
    seed (block ``k`` depends only on the blocks before it)."""

    def __init__(self, mix: Mix, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.blocks: List[List[Request]] = []

    def get(self, client: int, k: int) -> Request:
        while len(self.blocks) <= k:
            self._add_block()
        return self.blocks[k][client]

    def _add_block(self) -> None:
        mix, rng, n = self.mix, self.rng, self.mix.clients
        b = len(self.blocks)
        plens = rng.permutation(_shares(mix.prompt_tokens, n))
        lo, hi = mix.output_tokens
        outs = rng.permutation(
            [int(round(lo + (hi - lo) * (i + 0.5) / n)) for i in range(n)])
        if b == 0 and mix.stagger:
            # the first requests are part-way through their outputs
            left = rng.permutation([(i + 0.5) / n for i in range(n)])
            outs = [max(1, int(round(o * f))) for o, f in zip(outs, left)]
        self.blocks.append([
            Request(rid=b * n + c + 1, client=c,
                    prompt=rng.integers(1, self.vocab, int(plens[c]),
                                        dtype=np.int64),
                    out_len=int(outs[c]))
            for c in range(n)])

    def all(self) -> List[Request]:
        return [r for blk in self.blocks for r in blk]


class Clients:
    """Closed-loop clients, one request in flight each, no think time: a
    client sends its next request the moment the previous one
    completes."""

    def __init__(self, eng, mix: Mix, requests: Requests, rec):
        self.eng, self.mix, self.requests, self.rec = eng, mix, requests, rec
        self.next_k = [0] * mix.clients
        self.free_at: List[Optional[float]] = [None] * mix.clients
        self.live: Dict[int, Request] = {}
        self.sent: List[Request] = []
        self.failed: List[Request] = []
        self.steps: List[tuple] = []           # (t0, t1, tokens)
        self.crashes: List[dict] = []
        self.readback_errors = 0
        self.readback_tokens = 0
        self._crash_t0: Optional[float] = None
        self._crash_lens: Dict[int, int] = {}

    # -- the engine's entry points, each inside a span ------------------
    def admit_free(self) -> None:
        busy = {r.client for r in self.live.values()}
        for c in range(self.mix.clients):
            if c in busy:
                continue
            req = self.requests.get(c, self.next_k[c])
            self.next_k[c] += 1
            now = time.perf_counter()
            req.sent = self.free_at[c] if self.free_at[c] is not None \
                else now
            self.sent.append(req)
            try:
                with self.rec.span("engine.add_request"):
                    self.eng.add_request(req.rid, req.prompt)
            except (RuntimeError, MemoryError) as e:
                # refused: counted as failed; the client tries again
                # after the next step
                req.done = True
                self.failed.append(req)
                self.free_at[c] = time.perf_counter()
                print(f"[chipbench] request {req.rid} refused: {e}",
                      flush=True)
                continue
            self.live[req.rid] = req

    def step(self) -> None:
        t0 = time.perf_counter()
        with self.rec.span("engine.step"):
            out = self.eng.step()
        t1 = time.perf_counter()
        self.steps.append((t0, t1, len(out)))
        for rid, tok in out.items():
            req = self.live[rid]
            req.tokens.append(int(tok))
            req.times.append(t1)
        if self._crash_t0 is not None:
            self._after_crash(t1)
        for rid in [r for r, q in self.live.items()
                    if len(q.tokens) >= q.out_len]:
            req = self.live.pop(rid)
            with self.rec.span("engine.finish_request"):
                self.eng.finish_request(rid)
            req.done = True
            self.free_at[req.client] = time.perf_counter()

    def crash_due(self) -> bool:
        after = self.mix.crash_after
        return (after is not None and bool(self.live)
                and all(len(r.tokens) >= after for r in self.live.values())
                and any(r.crashes == 0 for r in self.live.values()))

    def crash_and_recover(self) -> None:
        self._crash_lens = {rid: len(r.prompt) + len(r.tokens)
                            for rid, r in self.live.items()}
        self._crash_t0 = time.perf_counter()
        with self.rec.span("engine.crash"):
            self.eng.crash()
        with self.rec.span("engine.recover"):
            self.eng.recover()
        self.crashes.append({"t0": self._crash_t0,
                             "recovered": time.perf_counter(),
                             "report": self.eng.last_recovery})
        for r in self.live.values():
            r.crashes += 1

    def _after_crash(self, t1: float) -> None:
        """The first step after a recovery: its end is the first token of
        every session live at the crash; then read back what was
        acknowledged before the crash."""
        self.crashes[-1]["first_token"] = t1
        self.crashes[-1]["ttft_s"] = t1 - self._crash_t0
        self._crash_t0 = None
        for rid, n in self._crash_lens.items():
            self.read_back(self.live[rid], n)

    def read_back(self, req: Request, n: int) -> None:
        """Compare the first ``n`` tokens of the request's persisted log
        (its table entry gives the slot and length) with the prompt and
        the tokens the engine acknowledged."""
        from repro.serve.engine import V_SLOT, V_TLEN

        want = np.concatenate([req.prompt, np.asarray(req.tokens,
                                                      np.int64)])[:n]
        self.readback_tokens += n
        ok, val = self.eng.table.find_batch(np.asarray([req.rid], np.int64))
        if not ok[0] or int(val[0, V_TLEN]) < n:
            self.readback_errors += n
            return
        slot = np.asarray([int(val[0, V_SLOT])], np.int64)
        got = np.asarray(self.eng.tok_region.read_at(slot, slice(0, n))[0],
                         np.int64)
        self.readback_errors += int((got != want).sum())

    # -- loops -----------------------------------------------------------
    def run(self, until: float) -> None:
        """Serve until the host clock passes ``until`` (checked between
        steps)."""
        while time.perf_counter() < until:
            with self.rec.span("harness"):
                self.admit_free()
            if self.crash_due():
                self.crash_and_recover()
            self.step()

    def warm_up(self) -> None:
        """Use every program the window will use once: a prefill per
        prompt length (the first block holds every length when the
        clients outnumber the lengths), a decode step, and, where the mix
        crashes, a whole crash and recovery at the window's sizes."""
        self.admit_free()
        self.step()
        if self.mix.crash_after is not None:
            while not self.crashes or "first_token" not in self.crashes[-1]:
                self.admit_free()
                if self.crash_due():
                    self.crash_and_recover()
                self.step()

    def read_back_live(self) -> None:
        for req in self.live.values():
            self.read_back(req, len(req.prompt) + len(req.tokens))
