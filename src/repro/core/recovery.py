"""Unified recovery subsystem (paper §V-F): vectorized chain primitives +
a dependency-ordered RecoveryManager that times every rebuild stage.

The paper's bargain is two-sided: persist fewer fields at write time, pay
to *recreate* them after a crash.  The write side batches through one
layer (core/writeset.py); this module is its mirror for the read side —
every crash-recovery path (pstruct structures, the serving engine, the
paged-KV allocator, the checkpoint manager) routes through it:

* ``chain_order`` / ``chain_lengths`` / ``chain_walk`` — shared vectorized
  pointer-jumping primitives (NumPy; Pallas variants live in
  ``kernels/chain_order.py``).  They replace the per-structure scalar
  ``while cur != NULL`` walks: recovery of a million-entry structure
  runs at hardware speed, not at Python-loop speed.  Two strategies sit
  behind one ``method=`` switch (DESIGN.md §8): pointer DOUBLING
  (binary-lifting tables, O(N log N), unbeatable while the tables fit
  in cache) and contraction-based LIST RANKING (sample every k-th row
  as a spine node, local-walk each spine segment, rank the ~N/k
  contracted chain with the same doubling tables, expand — O(N) gathers
  plus an O(N/k·log(N/k)) in-cache rank, which is what keeps the 10**6+
  chains of the north-star serving workload off the jump-table cache
  cliff).  ``method="auto"`` picks doubling below ``CONTRACT_MIN_N``
  and contraction at or above it.
* ``RecoveryManager`` — structures register their *pure* reconstructors
  (``core/reconstruct.py`` registry) under a name with declared
  dependencies (e.g. the serving engine depends on the request hashmap
  and the LRU page list).  ``recover()`` reopens the arenas once, does
  the generation/validity check once, runs the reconstructors in
  topological order, and times each stage into a ``RecoveryReport`` —
  the §V-F reconstruction-time metric, measured per stage.

``recover(concurrency=N)`` schedules stages by per-stage DEPENDENCY
COUNTERS in one thread pool: every stage starts the moment ITS OWN
declared dependencies land — not when its whole topological level does
(the level barrier the first concurrent implementation used; DESIGN.md
§7 has the scheduler diagram).  Recovery wall time approaches the
critical path over the dependency DAG instead of the serial stage sum
(the report carries all three — ``wall_ms`` / ``critical_path_ms`` /
``total_ms`` — and each StageReport carries ``ready_at``, the moment
its dependencies were satisfied, so queue wait and run time read
separately off the report).  Stage-completion callbacks
(``recover(on_stage=...)`` or ``add_listener``) fire the moment a stage
lands, which is how the serving engine admits traffic per slot before
the full report exists (DESIGN.md §6, "Concurrent recovery &
admission").  Sharded arenas reopen their shards in a pool of the same
width before any stage runs.

Reconstructors must be pure given the loaded persistent state: same
bytes => identical rebuilt volatile redundancy, which the torn-epoch
crash tests assert at every epoch boundary (tests/test_recovery.py)
and the crash-point fuzzer re-asserts through recover-crash-recover
double failures (tests/test_async_recovery.py) — purity is exactly
what makes a crash *during* recovery harmless.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core import reconstruct
from repro.core.arena import IntegrityError

NULL = -1

__all__ = [
    "NULL", "chain_order", "chain_lengths", "chain_walk", "jump_tables",
    "chain_method", "ChainSnapshot", "CONTRACT_K", "CONTRACT_MIN_N",
    "CONTRACT_MIN_COUNT",
    "StageReport", "RecoveryReport", "Recoverable", "RecoveryManager",
]

# ----------------------------------------------------------------------
# Method selection (DESIGN.md §8).  Doubling's working set is its
# (bits, n) jump tables — past the cache it loses even to the scalar
# walk (the BENCH_recovery.json crossover this module used to report
# honestly at 10**6).  Contraction's working set is the ~n/k contracted
# chain; its full-array passes are O(n) total gathers, so it scales
# through the crossover.  The threshold is the measured flip point on
# the reference host (contraction wins from ~10**5 up; doubling keeps a
# small edge below, where its tables still fit and its fixed costs are
# lower), and CONTRACT_MIN_COUNT keeps tiny explicit-count walks — a
# handful of table levels — on the doubling path.
CONTRACT_K = 32              # spine sampling stride (id % k == 0)
CONTRACT_MIN_N = 1 << 17     # auto: contract at/above this table size
CONTRACT_MIN_COUNT = 32      # auto: explicit counts below stay doubling
_CONTRACT_WALK_HEADS = 64    # chain_walk: contract only for few heads
_WALK_ESCALATE_ROUNDS = 128  # chain_walk auto: level-sync rounds before
                             # escalating to contraction (chains proven
                             # longer than this pay the restart; short
                             # ones — the hashmap unlink — never do)


def chain_method(n: int, count: Optional[int] = None,
                 method: str = "auto") -> str:
    """Resolve a chain-primitive ``method=`` argument to "double" or
    "contract" (the auto heuristic, exported so recovery reports can
    name the path a rebuild actually took)."""
    if method != "auto":
        if method not in ("double", "contract"):
            raise ValueError(f"unknown chain method {method!r}")
        return method
    if n >= CONTRACT_MIN_N and (count is None or count >= CONTRACT_MIN_COUNT):
        return "contract"
    return "double"


# ======================================================================
# Vectorized chain primitives (pointer doubling / binary lifting)
# ======================================================================

def jump_tables(nxt: np.ndarray, bits: int) -> np.ndarray:
    """(bits, n) binary-lifting tables: ``jump[k][i]`` = node 2**k hops
    after i along ``nxt`` (NULL-absorbing).  A pointer outside [0, n) is
    a terminator, like NULL — recovery slices ``nxt`` at the committed
    fresh-water mark, so a link flushed by a torn epoch into uncommitted
    territory ends the chain instead of faulting.

    Tables are int32: node ids are region row indices (< 2**31), and
    halving the table bytes keeps the doubling gathers in cache — the
    difference between beating and losing to the scalar walk at 10**6
    entries (see BENCH_recovery.json)."""
    n = nxt.shape[0]
    jump = np.empty((bits, n), np.int32)
    jump[0] = np.where((nxt >= 0) & (nxt < n), nxt, NULL)
    for k in range(1, bits):
        prev_j = jump[k - 1]
        safe = np.where(prev_j >= 0, prev_j, 0)
        jump[k] = np.where(prev_j >= 0, prev_j[safe], NULL)
    return jump


def _sanitize32(nxt: np.ndarray) -> np.ndarray:
    """OOB pointers -> NULL, narrowed to int32 AFTER the 64-bit range
    check (a torn 2**32+3 must terminate, not alias node 3).  int32
    halves the bytes every random gather touches."""
    n = nxt.shape[0]
    return np.where((nxt >= 0) & (nxt < n), nxt, NULL).astype(np.int32)


def _absorb(jump: np.ndarray, cnt: np.ndarray,
            heads: np.ndarray) -> np.ndarray:
    """Pointer-doubling absorb: after r rounds ``jump[i]`` = node
    min(2**r, L(i)) hops after i (NULL once the chain ran out) and
    ``cnt[i]`` = the counts of those nodes summed, so 2**rounds > n
    rounds yield exact chain totals.  Seeding ``cnt`` with ones counts
    nodes (chain_lengths); seeding it with segment weights sums a
    contracted chain's hop counts (the list-ranking rank step).  Raises
    on a cycle reachable from ``heads`` (it never absorbs).  Pure:
    every round rebinds, the caller's arrays are never written."""
    n = jump.shape[0]
    for _ in range(max(1, int(n).bit_length())):   # 2**rounds > n
        live = jump >= 0
        if not live.any():
            break
        safe = np.where(live, jump, 0)
        cnt = cnt + np.where(live, cnt[safe], 0)
        jump = np.where(live, jump[safe], NULL)
    if (jump[heads] >= 0).any():
        raise RuntimeError("cycle in chain")
    return cnt[heads]


def chain_lengths(nxt: np.ndarray, heads: np.ndarray, *,
                  method: str = "auto",
                  k: Optional[int] = None) -> np.ndarray:
    """Length of the NULL-terminated chain starting at each head.

    Doubling: the `_absorb` invariant over the full array, O(n log n)
    work, fully vectorized — the parallel analogue of the seed's
    sequential ``_chain_len`` walk.  Contraction: local-walk the ~n/k
    spine segments (every head is promoted to a spine node), then
    `_absorb` the contracted chain seeded with segment weights —
    O(n) gathers + an in-cache rank.  Both raise on cycles (a cycle
    never absorbs into NULL, so its count exceeds n)."""
    heads = np.asarray(heads, np.int64)
    n = nxt.shape[0]
    if n == 0 or heads.size == 0:
        return np.zeros(heads.shape, np.int64)
    out = np.zeros(heads.shape, np.int64)
    # heads outside [0, n) are terminated chains (length 0), per the
    # module-wide OOB-pointer contract
    ok = (heads >= 0) & (heads < n)
    if chain_method(n, None, method) == "contract":
        nxt32 = _sanitize32(np.asarray(nxt))
        spine, spine_pos, cnext, w = _contract(nxt32, heads[ok],
                                               k or CONTRACT_K)
        lens = _absorb(cnext, w, spine_pos[heads[ok]])
        if (lens > n).any():
            # a poisoned (spine-free-cycle) segment on some head's chain
            raise RuntimeError("cycle in chain")
        out[ok] = lens
        return out
    # int32 working arrays for the same cache reasons as jump_tables
    jump = _sanitize32(np.asarray(nxt))
    out[ok] = _absorb(jump, np.ones(n, np.int32), heads[ok])
    return out


class ChainSnapshot:
    """A candidate node order seeded from a committed incremental order
    snapshot (DESIGN.md §10), handed to ``chain_order(snapshot=...)``.

    The candidate is NEVER trusted: adoption requires one O(count)
    vectorized verification pass against the committed NEXT chain —
    ``cand[0] == head`` and ``nxt[cand[i]] == cand[i+1]`` for every
    position.  NEXT is a function of the node id, so a candidate that
    verifies is *mathematically* the chain_order output: bit-identical
    recovery whether the snapshot was used or not, in every torn-write
    scenario the crash fuzzer can produce.  Any mismatch (torn snapshot
    record, stale ring rows, crash inside the commit window) silently
    falls back to the full contraction/doubling rank.

    ``outcome`` is filled by chain_order — "snapshot" on adoption, else
    the fallback method name ("contract"/"double") — and ``replayed``
    is the suffix length the seed had to local-walk (set by the
    structure that built the candidate; reset to the full count on
    fallback), which is what RecoveryManager stage details report."""

    def __init__(self, candidate: np.ndarray, replayed: int = 0):
        self.candidate = np.asarray(candidate, np.int64).ravel()
        self.replayed = int(replayed)
        self.outcome: Optional[str] = None


def _snapshot_verify(nxt: np.ndarray, head: int, count: Optional[int],
                     cand: np.ndarray) -> bool:
    """True iff `cand` IS chain_order(nxt, head, count) — one pass of
    O(count) vectorized gathers, no scalar loop."""
    if count is None or cand.size != count:
        return False
    n = nxt.shape[0]
    if int(cand[0]) != int(head):
        return False
    if ((cand < 0) | (cand >= n)).any():
        return False
    if count > 1 and not np.array_equal(
            np.asarray(nxt)[cand[:-1]], cand[1:]):
        return False
    return True


def chain_order(nxt: np.ndarray, head: int, count: Optional[int] = None,
                *, method: str = "auto", k: Optional[int] = None,
                snapshot: Optional[ChainSnapshot] = None) -> np.ndarray:
    """node-at-position for positions 0..count-1.

    ``count=None`` derives the length first (one lifting descent off the
    doubling tables, or the contracted rank — cycle-detected either
    way); recovery paths that persist an explicit count (the DLL header)
    pass it instead — a stale-but-committed count then bounds the walk
    to the committed prefix, which is exactly the torn-epoch recovery
    guarantee.

    ``method`` — "double" (binary lifting, O(N log N) fully vectorized),
    "contract" (sample/contract/rank/expand list ranking, O(N) gathers +
    an O(N/k log(N/k)) in-cache rank), or "auto" (`chain_method`).

    A head outside [0, n) — NULL, or a HEAD field flushed by a torn
    epoch past the committed fresh-water mark — is a terminated chain:
    empty order, per the module-wide OOB-pointer contract."""
    n = nxt.shape[0]
    if head < 0 or head >= n:
        return np.empty(0, np.int64)
    if count == 0:
        return np.empty(0, np.int64)
    if snapshot is not None:
        if _snapshot_verify(nxt, head, count, snapshot.candidate):
            snapshot.outcome = "snapshot"
            return snapshot.candidate.copy()
        # verification failed: the snapshot lied about the committed
        # chain — fall back to the full rank and report it
        snapshot.outcome = chain_method(n, count, method)
        snapshot.replayed = int(count or 0)
    if chain_method(n, count, method) == "contract":
        return _order_contract(np.asarray(nxt), head, count,
                               k or CONTRACT_K)
    if count is None:
        # tables deep enough to absorb any valid chain; the length
        # derivation below and the position walk share this ONE build
        bits = max(1, int(n).bit_length())       # 2**bits > n
    else:
        bits = max(1, int(np.ceil(np.log2(max(count, 2)))))
    jump = jump_tables(np.asarray(nxt, np.int64), bits)
    if count is None:
        # read the length off the tables: descend from the top bit,
        # taking every jump that does not absorb — the hop count is the
        # tail position
        cur, tail_pos = head, 0
        for b in reversed(range(bits)):
            nb = int(jump[b][cur])
            if nb != NULL:
                tail_pos += 1 << b
                cur = nb
        count = tail_pos + 1
        if count > n:
            raise RuntimeError("cycle in chain")
    # int32 throughout the position walk (row ids < 2**31): mixed-dtype
    # masked gathers cost ~3x at 10**6 entries.  Only the low
    # (count-1).bit_length() table levels can set a position bit, so the
    # walk skips the deeper levels a count=None derivation built.
    pos = np.arange(count, dtype=np.int32)
    cur = np.full(count, head, np.int32)
    dead = np.zeros(count, bool)   # absorbed into NULL: count overran
    for b in range(min(bits, int(count - 1).bit_length())):
        m = ((pos >> b) & 1 == 1) & ~dead
        if m.any():
            cur[m] = jump[b][cur[m]]
            dead |= cur == NULL
    if dead.any():
        # an explicit count larger than the chain: fail loudly instead
        # of letting NULL wrap around as a numpy negative index
        raise ValueError("count exceeds chain length")
    return cur.astype(np.int64)


def chain_walk(nxt: np.ndarray, heads: np.ndarray, *,
               method: str = "auto",
               k: Optional[int] = None) -> np.ndarray:
    """Materialize many chains at once: (H, Lmax) member matrix, row h =
    nodes of the chain starting at heads[h] in order, NULL-padded.

    Level-synchronous by default — one vectorized round per chain
    *position*, all chains advanced together (the batched-probe idiom
    from hashmap._find_slots), so rounds = max chain length, not total
    nodes.  That is the right shape for many short chains (the hashmap's
    bucket unlink); for a FEW chains over a huge table (rounds = chain
    length, each round a tiny gather) the contraction path ranks all
    chains off one shared contraction instead.  "auto" ESCALATES rather
    than guesses — chain length isn't knowable up front, and routing a
    short-chain unlink on a big table to contraction's O(n) passes
    would regress the serving hot path — so it walks level-sync and
    restarts on the contraction path only once the chains have proven
    longer than _WALK_ESCALATE_ROUNDS (the discarded rounds are a few
    tiny gathers; the escalated case saves full-chain-length rounds)."""
    heads = np.asarray(heads, np.int64)
    n = nxt.shape[0]
    if method != "auto":
        method = chain_method(n, None, method)   # validates the string
    if method == "contract":
        return _walk_contract(np.asarray(nxt), heads, k or CONTRACT_K)
    escalate = (method == "auto" and n >= CONTRACT_MIN_N
                and 0 < heads.size <= _CONTRACT_WALK_HEADS)
    cols: List[np.ndarray] = []
    cur = np.where((heads >= 0) & (heads < n), heads, NULL)
    while (cur != NULL).any():
        if escalate and len(cols) >= _WALK_ESCALATE_ROUNDS:
            return _walk_contract(np.asarray(nxt), heads, k or CONTRACT_K)
        cols.append(cur.copy())
        safe = np.where(cur != NULL, cur, 0)
        cur = np.where(cur != NULL, nxt[safe], NULL)
        cur = np.where((cur >= 0) & (cur < n), cur, NULL)
        if len(cols) > n:
            raise RuntimeError("cycle in chain")
    if not cols:
        return np.empty((heads.shape[0], 0), np.int64)
    return np.stack(cols, axis=1)


# ======================================================================
# Contraction-based list ranking (sample / contract / rank / expand)
# ======================================================================

def _contract(nxt32: np.ndarray, extra_heads: np.ndarray, k: int
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Sample + local-walk steps of the list ranking (DESIGN.md §8).

    Spine nodes are every row with ``id % k == 0`` plus every in-range
    head (deterministic — no RNG in a recovery path, and the device
    variant can test membership with arithmetic alone).  Each spine
    node's SEGMENT is itself plus the non-spine nodes after it, up to
    the next spine node or the chain end; the local walk advances all
    segments together, retiring lanes as they arrive (compacted each
    round, so total gather work is O(n) — the sum of segment lengths —
    not rounds x lanes).

    Returns ``(spine, spine_pos, cnext, w)``: spine row ids, the (n,)
    id -> spine-index map (NULL off-spine), the contracted next pointer
    (spine-index space, NULL-terminated) and the segment weights
    (nodes per segment).  A cycle that contains a spine node shows up
    as a cycle in ``cnext`` (the rank step detects it); a spine-FREE
    cycle would spin the local walk forever, so after n rounds the
    stuck lanes are closed with a POISON weight of n+1 — any length
    summed through them exceeds n, which is exactly the condition the
    callers already treat as "cycle in chain".  Walks that never need
    the poisoned segment (an explicit committed count that stops short
    of torn territory) stay unaffected, matching the doubling path."""
    n = nxt32.shape[0]
    spine = np.arange(0, n, k, dtype=np.int64)
    extra = extra_heads[(extra_heads >= 0) & (extra_heads < n)]
    extra = np.unique(extra[extra % k != 0])
    if extra.size:
        spine = np.concatenate([spine, extra])
    S = spine.size
    spine_pos = np.full(n, NULL, np.int32)
    spine_pos[spine] = np.arange(S, dtype=np.int32)
    cnext = np.full(S, NULL, np.int32)
    w = np.ones(S, np.int64)
    lanes = np.arange(S)
    cur = nxt32[spine]
    for _ in range(n + 1):       # a legit segment closes within n hops
        if not lanes.size:
            break
        alive = cur >= 0
        sp = np.full(lanes.size, NULL, np.int32)
        sp[alive] = spine_pos[cur[alive]]
        arrived = sp >= 0
        if arrived.any():
            cnext[lanes[arrived]] = sp[arrived]
        keep = alive & ~arrived
        lanes = lanes[keep]
        cur = cur[keep]
        if lanes.size:
            w[lanes] += 1
            cur = nxt32[cur]
    if lanes.size:               # spine-free cycle: poison, don't raise
        w[lanes] = n + 1
    return spine, spine_pos, cnext, w


def _rank_expand(nxt32: np.ndarray, spine: np.ndarray, cjump: np.ndarray,
                 w: np.ndarray, hpos: int, count: int) -> np.ndarray:
    """Rank + expand steps: order of the chain starting at spine index
    ``hpos``, positions 0..count-1.

    Rank: ``cjump`` — the EXISTING binary-lifting tables, built ONCE by
    the caller over the contracted chain (a (bits, S) working set that
    stays in cache, shared across heads in the multi-head walk) — walks
    spine-at-contracted-position exactly like chain_order's position
    walk; the exclusive cumsum of segment weights turns contracted
    positions into global start positions.  Expand: re-walk only the
    segments whose start lands inside [0, count) — emitting straight
    into the output, so total work is count gathers + count scatters."""
    S = cjump.shape[1]
    cap = min(count, S)
    pos = np.arange(cap, dtype=np.int32)
    curq = np.full(cap, hpos, np.int32)
    dead = np.zeros(cap, bool)
    for b in range(min(cjump.shape[0], int(cap - 1).bit_length())):
        m = ((pos >> b) & 1 == 1) & ~dead
        if m.any():
            curq[m] = cjump[b][curq[m]]
            dead |= curq == NULL
    wq = np.where(dead, 0, w[np.where(dead, 0, curq)])
    g = np.concatenate([[0], np.cumsum(wq)[:-1]])   # global start of q
    use = ~dead & (g < count)
    starts = g[use]
    take = np.minimum(wq[use], count - starts)
    if int(take.sum()) != count:
        # the contracted chain ran out before covering count positions —
        # same contract as the doubling walk's dead check
        raise ValueError("count exceeds chain length")
    out = np.empty(count, np.int64)
    cur = spine[curq[use]].astype(np.int32)
    posn = starts.copy()
    rem = take.copy()
    while cur.size:
        out[posn] = cur
        rem -= 1
        kp = rem > 0
        cur = nxt32[cur[kp]]
        posn = posn[kp] + 1
        rem = rem[kp]
    return out


def _order_contract(nxt: np.ndarray, head: int, count: Optional[int],
                    k: int) -> np.ndarray:
    """chain_order via contraction: the full sample / contract / rank /
    expand pipeline for one head (head already validated in-range)."""
    n = nxt.shape[0]
    nxt32 = _sanitize32(nxt)
    spine, spine_pos, cnext, w = _contract(
        nxt32, np.asarray([head], np.int64), k)
    hpos = int(spine_pos[head])
    if count is None:
        count = int(_absorb(cnext, w, np.asarray([hpos]))[0])
        if count > n:
            raise RuntimeError("cycle in chain")
    cjump = _contract_tables(cnext, min(count, spine.shape[0]))
    return _rank_expand(nxt32, spine, cjump, w, hpos, count)


def _contract_tables(cnext: np.ndarray, cap: int) -> np.ndarray:
    """Binary-lifting tables over the contracted chain, deep enough for
    a position walk of ``cap`` contracted positions."""
    bits = max(1, int(np.ceil(np.log2(max(cap, 2)))))
    return jump_tables(cnext.astype(np.int64), bits)


def _walk_contract(nxt: np.ndarray, heads: np.ndarray,
                   k: int) -> np.ndarray:
    """chain_walk via ONE shared contraction: every head is a spine
    node, so each chain's rank+expand reads the same contracted tables
    (built once, deep enough for the longest chain); the per-head
    Python loop runs over the FEW heads this path is selected for,
    each iteration fully vectorized."""
    n = nxt.shape[0]
    nxt32 = _sanitize32(nxt)
    spine, spine_pos, cnext, w = _contract(nxt32, heads, k)
    ok = (heads >= 0) & (heads < n)
    lens = np.zeros(heads.shape, np.int64)
    lens[ok] = _absorb(cnext, w, spine_pos[heads[ok]])
    if (lens > n).any():
        raise RuntimeError("cycle in chain")
    lmax = int(lens.max()) if lens.size else 0
    out = np.full((heads.shape[0], lmax), NULL, np.int64)
    if lmax:
        cjump = _contract_tables(cnext, min(lmax, spine.shape[0]))
        for h in range(heads.shape[0]):
            if lens[h]:
                out[h, :lens[h]] = _rank_expand(
                    nxt32, spine, cjump, w,
                    int(spine_pos[heads[h]]), int(lens[h]))
    return out


# ======================================================================
# Recovery reports
# ======================================================================

@dataclass
class StageReport:
    """One timed rebuild stage (§V-F reconstruction-time row).

    ``t_start`` / ``t_end`` are wall-clock offsets (seconds) from the
    start of the recovery pass, so a concurrent recovery's timeline can
    be read off the report: overlapping [t_start, t_end) intervals are
    stages that ran in parallel.  ``ready_at`` is the offset at which
    the stage's declared dependencies were all satisfied — the moment
    the dependency-counter scheduler queued it — so
    ``t_start - ready_at`` is pure queue wait (pool contention), split
    from run time in BENCH_recovery.json."""
    name: str
    seconds: float
    detail: Dict[str, Any] = field(default_factory=dict)
    t_start: float = 0.0
    t_end: float = 0.0
    ready_at: float = 0.0
    # Salvage-mode outcome (DESIGN.md §13): ``quarantined`` — the stage
    # tripped on media corruption and its structure is untrusted;
    # ``degraded`` — the stage ran on partial inputs (a dependency was
    # quarantined) or salvaged around corrupt rows itself.
    quarantined: bool = False
    degraded: bool = False

    @property
    def queue_wait(self) -> float:
        return max(0.0, self.t_start - self.ready_at)

    def as_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "seconds": self.seconds,
                "t_start": self.t_start, "t_end": self.t_end,
                "ready_at": self.ready_at, "queue_wait": self.queue_wait,
                "quarantined": self.quarantined, "degraded": self.degraded,
                **self.detail}


@dataclass
class RecoveryReport:
    """Per-stage timing + validity of one recovery pass.  Produced by
    RecoveryManager and by ckpt.CheckpointManager.restore — the one
    report format every recovery path shares.

    Three times tell the concurrency story:

    * ``total_ms``         — summed per-stage seconds (serial work);
    * ``critical_path_ms`` — longest dependency chain (the floor any
      concurrency can reach);
    * ``wall_ms``          — what this pass actually took.

    ``total_seconds`` remains the wall-clock duration of the pass
    (``wall_ms / 1000``) for existing call sites.

    A stage's seconds are host time.  Where a reconstructor dispatches
    device work, its stage ends at dispatch, not when the device is
    done: the serving engine's ``engine`` stage ends once its grouped
    re-prefills and cache scatters are enqueued, and the benchmark's
    ``recovery.rebuild_device_ms`` reads their device time from a trace.
    No stage waits for the device, which would add a sync to the first
    token after a crash.  Under the JAX profiler each stage is also a
    ``recover.<name>`` span (``repro.obs``) over the same interval."""
    valid: bool = True
    generation: int = 0
    total_seconds: float = 0.0
    concurrency: int = 1
    critical_path_seconds: float = 0.0
    stages: List[StageReport] = field(default_factory=list)
    # salvage mode (DESIGN.md §13): stage names that tripped on media
    # corruption / ran degraded on partial inputs during this pass
    quarantined: List[str] = field(default_factory=list)
    degraded: List[str] = field(default_factory=list)

    @property
    def wall_ms(self) -> float:
        return self.total_seconds * 1e3

    @property
    def total_ms(self) -> float:
        return sum(s.seconds for s in self.stages) * 1e3

    @property
    def critical_path_ms(self) -> float:
        return self.critical_path_seconds * 1e3

    def add(self, name: str, seconds: float, **detail: Any) -> "StageReport":
        st = StageReport(name, seconds, dict(detail))
        self.stages.append(st)
        return st

    def stage(self, name: str) -> Optional[StageReport]:
        for st in self.stages:
            if st.name == name:
                return st
        return None

    def seconds(self, name: str) -> float:
        st = self.stage(name)
        return st.seconds if st is not None else 0.0

    def as_dict(self) -> Dict[str, Any]:
        return {"valid": self.valid, "generation": self.generation,
                "total_seconds": self.total_seconds,
                "concurrency": self.concurrency,
                "wall_ms": self.wall_ms, "total_ms": self.total_ms,
                "critical_path_ms": self.critical_path_ms,
                "quarantined": list(self.quarantined),
                "degraded": list(self.degraded),
                "stages": [s.as_dict() for s in self.stages]}


# ======================================================================
# RecoveryManager
# ======================================================================

@dataclass(frozen=True)
class Recoverable:
    name: str
    reconstructor: str          # name in the core.reconstruct registry
    target: Any                 # object handed to the reconstructor
    depends: Tuple[str, ...] = ()
    # Arena regions the reconstructor reads (beyond what its `depends`
    # already rebuilt).  On a SHARDED arena, declared regions become
    # per-region load stages in the dependency-counter scheduler: this
    # stage starts the moment ITS regions are loaded, overlapping the
    # other regions' shard loads with its rebuild (DESIGN.md §7).
    # None = unknown (conservative: waits for every load); () = reads no
    # regions directly (only its dependencies' outputs).
    regions: Optional[Tuple[str, ...]] = None


class RecoveryManager:
    """Dependency-ordered, timed crash recovery.

    Usage::

        mgr = RecoveryManager(engine.arena, paging.arena)
        mgr.add("req_table", "pstruct.hashmap", engine.table)
        mgr.add("lru", "pstruct.dll", paging.lru)
        mgr.add("pages", "serve.paged_alloc", paging, depends=("lru",))
        mgr.add("engine", "serve.engine", engine,
                depends=("req_table", "pages"))
        report = mgr.recover()

    ``recover()`` reopens every arena once (the generation/validity check
    happens here, not in each structure), then runs the registered pure
    reconstructors in topological order, timing each into the report.
    ``recover(concurrency=N)`` runs the independent stages of each
    topological level in a thread pool of N workers; the report's stage
    list stays in deterministic (level-major, registration) order no
    matter which thread finished first, so serial and concurrent passes
    produce equivalent reports modulo timing fields.
    """

    def __init__(self, *arenas: Any):
        # dedupe by identity: callers pass each structure's arena and
        # several structures often share one (e.g. the engine's table
        # and allocator) — a duplicate would reopen it twice and count
        # its block-fault deltas twice
        seen: set = set()
        self.arenas = []
        for a in arenas:
            if a is not None and id(a) not in seen:
                seen.add(id(a))
                self.arenas.append(a)
        self._items: Dict[str, Recoverable] = {}
        self._listeners: List[Callable[[StageReport], None]] = []

    # ------------------------------------------------------------- setup
    def add(self, name: str, reconstructor: str, target: Any,
            depends: Sequence[str] = (),
            regions: Optional[Sequence[str]] = None) -> "RecoveryManager":
        if name in self._items:
            raise ValueError(f"recoverable {name!r} already registered")
        if reconstructor not in reconstruct.names():
            raise KeyError(f"unknown reconstructor {reconstructor!r}")
        self._items[name] = Recoverable(
            name, reconstructor, target, tuple(depends),
            tuple(regions) if regions is not None else None)
        return self

    def add_listener(self, fn: Callable[[StageReport], None]
                     ) -> "RecoveryManager":
        """Register a stage-completion callback: ``fn(stage_report)`` is
        invoked the moment each stage (including "reopen") lands — from
        the completing worker thread under ``recover(concurrency>1)``,
        serialized by the manager's lock either way."""
        self._listeners.append(fn)
        return self

    def levels(self) -> List[List[str]]:
        """Topological *levels* over declared dependencies: level k holds
        every item whose dependencies all sit in levels < k, stable in
        registration order within a level.  Items of one level are
        mutually independent — the unit of stage concurrency."""
        items = self._items
        for it in items.values():
            for dep in it.depends:
                if dep not in items:
                    raise KeyError(
                        f"recoverable {it.name!r} depends on unregistered "
                        f"{dep!r}")
        done: set = set()
        out: List[List[str]] = []
        pending = list(items)
        while pending:
            ready = [n for n in pending
                     if all(d in done for d in items[n].depends)]
            if not ready:
                raise ValueError(f"dependency cycle among {pending}")
            out.append(ready)
            done.update(ready)
            pending = [n for n in pending if n not in done]
        return out

    def order(self) -> List[str]:
        """Topological order over declared dependencies, stable in
        registration order among ready items (levels, flattened)."""
        return [n for level in self.levels() for n in level]

    # ----------------------------------------------------------- recover
    def recover(self, reopen: bool = True, concurrency: int = 1,
                on_stage: Optional[Callable[[StageReport], None]] = None,
                salvage: bool = False) -> RecoveryReport:
        """``salvage=True`` (DESIGN.md §13) turns media corruption from
        a recovery abort into degraded-mode recovery: a stage that trips
        on an ``IntegrityError`` is QUARANTINED (reported, not raised),
        its transitive dependents are skipped as DEGRADED, and every
        structure off the corrupt dependency chain still rebuilds.
        Reconstructors see ``arena._salvage == True`` for the duration
        and may verify their own regions / drop provably-corrupt rows,
        reporting ``degraded`` / ``quarantined`` through their detail
        dict.  Default recovery stays trusting — detection is scrub's
        and the paged fault path's job, not the hot recovery path's."""
        t_all = time.perf_counter()
        report = RecoveryReport(concurrency=max(1, int(concurrency)))
        lock = threading.Lock()
        listeners = list(self._listeners)
        if on_stage is not None:
            listeners.append(on_stage)

        def emit(st: StageReport) -> None:
            with lock:
                for fn in listeners:
                    fn(st)

        order = self.order()            # validates deps / detects cycles
        items = self._items

        # Sharded arenas: regions a stage DECLARES become per-region
        # load stages, so its rebuild starts the moment its own regions
        # land instead of barriering on the whole reopen (DESIGN.md §7).
        # region name -> every sharded arena's region of that name (two
        # arenas MAY carry same-named regions; the load stage reloads
        # them all, and each arena's reopen excludes exactly the names
        # it contributed)
        split: Dict[str, List[Any]] = {}
        if reopen and any(it.regions for it in items.values()):
            declared = {r for it in items.values() for r in it.regions or ()}
            for a in self.arenas:
                if getattr(a, "n_shards", 1) > 1:
                    for rname, r in a.regions.items():
                        # small regions (headers) load in the prologue:
                        # a sub-ms load isn't worth a scheduler slot,
                        # and a header queued behind bulk loads would
                        # gate its structure's rebuild on THEIR finish
                        if rname in declared and r.nbytes >= 1 << 16:
                            split.setdefault(rname, []).append(r)
        # biggest loads first: a large region usually feeds the longest
        # rebuild, so its load must clear the pool earliest for that
        # rebuild's start time — the quantity the wall clock follows —
        # to beat the serial-reopen baseline
        load_order = sorted(
            split, key=lambda r: (-max(x.nbytes for x in split[r]), r))
        load_names = [f"load:{r}" for r in load_order]

        reopen_secs = 0.0
        if reopen and self.arenas:
            with obs.span("recover.reopen") as sp:
                t0 = time.perf_counter()
                valids = []
                for a in self.arenas:
                    if getattr(a, "n_shards", 1) > 1:
                        # pooled shard reload of whatever the load stages
                        # below don't cover (GIL-releasing block copies)
                        a.reopen(concurrency=report.concurrency,
                                 exclude=tuple(
                                     n for n, rs in split.items()
                                     if any(r.arena is a for r in rs)))
                    else:
                        a.reopen()
                    # garbage header/manifest magic is media corruption
                    # no power loss can produce — fail typed
                    # (ManifestError) before trusting the generation it
                    # claims, salvage or not (with no trustworthy
                    # generation there is no committed prefix to salvage
                    # toward)
                    if hasattr(a, "verify_header"):
                        a.verify_header()
                    valids.append(bool(a.header_valid()))
                reopen_secs = time.perf_counter() - t0
                sp.clock(t0, t0 + reopen_secs)
            st = report.add("reopen", reopen_secs,
                            arenas=len(self.arenas), valid=valids,
                            shards=[getattr(a, "n_shards", 1)
                                    for a in self.arenas])
            st.t_start = t0 - t_all
            st.t_end = st.t_start + reopen_secs
            report.valid = all(valids)
            # the committed (persisted) generation — survives recovery in
            # a fresh process, unlike the in-memory commit counter
            report.generation = max(a.header_generation()
                                    for a in self.arenas)
            emit(st)

        results: Dict[str, StageReport] = {}
        ready_at: Dict[str, float] = {n: reopen_secs for n in load_names}
        # a stage's load prerequisites: its declared regions' load
        # stages; an undeclared (regions=None) stage conservatively
        # waits for every load
        load_deps = {
            n: (load_names if items[n].regions is None
                else [f"load:{r}" for r in items[n].regions if r in split])
            for n in order}
        for n in order:
            if not items[n].depends and not load_deps[n]:
                ready_at[n] = reopen_secs

        # paged arenas (DESIGN.md §12): per-stage block-fault deltas make
        # demand-paged recovery visible — load: stages of paged regions
        # are free resets, and the faults attribute to whichever
        # reconstructor actually touched the blocks.  Under concurrent
        # recovery simultaneous stages share the counters, so per-stage
        # attribution is approximate (the TOTAL across stages is exact).
        caches = [a.cache for a in self.arenas
                  if getattr(a, "cache", None) is not None]

        def _cache_faults() -> int:
            return sum(c.faults for c in caches)

        # salvage bookkeeping: stages whose output is untrusted (they
        # tripped on corruption, or ran downstream of one that did).
        # Mutated inside run_stage BEFORE its future resolves, so both
        # schedulers see a dependency's taint before any dependent runs.
        tainted: set = set()
        if salvage:
            for a in self.arenas:
                a._salvage = True
                for sh in getattr(a, "shards", ()):
                    sh._salvage = True

        def run_stage(name: str) -> StageReport:
            with obs.span("recover." + name) as sp:
                st = timed_stage(name)
                sp.clock(t_all + st.t_start, t_all + st.t_end)
            return st

        def timed_stage(name: str) -> StageReport:
            t0 = time.perf_counter()
            faults0 = _cache_faults() if caches else 0
            bad_deps = sorted(d for d in depends_of.get(name, ())
                              if d in tainted)
            if salvage and bad_deps:
                # skipped, not failed: the stage itself is healthy but
                # its inputs are quarantined — running it would serve
                # reconstructed garbage
                tainted.add(name)
                st = StageReport(name, 0.0,
                                 {"skipped": "quarantined dependency",
                                  "tainted_deps": bad_deps},
                                 t_start=t0 - t_all,
                                 t_end=time.perf_counter() - t_all,
                                 ready_at=ready_at.get(name, reopen_secs),
                                 degraded=True)
                emit(st)
                return st
            try:
                if name.startswith("load:"):
                    regions = split[name[5:]]
                    for region in regions:
                        region.load(concurrency=report.concurrency)
                    secs = time.perf_counter() - t0
                    detail = {"rows": sum(int(r.shape[0]) for r in regions),
                              "shards": int(regions[0].arena.n_shards)}
                else:
                    it = items[name]
                    out, secs = reconstruct.run(it.reconstructor, it.target)
                    detail = dict(out) if isinstance(out, dict) else {}
                    detail.setdefault("reconstructor", it.reconstructor)
            except IntegrityError as e:
                if not salvage:
                    raise
                tainted.add(name)
                t1 = time.perf_counter()
                st = StageReport(name, t1 - t0,
                                 {"error": type(e).__name__,
                                  "message": str(e)},
                                 t_start=t0 - t_all, t_end=t1 - t_all,
                                 ready_at=ready_at.get(name, reopen_secs),
                                 quarantined=True)
                emit(st)
                return st
            # a reconstructor may partially salvage on its own: it drops
            # corrupt rows, keeps the rest, and reports through detail
            quarantined = bool(detail.pop("quarantined", False))
            degraded = bool(detail.pop("degraded", False))
            if quarantined:
                tainted.add(name)
            if caches:
                detail["block_faults"] = _cache_faults() - faults0
            t1 = time.perf_counter()
            st = StageReport(name, secs, detail,
                             t_start=t0 - t_all, t_end=t1 - t_all,
                             ready_at=ready_at.get(name, reopen_secs),
                             quarantined=quarantined, degraded=degraded)
            emit(st)
            return st

        full_order = load_names + order
        depends_of = {n: [] for n in load_names}
        depends_of.update({n: list(items[n].depends) + load_deps[n]
                           for n in order})
        try:
            if report.concurrency == 1:
                # serial: topological order; a stage is "ready" the moment
                # its last dependency finished
                for name in full_order:
                    st = run_stage(name)
                    results[name] = st
                    for m in full_order:
                        if name in depends_of[m]:
                            ready_at[m] = max(ready_at.get(m, 0.0), st.t_end)
            else:
                self._run_counters(full_order, depends_of, run_stage,
                                   results, ready_at, report.concurrency,
                                   t_all)
        finally:
            if salvage:
                for a in self.arenas:
                    a._salvage = False
                    for sh in getattr(a, "shards", ()):
                        sh._salvage = False
        # deterministic report order — loads first, then level-major
        # stages — whatever the completion order was
        report.stages.extend(results[n] for n in full_order
                             if n in results)
        report.quarantined = [s.name for s in report.stages
                              if s.quarantined]
        report.degraded = [s.name for s in report.stages if s.degraded]
        report.total_seconds = time.perf_counter() - t_all
        report.critical_path_seconds = reopen_secs + self._critical_path(
            full_order, depends_of,
            {s.name: s.seconds for s in report.stages})
        return report

    def _run_counters(self, order: List[str], depends_of: Dict[str, List[str]],
                      run_stage, results, ready_at,
                      concurrency: int, t_all: float) -> None:
        """Dependency-counter scheduler: one pool for the whole DAG
        (region-load stages included); a stage is submitted the instant
        its own dependency counter hits zero — no level barrier, so a
        fast chain races ahead of a slow sibling (DESIGN.md §7).
        Dependents of a failed stage are never scheduled; the earliest
        failure (in deterministic topological order) re-raises once
        in-flight stages drain."""
        remaining = {n: len(depends_of[n]) for n in order}
        dependents: Dict[str, List[str]] = {n: [] for n in order}
        for n in order:
            for d in depends_of[n]:
                dependents[d].append(n)
        errors: Dict[str, BaseException] = {}
        # RLock: a future that finishes before its done-callback attaches
        # runs the callback INLINE in the submitting thread, which may
        # already hold the scheduler lock
        done_cv = threading.Condition(threading.RLock())
        outstanding = [0]
        # an inline callback can also fire MID-submission-loop: it runs
        # finished() for the stage just submitted, which may drop a
        # LATER loop stage's counter to zero and submit it before the
        # loop reaches it — the loop's own remaining==0 check would then
        # submit it AGAIN, and the duplicate completion double-decrements
        # its dependents (a stage could start before a sibling dep
        # finished).  `submitted` makes submission idempotent.
        submitted: set = set()

        with ThreadPoolExecutor(max_workers=concurrency) as ex:
            def submit(name: str) -> None:
                if name in submitted:
                    return
                submitted.add(name)
                outstanding[0] += 1
                fut = ex.submit(run_stage, name)
                fut.add_done_callback(
                    lambda f, n=name: finished(n, f))

            def finished(name: str, fut) -> None:
                with done_cv:
                    try:
                        results[name] = fut.result()
                    except BaseException as e:   # noqa: BLE001
                        errors[name] = e
                    now = time.perf_counter() - t_all
                    if name not in errors:
                        for m in dependents[name]:
                            remaining[m] -= 1
                            ready_at[m] = max(ready_at.get(m, 0.0), now)
                            if remaining[m] == 0:
                                submit(m)
                    outstanding[0] -= 1
                    done_cv.notify_all()

            with done_cv:
                for n in order:
                    if remaining[n] == 0:
                        submit(n)
                while outstanding[0] > 0:
                    done_cv.wait()
        if errors:
            raise errors[min(errors, key=order.index)]

    def _critical_path(self, order: List[str],
                       depends_of: Dict[str, List[str]],
                       secs: Dict[str, float]) -> float:
        """Longest dependency-chain sum of stage times — the wall-time
        floor of an infinitely concurrent recovery, region-load stages
        included (excludes the reopen prologue, which is inherently
        serial and added by the caller)."""
        memo: Dict[str, float] = {}
        for name in order:               # deps resolve before dependents
            memo[name] = secs.get(name, 0.0) + max(
                (memo[d] for d in depends_of[name]), default=0.0)
        return max(memo.values(), default=0.0)
