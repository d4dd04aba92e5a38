"""Serving engine: batched decode with partly-persistent session state.

State classification (the paper's contract, applied to serving):
* ESSENTIAL  — request table (Hashmap: rid -> slot/lengths) and the token
  log (prompt + generated tokens per slot), both arena-backed;
* DERIVABLE  — everything on device: KV caches / recurrent states are
  rebuilt by re-prefilling the persisted token log after a crash; the
  paged-LRU metadata reconstructs from its persistent NEXT chain
  (kvcache.PagedAllocator).
Weights are not session state: the engine serves a compute-dtype copy of
the matmul weights (``Model.compute_params``), made once at construction,
and a crash keeps it.

The decode path runs one jit'd program per slot: the slot's cache rows
out at a traced index, a B=1 `decode_step`, the rows back in (fixed batch
slots, slot-contiguous caches; the paged allocator manages page *metadata* —
documented simplification, DESIGN.md §3).  A slot's device state is the
model state after every logged token but the last: the next step feeds
that last token at its own position, so admission and recovery both
prefill ``log[:-1]`` and rebuild exactly the state an uninterrupted run
holds.  Greedy sampling keeps recovery
bit-checkable: tokens generated after recovery must equal an uninterrupted
run, which tests/test_serving.py asserts.

Early traffic admission (DESIGN.md §6): the engine holds a per-slot
readiness bitmap (`slot_ready`).  A crash clears it; recovery re-admits
each slot the moment its grouped re-prefill lands — `step()` decodes
ready slots and skips the rest, and `add_request` only seats new work on
ready slots — so serving resumes at the first admitted group instead of
barriering on the full RecoveryReport.  `on_slot_ready` callbacks fire
per admitted group (slots, prompt length, seconds since recovery start).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import reconstruct as rec
from repro.core.arena import (CorruptLineError, QuarantinedError,
                              journal_enabled, open_arena)
from repro.core.recovery import RecoveryManager, RecoveryReport
from repro.pstruct.dll import _salvage_bad_rows
from repro.models.model import Model
from repro.pstruct.hashmap import H_FRESH as HM_FRESH
from repro.pstruct.hashmap import Hashmap
from repro.serve.journal import (OP_ADMIT, OP_COMPLETE, ST_NEVER,
                                 DuplicateRequestError, RequestJournal,
                                 args_digest)
from repro.serve.kvcache import PagedAllocator, PagedConfig

# request-table value row: (slot, prompt_len, total_len, active, 0, 0, 0)
V_SLOT, V_PLEN, V_TLEN, V_ACTIVE = range(4)


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 4
    s_max: int = 128
    max_requests: int = 64
    mode: str = "partly"          # persistence mode for host structures
    page_tokens: int = 16
    # Shard count of the host persistence substrate (DESIGN.md §7): the
    # token-log slab stripes slot-per-shard, the request hashmap's slab
    # hashes across shards, and the paged-KV metadata arena shards too —
    # recovery re-admits traffic per (shard, prompt-length) group.
    n_shards: int = 1
    # Chain-ranking strategy for every recovery NEXT walk (request-table
    # unlinks, LRU ring scan): doubling vs contraction list ranking
    # (core.recovery.chain_method, DESIGN.md §8)
    chain_method: str = "auto"
    # Incremental order snapshots (DESIGN.md §10) for the request
    # hashmap and the paged-KV LRU: None defers to REPRO_SNAPSHOT,
    # True/False overrides.  Gates TTFT-after-crash — recovery replays
    # only the suffix of rows younger than the newest committed
    # snapshot instead of ranking the whole chain.
    snapshot: Optional[bool] = None
    # Page-pool capacity override (None = the max_batch * s_max /
    # page_tokens working-set minimum).  Capacity planning headroom —
    # and the axis the --snapshot-slo bench grows 10x to show recovery
    # cost tracking the LIVE suffix, not the pool size.
    n_pages: Optional[int] = None
    # Persistent request journal (DESIGN.md §11): one sealed descriptor
    # line per admission/completion rides each epoch's flush, so
    # recovery classifies every request completed / must-retry /
    # never-admitted and refuses duplicate admissions.  None defers to
    # REPRO_JOURNAL, True/False overrides; journal-off layouts are
    # bit-identical to the pre-journal engine.
    journal: Optional[bool] = None
    # Paged regions (DESIGN.md §12): None defers to the REPRO_PAGED env
    # gate (default off).  With paging on, large data regions (the
    # token-log slab, the LRU node slab) keep only a block-cache-bounded
    # volatile working set, and recovery faults blocks on demand.
    paged: Optional[bool] = None
    block_bytes: int = 4096
    cache_blocks: int = 1024


class ServingEngine:
    def __init__(self, model: Model, params, cfg: EngineConfig,
                 arena_path: Optional[str] = None):
        self.model = model
        # matmul weights cast once here, not in every prefill and decode
        self.params, _ = model.compute_params(params)
        self.cfg = cfg
        layout = dict(Hashmap.layout(cfg.max_requests, cfg.mode, name="req",
                                     snapshot=cfg.snapshot))
        # token-log rows stripe slot-per-shard: re-prefill after a crash
        # reads each slot's prompt from its own shard file
        layout["tokens"] = (np.int32, (cfg.max_batch, cfg.s_max),
                            ("seg", 1))
        # journal ring appended LAST: journal-off layouts keep every
        # shared region at its pre-journal offset (bit-identical)
        jr_cap = 4 * cfg.max_requests
        if journal_enabled(cfg.journal):
            layout.update(RequestJournal.layout(jr_cap, name="req"))
        self.arena = open_arena(arena_path, layout, n_shards=cfg.n_shards,
                                paged=cfg.paged, block_bytes=cfg.block_bytes,
                                cache_blocks=cfg.cache_blocks)
        self.table = Hashmap(self.arena, cfg.max_requests, cfg.mode,
                             name="req", chain_method=cfg.chain_method,
                             snapshot=cfg.snapshot)
        # HEAD/TAIL piggyback on the request hashmap's header line
        # (words 4-5, unused by the hashmap), which every admission /
        # completion epoch already marks — journal overhead is exactly
        # the one ring line per epoch (FlushStats.journal_lines)
        self.journal = RequestJournal(
            self.arena, jr_cap, name="req", header=self.table.header) \
            if journal_enabled(cfg.journal) else None
        self.tok_region = self.arena.regions["tokens"]
        self.paging = PagedAllocator(PagedConfig(
            n_pages=max(cfg.n_pages or 0,
                        cfg.max_batch * (cfg.s_max // cfg.page_tokens)),
            page_tokens=cfg.page_tokens, mode=cfg.mode,
            n_shards=cfg.n_shards, chain_method=cfg.chain_method, snapshot=cfg.snapshot,
            paged=cfg.paged, block_bytes=cfg.block_bytes,
            cache_blocks=cfg.cache_blocks))
        # device state (DERIVABLE)
        self.cache = model.init_cache(cfg.max_batch, cfg.s_max)
        self.pos = np.zeros(cfg.max_batch, np.int64)       # per-slot length
        self.slot_rid = np.full(cfg.max_batch, -1, np.int64)
        # slot-granular admission: all ready in steady state; a crash
        # clears the bitmap and recovery re-admits per prefill group
        self.slot_ready = np.ones(cfg.max_batch, bool)
        self.on_slot_ready: Optional[Callable[[np.ndarray, int, float],
                                              None]] = None
        self._cache_lock = threading.Lock()
        # admission events serialize (like manager stage listeners), so
        # check-then-act callbacks stay race-free under pooled prefill;
        # distinct from _cache_lock so a callback may decode (step())
        self._admit_lock = threading.Lock()
        self._recover_concurrency = 1

        def slot_decode_step(params, cache, slot, token, pos):
            # one program a slot: the slot's rows out of the shared tree
            # at a traced index, the B=1 decode, the rows written back
            one = _map_slot(
                cache, cache,
                lambda full, _, ax: jax.lax.dynamic_slice_in_dim(
                    full, slot, 1, axis=ax))
            logits, one = model.decode_step(
                params, one, jnp.full((1,), token, jnp.int32),
                jnp.asarray(pos, jnp.int32))
            cache = _map_slot(
                cache, one,
                lambda full, o, ax: jax.lax.dynamic_update_slice_in_dim(
                    full, o.astype(full.dtype), slot, axis=ax))
            return logits[0], cache

        self._slot_decode = jax.jit(slot_decode_step)

        def prefill(params, batch):
            return model.prefill(params, batch, s_max=cfg.s_max)

        self._prefill = jax.jit(prefill)
        # rid -> device logits of the last step() (read, never synced)
        self.last_logits: Dict[int, jax.Array] = {}
        self.last_recovery: Optional[RecoveryReport] = None
        # rids lost to media corruption in the last salvage recovery:
        # admission refuses them (QuarantinedError) until readmit()
        self.quarantined_rids: set = set()

    # ------------------------------------------------------------------
    def _free_slot(self) -> int:
        for i in range(self.cfg.max_batch):
            if self.slot_rid[i] < 0 and self.slot_ready[i]:
                return i
        raise RuntimeError("no free slots")

    def add_request(self, rid: int, prompt: np.ndarray) -> int:
        if int(rid) in self.quarantined_rids:
            raise QuarantinedError(
                f"request {rid} was lost to media corruption in the last "
                "salvage recovery; readmit() it explicitly to resubmit")
        if self.journal is not None:
            st = self.journal.state_of(rid)
            if st != ST_NEVER:
                raise DuplicateRequestError(
                    f"request {rid} already journaled as {st}")
        slot = self._free_slot()
        plen = len(prompt)
        # ESSENTIAL: token log row + request-table entry (+ journal
        # admission descriptor), one epoch — all or none of it commits
        with self.arena.epoch():
            self.tok_region.write_at(np.asarray([slot], np.int64),
                                     slice(0, plen),
                                     np.asarray(prompt)[None])
            self.tok_region.mark_range(slot, slot + 1)
            val = np.zeros((1, 7), np.int64)
            val[0, :4] = [slot, plen, plen, 1]
            self.table.insert_batch(np.array([rid], np.int64), val)
            self.paging.alloc(rid, -(-plen // self.cfg.page_tokens))
            if self.journal is not None:
                self.journal.log(OP_ADMIT, rid,
                                 digest=args_digest(prompt), info=slot)
            self.arena.commit()
        # DERIVABLE: device state for all but the last prompt token,
        # which the first step() feeds
        self._prefill_slot(slot, np.asarray(prompt)[:-1])
        self.slot_rid[slot] = rid
        self.pos[slot] = plen
        return slot

    def _prefill_slot(self, slot: int, tokens: np.ndarray) -> None:
        self._prefill_slots(np.asarray([slot], np.int64),
                            np.asarray(tokens)[None])

    def _prefill_slots(self, slots: np.ndarray, tokens: np.ndarray) -> None:
        """Prefill a group of slots sharing one prompt length with a
        single batched model call (tokens: (g, plen)), then scatter the
        (g, ...) cache rows into their slots with one indexed device
        update per cache leaf — the grouped re-prefill unit of the
        batched recovery path.  An empty prefix (one-token log) seats the
        zero state every sequence starts from; with meta tokens, it
        prefills them alone."""
        g = len(slots)
        idx = jnp.asarray(slots, jnp.int32)
        if tokens.shape[1] == 0 and not self.model.cfg.meta_tokens:
            kv = self.model.init_cache(g, self.cfg.s_max)
            with self._cache_lock:
                self.cache = _map_slot(
                    self.cache, kv,
                    lambda full, grp, ax: _scatter_batch(full, grp, idx, ax))
            return
        batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
        if self.model.cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (g, self.model.cfg.encoder_seq, self.model.cfg.d_model),
                self.model.compute_dtype)
        if self.model.cfg.family == "vlm":
            batch["context"] = jnp.zeros(
                (g, self.model.cfg.context_seq, self.model.cfg.d_model),
                self.model.compute_dtype)
        _, kv = self._prefill(self.params, batch)
        # the model call above runs lock-free (groups prefill in
        # parallel under recover(concurrency>1)); the read-modify-write
        # scatter of the shared cache tree serializes
        with self._cache_lock:
            self.cache = _map_slot(
                self.cache, kv,
                lambda full, grp, ax: _scatter_batch(
                    full, grp.astype(full.dtype), idx, ax))

    def step(self) -> Dict[int, int]:
        """One greedy decode step for every active slot.  Returns
        {rid: token}.  Per-slot positions differ, so each slot runs its
        own program (``_decode_slot``; compiled once for every slot and
        position).

        The whole step is one persistence epoch: every slot's token-log
        row and table entry flush once at the closing commit, not once
        per slot."""
        out: Dict[int, int] = {}
        self.last_logits = {}
        with obs.span("serve.step"), self.arena.epoch():
            for slot in range(self.cfg.max_batch):
                rid = int(self.slot_rid[slot])
                if rid < 0 or not self.slot_ready[slot]:
                    continue
                p = int(self.pos[slot])
                if p >= self.cfg.s_max:
                    continue
                with obs.span("persist.record", rid):
                    last_tok = int(self.tok_region.read_one(slot, p - 1))
                logits = self._decode_slot(slot, last_tok, p - 1)
                tok = self._host_token(logits, rid)
                # ESSENTIAL: append the generated token + bump lengths
                with obs.span("persist.record", rid):
                    self.tok_region.write_at(np.asarray([slot], np.int64),
                                             p, tok)
                    self.tok_region.mark_range(slot, slot + 1)
                    ok, cur = self.table.find_batch(
                        np.array([rid], np.int64))
                    cur[0, V_TLEN] += 1
                    self.table.insert_batch(np.array([rid], np.int64), cur)
                self.pos[slot] = p + 1
                out[rid] = tok
                self.last_logits[rid] = logits
            self.arena.commit()
        return out

    @staticmethod
    def _host_token(logits, rid: int) -> int:
        """The greedy token on the host: the step path's one read of a
        device value, which waits out the slot's decode.  Every such
        read goes through here, inside a ``serve.sync`` span."""
        with obs.span("serve.sync", rid):
            return int(np.asarray(jnp.argmax(logits)))

    def finish_request(self, rid: int) -> int:
        """Retire a completed request: journal the completion and
        tombstone its table entry in ONE epoch (the COMPLETE descriptor
        and the table removal share the req.header flush line, so they
        commit atomically), then release its pages and slot.  Returns
        the final token count."""
        rid = int(rid)
        ok, val = self.table.find_batch(np.array([rid], np.int64))
        if not ok[0] or int(val[0, V_ACTIVE]) != 1:
            raise KeyError(f"request {rid} is not active")
        slot, tlen = int(val[0, V_SLOT]), int(val[0, V_TLEN])
        with self.arena.epoch():
            if self.journal is not None:
                toks = np.asarray(self.tok_region.read_at(
                    np.asarray([slot], np.int64),
                    slice(0, tlen))[0], np.int64)
                self.journal.log(OP_COMPLETE, rid,
                                 digest=args_digest(toks), info=tlen)
            self.table.remove_batch(np.array([rid], np.int64))
            self.arena.commit()
        self.paging.free_request(rid)
        self.slot_rid[slot] = -1
        self.pos[slot] = 0
        return tlen

    def _decode_slot(self, slot: int, token: int, p: int):
        """Decode ``token`` at position ``p`` in ``slot``; returns the
        slot's logits.  The program reads every slot's rows and returns
        a new tree, so reading ``self.cache``, the dispatch and the
        assignment all hold the cache lock: a sibling prefill group's
        scatter during early-admission decoding (step() inside an
        on_slot_ready callback while recovery still prefills other
        slots) must not be lost.  Dispatch is asynchronous, so the lock
        is held only while the program is enqueued."""
        rid = int(self.slot_rid[slot])
        with obs.span("serve.slot.decode", rid), self._cache_lock:
            logits, self.cache = self._slot_decode(
                self.params, self.cache, slot, token, p)
        return logits

    # ------------------------------------------------------------------
    def crash(self) -> None:
        """Drop ALL device + volatile host state.  No slot is ready to
        serve until recovery re-admits it."""
        self.cache = None
        self.pos = None
        self.slot_rid = None
        self.slot_ready = np.zeros(self.cfg.max_batch, bool)
        self.arena.crash()

    def readmit(self, rids) -> None:
        """Abandon quarantined ``rids``: lift the admission gate and —
        when journaling — close each rid's exactly-once accounting with
        a COMPLETE descriptor (its effects are unrecoverable, so the
        retry obligation is formally discharged; a resubmission is a
        NEW request under a new rid, per the journal's dedup window)."""
        rids = {int(r) for r in np.atleast_1d(rids)}
        self.quarantined_rids -= rids
        if self.journal is None:
            return
        stale = [r for r in sorted(rids)
                 if r in self.journal._admit
                 and r not in self.journal._complete]
        if stale:
            with self.arena.epoch():
                for r in stale:
                    self.journal.log(OP_COMPLETE, r, info=-1)
                self.arena.commit()

    def recover(self, concurrency: int = 1,
                on_stage=None, salvage: bool = False) -> float:
        """Paper-style recovery through the unified manager: reopen the
        arenas once, then reconstruct in dependency order — request
        hashmap + LRU chain (independent: one topological level), page
        tables, engine slots (batched slab scan + grouped re-prefill).
        ``concurrency>1`` runs independent stages AND the engine's
        prefill groups in thread pools, and slots are re-admitted
        (``slot_ready``) group by group as their prefill lands.
        ``salvage=True`` rides the manager's salvage mode (DESIGN.md
        §13): corrupted stages quarantine instead of aborting, and rids
        whose table entry or token-log row was lost land in
        ``quarantined_rids`` — admission refuses exactly those until
        ``readmit()``.  Returns seconds; the staged RecoveryReport
        lands in ``last_recovery``."""
        self._recover_concurrency = max(1, int(concurrency))
        # .jrnl rings load with the journal stage, .integ sidecars with
        # the arena-level verify paths — neither belongs to the table's
        # own load stage
        req_regions = tuple(n for n in self.arena.regions
                            if n.startswith("req.")
                            and not n.endswith(".jrnl")
                            and not n.endswith(".integ"))
        mgr = RecoveryManager(self.arena, self.paging.arena)
        mgr.add("req_table", "pstruct.hashmap", self.table,
                regions=req_regions)
        lru_regions = ("lru.nodes", "lru.header")
        if self.paging.lru.snapshot:
            lru_regions += ("lru.snapring", "lru.snaprec")
        mgr.add("lru", "pstruct.dll", self.paging.lru, regions=lru_regions)
        mgr.add("pages", "serve.paged_alloc", self.paging,
                depends=("lru",), regions=("lru.nodes",))
        eng_deps = ("req_table", "pages")
        if self.journal is not None:
            # replay the committed journal window, then cross-check the
            # classification against the recovered table in the engine
            # stage (detectable exactly-once semantics, DESIGN.md §11)
            mgr.add("journal", "serve.journal", self.journal,
                    regions=("req.jrnl", "req.header"))
            eng_deps += ("journal",)
        mgr.add("engine", "serve.engine", self, depends=eng_deps,
                regions=req_regions + ("tokens",))
        report = mgr.recover(concurrency=concurrency, on_stage=on_stage,
                             salvage=salvage)
        self.last_recovery = report
        self.quarantined_rids = {
            int(k) for k in getattr(self.table, "quarantined", ())}
        return report.total_seconds


@rec.register("serve.engine")
def _reconstruct_engine(eng: "ServingEngine") -> dict:
    """Pure rebuild of the engine's DERIVABLE state from the recovered
    request table: one vectorized scan over the dense entry slab (no
    per-entry Python loop), then grouped re-prefill — slots sharing a
    (token-log shard, prompt length) pair share a single batched prefill
    call.  Each group's slots are re-admitted (``slot_ready``) the
    moment its prefill lands, and ``on_slot_ready`` fires with the
    admission offset — empty slots admit right after the scan, so new
    requests need not wait for old ones to re-prefill.  On a sharded
    arena admission goes per SHARD-GROUP (DESIGN.md §7): each group
    reads only its own shard's token rows, so groups stream out of
    independent shard files instead of queueing behind one; on
    ``n_shards=1`` the grouping degenerates to the per-length grouping
    exactly.  Groups run in a thread pool when the engine is recovering
    with ``concurrency>1`` (model calls parallel, cache scatter
    serialized by the cache lock).

    The stage's seconds end at dispatch: nothing here waits for the
    prefills and scatters to finish on the device (the first ``step()``
    does).  Their device time is ``recovery.rebuild_device_ms`` in the
    benchmark, read from a trace after the ``recover.engine`` span.  Each
    group's prefill and scatter is a ``recover.prefill_group`` span; the
    details count the positions re-prefilled, meta tokens included
    (``rebuild_positions``), and each group's ``[tokens, sequences]``
    (``rebuild_groups``)."""
    cfg = eng.cfg
    t0 = time.perf_counter()
    eng.cache = eng.model.init_cache(cfg.max_batch, cfg.s_max)
    eng.pos = np.zeros(cfg.max_batch, np.int64)
    eng.slot_rid = np.full(cfg.max_batch, -1, np.int64)
    fresh = int(eng.table.header.vol[0, HM_FRESH])
    keys = eng.table.keys[:fresh]
    vals = eng.table.values[:fresh]
    # valid rids are non-negative; KEY_NULL tombstones are negative too,
    # so one sign check covers both
    live = (keys >= 0) & (vals[:, V_ACTIVE] == 1)
    salvage = bool(getattr(eng.arena, "_salvage", False))
    lost_tok = 0
    if salvage:
        # token-log salvage: a corrupt slot row loses its request's
        # prompt — the table entry is intact, so the rid quarantines by
        # name and its slot frees for new work
        bad_slots = _salvage_bad_rows(eng.arena, eng.tok_region)
        if bad_slots.size:
            hit = live & np.isin(vals[:, V_SLOT], bad_slots)
            eng.table.quarantined.update(int(k) for k in keys[hit])
            live = live & ~hit
            lost_tok = int(hit.sum())
    lost = set(getattr(eng.table, "quarantined", ()))
    if eng.journal is not None:
        # the journal's must-retry set and the table's live set are two
        # independent persisted records of the same fact; the shared
        # req.header flush line makes divergence impossible in any
        # committed image, so a mismatch here is corruption — fail
        # loudly instead of double-admitting (DESIGN.md §11)
        retry = eng.journal.must_retry()
        table_live = {int(k) for k in keys[live]}
        if salvage and lost:
            # rids cut out by salvage are EXPECTED to diverge: the
            # journal still remembers admissions the table lost
            retry = retry - lost
            table_live = table_live - lost
        if retry != table_live:
            msg = ("journal/table divergence after recovery: journal "
                   f"must-retry={sorted(retry)} vs table live="
                   f"{sorted(table_live)}")
            if salvage:
                # residual divergence IS corruption — quarantine the
                # engine stage rather than abort the whole recovery
                raise CorruptLineError("req.jrnl", np.empty(0, np.int64),
                                       detail=msg)
            raise RuntimeError(msg)
    slots = vals[live, V_SLOT]
    tlens = vals[live, V_TLEN]
    eng.slot_rid[slots] = keys[live]
    eng.pos[slots] = tlens
    # admit everything the scan proved empty; occupied slots stay gated
    # until their group's prefill lands
    ready = np.ones(cfg.max_batch, bool)
    ready[slots] = False
    eng.slot_ready = ready
    shards = eng.arena.region_shards("tokens", slots)
    groups = sorted({(int(s), int(tl)) for s, tl in zip(shards, tlens)})

    def prefill_group(key: Tuple[int, int]) -> float:
        shard, tl = key
        sel = slots[(shards == shard) & (tlens == tl)]
        with obs.span("recover.prefill_group"):
            eng._prefill_slots(sel, np.asarray(
                eng.tok_region.read_at(sel, slice(0, tl - 1)), np.int32))
        with eng._admit_lock:
            eng.slot_ready[sel] = True
            admitted = time.perf_counter() - t0
            cb = eng.on_slot_ready
            if cb is not None:
                cb(sel, int(tl), admitted)
        return admitted

    conc = max(1, int(eng._recover_concurrency))
    if conc > 1 and len(groups) > 1:
        with ThreadPoolExecutor(
                max_workers=min(conc, len(groups))) as ex:
            admissions = list(ex.map(prefill_group, groups))
    else:
        admissions = [prefill_group(g) for g in groups]
    sizes = [[tl - 1, int(((shards == sh) & (tlens == tl)).sum())]
             for sh, tl in groups]
    meta = eng.model.cfg.meta_tokens
    out = {"requests": int(live.sum()),
           "prefill_groups": len(groups),
           "rebuild_groups": sizes,
           "rebuild_positions": sum(n * (t + meta) for t, n in sizes
                                    if t or meta),
           "shard_groups": int(np.unique(shards).size) if slots.size
           else 0,
           "first_admission_s": round(min(admissions), 6)
           if admissions else 0.0,
           "last_admission_s": round(max(admissions), 6)
           if admissions else 0.0}
    if lost:
        out.update(degraded=True, quarantined_rids=sorted(lost),
                   lost_token_rows=lost_tok)
    return out


def _scatter_batch(full, grp, idx, ax):
    """full.at[slots].set(rows) along the structural batch axis."""
    if ax == 0:
        return full.at[idx].set(grp)
    return full.at[:, idx].set(grp)


def _map_slot(full_tree, other_tree, fn):
    """Apply fn(full_leaf, other_leaf, batch_axis) over a cache pytree.
    The batch axis is structural, not shape-inferred: leaves under the
    stacked "blocks" subtree carry a leading superblock dim (batch at axis
    1); leaves under "rem" have batch at axis 0."""
    out = dict(full_tree)
    if "blocks" in full_tree:
        out["blocks"] = jax.tree.map(lambda f, o: fn(f, o, 1),
                                     full_tree["blocks"],
                                     other_tree["blocks"])
    if "rem" in full_tree:
        out["rem"] = jax.tree.map(lambda f, o: fn(f, o, 0),
                                  full_tree["rem"], other_tree["rem"])
    return out
