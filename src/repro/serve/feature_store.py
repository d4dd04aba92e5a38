"""Partly-persistent embedding/feature store with exactly-once request
semantics (the ROADMAP recommender workload; DESIGN.md §11).

A recommender-style serving path beyond the LLM KV-cache: requests
carry per-key embedding deltas (gradient-style updates).  The paper's
state split, applied per structure:

* ESSENTIAL — the embedding hashmap ``emb`` (key -> per-key apply
  counters; keys + NEXT chains persisted by the hashmap itself), the
  sample log (the B+Tree ``sx``: sample id -> (emb key, delta) — tree
  records ARE the log), and the request journal ring.
* DERIVABLE — the dense hot rows (``vectors``, one fixed-point
  accumulator row per hashmap slab slot) and the ``next_sample``
  cursor: both rebuilt by replaying the committed sample log.  Delta
  accumulation commutes, so the replay is one ``np.add.at`` scatter —
  order-free and vectorized.

Exactly-once: every ``apply`` journals one fused OP_APPLY descriptor in
the SAME epoch as its table/tree mutations.  After a crash, recovery
classifies each request off the committed journal window; a retry of a
completed request is refused (``apply`` returns False), a request whose
epoch never committed left no trace anywhere (the descriptor, the
samples, and the count bumps commit atomically) and retries cleanly.
This store is the first consumer the journal's guarantee is asserted
against — the duplicate-admission oracle in tests/test_async_recovery.py
crashes at every epoch boundary and replays the full workload, and the
twin uninterrupted run's effect-set must match exactly.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.core import reconstruct as rec
from repro.core.arena import (CorruptLineError, QuarantinedError,
                              journal_enabled, open_arena)
from repro.core.recovery import RecoveryManager
from repro.pstruct.bptree import BPTree
from repro.pstruct.hashmap import KEY_NULL, Hashmap
from repro.pstruct.hashmap import H_FRESH as HM_FRESH
from repro.serve.journal import (OP_APPLY, ST_NEVER, RequestJournal,
                                 args_digest)

# the emb header line, word by word: the hashmap owns 0-3
# (H_FLAG/H_SIZE/H_FRESH/H_BUCKETS), the piggybacked journal takes 4-5
# (HEAD/TAIL), and the store's committed sample cursor rides word 6 —
# table size, journal head, and log cursor commit in ONE 64 B line, so
# no crash point can ever let them diverge.  The cursor must live here
# and not be derived from table values or tree keys: torn (data-phase)
# crashes leave in-place row rewrites visible-but-durable in both
# structures, and only metadata lines are crash-ordered.
FS_CURSOR = 6


@dataclasses.dataclass
class FeatureConfig:
    n_keys: int = 256             # embedding-table capacity (slab slots)
    dim: int = 4                  # delta words per key (<= 6: the tree
                                  # record packs (key, delta) in 7 words)
    n_samples: int = 1024         # sample-log capacity
    mode: str = "partly"
    n_shards: int = 1
    chain_method: str = "auto"
    snapshot: Optional[bool] = None
    journal: Optional[bool] = None


class FeatureStore:
    def __init__(self, cfg: FeatureConfig, path: Optional[str] = None):
        assert 1 <= cfg.dim <= 6
        self.cfg = cfg
        node_cap = max(64, cfg.n_samples // 4)
        layout = dict(Hashmap.layout(cfg.n_keys, cfg.mode, name="emb",
                                     snapshot=cfg.snapshot))
        layout.update(BPTree.layout(node_cap, cfg.n_samples, cfg.mode,
                                    name="sx"))
        jr_cap = 2 * cfg.n_samples
        if journal_enabled(cfg.journal):
            layout.update(RequestJournal.layout(jr_cap, name="emb"))
        self.arena = open_arena(path, layout, n_shards=cfg.n_shards)
        self.table = Hashmap(self.arena, cfg.n_keys, cfg.mode, name="emb",
                             chain_method=cfg.chain_method,
                             snapshot=cfg.snapshot)
        self.tree = BPTree(self.arena, node_cap, cfg.n_samples, cfg.mode,
                           name="sx", chain_method=cfg.chain_method)
        # HEAD/TAIL piggyback on the emb header line, which apply()
        # marks every epoch through insert_batch — same one-ring-line
        # overhead argument as the engine journal (DESIGN.md §11)
        self.journal = RequestJournal(
            self.arena, jr_cap, name="emb", header=self.table.header) \
            if journal_enabled(cfg.journal) else None
        # DERIVABLE hot rows + per-key apply counters, indexed by
        # hashmap slab slot; both replayed from the committed sample log
        self.vectors = np.zeros((cfg.n_keys, cfg.dim), np.int64)
        self.counts = np.zeros(cfg.n_keys, np.int64)
        self.next_sample = 0
        self.last_recovery = None
        # keys whose state was lost to media corruption in the last
        # salvage recovery: lookup/apply refuse them until readmit()
        self.quarantined_keys: set = set()

    # ------------------------------------------------------------- write
    def apply(self, rid: int, keys, deltas, _torn_crash: bool = False
              ) -> bool:
        """Apply one request's embedding deltas, exactly once.  Returns
        False (no effects) when the journal has already seen ``rid`` —
        the crash-retry path replays its whole workload and completed
        requests are refused here.  One atomic epoch: per-key counter
        bumps in the table, the request's samples appended to the log,
        and the fused OP_APPLY descriptor.  ``_torn_crash`` is the
        crash-injection hook: flush the data phase, then lose power
        before the commit (tests/test_async_recovery.py)."""
        rid = int(rid)
        keys = np.asarray(keys, np.int64)
        deltas = np.asarray(deltas, np.int64).reshape(len(keys),
                                                      self.cfg.dim)
        assert len(np.unique(keys)) == len(keys), \
            "apply expects unique keys per request"
        self._refuse_quarantined(keys)
        if self.journal is not None and \
                self.journal.state_of(rid) != ST_NEVER:
            return False
        if self.next_sample + len(keys) > self.cfg.n_samples:
            raise MemoryError("sample log full")
        sids = np.arange(self.next_sample, self.next_sample + len(keys),
                         dtype=np.int64)
        # value rows are written from VOLATILE truth, never
        # read-modify-write of the table copy: a torn crash can leave an
        # uncommitted in-place value rewrite durable, and incrementing
        # that on retry would double-count
        slots0 = self.table._find_slots(keys)
        pre = np.where(slots0 >= 0,
                       self.counts[np.clip(slots0, 0, None)], 0)
        with self.arena.epoch():
            # per-key value row: word 0 = applied-sample count, word 1 =
            # last sample id.  ALWAYS rewritten for every touched key,
            # so the emb.header line is marked every apply epoch (the
            # journal's piggyback ride).
            vals = np.zeros((len(keys), 7), np.int64)
            vals[:, 0] = pre + 1
            vals[:, 1] = sids
            self.table.insert_batch(keys, vals)
            self.table.header.vol[0, FS_CURSOR] = \
                self.next_sample + len(keys)
            recs = np.zeros((len(keys), 7), np.int64)
            recs[:, 0] = keys
            recs[:, 1:1 + self.cfg.dim] = deltas
            self.tree.insert_batch(sids, recs)
            if self.journal is not None:
                self.journal.log(
                    OP_APPLY, rid,
                    digest=args_digest(np.concatenate([keys,
                                                       deltas.ravel()])),
                    info=len(keys))
            if _torn_crash:
                self.arena.writeset.flush(include_meta=False)
                self.crash()
                return False
            self.arena.commit()
        slots = self.table._find_slots(keys)
        np.add.at(self.vectors, slots, deltas)
        self.counts[slots] = pre + 1
        self.next_sample += len(keys)
        return True

    def _refuse_quarantined(self, keys) -> None:
        if not self.quarantined_keys:
            return
        bad = sorted(int(k) for k in np.atleast_1d(keys)
                     if int(k) in self.quarantined_keys)
        if bad:
            raise QuarantinedError(
                f"keys {bad} were lost to media corruption in the last "
                "salvage recovery; readmit() them to start fresh")

    def readmit(self, keys) -> None:
        """Lift the quarantine on ``keys``: the caller accepts that the
        lost history is gone and wants the keys writable again (their
        accumulators restart from the salvaged committed state)."""
        self.quarantined_keys -= {int(k) for k in np.atleast_1d(keys)}

    # -------------------------------------------------------------- read
    def lookup(self, keys) -> np.ndarray:
        """Dense embedding rows for ``keys`` (zeros for absent keys).
        Raises QuarantinedError if any key's state was lost to media
        corruption in the last salvage recovery."""
        keys = np.asarray(keys, np.int64)
        self._refuse_quarantined(keys)
        slots = self.table._find_slots(keys)
        out = np.zeros((len(keys), self.cfg.dim), np.int64)
        ok = slots >= 0
        out[ok] = self.vectors[slots[ok]]
        return out

    # ---------------------------------------------------------- recovery
    def crash(self) -> None:
        self.vectors = np.zeros_like(self.vectors)
        self.counts = np.zeros_like(self.counts)
        self.next_sample = 0
        self.arena.crash()

    def recover(self, concurrency: int = 1, on_stage=None,
                salvage: bool = False):
        mgr = RecoveryManager(self.arena)
        emb_regions = tuple(n for n in self.arena.regions
                            if n.startswith("emb.")
                            and not n.endswith(".jrnl")
                            and not n.endswith(".integ"))
        sx_regions = tuple(n for n in self.arena.regions
                           if n.startswith("sx.")
                           and not n.endswith(".integ"))
        mgr.add("emb", "pstruct.hashmap", self.table, regions=emb_regions)
        mgr.add("samples", "pstruct.bptree", self.tree, regions=sx_regions)
        deps = ("emb", "samples")
        if self.journal is not None:
            mgr.add("journal", "serve.journal", self.journal,
                    regions=("emb.jrnl", "emb.header"))
            deps += ("journal",)
        mgr.add("store", "serve.feature_store", self, depends=deps,
                regions=())
        report = mgr.recover(concurrency=concurrency, on_stage=on_stage,
                             salvage=salvage)
        self.last_recovery = report
        if salvage:
            # belt and braces: even if the store stage was skipped
            # (quarantined dependency), table-level losses still gate
            self.quarantined_keys |= {
                int(k) for k in getattr(self.table, "quarantined", ())}
        return report


@rec.register("serve.feature_store")
def _reconstruct_feature_store(fs: FeatureStore) -> dict:
    """Pure rebuild of the hot rows: replay the committed sample log
    (tree records) into the slot-indexed accumulators with one
    ``np.add.at`` scatter — commutative deltas make the replay
    order-free.  The committed cursor comes from the header line's
    FS_CURSOR word, NOT from ``tree.max_key()`` or table values: a torn
    (data-phase-only) crash leaves in-place row rewrites
    visible-but-durable in both slabs
    (test_torn_bptree_leaf_rewrite_is_visible_but_durable), so only the
    crash-ordered metadata line can say where the committed prefix
    ends.  Torn tree records beyond the cursor are ignored here and
    overwritten in place when the request retries (tree inserts are
    insert-or-update).  Within the committed prefix, holes or unknown
    keys ARE corruption: fail loudly (detectability over silent
    drift)."""
    cfg = fs.cfg
    salvage = bool(getattr(fs.arena, "_salvage", False))
    fs.quarantined_keys = ({int(k) for k in
                            getattr(fs.table, "quarantined", ())}
                           if salvage else set())
    fs.vectors = np.zeros((cfg.n_keys, cfg.dim), np.int64)
    fs.counts = np.zeros(cfg.n_keys, np.int64)
    fs.next_sample = int(fs.table.header.vol[0, FS_CURSOR])
    if not 0 <= fs.next_sample <= cfg.n_samples:
        if salvage:
            raise CorruptLineError(
                "emb.header", np.array([0], np.int64),
                detail=f"committed sample cursor {fs.next_sample} "
                       "out of range")
        raise RuntimeError(
            f"committed sample cursor {fs.next_sample} out of range")
    replayed = missing = 0
    if fs.next_sample:
        sids = np.arange(fs.next_sample, dtype=np.int64)
        ok, recs = fs.tree.find_batch(sids)
        if not ok.all():
            if not salvage:
                raise RuntimeError(
                    f"sample log has holes: {int((~ok).sum())} "
                    "missing ids")
            # salvage: quarantined/lost log records replay as holes —
            # the per-key count cross-check below names the losers
            missing = int((~ok).sum())
            recs = recs[ok]
        keys = recs[:, 0]
        slots = fs.table._find_slots(keys)
        if (slots < 0).any():
            if not salvage:
                raise RuntimeError(
                    "sample log names keys absent from the committed "
                    "table")
            # the table lost these keys (row quarantined): their log
            # records survive and name them precisely
            fs.quarantined_keys.update(int(k) for k in keys[slots < 0])
            keep = slots >= 0
            recs, slots = recs[keep], slots[keep]
        np.add.at(fs.vectors, slots, recs[:, 1:1 + cfg.dim])
        np.add.at(fs.counts, slots, 1)
        replayed = int(slots.size) if salvage else int(sids.size)
    if salvage:
        # cross-check: the table's committed per-key apply counters vs
        # the replayed ones — any key whose samples were lost (the log
        # record was corrupt, so the key inside it is unreadable) shows
        # up as a counter shortfall and quarantines BY NAME here
        fresh = int(fs.table.header.vol[0, HM_FRESH])
        tk = np.asarray(fs.table.keys[:fresh], np.int64)
        tv = np.asarray(fs.table.values[:fresh], np.int64)
        bad = (tk != KEY_NULL) & (tv[:, 0] != fs.counts[:fresh])
        fs.quarantined_keys.update(int(k) for k in tk[bad])
    detail = {"samples": replayed, "keys": int(fs.table.size)}
    if fs.journal is not None:
        cls = fs.journal.classify()
        detail["journal_completed"] = sum(
            1 for s in cls.values() if s == "completed")
    if salvage and (fs.quarantined_keys or missing):
        detail.update(degraded=True, missing_samples=missing,
                      quarantined_keys=sorted(fs.quarantined_keys))
    return detail
