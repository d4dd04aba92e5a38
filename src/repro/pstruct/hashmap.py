"""Partly-persistent hashmap (paper §IV-E, AOSP-chaining layout).

Layout mirrors the paper's Listing 3 at flush-unit granularity:

* Entries live in a dense append-only slab (the paper's spatially-adjacent
  struct Entry file).  Partly persistent row = KEY (8 B) + VALUE (7 x 8 B)
  = 64 B = 1 line.  Fully persistent row additionally persists HASH + NEXT
  (2nd line; 128 B row).
* struct Hashmap: only SIZE is essential (one header line).  BUCKETCOUNT,
  the bucket array, chain links and cached hashes are all volatile
  redundancy (DERIVABLE).

Deletions in a dense slab: partly-persistent deletion writes a NULL key
tombstone into the entry row (1 line — the paper's "KEY is not NULL =>
valid entry" check) — the slab is compacted lazily on rehash.

Batched ops vectorize the chain walks: a probe advances *all* pending
lookups one link per round (rounds = max chain length, ~O(1/load-factor)).

Reconstruction (paper §IV-E3): scan the slab rows [0, fresh), drop NULL
keys, recompute hashes, re-derive bucket count from SIZE and the load
factor, and rebuild chains in slab order (the paper appends at chain tail,
preserving insertion order — we reproduce that with a grouped argsort).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.core import reconstruct as rec
from repro.core.arena import (Arena, FlushStats, SNAP_SLOTS, SNAP_WORDS,
                              snap_record_pack, snap_record_parse,
                              snapshot_enabled)
from repro.core.recovery import chain_walk
from repro.pstruct.dll import _salvage_bad_rows

NULL = -1
KEY_NULL = np.int64(-(2 ** 62))  # tombstone / empty key sentinel
VALUE_WORDS = 7

H_FLAG, H_SIZE, H_FRESH, H_BUCKETS = range(4)


def hash64(keys: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer — cheap, good avalanche, vectorizable."""
    x = keys.astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    x = x ^ (x >> np.uint64(31))
    return x


class Hashmap:
    def __init__(self, arena: Arena, capacity: int, mode: str = "partly",
                 load_factor: float = 0.75, name: str = "hm",
                 chain_method: str = "auto",
                 snapshot: Optional[bool] = None):
        assert mode in ("partly", "full")
        self.mode = mode
        self.capacity = capacity
        self.load_factor = load_factor
        # bucket-chain walk strategy for the batched unlink ("auto"
        # keeps the level-synchronous walk for many short chains and
        # flips to contraction list ranking only for few chains over a
        # huge slab — core.recovery.chain_walk, DESIGN.md §8)
        self.chain_method = chain_method
        self.arena = arena
        row = 8 if mode == "partly" else 16
        self._row = row
        # Sharded routing (DESIGN.md §7): entry rows scatter by a hash
        # of their 64-row segment — the paper's bucket-hash dispersal
        # decoupled from insert order, so an append burst fans out
        # across shard files instead of serializing on the shard that
        # owns the slab frontier (segment-granular: loads block-copy).
        self.entries = arena.regions.get(f"{name}.entries") or arena.region(
            f"{name}.entries", np.int64, (capacity, row), router=("hash",))
        self.header = arena.regions.get(f"{name}.header") or arena.region(
            f"{name}.header", np.int64, (1, 8))
        n_max = _next_pow2(max(16, int(capacity / load_factor)))
        self.n_buckets_max = n_max
        # Fully-persistent mode keeps the bucket array itself in PM (the
        # paper's struct Hashmap stores BUCKETS persistently); partly mode
        # keeps it volatile only.
        self._pbuckets = None
        if mode == "full":
            self._pbuckets = arena.regions.get(f"{name}.buckets") or \
                arena.region(f"{name}.buckets", np.int64, (n_max, 1),
                             router=("seg", 64))
        self.n_buckets = _next_pow2(max(16, int(capacity / load_factor)))
        self.buckets = np.full(self.n_buckets, NULL, np.int64)  # volatile
        self.chain = np.full(capacity, NULL, np.int64)  # volatile next
        self.hashes = np.zeros(capacity, np.uint64)  # volatile cached hash
        # keys whose entry rows were dropped by salvage recovery
        # (DESIGN.md §13) — consumers refuse these instead of serving
        # reconstructed garbage
        self.quarantined: set = set()
        # incremental order snapshots (DESIGN.md §10): persisted mirrors
        # of the volatile bucket heads + chain links, plus a 4-slot
        # sealed-record ring — recovery adopts them after verification,
        # replacing the O(N log N) rebuild argsort with O(N) gathers
        snap_on = snapshot_enabled(snapshot)
        self.snapbkt = arena.regions.get(f"{name}.snapbkt")
        self.snapchain = arena.regions.get(f"{name}.snapchain")
        self.snaprec = arena.regions.get(f"{name}.snaprec")
        if snap_on and self.snapbkt is None and not arena._layout_final:
            self.snapbkt = arena.region(f"{name}.snapbkt", np.int64,
                                        (n_max,), router=("seg", 64))
            self.snapchain = arena.region(f"{name}.snapchain", np.int64,
                                          (capacity,), router=("hash",))
            self.snaprec = arena.region(f"{name}.snaprec", np.int64,
                                        (SNAP_SLOTS, SNAP_WORDS))
        self.snapshot = snap_on and self.snapbkt is not None
        if self.snapshot:
            self._snap_bkt_dirty = np.zeros(n_max, bool)
            self._snap_chain_dirty = np.zeros(capacity, bool)
            self._snap_seq = 0
            self._snap_resync = True
            self._snap_last = None     # (nb, fresh, size) at last emit
            arena.add_snapshot_provider(self._snap_emit)

    @staticmethod
    def layout(capacity: int, mode: str = "partly", name: str = "hm",
               load_factor: float = 0.75,
               snapshot: Optional[bool] = None):
        row = 8 if mode == "partly" else 16
        out = {f"{name}.entries": (np.int64, (capacity, row), ("hash",)),
               f"{name}.header": (np.int64, (1, 8))}
        n_max = _next_pow2(max(16, int(capacity / load_factor)))
        if mode == "full":
            out[f"{name}.buckets"] = (np.int64, (n_max, 1), ("seg", 64))
        if snapshot_enabled(snapshot):
            out[f"{name}.snapbkt"] = (np.int64, (n_max,), ("seg", 64))
            out[f"{name}.snapchain"] = (np.int64, (capacity,), ("hash",))
            out[f"{name}.snaprec"] = (np.int64, (SNAP_SLOTS, SNAP_WORDS))
        return out

    def _persist_buckets(self, bkts: np.ndarray) -> None:
        if self._pbuckets is not None and bkts.size:
            self._pbuckets.vol[bkts, 0] = self.buckets[bkts]
            self._pbuckets.mark_rows(bkts)

    # -------- views --------
    @property
    def keys(self) -> np.ndarray:
        return self.entries.vol[:, 0]

    @property
    def values(self) -> np.ndarray:
        return self.entries.vol[:, 1:1 + VALUE_WORDS]

    @property
    def size(self) -> int:
        return int(self.header.vol[0, H_SIZE])

    # -------- core probe (vectorized chain walk) --------
    def _find_slots(self, keys: np.ndarray) -> np.ndarray:
        """Slab index of each key (NULL if absent)."""
        h = hash64(keys)
        b = (h & np.uint64(self.n_buckets - 1)).astype(np.int64)
        cur = self.buckets[b]
        found = np.full(len(keys), NULL, np.int64)
        active = cur != NULL
        while active.any():
            idx = cur[active]
            hit = self.keys[idx] == keys[active]
            tgt = np.nonzero(active)[0]
            found[tgt[hit]] = idx[hit]
            nxt = self.chain[idx]
            cur[active] = np.where(hit, NULL, nxt)
            active = cur != NULL
        return found

    def find_batch(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (present mask, values (m, 7))."""
        slots = self._find_slots(np.asarray(keys, np.int64))
        ok = slots != NULL
        vals = np.zeros((len(keys), VALUE_WORDS), np.int64)
        vals[ok] = self.values[np.where(ok, slots, 0)][ok]
        return ok, vals

    # -------- mutation --------
    def insert_batch(self, keys: np.ndarray, values: np.ndarray) -> None:
        """Insert-or-update.  keys: (m,); values: (m, 7)."""
        with self.arena.epoch():
            self._insert_batch(keys, values)

    def _insert_batch(self, keys: np.ndarray, values: np.ndarray) -> None:
        keys = np.asarray(keys, np.int64)
        values = np.asarray(values, np.int64)
        # de-dup within batch: keep the last occurrence
        _, last = np.unique(keys[::-1], return_index=True)
        keep = np.sort(len(keys) - 1 - last)
        keys, values = keys[keep], values[keep]
        slots = self._find_slots(keys)
        upd = slots != NULL
        hv = self.header.vol[0]
        if upd.any():
            s = slots[upd]
            self.entries.vol[s, 1:1 + VALUE_WORDS] = values[upd]
            self.entries.mark_rows(s)
        new_keys = keys[~upd]
        if len(new_keys):
            fresh0 = int(hv[H_FRESH])
            if fresh0 + len(new_keys) > self.capacity:
                raise MemoryError("hashmap slab exhausted")
            ids = np.arange(fresh0, fresh0 + len(new_keys), dtype=np.int64)
            hv[H_FRESH] = fresh0 + len(new_keys)
            self.entries.vol[ids, 0] = new_keys
            self.entries.vol[ids, 1:1 + VALUE_WORDS] = values[~upd]
            h = hash64(new_keys)
            self.hashes[ids] = h
            hv[H_SIZE] += len(new_keys)
            self._link(ids, h)
            if self.mode == "full":
                self.entries.vol[ids, 8] = h.astype(np.int64) >> np.int64(1)
                # chain pointers persisted too (set in _link)
            self.entries.mark_rows(ids)
            if hv[H_SIZE] > self.load_factor * self.n_buckets:
                self._grow()
        hv[H_FLAG] = 1
        self.header.mark_rows(np.array([0]))

    def _link(self, ids: np.ndarray, h: np.ndarray) -> None:
        """Append ids to their bucket chains (chain-tail order, as the
        paper's reconstruction expects).  Vectorized by bucket grouping."""
        b = (h & np.uint64(self.n_buckets - 1)).astype(np.int64)
        order = np.argsort(b, kind="stable")
        bs, ids_s = b[order], ids[order]
        grp_start = np.concatenate([[True], bs[1:] != bs[:-1]])
        # head of each new group links after current chain tail
        tails = self._chain_tails(bs[grp_start])
        # intra-group chaining
        self.chain[ids_s[:-1]] = np.where(~grp_start[1:], ids_s[1:], NULL)
        self.chain[ids_s[-1]] = NULL
        heads = ids_s[grp_start]
        # tail linking, one scatter per case: empty buckets adopt the
        # group head; occupied buckets chain it after their tail
        empty = tails == NULL
        self.buckets[bs[grp_start][empty]] = heads[empty]
        self.chain[tails[~empty]] = heads[~empty]
        if self.snapshot:
            self._snap_chain_dirty[ids_s] = True
            self._snap_chain_dirty[tails[~empty]] = True
            self._snap_bkt_dirty[bs[grp_start][empty]] = True
        if self.mode == "full":
            self.entries.vol[ids_s, 9] = self.chain[ids_s]
            link_dirty = tails[~empty]
            if link_dirty.size:
                self.entries.vol[link_dirty, 9] = self.chain[link_dirty]
                self.entries.mark_rows(link_dirty)
            self._persist_buckets(bs[grp_start][empty])

    def _chain_tails(self, bkts: np.ndarray) -> np.ndarray:
        cur = self.buckets[bkts]
        tails = np.full(len(bkts), NULL, np.int64)
        active = cur != NULL
        while active.any():
            idx = cur[active]
            tails[np.nonzero(active)[0]] = idx
            cur[active] = self.chain[idx]
            active = cur != NULL
        return tails

    def remove_batch(self, keys: np.ndarray) -> np.ndarray:
        """Tombstone deletion.  Returns mask of keys that were present."""
        with self.arena.epoch():
            return self._remove_batch(keys)

    def _remove_batch(self, keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, np.int64)
        slots = self._find_slots(keys)
        ok = slots != NULL
        s = np.unique(slots[ok])
        if s.size == 0:
            self.header.mark_rows(np.array([0]))
            return ok
        hv = self.header.vol[0]
        # unlink from volatile chains (vectorized per chain via predecessor
        # search), write tombstone key persistently; chain fixes in full
        # mode are marked inside _unlink.
        self._unlink(s)
        self.entries.vol[s, 0] = KEY_NULL
        hv[H_SIZE] -= s.size
        self.entries.mark_rows(s)
        self.header.mark_rows(np.array([0]))
        return ok

    def _unlink(self, slots: np.ndarray) -> None:
        """Remove `slots` from their bucket chains, all buckets in
        parallel: materialize the affected chains with the shared
        chain_walk primitive, mask out the removed members, and relink
        the survivors (order preserved) with two scatters."""
        hs = self.hashes[slots]
        bkts = np.unique((hs & np.uint64(self.n_buckets - 1)).astype(np.int64))
        members = chain_walk(self.chain, self.buckets[bkts],
                             method=self.chain_method)
        if self.snapshot:
            self._snap_bkt_dirty[bkts] = True
            self._snap_chain_dirty[slots] = True
        if members.shape[1] == 0:
            self.chain[slots] = NULL
            return
        valid = members != NULL
        keep = valid & ~np.isin(members, slots)
        # compact survivors left (stable: chain order preserved)
        comp = np.take_along_axis(
            members, np.argsort(~keep, axis=1, kind="stable"), axis=1)
        cnt = keep.sum(1)
        old_heads = self.buckets[bkts]
        new_heads = np.where(cnt > 0, comp[:, 0], NULL)
        self.buckets[bkts] = new_heads
        # relink: comp[b, j] -> comp[b, j+1] for j+1 < cnt, last -> NULL
        chain_dirty = []
        if comp.shape[1] > 1:
            m = (np.arange(comp.shape[1] - 1)[None, :] + 1) < cnt[:, None]
            src, dst = comp[:, :-1][m], comp[:, 1:][m]
            changed = self.chain[src] != dst
            self.chain[src] = dst
            chain_dirty.append(src[changed])
            if self.snapshot:
                self._snap_chain_dirty[src[changed]] = True
        nz = np.nonzero(cnt > 0)[0]
        last = comp[nz, cnt[nz] - 1]
        last_changed = self.chain[last] != NULL
        self.chain[last] = NULL
        chain_dirty.append(last[last_changed])
        if self.snapshot:
            self._snap_chain_dirty[last[last_changed]] = True
        self.chain[slots] = NULL
        if self.mode == "full":
            dirty = np.unique(np.concatenate(chain_dirty)) \
                if chain_dirty else np.empty(0, np.int64)
            if dirty.size:
                self.entries.vol[dirty, 9] = self.chain[dirty]
                self.entries.mark_rows(dirty)
            self._persist_buckets(bkts[new_heads != old_heads])

    def _grow(self) -> None:
        if self.n_buckets >= self.n_buckets_max:
            return
        self.n_buckets *= 2
        self._rebuild_chains()
        if self.mode == "full":
            # A PM-resident rehash rewrites every chain pointer and the
            # whole bucket array — the full (expensive) flush, which is
            # exactly why the paper keeps this structure volatile.
            fresh = int(self.header.vol[0, H_FRESH])
            live = np.nonzero(self.keys[:fresh] != KEY_NULL)[0]
            self.entries.vol[live, 9] = self.chain[live]
            self.entries.mark_rows(live)
            self._pbuckets.vol[: self.n_buckets, 0] = \
                self.buckets[: self.n_buckets]
            self._pbuckets.mark_range(0, self.n_buckets)

    def _rebuild_chains(self) -> None:
        fresh = int(self.header.vol[0, H_FRESH])
        live = np.nonzero(self.keys[:fresh] != KEY_NULL)[0]
        self.buckets = np.full(self.n_buckets, NULL, np.int64)
        self.chain = np.full(self.capacity, NULL, np.int64)
        if self.snapshot:
            # every link potentially moved: re-mirror wholesale at the
            # next commit (grows are O(log N) rare, so this amortizes)
            self._snap_resync = True
        if live.size == 0:
            return
        h = self.hashes[live]
        b = (h & np.uint64(self.n_buckets - 1)).astype(np.int64)
        order = np.argsort(b, kind="stable")  # slab order within bucket
        bs, ls = b[order], live[order]
        grp_start = np.concatenate([[True], bs[1:] != bs[:-1]])
        self.buckets[bs[grp_start]] = ls[grp_start]
        self.chain[ls[:-1]] = np.where(~grp_start[1:], ls[1:], NULL)
        if ls.size:
            self.chain[ls[-1]] = NULL

    # -------- incremental order snapshots (DESIGN.md §10) --------
    def _snap_emit(self):
        """Commit-time provider: mirror the bucket heads and chain links
        dirtied since the last commit, then seal one record line naming
        (n_buckets, fresh, size) for the generation this commit
        targets.

        Idempotent: a flush with nothing newly dirty and unchanged
        (n_buckets, fresh, size) emits nothing — the writeset drains
        providers at every epoch flush, and a commit's own flush must
        not add bytes beyond the preceding epoch's (the inter-shard
        commit-window byte-identity invariant)."""
        out = []
        hv = self.header.vol[0]
        fresh = int(hv[H_FRESH])
        if self._snap_resync:
            self._snap_chain_dirty[:] = False
            self._snap_bkt_dirty[:] = False
            self._snap_chain_dirty[:fresh] = True
            self._snap_bkt_dirty[:self.n_buckets] = True
            self._snap_resync = False
        state = (self.n_buckets, fresh, int(hv[H_SIZE]))
        if state == self._snap_last and not self._snap_chain_dirty.any() \
                and not self._snap_bkt_dirty.any():
            return out
        self._snap_last = state
        cd = np.nonzero(self._snap_chain_dirty)[0]
        if cd.size:
            self.snapchain.vol[cd] = self.chain[cd]
            out.append((self.snapchain, cd))
            self._snap_chain_dirty[:] = False
        bd = np.nonzero(self._snap_bkt_dirty)[0]
        if bd.size:
            self.snapbkt.vol[bd] = self.buckets[bd]
            out.append((self.snapbkt, bd))
            self._snap_bkt_dirty[:] = False
        seq = self._snap_seq
        self._snap_seq += 1
        slot = seq % SNAP_SLOTS
        self.snaprec.vol[slot] = snap_record_pack(
            self.arena.generation + 1, seq, self.n_buckets, fresh,
            int(hv[H_SIZE]))
        out.append((self.snaprec, np.asarray([slot], np.int64)))
        return out

    # -------- crash / reconstruction --------
    def reconstruct(self) -> None:
        """Thin shim over the registered pure reconstructor — recovery
        paths route through core.recovery.RecoveryManager, which loads
        the regions once and times the stage."""
        self.header.load()
        self.entries.load()
        if self.snapshot:
            self.snapbkt.load()
            self.snapchain.load()
            self.snaprec.load()
        rec.get("pstruct.hashmap")(self)

    def check_against(self, ref: dict) -> bool:
        ks = np.fromiter(ref.keys(), np.int64, len(ref))
        ok, vals = self.find_batch(ks)
        if not ok.all() or self.size != len(ref):
            return False
        want = np.stack([ref[int(k)] for k in ks]) if len(ref) else vals
        return bool((vals == want).all())

    def flush_stats(self) -> FlushStats:
        return self.arena.stats


def _hm_snap_records(snaprec) -> list:
    return [r for r in (snap_record_parse(snaprec.vol[s])
                        for s in range(SNAP_SLOTS)) if r is not None]


def _hm_snap_resume(h: "Hashmap") -> None:
    recs = _hm_snap_records(h.snaprec)
    h._snap_seq = (max(r[1] for r in recs) + 1) if recs else 0
    h._snap_bkt_dirty[:] = False
    h._snap_chain_dirty[:] = False
    h._snap_resync = True
    h._snap_last = None


def _hm_snap_adopt(h: "Hashmap", fresh: int, idx: np.ndarray
                   ) -> Optional[int]:
    """Seed the bucket chains from the newest committed snapshot, link
    the suffix of slab rows younger than the record, VERIFY the result
    is a canonical chain assembly (every live row exactly once, in its
    hash bucket, ascending slab order — the invariant both _link and
    _rebuild_chains maintain), and scatter it into fresh volatile
    arrays.  The snapshot carries the PRE-CRASH bucket basis (rec_nb),
    so adoption also restores n_buckets — same logical map, no argsort
    and no immediate regrow churn.  Returns the replayed-suffix length
    on adoption, None on any mismatch (callers fall back to the full
    size-derived rebuild)."""
    committed = h.arena.header_generation()
    best = None
    for r in _hm_snap_records(h.snaprec):
        if r[0] > committed:
            continue
        if best is None or r[1] > best[1]:
            best = r
    if best is None:
        return None
    _, _, rec_nb, rec_fresh, _, _ = best
    if not (16 <= rec_nb <= h.n_buckets_max and rec_nb & (rec_nb - 1) == 0):
        return None
    if not 0 <= rec_fresh <= fresh:
        return None
    mask = np.uint64(rec_nb - 1)
    cand_bkt = np.array(h.snapbkt.vol[:rec_nb], np.int64).reshape(-1)
    cand_chain = np.array(h.snapchain.vol, np.int64).reshape(-1)
    # local-walk only the suffix: rows the record predates were appended
    # at their bucket's chain tail in ascending slab order — replay that
    sfx = idx[idx >= rec_fresh]
    if sfx.size:
        b = (hash64(h.keys[sfx]) & mask).astype(np.int64)
        order = np.argsort(b, kind="stable")
        bs, ids_s = b[order], sfx[order]
        grp_start = np.concatenate([[True], bs[1:] != bs[:-1]])
        # tails of the affected buckets, walked over the candidate
        # arrays (bounded: torn links can cycle, so cap the rounds)
        tb = bs[grp_start]
        cur = cand_bkt[tb]
        tails = np.full(tb.size, NULL, np.int64)
        for _ in range(fresh + 1):
            ok = (cur >= 0) & (cur < h.capacity) & (cur < rec_fresh)
            if not ok.any():
                break
            tails[ok] = cur[ok]
            nxt = cand_chain[np.where(ok, cur, 0)]
            cur = np.where(ok, nxt, NULL)
        else:
            return None                       # never terminated: cycle
        cand_chain[ids_s[:-1]] = np.where(~grp_start[1:], ids_s[1:], NULL)
        cand_chain[ids_s[-1]] = NULL
        heads = ids_s[grp_start]
        empty = tails == NULL
        cand_bkt[tb[empty]] = heads[empty]
        cand_chain[tails[~empty]] = heads[~empty]
    # verify-always: materialize every chain and check it IS the
    # canonical state (one O(N) walk — the saving over the O(N log N)
    # argsort is the point of the seed)
    try:
        members = chain_walk(cand_chain, cand_bkt,
                             method=h.chain_method)
    except RuntimeError:
        return None                           # cycle in a torn chain
    valid = members != NULL
    flat = members[valid]
    if flat.size != idx.size:
        return None
    if flat.size:
        if ((flat < 0) | (flat >= fresh)).any():
            return None
        if (h.keys[flat] == KEY_NULL).any():
            return None
        want_b = (hash64(h.keys[flat]) & mask).astype(np.int64)
        got_b = np.broadcast_to(
            np.arange(rec_nb)[:, None], members.shape)[valid]
        if not np.array_equal(want_b, got_b):
            return None
        # ascending slab order within each bucket row (rules out both
        # misordering and duplicates: a dupe must share a bucket)
        if members.shape[1] > 1:
            step = valid[:, 1:]
            if (members[:, 1:][step] <= members[:, :-1][step]).any():
                return None
    # adopt: restore the record's basis and scatter the verified chains
    h.n_buckets = int(rec_nb)
    h.buckets = np.full(h.n_buckets, NULL, np.int64)
    h.chain = np.full(h.capacity, NULL, np.int64)
    if members.shape[1]:
        h.buckets[valid[:, 0]] = members[valid[:, 0], 0]
        if members.shape[1] > 1:
            step = valid[:, 1:]
            h.chain[members[:, :-1][step]] = members[:, 1:][step]
    return int(sfx.size)


@rec.register("pstruct.hashmap")
def _reconstruct_hashmap(h: "Hashmap") -> dict:
    """Pure rebuild (paper §IV-E3): SIZE + dense (KEY, VALUE) rows ->
    full hashmap.  Scan the slab rows [0, fresh) in one vectorized pass,
    drop NULL keys, recompute hashes, re-derive the bucket count from
    SIZE and the load factor, and rebuild chains in slab order — seeded
    from the newest committed order snapshot when one verifies
    (DESIGN.md §10)."""
    hv = h.header.vol[0]
    if hv[H_FLAG] != 1:
        # uninitialized image recovers as an empty map (§IV-E3 validity
        # check on struct Hashmap)
        hv[:] = 0
    fresh = int(hv[H_FRESH])
    # salvage (DESIGN.md §13): entry rows failing their sidecar become
    # volatile tombstones — the map recovers every verifiable entry and
    # refuses the rest by key.  A corrupt VALUE word leaves the key
    # word intact, so the quarantine names the real key; a corrupt KEY
    # word degrades to row-level loss (the garbage key is recorded
    # best-effort, and the structure is flagged degraded either way).
    h.quarantined = set()
    dropped = 0
    if getattr(h.arena, "_salvage", False):
        bad = _salvage_bad_rows(h.arena, h.entries)
        bad = bad[bad < fresh]
        if bad.size:
            img = np.asarray(h.arena._pimage(h.entries))
            for r in bad.tolist():
                key = int(img[r, 0])
                if key != KEY_NULL:
                    h.quarantined.add(key)
            was_live = int((h.keys[bad] != KEY_NULL).sum())
            h.entries.vol[bad, 0] = KEY_NULL
            hv[H_SIZE] = max(0, int(hv[H_SIZE]) - was_live)
            dropped = int(bad.size)
    live = h.keys[:fresh] != KEY_NULL
    # SIZE -> derive bucket count (paper derives BUCKETCOUNT from SIZE)
    size = int(hv[H_SIZE])
    h.n_buckets = _next_pow2(max(16, int(size / h.load_factor) + 1))
    h.hashes = np.zeros(h.capacity, np.uint64)
    idx = np.nonzero(live)[0]
    h.hashes[idx] = hash64(h.keys[idx])
    detail = {"mode": h.mode, "size": size, "live": int(idx.size)}
    if dropped:
        detail.update(degraded=True, quarantined_rows=dropped,
                      quarantined_keys=sorted(h.quarantined))
    snap_on = getattr(h, "snapshot", False)
    # a salvaged map never adopts a snapshot (the mirrors may reference
    # quarantined rows) — rebuild from the tombstoned slab instead
    replayed = _hm_snap_adopt(h, fresh, idx) \
        if snap_on and not dropped else None
    if replayed is None:
        h._rebuild_chains()
    if snap_on:
        detail["chain"] = "snapshot" if replayed is not None else "rebuild"
        detail["replayed"] = replayed if replayed is not None \
            else int(idx.size)
        _hm_snap_resume(h)
    return detail


def _next_pow2(x: int) -> int:
    return 1 << (int(x - 1)).bit_length()
