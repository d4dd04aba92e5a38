"""Partly-persistent B+Tree (paper §IV-D).

Node layout mirrors the paper's Listing 2: one node = 256 B = 4 cache
lines (int32 row of 64 words):

  [0] num_keys  [1] is_leaf  [2:20] keys (18 x i32)
  [20:39] pointers (19 x i32: children for inner, record ids for leaves)
  [40] next (leaf chain)  [41] parent  [42:] pad

Records (the paper's 64 B ``struct record`` holding a 7-word Value) live in
a dense (cap, 8) int64 region — 1 line per record.

Persistence policy is the paper's exactly: both modes share one node
region; *partly* persists only rows with is_leaf=1 (+ records + header),
inner rows exist only as volatile redundancy; *fully* persists every dirty
node row — including the parent path on splits, which is where the
(1 - 1/n) * (t/(t-1)) flush saving comes from.

Simplifications vs the paper (identical across both modes, so the
fully-vs-partly comparison stays apples-to-apples; documented in
EXPERIMENTS.md): deletes remove keys from leaves and unlink emptied leaves
but do not rebalance inner nodes; splits fill to ORDER/2 (the paper's
insert-optimized minimum-bucket choice, §IV-D).

Reconstruction (paper §IV-D3): walk the persistent leaf chain (vectorized
binary lifting), then bulk-load inner levels by bucketing ORDER children
per parent — the paper's maximum-bucket-size choice, matching DCPMM 256 B
granularity.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import reconstruct as rec
from repro.core.arena import Arena, CorruptLineError, FlushStats
from repro.core.recovery import chain_method, chain_order
from repro.pstruct.dll import _salvage_bad_rows

ORDER = 19
MAX_KEYS = ORDER - 1           # 18
SPLIT_FILL = ORDER // 2        # 9..10 keys per split target
NULL = -1
VALUE_WORDS = 7

# Sharded-arena routing (DESIGN.md §7): node rows route by LEAF RANGE —
# block-cyclic runs of 16 node ids (sequentially allocated leaves land
# in runs, so a key-range scan's dirty leaves spread across shard files
# while adjacent leaf splits share one); records in 64-row ranges.
LEAF_RANGE = 16
REC_RANGE = 64

H_FLAG, H_ROOT, H_FIRST_LEAF, H_COUNT, H_FRESH_NODES, H_FRESH_RECS = range(6)

C_NK, C_LEAF = 0, 1
K0, K1 = 2, 20
P0, P1 = 20, 39
C_NEXT, C_PARENT = 40, 41


class BPTree:
    def __init__(self, arena: Arena, cap_nodes: int, cap_records: int,
                 mode: str = "partly", name: str = "bt",
                 chain_method: str = "auto"):
        assert mode in ("partly", "full")
        self.mode = mode
        self.arena = arena
        self.cap_nodes = cap_nodes
        self.cap_records = cap_records
        # leaf-chain ranking strategy (doubling vs contraction list
        # ranking, core.recovery.chain_method / DESIGN.md §8)
        self.chain_method = chain_method
        self.nodes = arena.regions.get(f"{name}.nodes") or arena.region(
            f"{name}.nodes", np.int32, (cap_nodes, 64),
            router=("seg", LEAF_RANGE))
        self.records = arena.regions.get(f"{name}.records") or arena.region(
            f"{name}.records", np.int64, (cap_records, 8),
            router=("seg", REC_RANGE))
        self.header = arena.regions.get(f"{name}.header") or arena.region(
            f"{name}.header", np.int64, (1, 8))
        self._free_nodes: List[int] = []
        self._free_recs: List[int] = []
        self.leaf_prev = np.full(cap_nodes, NULL, np.int32)  # volatile
        # keys lost to media corruption in the last salvage recovery
        # (best effort: readable from intact-but-unreachable leaf rows)
        self.quarantined: set = set()

    @staticmethod
    def layout(cap_nodes: int, cap_records: int, mode: str = "partly",
               name: str = "bt"):
        return {f"{name}.nodes": (np.int32, (cap_nodes, 64),
                                  ("seg", LEAF_RANGE)),
                f"{name}.records": (np.int64, (cap_records, 8),
                                    ("seg", REC_RANGE)),
                f"{name}.header": (np.int64, (1, 8))}

    # ---------------- allocation ----------------
    def _alloc_nodes(self, m: int) -> np.ndarray:
        hv = self.header.vol[0]
        ids = []
        take = min(len(self._free_nodes), m)
        if take:
            ids.extend(self._free_nodes[-take:])
            del self._free_nodes[-take:]
        need = m - take
        if need:
            f0 = int(hv[H_FRESH_NODES])
            if f0 + need > self.cap_nodes:
                raise MemoryError("bptree node arena exhausted")
            ids.extend(range(f0, f0 + need))
            hv[H_FRESH_NODES] = f0 + need
        arr = np.asarray(ids, np.int32)
        self.nodes.vol[arr] = 0
        self.nodes.vol[arr, C_NEXT] = NULL
        self.nodes.vol[arr, C_PARENT] = NULL
        return arr

    def _alloc_recs(self, m: int) -> np.ndarray:
        hv = self.header.vol[0]
        ids = []
        take = min(len(self._free_recs), m)
        if take:
            ids.extend(self._free_recs[-take:])
            del self._free_recs[-take:]
        need = m - take
        if need:
            f0 = int(hv[H_FRESH_RECS])
            if f0 + need > self.cap_records:
                raise MemoryError("bptree record arena exhausted")
            ids.extend(range(f0, f0 + need))
            hv[H_FRESH_RECS] = f0 + need
        return np.asarray(ids, np.int64)

    # ---------------- flush policy ----------------
    def _mark_nodes(self, dirty: np.ndarray) -> None:
        """Mark dirty node rows into the arena write set.  Partly mode
        persists only leaf rows — inner nodes are volatile redundancy."""
        dirty = np.unique(np.asarray(dirty, np.int64))
        if dirty.size == 0:
            return
        if self.mode == "partly":
            leaf = self.nodes.vol[dirty, C_LEAF] == 1
            dirty = dirty[leaf]
            if dirty.size == 0:
                return
        self.nodes.mark_rows(dirty)

    # ---------------- search ----------------
    def _descend(self, keys: np.ndarray) -> np.ndarray:
        """Leaf id for each key (vectorized level-synchronous descent)."""
        hv = self.header.vol[0]
        m = len(keys)
        cur = np.full(m, int(hv[H_ROOT]), np.int64)
        keys = keys.astype(np.int32)
        for _ in range(64):  # depth bound
            rows = self.nodes.vol[cur]
            inner = rows[:, C_LEAF] == 0
            if not inner.any():
                break
            r = rows[inner]
            nk = r[:, C_NK:C_NK + 1]
            keymat = r[:, K0:K1]
            valid = np.arange(MAX_KEYS)[None, :] < nk
            pos = ((keymat <= keys[inner, None]) & valid).sum(1)
            child = r[np.arange(len(r)), P0 + pos]
            nxt = cur.copy()
            nxt[inner] = child
            cur = nxt
        return cur

    def find_batch(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        keys = np.asarray(keys, np.int64)
        hv = self.header.vol[0]
        if hv[H_FLAG] == 0 or hv[H_ROOT] == NULL:
            return (np.zeros(len(keys), bool),
                    np.zeros((len(keys), VALUE_WORDS), np.int64))
        leaves = self._descend(keys)
        rows = self.nodes.vol[leaves]
        nk = rows[:, C_NK:C_NK + 1]
        keymat = rows[:, K0:K1]
        valid = np.arange(MAX_KEYS)[None, :] < nk
        hit = (keymat == keys[:, None].astype(np.int32)) & valid
        ok = hit.any(1)
        slot = hit.argmax(1)
        rec = rows[np.arange(len(keys)), P0 + slot]
        vals = np.zeros((len(keys), VALUE_WORDS), np.int64)
        if ok.any():
            vals[ok] = self.records.vol[rec[ok], :VALUE_WORDS]
        return ok, vals

    # ---------------- insert ----------------
    def insert_batch(self, keys: np.ndarray, values: np.ndarray) -> None:
        with self.arena.epoch():
            self._insert_batch(keys, values)

    def _insert_batch(self, keys: np.ndarray, values: np.ndarray) -> None:
        keys = np.asarray(keys, np.int64)
        values = np.asarray(values, np.int64)
        # de-dup batch (keep last)
        _, last = np.unique(keys[::-1], return_index=True)
        keep = np.sort(len(keys) - 1 - last)
        keys, values = keys[keep], values[keep]
        hv = self.header.vol[0]

        if hv[H_FLAG] == 0 or hv[H_ROOT] == NULL:
            root = int(self._alloc_nodes(1)[0])
            self.nodes.vol[root, C_LEAF] = 1
            hv[H_ROOT] = root
            hv[H_FIRST_LEAF] = root
            hv[H_FLAG] = 1

        leaves = self._descend(keys)
        order = np.argsort(leaves, kind="stable")
        pending: List[Tuple[int, np.ndarray, np.ndarray]] = []
        i = 0
        while i < len(order):
            j = i
            leaf = leaves[order[i]]
            while j < len(order) and leaves[order[j]] == leaf:
                j += 1
            sel = order[i:j]
            pending.append((int(leaf), keys[sel], values[sel]))
            i = j

        # parent insertions accumulated per level
        promo: List[Tuple[int, int, int]] = []  # (left_node, sep_key, right_node)
        for leaf, ks, vs in pending:
            promo.extend(self._leaf_merge(leaf, ks, vs))
        # propagate splits upward
        while promo:
            promo = self._parent_insert(promo)
        self.header.mark_rows(np.array([0]))

    def _leaf_merge(self, leaf: int, ks: np.ndarray, vs: np.ndarray):
        hv = self.header.vol[0]
        row = self.nodes.vol[leaf]
        nk = int(row[C_NK])
        old_k = row[K0:K0 + nk].astype(np.int64)
        old_p = row[P0:P0 + nk].copy()
        ks32 = ks.astype(np.int32)
        # in-place updates for duplicates
        dup = np.isin(ks32, old_k.astype(np.int32))
        if dup.any():
            pos = np.searchsorted(old_k, ks[dup])
            recs = old_p[pos].astype(np.int64)
            self.records.vol[recs, :VALUE_WORDS] = vs[dup]
            self.records.mark_rows(recs)
        new_mask = ~dup
        if not new_mask.any():
            return []
        nks, nvs = ks[new_mask], vs[new_mask]
        f0 = int(hv[H_FRESH_RECS])
        recs = self._alloc_recs(len(nks))
        self.records.vol[recs, :VALUE_WORDS] = nvs
        # fresh-range slots and free-list reuses are two marks (the
        # write set's marks / saved_lines counters count them apart)
        fr = recs[recs >= f0]
        if fr.size:
            self.records.mark_rows(fr)
        rew = recs[recs < f0]
        if rew.size:
            self.records.mark_rows(rew)
        merged_k = np.concatenate([old_k, nks])
        merged_p = np.concatenate([old_p.astype(np.int64), recs])
        so = np.argsort(merged_k, kind="stable")
        merged_k, merged_p = merged_k[so], merged_p[so]
        hv[H_COUNT] += len(nks)
        if len(merged_k) <= MAX_KEYS:
            self._write_leaf(leaf, merged_k, merged_p)
            self._mark_nodes(np.array([leaf]))
            return []
        # split into chunks of SPLIT_FILL (last chunk takes remainder <= MAX)
        n = len(merged_k)
        cuts = list(range(SPLIT_FILL, n, SPLIT_FILL))
        if cuts and n - cuts[-1] < 2:
            cuts = cuts[:-1]
        chunks_k = np.split(merged_k, cuts)
        chunks_p = np.split(merged_p, cuts)
        n_new = len(chunks_k) - 1
        new_ids = self._alloc_nodes(n_new)
        self.nodes.vol[new_ids, C_LEAF] = 1
        old_next = int(row[C_NEXT])
        chain = [leaf] + new_ids.tolist()
        promos = []
        for idx, (nid, ck, cp) in enumerate(zip(chain, chunks_k, chunks_p)):
            self._write_leaf(nid, ck, cp)
            if idx > 0:
                promos.append((chain[idx - 1], int(ck[0]), nid))
        for a, b in zip(chain[:-1], chain[1:]):
            self.nodes.vol[a, C_NEXT] = b
            self.leaf_prev[b] = a
        self.nodes.vol[chain[-1], C_NEXT] = old_next
        if old_next != NULL:
            self.leaf_prev[old_next] = chain[-1]
        parent = int(row[C_PARENT])
        for nid in new_ids:
            self.nodes.vol[nid, C_PARENT] = parent
        self._mark_nodes(np.asarray(chain, np.int64))
        return promos

    def _write_leaf(self, nid: int, ks: np.ndarray, ps: np.ndarray) -> None:
        row = self.nodes.vol[nid]
        row[C_NK] = len(ks)
        row[K0:K1] = 0
        row[K0:K0 + len(ks)] = ks.astype(np.int32)
        row[P0:P1] = 0
        row[P0:P0 + len(ks)] = ps.astype(np.int32)

    def _parent_insert(self, promo: List[Tuple[int, int, int]]):
        """Insert (sep, right) pairs after `left` in their parents.  Returns
        next level's promotions."""
        hv = self.header.vol[0]
        dirty: List[int] = []
        by_parent: Dict[int, List[Tuple[int, int, int]]] = {}
        for left, sep, right in promo:
            parent = int(self.nodes.vol[left, C_PARENT])
            if parent == NULL:
                # splitting the root: create a new root holding just `left`
                # (0 separators); the (sep, right) pair is then inserted via
                # the regular path below.
                new_root = int(self._alloc_nodes(1)[0])
                r = self.nodes.vol[new_root]
                r[C_LEAF] = 0
                r[C_NK] = 0
                r[P0] = left
                self.nodes.vol[left, C_PARENT] = new_root
                hv[H_ROOT] = new_root
                dirty.append(new_root)
                parent = new_root
            # Set the right child's parent EAGERLY so later promotions in
            # this same pass (whose `left` is this `right`) resolve to the
            # correct parent.
            self.nodes.vol[right, C_PARENT] = parent
            if self.mode == "full":
                dirty.append(right)  # parent field is persistent
            by_parent.setdefault(parent, []).append((left, sep, right))
        next_promo: List[Tuple[int, int, int]] = []
        for parent, items in by_parent.items():
            row = self.nodes.vol[parent]
            nk = int(row[C_NK])
            keysv = row[K0:K0 + nk].astype(np.int64).tolist()
            ptrs = row[P0:P0 + nk + 1].astype(np.int64).tolist()
            for left, sep, right in items:
                at = ptrs.index(left) + 1
                keysv.insert(at - 1, sep)
                ptrs.insert(at, right)
            if len(keysv) <= MAX_KEYS:
                self._write_inner(parent, keysv, ptrs)
                dirty.append(parent)
                continue
            # split inner node into chunks of <= MAX_KEYS keys
            all_k, all_p = keysv, ptrs
            chunks: List[Tuple[List[int], List[int]]] = []
            seps: List[int] = []
            i = 0
            n = len(all_k)
            while True:
                take = min(SPLIT_FILL, n - i)
                if n - (i + take) == 0:
                    chunks.append((all_k[i:i + take], all_p[i:i + take + 1]))
                    break
                if n - (i + take + 1) < 1:  # leave >=1 key for the last chunk
                    take = n - i - 2
                chunks.append((all_k[i:i + take], all_p[i:i + take + 1]))
                seps.append(all_k[i + take])
                i += take + 1
            new_ids = self._alloc_nodes(len(chunks) - 1)
            node_ids = [parent] + new_ids.tolist()
            for nid, (ck, cp) in zip(node_ids, chunks):
                self._write_inner(nid, ck, cp)
                for c in cp:
                    self.nodes.vol[c, C_PARENT] = nid
                if self.mode == "full":
                    dirty.extend(int(c) for c in cp)
                dirty.append(nid)
            gp = int(self.nodes.vol[parent, C_PARENT])
            for nid in new_ids:
                self.nodes.vol[nid, C_PARENT] = gp
            for li, sep in enumerate(seps):
                next_promo.append((node_ids[li], sep, node_ids[li + 1]))
        self._mark_nodes(np.asarray(dirty, np.int64))
        return next_promo

    def _write_inner(self, nid: int, ks, ps) -> None:
        row = self.nodes.vol[nid]
        row[C_LEAF] = 0
        row[C_NK] = len(ks)
        row[K0:K1] = 0
        row[K0:K0 + len(ks)] = np.asarray(ks, np.int32)
        row[P0:P1] = 0
        row[P0:P0 + len(ps)] = np.asarray(ps, np.int32)

    # ---------------- delete ----------------
    def delete_batch(self, keys: np.ndarray) -> np.ndarray:
        with self.arena.epoch():
            return self._delete_batch(keys)

    def _delete_batch(self, keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, np.int64)
        hv = self.header.vol[0]
        if hv[H_FLAG] == 0 or hv[H_ROOT] == NULL:
            return np.zeros(len(keys), bool)
        leaves = self._descend(keys)
        ok = np.zeros(len(keys), bool)
        order = np.argsort(leaves, kind="stable")
        i = 0
        while i < len(order):
            j = i
            leaf = int(leaves[order[i]])
            while j < len(order) and leaves[order[j]] == leaf:
                j += 1
            sel = order[i:j]
            i = j
            row = self.nodes.vol[leaf]
            nk = int(row[C_NK])
            old_k = row[K0:K0 + nk].astype(np.int64)
            old_p = row[P0:P0 + nk].astype(np.int64)
            hit = np.isin(old_k, keys[sel])
            ok[sel] = np.isin(keys[sel], old_k)
            if not hit.any():
                continue
            self._free_recs.extend(old_p[hit].tolist())
            keep_k, keep_p = old_k[~hit], old_p[~hit]
            hv[H_COUNT] -= int(hit.sum())
            self._write_leaf(leaf, keep_k, keep_p)
            self._mark_nodes(np.array([leaf]))
            if len(keep_k) == 0:
                self._unlink_leaf(leaf)
        self.header.mark_rows(np.array([0]))
        return ok

    def _unlink_leaf(self, leaf: int) -> None:
        hv = self.header.vol[0]
        nxt = int(self.nodes.vol[leaf, C_NEXT])
        prv = int(self.leaf_prev[leaf])
        if prv != NULL:
            self.nodes.vol[prv, C_NEXT] = nxt
            self._mark_nodes(np.array([prv]))
        else:
            hv[H_FIRST_LEAF] = nxt
        if nxt != NULL:
            self.leaf_prev[nxt] = prv
        # detach from parent (recursively removing emptied inner nodes)
        self._remove_child(int(self.nodes.vol[leaf, C_PARENT]), leaf)
        self._free_nodes.append(leaf)

    def _remove_child(self, parent: int, child: int) -> None:
        hv = self.header.vol[0]
        if parent == NULL:
            if int(hv[H_ROOT]) == child:
                hv[H_ROOT] = NULL
                hv[H_FLAG] = 1  # initialized-but-empty
            return
        row = self.nodes.vol[parent]
        nk = int(row[C_NK])
        ptrs = row[P0:P0 + nk + 1].astype(np.int64).tolist()
        if child in ptrs:
            at = ptrs.index(child)
            keysv = row[K0:K0 + nk].astype(np.int64).tolist()
            del ptrs[at]
            if nk:
                del keysv[max(0, at - 1)]
            if not ptrs:
                self._remove_child(int(row[C_PARENT]), parent)
                self._free_nodes.append(parent)
                return
            self._write_inner(parent, keysv, ptrs)
            self._mark_nodes(np.array([parent]))

    # ---------------- traversal ----------------
    def leaves(self) -> np.ndarray:
        """Leaf ids in chain order via the shared vectorized primitive —
        the one place that knows how to enumerate the persistent NEXT
        chain (sliced at the committed fresh-water mark; empty for an
        empty tree)."""
        hv = self.header.vol[0]
        first = int(hv[H_FIRST_LEAF])
        if hv[H_FLAG] != 1 or first == NULL:
            return np.empty(0, np.int64)
        fresh = int(hv[H_FRESH_NODES])
        return chain_order(
            self.nodes.vol[:fresh, C_NEXT].astype(np.int64), first,
            method=self.chain_method)

    def keys_in_order(self) -> np.ndarray:
        """All keys in sorted (leaf-chain) order — one masked gather over
        the leaf rows, no per-leaf Python loop."""
        leaves = self.leaves()
        if leaves.size == 0:
            return np.empty(0, np.int64)
        rows = self.nodes.vol[leaves]
        nk = rows[:, C_NK]
        keymat = rows[:, K0:K1].astype(np.int64)
        valid = np.arange(MAX_KEYS)[None, :] < nk[:, None]
        return keymat[valid]

    def max_key(self) -> Optional[int]:
        """Largest key, read off the last non-empty leaf in O(chain
        enumeration) — no full key materialization."""
        leaves = self.leaves()
        if leaves.size == 0:
            return None
        nks = self.nodes.vol[leaves, C_NK]
        ne = np.nonzero(nks > 0)[0]
        if ne.size == 0:
            return None
        row = self.nodes.vol[leaves[ne[-1]]]
        return int(row[K0 + int(nks[ne[-1]]) - 1])

    # ---------------- crash / reconstruction ----------------
    def reconstruct(self) -> None:
        """Thin shim over the registered pure reconstructor — recovery
        paths route through core.recovery.RecoveryManager, which loads
        the regions once and times the stage."""
        self.header.load()
        self.nodes.load()
        self.records.load()
        rec.get("pstruct.bptree")(self)

    def _bulk_load_level(self, parents: np.ndarray, level: np.ndarray,
                         mins: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Write one inner level in a single vectorized pass: bucket ORDER
        children per parent, build all parent rows in one (P, 64) buffer,
        scatter children's parent pointers once."""
        n_parents = len(parents)
        n_level = len(level)
        kids = np.zeros((n_parents, ORDER), np.int64)
        kids.reshape(-1)[:n_level] = level
        kmins = np.zeros((n_parents, ORDER), np.int64)
        kmins.reshape(-1)[:n_level] = mins
        counts = np.minimum(ORDER, n_level - np.arange(n_parents) * ORDER)
        rowbuf = np.zeros((n_parents, 64), np.int32)
        rowbuf[:, C_NK] = (counts - 1).astype(np.int32)
        keymask = np.arange(MAX_KEYS)[None, :] < (counts - 1)[:, None]
        rowbuf[:, K0:K1] = np.where(keymask, kmins[:, 1:], 0).astype(np.int32)
        ptrmask = np.arange(ORDER)[None, :] < counts[:, None]
        rowbuf[:, P0:P0 + ORDER] = np.where(ptrmask, kids, 0).astype(np.int32)
        rowbuf[:, C_NEXT] = NULL
        rowbuf[:, C_PARENT] = NULL
        self.nodes.vol[parents] = rowbuf
        self.nodes.vol[level, C_PARENT] = np.repeat(
            parents.astype(np.int32), ORDER)[:n_level]
        return parents.astype(np.int64), kmins[:, 0]

    def _live_record_mask(self, leaves: np.ndarray) -> np.ndarray:
        """Records referenced by live leaves, one vectorized gather."""
        rec_live = np.zeros(self.cap_records, bool)
        if leaves.size:
            rows = self.nodes.vol[leaves]
            nk = rows[:, C_NK]
            recmat = rows[:, P0:P0 + MAX_KEYS].astype(np.int64)
            valid = np.arange(MAX_KEYS)[None, :] < nk[:, None]
            rec_live[recmat[valid]] = True
        return rec_live

    def _alloc_nodes_reconstruct(self, m: int, live: np.ndarray) -> np.ndarray:
        """Allocate inner nodes during rebuild from non-live slots."""
        free = np.nonzero(~live[:])[0][:m]
        if len(free) < m:
            raise MemoryError("bptree node arena exhausted during rebuild")
        live[free] = True
        arr = free.astype(np.int32)
        self.nodes.vol[arr] = 0
        self.nodes.vol[arr, C_NEXT] = NULL
        self.nodes.vol[arr, C_PARENT] = NULL
        hv = self.header.vol[0]
        hv[H_FRESH_NODES] = max(int(hv[H_FRESH_NODES]), int(arr.max()) + 1)
        return arr

    def _rebuild_volatile_only(self) -> None:
        """Fully-persistent mode: tree is complete in PM; rebuild leaf_prev
        and free lists."""
        hv = self.header.vol[0]
        fresh = int(hv[H_FRESH_NODES])
        self.leaf_prev[:] = NULL
        leaves = self.leaves()
        if leaves.size == 0:
            return
        self.leaf_prev[leaves[1:]] = leaves[:-1].astype(np.int32)
        live = np.zeros(self.cap_nodes, bool)
        live[leaves] = True
        cur = leaves
        while True:   # one round per tree LEVEL (O(log n) rounds)
            parents = np.unique(self.nodes.vol[cur, C_PARENT])
            parents = parents[parents != NULL]
            if parents.size == 0:
                break
            live[parents] = True
            cur = parents
        self._free_nodes = np.nonzero(~live[:fresh])[0].tolist()
        rec_live = self._live_record_mask(leaves)
        self._free_recs = np.nonzero(
            ~rec_live[:int(hv[H_FRESH_RECS])])[0].tolist()

    # ---------------- verification ----------------
    def check_invariants(self) -> None:
        """Leaf-chain order/sortedness/count — vectorized over the whole
        chain (one chain_order + masked matrix checks)."""
        hv = self.header.vol[0]
        if hv[H_FLAG] == 0 or hv[H_ROOT] == NULL:
            return
        leaves = self.leaves()
        if leaves.size == 0:
            assert int(hv[H_COUNT]) == 0, int(hv[H_COUNT])
            return
        rows = self.nodes.vol[leaves]
        assert (rows[:, C_LEAF] == 1).all(), "non-leaf on leaf chain"
        nk = rows[:, C_NK]
        keymat = rows[:, K0:K1].astype(np.int64)
        valid = np.arange(MAX_KEYS)[None, :] < nk[:, None]
        sorted_ok = (np.diff(keymat, axis=1) > 0) | ~valid[:, 1:]
        assert sorted_ok.all(), "leaf keys not sorted"
        ne = nk > 0
        firsts = keymat[ne, 0]
        lasts = keymat[ne, nk[ne] - 1]
        assert (firsts[1:] > lasts[:-1]).all(), "leaf chain out of order"
        total = int(nk.sum())
        assert total == int(hv[H_COUNT]), (total, int(hv[H_COUNT]))

    def flush_stats(self) -> FlushStats:
        return self.arena.stats


@rec.register("pstruct.bptree")
def _reconstruct_bptree(t: "BPTree") -> dict:
    """Pure rebuild (paper §IV-D3): enumerate leaves via the persistent
    NEXT chain (shared chain_order primitive — count derived by pointer
    doubling, cycle-checked), then bulk-load inner levels bucketing ORDER
    children per parent, one vectorized pass per level."""
    hv = t.header.vol[0]
    t.quarantined = set()
    if hv[H_FLAG] != 1:
        # uninitialized image recovers as an empty tree (§IV-D3 validity
        # check on the root node)
        hv[:] = 0
        hv[H_ROOT] = NULL
        hv[H_FIRST_LEAF] = NULL
        t.leaf_prev[:] = NULL
        t._free_nodes = []
        t._free_recs = []
        return {"mode": t.mode, "count": 0}
    salvage = bool(getattr(t.arena, "_salvage", False))
    bad_nodes = (_salvage_bad_rows(t.arena, t.nodes) if salvage
                 else np.empty(0, np.int64))
    bad_recs = (_salvage_bad_rows(t.arena, t.records) if salvage
                else np.empty(0, np.int64))
    bad_nodes = bad_nodes[bad_nodes < t.cap_nodes]
    bad_recs = bad_recs[bad_recs < t.cap_records]
    corrupt = int(bad_nodes.size + bad_recs.size)
    if t.mode == "full":
        if corrupt:
            # a fully-persistent tree has parent/child pointers woven
            # through every row — there is no committed-prefix remainder
            # to keep, so the whole stage quarantines
            raise CorruptLineError(
                t.nodes.name if bad_nodes.size else t.records.name,
                bad_nodes if bad_nodes.size else bad_recs,
                detail="fully-persistent tree: no salvageable remainder")
        t._rebuild_volatile_only()
        return {"mode": "full", "count": int(hv[H_COUNT])}
    detail = {"mode": "partly"}
    # 1. enumerate leaves via the persistent next chain
    if bad_nodes.size:
        # salvage: keep the maximal leaf-chain prefix that never touches
        # a corrupt row — everything downstream is unreachable without
        # trusting rotten bytes
        img = np.asarray(t.arena._pimage(t.nodes))
        badset = set(bad_nodes.tolist())
        fresh_n = int(hv[H_FRESH_NODES])
        seen: set = set()
        prefix: List[int] = []
        cur = int(hv[H_FIRST_LEAF])
        while 0 <= cur < fresh_n and cur not in badset and cur not in seen:
            seen.add(cur)
            prefix.append(cur)
            cur = int(img[cur, C_NEXT])
        leaves = np.asarray(prefix, np.int64)
        if leaves.size:
            t.nodes.vol[leaves[-1], C_NEXT] = NULL  # volatile chain cut
        # name the lost keys best-effort: intact-but-unreachable leaf
        # rows are readable even though the chain can no longer prove
        # them live (stale freed leaves over-quarantine only keys that
        # are absent anyway — refusal stays conservative); keys inside
        # the corrupt rows themselves are unreadable and stay anonymous
        for r in range(fresh_n):
            if r in seen or r in badset or img[r, C_LEAF] != 1:
                continue
            nk = min(int(img[r, C_NK]), MAX_KEYS)
            t.quarantined.update(int(k) for k in img[r, K0:K0 + nk])
    else:
        try:
            leaves = t.leaves()
        except (RuntimeError, ValueError) as e:
            if not salvage:
                raise
            raise CorruptLineError(t.nodes.name, np.empty(0, np.int64),
                                   detail=f"leaf chain rebuild: {e}") from e
    if leaves.size == 0:
        hv[H_ROOT] = NULL
        if corrupt:
            hv[H_FIRST_LEAF] = NULL
            hv[H_COUNT] = 0
            t.leaf_prev[:] = NULL
            live = np.zeros(t.cap_nodes, bool)
            live[bad_nodes] = True  # corrupt rows are never reusable
            t._free_nodes = np.nonzero(
                ~live[:int(hv[H_FRESH_NODES])])[0].tolist()
            rec_live = np.zeros(t.cap_records, bool)
            rec_live[bad_recs] = True
            t._free_recs = np.nonzero(
                ~rec_live[:int(hv[H_FRESH_RECS])])[0].tolist()
            detail.update(count=0, quarantined=True, degraded=True,
                          quarantined_rows=corrupt,
                          quarantined_keys=sorted(t.quarantined))
            return detail
        return {"mode": "partly", "count": 0}
    # 2. leaf prev (volatile redundancy)
    t.leaf_prev[:] = NULL
    t.leaf_prev[leaves[1:]] = leaves[:-1].astype(np.int32)
    # 2b. salvage: drop leaf slots whose record row is corrupt — the key
    #     is readable from the intact leaf, so it quarantines by name
    if bad_recs.size:
        badrec = np.zeros(t.cap_records, bool)
        badrec[bad_recs] = True
        for lf in leaves.tolist():
            row = t.nodes.vol[lf]
            nk = int(row[C_NK])
            ptrs = row[P0:P0 + nk].astype(np.int64)
            hit = badrec[ptrs]
            if not hit.any():
                continue
            t.quarantined.update(int(k) for k in row[K0:K0 + nk][hit])
            keep = ~hit
            kept = int(keep.sum())
            row[K0:K0 + kept] = row[K0:K0 + nk][keep]
            row[P0:P0 + kept] = ptrs[keep].astype(np.int32)
            row[K0 + kept:K0 + nk] = 0
            row[P0 + kept:P0 + nk] = 0
            row[C_NK] = kept
    if corrupt:
        rows = t.nodes.vol[leaves]
        nk = rows[:, C_NK]
        keymat = rows[:, K0:K1].astype(np.int64)
        valid = np.arange(MAX_KEYS)[None, :] < nk[:, None]
        t.quarantined -= set(keymat[valid].tolist())  # survivors aren't lost
        hv[H_COUNT] = int(nk.sum())
        detail.update(degraded=True, quarantined_rows=corrupt,
                      quarantined_keys=sorted(t.quarantined))
    # 3. bulk-load inner levels, bucket size = ORDER (paper §IV-D:
    #    maximum bucket -> fewest levels, matches 256B granularity);
    #    subtree minima are the separators, tracked per level
    level = leaves
    mins = t.nodes.vol[leaves, K0].astype(np.int64)
    # wipe any stale inner rows: everything not a live leaf is free
    live = np.zeros(t.cap_nodes, bool)
    live[level] = True
    live[bad_nodes] = True  # corrupt rows are never reusable
    while len(level) > 1:
        n_parents = (len(level) + ORDER - 1) // ORDER
        parents = t._alloc_nodes_reconstruct(n_parents, live)
        level, mins = t._bulk_load_level(parents, level, mins)
    root = int(level[0])
    t.nodes.vol[root, C_PARENT] = NULL
    hv[H_ROOT] = root
    # 4. free lists: records referenced by live leaves are live
    t._free_nodes = np.nonzero(~live[:int(hv[H_FRESH_NODES])])[0].tolist()
    rec_live = t._live_record_mask(leaves)
    rec_live[bad_recs] = True  # corrupt rows are never reusable
    t._free_recs = np.nonzero(
        ~rec_live[:int(hv[H_FRESH_RECS])])[0].tolist()
    detail.update(count=int(hv[H_COUNT]), leaves=int(leaves.size),
                  chain=chain_method(int(hv[H_FRESH_NODES]), None,
                                     getattr(t, "chain_method", "auto")))
    return detail
