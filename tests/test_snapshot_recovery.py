"""Incremental order snapshots (DESIGN.md §10): torn-snapshot-record
sweep, suffix-only replay, env gating, accounting isolation, and the
device-side verify.

The torn-record sweep is the crash-point fuzzer's snapshot axis: power
fails mid-snapshot-append at every epoch boundary, on one shard and on
a sharded substrate.  Recovery must refuse the torn snapshot
(verify-always adoption) and land on EXACTLY the state a full
contraction rebuild recovers.
"""
import numpy as np
import pytest

from repro.core.arena import (SNAP_SLOTS, open_arena, snap_record_pack,
                              snap_record_parse, snapshot_enabled)
from repro.core.recovery import ChainSnapshot, RecoveryManager, chain_order
from repro.pstruct.dll import DoublyLinkedList, _reconstruct_dll
from repro.pstruct.hashmap import Hashmap, _reconstruct_hashmap

SHARDS = [1, 4]


# ----------------------------------------------------------- helpers

def _build(n_shards, snapshot=True):
    layout = {}
    layout.update(DoublyLinkedList.layout(256, name="dll",
                                          snapshot=snapshot))
    layout.update(Hashmap.layout(512, name="hm", snapshot=snapshot))
    a = open_arena(None, layout, n_shards=n_shards)
    return (a, DoublyLinkedList(a, 256, name="dll", snapshot=snapshot),
            Hashmap(a, 512, name="hm", snapshot=snapshot))


def _script(n_ops, seed=0):
    """Mixed append/insert/delete workload: every op is one epoch +
    commit, so every boundary seals a snapshot record."""
    rng = np.random.default_rng(seed)
    ops = []
    key = 0
    for i in range(n_ops):
        m = int(rng.integers(2, 7))
        vals = rng.integers(0, 1 << 30, (m, 7)).astype(np.int64)
        keys = np.arange(key, key + m, dtype=np.int64)
        key += m
        ops.append(("dll" if i % 3 == 0 else ("hm" if i % 3 == 1
                                              else "dll_del"),
                    keys, vals))
    return ops


def _apply(d, h, op, dll_ids):
    kind, keys, vals = op
    if kind == "dll":
        dll_ids.extend(d.append_batch(vals).tolist())
    elif kind == "hm":
        h.insert_batch(keys, vals)
    elif kind == "dll_del" and len(dll_ids) >= 2:
        doomed = np.asarray(dll_ids[::7][:2], np.int64)
        d.delete_batch(doomed)
        for x in doomed.tolist():
            dll_ids.remove(x)
    else:
        dll_ids.extend(d.append_batch(vals).tolist())


def _state(d, h, hm_keys):
    order = d.to_list()
    if hm_keys:
        ok, got = h.find_batch(np.asarray(hm_keys, np.int64))
    else:
        ok, got = np.ones(0, bool), np.zeros((0, 7), np.int64)
    return {"order": order.copy(), "data": d.data[order].copy(),
            "hm_size": h.size, "hm_ok": ok.copy(), "hm_vals": got.copy()}


def _reload(a, d, h):
    a.reopen()
    d.header.load(); d.nodes.load()
    h.header.load(); h.entries.load()
    if d.snapshot:
        d.snapring.load(); d.snaprec.load()
    if h.snapshot:
        h.snapbkt.load(); h.snapchain.load(); h.snaprec.load()


def _assert_state(d, h, hm_keys, want):
    got = _state(d, h, hm_keys)
    np.testing.assert_array_equal(got["order"], want["order"])
    np.testing.assert_array_equal(got["data"], want["data"])
    assert got["hm_size"] == want["hm_size"]
    assert got["hm_ok"].all() == want["hm_ok"].all()
    np.testing.assert_array_equal(got["hm_vals"], want["hm_vals"])


# ----------------------------------- torn-snapshot-record crash sweep

@pytest.mark.parametrize("n_shards", SHARDS)
@pytest.mark.parametrize("tear", ["record", "all"])
def test_torn_snapshot_record_sweep(n_shards, tear):
    """Crash mid-snapshot-append at EVERY epoch boundary: the newest
    record line lands garbled ("record") or the whole record ring plus
    half the mirror lands garbled ("all").  Verify-always adoption must
    refuse anything inconsistent and recover bit-identical state — via
    an older record + suffix replay, or the full contraction/rebuild
    fallback."""
    ops = _script(12)
    for boundary in range(len(ops)):
        a, d, h = _build(n_shards)
        hm_keys, dll_ids = [], []
        for i in range(boundary + 1):
            _apply(d, h, ops[i], dll_ids)
            if ops[i][0] == "hm":
                hm_keys.extend(ops[i][1].tolist())
            a.commit()
        want = _state(d, h, hm_keys)
        a.crash()
        _reload(a, d, h)
        # garble snapshot bytes as loaded — the mid-append torn image
        newest = max((r for s in range(SNAP_SLOTS)
                      if (r := snap_record_parse(d.snaprec.vol[s]))
                      is not None), key=lambda r: r[1], default=None)
        if tear == "record":
            if newest is not None:
                d.snaprec.vol[newest[1] % SNAP_SLOTS, 3:] = -777
                h.snaprec.vol[newest[1] % SNAP_SLOTS, 3:] = -777
        else:
            d.snaprec.vol[:, 2:] = -777
            h.snaprec.vol[:, 2:] = -777
            d.snapring.vol[::2] = 2 ** 40
            h.snapchain.vol[::2] = 2 ** 40
        det_d = _reconstruct_dll(d)
        det_h = _reconstruct_hashmap(h)
        if tear == "all":
            assert det_d["chain"] in ("double", "contract")
            assert det_h["chain"] == "rebuild"
        _assert_state(d, h, hm_keys, want)


# ------------------------------------------------- suffix-only replay

@pytest.mark.parametrize("n_shards", SHARDS)
def test_suffix_replay_length_matches_delta(n_shards):
    """Tear only the newest record: recovery seeds from the previous
    record and replays exactly the rows committed after it."""
    a, d, h = _build(n_shards)
    d.append_batch(np.arange(280).reshape(40, 7).astype(np.int64))
    a.commit()
    k = np.arange(50, dtype=np.int64)
    h.insert_batch(k, np.tile(k[:, None], (1, 7)))
    a.commit()
    d.append_batch(np.ones((9, 7), np.int64))          # suffix: 9 nodes
    a.commit()
    h.insert_batch(k + 100, np.zeros((50, 7), np.int64))  # suffix: 50
    a.commit()
    want = _state(d, h, k.tolist() + (k + 100).tolist())
    a.crash()
    _reload(a, d, h)
    for reg in (d.snaprec, h.snaprec):
        newest = max((r for s in range(SNAP_SLOTS)
                      if (r := snap_record_parse(reg.vol[s])) is not None),
                     key=lambda r: r[1])
        reg.vol[newest[1] % SNAP_SLOTS, 3:] = -777
    det_d = _reconstruct_dll(d)
    det_h = _reconstruct_hashmap(h)
    assert det_d["chain"] == "snapshot" and det_d["replayed"] == 9
    assert det_h["chain"] == "snapshot" and det_h["replayed"] == 50
    _assert_state(d, h, k.tolist() + (k + 100).tolist(), want)


@pytest.mark.parametrize("n_shards", SHARDS)
def test_clean_recovery_adopts_without_replay(n_shards):
    a, d, h = _build(n_shards)
    d.append_batch(np.arange(70).reshape(10, 7).astype(np.int64))
    k = np.arange(30, dtype=np.int64)
    h.insert_batch(k, np.tile(k[:, None], (1, 7)))
    a.commit()
    a.crash()
    _reload(a, d, h)
    det_d = _reconstruct_dll(d)
    det_h = _reconstruct_hashmap(h)
    assert det_d == {"mode": "partly", "count": 10, "chain": "snapshot",
                     "replayed": 0}
    assert det_h["chain"] == "snapshot" and det_h["replayed"] == 0


def test_persisted_record_tear_survives_restart():
    """Tear the record at the PERSISTED layer (no reliance on the
    volatile load path) and reconstruct through fresh objects — the
    cross-process shape of the fuzzer."""
    a, d, h = _build(1)
    d.append_batch(np.arange(70).reshape(10, 7).astype(np.int64))
    a.commit()
    d.append_batch(np.ones((5, 7), np.int64))
    a.commit()
    want_order = d.to_list().copy()
    newest = max((r for s in range(SNAP_SLOTS)
                  if (r := snap_record_parse(d.snaprec.vol[s])) is not None),
                 key=lambda r: r[1])
    d.snaprec._pview()[newest[1] % SNAP_SLOTS, 4:] = -777
    a.crash()
    _reload(a, d, h)
    det = _reconstruct_dll(d)
    assert det["chain"] == "snapshot" and det["replayed"] == 5
    np.testing.assert_array_equal(d.to_list(), want_order)


# ------------------------------------------- gating + layout parity

def test_env_gate_and_layout_parity(monkeypatch):
    monkeypatch.setenv("REPRO_SNAPSHOT", "0")
    assert not snapshot_enabled(None)
    assert snapshot_enabled(True)          # explicit flag wins
    off = DoublyLinkedList.layout(64, name="x")
    assert not any(".snap" in n for n in off)
    off_hm = Hashmap.layout(64, name="x")
    assert not any(".snap" in n for n in off_hm)
    monkeypatch.setenv("REPRO_SNAPSHOT", "1")
    assert snapshot_enabled(None)
    assert not snapshot_enabled(False)
    on = DoublyLinkedList.layout(64, name="x")
    assert {n for n in on} - {n for n in off} == {"x.snapring", "x.snaprec"}


@pytest.mark.parametrize("n_shards", SHARDS)
def test_snapshot_off_recovery_identical_states(n_shards):
    """The REPRO_SNAPSHOT=0 rerun axis: recovered structure state must
    be identical with snapshots on and off (the snapshot is pure
    derivable redundancy)."""
    states = {}
    for snap in (True, False):
        a, d, h = _build(n_shards, snapshot=snap)
        hm_keys, dll_ids = [], []
        for op in _script(8):
            _apply(d, h, op, dll_ids)
            if op[0] == "hm":
                hm_keys.extend(op[1].tolist())
            a.commit()
        a.crash()
        _reload(a, d, h)
        _reconstruct_dll(d)
        _reconstruct_hashmap(h)
        states[snap] = _state(d, h, hm_keys)
    np.testing.assert_array_equal(states[True]["order"],
                                  states[False]["order"])
    np.testing.assert_array_equal(states[True]["data"],
                                  states[False]["data"])
    np.testing.assert_array_equal(states[True]["hm_vals"],
                                  states[False]["hm_vals"])
    assert states[True]["hm_size"] == states[False]["hm_size"]


# ------------------------------------------------ accounting isolation

@pytest.mark.parametrize("n_shards", SHARDS)
def test_snapshot_lines_accounted_separately(n_shards):
    """snapshot_lines is a separate counter: data lines / bytes / dedup
    savings are bit-comparable between snapshot-on and snapshot-off runs
    of the same workload."""
    stats = {}
    for snap in (True, False):
        a, d, h = _build(n_shards, snapshot=snap)
        hm_keys, dll_ids = [], []
        for op in _script(10, seed=3):
            _apply(d, h, op, dll_ids)
            a.commit()
        stats[snap] = a.stats
    on, off = stats[True], stats[False]
    assert on.snapshot_lines > 0
    assert off.snapshot_lines == 0
    assert on.lines == off.lines
    assert on.bytes == off.bytes
    assert on.saved_lines == off.saved_lines
    assert on.calls == off.calls


# --------------------------------------------- manager stage details

@pytest.mark.parametrize("n_shards", SHARDS)
def test_manager_stage_detail_reports_chain(n_shards):
    a, d, h = _build(n_shards)
    d.append_batch(np.arange(70).reshape(10, 7).astype(np.int64))
    k = np.arange(20, dtype=np.int64)
    h.insert_batch(k, np.tile(k[:, None], (1, 7)))
    a.commit()
    a.crash()
    mgr = RecoveryManager(a)
    mgr.add("dll", "pstruct.dll", d)
    mgr.add("hm", "pstruct.hashmap", h)
    report = mgr.recover()
    details = {s.name: s.detail for s in report.stages}
    assert details["dll"]["chain"] == "snapshot"
    assert details["dll"]["replayed"] == 0
    assert details["hm"]["chain"] == "snapshot"
    assert details["hm"]["replayed"] == 0


# ------------------------------------------------- host + device seed

def test_chain_order_snapshot_seed_host():
    n = 300
    perm = np.random.default_rng(1).permutation(n)[:120]
    nxt = np.full(n, -1, np.int64)
    nxt[perm[:-1]] = perm[1:]
    head = int(perm[0])
    s = ChainSnapshot(perm)
    got = chain_order(nxt, head, 120, snapshot=s)
    np.testing.assert_array_equal(got, perm)
    assert s.outcome == "snapshot"
    bad = perm.copy()
    bad[5] = bad[6]
    s2 = ChainSnapshot(bad)
    got2 = chain_order(nxt, head, 120, snapshot=s2)
    np.testing.assert_array_equal(got2, perm)
    assert s2.outcome != "snapshot" and s2.replayed == 120


def test_chain_order_snapshot_seed_device():
    from repro.kernels import chain_order as co
    n = 600
    perm = np.random.default_rng(2).permutation(n)[:200]
    nxt = np.full(n, -1, np.int64)
    nxt[perm[:-1]] = perm[1:]
    head = int(perm[0])
    calls0 = co.KERNEL_CALLS
    s = ChainSnapshot(perm)
    got = co.chain_order_device(nxt, head, snapshot=s, interpret=True)
    np.testing.assert_array_equal(got, perm)
    assert s.outcome == "snapshot"
    assert co.KERNEL_CALLS - calls0 == 1     # one verify gather, no rank
    # a strict prefix must NOT be adopted (chain continues past it)
    s2 = ChainSnapshot(perm[:50])
    got2 = co.chain_order_device(nxt, head, snapshot=s2, interpret=True)
    np.testing.assert_array_equal(got2, perm)
    assert s2.outcome != "snapshot" and s2.replayed == 200


def test_record_checksum_rejects_bitflips():
    rec = snap_record_pack(3, 7, 10, 20, 30)
    assert snap_record_parse(rec) == (3, 7, 10, 20, 30, 0)
    for w in range(8):
        bad = rec.copy()
        bad[w] ^= 1 << 17
        assert snap_record_parse(bad) is None
