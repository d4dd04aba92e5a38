"""Integrity-checked arenas: media-fault injection, scrub, and salvage
recovery (DESIGN.md §13).

Invariant families:

* checksum unification: the snapshot record checksum, the journal batch
  checksum, and the integrity sidecar all speak ONE vectorized mixer
  (``core.arena.mix_checksums``);
* detection: a single flipped bit or stuck-at line in any COMMITTED
  data row is caught by ``Arena.scrub()`` (and by the paged fault path
  before a corrupt block is admitted), across 1 and 4 shards, paged
  and resident — with zero false positives on clean
  arenas at every commit point (scrub under live traffic);
* corruption x crash double failure: a crash (power-loss or torn
  flavor) composed with a media fault must end detected-or-harmless —
  either scrub names the corruption, or recovery lands bit-identically
  to an uncorrupted twin;
* typed media losses: shard truncation/removal -> ``ShardLossError``
  at fresh open; scribbled header/manifest magic -> ``ManifestError``;
* salvage: ``recover(salvage=True)`` quarantines what corruption
  proves untrustworthy and recovers every other structure of a mixed
  arena; the serving layers refuse exactly the quarantined keys
  (``QuarantinedError``) until readmitted.
"""
import numpy as np
import pytest

from repro.core import faultinject as fi
from repro.core.arena import (LINE, CorruptLineError, IntegrityError,
                              ManifestError, QuarantinedError,
                              ShardLossError, mix_checksums, open_arena,
                              sidecar_checksums, snap_checksum)
from repro.core.recovery import RecoveryManager
from repro.pstruct.bptree import BPTree
from repro.pstruct.dll import DoublyLinkedList
from repro.pstruct.hashmap import H_FRESH, KEY_NULL, Hashmap
from repro.serve.journal import _batch_cksum

SHARDS = [1, 4]


# ---------------------------------------------------------------- helpers


def _mixed(path, n_shards, mode="partly", **kw):
    layout = {}
    layout.update(DoublyLinkedList.layout(256, mode, name="dll"))
    layout.update(BPTree.layout(256, 1024, mode, name="bt"))
    layout.update(Hashmap.layout(512, mode, name="hm"))
    a = open_arena(path, layout, n_shards=n_shards, **kw)
    return (a, DoublyLinkedList(a, 256, mode, name="dll"),
            BPTree(a, 256, 1024, mode, name="bt"),
            Hashmap(a, 512, mode, name="hm"))


def _script(n_ops, seed=0):
    rng = np.random.default_rng(seed)
    ops, key = [], 0
    for i in range(n_ops):
        m = int(rng.integers(2, 7))
        vals = rng.integers(0, 1 << 30, (m, 7)).astype(np.int64)
        keys = np.arange(key, key + m, dtype=np.int64)
        key += m
        ops.append(("dll" if i % 3 == 0 else ("bt" if i % 3 == 1 else "hm"),
                    keys, vals))
    return ops


def _apply(d, t, h, op):
    kind, keys, vals = op
    if kind == "dll":
        d.append_batch(vals)
    elif kind == "bt":
        t.insert_batch(keys, vals)
    else:
        h.insert_batch(keys, vals)


def _run(a, d, t, h, ops):
    for op in ops:
        with a.epoch():
            _apply(d, t, h, op)
        a.commit()


def _manager(a, d, t, h):
    mgr = RecoveryManager(a)
    mgr.add("dll", "pstruct.dll", d)
    mgr.add("bt", "pstruct.bptree", t)
    mgr.add("hm", "pstruct.hashmap", h)
    return mgr


def _fingerprint(d, t, h):
    """Full logical state of all three structures.  Region-byte
    comparison would be too strong: a flip in a never-flushed row is
    undetectable by design (the 0 sidecar sentinel) and lingers as
    dead-space garbage — harmless means the LOGICAL state matches."""
    fp = {"dll.values": np.asarray(d.to_list()).copy(),
          "bt.keys": t.keys_in_order().copy()}
    fresh = int(h.header.vol[0, H_FRESH])
    ks = np.asarray(h.keys[:fresh])
    vs = np.asarray(h.values[:fresh])
    live = ks != KEY_NULL
    o = np.argsort(ks[live], kind="stable")
    fp["hm.keys"] = ks[live][o].copy()
    fp["hm.values"] = np.asarray(vs)[live][o].copy()
    return fp


# --------------------------------------------- checksum unification


def test_checksum_helpers_agree():
    rng = np.random.default_rng(7)
    rows = rng.integers(-(1 << 60), 1 << 60, (32, 8)).astype(np.int64)
    # the journal batch checksum IS the shared mixer over words 0..6
    np.testing.assert_array_equal(_batch_cksum(rows),
                                  mix_checksums(rows[:, :7]))
    # the scalar snapshot checksum is its row-wise special case
    for r in rows[:4]:
        assert snap_checksum(r) == int(mix_checksums(r[None, :7])[0])
    # the sidecar vectorization agrees with the per-line mixer
    arr = rng.integers(-(1 << 60), 1 << 60, (16, 16)).astype(np.int64)
    sc = sidecar_checksums(arr, 2)          # 128 B rows = 2 lines
    assert sc.shape == (16, 2)
    for i in range(4):
        for c in range(2):
            want = int(mix_checksums(arr[i, c * 8:(c + 1) * 8][None])[0])
            got = int(sc[i, c])
            assert got == want or (want == 0 and got == 1)


def test_checksum_zero_is_reserved_sentinel():
    # a computed 0 must nudge away from the never-written sentinel
    z = np.zeros((4, 8), np.int64)
    assert (sidecar_checksums(z, 1) != 0).all()


# ----------------------------------------------------- detection


@pytest.mark.parametrize("n_shards", SHARDS)
@pytest.mark.parametrize("paged", [False, True])
def test_scrub_detects_flip_and_stuck_line(tmp_path, n_shards, paged):
    kw = dict(paged=True, block_bytes=256, cache_blocks=8) if paged else {}
    a, d, t, h = _mixed(str(tmp_path / "a.pm"), n_shards, **kw)
    _run(a, d, t, h, _script(12, seed=1))
    row = int(d.order()[2])
    a.crash()
    off = fi.flip_bits(a, a.regions["dll.nodes"], row, byte=8, mask=0x01)
    a.reopen()
    bad = a.scrub()
    assert list(bad) == ["dll.nodes"] and row in bad["dll.nodes"].tolist()
    fi.flip_bits(a, a.regions["dll.nodes"], row, byte=8, mask=0x01)  # undo
    assert a.scrub() == {}, "flip_bits is not an involution"
    # stuck-at line on a hashmap entry row
    hrow = 2
    fi.stuck_line(a, a.regions["hm.entries"], hrow, line=0, value=0xAB)
    bad = a.scrub()
    assert list(bad) == ["hm.entries"] and hrow in bad["hm.entries"].tolist()
    with pytest.raises(CorruptLineError):
        a.scrub(raise_on_error=True)
    assert off >= 0


def test_paged_fault_path_verifies_blocks(tmp_path):
    a, d, t, h = _mixed(str(tmp_path / "a.pm"), 1, paged=True,
                        block_bytes=256, cache_blocks=4)
    _run(a, d, t, h, _script(12, seed=2))
    row = int(d.order()[1])
    a.crash()
    fi.flip_bits(a, a.regions["dll.nodes"], row, byte=8, mask=0x04)
    a.reopen()
    # a demand fault that assembles the corrupt row's block must refuse
    # to admit it
    with pytest.raises(CorruptLineError) as ei:
        a.regions["dll.nodes"].read_rows(np.array([row], np.int64))
    assert ei.value.region == "dll.nodes"
    assert row in np.asarray(ei.value.rows).tolist()


def test_integrity_off_layout_and_bytes_are_identical(tmp_path):
    """Integrity-off arenas lay out exactly the pre-integrity image:
    same region offsets, no sidecars, and bit-identical committed bytes
    for the same traffic (the sidecar is a pure suffix)."""
    ops = _script(10, seed=3)
    arenas = {}
    for integ in (False, True):
        a, d, t, h = _mixed(str(tmp_path / f"i{int(integ)}.pm"), 1,
                            integrity=integ)
        _run(a, d, t, h, ops)
        arenas[integ] = a
    offs_off = {n: r.offset for n, r in arenas[False].regions.items()}
    offs_on = {n: r.offset for n, r in arenas[True].regions.items()
               if not n.endswith(".integ")}
    assert offs_off == offs_on
    assert not any(n.endswith(".integ") for n in arenas[False].regions)
    assert any(n.endswith(".integ") for n in arenas[True].regions)
    for n, r in arenas[False].regions.items():
        np.testing.assert_array_equal(
            np.asarray(arenas[True]._pimage(arenas[True].regions[n])),
            np.asarray(arenas[False]._pimage(r)), err_msg=n)
    assert arenas[False].stats.integrity_lines == 0
    assert arenas[True].stats.integrity_lines > 0
    assert arenas[True].stats.lines == arenas[False].stats.lines


# --------------------------------------- scrub under live traffic


@pytest.mark.parametrize("n_shards", SHARDS)
def test_scrub_under_traffic_no_false_positives(tmp_path, n_shards):
    """Data and sidecar always move in the same flush phase, so a
    scrub between ANY two commits — and after any crash point — must
    come back clean."""
    a, d, t, h = _mixed(str(tmp_path / "a.pm"), n_shards)
    for i, op in enumerate(_script(10, seed=4)):
        with a.epoch():
            _apply(d, t, h, op)
        a.commit()
        assert a.scrub() == {}, f"false positive after commit {i}"
    # crash + recover, scrub stays clean
    a.crash()
    _manager(a, d, t, h).recover()
    assert a.scrub() == {}


def test_mid_scrub_crash_is_harmless(tmp_path):
    """Scrub is pure reads: crashing between per-region verify calls
    leaves nothing behind — recovery and a full re-scrub behave exactly
    as if the interrupted scrub never ran."""
    a, d, t, h = _mixed(str(tmp_path / "a.pm"), 1)
    _run(a, d, t, h, _script(8, seed=5))
    covered = [n for n, r in a.regions.items() if r._integ is not None]
    assert len(covered) >= 2
    for n in covered[: len(covered) // 2]:     # half a scrub...
        assert a.verify_region(n).size == 0
    a.crash()                                  # ...then power loss
    rep = _manager(a, d, t, h).recover()
    assert rep.valid
    assert a.scrub() == {}


# -------------------------- corruption x crash double failure sweep


@pytest.mark.parametrize("n_shards", SHARDS)
@pytest.mark.parametrize("torn", [False, True])
def test_corruption_crash_double_failure(tmp_path, n_shards, torn):
    """Satellite sweep: compose a crash (power-loss or torn data-phase
    flavor) with a one-byte media fault in a data region and require
    DETECTED-OR-BIT-IDENTICAL — either scrub names the corruption, or
    the fault landed in dead bytes and recovery matches an uncorrupted
    twin bit-for-bit."""
    ops = _script(8, seed=6)
    targets = [("dll.nodes", 1), ("bt.nodes", 0), ("hm.entries", 0),
               ("dll.nodes", 200), ("hm.entries", 400)]  # dead tails too
    stage_of = {"dll.nodes": "dll", "bt.nodes": "bt", "hm.entries": "hm"}

    def _crash(a, d, t, h, boundary):
        _run(a, d, t, h, ops[: boundary + 1])
        if boundary + 1 < len(ops):
            with a.epoch():
                _apply(d, t, h, ops[boundary + 1])
                if torn:
                    a.writeset.flush(include_meta=False)
                a.crash()
        else:
            a.crash()

    for boundary in (3, len(ops) - 1):
        # twin A: same crash, no corruption
        a, d, t, h = _mixed(str(tmp_path / f"tw{boundary}.pm"), n_shards)
        _crash(a, d, t, h, boundary)
        _manager(a, d, t, h).recover()
        ref = _fingerprint(d, t, h)
        for j, (reg, row) in enumerate(targets):
            b, d2, t2, h2 = _mixed(
                str(tmp_path / f"b{boundary}.{j}.pm"), n_shards)
            _crash(b, d2, t2, h2, boundary)
            fi.flip_bits(b, b.regions[reg], row, byte=3, mask=0x80)
            rep = _manager(b, d2, t2, h2).recover(salvage=True)
            named = set(rep.quarantined) | set(rep.degraded)
            if named:
                # DETECTED: only the struck structure may be named
                assert named == {stage_of[reg]}, (reg, row, named)
                bad = b.scrub()
                assert reg in bad and row in bad[reg].tolist(), \
                    (reg, row, bad)
                continue
            got = _fingerprint(d2, t2, h2)     # or HARMLESS
            assert set(got) == set(ref)
            for k in ref:
                np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
            assert b.scrub() == {}             # dead-row flip: unseen


# ----------------------------------------------- typed media losses


def test_shard_loss_errors(tmp_path):
    path = str(tmp_path / "s.pm")
    a, d, t, h = _mixed(path, 4)
    _run(a, d, t, h, _script(8, seed=7))
    layout = {}
    layout.update(DoublyLinkedList.layout(256, "partly", name="dll"))
    layout.update(BPTree.layout(256, 1024, "partly", name="bt"))
    layout.update(Hashmap.layout(512, "partly", name="hm"))
    del a, d, t, h
    fi.truncate_shard(path, shard=2, nbytes=64)
    with pytest.raises(ShardLossError):
        open_arena(path, layout, n_shards=4)
    fi.remove_shard(path, shard=2)
    with pytest.raises(ShardLossError):
        open_arena(path, layout, n_shards=4)


@pytest.mark.parametrize("n_shards", [1, 4])
def test_manifest_errors(tmp_path, n_shards):
    a, d, t, h = _mixed(str(tmp_path / "m.pm"), n_shards)
    _run(a, d, t, h, _script(6, seed=8))
    a.crash()
    if n_shards > 1:
        fi.corrupt_manifest(a)
    else:
        fi.corrupt_header(a)
    with pytest.raises(ManifestError):
        a.verify_header()
    # garbage magic is fatal even in salvage: with no trustworthy
    # generation there is no committed prefix to salvage toward
    with pytest.raises(ManifestError):
        _manager(a, d, t, h).recover(salvage=True)
    assert issubclass(ManifestError, IntegrityError)
    assert issubclass(CorruptLineError, IntegrityError)
    assert issubclass(ShardLossError, IntegrityError)


# --------------------------------------------------------- salvage


@pytest.mark.parametrize("n_shards", SHARDS)
@pytest.mark.parametrize("victim", ["dll", "bt", "hm"])
def test_mixed_salvage_recovers_the_rest(tmp_path, n_shards, victim):
    """Acceptance: one corrupted slab of a mixed three-structure arena
    quarantines/degrades ONLY its own stage; the other two recover to
    their exact pre-crash state, and the report names the loss."""
    a, d, t, h = _mixed(str(tmp_path / "a.pm"), n_shards)
    _run(a, d, t, h, _script(30, seed=9))
    dll_order = d.order().copy()
    bt_keys = t.keys_in_order().copy()
    bt_leaves = t.leaves().copy()
    hm_size = int(h.size)
    a.crash()
    reg = {"dll": "dll.nodes", "bt": "bt.nodes", "hm": "hm.entries"}[victim]
    row = {"dll": int(dll_order[1]),
           "bt": int(bt_leaves[1]) if bt_leaves.size > 1
           else int(bt_leaves[0]),
           "hm": 3}[victim]
    fi.flip_bits(a, a.regions[reg], row, byte=8, mask=0x40)
    rep = _manager(a, d, t, h).recover(salvage=True)
    st = {s.name: s for s in rep.stages}
    assert st[victim].quarantined or st[victim].degraded, \
        st[victim].as_dict()
    assert victim in set(rep.quarantined) | set(rep.degraded)
    for other in ("dll", "bt", "hm"):
        if other == victim:
            continue
        assert other not in rep.quarantined
        assert other not in rep.degraded
    if victim != "dll":
        np.testing.assert_array_equal(d.order(), dll_order)
    if victim != "bt":
        np.testing.assert_array_equal(t.keys_in_order(), bt_keys)
    if victim != "hm":
        assert int(h.size) == hm_size
    # victim-specific salvage shape
    if victim == "dll":
        got = d.order()
        assert got.size < dll_order.size
        np.testing.assert_array_equal(got, dll_order[: got.size])
    elif victim == "bt":
        got = t.keys_in_order()
        assert set(got.tolist()) <= set(bt_keys.tolist())
        assert set(t.quarantined).isdisjoint(got.tolist())
    else:
        assert h.quarantined, "hashmap salvage named no keys"


def test_full_mode_tree_quarantines_wholesale(tmp_path):
    a, d, t, h = _mixed(str(tmp_path / "a.pm"), 1, mode="full")
    _run(a, d, t, h, _script(30, seed=10))
    dll_order = d.order().copy()
    leaf = int(t.leaves()[0])
    a.crash()
    fi.flip_bits(a, a.regions["bt.nodes"], leaf, byte=8, mask=0x40)
    rep = _manager(a, d, t, h).recover(salvage=True)
    assert rep.quarantined == ["bt"]
    np.testing.assert_array_equal(d.order(), dll_order)


def test_salvage_off_still_aborts_nothing_silently(tmp_path):
    """Without salvage the corrupt stage keeps its pre-integrity
    behavior (possibly recovering garbage the scrub then names) — but
    nothing is EVER silent: on a paged arena the verifying fault path
    raises mid-recovery, on a resident one the scrub names the row."""
    a, d, t, h = _mixed(str(tmp_path / "a.pm"), 1)
    _run(a, d, t, h, _script(12, seed=11))
    row = int(d.order()[1])
    a.crash()
    fi.flip_bits(a, a.regions["dll.nodes"], row, byte=8, mask=0x40)
    try:
        _manager(a, d, t, h).recover()       # plain recovery: no verify
    except CorruptLineError as e:            # paged fault path verifies
        assert e.region == "dll.nodes" and row in e.rows.tolist()
        return
    bad = a.scrub()                          # ...but scrub detects
    assert "dll.nodes" in bad and row in bad["dll.nodes"].tolist()


def test_quarantined_dependents_skip(tmp_path):
    """A stage whose dependency quarantined self-skips with a degraded
    report instead of reconstructing from untrusted inputs."""
    a, d, t, h = _mixed(str(tmp_path / "a.pm"), 1, mode="full")
    _run(a, d, t, h, _script(12, seed=12))
    leaf = int(t.leaves()[0])
    a.crash()
    fi.flip_bits(a, a.regions["bt.nodes"], leaf, byte=8, mask=0x40)
    mgr = RecoveryManager(a)
    mgr.add("bt", "pstruct.bptree", t)
    mgr.add("dll", "pstruct.dll", d, depends=("bt",))
    rep = mgr.recover(salvage=True)
    st = {s.name: s for s in rep.stages}
    assert st["bt"].quarantined
    assert st["dll"].degraded
    assert st["dll"].detail.get("skipped") == "quarantined dependency"
    assert rep.quarantined == ["bt"] and rep.degraded == ["dll"]


# ------------------------------------------------- serving quarantine


@pytest.mark.parametrize("n_shards", SHARDS)
def test_feature_store_refuses_only_quarantined_keys(tmp_path, n_shards):
    from repro.serve.feature_store import FeatureConfig, FeatureStore
    fs = FeatureStore(FeatureConfig(n_keys=64, dim=3, n_samples=256,
                                    n_shards=n_shards),
                      str(tmp_path / "fs.pm"))
    rng = np.random.default_rng(13)
    for rid in range(8):
        fs.apply(rid, np.array([rid * 3, rid * 3 + 1], np.int64),
                 rng.integers(0, 100, (2, 3)))
    keep = fs.lookup(np.array([3], np.int64)).copy()
    slot = int(fs.table._find_slots(np.array([0], np.int64))[0])
    fs.crash()
    fi.flip_bits(fs.arena, fs.arena.regions["emb.entries"], slot,
                 byte=16, mask=0x20)          # a VALUE word: key readable
    rep = fs.recover(salvage=True)
    assert {s.name: s for s in rep.stages}["emb"].degraded
    assert 0 in fs.quarantined_keys
    with pytest.raises(QuarantinedError):
        fs.lookup(np.array([0], np.int64))
    with pytest.raises(QuarantinedError):
        fs.apply(99, np.array([0], np.int64), np.zeros((1, 3), np.int64))
    np.testing.assert_array_equal(fs.lookup(np.array([3], np.int64)),
                                  keep)
    fs.readmit([0])
    fs.lookup(np.array([0], np.int64))       # fresh start, no raise


def test_feature_store_record_loss_names_keys_by_shortfall(tmp_path):
    from repro.serve.feature_store import FeatureConfig, FeatureStore
    fs = FeatureStore(FeatureConfig(n_keys=64, dim=3, n_samples=256),
                      str(tmp_path / "fs.pm"))
    rng = np.random.default_rng(14)
    for rid in range(8):
        fs.apply(rid, np.array([rid * 3, rid * 3 + 1], np.int64),
                 rng.integers(0, 100, (2, 3)))
    fs.crash()
    fi.flip_bits(fs.arena, fs.arena.regions["sx.records"], 4,
                 byte=24, mask=0x08)
    rep = fs.recover(salvage=True)
    st = {s.name: s for s in rep.stages}
    assert st["samples"].degraded or st["samples"].quarantined
    assert fs.quarantined_keys, "record loss named no keys"
    det = st["store"].detail
    assert det.get("skipped") or det.get("missing_samples", 0) > 0


def test_engine_rejects_only_quarantined_rids(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.configs import base, registry
    from repro.models.model import build
    from repro.serve.engine import EngineConfig, ServingEngine
    model = build(base.reduced(registry.get("llama3.2-3b")),
                  compute_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params,
                        EngineConfig(max_batch=3, s_max=16,
                                     max_requests=16),
                        arena_path=str(tmp_path / "a"))
    eng.add_request(7, np.array([1, 2, 3], np.int64))
    eng.add_request(8, np.array([4, 5, 6, 9, 2], np.int64))
    eng.step()
    eng.crash()
    fi.flip_bits(eng.arena, eng.arena.regions["tokens"], 0,
                 byte=4, mask=0x10)          # rid 7's token-log row
    eng.recover(salvage=True)
    assert eng.quarantined_rids == {7}
    st = eng.last_recovery.stage("engine")
    assert st.degraded and st.detail["quarantined_rids"] == [7]
    out = eng.step()                          # rid 8 serves on
    assert 8 in out and 7 not in out
    with pytest.raises(QuarantinedError):
        eng.add_request(7, np.array([1, 2, 3], np.int64))
    eng.add_request(9, np.array([2, 2], np.int64))   # others admit fine
    eng.readmit([7])
    assert eng.quarantined_rids == set()
    if eng.journal is not None:
        # the abandoned rid's exactly-once accounting is closed
        assert eng.journal.state_of(7) == "completed"
