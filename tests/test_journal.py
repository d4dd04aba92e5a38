"""Request journal unit tests (serve/journal.py, DESIGN.md §11) plus
the feature store's replay reconstructor (serve/feature_store.py).

Covered here:

* append / classify roundtrip across crash+recover, on one shard and
  on four;
* the admission state machine: duplicate ADMIT/APPLY refused,
  COMPLETE without ADMIT refused, appends outside an epoch refused,
  ring-full refused until ``retire_completed`` frees slots;
* crash-window visibility: a torn (data-phase-only) append recovers as
  never-admitted — the entry bytes may be durable, the committed HEAD
  is not past them;
* the sealing rule: a wrapped append may destroy a RETIRED entry's
  slot without committing; recovery must skip the seq-mismatched slot
  and an orphaned COMPLETE must still classify its rid as completed;
* journal-off identity: with REPRO_JOURNAL=0 (or journal=False) the
  feature store lays out NO journal regions, every shared region keeps
  its offset, and the flushed line/byte counts are bit-identical to
  the journal-on run minus exactly the ring lines — the overhead bound
  (<= 1 journal line per epoch) the CI matrix asserts.
"""
import numpy as np
import pytest

from repro.core.arena import journal_enabled, open_arena
from repro.core.recovery import RecoveryManager
from repro.serve.feature_store import FeatureConfig, FeatureStore
from repro.serve.journal import (JR_MAGIC, OP_ADMIT, OP_APPLY, OP_COMPLETE,
                                 ST_DONE, ST_NEVER, ST_RETRY,
                                 DuplicateRequestError, RequestJournal,
                                 args_digest, snap_checksum)

SHARDS = [1, 4]


def _jr(n_shards, cap=64):
    """Standalone journal (own .jrnlheader line) on a fresh arena."""
    a = open_arena(None, RequestJournal.layout(cap, name="jr",
                                               standalone=True),
                   n_shards=n_shards)
    return a, RequestJournal(a, cap, name="jr")


def _recover(a, j):
    a.reopen()
    mgr = RecoveryManager(a)
    mgr.add("journal", "serve.journal", j,
            regions=("jr.jrnl", "jr.jrnlheader"))
    rep = mgr.recover()
    assert rep.valid
    return rep.stage("journal").detail


# ------------------------------------------------------------ state machine


@pytest.mark.parametrize("n_shards", SHARDS)
def test_roundtrip_classify_across_crash(n_shards):
    a, j = _jr(n_shards)
    with a.epoch():
        j.log(OP_ADMIT, 1, digest=args_digest([1, 2, 3]))
        j.log(OP_ADMIT, 2)
        a.commit()
    with a.epoch():
        j.log(OP_COMPLETE, 1)
        j.log(OP_APPLY, 3)
        a.commit()
    a.crash()
    detail = _recover(a, j)
    assert detail["entries"] == 4 and detail["skipped"] == 0
    assert j.classify() == {1: ST_DONE, 2: ST_RETRY, 3: ST_DONE}
    assert j.must_retry() == {2}
    assert j.state_of(99) == ST_NEVER


@pytest.mark.parametrize("n_shards", SHARDS)
def test_duplicate_admission_raises(n_shards):
    a, j = _jr(n_shards)
    with a.epoch():
        j.log(OP_ADMIT, 5)
        a.commit()
    with a.epoch():
        with pytest.raises(DuplicateRequestError):
            j.log(OP_ADMIT, 5)
        with pytest.raises(DuplicateRequestError):
            j.log(OP_APPLY, 5)
        j.log(OP_COMPLETE, 5)
        # completed is STILL a known rid inside the dedup window
        with pytest.raises(DuplicateRequestError):
            j.log(OP_ADMIT, 5)
        with pytest.raises(DuplicateRequestError):
            j.log(OP_COMPLETE, 5)
        a.commit()


@pytest.mark.parametrize("n_shards", SHARDS)
def test_complete_without_admit_raises(n_shards):
    a, j = _jr(n_shards)
    with a.epoch():
        with pytest.raises(KeyError):
            j.log(OP_COMPLETE, 7)
        a.commit()


@pytest.mark.parametrize("n_shards", SHARDS)
def test_log_outside_epoch_refused(n_shards):
    a, j = _jr(n_shards)
    with pytest.raises(AssertionError):
        j.log(OP_ADMIT, 1)


@pytest.mark.parametrize("n_shards", SHARDS)
def test_unknown_op_refused(n_shards):
    a, j = _jr(n_shards)
    with a.epoch():
        with pytest.raises(ValueError):
            j.log(0, 1)
        a.commit()


# ------------------------------------------------------------ crash windows


@pytest.mark.parametrize("n_shards", SHARDS)
def test_torn_append_recovers_as_never_admitted(n_shards):
    """Data-phase-only flush: the ring line may be durable but the
    committed HEAD is not past it — the op must classify never-admitted
    (this is the exactly-once crash window)."""
    a, j = _jr(n_shards)
    with a.epoch():
        j.log(OP_ADMIT, 1)
        a.commit()
    with a.epoch():
        j.log(OP_ADMIT, 2)
        a.writeset.flush(include_meta=False)
        a.crash()
    detail = _recover(a, j)
    assert detail["window"] == 1
    assert j.state_of(1) == ST_RETRY
    assert j.state_of(2) == ST_NEVER
    # the retry is not a duplicate
    with a.epoch():
        j.log(OP_ADMIT, 2)
        a.commit()
    assert j.state_of(2) == ST_RETRY


@pytest.mark.parametrize("n_shards", SHARDS)
def test_uncommitted_epoch_recovers_clean(n_shards):
    a, j = _jr(n_shards)
    with a.epoch():
        j.log(OP_ADMIT, 1)
        a.commit()
    with a.epoch():
        j.log(OP_ADMIT, 2)
        j.log(OP_COMPLETE, 1)
        a.crash()
    _recover(a, j)
    assert j.classify() == {1: ST_RETRY}


@pytest.mark.parametrize("n_shards", SHARDS)
def test_recover_twice_is_idempotent(n_shards):
    a, j = _jr(n_shards)
    with a.epoch():
        j.log(OP_ADMIT, 1)
        j.log(OP_APPLY, 2)
        a.commit()
    a.crash()
    d1 = _recover(a, j)
    c1, h1, t1 = dict(j.classify()), j.head, j.tail
    d2 = _recover(a, j)
    assert (d1, c1, h1, t1) == (d2, dict(j.classify()), j.head, j.tail)


# ------------------------------------------------- ring wrap + sealing rule


@pytest.mark.parametrize("n_shards", SHARDS)
def test_ring_full_then_retire_and_wrap(n_shards):
    a, j = _jr(n_shards, cap=4)
    for rid in range(4):
        with a.epoch():
            j.log(OP_APPLY, rid)
            a.commit()
    with a.epoch():
        with pytest.raises(MemoryError):
            j.log(OP_ADMIT, 4)
        a.commit()
    assert j.space() == 0
    assert j.retire_completed() == 4
    assert j.space() == 4
    with a.epoch():
        j.log(OP_ADMIT, 5)       # seq 4 -> wraps onto slot 0
        a.commit()
    with a.epoch():              # torn second lap append
        j.log(OP_ADMIT, 6)
        a.writeset.flush(include_meta=False)
        a.crash()
    _recover(a, j)
    assert j.state_of(5) == ST_RETRY
    assert j.state_of(6) == ST_NEVER
    assert j.state_of(0) == ST_NEVER     # retired: out of the window


@pytest.mark.parametrize("n_shards", SHARDS)
def test_retire_inside_epoch_refused(n_shards):
    a, j = _jr(n_shards)
    with a.epoch():
        j.log(OP_APPLY, 1)
        with pytest.raises(AssertionError):
            j.retire_completed()
        a.commit()


@pytest.mark.parametrize("n_shards", SHARDS)
def test_sealing_rule_skips_destroyed_retired_slot(n_shards):
    """A wrapped TORN append destroys slot 0's retired first-lap entry
    while the committed window still spans it (ADMIT retired, its
    COMPLETE not yet).  Recovery must skip the seq-mismatched slot and
    the orphaned COMPLETE must still classify rid 0 as completed."""
    a, j = _jr(n_shards, cap=4)
    with a.epoch():
        j.log(OP_ADMIT, 0)       # seq 0 -> slot 0
        j.log(OP_ADMIT, 1)       # seq 1
        a.commit()
    with a.epoch():
        j.log(OP_COMPLETE, 0)    # seq 2
        j.log(OP_COMPLETE, 1)    # seq 3
        a.commit()
    j.retire_completed()         # volatile TAIL -> 4; committed TAIL
    assert j.tail == 4           # still 0 until the next log's line
    with a.epoch():
        j.log(OP_ADMIT, 5)       # seq 4 -> slot 0, overwrites rid 0's ADMIT
        a.writeset.flush(include_meta=False)
        a.crash()
    detail = _recover(a, j)
    # committed window is still [0, 4); slot 0 holds the torn lap-2 bytes
    assert detail["window"] == 4
    assert detail["skipped"] == 1
    assert j.state_of(0) == ST_DONE      # orphaned COMPLETE suffices
    assert j.state_of(1) == ST_DONE
    assert j.state_of(5) == ST_NEVER


@pytest.mark.parametrize("n_shards", SHARDS)
def test_checksum_rejects_corrupt_entry(n_shards):
    a, j = _jr(n_shards)
    with a.epoch():
        j.log(OP_ADMIT, 1)
        j.log(OP_ADMIT, 2)
        a.commit()
    # flip one digest word of entry 0 directly in "persistent memory"
    row = np.array(j.ring.vol[0])
    assert row[0] == JR_MAGIC and row[7] == snap_checksum(row)
    row[4] ^= 1
    j.ring.vol[0] = row
    j.ring.persist_rows(np.array([0]))
    a.crash()
    detail = _recover(a, j)
    assert detail["skipped"] == 1
    assert j.state_of(1) == ST_NEVER
    assert j.state_of(2) == ST_RETRY


def test_args_digest_is_order_and_length_sensitive():
    assert args_digest([1, 2, 3]) == args_digest(np.array([1, 2, 3]))
    assert args_digest([1, 2, 3]) != args_digest([3, 2, 1])
    assert args_digest([]) != args_digest([0])
    assert args_digest([0]) != args_digest([0, 0])


# -------------------------------------------------- journal-off identity


def _fs_workload(fs, n_ops=6, seed=3):
    rng = np.random.default_rng(seed)
    for rid in range(n_ops):
        keys = rng.choice(fs.cfg.n_keys, size=4, replace=False)
        deltas = rng.integers(-9, 10, (4, fs.cfg.dim))
        assert fs.apply(rid, keys, deltas)


@pytest.mark.parametrize("n_shards", SHARDS)
def test_journal_off_layout_and_traffic_identical(n_shards):
    """REPRO_JOURNAL=0 layouts must be bit-identical to the pre-journal
    engine: no .jrnl regions, shared regions at unchanged offsets, and
    the journal's entire flush overhead isolated in
    ``FlushStats.journal_lines`` (<= 1 line per epoch)."""
    cfg_kw = dict(n_keys=32, dim=3, n_samples=256, n_shards=n_shards)
    on = FeatureStore(FeatureConfig(journal=True, **cfg_kw))
    off = FeatureStore(FeatureConfig(journal=False, **cfg_kw))
    assert on.journal is not None and off.journal is None
    assert not [n for n in off.arena.regions if ".jrnl" in n]
    for name, r_off in off.arena.regions.items():
        r_on = on.arena.regions[name]
        assert r_on.shape == r_off.shape
        # integrity sidecars are appended AFTER every declared region
        # (DESIGN.md §13), so the journal regions legitimately shift
        # them; every declared region must sit at an unchanged offset
        if hasattr(r_on, "offset") and not name.endswith(".integ"):
            assert r_on.offset == r_off.offset, name
    s_on, s_off = on.arena.stats.snapshot(), off.arena.stats.snapshot()
    _fs_workload(on)
    _fs_workload(off)
    d_on = on.arena.stats.delta(s_on)
    d_off = off.arena.stats.delta(s_off)
    assert d_off.journal_lines == 0
    assert 0 < d_on.journal_lines <= d_on.epochs
    # journal traffic lives ONLY in journal_lines: the data-line/byte
    # ledgers are bit-identical to the journal-off run
    assert d_on.lines == d_off.lines and d_on.bytes == d_off.bytes
    # and the effects are identical either way
    probe = np.arange(32)
    np.testing.assert_array_equal(on.lookup(probe), off.lookup(probe))


def test_journal_env_default(monkeypatch):
    assert journal_enabled(True) and not journal_enabled(False)
    monkeypatch.setenv("REPRO_JOURNAL", "0")
    assert not journal_enabled(None)
    assert journal_enabled(True)      # explicit flag beats the env
    monkeypatch.setenv("REPRO_JOURNAL", "1")
    assert journal_enabled(None)
    monkeypatch.delenv("REPRO_JOURNAL")
    assert journal_enabled(None)      # default on
