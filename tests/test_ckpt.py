"""Checkpoint manager: policies, commit protocol, reconstruction,
quantized persist, incremental skip, elastic restore spec."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.manager import CheckpointManager
from repro.ckpt.manifest import CheckpointCatalog
from repro.core import policy as pol
from repro.train.state import TrainState, new_state


def tiny_state(seed=0):
    k = jax.random.PRNGKey(seed)
    params = {"w": jax.random.normal(k, (32, 16)),
              "b": jnp.zeros((16,))}
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    st = new_state(params, mu, nu, seed=7)
    # keep the DERIVABLE-rng invariant: rng == fold_in(PRNGKey(seed), step)
    return st._replace(step=jnp.asarray(42, jnp.int32),
                       rng=jax.random.fold_in(jax.random.PRNGKey(7), 42))


def state_spec(st):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st)


def test_policy_classification():
    st = tiny_state()
    plans = {p.path: p for p in pol.plan(st.as_dict(), pol.PARTLY_PERSISTENT)}
    assert plans["params/w"].kind == pol.Kind.ESSENTIAL
    assert plans["mu/w"].kind == pol.Kind.APPROXIMABLE
    assert plans["rng"].kind == pol.Kind.DERIVABLE
    assert not plans["rng"].persisted
    assert plans["params/w"].persisted


def test_partly_persists_fewer_bytes():
    st = tiny_state().as_dict()
    full = pol.persisted_bytes(st, pol.FULLY_PERSISTENT)
    partly = pol.persisted_bytes(st, pol.PARTLY_PERSISTENT)
    drop = pol.persisted_bytes(st, pol.PARTLY_DROP)
    q8 = pol.persisted_bytes(st, pol.PARTLY_Q8)
    assert drop < q8 < partly < full


@pytest.mark.parametrize("policy", [pol.FULLY_PERSISTENT,
                                    pol.PARTLY_PERSISTENT])
def test_save_restore_bitexact(tmp_path, policy):
    st = tiny_state()
    mgr = CheckpointManager(str(tmp_path), policy)
    rep = mgr.save(st)
    assert rep.step == 42 and rep.bytes_written > 0
    got = mgr.restore(state_spec(st))
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_reconstructs_rng(tmp_path):
    """rng is DERIVABLE: never written, rebuilt as fold_in(seed, step)."""
    st = tiny_state()
    st = st._replace(rng=jax.random.fold_in(jax.random.PRNGKey(7), 42))
    mgr = CheckpointManager(str(tmp_path), pol.PARTLY_PERSISTENT)
    mgr.save(st)
    with open(os.path.join(str(tmp_path), "manifest.json")) as f:
        manifest = json.load(f)
    assert "rng" not in manifest["leaves"]
    got = mgr.restore(state_spec(st))
    np.testing.assert_array_equal(np.asarray(got.rng), np.asarray(st.rng))


def test_quantized_moments_bounded_error(tmp_path):
    st = tiny_state()
    st = st._replace(mu=jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(1), x.shape),
        st.mu))
    mgr = CheckpointManager(str(tmp_path), pol.PARTLY_Q8)
    rep = mgr.save(st)
    assert rep.quantized
    got = mgr.restore(state_spec(st))
    # params bit-exact, moments within int8 blockwise error
    np.testing.assert_array_equal(np.asarray(got.params["w"]),
                                  np.asarray(st.params["w"]))
    err = np.max(np.abs(np.asarray(got.mu["w"]) - np.asarray(st.mu["w"])))
    amax = np.max(np.abs(np.asarray(st.mu["w"])))
    assert err <= amax / 127 * 1.01


def test_drop_policy_rewarns_moments(tmp_path):
    st = tiny_state()
    st = st._replace(nu=jax.tree.map(lambda x: x + 3.0, st.nu))
    mgr = CheckpointManager(str(tmp_path), pol.PARTLY_DROP)
    mgr.save(st)
    got = mgr.restore(state_spec(st))
    assert float(jnp.sum(jnp.abs(got.nu["w"]))) == 0.0


def test_manifest_last_commit(tmp_path):
    """A crash before the manifest rename leaves the PREVIOUS checkpoint
    fully valid (the paper's flag-bit ordering)."""
    st = tiny_state()
    mgr = CheckpointManager(str(tmp_path), pol.PARTLY_PERSISTENT)
    mgr.save(st)
    st2 = st._replace(step=jnp.asarray(43, jnp.int32),
                      params=jax.tree.map(lambda x: x + 1, st.params))
    # simulate crash mid-write: leaf tmp files written, manifest NOT renamed
    sd = st2.as_dict()
    from repro.ckpt.manager import _leaf_file
    for pth, leaf in jax.tree_util.tree_flatten_with_path(sd)[0]:
        pstr = pol.path_str(pth)
        if pstr.startswith("params"):
            fp = os.path.join(str(tmp_path), _leaf_file(pstr) + ".tmp")
            with open(fp, "wb") as f:
                np.savez(f, x=np.asarray(leaf))
    got = mgr.restore(state_spec(st))
    assert int(got.step) == 42  # previous checkpoint intact
    np.testing.assert_array_equal(np.asarray(got.params["w"]),
                                  np.asarray(st.params["w"]))


def test_incremental_skips_unchanged(tmp_path):
    st = tiny_state()
    mgr = CheckpointManager(str(tmp_path), pol.PARTLY_PERSISTENT,
                            incremental=True)
    r1 = mgr.save(st)
    assert r1.bytes_skipped_unchanged == 0
    st2 = st._replace(step=jnp.asarray(43, jnp.int32))  # params unchanged
    r2 = mgr.save(st2)
    assert r2.bytes_skipped_unchanged > 0
    assert r2.bytes_written < r1.bytes_written
    got = mgr.restore(state_spec(st2))
    np.testing.assert_array_equal(np.asarray(got.params["w"]),
                                  np.asarray(st.params["w"]))
    assert int(got.step) == 43


def test_async_save_equivalent(tmp_path):
    st = tiny_state()
    mgr = CheckpointManager(str(tmp_path), pol.PARTLY_PERSISTENT)
    mgr.save(st, blocking=False)
    mgr.wait()
    got = mgr.restore(state_spec(st))
    np.testing.assert_array_equal(np.asarray(got.params["w"]),
                                  np.asarray(st.params["w"]))


def test_catalog_roundtrip(tmp_path):
    path = str(tmp_path / "cat.arena")
    cat = CheckpointCatalog(path)
    for s in (10, 20, 30):
        cat.record(s, s // 10, 1000 * s, 5)
    assert cat.latest()[0] == 30
    assert cat.steps().tolist() == [10, 20, 30]
    # crash + reopen: inner nodes rebuilt from leaves
    cat.arena.crash()
    cat2 = CheckpointCatalog(path)
    assert cat2.steps().tolist() == [10, 20, 30]
    assert cat2.latest()[0] == 30


def test_elastic_restore_reshards(tmp_path):
    """A checkpoint saved without shardings restores under a target-mesh
    sharding spec (the elastic-scaling path: restore onto a different
    mesh = same code, different NamedShardings)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    st = tiny_state()
    mgr = CheckpointManager(str(tmp_path), pol.PARTLY_PERSISTENT)
    mgr.save(st)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), state_spec(st))
    got = mgr.restore(state_spec(st), shardings=sh)
    np.testing.assert_array_equal(np.asarray(got.params["w"]),
                                  np.asarray(st.params["w"]))
    assert got.params["w"].sharding.mesh.shape == {"data": 1, "model": 1}


def test_restore_pairs_each_sharding_with_its_leaf(tmp_path):
    """A TrainState of shardings flattens in field order, the state spec
    in key order: restore must pair each leaf with its own sharding."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    st = tiny_state()
    mgr = CheckpointManager(str(tmp_path), pol.PARTLY_PERSISTENT)
    mgr.save(st)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    sh = jax.tree.map(lambda s: NamedSharding(mesh, P(*[None] * s.ndim)),
                      state_spec(st))
    got = mgr.restore(state_spec(st), shardings=sh)
    for g, s, w in zip(jax.tree.leaves(got), jax.tree.leaves(sh),
                       jax.tree.leaves(st)):
        assert g.sharding == s
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
