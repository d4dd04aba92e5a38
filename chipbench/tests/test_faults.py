"""Runs of each cell at a reduced size on the CPU with the timed path
broken underneath: ``correct`` must come out false.  The look for a chip
is skipped (``run.run`` is called directly); everything else is a whole
run."""
import jax.numpy as jnp
import pytest

from chipbench import spec
from chipbench.tests.harness_util import CELLS, run_small
from repro.serve.engine import ServingEngine

CRASH_CELLS = [c for c in CELLS if spec.cell(c)["traffic"].get("crash")]


def _state_unchanged(monkeypatch):
    """Decode leaves the slot's state as it was (no cache update)."""
    orig = ServingEngine._decode_slot

    def decode(self, slot, token, p):
        before = self.cache
        out = orig(self, slot, token, p)
        self.cache = before
        return out

    monkeypatch.setattr(ServingEngine, "_decode_slot", decode)


def _token_altered(monkeypatch):
    """The token is altered where it is produced: the logits the engine
    takes its argmax of put another token first."""
    orig = ServingEngine._decode_slot

    def decode(self, slot, token, p):
        logits = orig(self, slot, token, p)
        wrong = (jnp.argmax(logits) + 7) % self.model.cfg.vocab
        return logits.at[wrong].set(jnp.finfo(logits.dtype).max)

    monkeypatch.setattr(ServingEngine, "_decode_slot", decode)


def _token_not_persisted(monkeypatch):
    """A step's token-log appends are never marked dirty, so they are
    acknowledged but not flushed."""
    orig = ServingEngine.step

    def step(self):
        region = self.tok_region
        saved = region.mark_range
        region.mark_range = lambda *a, **k: None
        try:
            return orig(self)
        finally:
            region.mark_range = saved

    monkeypatch.setattr(ServingEngine, "step", step)


def _half_recovered(monkeypatch):
    """Recovery rebuilds the state of every other prefill group and
    leaves the rest at the zero state (half the batch left out)."""
    orig = ServingEngine._prefill_slots
    calls = {"n": 0}

    def prefill(self, slots, tokens):
        calls["n"] += 1
        if getattr(self, "_chipbench_recovering", False) and calls["n"] % 2:
            return None
        return orig(self, slots, tokens)

    orig_recover = ServingEngine.recover

    def recover(self, *a, **k):
        self._chipbench_recovering = True
        try:
            return orig_recover(self, *a, **k)
        finally:
            self._chipbench_recovering = False

    monkeypatch.setattr(ServingEngine, "_prefill_slots", prefill)
    monkeypatch.setattr(ServingEngine, "recover", recover)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    res = run_small(name)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", CRASH_CELLS)
@pytest.mark.parametrize("fault", [_token_not_persisted, _half_recovered])
def test_recovery_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    res = run_small(name)
    assert not res["correct"], res["compared"]
