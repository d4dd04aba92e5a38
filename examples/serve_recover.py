"""Serving with partly-persistent session state + crash recovery.

Boots the ServingEngine on a reduced gemma2 config, serves a batch of
requests with greedy decode, crashes mid-generation (dropping KV caches,
the request hashmap, and the paged-LRU metadata), recovers from the
persistent arena, and asserts the continued generations equal those of
an uninterrupted twin engine.

    PYTHONPATH=src python examples/serve_recover.py
"""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base, registry
from repro.models.model import build
from repro.serve.engine import EngineConfig, ServingEngine


def main():
    cfg = base.reduced(registry.get("gemma2-9b"))
    model = build(cfg, compute_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))

    rng = np.random.default_rng(7)
    prompts = {rid: rng.integers(1, cfg.vocab, int(rng.integers(4, 9)))
               for rid in (901, 902, 903)}

    with tempfile.TemporaryDirectory() as td:
        def engine(name):
            eng = ServingEngine(
                model, params,
                EngineConfig(max_batch=4, s_max=48, max_requests=32),
                arena_path=os.path.join(td, name))
            for rid, p in prompts.items():
                eng.add_request(rid, p.astype(np.int64))
            return eng

        eng = engine("arena")
        for rid, p in prompts.items():
            print(f"request {rid}: prompt {p.tolist()}")

        print("\n-- serving 4 steps --")
        for i in range(4):
            print(f"step {i}: {eng.step()}")

        # an uninterrupted twin serves the same 8 steps
        twin = engine("twin")
        expected = [twin.step() for _ in range(8)][4:]
        print("\n-- CRASH: device caches + volatile host tables dropped --")
        eng.crash()
        dt = eng.recover()
        print(f"recovered in {dt:.2f}s: hashmap rebuilt from (KEY,VALUE) "
              f"slab, LRU from NEXT chain, KV caches re-prefilled from "
              f"the persisted token log")

        got = [eng.step() for _ in range(4)]
        assert got == expected, (got, expected)
        print("\npost-recovery generations identical to the "
              "uninterrupted run:")
        for i, toks in enumerate(got):
            print(f"step {i + 4}: {toks}")
        st = eng.arena.stats
        print(f"\narena flush stats: {st.lines} lines, {st.bytes} bytes, "
              f"{st.calls} calls")


if __name__ == "__main__":
    main()
