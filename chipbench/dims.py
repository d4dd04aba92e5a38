"""A configuration's program settings and sizes.

``configs/<name>.json`` names the program's registry entry (``arch``),
the settings the benchmark gives it on top of that entry (``program``,
such as a depth cut), and the sizes it runs at (``model``).  The harness
builds the program's config from the first two and refuses a run whose
program no longer has the sizes of the third."""
from __future__ import annotations

import dataclasses


def arch_of(conf: dict):
    """The program's ``ArchConfig`` for the configuration file ``conf``."""
    from repro.configs import registry

    return dataclasses.replace(registry.get(conf["arch"]),
                               **conf.get("program", {}))


def dims_of(cfg, keys) -> dict:
    """The program config ``cfg``'s value of each key in ``keys``, as a
    configuration file states it (``head_dim`` resolved, tuples as
    lists)."""
    out = {}
    for k in keys:
        v = cfg.resolved_head_dim if k == "head_dim" else getattr(cfg, k)
        out[k] = list(v) if isinstance(v, tuple) else v
    return out
