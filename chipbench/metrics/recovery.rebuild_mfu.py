"""Share of the chip's bf16 peak that recovery's rebuild needs, in %: the
model FLOPs of the positions the ``engine`` stage re-prefills, per crash
(the program's ``rebuild_groups``: each group's tokens and sequences, meta
tokens before each), over ``recovery.rebuild_device_ms`` times the peak.
A position's FLOPs come from ``flops.token_flops``, the LM head once per
sequence (a prefill computes the last position's logits only).
``flops.py`` counts matrix products and attention, not element-wise work,
so the distance from 100 % is the share of the scan, the norms, the cache
scatter and the device's gaps.  ``None`` without the detail or a trace."""
from chipbench import flops, spec


def _groups(run):
    """Each window crash's ``rebuild_groups``; ``None`` if one lacks it."""
    found = []
    for c in run.window_crashes():
        st = c["report"].stage("engine")
        if st is None or "rebuild_groups" not in st.detail:
            return None
        found.append(st.detail["rebuild_groups"])
    return found


def prefill_flops(dims: dict, tokens: int) -> float:
    """FLOPs of prefilling one sequence of ``tokens`` after the meta
    tokens."""
    meta = dims.get("meta_tokens", 0)
    head = 2.0 * dims["d_model"] * dims["vocab"]
    return sum(flops.token_flops(dims, p) - head
               for p in range(-meta, tokens)) + head


def read(run):
    if not run.peaks:
        return None
    groups = _groups(run)
    if not groups:
        return None
    device_ms = spec.reader("recovery.rebuild_device_ms")(run)
    if not device_ms:
        return None
    meta = run.dims.get("meta_tokens", 0)
    per_length = {}
    total = 0.0
    for crash in groups:
        for tokens, seqs in crash:
            if tokens + meta == 0:          # the zero state, no prefill
                continue
            if tokens not in per_length:
                per_length[tokens] = prefill_flops(run.dims, tokens)
            total += seqs * per_length[tokens]
    per_crash = total / len(groups)
    return 100.0 * per_crash / (device_ms / 1e3
                                * run.peaks["bf16_flops_per_s"])
