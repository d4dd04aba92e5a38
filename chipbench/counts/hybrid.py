"""Counts of one hymba layer (``hybrid`` in the program's
``layer_pattern``): attention heads and SSM heads side by side, one shared
out_proj over the mean of their normed outputs, and a SwiGLU MLP.

A tag ``hybrid:full`` attends over every position, ``hybrid:local`` over a
window; ``...:shared`` layers project only Q and attend over the cache of
the layer before them in their KV group.  Every sequence starts with
``meta_tokens`` learned tokens, which every query sees; ``pos`` counts the
tokens after them, so a meta token sits at a negative ``pos``."""

BF16 = 2
F32 = 4


def _shared(tag: str) -> bool:
    return tag.endswith(":shared")


def matmul_weights(dims: dict, tag: str) -> int:
    """Q (and K, V unless shared), the SSM's in_proj (x and gate z), x_proj
    (dt's low-rank input, B, C) and dt_proj, the shared out_proj, and the
    MLP's gate, up and down projections."""
    d, h, kv = dims["d_model"], dims["n_heads"], dims["n_kv_heads"]
    e, ev, f = dims["head_dim"], dims["v_head_dim"], dims["d_ff"]
    di, n, r = dims["d_inner"], dims["ssm_state"], dims["dt_rank"]
    attn = d * h * e + (0 if _shared(tag) else d * kv * (e + ev))
    ssm = d * 2 * di + di * (r + 2 * n) + r * di
    return attn + ssm + di * d + 3 * d * f


def small_weights(dims: dict, tag: str) -> int:
    """The two layer norms, the two stream norms, the conv and its bias,
    dt's bias and the norms on dt, B and C, A and D."""
    d, di, n, r = (dims["d_model"], dims["d_inner"], dims["ssm_state"],
                   dims["dt_rank"])
    norms = 2 * d + dims["n_heads"] * dims["v_head_dim"] + di + r + 2 * n
    return norms + di * dims["conv_width"] + di + di + di * n + di


def attended(dims: dict, tag: str, pos: int) -> int:
    """Keys a token at ``pos`` sees: the meta tokens, and every earlier
    token and itself (full) or the last ``window`` of them (local)."""
    seen = pos + 1
    if tag.split(":")[1] == "local":
        seen = min(seen, dims["window"])
    return seen + dims["meta_tokens"]


def attn_flops(dims: dict, tag: str, pos: int) -> float:
    """Scores (q/k head size) and weighted sum (value head size) over the
    attended keys, every query head."""
    return 2.0 * dims["n_heads"] * (dims["head_dim"] + dims["v_head_dim"]) \
        * attended(dims, tag, pos)


def state_bytes(dims: dict, tag: str, pos: int) -> int:
    """K and V of the attended positions in bf16, once per KV group (a
    shared layer reads the cache its group's first layer read), the SSM
    state in float32 as the program keeps it, and the conv tail in bf16."""
    di = dims["d_inner"]
    kv = 0 if _shared(tag) else dims["n_kv_heads"] * (
        dims["head_dim"] + dims["v_head_dim"]) * BF16 * attended(dims, tag,
                                                                  pos)
    return kv + di * dims["ssm_state"] * F32 + (dims["conv_width"] - 1) * \
        di * BF16
