"""Counts of one pre-norm decoder layer (``dense`` in the program's
``layer_pattern``): grouped-query attention and a SwiGLU MLP."""

BF16 = 2


def matmul_weights(dims: dict, tag: str) -> int:
    """Weights of the layer's matrix products: Q, K, V, O and the MLP's
    gate, up and down projections."""
    d, h, kv = dims["d_model"], dims["n_heads"], dims["n_kv_heads"]
    e, f = dims["head_dim"], dims["d_ff"]
    return d * h * e + 2 * d * kv * e + h * e * d + 3 * d * f


def small_weights(dims: dict, tag: str) -> int:
    """The two norm gains."""
    return 2 * dims["d_model"]


def attended(dims: dict, tag: str, pos: int) -> int:
    """Keys a token at ``pos`` attends to: every earlier one and itself."""
    return pos + 1


def attn_flops(dims: dict, tag: str, pos: int) -> float:
    """Scores and weighted sum over the attended keys, every query head."""
    return 4.0 * dims["n_heads"] * dims["head_dim"] * attended(dims, tag, pos)


def state_bytes(dims: dict, tag: str, pos: int) -> int:
    """K and V of the attended positions, in bf16."""
    return 2 * dims["n_kv_heads"] * dims["head_dim"] * BF16 * \
        attended(dims, tag, pos)
