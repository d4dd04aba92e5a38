"""hymba-1.5b [hybrid] — parallel attention + mamba heads in every layer.

Sizes from the published ``config.json`` of nvidia/Hymba-1.5B-Base and
arXiv:2411.13676: 32 layers, d_model 1600, 25 query heads over 5 KV heads,
q/k head size 64 and value head size 128 (so the attention heads' output,
25 x 128, matches the SSM's d_inner 3200), SwiGLU d_ff 5504, vocab 32001,
tied embeddings, RMSNorm eps 1e-6, RoPE theta 10000.  The SSM heads are
Mamba-1 style: expand 2, state 16, a width-4 convolution with bias,
dt_rank 100, RMSNorms on dt, B and C.  Full attention at layers 0, 15 and
31 (``global_attn_idx``), a 1024-token sliding window elsewhere; the KV
cache is shared within ``kv_reuse_group`` (1,2), (3,4), ... (13,14),
(16,17,18), (19,20), ... (29,30), whose later members project only Q;
128 meta tokens are prepended to every sequence.
"""
from repro.configs.base import HYBRID, SHARED_KV, ArchConfig, SSMConfig

FULL = HYBRID + ":full"
LOCAL = HYBRID + ":local"
REUSE = LOCAL + SHARED_KV


def _pattern():
    pairs = (LOCAL, REUSE) * 7
    return ((FULL,) + pairs + (FULL, LOCAL, REUSE, REUSE) + (LOCAL, REUSE) * 6
            + (FULL,))


CONFIG = ArchConfig(
    name="hymba-1.5b",
    family="hybrid",
    n_layers=32,
    d_model=1600,
    n_heads=25,
    n_kv_heads=5,
    d_ff=5504,
    vocab=32001,
    head_dim=64,
    v_head_mult=2,
    layer_pattern=_pattern(),
    window=1024,
    meta_tokens=128,
    ssm=SSMConfig(state_dim=16, conv_width=4, expand=2, dt_rank=100),
    rope_theta=10000.0,
    norm_eps=1e-6,
    tie_embeddings=True,
)
