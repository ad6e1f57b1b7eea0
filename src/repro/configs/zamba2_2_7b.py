"""zamba2-2.7b [hybrid] — Mamba2 backbone + two shared attention blocks. [arXiv:2411.15242]

Every sixth layer is a hybrid layer: one of the two shared attention+MLP
blocks (used in turn) reads ``concat(h, e0)`` (width 2d, ``e0`` the token
embedding), its MLP carries that layer's own LoRA adapter, a per-layer
d -> d linear maps its output, and the sum feeds that layer's Mamba2 block
(``models/hybrid.py``).  Attention heads are 2d / heads = 160 wide.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="zamba2-2.7b",
    family="hybrid",
    n_layers=54,
    d_model=2560,
    n_heads=32,
    n_kv_heads=32,
    head_dim=160,                 # attention_hidden_size 2d over 32 heads
    d_ff=10240,                   # MLP inside the shared blocks
    vocab_size=32000,
    mlp="geglu",                  # gated; Zamba2's "gelu" is the exact form
    tie_embeddings=True,
    ssm_state=64,
    ssm_expand=2,
    ssm_head_dim=64,              # -> 80 SSD heads (d_inner=5120)
    ssm_chunk=256,
    conv_width=4,
    attn_every=6,                 # hybrid layers 5, 11, ..., 53
    n_shared_blocks=2,
    adapter_rank=128,
)


def smoke() -> ArchConfig:
    """4 layers, a hybrid layer every 2: layers 1 and 3 call blocks A, B."""
    return CONFIG.replace(
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=128, vocab_size=256, ssm_state=16, ssm_head_dim=16, ssm_chunk=8,
        attn_every=2, adapter_rank=8, loss_chunk=16,
    )
