"""Roofline share of the `flash_bwd_dkv` kernel at head_dim 256, 16 heads,
seq 8192 (this cell's gated softmax-attention layer)."""
from benchmarks.layer_metrics._qwen3next import flash_d256_roofline_pct


def read(facts):
    return flash_d256_roofline_pct(facts, "flash_bwd_dkv")
