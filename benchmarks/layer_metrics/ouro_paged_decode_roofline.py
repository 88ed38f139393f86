"""Roofline share of the `paged_attention` kernel's decode calls at the
Ouro cell's shape: 16 KV heads, ONE query row a KV head."""
from benchmarks.layer_metrics._ouro import paged_decode_roofline_pct


def read(facts):
    return paged_decode_roofline_pct(facts)
