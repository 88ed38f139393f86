"""Roofline share of the `paged_attention` kernel's calls in block steps
at the SDAR cell's shape: 4 KV heads, 32 query rows a KV head, a row's live
pages once for its four queries."""
from benchmarks.layer_metrics._sdar import paged_block_roofline_pct


def read(facts):
    return paged_block_roofline_pct(facts)
