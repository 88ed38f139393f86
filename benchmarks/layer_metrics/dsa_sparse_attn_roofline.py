"""Roofline share of gather and attention together (scopes `dsa_gather`,
`dsa_attend`) over the chosen rows' bytes, whatever implements them."""
from benchmarks.layer_metrics._dots3 import sparse_attn_roofline_pct


def read(facts):
    return sparse_attn_roofline_pct(facts)
