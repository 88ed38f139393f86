"""Roofline share of the `dsa_index` kernel: every visible index key once a
sequence, a float32 score a (query, key) out, over its device time."""
from benchmarks.layer_metrics._dots3 import index_roofline_pct


def read(facts):
    return index_roofline_pct(facts)
