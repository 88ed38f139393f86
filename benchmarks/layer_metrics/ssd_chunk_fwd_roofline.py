"""Roofline share of the `ssd_chunk_fwd` kernel at the cell's prefill
chunk."""
from benchmarks.layer_metrics._falconh1 import kernel_roofline_pct


def read(facts):
    return kernel_roofline_pct(facts, "ssd_chunk_fwd")
