"""Roofline share of the `retention_chunk_fwd` kernel at the cell's prefill
chunk."""
from benchmarks.layer_metrics._brumby import kernel_roofline_pct


def read(facts):
    return kernel_roofline_pct(facts, "retention_chunk_fwd")
