"""Roofline share of the `retention_step` kernel at the cell's slots."""
from benchmarks.layer_metrics._brumby import kernel_roofline_pct


def read(facts):
    return kernel_roofline_pct(facts, "retention_step")
