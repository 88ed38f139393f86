"""Roofline share of the `ssd_step` kernel at the cell's slots."""
from benchmarks.layer_metrics._falconh1 import kernel_roofline_pct


def read(facts):
    return kernel_roofline_pct(facts, "ssd_step")
