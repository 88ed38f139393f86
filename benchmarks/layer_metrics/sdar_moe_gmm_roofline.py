"""Roofline share of `moe_gmm` in the SDAR cell's executions: the weights
of the experts that DREW a row once (all 128 in a block step), plus the
rows, over the kernel's time."""
from benchmarks.layer_metrics._sdar import moe_gmm_roofline_pct


def read(facts):
    return moe_gmm_roofline_pct(facts)
