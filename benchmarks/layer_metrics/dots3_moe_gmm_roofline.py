"""Roofline share of `moe_gmm` in the dots3 serve cell: an assignment's
row through its expert, a drawn expert's weights once."""
from benchmarks.layer_metrics._dots3 import moe_gmm_roofline_pct


def read(facts):
    return moe_gmm_roofline_pct(facts)
