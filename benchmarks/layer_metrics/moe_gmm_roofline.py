"""Roofline share of the nine grouped products of a step, over the
assignments the last step made to held experts."""
from benchmarks.layer_metrics._qwen3next import moe_gmm_roofline_pct


def read(facts):
    return moe_gmm_roofline_pct(facts)
