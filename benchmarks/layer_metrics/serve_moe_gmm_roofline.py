"""Roofline share of `moe_gmm` in served steps: the weights of the experts
that DREW a row once, plus the rows, over the kernel's time."""
from benchmarks.layer_metrics._kanana2 import serve_moe_gmm_roofline_pct


def read(facts):
    return serve_moe_gmm_roofline_pct(facts)
