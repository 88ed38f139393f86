"""Roofline share of the `flash_bwd_dkv` kernel at the train shape."""
from benchmarks.layer_metrics._common import flash_roofline_pct


def read(facts):
    return flash_roofline_pct(facts, "flash_bwd_dkv")
