"""Roofline share of the `gdn_chunk_fwd` kernel at the train shape."""
from benchmarks.layer_metrics._qwen3next import gdn_roofline_pct


def read(facts):
    return gdn_roofline_pct(facts, "gdn_chunk_fwd")
