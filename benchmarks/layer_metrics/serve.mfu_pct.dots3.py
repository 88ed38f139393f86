"""The share of the chip's bf16 peak that the whole window of the dots3
serve cell (chunks and decode steps) puts to the model's required
arithmetic."""
from benchmarks.layer_metrics._dots3 import serve_mfu_pct


def read(facts):
    return serve_mfu_pct(facts)
