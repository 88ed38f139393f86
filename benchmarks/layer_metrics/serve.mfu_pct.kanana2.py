"""Model FLOP/s utilisation of the Kanana-2 serve cell: the share of the
chip's bf16 peak that the whole step (prefill and decode) puts to the
model's required work."""
from benchmarks.layer_metrics._kanana2 import serve_mfu_pct


def read(facts):
    return serve_mfu_pct(facts)
