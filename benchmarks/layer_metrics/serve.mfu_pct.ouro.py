"""Model FLOP/s utilisation of the Ouro serve cell: the share of the
chip's bf16 peak that the whole step (prefill and decode, four passes of
48 layers, the head, attention at the window's mean context) puts to the
model's required work."""
from benchmarks.layer_metrics._ouro import serve_mfu_pct


def read(facts):
    return serve_mfu_pct(facts)
