"""Model FLOP/s utilisation of the SDAR serve cell: the share of the
chip's bf16 peak that the whole window (prefills and block executions)
puts to the model's required arithmetic, FIVE passes of a position a
committed token under the cell's schedule."""
from benchmarks.layer_metrics._sdar import serve_mfu_pct


def read(facts):
    return serve_mfu_pct(facts)
