"""The share of the chip's HBM bandwidth that the executions' REQUIRED
bytes take in the SDAR serve cell (attention and router weights, every
expert that drew a row, the head, the visible keys and values): the bound
that binds a block step here."""
from benchmarks.layer_metrics._sdar import serve_membw_pct


def read(facts):
    return serve_membw_pct(facts)
