"""The share of the chip's HBM bandwidth that the steps' REQUIRED bytes
take in the Ouro serve cell (the layers' weights once a pass, the head,
the live keys and values read, the new ones written): the bound that
binds a decode step here."""
from benchmarks.layer_metrics._ouro import serve_membw_pct


def read(facts):
    return serve_membw_pct(facts)
