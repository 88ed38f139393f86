"""Blocks held by a decode step's live rows over the blocks the manager
may hand out, in the Ouro serve cell: how much of the arena the batch has
at work."""
from benchmarks.layer_metrics._ouro import arena_fill_pct


def read(facts):
    return arena_fill_pct(facts)
