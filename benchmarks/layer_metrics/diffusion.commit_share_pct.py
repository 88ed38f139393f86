"""Commit row-passes over all row-passes, from `stats()["diffusion"]`:
20 under the cell's schedule (the fifth pass, which chooses nothing)."""
from benchmarks.layer_metrics._sdar import commit_share_pct


def read(facts):
    return commit_share_pct(facts)
