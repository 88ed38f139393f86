"""Passes of the layer stack executed a live token (`total_ut_steps`
where every token takes every pass), from the step's own counters."""
from benchmarks.layer_metrics._ouro import passes_per_token


def read(facts):
    return passes_per_token(facts)
