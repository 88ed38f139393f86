"""Prompt tokens adopted from the radix prefix cache over the prompt tokens
admitted in the dots3 cell's window."""
from benchmarks.layer_metrics._dots3 import prefix_hit_pct


def read(facts):
    return prefix_hit_pct(facts)
