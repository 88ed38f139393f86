"""Prompt tokens adopted from the radix prefix cache over the prompt
tokens admitted in the window (`stats()["prefix_cache"]["hit_tokens"]`)."""
from benchmarks.layer_metrics._kanana2 import prefix_hit_pct


def read(facts):
    return prefix_hit_pct(facts)
