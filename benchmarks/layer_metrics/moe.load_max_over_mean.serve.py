"""The fullest expert's load over the mean load in a decode step, a layer,
averaged over the window's decode steps (the program's device counters)."""
from benchmarks.layer_metrics._kanana2 import load_max_over_mean


def read(facts):
    return load_max_over_mean(facts)
