"""Held experts that drew a row, a layer a decode step (of 32 held), from
the counters the step keeps in its cache."""
from benchmarks.layer_metrics._dots3 import experts_drawn_per_step


def read(facts):
    return experts_drawn_per_step(facts)
