"""Experts that drew a row, a layer a block step, from the counters the
step keeps in its cache: near 128 of 128 at 128 rows x 8 choices."""
from benchmarks.layer_metrics._sdar import experts_drawn_per_step


def read(facts):
    return experts_drawn_per_step(facts)
