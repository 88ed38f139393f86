"""Keys the selection chose over keys visible in the window's decode
steps, from the counters the step keeps in its cache."""
from benchmarks.layer_metrics._dots3 import selected_pct


def read(facts):
    return selected_pct(facts)
