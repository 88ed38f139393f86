"""1 - union of device-op intervals over the traced window."""
from benchmarks.layer_metrics._common import idle_pct


def read(facts):
    return idle_pct(facts)
