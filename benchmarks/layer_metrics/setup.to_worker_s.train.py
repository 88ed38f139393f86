"""`fit()` called -> first line of the train loop in the granted worker
(system-wide CLOCK_MONOTONIC): grant, spawn, imports, backend start."""
from benchmarks.layer_metrics._common import span_between


def read(facts):
    return span_between(facts, "fit_called", "worker_first_line")
