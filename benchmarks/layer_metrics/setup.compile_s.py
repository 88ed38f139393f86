"""Lower + compile (or cache load) + first call of every program the cell
uses: the train step, or prefill and decode through the warm-up requests."""
from benchmarks.layer_metrics._common import span


def read(facts):
    return span(facts, "compile_s")
