"""Device time per execution of the engine's prefill program in a
closed-loop cell: one prompt chunk between two decode steps."""
from benchmarks.layer_metrics._common import PREFILL_MODULE, module_step_ms


def read(facts):
    return module_step_ms(facts, PREFILL_MODULE)
