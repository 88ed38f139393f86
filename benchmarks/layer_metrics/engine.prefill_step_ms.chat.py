"""Device time per execution of the engine's prefill program: the stall
one prompt chunk puts between two tokens of every running row."""
from benchmarks.layer_metrics._common import PREFILL_MODULE, module_step_ms


def read(facts):
    return module_step_ms(facts, PREFILL_MODULE)
