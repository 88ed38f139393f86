"""Device time per execution of the engine's decode program."""
from benchmarks.layer_metrics._common import DECODE_MODULE, module_step_ms


def read(facts):
    return module_step_ms(facts, DECODE_MODULE)
