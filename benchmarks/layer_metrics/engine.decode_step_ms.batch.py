"""Device time per execution of the engine's decode program. Where the
model offers a fused step (PR 58) it is the MEAN over plain decode steps
and those with a prefill chunk aboard: both are `jit_decode_fn`, and the
trace cannot tell them apart (`engine.chunk_aboard_pct` says how many of
the chunks rode)."""
from benchmarks.layer_metrics._common import DECODE_MODULE, module_step_ms


def read(facts):
    return module_step_ms(facts, DECODE_MODULE)
