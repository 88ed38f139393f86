"""Device time of the `gdn_chunk_*` kernels over the device time of the
train step, from the trace."""
from benchmarks.layer_metrics._qwen3next import share_of_step_pct


def read(facts):
    return share_of_step_pct(facts, r"gdn_chunk_(fwd|bwd)")
