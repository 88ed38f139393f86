"""Device time of the `moe_gmm*` kernels (the held experts' grouped
products) over the device time of the train step, from the trace."""
from benchmarks.layer_metrics._qwen3next import (MOE_KERNELS,
                                                 share_of_step_pct)


def read(facts):
    return share_of_step_pct(facts, MOE_KERNELS)
