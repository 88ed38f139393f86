"""Device time of the grouped expert products (`moe_gmm`) over the device
time of `jit_decode_fn` + `jit_prefill_fn` in the SDAR serve cell, from
the trace."""
from benchmarks.layer_metrics._sdar import MOE_KERNEL, share_pct


def read(facts):
    return share_pct(facts, MOE_KERNEL)
