"""Device time of the `retention_step` and `retention_chunk_fwd` kernels
over the device time of `jit_decode_fn` + `jit_prefill_fn`, from the
trace."""
from benchmarks.layer_metrics._brumby import retention_share_pct


def read(facts):
    return retention_share_pct(facts)
