"""Device time of the `ssd_step` and `ssd_chunk_fwd` kernels over the
device time of `jit_decode_fn` + `jit_prefill_fn`, from the trace."""
from benchmarks.layer_metrics._falconh1 import ssm_share_pct


def read(facts):
    return ssm_share_pct(facts)
