"""Device time of the `paged_attention` kernel over the device time of
`jit_decode_fn` + `jit_prefill_fn` in the SDAR serve cell, from the
trace."""
from benchmarks.layer_metrics._sdar import PAGED_KERNEL, share_pct


def read(facts):
    return share_pct(facts, PAGED_KERNEL)
