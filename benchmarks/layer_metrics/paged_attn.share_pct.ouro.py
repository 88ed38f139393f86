"""Device time of the `paged_attention` kernel over the device time of
`jit_decode_fn` + `jit_prefill_fn` in the Ouro serve cell, from the
trace."""
from benchmarks.layer_metrics._ouro import paged_share_pct


def read(facts):
    return paged_share_pct(facts)
