"""Device time of the ops under the four `dsa_*` scopes (index, select,
gather, attend) over that of `jit_decode_fn` + `jit_prefill_fn`."""
from benchmarks.layer_metrics._dots3 import DSA_SCOPES, scope_share_pct


def read(facts):
    return scope_share_pct(facts, DSA_SCOPES)
