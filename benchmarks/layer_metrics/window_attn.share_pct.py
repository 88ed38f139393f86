"""Device time of the sliding layers' attention (scope `window_attn`) over
the step programs'."""
from benchmarks.layer_metrics._dots3 import scope_share_pct


def read(facts):
    return scope_share_pct(facts, ("window_attn",))
