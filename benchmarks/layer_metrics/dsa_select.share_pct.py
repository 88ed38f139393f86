"""Device time of the selection (scope `dsa_select`: the threshold by
counting, the ties, the layout of the chosen positions) over the step
programs'."""
from benchmarks.layer_metrics._dots3 import scope_share_pct


def read(facts):
    return scope_share_pct(facts, ("dsa_select",))
