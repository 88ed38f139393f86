"""(token, choice) pairs routed to a held expert, a token a layer, over the
window's steps, from each step's own outputs (10 x 32/512 = 0.625 at even
routing). It describes the traffic the router makes: every assignment is a
row of the grouped products, so fewer of them is a shorter step."""
from benchmarks.layer_metrics._qwen3next import moe_counter


def read(facts):
    return moe_counter(facts, "held_assignments_per_token")
