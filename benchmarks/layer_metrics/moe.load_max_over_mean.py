"""The fullest held expert's load over the mean load, over all layers of a
step, averaged over the window's steps (1.0 = even)."""
from benchmarks.layer_metrics._qwen3next import moe_counter


def read(facts):
    return moe_counter(facts, "load_max_over_mean")
