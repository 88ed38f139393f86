"""Window-kind pages given back behind live sequences' windows a second
of the window (`stats()["kv_kinds"]`)."""
from benchmarks.layer_metrics._dots3 import window_blocks_released_per_s


def read(facts):
    return window_blocks_released_per_s(facts)
