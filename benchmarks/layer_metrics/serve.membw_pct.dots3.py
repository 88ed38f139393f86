"""The dots3 serve cell's required bytes a second (`peaks_dots3.step_bytes`
at the window's counts) over the chip's HBM bandwidth."""
from benchmarks.layer_metrics._dots3 import serve_membw_pct


def read(facts):
    return serve_membw_pct(facts)
