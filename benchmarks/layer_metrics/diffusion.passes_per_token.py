"""Row-passes (a row's block through one execution, denoise or commit)
over the positions they committed, from `stats()["diffusion"]`: 1.25
under the cell's schedule (five executions a block of four)."""
from benchmarks.layer_metrics._sdar import passes_per_token


def read(facts):
    return passes_per_token(facts)
