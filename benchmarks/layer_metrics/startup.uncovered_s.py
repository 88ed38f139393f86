"""Seconds of the start-up root (`train.startup` / `serve.run`) that no
other span of any process covers: time nobody has named (program span)."""
from benchmarks.layer_metrics._startup import uncovered_s as read  # noqa: F401
