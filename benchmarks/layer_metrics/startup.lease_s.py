"""`raylet.lease` spans on the way to the process that holds the chip(s):
the granted lease and every refused attempt before it (program span)."""
from benchmarks.layer_metrics._startup import lease_s as read  # noqa: F401
