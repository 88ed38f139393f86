"""`worker.spawn` start -> `worker.boot` end of the process that holds the
chip(s): fork or exec, interpreter, imports, registration (program span)."""
from benchmarks.layer_metrics._startup import spawn_s as read  # noqa: F401
