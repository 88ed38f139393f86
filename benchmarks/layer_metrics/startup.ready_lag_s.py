"""End of the chip holder's last start-up span (`serve.replica.ctor` /
`train.backend.on_start`) -> the root's end: promotion, routing table and
`wait_ready`'s polling; the hand-over to the train function (program span)."""
from benchmarks.layer_metrics._startup import ready_lag_s as read  # noqa: F401
