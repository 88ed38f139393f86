"""`jax.claim_devices` (jax import, backend up, grant check) and
`jax.distributed` in the process that holds the chip(s) (program span)."""
from benchmarks.layer_metrics._startup import backend_s as read  # noqa: F401
