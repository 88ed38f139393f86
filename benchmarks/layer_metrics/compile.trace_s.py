"""Seconds the chip-holding process spent tracing (jax's `jaxpr_trace_duration`; `jax.trace` spans)
from its first line to `setup_end`, events under 10 ms included."""
from benchmarks.layer_metrics._startup import compile_s


def read(facts):
    return compile_s(facts, "trace_s")
