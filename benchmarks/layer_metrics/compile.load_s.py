"""Seconds the chip-holding process spent loading programs the persistent cache held
(`backend_compile_duration` on cache hits; `jax.compile` spans, `cache: hit`)
from its first line to `setup_end`, events under 10 ms included."""
from benchmarks.layer_metrics._startup import compile_s


def read(facts):
    return compile_s(facts, "load_s")
