"""Seconds the chip-holding process spent compiling programs the cache did not hold
(`backend_compile_duration` on misses; `jax.compile` spans)
from its first line to `setup_end`, events under 10 ms included."""
from benchmarks.layer_metrics._startup import compile_s


def read(facts):
    return compile_s(facts, "cold_s")
