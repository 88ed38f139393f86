"""Seconds the chip-holding process spent lowering to MLIR (`jaxpr_to_mlir_module_duration`; `jax.lower`
spans)
from its first line to `setup_end`, events under 10 ms included."""
from benchmarks.layer_metrics._startup import compile_s


def read(facts):
    return compile_s(facts, "lower_s")
