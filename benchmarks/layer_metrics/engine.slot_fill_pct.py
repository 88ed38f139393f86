"""Decode tokens emitted in the traced window over decode executions
times batch slots. A request's first token comes from its last prefill
chunk, not from a decode step, and is taken out."""
from benchmarks import xplane
from benchmarks.layer_metrics._common import DECODE_MODULE


def read(facts):
    trace, counters = facts.get("trace"), facts.get("counters") or {}
    emitted = counters.get("tokens_emitted_in_trace")
    if not trace or emitted is None:
        return None
    runs, _ = xplane.module_matching(trace, DECODE_MODULE)
    if not runs:
        return None
    decoded = emitted - counters.get("first_tokens_in_trace", 0)
    return 100.0 * decoded / (runs * counters["batch_slots"])
