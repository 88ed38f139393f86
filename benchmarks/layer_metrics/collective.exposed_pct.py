"""Device time of collective ops during which no compute op runs on that
device, over the device time of the train step."""
from benchmarks import xplane
from benchmarks.layer_metrics._common import STEP_MODULE


def read(facts):
    trace = facts.get("trace")
    if not trace:
        return None
    _, step_s = xplane.module_matching(trace, STEP_MODULE)
    return 100.0 * trace["exposed_collective_s"] / step_s if step_s else None
