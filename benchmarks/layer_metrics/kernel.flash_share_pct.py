"""Device time of the three flash kernels over the device time of the
train step, from the trace."""
from benchmarks import xplane
from benchmarks.layer_metrics._common import (FLASH_KERNELS, STEP_MODULE,
                                              kernel_label)


def read(facts):
    trace = facts.get("trace")
    if not trace:
        return None
    _, step_s = xplane.module_matching(trace, STEP_MODULE)
    kernel_s = sum(xplane.ops_matching(trace, kernel_label(k))[1]
                   for k in FLASH_KERNELS)
    return 100.0 * kernel_s / step_s if step_s and kernel_s else None
