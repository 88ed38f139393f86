"""Device time of the grouped expert products (`moe_gmm`) over the step
programs' in the dots3 serve cell."""
from benchmarks.layer_metrics._dots3 import MOE_KERNEL, kernel_share_pct


def read(facts):
    return kernel_share_pct(facts, MOE_KERNEL)
