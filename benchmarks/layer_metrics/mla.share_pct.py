"""Device time of the latent-attention kernels (`latent_decode`,
`latent_prefill`) over the device time of `jit_decode_fn` +
`jit_prefill_fn`, from the trace."""
from benchmarks.layer_metrics._kanana2 import LATENT_KERNELS, share_pct


def read(facts):
    return share_pct(facts, LATENT_KERNELS)
