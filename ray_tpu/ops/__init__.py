"""TPU kernels (Pallas) + XLA fallbacks for the hot ops."""

from ray_tpu.ops.attention import (flash_attention, flash_attention_bse,
                                   mha_reference)
from ray_tpu.ops.ring_attention import ring_attention

__all__ = ["flash_attention", "flash_attention_bse", "mha_reference",
           "ring_attention"]
