"""Roofline share of the sliding layers' latent kernel calls (scope
`window_attn`): the rows a sequence's queries can see, once a sequence."""
from benchmarks.layer_metrics._dots3 import window_latent_roofline_pct


def read(facts):
    return window_latent_roofline_pct(facts)
