"""Roofline share of the `latent_prefill` kernel at the window's mean
question behind a cached document."""
from benchmarks.layer_metrics._kanana2 import latent_roofline_pct


def read(facts):
    return latent_roofline_pct(facts, "latent_prefill")
