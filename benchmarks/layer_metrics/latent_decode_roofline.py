"""Roofline share of the `latent_decode` kernel at the window's live
rows and mean context (each live page once a slot, q and o once)."""
from benchmarks.layer_metrics._kanana2 import latent_roofline_pct


def read(facts):
    return latent_roofline_pct(facts, "latent_decode")
