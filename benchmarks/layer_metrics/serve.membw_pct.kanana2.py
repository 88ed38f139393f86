"""The share of the chip's HBM bandwidth that the steps' REQUIRED bytes
(weights once an execution, drawn experts, live latent pages, the head)
take: the bound that binds a decode step here. A decode step with a chunk
aboard is one execution."""
from benchmarks.layer_metrics._kanana2 import serve_membw_pct


def read(facts):
    return serve_membw_pct(facts)
