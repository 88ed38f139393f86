"""What the Kanana-2 serve cell's new kernels and its whole step REQUIRE,
from shapes (conventions as in `benchmarks/peaks.py`: operands read once,
results written once, at the dtype they are passed in; work the
implementation chose to repeat, rows it chose to pad and lanes it chose to
pad are not counted).

H heads, L = `kv_lora_rank`, r = `qk_rope_head_dim`, n = `qk_nope_head_dim`,
v = `v_head_dim`, e = `hidden_size`.

The latent kernels (`latent_decode`: one call = one layer's decode step
over every slot; `latent_prefill`: one call = one layer over one chunk).
A (query token, cached token) pair costs every head a score of depth L + r
and a value product of depth L: 2 H (2 L + r) FLOP (2 x 32 x 1,088 =
69,632). Bytes: each live page ONCE a slot a call, the L + r real values a
token (1,152 B; the program stores 640 lanes, and the 64 extra show as a
lower share), q [rows, L + r] in and o [rows, L] out once, bf16.

The grouped product `moe_gmm` in a served step (one layer): 2 x 3 e F FLOP
an assignment (gate, up, down); bytes: the 3 e F weights of every expert
that DREW a row, once, and an assignment's rows: e in, 2F out, F in, e
out, bf16. The count is of the work, not of what 128-row tiles read: an
expert that drew 3 rows needs its weights once and 3 rows.

A token's model FLOPs (`serve_flops_per_token`): 2 a parameter that sits
in a product the token passes through (q_proj, kv_a_proj, the absorbed
W_kvb^K and W_kvb^V, o_proj; the dense SwiGLU; the router, k experts and
the shared expert), the attention's 2 H (2 L + r) a cached token, and for
a DECODED token the head (2 V e); a prefilled token does not pay the head
(one position a chunk is read).

A step's required bytes (`step_bytes`): every parameter outside the routed
experts and the embedding once, the routed experts that drew a row once,
the embedding's rows of the step's tokens, the head where a position is
read, and the latent pages of every live row's context once a layer. A
step is ONE execution: a decode step with a prefill chunk aboard (PR 58)
is one step over the decode rows and the chunk's rows together, its
weights and the union of the experts they drew charged once, not a decode
step and a chunk with weights each.
"""

from __future__ import annotations

from typing import Any, Dict


def _dims(cfg: Dict[str, Any]):
    """(H, L, r, n, v, e)."""
    return (int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"]),
            int(cfg["qk_rope_head_dim"]), int(cfg["qk_nope_head_dim"]),
            int(cfg["v_head_dim"]), int(cfg["hidden_size"]))


def pair_flops(cfg: Dict[str, Any]) -> int:
    """FLOP of one (query token, cached token) pair, all heads."""
    h, lat, r, _, _, _ = _dims(cfg)
    return 2 * h * (2 * lat + r)


def token_row_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """The real values of a token's cache row a layer."""
    _, lat, r, _, _, _ = _dims(cfg)
    return (lat + r) * itemsize


def latent_required(cfg: Dict[str, Any], rows: float, q_tokens: float,
                    pairs: float, cached_tokens: float) -> Dict[str, float]:
    """One call over `rows` live rows of `q_tokens` query tokens each:
    `pairs` (query token, cached token) pairs in all, `cached_tokens` live
    cached tokens in all (each row's context once)."""
    h, lat, r, _, _, _ = _dims(cfg)
    q_and_o = 2.0 * rows * q_tokens * h * (2 * lat + r)
    return {"flops": float(pairs * pair_flops(cfg)),
            "bytes": cached_tokens * token_row_bytes(cfg) + q_and_o}


def latent_decode_required(cfg: Dict[str, Any], rows: float,
                           context: float) -> Dict[str, float]:
    """A decode step's call: `rows` live slots at a mean `context`."""
    return latent_required(cfg, rows, 1.0, rows * context, rows * context)


def latent_prefill_required(cfg: Dict[str, Any], chunk: float,
                            prefix: float) -> Dict[str, float]:
    """A prefill chunk's call: `chunk` live query tokens behind `prefix`
    cached ones (causal inside the chunk)."""
    return latent_required(cfg, 1.0, chunk,
                           chunk * prefix + chunk * (chunk + 1) / 2.0,
                           prefix + chunk)


def expert_params(cfg: Dict[str, Any]) -> int:
    """One routed expert's parameters."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def moe_gmm_required(cfg: Dict[str, Any], assignments: float,
                     experts_drawn: float) -> Dict[str, float]:
    """One layer's two grouped products."""
    e, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    return {"flops": 2.0 * assignments * expert_params(cfg),
            "bytes": 2.0 * experts_drawn * expert_params(cfg)
            + 2.0 * assignments * (2 * e + 3 * f)}


def attention_params(cfg: Dict[str, Any]) -> int:
    """q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj."""
    h, lat, r, n, v, e = _dims(cfg)
    return e * h * (n + r) + e * (lat + r) + lat * h * (n + v) + h * v * e


def dense_mlp_params(cfg: Dict[str, Any]) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def shared_params(cfg: Dict[str, Any]) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["n_shared_experts"]) \
        * int(cfg["moe_intermediate_size"])


def router_params(cfg: Dict[str, Any]) -> int:
    return int(cfg["hidden_size"]) * int(cfg["n_routed_experts"])


def layer_counts(cfg: Dict[str, Any]):
    """(dense layers, expert layers)."""
    dense = int(cfg["first_k_dense_replace"])
    return dense, int(cfg["num_hidden_layers"]) - dense


def model_params(cfg: Dict[str, Any]) -> int:
    """Every parameter (norms and the selection bias included)."""
    h, lat, r, n, v, e = _dims(cfg)
    dense, moe = layer_counts(cfg)
    per_layer = attention_params(cfg) + lat + 2 * e
    experts = int(cfg["n_routed_experts"])
    return (dense + moe) * per_layer + dense * dense_mlp_params(cfg) \
        + moe * (experts * expert_params(cfg) + shared_params(cfg)
                 + router_params(cfg) + experts) \
        + 2 * int(cfg["vocab_size"]) * e + e


def serve_flops_per_token(cfg: Dict[str, Any], decoded: bool,
                          context: float) -> float:
    """Model FLOPs of one token that sees `context` cached tokens."""
    dense, moe = layer_counts(cfg)
    k = int(cfg["num_experts_per_tok"])
    per_layer = 2.0 * attention_params(cfg) + pair_flops(cfg) * context
    flops = (dense + moe) * per_layer + dense * 2.0 * dense_mlp_params(cfg) \
        + moe * 2.0 * (router_params(cfg) + k * expert_params(cfg)
                       + shared_params(cfg))
    head = 2.0 * int(cfg["vocab_size"]) * int(cfg["hidden_size"])
    return flops + (head if decoded else 0.0)


def step_bytes(cfg: Dict[str, Any], tokens: float, cached_tokens: float,
               experts_drawn: float, head_rows: float,
               itemsize: int = 2) -> float:
    """Required bytes of one step (one execution: module docstring) over
    `tokens` live tokens whose rows see `cached_tokens` cached tokens in
    all (each row's context once a layer), with `experts_drawn` experts
    drawing a row a layer (mean); the head is read where `head_rows` > 0."""
    e = int(cfg["hidden_size"])
    dense, moe = layer_counts(cfg)
    weights = (dense + moe) * attention_params(cfg) \
        + dense * dense_mlp_params(cfg) \
        + moe * (shared_params(cfg) + router_params(cfg)
                 + experts_drawn * expert_params(cfg)) \
        + tokens * e + (int(cfg["vocab_size"]) * e if head_rows else 0)
    pages = (dense + moe) * cached_tokens * token_row_bytes(cfg, itemsize)
    return itemsize * weights + pages
