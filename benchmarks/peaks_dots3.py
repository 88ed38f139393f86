"""What the `dots3_note` serve cell's algorithm REQUIRES, from shapes: the
model's FLOPs a token at a context, the bytes of a decode step and of a
chunk, and each kernel's own floor. The conventions are `peaks.py`'s: work
the algorithm needs, not work an implementation chose; every operand read
once and every result written once at its stored width.

A token's FLOPs (2 a multiply-add): every product's parameters once; the
absorbed attention (a head's query into the latent and its output out of
it, and its scores and values over the keys it READS: min(context,
`index_topk`) in a full layer, min(context, `sliding_window_size`) in a
sliding one); the indexer over EVERY visible key (`index_n_heads` x
`index_head_dim` a key); the shared expert and the HELD share of a token's
routed experts (`num_experts_per_tok` x held / routed on average: what the
other chips compute is theirs); the head over the held rows.

A step's bytes: the weights outside the routed experts once; the experts
that DREW A ROW once each (the window's counters say how many); a full
layer the index keys of every visible position once a SEQUENCE and the
chosen rows once a QUERY (a chunk's queries choose their own, but never
more rows than are visible); a sliding layer the rows a sequence's queries
can see; the queries' own rows written once.
"""

from __future__ import annotations

from typing import Any, Dict

FULL = "full_attention"
BYTES = 2          # bf16


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def geometry(cfg: Dict[str, Any], kind: str) -> Dict[str, int]:
    p = "" if kind == FULL else "swa_"
    g = {"heads": cfg[p + "num_attention_heads"],
         "q_rank": cfg[p + "q_lora_rank"], "kv_rank": cfg[p + "kv_lora_rank"],
         "n": cfg[p + "qk_nope_head_dim"], "r": cfg[p + "qk_rope_head_dim"],
         "v": cfg[p + "v_head_dim"]}
    g["page"] = _lanes(g["kv_rank"] + g["r"])
    return g


def kinds(cfg) -> list:
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def attention_params(cfg, kind: str) -> int:
    """A layer's attention products (norms left out: not matmuls)."""
    e, g = int(cfg["hidden_size"]), geometry(cfg, kind)
    n = e * g["q_rank"] + g["q_rank"] * g["heads"] * (g["n"] + g["r"]) \
        + e * (g["kv_rank"] + g["r"]) \
        + g["kv_rank"] * g["heads"] * (g["n"] + g["v"]) \
        + g["heads"] * g["v"] * e + e * g["heads"]
    if kind == FULL:
        n += g["q_rank"] * cfg["index_n_heads"] * cfg["index_head_dim"] \
            + e * cfg["index_head_dim"] + e * cfg["index_n_heads"]
    return n


def expert_params(cfg) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def keys_read(cfg, kind: str, context: float) -> float:
    limit = cfg["index_topk"] if kind == FULL else cfg["sliding_window_size"]
    return min(float(context), float(limit))


def held_share(cfg) -> float:
    return float(cfg["n_routed_experts"]) \
        / float(cfg["deployment"]["experts_routed"])


def serve_flops_per_token(cfg, context: float) -> float:
    """The model's required FLOPs for one token whose query sees `context`
    positions (module docstring)."""
    e = int(cfg["hidden_size"])
    flops = 0.0
    for i, kind in enumerate(kinds(cfg)):
        g = geometry(cfg, kind)
        # W_kvb is absorbed: a head's query into the latent, its output out
        absorbed = g["heads"] * (g["n"] * g["kv_rank"]
                                 + g["kv_rank"] * g["v"])
        products = attention_params(cfg, kind) \
            - g["kv_rank"] * g["heads"] * (g["n"] + g["v"]) + absorbed
        keys = keys_read(cfg, kind, context)
        flops += 2.0 * products \
            + 2.0 * g["heads"] * keys * (2 * g["kv_rank"] + g["r"])
        if kind == FULL:
            flops += 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"] \
                * context
        if i < int(cfg["first_k_dense_replace"]):
            flops += 2.0 * 3 * e * int(cfg["intermediate_size"])
        else:
            flops += 2.0 * (e * cfg["deployment"]["experts_routed"]
                            + expert_params(cfg) * (
                                cfg["n_shared_experts"]
                                + cfg["num_experts_per_tok"]
                                * held_share(cfg)))
    return flops + 2.0 * e * int(cfg["vocab_size"])


def fixed_weight_bytes(cfg) -> float:
    """The weights every step reads whatever it routes: attention,
    indexer, dense MLP, routers, shared experts, the head."""
    e = int(cfg["hidden_size"])
    total = e * int(cfg["vocab_size"])
    for i, kind in enumerate(kinds(cfg)):
        total += attention_params(cfg, kind)
        if i < int(cfg["first_k_dense_replace"]):
            total += 3 * e * int(cfg["intermediate_size"])
        else:
            total += e * cfg["deployment"]["experts_routed"] \
                + expert_params(cfg) * int(cfg["n_shared_experts"])
    return float(total * BYTES)


def n_moe_layers(cfg) -> int:
    return int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])


def step_bytes(cfg, sequences: float, queries: float, context: float,
               experts_drawn: float) -> float:
    """The required bytes of ONE execution: `sequences` rows of the batch,
    `queries` query tokens in all, each seeing ~`context` positions,
    `experts_drawn` experts a layer that drew a row."""
    total = fixed_weight_bytes(cfg) \
        + n_moe_layers(cfg) * experts_drawn * expert_params(cfg) * BYTES
    per_seq = queries / max(sequences, 1e-9)
    for kind in kinds(cfg):
        g = geometry(cfg, kind)
        row = g["page"] * BYTES
        if kind == FULL:
            total += sequences * context * cfg["index_head_dim"] * BYTES
            total += sequences * min(
                per_seq * keys_read(cfg, kind, context), context) * row
            total += queries * (row + cfg["index_head_dim"] * BYTES)
        else:
            total += sequences * min(
                keys_read(cfg, kind, context) + per_seq - 1, context) * row
            total += queries * row
    return total


def index_required(cfg, sequences: float, queries: float, context: float
                   ) -> Dict[str, float]:
    """The `dsa_index` kernel's call: every visible key once a sequence,
    the queries' heads in, a float32 score a (query, key) out."""
    n, d = cfg["index_n_heads"], cfg["index_head_dim"]
    return {"flops": 2.0 * queries * n * d * context,
            "bytes": sequences * context * d * BYTES
            + queries * n * (d * BYTES + 4) + queries * context * 4}


def sparse_attn_required(cfg, queries: float, keys: float
                         ) -> Dict[str, float]:
    """Gather and attention together: a chosen row read ONCE a query (what
    implements the gather may copy it again: not counted), the absorbed
    query in, the latent output out."""
    g = geometry(cfg, FULL)
    return {"flops": 2.0 * queries * g["heads"] * keys
            * (2 * g["kv_rank"] + g["r"]),
            "bytes": queries * keys * g["page"] * BYTES
            + queries * g["heads"] * (g["kv_rank"] + g["r"]
                                      + g["kv_rank"]) * BYTES}


def window_latent_required(cfg, sequences: float, queries: float,
                           context: float) -> Dict[str, float]:
    """A sliding layer's latent kernel: the rows a sequence's queries can
    see once a sequence, each query against its window."""
    g = geometry(cfg, "sliding_attention")
    keys = keys_read(cfg, "sliding_attention", context)
    per_seq = queries / max(sequences, 1e-9)
    return {"flops": 2.0 * queries * g["heads"] * keys
            * (2 * g["kv_rank"] + g["r"]),
            "bytes": sequences * min(keys + per_seq - 1, context)
            * g["page"] * BYTES
            + queries * g["heads"] * (2 * g["kv_rank"] + g["r"]) * BYTES}


def moe_gmm_required(cfg, assignments: float, experts_drawn: float
                     ) -> Dict[str, float]:
    """One expert layer's grouped products: an assignment's row through an
    expert's three products, a drawn expert's weights once."""
    e, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    return {"flops": 2.0 * assignments * 3 * e * f,
            "bytes": experts_drawn * expert_params(cfg) * BYTES
            + assignments * (2 * e + 3 * f) * BYTES}
