"""What a Qwen3-Next training step REQUIRES, from shapes and routed counts
alone (never from the implementation), by the conventions of
`benchmarks/peaks.py`: 2 FLOPs forward and 4 backward a matmul parameter a
token, causal attention over s/2 keys on average, no recomputation (the
rematerialised forward is work the implementation chose), embedding
gathers, norms, gates and the convolution's four taps not counted.

`cfg` is the configuration file's keys (`num_experts` = experts HELD
here).
"""

from __future__ import annotations

from typing import Any, Dict

CHUNK = 64      # the family's chunk: part of the chunked algorithm


def layer_kinds(cfg: Dict[str, Any]) -> Dict[str, int]:
    layers = int(cfg["num_hidden_layers"])
    attention = layers // int(cfg["full_attention_interval"])
    return {"attention": attention, "linear": layers - attention}


def dense_matmul_params(cfg: Dict[str, Any]) -> int:
    """Matmul parameters every token passes: the mixers, the router, the
    shared expert and its gate, and the head over the vocabulary slice."""
    d = int(cfg["hidden_size"])
    kinds = layer_kinds(cfg)
    key_w = int(cfg["linear_num_key_heads"]) * int(cfg["linear_key_head_dim"])
    hv = int(cfg["linear_num_value_heads"])
    val_w = hv * int(cfg["linear_value_head_dim"])
    linear = d * (2 * key_w + 2 * val_w) + d * 2 * hv + val_w * d
    h, kv, hd = (int(cfg["num_attention_heads"]),
                 int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    attention = d * 2 * h * hd + 2 * d * kv * hd + h * hd * d
    routed = int(cfg["deployment"]["experts_routed"])
    shared = 3 * d * int(cfg["shared_expert_intermediate_size"]) + d
    per_layer = d * routed + shared
    return (kinds["linear"] * linear + kinds["attention"] * attention
            + int(cfg["num_hidden_layers"]) * per_layer
            + d * int(cfg["vocab_size"]))


def expert_params(cfg: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def train_flops_per_token(cfg: Dict[str, Any], seq: int,
                          held_assignments_per_token: float) -> float:
    """6 x (dense matmul parameters + held assignments a token a layer x
    one expert's parameters x layers) + 6 x heads x head_dim x seq a
    softmax layer + 6 x 2 x dk x dv a value head a recurrent layer (the
    state meets two vectors a token: the key it corrects and the query it
    answers)."""
    kinds = layer_kinds(cfg)
    touched = dense_matmul_params(cfg) + (
        held_assignments_per_token * int(cfg["num_hidden_layers"])
        * expert_params(cfg))
    softmax = 6.0 * int(cfg["num_attention_heads"]) * int(cfg["head_dim"]) \
        * seq * kinds["attention"]
    state = int(cfg["linear_key_head_dim"]) * int(cfg["linear_value_head_dim"])
    recurrent = 6.0 * 2 * state * int(cfg["linear_num_value_heads"]) \
        * kinds["linear"]
    return 6.0 * touched + softmax + recurrent


def gdn_required(batch: int, seq: int, key_heads: int, value_heads: int,
                 d: int, chunk: int = CHUNK) -> Dict[str, Dict[str, float]]:
    """Required FLOPs and HBM bytes of ONE call of the chunked gated delta
    rule's kernels (dk = dv = d), the chunked form being the algorithm: the
    step-by-step form has no matrix product to give an MXU.

    Forward, a chunk of C positions of one value head: K K^T and Q K^T
    (the causal halves, 2 C^2 d together), the unit-triangular solve for W
    and U0 by substitution (2 C^2 d), W S, Q S and K^T U (2 C d^2 each),
    P U (the causal half, C^2 d): 5 C^2 d + 6 C d^2.
    Backward: two gradient products a forward product, and the forward's
    state-independent part and W S again (the residual is the state at each
    chunk's start, nothing else): 2 x forward + 4 C^2 d + 2 C d^2.
    Bytes: every operand read once and every result written once at the
    dtype it has: q, k a key head, v, o, do, dv a value head in bf16, dq, dk
    a key head in bf16, g, beta and their gradients a value head in f32.
    The saved states are the implementation's choice and are not counted.
    """
    chunks = batch * value_heads * (seq / chunk)
    fwd = 5.0 * chunk * chunk * d + 6.0 * chunk * d * d
    bwd = 2.0 * fwd + 4.0 * chunk * chunk * d + 2.0 * chunk * d * d
    key = float(batch * seq * key_heads * d * 2)
    value = float(batch * seq * value_heads * d * 2)
    gates = float(batch * seq * value_heads * 4)
    return {
        "gdn_chunk_fwd": {"flops": chunks * fwd,
                          "bytes": 2 * key + 2 * value + 2 * gates},
        "gdn_chunk_bwd": {"flops": chunks * bwd,
                          "bytes": 4 * key + 4 * value + 4 * gates},
    }


def moe_gmm_required(assignments: float, layers: int, held: int, d: int,
                     width: int) -> Dict[str, float]:
    """Required FLOPs and bytes of one STEP's nine grouped products (gate,
    up, down x forward, data gradient, weight gradient) over `assignments`
    (token, choice) pairs routed to held experts, summed over `layers`
    layers. Bytes: the held weights once a product (bf16 read forward and
    for the data gradient, f32 written for the weight gradient), and each
    product's row operands once in bf16."""
    flops = 9 * 2.0 * assignments * d * width
    weights = layers * held * d * width
    weight_bytes = 3 * weights * (2 + 2 + 4)
    rows = assignments * 2.0 * (d + width)      # one operand in, one out
    return {"flops": flops, "bytes": weight_bytes + 9 * rows}
