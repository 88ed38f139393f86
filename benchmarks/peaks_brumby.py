"""What the Brumby serve cell's new kernels and its whole step REQUIRE, from
shapes (conventions as in `benchmarks/peaks.py`: operands read once, results
written once, at the dtype they are passed in; work the implementation
chose to repeat, and rows it chose to pad, are not counted).

The state of one KV head is D = d(d+1)/2 rows of d (8,256 x 128 at d =
128), the key sum D values, float32: the MINIMUM, whatever layout a kernel
keeps (the program stores 8,320 rows; the 64 extra show as a lower share).

`retention_step` (one call = one layer's decode step over every slot): the
state and the key sum once in and once out; q [slots, heads, d], k, v
[slots, kv_heads, d], the gate [slots, kv_heads] f32 in; y [slots, heads,
d] f32 out. FLOPs a state element: 1 for the decay, 2 for the outer
product's multiply and add, 2 for the readout's multiply and add of each of
the group's `rep` query heads: 3 + 2 rep (13 at rep = 5).

`retention_chunk_fwd` (one call = one layer over `batch` rows of `seq`
positions): a row's state and key sum once in and once out; q [seq, heads
d], k, v [seq, kv_heads d] bf16 in; the gates [seq, kv_heads] f32 in; y
[seq, heads d] f32 out. FLOPs of the chunked algorithm over a chunk of C:
the update phi(K)^T V, 2 C D d a KV head; the start state's readout phi(Q)
S_0, 2 C D d a query head; inside the chunk Q K^T and A V, the causal half
of 2 C^2 d each, a query head.

A token's model FLOPs (`serve_flops_per_token`): 2 a parameter that sits in
a matmul (q, k, v, o and the gate's projection; the three MLP products) a
layer, the retention's 3 + 2 rep a state element a layer; a DECODED token
also pays the head (2 V e), a prefilled one does not (one position a chunk
is read).
"""

from __future__ import annotations

from typing import Any, Dict


def _dims(cfg: Dict[str, Any]):
    """(query heads, KV heads, d, D)."""
    d = int(cfg["head_dim"])
    return (int(cfg["num_attention_heads"]),
            int(cfg["num_key_value_heads"]), d, d * (d + 1) // 2)


def state_elements(cfg: Dict[str, Any]) -> int:
    """A slot's state elements a layer (the key sum not counted)."""
    _, hk, d, big = _dims(cfg)
    return hk * big * d


def state_bytes(cfg: Dict[str, Any]) -> int:
    """A slot's float32 state and key sum a layer."""
    _, hk, _, big = _dims(cfg)
    return 4 * (state_elements(cfg) + hk * big)


def state_flops_per_element(cfg: Dict[str, Any]) -> int:
    hq, hk, _, _ = _dims(cfg)
    return 3 + 2 * (hq // hk)


def retention_step_required(cfg: Dict[str, Any], slots: int
                            ) -> Dict[str, float]:
    hq, hk, d, _ = _dims(cfg)
    rows = 4.0 * slots * (2 * hq * d + 2 * hk * d + hk)    # q, y, k, v, gate
    return {"flops": float(slots * state_flops_per_element(cfg)
                           * state_elements(cfg)),
            "bytes": 2.0 * slots * state_bytes(cfg) + rows}


def retention_chunk_fwd_required(cfg: Dict[str, Any], batch: int, seq: int
                                 ) -> Dict[str, float]:
    hq, hk, d, big = _dims(cfg)
    flops = 2.0 * seq * big * d * (hk + hq) + hq * 2.0 * seq * seq * d
    nbytes = (2.0 * state_bytes(cfg)                       # state in and out
              + 2.0 * seq * d * (hq + 2 * hk)              # q, k, v
              + 4.0 * seq * hk                             # gates
              + 4.0 * seq * hq * d)                        # y
    return {"flops": batch * flops, "bytes": batch * nbytes}


def layer_params(cfg: Dict[str, Any]) -> int:
    """Every parameter of one layer."""
    e, (hq, hk, d, _) = int(cfg["hidden_size"]), _dims(cfg)
    return layer_matmul_params(cfg) + hk + 2 * d + 2 * e


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    e, (hq, hk, d, _) = int(cfg["hidden_size"]), _dims(cfg)
    return (2 * e * hq * d + 2 * e * hk * d + e * hk
            + 3 * e * int(cfg["intermediate_size"]))


def serve_flops_per_token(cfg: Dict[str, Any], decoded: bool) -> float:
    per_layer = 2.0 * layer_matmul_params(cfg) \
        + state_flops_per_element(cfg) * state_elements(cfg)
    head = 2.0 * int(cfg["vocab_size"]) * int(cfg["hidden_size"])
    return int(cfg["num_hidden_layers"]) * per_layer \
        + (head if decoded else 0.0)
