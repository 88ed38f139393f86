"""What the Falcon-H1 serve cell's new kernels and its whole step REQUIRE,
from shapes (conventions as in `benchmarks/peaks.py`: operands read once,
results written once, at the dtype they are passed in; work the
implementation chose to repeat is not counted).

`ssd_step` (one call = one layer's decode step over every slot): the state
[slots, heads, N, P] f32 once in and once out; dt*x and the decay [slots,
heads, P] f32, B and C [slots, groups, N] f32 in; y [slots, heads, P] f32
out. FLOPs: 5 a state element (the decay's multiply, the outer product's
multiply and add, the readout's multiply and add).

`ssd_chunk_fwd` (one call = one layer over `batch` rows of `seq` positions,
chunk 128): a row's state [heads, N, P] f32 once in and once out; x [seq,
heads*P], B, C [seq, groups*N] bf16 in; the running log-decay and dt [heads,
seq] f32 in; y [seq, heads*P] f32 out. FLOPs of the chunked algorithm a
chunk of L: C B^T once a GROUP and M X once a head, both the causal half
(L^2 N and L^2 P), C H_0 and B^T X a head (2 L N P each).

A token's model FLOPs (`serve_flops_per_token`): 2 a parameter that sits in
a matmul (attention q, k, v, o; the mixer's in- and out-projection; the
three MLP products) a layer, the recurrence's 5 a state element a layer,
attention's QK^T and PV over the context (4 x heads x head_dim x context a
layer); a DECODED token also pays the head (2 V d), a prefilled one does
not (one position a chunk is read).
"""

from __future__ import annotations

from typing import Any, Dict

CHUNK = 128


def _dims(cfg: Dict[str, Any]):
    return (int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]),
            int(cfg["mamba_d_state"]), int(cfg["mamba_n_groups"]))


def ssd_step_required(cfg: Dict[str, Any], slots: int) -> Dict[str, float]:
    h, p, n, g = _dims(cfg)
    state = slots * h * n * p
    return {"flops": 5.0 * state,
            "bytes": 4.0 * (2 * state + 3 * slots * h * p
                            + 2 * slots * g * n)}


def ssd_chunk_fwd_required(cfg: Dict[str, Any], batch: int, seq: int
                           ) -> Dict[str, float]:
    h, p, n, g = _dims(cfg)
    chunks = seq // CHUNK
    per_chunk = g * CHUNK * CHUNK * n + h * (CHUNK * CHUNK * p
                                             + 4 * CHUNK * n * p)
    nbytes = (4.0 * 2 * h * n * p                    # state in and out
              + 2.0 * seq * (h * p + 2 * g * n)      # x, B, C
              + 4.0 * 2 * h * seq                    # log-decay, dt
              + 4.0 * seq * h * p)                   # y
    return {"flops": float(batch * chunks * per_chunk),
            "bytes": float(batch) * nbytes}


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    d = int(cfg["hidden_size"])
    hd = int(cfg["head_dim"])
    q, kv = int(cfg["num_attention_heads"]) * hd, \
        int(cfg["num_key_value_heads"]) * hd
    h, p, n, g = _dims(cfg)
    d_ssm = int(cfg["mamba_d_ssm"])
    in_proj = d_ssm + (d_ssm + 2 * g * n) + h
    return (d * (q + 2 * kv) + q * d + d * in_proj + d_ssm * d
            + 3 * d * int(cfg["intermediate_size"]))


def serve_flops_per_token(cfg: Dict[str, Any], context: float,
                          decoded: bool) -> float:
    h, p, n, _ = _dims(cfg)
    per_layer = (2.0 * layer_matmul_params(cfg) + 5.0 * h * n * p
                 + 4.0 * int(cfg["num_attention_heads"])
                 * int(cfg["head_dim"]) * context)
    head = 2.0 * int(cfg["vocab_size"]) * int(cfg["hidden_size"])
    return int(cfg["num_hidden_layers"]) * per_layer \
        + (head if decoded else 0.0)
