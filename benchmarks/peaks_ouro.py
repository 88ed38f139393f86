"""The operations and bytes that Ouro's served step REQUIRES, from its
configuration (the published `config.json` keys), for the cell's
utilisations and the paged kernel's roofline share at one query row a KV
head.

Conventions, beside `peaks.py`'s:

- a product of [rows, in] x [in, out] is 2 * rows * in * out FLOPs; norms,
  the rotary, the softmax and the exit gate are not counted;
- the layers are applied `total_ut_steps` times a token and counted that
  often: the loop is the model, not a choice of the implementation. The
  head runs once a token, after the last pass (a prefill chunk's only at
  its last position: not counted there);
- attention of one query token over c cached tokens is QK^T and PV over
  every head, 4 * heads * head_dim * c FLOPs a layer a pass;
- a step's required bytes: the layers' weights ONCE A PASS (each pass
  streams them again: they are 4.9 GB and no cache holds them between
  passes), the head once, every live token's keys and values of every
  layer of every pass read once and the new tokens' written once, in the
  cache's dtype. Activations are not counted.
"""

from __future__ import annotations

from typing import Any, Dict


def _widths(cfg: Dict[str, Any]):
    return (int(cfg["hidden_size"]), int(cfg["intermediate_size"]),
            int(cfg["num_attention_heads"]) * int(cfg["head_dim"]),
            int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]))


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters of one layer that sit in a product: q and o, k and v,
    the three of SwiGLU."""
    e, f, qd, kvd = _widths(cfg)
    return 2 * e * qd + 2 * e * kvd + 3 * e * f


def layer_params(cfg: Dict[str, Any]) -> int:
    """Every parameter of one layer: the products and the four norms."""
    return layer_matmul_params(cfg) + 4 * int(cfg["hidden_size"])


def model_params(cfg: Dict[str, Any]) -> int:
    """Layers, embedding, head, final norm, exit gate and its bias."""
    e, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    return int(cfg["num_hidden_layers"]) * layer_params(cfg) \
        + 2 * v * e + e + e + 1


def layer_applications(cfg: Dict[str, Any]) -> int:
    return int(cfg["total_ut_steps"]) * int(cfg["num_hidden_layers"])


def kv_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of every layer of every pass."""
    return layer_applications(cfg) * 2 * _widths(cfg)[3] * itemsize


def flops_per_token_outside_attention(cfg: Dict[str, Any],
                                      head: bool = True) -> float:
    e, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    return 2.0 * (layer_applications(cfg) * layer_matmul_params(cfg)
                  + (v * e if head else 0))


def attention_flops_per_token(cfg: Dict[str, Any], context: float) -> float:
    return 4.0 * _widths(cfg)[2] * context * layer_applications(cfg)


def serve_flops_per_token(cfg: Dict[str, Any], context: float,
                          decode: bool) -> float:
    """A decoded token at `context` cached tokens, or a prompt token whose
    causal context averages `context` (no head)."""
    return flops_per_token_outside_attention(cfg, head=decode) \
        + attention_flops_per_token(cfg, context)


def step_weight_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> float:
    """What one execution of a step program must read of the weights:
    the layers once a pass, the head once."""
    e, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    return float(itemsize) * (
        int(cfg["total_ut_steps"]) * int(cfg["num_hidden_layers"])
        * layer_params(cfg) + v * e)


def step_bytes(cfg: Dict[str, Any], new_tokens: float, live_tokens: float,
               itemsize: int = 2) -> float:
    """One execution: the weights, `live_tokens` cached tokens read and
    `new_tokens` written."""
    return step_weight_bytes(cfg, itemsize) \
        + (live_tokens + new_tokens) * kv_bytes_per_token(cfg, itemsize)


def paged_decode_required(cfg: Dict[str, Any], rows: float,
                          live_tokens: float, itemsize: int = 2
                          ) -> Dict[str, float]:
    """ONE paged-attention call of a decode step (one layer, one pass):
    `rows` query tokens over `live_tokens` cached tokens in all. Bytes:
    their keys and values once, q read and o written."""
    qd, kvd = _widths(cfg)[2:]
    return {"flops": 4.0 * qd * live_tokens,
            "bytes": float(itemsize) * (2 * kvd * live_tokens
                                        + 2 * qd * rows)}
