"""The operations and bytes that SDAR's served executions REQUIRE, from the
configuration (the published `config.json` keys and the family's block
constants), for the cell's utilisations and its two kernels' roofline
shares.

Conventions, beside `peaks.py`'s:

- a product of [rows, in] x [in, out] is 2 * rows * in * out FLOPs; norms,
  the rotary, the softmaxes and the selection rule are not counted;
- a ROW-PASS is one position of a block through one execution. Under the
  static schedule a block of `block_length` masks takes `block_length /
  per-pass` denoise passes and one commit pass: with `block_length` 4 and
  `denoising_steps` 4, FIVE row-passes a committed token. Every one of them
  is counted as required work of the SCHEDULE the cell serves (upstream's
  `generate.py` runs exactly these passes); that the fifth could ride in the
  next block's first is an optimisation of the schedule (PERF.md section 7),
  not of the count;
- a row-pass reads the head (its logits choose what to commit; the commit
  pass's are not read and still computed: one program serves rows at every
  pass), a prompt token does not: a prefill makes keys and values and reads
  no logits (no next-token shift), so neither the head NOR THE LAST LAYER'S
  EXPERT LAYER is required of it (nothing reads what that layer adds; the
  compiler drops both as dead code, my compile rehearsal, PR 62);
- attention of one query over c visible tokens is QK^T and PV over every
  query head, 4 * heads * head_dim * c FLOPs a layer;
- an execution's required bytes: every layer's attention, norm and router
  weights once; the experts that DREW a row once (every one of 128 in a
  block step of 128 rows x 8 choices, by the step's own counters); the head
  once where logits are made; each live row's visible keys and values of
  every layer read once and the new ones written once, in the cache's
  dtype. Activations are not counted.
"""

from __future__ import annotations

from typing import Any, Dict


def _dims(cfg: Dict[str, Any]):
    """(hidden, query width, kv width, expert width)."""
    hd = int(cfg["head_dim"])
    return (int(cfg["hidden_size"]), int(cfg["num_attention_heads"]) * hd,
            int(cfg["num_key_value_heads"]) * hd,
            int(cfg["moe_intermediate_size"]))


def attention_params(cfg: Dict[str, Any]) -> int:
    """q_proj and o_proj, k_proj and v_proj."""
    e, qd, kvd, _ = _dims(cfg)
    return 2 * e * qd + 2 * e * kvd


def expert_params(cfg: Dict[str, Any]) -> int:
    """One expert: gate, up and down."""
    e, _, _, f = _dims(cfg)
    return 3 * e * f


def router_params(cfg: Dict[str, Any]) -> int:
    return int(cfg["hidden_size"]) * int(cfg["num_experts"])


def layer_params(cfg: Dict[str, Any]) -> int:
    """Every parameter of one layer: the products, the router, the two
    norms and the q/k norms."""
    return attention_params(cfg) + router_params(cfg) \
        + int(cfg["num_experts"]) * expert_params(cfg) \
        + 2 * int(cfg["hidden_size"]) + 2 * int(cfg["head_dim"])


def model_params(cfg: Dict[str, Any]) -> int:
    """Layers, embedding, head, final norm."""
    e, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    return int(cfg["num_hidden_layers"]) * layer_params(cfg) + 2 * v * e + e


def kv_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    return int(cfg["num_hidden_layers"]) * 2 * _dims(cfg)[2] * itemsize


def passes_per_block(cfg: Dict[str, Any], masks: int = None) -> int:
    """Row-passes a position of a block of `masks` masked positions takes
    under the static schedule: its denoise passes and the commit pass."""
    length, steps = int(cfg["block_length"]), int(cfg["denoising_steps"])
    masks = length if masks is None else masks
    base, rest = divmod(length, steps)
    left, t = masks, 0
    while left > 0:
        left -= min(base + (t < rest), left)
        t += 1
    return t + 1


def flops_per_row_pass(cfg: Dict[str, Any], context: float,
                       head: bool = True) -> float:
    """Model FLOPs of one position through one execution, seeing `context`
    tokens: a row-pass of a block step (`head`: every layer and the head),
    or a prompt token of a prefill chunk (neither the head nor the last
    layer's expert layer: module docstring)."""
    e, qd, _, _ = _dims(cfg)
    k = int(cfg["num_experts_per_tok"])
    layers = int(cfg["num_hidden_layers"])
    return layers * (2.0 * attention_params(cfg) + 4.0 * qd * context) \
        + (layers - (not head)) * 2.0 * (router_params(cfg)
                                         + k * expert_params(cfg)) \
        + (2.0 * int(cfg["vocab_size"]) * e if head else 0.0)


def execution_bytes(cfg: Dict[str, Any], new_tokens: float,
                    visible_tokens: float, experts_drawn: float,
                    head: bool, itemsize: int = 2) -> float:
    """Required bytes of ONE execution over `new_tokens` live positions
    whose rows see `visible_tokens` cached tokens in all (each row's
    context once a layer), `experts_drawn` experts a layer drawing a row.
    `head`: a block execution, every layer and the head; else a prefill,
    without the head and the last layer's expert layer."""
    e = int(cfg["hidden_size"])
    layers = int(cfg["num_hidden_layers"])
    weights = layers * attention_params(cfg) + (layers - (not head)) * (
        router_params(cfg) + experts_drawn * expert_params(cfg)) \
        + new_tokens * e + (int(cfg["vocab_size"]) * e if head else 0)
    return itemsize * weights \
        + (visible_tokens + new_tokens) * kv_bytes_per_token(cfg, itemsize)


def paged_required(cfg: Dict[str, Any], queries: float,
                   visible_tokens: float, pair_tokens: float,
                   itemsize: int = 2) -> Dict[str, float]:
    """ONE paged-attention call (one layer): `queries` query tokens over
    `visible_tokens` cached tokens read in all (a row's pages once for all
    its queries: the 4 of a block step, the up to 256 of a chunk), and
    `pair_tokens` (query, key) pairs in all. Bytes: those keys and values
    once, q read and o written."""
    _, qd, kvd, _ = _dims(cfg)
    return {"flops": 4.0 * qd * pair_tokens,
            "bytes": float(itemsize) * (2 * kvd * visible_tokens
                                        + 2 * qd * queries)}


def moe_gmm_required(cfg: Dict[str, Any], assignments: float,
                     experts_drawn: float, itemsize: int = 2
                     ) -> Dict[str, float]:
    """The two grouped products of one layer of one execution:
    `assignments` rows (token, choice), `experts_drawn` experts read once
    each; rows in and out (hidden in, 2 x width between, hidden out)."""
    e, _, _, f = _dims(cfg)
    return {"flops": 2.0 * assignments * expert_params(cfg),
            "bytes": float(itemsize) * (experts_drawn * expert_params(cfg)
                                        + assignments * (2 * e + 3 * f))}
