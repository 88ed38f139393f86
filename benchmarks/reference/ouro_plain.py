"""Ouro decoder forward (`model_type` `ouro`: one stack of layers applied
`total_ut_steps` times with the same weights; ByteDance Seed, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741), in plain
`jax.numpy` float32: four plain Python passes over 48 plain layers, full
causal attention over the whole sequence. No kernel, no cache, no scan, no
batching tricks: independent of the step the system runs.

Per layer, `cfg` the published `config.json` as a dict (d = `head_dim`,
N_i RMSNorm with a learned weight, eps `rms_norm_eps`):

    a = o_proj(Attn(q, k, v)),  q, k, v = {q,k,v}_proj(N_1(x)) [heads, d]
        rotary on q and k, causal softmax(q k^T / sqrt(d)) v, no bias
    x = x + N_2(a)
    m = down_proj(silu(gate_proj(N_3(x))) * up_proj(N_3(x)))
    x = x + N_4(m)

and over the model, h_0 = embed_tokens[ids], U = `total_ut_steps`:

    for u in 1..U:  h_u = norm(Layers(h_{u-1}))    the model's norm after
                                                   EVERY pass
                    lambda_u = sigmoid(early_exit_gate(h_u))
    logits = lm_head(h_U)
    p_exit(u) = lambda_u prod_{j<u} (1 - lambda_j), the rest on u = U

Notes on conventions:
- weights under the published names and layouts: a product's weight is
  [out, in] (y = x W^T); N_1..N_4 are `input_layernorm`,
  `input_layernorm_2`, `post_attention_layernorm`,
  `post_attention_layernorm_2`; the gate is a Linear(hidden, 1) with a
  bias;
- RoPE rotates the two HALVES of a head over all d dims, frequencies
  theta^(-i/(d/2)), theta = `rope_theta` (1e6), no scaling, the same
  positions in every pass;
- ASSUMED, not keys of the published config (each also in the
  configuration file's `assumed`): the sandwich norms N_2 and N_4, the
  norm between passes, and the gate, which are the family's published
  form (the paper's architecture section, `modeling_ouro.py`);
- weights come a layer at a time through `layer(i)`, in EVERY pass, so
  that only one layer is ever held in float32 (0.2 GB at the published
  widths); a layer is one jitted function of (its weights, x), traced once
  a sequence length, and the head runs only at the positions asked for.

`keep` lists (pass, layer) pairs, both 0-based: for each the forward also
returns that layer's keys (after the rotary) and values in that pass,
[b, s, heads, d], which is what a cache would hold there.

`top`: {"model.embed_tokens.weight": [V, e], "model.norm.weight": [e],
"model.early_exit_gate.weight": [1, e], "model.early_exit_gate.bias": [1],
"lm_head.weight": [V, e]}; `layer(i)`: the published names below
`model.layers.<i>.` (`models/ouro.py published_weights` gives both).
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

HEAD_BLOCK_ROWS = 16384


def _f32(t):
    return jnp.asarray(t, jnp.float32)


def _linear(x, w):
    return x @ _f32(w).T


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * _f32(weight)


def _rope(x, theta):
    """x [b, s, heads, d] at positions 0 .. s-1: rotate-half."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs    # [s, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, w, x):
    """One layer: (x after it, its keys, its values [b, s, heads, d])."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, s, _ = x.shape
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    h = _rms_norm(x, w["input_layernorm.weight"], eps)
    q = _rope(_linear(h, w["self_attn.q_proj.weight"]).reshape(
        b, s, heads, d), theta)
    k = _rope(_linear(h, w["self_attn.k_proj.weight"]).reshape(
        b, s, heads, d), theta)
    v = _linear(h, w["self_attn.v_proj.weight"]).reshape(b, s, heads, d)
    scores = jnp.einsum("bthd,buhd->bhtu", q, k) / math.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    attn = jnp.einsum("bhtu,buhd->bthd", jax.nn.softmax(scores, axis=-1), v)
    a = _linear(attn.reshape(b, s, heads * d), w["self_attn.o_proj.weight"])
    x = x + _rms_norm(a, w["input_layernorm_2.weight"], eps)
    n = _rms_norm(x, w["post_attention_layernorm.weight"], eps)
    m = _linear(jax.nn.silu(_linear(n, w["mlp.gate_proj.weight"]))
                * _linear(n, w["mlp.up_proj.weight"]),
                w["mlp.down_proj.weight"])
    return x + _rms_norm(m, w["post_attention_layernorm_2.weight"], eps), k, v


@functools.lru_cache(maxsize=8)
def _jitted(cfg_json: str):
    """(the layer, the norm and gate after a pass, the head over one row
    block) of a configuration, each one jitted function: every layer of
    every pass shares one trace."""
    cfg = json.loads(cfg_json)

    def after_pass(x, norm, gate_w, gate_b):
        h = _rms_norm(x, norm, cfg["rms_norm_eps"])
        return h, jax.nn.sigmoid(_linear(h, gate_w)[..., 0] + _f32(gate_b)[0])

    return (jax.jit(functools.partial(_layer, cfg)), jax.jit(after_pass),
            jax.jit(_linear))


def exit_distribution(gates):
    """p_exit [..., U] from the gates lambda [..., U]."""
    out, stay = [], jnp.ones_like(gates[..., 0])
    for u in range(gates.shape[-1] - 1):
        out.append(gates[..., u] * stay)
        stay = stay * (1.0 - gates[..., u])
    return jnp.stack(out + [stay], axis=-1)


def forward(top: Dict[str, Any], layer: Callable[[int], Dict[str, Any]],
            input_ids, cfg: Dict[str, Any],
            positions: Optional[Sequence[int]] = None,
            keep: Sequence[Tuple[int, int]] = ()):
    """(logits, p_exit) in float32: logits [b, s, V], or [b,
    len(positions), V] at `positions` only; p_exit [b, s, U] (at
    `positions` likewise). With `keep`: (logits, p_exit, {(pass, layer):
    (keys, values)})."""
    block, after_pass, head = _jitted(
        json.dumps(cfg, sort_keys=True, default=str))
    kept, gates = {}, []
    with jax.default_matmul_precision("highest"):
        x = _f32(top["model.embed_tokens.weight"][input_ids])
        for u in range(cfg["total_ut_steps"]):
            for i in range(cfg["num_hidden_layers"]):
                # Waited for, so that one layer's tensors are alive at a
                # time: dispatch is asynchronous, and `layer(i)` MAKES its
                # tensors (the program keeps products [in, out], fused).
                x, k, v = jax.block_until_ready(block(layer(i), x))
                if (u, i) in keep:
                    kept[(u, i)] = (k, v)
            x, gate = after_pass(x, top["model.norm.weight"],
                                 top["model.early_exit_gate.weight"],
                                 top["model.early_exit_gate.bias"])
            gates.append(gate)
        p_exit = exit_distribution(jnp.stack(gates, axis=-1))
        if positions is not None:
            at = jnp.asarray(positions, jnp.int32)
            x, p_exit = x[:, at], p_exit[:, at]
        rows = top["lm_head.weight"]
        logits = jnp.concatenate(
            [jax.block_until_ready(head(x, rows[r:r + HEAD_BLOCK_ROWS]))
             for r in range(0, rows.shape[0], HEAD_BLOCK_ROWS)], axis=-1)
    return (logits, p_exit, kept) if keep else (logits, p_exit)


def chosen_token_gaps(rows, generated):
    """For each generated token, how far its reference logit lies under
    the reference's maximum at that position (0 = the reference's own
    greedy choice). `rows` [len(generated), V]: the reference's logits at
    the positions that chose them."""
    generated = jnp.asarray(generated, jnp.int32)
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, generated[:, None], axis=-1)[:, 0]
