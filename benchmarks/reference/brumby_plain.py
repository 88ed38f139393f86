"""Brumby decoder forward (`model_type` `brumby`: the widths of a 14B GQA
family whose attention was replaced, layer for layer, by power retention;
Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239), in plain `jax.numpy` float32: the retention in its
QUADRATIC form, a [t, t] weight matrix a head with the decay mask, over the
whole sequence. No kernels, no cache, no state, no feature map, no
batching tricks: independent of the state form the system runs.

Per block, `cfg` the published `config.json` as a dict plus the `assumed`
keys below (d = `head_dim`, r = query heads a KV head):

    h = RMSNorm(x; rms_norm_eps)
    q = q_proj(h) [heads, d], k = k_proj(h), v = v_proj(h) [kv_heads, d]
    q, k <- RMSNorm over d with weight q_norm / k_norm, then RoPE
    gamma = logsigmoid(g_proj(h) + bias)              [kv_heads], float32
    c_t = sum_{u<=t} gamma_u
    a_{t,u} = exp(c_t - c_u) (q_t[i] . k_u[i // r])^2 / d        u <= t
    y_t[i] = sum_u a_{t,u} v_u[i // r] / (sum_u a_{t,u} + eps_r)
    x = x + o_proj(concat_i y[i])
    x = x + down_proj(silu(gate_proj(n)) * up_proj(n)),  n = RMSNorm(x)
    logits = lm_head(RMSNorm(x))

Notes on conventions:
- weights under the published names and layouts: a product's weight is
  [out, in] (y = x W^T); no bias but the gate's (`attention_bias` false
  speaks of q, k, v, o);
- RoPE rotates the two HALVES of a head over all d dims, frequencies
  theta^(-i/(d/2)), theta = `rope_theta` (1e6), no scaling;
- ASSUMED, not in the published config (each also in the configuration
  file's `assumed` with its origin): `retention_degree` 2 and the
  normalised, gated form above (the mechanism as published: the reference
  kernels take Q, K, V, log_G, deg and carry `state` and `sum_of_keys`);
  the gate as logsigmoid of a projection to one value a KV head with a
  bias (the state is a function of K, V and the gate alone, so they share
  a head count); `q_norm` / `k_norm` and the full-width rotary (the config
  keeps `rope_theta` and `rms_norm_eps`, and the widths are those of a
  family that has both); `eps_r` 1e-6;
- DEPARTURE in implementation, not in the mathematics: the published
  inference path switches between an attention form (below a switch-over
  length) and a state form; both compute the function above. This file is
  the attention form at every length; the system holds the state form from
  the first token;
- weights come a layer at a time through `layer(i)`, so that only one
  layer is ever held in float32 (1.3 GB at the published widths); a layer
  is one jitted function of (its weights, x), traced once a sequence
  length, and the head runs in row blocks of 37,984 and only at the
  positions asked for.

`probes` [n, d]: for the comparison of a served STATE with this stateless
form, a layer also answers what `n` extra query vectors p placed at the
LAST position would read before the division: sum_u exp(c_T - c_u)
(p . k_u)^2 / d v_u and the same without v, per KV head. The system's
state answers the same question through its feature map.

`top`: {"model.embed_tokens.weight": [V, e], "model.norm.weight": [e],
"lm_head.weight": [V, e]}; `layer(i)`: the published names below
`model.layers.<i>.` (`models/brumby.py published_weights` gives both).
"""

from __future__ import annotations

import functools
import json
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

HEAD_BLOCK_ROWS = 37984


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


def _retention(cfg, w, h, probes):
    b, s, _ = h.shape
    hq, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    r = hq // hk
    eps = cfg["rms_norm_eps"]
    q = _linear(h, w["self_attn.q_proj.weight"]).reshape(b, s, hq, d)
    k = _linear(h, w["self_attn.k_proj.weight"]).reshape(b, s, hk, d)
    v = _linear(h, w["self_attn.v_proj.weight"]).reshape(b, s, hk, d)
    q = _rope(_rms_norm(q, w["self_attn.q_norm.weight"], eps),
              cfg["rope_theta"])
    k = _rope(_rms_norm(k, w["self_attn.k_norm.weight"], eps),
              cfg["rope_theta"])
    gamma = jax.nn.log_sigmoid(_linear(h, w["self_attn.g_proj.weight"])
                               + _f32(w["self_attn.g_proj.bias"]))
    c = jnp.cumsum(gamma, axis=1)                              # [b, s, hk]
    causal = jnp.tril(jnp.ones((s, s), bool))
    # exp() only of what is <= 0.
    decay = jnp.exp(jnp.where(
        causal[None, :, :, None], c[:, :, None, :] - c[:, None, :, :],
        -jnp.inf))                                             # [b, t, u, hk]
    qg = q.reshape(b, s, hk, r, d)
    a = jnp.square(jnp.einsum("btjrd,bujd->btujr", qg, k)) / d \
        * decay[..., None]
    y = jnp.einsum("btujr,bujd->btjrd", a, v) \
        / (jnp.sum(a, axis=2)[..., None] + cfg["eps_r"])
    out = _linear(y.reshape(b, s, hq * d), w["self_attn.o_proj.weight"])
    if probes is None:
        return out, None
    weight = jnp.square(jnp.einsum("nd,bujd->bujn", _f32(probes), k)) / d \
        * jnp.exp(c[:, -1:, :] - c)[..., None]                 # [b, u, hk, n]
    return out, (jnp.einsum("bujn,bujd->bjnd", weight, v),
                 jnp.sum(weight, axis=1))


def _layer(cfg, w, x, probes):
    """One block: (x after it, the probes' readings of its last state)."""
    eps = cfg["rms_norm_eps"]
    out, read = _retention(
        cfg, w, _rms_norm(x, w["input_layernorm.weight"], eps), probes)
    x = x + out
    n = _rms_norm(x, w["post_attention_layernorm.weight"], eps)
    x = x + _linear(jax.nn.silu(_linear(n, w["mlp.gate_proj.weight"]))
                    * _linear(n, w["mlp.up_proj.weight"]),
                    w["mlp.down_proj.weight"])
    return x, read


@functools.lru_cache(maxsize=8)
def _jitted(cfg_json: str):
    """(the layer, the head over one row block) of a configuration, each
    one jitted function: every layer and every block share its trace."""
    cfg = json.loads(cfg_json)

    def head(x, norm, rows):
        return _linear(_rms_norm(x, norm, cfg["rms_norm_eps"]), rows)

    return jax.jit(functools.partial(_layer, cfg)), jax.jit(head)


def forward(top: Dict[str, Any], layer: Callable[[int], Dict[str, Any]],
            input_ids, cfg: Dict[str, Any],
            positions: Optional[Sequence[int]] = None, probes=None):
    """Logits in float32: [b, s, V], or [b, len(positions), V] at
    `positions` only (the head is 151,936 rows wide). With `probes` [n, d]:
    (logits, for every layer the pair ([b, kv_heads, n, d], [b, kv_heads,
    n]) the probes read of its state after the last token)."""
    block, head = _jitted(json.dumps(cfg, sort_keys=True, default=str))
    reads = []
    with jax.default_matmul_precision("highest"):
        x = _f32(top["model.embed_tokens.weight"][input_ids])
        for i in range(cfg["num_hidden_layers"]):
            # Waited for, so that one layer's tensors are alive at a time:
            # dispatch is asynchronous, and `layer(i)` may MAKE its tensors
            # (a program that keeps products [in, out] transposes them).
            x, read = jax.block_until_ready(block(layer(i), x, probes))
            reads.append(read)
        if positions is not None:
            x = x[:, jnp.asarray(positions, jnp.int32)]
        rows = top["lm_head.weight"]
        logits = jnp.concatenate(
            [jax.block_until_ready(head(x, top["model.norm.weight"],
                                        rows[r:r + HEAD_BLOCK_ROWS]))
             for r in range(0, rows.shape[0], HEAD_BLOCK_ROWS)], axis=-1)
        return logits if probes is None else (logits, reads)


def chosen_token_gaps(rows, generated):
    """For each generated token, how far its reference logit lies under
    the reference's maximum at that position (0 = the reference's own
    greedy choice). `rows` [len(generated), V]: the reference's logits at
    the positions that chose them."""
    generated = jnp.asarray(generated, jnp.int32)
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, generated[:, None], axis=-1)[:, 0]
