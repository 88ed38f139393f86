"""Falcon-H1 decoder forward (`modeling_falcon_h1.py` of the published
model, `model_type` `falcon_h1`; the mixer is Mamba-2's, Dao & Gu 2024), in
plain `jax.numpy` float32: full causal attention over the whole sequence,
the state-space recurrence ONE TOKEN AT A TIME under `lax.scan` (not the
chunked algorithm), no cache, no paging, no batching tricks.

Every block runs attention and the Mamba-2 mixer in parallel on the same
normed input and adds both to the residual, then a SwiGLU MLP; every branch
carries one of the family's fixed muP scalars, each a key of `cfg` (the
published `config.json` as a dict):

    x0 = embed[ids] * embedding_multiplier
    h  = RMSNorm(x)
    x  = x + attention_out_multiplier * Attn(attention_in_multiplier * h)
           + ssm_out_multiplier * SSM(ssm_in_multiplier * h)
    h2 = RMSNorm(x)
    x  = x + mlp_multipliers[1] * down(up(h2) * silu(mlp_multipliers[0] * gate(h2)))
    logits = lm_head_multiplier * head(RMSNorm(x))

Notes on conventions:
- weights under the published names and layouts: a product's weight is
  [out, in] (y = x W^T), `mamba.conv1d.weight` is [channels, 1, width];
- RoPE rotates the two HALVES of a head, frequencies theta^(-i/(d/2)),
  theta = `rope_theta` (1e11), no scaling; k carries `key_multiplier`
  before the rotation;
- `mamba.in_proj`'s output is [z d_ssm | x d_ssm | B groups*state | C
  groups*state | dt heads] and is multiplied element-wise by a vector
  holding `ssm_multipliers[0..4]` on those five segments;
- the depthwise causal convolution (width `mamba_d_conv`, with bias, zeros
  before the sequence's start) runs over [x | B | C], then silu;
- dt = softplus(dt + dt_bias), a = -exp(A_log); per head the state H
  [d_head, d_state]: H_t = exp(dt_t a) H_{t-1} + dt_t x_t B_t^T,
  y_t = H_t C_t + D x_t; the heads // groups adjacent heads of a group
  share B and C;
- `mamba_rms_norm` true, `mamba_norm_before_gate` false: y = RMSNorm(y *
  silu(z)) * w with the variance over each of `mamba_n_groups` groups;
- weights come a layer at a time through `layer(i)`, so that only one
  layer is ever held in float32 (1.7 GB at the published widths); a layer
  is one jitted function of (its weights, x), traced once a sequence
  length, and the head runs in row blocks of 32,640 and only at the
  positions asked for.

`top`: {"model.embed_tokens.weight": [V, d], "model.final_layernorm.weight":
[d], "lm_head.weight": [V, d]}; `layer(i)`: the published names below
`model.layers.<i>.` (`models/falcon_h1.py published_weights` gives both).
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

HEAD_BLOCK_ROWS = 32640


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, weight, eps, groups: int = 1):
    shape = x.shape
    xg = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    xg = xg * jax.lax.rsqrt(jnp.mean(jnp.square(xg), axis=-1, keepdims=True)
                            + eps)
    return xg.reshape(shape) * weight


def _rope(x, theta):
    """x [b, heads, s, hd] at positions 0..s-1."""
    s, hd = x.shape[2], x.shape[3]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _linear(x, weight):
    """y = x W^T, W as published [out, in]."""
    return jnp.einsum("...i,oi->...o", x, _f32(weight))


def _attention(cfg, w, u):
    b, s, _ = u.shape
    n_head, n_kv, hd = (cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"])

    def heads(t, n):
        return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    q = heads(_linear(u, w["self_attn.q_proj.weight"]), n_head)
    k = heads(_linear(u, w["self_attn.k_proj.weight"])
              * cfg["key_multiplier"], n_kv)
    v = heads(_linear(u, w["self_attn.v_proj.weight"]), n_kv)
    q, k = _rope(q, float(cfg["rope_theta"])), _rope(k, float(cfg["rope_theta"]))
    k = jnp.repeat(k, n_head // n_kv, axis=1)
    v = jnp.repeat(v, n_head // n_kv, axis=1)
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1) @ v
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, n_head * hd)
    return _linear(attn, w["self_attn.o_proj.weight"])


def _mixer(cfg, w, u):
    b, s, _ = u.shape
    heads, p, n, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                      cfg["mamba_d_state"], cfg["mamba_n_groups"])
    d_ssm, width = cfg["mamba_d_ssm"], cfg["mamba_d_conv"]
    gn = g * n
    mup = jnp.concatenate([jnp.full((size,), m, jnp.float32) for size, m in
                           zip((d_ssm, d_ssm, gn, gn, heads),
                               cfg["ssm_multipliers"])])
    proj = _linear(u, w["mamba.in_proj.weight"]) * mup
    z, xbc, dt = (proj[..., :d_ssm], proj[..., d_ssm:2 * d_ssm + 2 * gn],
                  proj[..., 2 * d_ssm + 2 * gn:])
    kernel = _f32(w["mamba.conv1d.weight"])[:, 0, :]         # [channels, w]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    xbc = sum(padded[:, j:j + s] * kernel[:, j] for j in range(width)) \
        + _f32(w["mamba.conv1d.bias"])
    xbc = jax.nn.silu(xbc)
    x = xbc[..., :d_ssm].reshape(b, s, heads, p)
    bm = jnp.repeat(xbc[..., d_ssm:d_ssm + gn].reshape(b, s, g, n),
                    heads // g, axis=2)
    cm = jnp.repeat(xbc[..., d_ssm + gn:].reshape(b, s, g, n),
                    heads // g, axis=2)
    dt = jax.nn.softplus(dt + _f32(w["mamba.dt_bias"]))      # [b, s, heads]
    a = -jnp.exp(_f32(w["mamba.A_log"]))

    def step(state, xs):                                     # one token
        x_t, b_t, c_t, dt_t = xs
        state = state * jnp.exp(dt_t * a)[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, bm, cm, dt))
    state, y = jax.lax.scan(step, jnp.zeros((b, heads, p, n), jnp.float32),
                            xs)
    y = jnp.moveaxis(y, 0, 1) + _f32(w["mamba.D"])[:, None] * x
    y = y.reshape(b, s, d_ssm) * jax.nn.silu(z)
    y = _rms_norm(y, _f32(w["mamba.norm.weight"]), cfg["rms_norm_eps"],
                  groups=g)
    return _linear(y, w["mamba.out_proj.weight"]), state


def _layer(cfg, w, x):
    """One block: (x after it, the mixer's state after the last token)."""
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, _f32(w["input_layernorm.weight"]), eps)
    mixed, state = _mixer(cfg, w, h * cfg["ssm_in_multiplier"])
    x = x + cfg["attention_out_multiplier"] * _attention(
        cfg, w, h * cfg["attention_in_multiplier"]) \
        + cfg["ssm_out_multiplier"] * mixed
    h = _rms_norm(x, _f32(w["pre_ff_layernorm.weight"]), eps)
    gate = jax.nn.silu(_linear(h, w["feed_forward.gate_proj.weight"])
                       * cfg["mlp_multipliers"][0])
    x = x + cfg["mlp_multipliers"][1] * _linear(
        _linear(h, w["feed_forward.up_proj.weight"]) * gate,
        w["feed_forward.down_proj.weight"])
    return x, state


@functools.lru_cache(maxsize=8)
def _jitted(cfg_json: str):
    """(the layer, the head over one row block) of a configuration, each
    one jitted function: every layer and every block share its trace."""
    cfg = json.loads(cfg_json)

    def head(x, norm, rows):
        return _linear(_rms_norm(x, _f32(norm), cfg["rms_norm_eps"]), rows) \
            * cfg["lm_head_multiplier"]

    return jax.jit(functools.partial(_layer, cfg)), jax.jit(head)


def forward(top: Dict[str, Any], layer: Callable[[int], Dict[str, Any]],
            input_ids, cfg: Dict[str, Any],
            positions: Optional[Sequence[int]] = None,
            with_states: bool = False):
    """Logits in float32: [b, s, V], or [b, len(positions), V] at
    `positions` only (the head is 261,120 rows wide). `with_states`:
    (logits, every layer's recurrent state [b, heads, d_head, d_state]
    after the last token)."""
    block, head = _jitted(json.dumps(cfg, sort_keys=True, default=str))
    states = []
    with jax.default_matmul_precision("highest"):
        x = _f32(top["model.embed_tokens.weight"][input_ids]) \
            * cfg["embedding_multiplier"]
        for i in range(cfg["num_hidden_layers"]):
            # Waited for, so that one layer's tensors are alive at a time:
            # dispatch is asynchronous, and `layer(i)` may MAKE its tensors
            # (a program that keeps products [in, out] transposes them).
            x, state = jax.block_until_ready(block(layer(i), x))
            states.append(state)
        if positions is not None:
            x = x[:, jnp.asarray(positions, jnp.int32)]
        rows = top["lm_head.weight"]
        logits = jnp.concatenate(
            [jax.block_until_ready(head(
                x, top["model.final_layernorm.weight"],
                rows[r:r + HEAD_BLOCK_ROWS]))
             for r in range(0, rows.shape[0], HEAD_BLOCK_ROWS)], axis=-1)
        return (logits, states) if with_states else logits


def chosen_token_gaps(rows, generated):
    """For each generated token, how far its reference logit lies under
    the reference's maximum at that position (0 = the reference's own
    greedy choice). `rows` [len(generated), V]: the reference's logits at
    the positions that chose them."""
    generated = jnp.asarray(generated, jnp.int32)
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, generated[:, None], axis=-1)[:, 0]
