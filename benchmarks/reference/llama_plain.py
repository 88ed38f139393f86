"""Llama-equations decoder forward (Touvron et al. 2023; the layer
equations Mistral-7B-v0.3 publishes in `modeling_mistral.py`: RMSNorm,
rotary embeddings, grouped-query attention, SwiGLU, untied head), in
plain `jax.numpy` float32: full causal attention over the whole
sequence, no cache, no paging.

Notes on conventions:
- RoPE rotates the two HALVES of a head (feature i with i + d/2), the
  convention of the published Hugging Face weights and of the program's
  `apply_rope`; the frequencies are theta^(-i/(d/2)).
- no sliding window (v0.3 publishes `sliding_window: null`).
- weights come a layer at a time through `layer(i)`, so that only one
  layer is ever held in float32 (a 7B-wide layer is 0.87 GB there).

`top`: {"embed": [V,d], "final_norm": [d], "lm_head": [d,V]};
`layer(i)`: {"attn_norm": [d], "wq": [d,h*hd], "wk": [d,kv*hd],
"wv": [d,kv*hd], "wo": [h*hd,d], "mlp_norm": [d], "w_gate": [d,f],
"w_up": [d,f], "w_down": [f,d]}.
"""

from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [b, heads, s, hd] at positions 0..s-1."""
    s, hd = x.shape[2], x.shape[3]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(top: Dict[str, jax.Array],
            layer: Callable[[int], Dict[str, jax.Array]], input_ids,
            n_layer: int, n_head: int, n_kv_head: int, rope_theta: float,
            rms_eps: float):
    """Logits [b, s, V] in float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        b, s = input_ids.shape
        x = f32(top["embed"][input_ids])
        d = x.shape[-1]
        hd = d // n_head
        groups = n_head // n_kv_head
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(n_layer):
            w = {k: f32(v) for k, v in layer(i).items()}
            h = _rms_norm(x, w["attn_norm"], rms_eps)
            q = (h @ w["wq"]).reshape(b, s, n_head, hd).transpose(0, 2, 1, 3)
            k = (h @ w["wk"]).reshape(b, s, n_kv_head, hd).transpose(0, 2, 1, 3)
            v = (h @ w["wv"]).reshape(b, s, n_kv_head, hd).transpose(0, 2, 1, 3)
            q, k = _rope(q, rope_theta), _rope(k, rope_theta)
            k = jnp.repeat(k, groups, axis=1)
            v = jnp.repeat(v, groups, axis=1)
            scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
            scores = jnp.where(causal, scores, -jnp.inf)
            attn = jax.nn.softmax(scores, axis=-1) @ v
            attn = attn.transpose(0, 2, 1, 3).reshape(b, s, n_head * hd)
            x = x + attn @ w["wo"]
            h = _rms_norm(x, w["mlp_norm"], rms_eps)
            x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) \
                @ w["w_down"]
        x = _rms_norm(x, f32(top["final_norm"]), rms_eps)
        return x @ f32(top["lm_head"])


def chosen_token_gaps(logits, prompt_len: int, generated):
    """For each generated token, how far its reference logit lies under
    the reference's maximum at that position (0 = the reference's own
    greedy choice). `logits` [s, V] of prompt + generated[:-1]."""
    generated = jnp.asarray(generated, jnp.int32)
    rows = logits[prompt_len - 1:prompt_len - 1 + generated.shape[0]]
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, generated[:, None], axis=-1)[:, 0]
