"""GPT-2 forward and next-token loss as published (Radford et al. 2019;
`huggingface.co/openai-community/gpt2-medium` `modeling_gpt2.py`), in
plain `jax.numpy` float32.

Departures from the published description, each forced by the
configuration under test and listed in its file:
- the vocabulary table may be padded (50257 -> 50304 rows); padded rows
  take part in the softmax exactly as the system's do;
- positions are learned embeddings (`wpe`), as published, added to the
  token embedding; the head is tied to `wte`;
- `gelu_new` (the tanh approximation), as published;
- LayerNorm epsilon is the published 1e-5 unless the caller passes
  another (the program's flax LayerNorm uses 1e-6: see PERF.md, Open
  questions).

Weights: {"wte": [V,d], "wpe": [P,d], "ln_f.g", "ln_f.b",
"h.<i>.ln_1.g|b", "h.<i>.attn.c_attn.w" [d,3d] |".b",
"h.<i>.attn.c_proj.w|b", "h.<i>.ln_2.g|b", "h.<i>.mlp.c_fc.w" [d,4d] |".b",
"h.<i>.mlp.c_proj.w|b"}.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(weights: Dict[str, jax.Array], input_ids, n_layer: int,
            n_head: int, eps: float = 1e-5):
    """Logits [b, s, V] in float32."""
    w = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()}
    with jax.default_matmul_precision("highest"):
        b, s = input_ids.shape
        x = w["wte"][input_ids] + w["wpe"][None, :s]
        d = x.shape[-1]
        hd = d // n_head
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(n_layer):
            p = f"h.{i}."
            h = _layer_norm(x, w[p + "ln_1.g"], w[p + "ln_1.b"], eps)
            qkv = h @ w[p + "attn.c_attn.w"] + w[p + "attn.c_attn.b"]
            q, k, v = (t.reshape(b, s, n_head, hd).transpose(0, 2, 1, 3)
                       for t in jnp.split(qkv, 3, axis=-1))
            scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
            scores = jnp.where(causal, scores, -jnp.inf)
            attn = jax.nn.softmax(scores, axis=-1) @ v
            attn = attn.transpose(0, 2, 1, 3).reshape(b, s, d)
            x = x + attn @ w[p + "attn.c_proj.w"] + w[p + "attn.c_proj.b"]
            h = _layer_norm(x, w[p + "ln_2.g"], w[p + "ln_2.b"], eps)
            h = _gelu_new(h @ w[p + "mlp.c_fc.w"] + w[p + "mlp.c_fc.b"])
            x = x + h @ w[p + "mlp.c_proj.w"] + w[p + "mlp.c_proj.b"]
        x = _layer_norm(x, w["ln_f.g"], w["ln_f.b"], eps)
        return x @ w["wte"].T


def next_token_loss(logits, labels):
    """Mean cross-entropy of token t+1 given tokens <= t."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
