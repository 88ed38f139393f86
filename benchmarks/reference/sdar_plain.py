"""SDAR forward and generation (`model_type` `sdar_moe`: autoregressive
across blocks of `block_length` positions, masked discrete diffusion inside
one; JetLM, "SDAR: A Synergistic Diffusion-AutoRegression Paradigm for
Scalable Sequence Generation", 2025-10, `modeling_sdar_moe.py` and
`generate.py`), in plain `jax.numpy` float32: a dense mask over the whole
sequence, a Python loop over the experts, a Python loop of full forward
passes for generation. No kernel, no cache, no grouping of experts, no
batching tricks: independent of the step the system runs
(`ray_tpu/models/sdar.py`) and of `ray_tpu/ops/`.

Per layer, `cfg` the published `config.json` as a dict plus the family's
published constants (d = `head_dim`, N RMSNorm with a learned weight, eps
`rms_norm_eps`, L = `block_length`):

    h = input_layernorm(x)
    q = rope(q_norm(q_proj(h)));  k = rope(k_norm(k_proj(h)));  v = v_proj(h)
        q_norm / k_norm over the d of each head; `num_attention_heads`
        query heads, `num_key_value_heads` KV heads, each KV head repeated
        over its group of query heads; no bias
    a_p = sum_{j <= vis(p)} softmax_j(q_p . k_j / sqrt(d)) v_j,
        vis(p) = (p // L + 1) L - 1: p's whole block and all before it
    x = x + o_proj(a)
    n = post_attention_layernorm(x)
    s = softmax(gate(n)) over `num_experts`
    idx = top-`num_experts_per_tok`(s);  g = s[idx] / sum(s[idx])
          (`norm_topk_prob`; else g = s[idx])
    x = x + sum_{e in idx} g_e down_e(silu(gate_e(n)) * up_e(n))

logits = lm_head(norm(x)); logits at p are the distribution of the token AT
p (no shift).

Notes on conventions:
- weights under the published names and layouts: a product's weight is
  [out, in] (y = x W^T); the experts' three come stacked, [experts, out,
  in];
- RoPE rotates the two HALVES of a head over all d dims, frequencies
  theta^(-i/(d/2)), theta = `rope_theta`, no scaling;
- ASSUMED, not keys of the published config (each also in the
  configuration file's `assumed`): the q/k norms, the absent shift,
  `block_length`, `denoising_steps`, `remasking_strategy`,
  `confidence_threshold`, `mask_token_id`;
- weights come a layer at a time through `layer(i)` and an expert at a time
  into float32; the head runs only at the positions asked for.

`top`: {"model.embed_tokens.weight": [V, e], "model.norm.weight": [e],
"lm_head.weight": [V, e]}; `layer(i)`: the published names below
`model.layers.<i>.` (`models/sdar.py published_weights` gives both).
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

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


def block_mask(s: int, length: int):
    """[s, s] bool: query p sees key j iff j <= vis(p)."""
    p = jnp.arange(s)
    return p[None, :] <= ((p // length + 1) * length - 1)[:, None]


def _attention(cfg, w, x):
    """(x after the attention sub-layer, keys after the rotary, values
    [b, s, kv_heads, d])."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, s, _ = x.shape
    heads, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    h = _rms_norm(x, w["input_layernorm.weight"], eps)
    q = _linear(h, w["self_attn.q_proj.weight"]).reshape(b, s, heads, d)
    k = _linear(h, w["self_attn.k_proj.weight"]).reshape(b, s, kvh, d)
    v = _linear(h, w["self_attn.v_proj.weight"]).reshape(b, s, kvh, d)
    q = _rope(_rms_norm(q, w["self_attn.q_norm.weight"], eps), theta)
    k = _rope(_rms_norm(k, w["self_attn.k_norm.weight"], eps), theta)
    kr, vr = (jnp.repeat(t, heads // kvh, axis=2) for t in (k, v))
    scores = jnp.einsum("bthd,buhd->bhtu", q, kr) / math.sqrt(d)
    scores = jnp.where(block_mask(s, cfg["block_length"]), scores, -jnp.inf)
    attn = jnp.einsum("bhtu,buhd->bthd", jax.nn.softmax(scores, axis=-1), vr)
    return x + _linear(attn.reshape(b, s, heads * d),
                       w["self_attn.o_proj.weight"]), k, v


def _route(cfg, w, x):
    """(n the normed input [b, s, e], the dense gate matrix [b, s, experts]:
    a token's gate at each chosen expert and 0 elsewhere, the chosen experts
    sorted [b, s, k], their gates in that order)."""
    n = _rms_norm(x, w["post_attention_layernorm.weight"],
                  cfg["rms_norm_eps"])
    probs = jax.nn.softmax(_linear(n, w["mlp.gate.weight"]), axis=-1)
    top, index = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    order = jnp.argsort(index, axis=-1)
    index = jnp.take_along_axis(index, order, axis=-1)
    top = jnp.take_along_axis(top, order, axis=-1)
    dense = jnp.sum(jax.nn.one_hot(index, probs.shape[-1]) * top[..., None],
                    axis=-2)
    return n, dense, index, top


def _expert(n, gate, w_gate, w_up, w_down):
    """One expert's share of the layer's output: its SwiGLU on every token,
    times the token's gate for it (0 where the token did not choose it)."""
    return gate[..., None] * _linear(
        jax.nn.silu(_linear(n, w_gate)) * _linear(n, w_up), w_down)


@functools.lru_cache(maxsize=8)
def _jitted(cfg_json: str):
    cfg = json.loads(cfg_json)
    return (jax.jit(functools.partial(_attention, cfg)),
            jax.jit(functools.partial(_route, cfg)), jax.jit(_expert),
            jax.jit(lambda x, w: _rms_norm(x, w, cfg["rms_norm_eps"])),
            jax.jit(_linear))


def forward(top: Dict[str, Any], layer: Callable[[int], Dict[str, Any]],
            input_ids, cfg: Dict[str, Any], at=None,
            keep: Sequence[int] = (), taps: bool = False):
    """Logits in float32, [b, s, V], or [b, n, V] at the positions `at`
    [b, n] of each row. With `keep` (layers, 0-based) or `taps` a dict
    beside them: `kv` {layer: (keys after the rotary, values) [b, s,
    kv_heads, d]} and, with `taps`, `experts` / `gates` [b, s, k]: the
    FIRST layer's chosen experts sorted and their gates."""
    attention, route, expert, norm, head = _jitted(
        json.dumps(cfg, sort_keys=True, default=str))
    extra: Dict[str, Any] = {"kv": {}}
    with jax.default_matmul_precision("highest"):
        x = _f32(top["model.embed_tokens.weight"][input_ids])
        for i in range(cfg["num_hidden_layers"]):
            w = layer(i)
            x, k, v = attention(w, x)
            if i in keep:
                extra["kv"][i] = (k, v)
            n, dense, index, gates = route(w, x)
            if taps and i == 0:
                extra["experts"], extra["gates"] = index, gates
            for e in range(cfg["num_experts"]):        # the plain loop
                x = x + expert(n, dense[..., e],
                               w["mlp.experts.gate_proj.weight"][e],
                               w["mlp.experts.up_proj.weight"][e],
                               w["mlp.experts.down_proj.weight"][e])
            # One layer's tensors alive at a time: dispatch is
            # asynchronous, and `layer(i)` MAKES its tensors.
            x = jax.block_until_ready(x)
            del w
        x = norm(x, top["model.norm.weight"])
        if at is not None:
            x = jnp.take_along_axis(
                x, jnp.asarray(at, jnp.int32)[:, :, None], axis=1)
        rows = top["lm_head.weight"]
        logits = jnp.concatenate(
            [jax.block_until_ready(head(x, rows[r:r + HEAD_BLOCK_ROWS]))
             for r in range(0, rows.shape[0], HEAD_BLOCK_ROWS)], axis=-1)
    return (logits, extra) if (keep or taps) else logits


# --------------------------------------------------------------------------- #
# Generation
# --------------------------------------------------------------------------- #


def transfer_counts(cfg: Dict[str, Any]) -> List[int]:
    """Positions denoise pass t commits under the static schedule
    (`get_num_transfer_tokens`)."""
    base, rest = divmod(cfg["block_length"], cfg["denoising_steps"])
    return [base + (t < rest) for t in range(cfg["denoising_steps"])]


def confidences(logits):
    """(x0 the argmax, log of its softmax probability) at every position,
    float32."""
    logits = _f32(logits)
    return jnp.argmax(logits, axis=-1), \
        jnp.max(logits, axis=-1) - jax.nn.logsumexp(logits, axis=-1)


def select(cfg: Dict[str, Any], log_conf, masked, t: int) -> List[int]:
    """The positions of one block that denoise pass `t` commits: the n_t
    masked ones of largest confidence (ties to the earlier), or under
    `low_confidence_dynamic` every masked one over the threshold where
    those are at least n_t. Never an unmasked position."""
    conf = np.asarray(log_conf, np.float64)
    open_ = [p for p in range(len(conf)) if masked[p]]
    n = min(transfer_counts(cfg)[t], len(open_))
    if cfg["remasking_strategy"] == "low_confidence_dynamic":
        high = [p for p in open_
                if conf[p] > math.log(cfg["confidence_threshold"])]
        if len(high) >= n:
            return high
    return sorted(sorted(open_, key=lambda p: (-conf[p], p))[:n])


def block_diffusion_generate(top, layer, prompt: Sequence[int],
                             cfg: Dict[str, Any], max_new_tokens: int,
                             forced: Optional[Callable] = None):
    """Greedy generation as upstream runs it, every pass a full forward
    over the whole sequence so far: (the new tokens, the passes: a list of
    {"start", "entered" (ids, -1 where masked), "committed" {position in
    the block: token}}; a commit pass commits nothing). `forced(start,
    entered)` may dictate a denoise pass's commitments (teacher forcing)."""
    length, mask_id = cfg["block_length"], cfg["mask_token_id"]
    seq = list(prompt[:len(prompt) // length * length])
    tail = list(prompt[len(seq):])
    out: List[int] = []
    passes: List[Dict[str, Any]] = []
    while len(out) < max_new_tokens:
        buf = tail + [-1] * (length - len(tail))
        given, tail, t = length - buf.count(-1), [], 0
        while True:
            ids = seq + [mask_id if v < 0 else v for v in buf]
            at = [list(range(len(seq), len(seq) + length))]
            logits = forward(top, layer, jnp.asarray([ids], jnp.int32), cfg,
                             at=at)[0]
            rec = {"start": len(seq), "entered": list(buf), "committed": {}}
            passes.append(rec)
            if -1 not in buf:
                break                       # that was the commit pass
            x0, conf = confidences(logits)
            if forced is not None:
                rec["committed"] = dict(forced(len(seq), list(buf)))
            else:
                rec["committed"] = {
                    p: int(x0[p]) for p in select(
                        cfg, conf, [v < 0 for v in buf], t)}
            for p, token in rec["committed"].items():
                buf[p] = token
            t += 1
        seq += buf
        out += buf[given:]
    return out[:max_new_tokens], passes


def chosen_token_gaps(rows, tokens):
    """For each token, how far its reference logit lies under the
    reference's maximum at that position (0 = the reference's own greedy
    choice). `rows` [n, V]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, tokens[:, None], axis=-1)[:, 0]
