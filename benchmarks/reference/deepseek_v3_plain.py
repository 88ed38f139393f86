"""`deepseek_v3` decoder forward (multi-head latent attention, a routed
expert layer with sigmoid scores and a selection bias; the published
`modeling_deepseek_v3`), in plain `jax.numpy` float32 under
`jax.default_matmul_precision("highest")`: the EXPANDED attention form
(every cached token's k_nope and v made from its latent, a [t, t] score
matrix a head), a loop over the experts, no cache, no kernel, no absorbed
product, no batching tricks: independent of the form the system runs.

Per block, `cfg` the published `config.json` as a dict (H heads, n =
`qk_nope_head_dim`, r = `qk_rope_head_dim`, v = `v_head_dim`, L =
`kv_lora_rank`; `q_lora_rank` null, `rope_scaling` null, one group):

    h = RMSNorm(x; rms_norm_eps)
    q = q_proj(h) -> H x [q_nope n | q_rope r]
    [c | k_r] = kv_a_proj_with_mqa(h) -> L + r;  c <- kv_a_layernorm(c)
    q_rope, k_r <- rotary: de-interleave (x_0 x_2 .. | x_1 x_3 ..), then
                   rotate the two halves (`rope_interleave`), theta
                   `rope_theta`; k_r is ONE head shared by all H
    [k_nope | v]_head = kv_b_proj(c) -> H x (n + v)
    score = (q_nope . k_nope + q_rope . k_r) / sqrt(n + r), causal softmax
    x = x + o_proj(concat_head sum p v)
    m = RMSNorm(x)
    layer < first_k_dense_replace:  x = x + down(silu(gate(m)) * up(m))
    else: s = sigmoid(m W_r^T) (float32); E = top-k of (s + bias);
          w_e = routed_scaling_factor * s_e / (sum_{e in E} s_e + 1e-20)
          x = x + sum_{e in E} w_e expert_e(m) + shared_experts(m)
    logits = lm_head(RMSNorm(x))

Notes on conventions:
- weights under the published names and layouts: a product's weight is
  [out, in] (y = x W^T), no bias anywhere; `kv_b_proj` [H (n + v), L], a
  head's k_nope rows above its v rows;
- DEPARTURE 1 (layout, not mathematics): the experts arrive STACKED,
  `mlp.experts.gate_up` [experts, in, 2F] (expert e's gate_proj^T beside
  its up_proj^T) and `mlp.experts.down` [experts, F, out], and are upcast
  and applied ONE AT A TIME in a loop; every token passes through every
  expert and takes its weight w_e (zero where the expert was not chosen),
  which is the published sum written densely;
- DEPARTURE 2: none in the rope. The published code's interleaved lanes
  are de-interleaved here exactly as it does; a program that keeps its
  rope columns half-split hands them back interleaved (`models/
  deepseek_v3.py published_weights`);
- `n_group` 1 / `topk_group` 1: the group limit selects everything and is
  not written;
- weights come a layer at a time through `layer(i)`, so that one layer's
  attention (105 MB), one dense MLP (151 MB) or one expert (19 MB) is held
  in float32 at a time; attention runs in blocks of `Q_BLOCK` query
  positions (a block's scores are H x Q_BLOCK x t float32: 277 MB at
  8,448), and the head in row blocks at the positions asked for.

`forward(..., with_taps=True)` also returns what the cell's two further
limits read: the FIRST layer's rows `[c | k_r]` [b, t, L + r] (normed,
rotated, half-split lanes: what a latent cache holds) and the FIRST expert
layer's chosen experts [b, t, k] and gates [b, t, k] (sorted by expert).

`top`: {"model.embed_tokens.weight": [V, e], "model.norm.weight": [e],
"lm_head.weight": [V, e]}; `layer(i)`: the published names below
`model.layers.<i>.`.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

HEAD_BLOCK_ROWS = 32064
Q_BLOCK = 256


def _f32(t):
    return jnp.asarray(t, jnp.float32)


def _linear(x, w):
    return x @ _f32(w).T


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * _f32(weight)


def _rope_interleaved(x, theta):
    """x [b, t, heads, r] at positions 0 .. t-1, lanes interleaved as
    published: de-interleave, then rotate halves."""
    b, t, heads, r = x.shape
    half = r // 2
    x = x.reshape(b, t, heads, half, 2).swapaxes(-1, -2).reshape(
        b, t, heads, r)
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs     # [t, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(cfg, w, h):
    """(the sub-block's output [b, t, e], the rows [c | k_r] [b, t, L + r])."""
    b, t, _ = h.shape
    heads, n, r, v, lat = (cfg["num_attention_heads"],
                           cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                           cfg["v_head_dim"], cfg["kv_lora_rank"])
    theta = float(cfg["rope_theta"])
    q = _linear(h, w["self_attn.q_proj.weight"]).reshape(b, t, heads, n + r)
    q_nope, q_rope = q[..., :n], _rope_interleaved(q[..., n:], theta)
    ckr = _linear(h, w["self_attn.kv_a_proj_with_mqa.weight"])
    c = _rms_norm(ckr[..., :lat], w["self_attn.kv_a_layernorm.weight"],
                  cfg["rms_norm_eps"])
    k_r = _rope_interleaved(ckr[..., None, lat:], theta)       # [b, t, 1, r]
    kv = _linear(c, w["self_attn.kv_b_proj.weight"]).reshape(
        b, t, heads, n + v)
    k_nope, val = kv[..., :n], kv[..., n:]
    scale = 1.0 / math.sqrt(n + r)
    pad = -t % Q_BLOCK
    blocks = (t + pad) // Q_BLOCK

    def queries(x):                       # [b, t, ..] -> [blocks, b, Q, ..]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((b, blocks, Q_BLOCK) + x.shape[2:]),
                            1, 0)

    def one_block(args):
        qn, qr, first = args
        scores = (jnp.einsum("bqhn,bkhn->bhqk", qn, k_nope)
                  + jnp.einsum("bqhr,bkr->bhqk", qr, k_r[:, :, 0])) * scale
        q_pos = first + jnp.arange(Q_BLOCK)
        causal = jnp.arange(t)[None, :] <= q_pos[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhv->bqhv", probs, val)

    out = jax.lax.map(one_block, (queries(q_nope), queries(q_rope),
                                  jnp.arange(blocks) * Q_BLOCK))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, heads * v)[:, :t]
    return _linear(out, w["self_attn.o_proj.weight"]), \
        jnp.concatenate([c, k_r[:, :, 0]], axis=-1)


def route(cfg, w, m):
    """(chosen experts [.., k] sorted by expert, their gates [.., k])."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_linear(m, w["mlp.gate.weight"]))
    _, index = jax.lax.top_k(
        scores + _f32(w["mlp.gate.e_score_correction_bias"]), k)
    index = jnp.sort(index, axis=-1)
    chosen = jnp.take_along_axis(scores, index, axis=-1)
    gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return index, gates * cfg["routed_scaling_factor"]


def _experts(cfg, w, m):
    """(sum_e w_e expert_e(m) + shared(m), the routing)."""
    experts = cfg["n_routed_experts"]
    index, gates = route(cfg, w, m)
    weight = jnp.sum(jax.nn.one_hot(index, experts, dtype=jnp.float32)
                     * gates[..., None], axis=-2)              # [b, t, E]
    gate_up, down = w["mlp.experts.gate_up"], w["mlp.experts.down"]
    width = down.shape[1]

    def one_expert(e, acc):
        gu = m @ _f32(gate_up[e])                              # [b, t, 2F]
        out = (jax.nn.silu(gu[..., :width]) * gu[..., width:]) @ _f32(down[e])
        return acc + jax.lax.dynamic_slice_in_dim(weight, e, 1, -1) * out

    routed = jax.lax.fori_loop(0, experts, one_expert, jnp.zeros_like(m))
    shared = _linear(
        jax.nn.silu(_linear(m, w["mlp.shared_experts.gate_proj.weight"]))
        * _linear(m, w["mlp.shared_experts.up_proj.weight"]),
        w["mlp.shared_experts.down_proj.weight"])
    return routed + shared, (index, gates)


def _layer(cfg, w, x):
    """One block: (x after it, its rows [c | k_r], its routing or None)."""
    eps = cfg["rms_norm_eps"]
    out, rows = _attention(
        cfg, w, _rms_norm(x, w["input_layernorm.weight"], eps))
    x = x + out
    m = _rms_norm(x, w["post_attention_layernorm.weight"], eps)
    if "mlp.gate.weight" not in w:
        return x + _linear(
            jax.nn.silu(_linear(m, w["mlp.gate_proj.weight"]))
            * _linear(m, w["mlp.up_proj.weight"]),
            w["mlp.down_proj.weight"]), rows, None
    y, routing = _experts(cfg, w, m)
    return x + y, rows, routing


@functools.lru_cache(maxsize=8)
def _jitted(cfg_json: str):
    """(the layer, the head over one row block) of a configuration, each
    one jitted function (the layer traces once for a dense block and once
    for an expert block: their weights differ in structure)."""
    cfg = json.loads(cfg_json)

    def head(x, norm, rows):
        return _linear(_rms_norm(x, norm, cfg["rms_norm_eps"]), rows)

    return jax.jit(functools.partial(_layer, cfg)), jax.jit(head)


def forward(top: Dict[str, Any], layer: Callable[[int], Dict[str, Any]],
            input_ids, cfg: Dict[str, Any],
            positions: Optional[Sequence[int]] = None,
            with_taps: bool = False):
    """Logits in float32: [b, t, V], or [b, len(positions), V] at
    `positions` only (the head is 128,256 rows wide). `with_taps`: (logits,
    {"latent_rows": the first layer's [b, t, L + r], "experts", "gates":
    the first expert layer's [b, t, k]})."""
    block, head = _jitted(json.dumps(cfg, sort_keys=True, default=str))
    taps: Dict[str, Any] = {}
    with jax.default_matmul_precision("highest"):
        x = _f32(top["model.embed_tokens.weight"][input_ids])
        for i in range(cfg["num_hidden_layers"]):
            # Waited for, so that one layer's tensors are alive at a time.
            x, rows, routing = jax.block_until_ready(block(layer(i), x))
            if i == 0:
                taps["latent_rows"] = rows
            if routing is not None and "experts" not in taps:
                taps["experts"], taps["gates"] = routing
            del rows, routing
        if positions is not None:
            x = x[:, jnp.asarray(positions, jnp.int32)]
        rows = top["lm_head.weight"]
        logits = jnp.concatenate(
            [jax.block_until_ready(head(x, top["model.norm.weight"],
                                        rows[r:r + HEAD_BLOCK_ROWS]))
             for r in range(0, rows.shape[0], HEAD_BLOCK_ROWS)], axis=-1)
        return (logits, taps) if with_taps else logits


def chosen_token_gaps(rows, generated):
    """For each generated token, how far its reference logit lies under
    the reference's maximum at that position (0 = the reference's own
    greedy choice). `rows` [len(generated), V]."""
    generated = jnp.asarray(generated, jnp.int32)
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, generated[:, None], axis=-1)[:, 0]
