"""Qwen3-Next, plainly: float32, `highest` matmul precision, no kernels, no
chunks, no sorting. Written from the public config and the family's public
description, independently of `ray_tpu/models/qwen3_next.py`; weights come
under the PUBLISHED names and layouts (linear weights as [in, out]).

    norm(x)  = x / sqrt(mean(x^2) + eps) * (1 + w)
    layer i  : h = x + mixer(norm(x)); out = h + moe(norm(h))
               mixer = gated attention when (i + 1) % 4 == 0, else Gated
               DeltaNet

Gated DeltaNet: `in_proj_qkvz` is interleaved by key head ([q | k | v x r |
z x r], r = value heads per key head), `in_proj_ba` likewise ([b x r | a x
r]); [q; k; v] pass a causal depthwise convolution (no bias) and SiLU; q, k
are L2-normalised per head (x * rsqrt(sum x^2 + 1e-6)) and repeated to the
value heads, q scaled by dk^-0.5; beta = sigmoid(b), g = -exp(A_log) *
softplus(a + dt_bias); then, per head and one position at a time,

    S' = exp(g_t) S;  u = beta_t (v_t - S'^T k_t);  S = S' + k_t u^T;
    o_t = S^T q_t

and out_proj(w * rmsnorm(o) * silu(z)).

Gated attention: `q_proj` gives [query | gate] per head; q, k pass a
zero-centred RMS norm over the head, rotate-half rotary on the first
`partial_rotary_factor` of the head, causal softmax at d^-0.5 with each KV
head serving heads/kv query heads; o_proj(attn * sigmoid(gate)).

Expert layer: p = softmax(x W_r) over all experts, top-k, renormalised;
the sum over the chosen experts THAT ARE HELD (`held = (first, count)`:
`experts.*[j]` is expert first + j) of p_e down_e(silu(gate_e x) * up_e x),
plus sigmoid(x w_s) * shared(x). Every token visits every held expert
densely and is masked by its gate: nothing to sort, nothing to drop.

Left out, as in the program: the multi-token-prediction module.

Two things the builder's comparison asks of it (`benchmarks/builders/
qwen3_next_train.py`). `forward(..., picks=...)` routes every layer with
the experts it is HANDED (the system's own choices) and takes its gates from
its own probabilities at those experts, so that loss, logits and gradients
read the system's rounding and not a different top-k set; its own free
choice is returned beside. `taps=True` also returns what each layer's router
read and what it answered, the held experts' sum, and the recurrence's
operands: the inputs on which the system's router, recurrence and expert
layer are run alone. For the controls that must come out as not correct
(`benchmarks/qwen3next_controls.py`) `router` is called on operands rounded
to bf16, and `delta_rule(..., carry=jnp.bfloat16)` holds its state in bf16
between positions: the one place here that computes lower when told to.

So that one 8k sequence's gradients fit a 16 GB chip beside the system,
four things are `jax.checkpoint`ed (recomputed in the backward pass, the
same values): each layer, each expert's pass over the tokens, each block
of `QUERY_BLOCK` query rows of the softmax attention (all keys at once: no
online softmax), and each run of `SCAN_BLOCK` steps of the recurrence
(still one position at a time).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
SCAN_BLOCK = 64


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def _rope(x, rotary: int, theta: float):
    """x [seq, heads, d]: rotate-half on the first `rotary` dims."""
    half = rotary // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]   # [s, 1, rotary]
    rot, rest = x[..., :rotary], x[..., rotary:]
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    return jnp.concatenate([rot * jnp.cos(ang) + turned * jnp.sin(ang),
                            rest], axis=-1)


def delta_rule(q, k, v, g, beta, carry=jnp.float32):
    """The recurrence, one position at a time: q, k, v [seq, heads, d] (q, k
    already repeated to the value heads), g, beta [seq, heads]; the state
    [heads, dk, dv] from zero. `carry` is the dtype the state is held in
    between positions (float32 IS the reference; bfloat16 is the control)."""
    s, hv, dv = v.shape

    def step(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        state = state.astype(jnp.float32) * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state.astype(carry), jnp.einsum("hkv,hk->hv", state, q_t)

    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s
    _, o = jax.lax.scan(
        jax.checkpoint(lambda state, ts: jax.lax.scan(step, state, ts)),
        jnp.zeros((hv, q.shape[-1], dv), carry),
        jax.tree.map(lambda t: t.reshape(s // block, block, *t.shape[1:]),
                     (q, k, v, g, beta)))
    return o.reshape(s, hv, dv)


def gated_delta_net(w: Dict[str, Any], pre: str, x, cfg: Dict[str, Any]):
    """(the mixer's output [s, hidden], the recurrence's operands (q, k per
    KEY head, v, g, beta) as the taps)."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    r = hv // hk
    s = x.shape[0]
    qkvz = (x @ w[pre + "in_proj_qkvz"]).reshape(s, hk, 2 * dk + 2 * r * dv)
    ba = (x @ w[pre + "in_proj_ba"]).reshape(s, hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(s, hv, dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(s, hv, dv)
    b, a = ba[..., :r].reshape(s, hv), ba[..., r:].reshape(s, hv)
    mixed = jnp.concatenate([q.reshape(s, -1), k.reshape(s, -1),
                             v.reshape(s, -1)], axis=-1)
    conv = w[pre + "conv1d"]                        # [channels, width]
    width = conv.shape[1]
    padded = jnp.pad(mixed, ((width - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[j:j + s] * conv[:, j]
                            for j in range(width)))
    q = mixed[:, :hk * dk].reshape(s, hk, dk)
    k = mixed[:, hk * dk:2 * hk * dk].reshape(s, hk, dk)
    v = mixed[:, 2 * hk * dk:].reshape(s, hv, dv)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                 + 1e-6)

    q = jnp.repeat(unit(q), r, axis=1) * dk ** -0.5
    k = jnp.repeat(unit(k), r, axis=1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w[pre + "A_log"]) * jax.nn.softplus(a + w[pre + "dt_bias"])

    o = delta_rule(q, k, v, g, beta)
    taps = (q[:, ::r], k[:, ::r], v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"]) * w[pre + "norm"]
    return (o * jax.nn.silu(z)).reshape(s, hv * dv) @ w[pre + "out_proj"], taps


def gated_attention(w: Dict[str, Any], pre: str, x, cfg: Dict[str, Any]):
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    s = x.shape[0]
    eps = cfg["rms_norm_eps"]
    qg = (x @ w[pre + "q_proj"]).reshape(s, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (x @ w[pre + "k_proj"]).reshape(s, kv, d)
    v = (x @ w[pre + "v_proj"]).reshape(s, kv, d)
    rotary = int(d * cfg["partial_rotary_factor"])
    q = _rope(_norm(q, w[pre + "q_norm"], eps), rotary, cfg["rope_theta"])
    k = _rope(_norm(k, w[pre + "k_norm"], eps), rotary, cfg["rope_theta"])
    k, v = (jnp.repeat(t, h // kv, axis=1) for t in (k, v))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    @jax.checkpoint
    def rows(args):
        q_rows, at = args                       # [block, h, d], [block]
        scores = jnp.einsum("qhd,khd->hqk", q_rows, k) * d ** -0.5
        seen = at[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    attn = jax.lax.map(rows, (q.reshape(s // block, block, h, d),
                              jnp.arange(s).reshape(s // block, block))
                       ).reshape(s, h, d)
    return (attn * jax.nn.sigmoid(gate)).reshape(s, h * d) @ w[pre + "o_proj"]


def router(x, w_gate, top_k: int):
    """(probs [s, experts] over ALL experts, the top-k's index [s, k])."""
    probs = jax.nn.softmax(x @ w_gate, axis=-1)
    return probs, jax.lax.top_k(probs, top_k)[1]


def held_experts(w: Dict[str, Any], pre: str, x, gates, index,
                 held: Tuple[int, int]):
    """sum over the chosen experts that are held of gate * expert(x): x
    [s, hidden], gates and index [s, k]."""
    first, count = held

    @jax.checkpoint
    def one_expert(total, args):
        j, gate_proj, up_proj, down_proj = args
        gate = jnp.sum(jnp.where(index == first + j, gates, 0.0), axis=-1)
        hidden = jax.nn.silu(x @ gate_proj) * (x @ up_proj)
        return total + gate[:, None] * (hidden @ down_proj), None

    out = jnp.zeros_like(x)
    if count:       # one expert after another (lax.scan: compiled once)
        out, _ = jax.lax.scan(one_expert, out, (
            jnp.arange(count), w[pre + "experts.gate_proj"],
            w[pre + "experts.up_proj"], w[pre + "experts.down_proj"]))
    return out


def expert_layer(w: Dict[str, Any], pre: str, x, cfg: Dict[str, Any],
                 held: Tuple[int, int], picks=None):
    """(out [s, hidden], top-k index [s, k], load-balance loss, taps).
    With `picks` [s, k] the layer is routed to THOSE experts (gates from its
    own probabilities there); the index returned is its own free choice
    either way."""
    top_k, experts = cfg["num_experts_per_tok"], cfg["router_width"]
    probs, index = router(x, w[pre + "gate"], top_k)
    used = index if picks is None else picks
    top_p = jnp.take_along_axis(probs, used, axis=-1)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    routed = held_experts(w, pre, x, top_p, used, held)
    shared = (jax.nn.silu(x @ w[pre + "shared_expert.gate_proj"])
              * (x @ w[pre + "shared_expert.up_proj"])
              ) @ w[pre + "shared_expert.down_proj"]
    out = routed + jax.nn.sigmoid(x @ w[pre + "shared_expert_gate"]) * shared
    hits = jnp.sum(jax.nn.one_hot(used, experts), axis=(0, 1))
    balance = experts * jnp.sum(hits / x.shape[0] * jnp.mean(probs, axis=0))
    return out, index, balance, {"router_in": x, "probs": probs,
                                 "gates": top_p, "routed": routed}


def forward(w: Dict[str, Any], ids, cfg: Dict[str, Any],
            held: Tuple[int, int], precision: str = "highest", picks=None,
            taps: bool = False):
    """One sequence: ids [seq] -> (logits [seq, vocab], top-k index
    [layers, seq, k], load-balance losses [layers]), and with `taps` a
    fourth: {"router_in", "probs", "gates", "routed": [layers, seq, ...],
    "recurrence": per Gated DeltaNet layer (q, k, v, g, beta)}. `cfg` is
    the configuration file's keys, with `router_width` for the count of
    experts the router chooses among. `picks` [layers, seq, k] routes every
    layer with those experts (module docstring). `precision` is the matmul
    precision: the reference IS "highest"."""
    with jax.default_matmul_precision(precision):
        eps = cfg["rms_norm_eps"]
        x = w["embed_tokens"][ids].astype(jnp.float32)
        free, balances, tapped, recurrences = [], [], [], []

        def layer(i, w, x, picked):
            pre = f"layers.{i}."
            normed = _norm(x, w[pre + "input_layernorm"], eps)
            operands = None
            if (i + 1) % cfg["full_attention_interval"] == 0:
                x = x + gated_attention(w, pre + "self_attn.", normed, cfg)
            else:
                mixed, operands = gated_delta_net(w, pre + "linear_attn.",
                                                  normed, cfg)
                x = x + mixed
            out, index, balance, tap = expert_layer(
                w, pre + "mlp.",
                _norm(x, w[pre + "post_attention_layernorm"], eps), cfg, held,
                picked)
            return x + out, index, balance, tap, operands

        for i in range(cfg["num_hidden_layers"]):
            x, index, balance, tap, operands = jax.checkpoint(
                layer, static_argnums=0)(
                i, w, x, None if picks is None else picks[i])
            free.append(index)
            balances.append(balance)
            tapped.append(tap)
            if operands is not None:
                recurrences.append(operands)
        logits = _norm(x, w["norm"], eps) @ w["lm_head"]
        out = (logits, jnp.stack(free), jnp.stack(balances))
        if not taps:
            return out
        return out + ({**jax.tree.map(lambda *a: jnp.stack(a), *tapped),
                       "recurrence": recurrences},)


def next_token_loss(logits, ids):
    """Mean cross-entropy of position t's logits against token t+1."""
    logp = jax.nn.log_softmax(logits[:-1].astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))
