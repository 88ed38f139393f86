"""Qwen3-Next: a hybrid decoder, three Gated DeltaNet (linear attention)
layers to one gated softmax-attention layer, every layer followed by a
routed expert layer beside one sigmoid-gated shared expert.

    h   = x + mixer(norm(x))        mixer: gated attention when (i + 1) % 4
    out = h + moe(norm(h))                 == 0, else Gated DeltaNet

Written from the public config (hidden 2048; 16 query / 2 KV heads of 256
with rotary on a quarter of each head; 16 key / 32 value linear heads of
128 behind a width-4 causal convolution; 512 experts of width 512, top-10
renormalised; untied head) and trained by `JaxTrainer` ->
`make_train_step(model, opt, loss_fn=make_loss_fn(model))`: bf16
activations over f32 parameters, as the other families.

One chip of an expert-parallel group is TOLD which experts it holds
(`held_experts = (first, count)`): the router keeps its published width and
top-k, and the layer adds up only what its own experts give
(`ops/held_experts.py`). With `held_experts = (0, num_experts)` this is the
whole model.

Shared with the flax family (`models/_nn.py`): `dense`. Not shared, because
the equations differ: the norms here are zero-centred (`x / rms * (1 + w)`,
w from zeros; `_nn.RMSNorm` multiplies by a w from ones and `_nn.rms_norm`
reshapes into groups), the rotary covers the first quarter of a head and
runs on [batch, seq, heads, d] (`_nn.apply_rope` rotates whole heads on
[batch, heads, seq, d]: two transposes of whole activations to borrow ten
lines).

Weight layout against the published one (`published_weights` maps ours to
theirs; the plain reference keeps theirs): the linear layer's `in_proj_qkvz`
is published interleaved by key head, [q 128 | k 128 | v 256 | z 256] x 16,
and `in_proj_ba` as [b 2 | a 2] x 16; here the columns are [q | k | v | z]
and [b | a], each over all heads, so that the convolution's input and the
kernels' column blocks are contiguous. The attention layer's `q_proj` is
published as [query 256 | gate 256] x 16; here [query x 16 | gate x 16]. An
expert's `gate_proj` and `up_proj` sit side by side in one [hidden, 2 x
width] matrix (one grouped product, not two).

Left out, here and in the reference: the multi-token-prediction module the
model card mentions; the public config holds no key of it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models._nn import dense
from ray_tpu.models.gpt2 import next_token_loss
from ray_tpu.ops.attention import flash_attention_bse
from ray_tpu.ops.gated_delta import (gated_delta_rule, gdn_gate,
                                     gdn_prep)
from ray_tpu.ops.held_experts import (held_expert_mlp, load_balance_loss,
                                      route)


# What a rematerialised layer keeps between its forward and its backward,
# by the names the kernels' `custom_vjp`s and the expert layer give what
# they make; everything else of a layer (the small projections, the norms,
# the shared expert, the gathers) is made again in the backward.
# Chosen by one rule (ISSUE 38): the step's arguments + temporaries stay
# under 15.9 GB of a v5e's 16.9, names dropped from the cheap end by
# milliseconds a GB. All ten fit: 7.508 + 7.228 = 14.74 GB (PERF.md section
# 4 has the table). At [1, 8192], a layer, with the ms a step of
# `train_qwen3next_8k_ep16share` that no longer run twice:
#   gdn_out 67 MB + gdn_states 268 MB   x3 layers   13.7 ms (gdn_chunk_fwd)
#   flash_out 67 MB + flash_lse 0.5 MB  x1          13.2 ms (flash_fwd)
#   moe_plan 19 MB                      x4          4.2 ms (top-10 sort 2.4)
#   moe_h 40 MB                         x4          2.1 ms (moe_gmm gate|up)
#   moe_y 80 MB                         x4          1.8 ms (moe_gmm down)
#   gdn_in 201 MB                       x3          6.6 ms (the in_proj_qkvz
#       product: `gdn_prep`'s and `gdn_gate`'s one large residual, bf16)
#   gdn_qkv 134 MB + gdn_gated 67 MB    x3          1.4 ms (gdn_prep_fwd,
#       gdn_gate_fwd; jax's rounding pass on gdn_gated stays, 0.16 a layer)
KEPT_BY_REMAT = ("gdn_out", "gdn_states", "flash_out", "flash_lse",
                 "moe_plan", "moe_h", "moe_y", "gdn_in", "gdn_qkv",
                 "gdn_gated")
_KEEP = jax.checkpoint_policies.save_only_these_names(*KEPT_BY_REMAT)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512                 # the router's width
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    rms_norm_eps: float = 1e-6
    router_aux_loss_coef: float = 0.001
    held_experts: Tuple[int, int] = (0, 512)   # (first, count) held here
    # jax.checkpoint each layer: the backward makes a layer's forward again
    # except what `KEPT_BY_REMAT` names (no kernel's forward runs twice)
    remat: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def tiny(**kw) -> "Qwen3NextConfig":
        """One period at toy widths (CPU tests)."""
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=32, linear_num_key_heads=2,
                    linear_num_value_heads=4, linear_key_head_dim=16,
                    linear_value_head_dim=16, num_experts=16,
                    num_experts_per_tok=4, moe_intermediate_size=32,
                    shared_expert_intermediate_size=32,
                    held_experts=(0, 16), remat=False)
        return Qwen3NextConfig(**{**base, **kw})

    def is_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0


def _rms(x, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


class ZeroCentredNorm(nn.Module):
    """x / sqrt(mean(x^2) + eps) * (1 + w) in f32, w from zeros."""
    eps: float

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.zeros, (x.shape[-1],),
                       jnp.float32)
        return _rms(x, self.eps) * (1.0 + w)


def rope_first_dims(x, rotary: int, theta: float):
    """Rotate-half rotary on the first `rotary` of a head's dims; x [batch,
    seq, heads, d], positions 0..seq-1."""
    half = rotary // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            xf[..., rotary:]], axis=-1)


class GatedDeltaNet(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        key_w, val_w = hk * dk, hv * dv
        qkvz = dense(2 * key_w + 2 * val_w, ("embed", "mlp"), cfg,
                     "in_proj_qkvz")(x)
        ba = dense(2 * hv, ("embed", "heads"), cfg, "in_proj_ba")(x)
        conv_w = self.param(
            "conv1d", lambda k, shape: jax.random.uniform(
                k, shape, jnp.float32, -0.5, 0.5),
            (2 * key_w + val_w, cfg.linear_conv_kernel_dim))
        a_log = self.param(
            "A_log", lambda k, shape: jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1e-3, 16.0)), (hv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,),
                             jnp.float32)
        norm_w = self.param("norm", nn.initializers.ones, (dv,), jnp.float32)
        with jax.named_scope("gdn_conv"):
            q, k, v, z_in = gdn_prep(qkvz, conv_w, dk)
        beta = jax.nn.sigmoid(ba[..., :hv].astype(jnp.float32))
        g = -jnp.exp(a_log) * jax.nn.softplus(
            ba[..., hv:].astype(jnp.float32) + dt_bias)
        o = gated_delta_rule(q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk),
                             v.reshape(b, s, hv, dv), g, beta)
        with jax.named_scope("gdn_gate"):
            o = gdn_gate(o.reshape(b, s, val_w), z_in, norm_w,
                         cfg.rms_norm_eps)
        return dense(cfg.hidden_size, ("mlp", "embed"), cfg, "out_proj")(o)


class GatedAttention(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        h, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        rotary = int(d * cfg.partial_rotary_factor)
        qg = dense(2 * h * d, ("embed", "heads"), cfg, "q_proj")(x)
        k = dense(kv * d, ("embed", "kv"), cfg, "k_proj")(x)
        v = dense(kv * d, ("embed", "kv"), cfg, "v_proj")(x)
        q, gate = qg[..., :h * d], qg[..., h * d:]
        q = ZeroCentredNorm(cfg.rms_norm_eps, name="q_norm")(
            q.reshape(b, s, h, d))
        k = ZeroCentredNorm(cfg.rms_norm_eps, name="k_norm")(
            k.reshape(b, s, kv, d))
        q = rope_first_dims(q, rotary, cfg.rope_theta).astype(cfg.dtype)
        k = rope_first_dims(k, rotary, cfg.rope_theta).astype(cfg.dtype)
        # Each KV head serves h // kv query heads: k and v go in as they are,
        # [batch, seq, kv*d], and the kernels read a KV head in place for
        # its group (at d < 128, the CPU tests' widths, the entry repeats).
        attn = flash_attention_bse(
            (q.reshape(b, s, h * d), k.reshape(b, s, kv * d), v), d,
            causal=True)
        gated = attn.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32))
        return dense(cfg.hidden_size, ("heads", "embed"), cfg, "o_proj")(
            gated.astype(cfg.dtype))


class SparseMoe(nn.Module):
    """Routed experts (the held ones) + the sigmoid-gated shared expert.
    Takes the f32 normed activations: the router reads them as they are,
    every other product their bf16 rounding."""
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x32):
        cfg = self.cfg
        b, s, d = x32.shape
        _, count = cfg.held_experts
        width = cfg.moe_intermediate_size
        init = nn.initializers.normal(0.02)
        w_router = self.param("router", init, (d, cfg.num_experts),
                              jnp.float32)
        w_gate_up = self.param("experts_gate_up", init,
                               (count, d, 2 * width), cfg.param_dtype)
        w_down = self.param("experts_down", init, (count, width, d),
                            cfg.param_dtype)
        x = x32.astype(cfg.dtype)
        with jax.named_scope("moe_route"):
            probs, gates, index = route(x32.reshape(b * s, d), w_router,
                                        cfg.num_experts_per_tok)
        with jax.named_scope("moe_experts"):
            y, counts = held_expert_mlp(
                x.reshape(b * s, d), gates, index, w_gate_up, w_down,
                cfg.held_experts, cfg.num_experts)
        sw = cfg.shared_expert_intermediate_size
        shared = dense(d, ("mlp", "embed"), cfg, "shared_down")(
            jax.nn.silu(dense(sw, ("embed", "mlp"), cfg, "shared_gate")(x))
            * dense(sw, ("embed", "mlp"), cfg, "shared_up")(x))
        share = jax.nn.sigmoid(
            dense(1, ("embed", None), cfg, "shared_expert_gate")(x).astype(
                jnp.float32))
        out = y.reshape(b, s, d) + share * shared.astype(jnp.float32)
        aux = {"load_balance": load_balance_loss(probs, index,
                                                 cfg.num_experts),
               "index": index, **counts}
        return out, aux


class Qwen3NextLayer(nn.Module):
    cfg: Qwen3NextConfig
    attention: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        mixer = (GatedAttention(cfg, name="self_attn") if self.attention
                 else GatedDeltaNet(cfg, name="linear_attn"))
        normed = ZeroCentredNorm(cfg.rms_norm_eps, name="input_norm")(x)
        h = x + mixer(normed.astype(cfg.dtype))
        out, aux = SparseMoe(cfg, name="mlp")(
            ZeroCentredNorm(cfg.rms_norm_eps, name="post_norm")(h))
        return (h.astype(jnp.float32) + out).astype(cfg.dtype), aux


class Qwen3Next(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, input_ids, return_aux: bool = False):
        """Logits [batch, seq, vocab] in the activation dtype; with
        `return_aux` also {"load_balance": [layers], "index": [layers,
        tokens, k] (the router's choices), "load": [layers, held],
        "assigned", "placed": [layers]} from the expert layers."""
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype,
                     embedding_init=nn.initializers.normal(0.02),
                     name="embed_tokens")(input_ids)
        layer = (nn.remat(Qwen3NextLayer, policy=_KEEP) if cfg.remat
                 else Qwen3NextLayer)
        auxes = []
        for i in range(cfg.num_hidden_layers):
            x, aux = layer(cfg, cfg.is_attention(i), name=f"layers_{i}")(x)
            auxes.append(aux)
        x = ZeroCentredNorm(cfg.rms_norm_eps, name="norm")(x)
        with jax.named_scope("lm_head"):
            logits = dense(cfg.vocab_size, ("embed", "vocab"), cfg,
                           "lm_head")(x.astype(cfg.dtype))
        if not return_aux:
            return logits
        return logits, jax.tree.map(lambda *a: jnp.stack(a), *auxes)


def make_loss_fn(model: Qwen3Next):
    """`loss_fn(params, batch)` for `make_train_step`: the objective is the
    cross-entropy plus `router_aux_loss_coef` x the layers' mean
    load-balance loss; what is shown is {"loss": the cross-entropy alone,
    "moe": the expert layers' counts}, outputs of the step."""
    coef = model.cfg.router_aux_loss_coef

    def loss_fn(params, batch):
        logits, aux = model.apply(params, batch["input_ids"],
                                  return_aux=True)
        with jax.named_scope("head_loss"):
            ce = next_token_loss(logits, batch["labels"])
        balance = aux.pop("load_balance")
        del aux["index"]
        return ce + coef * jnp.mean(balance), {
            "loss": ce, "load_balance": balance, "moe": aux}

    return loss_fn


def published_weights(params, cfg: Qwen3NextConfig) -> Dict[str, Any]:
    """The parameters under the published names and in the published
    layouts (module docstring), as the plain reference reads them. Linear
    weights are [in, out]. Experts keep the program's held range: expert j
    of the result is expert held_experts[0] + j of the model."""
    p = nn.unbox(params["params"] if "params" in params else params)
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    per = hv // hk
    out = {"embed_tokens": p["embed_tokens"]["embedding"],
           "norm": p["norm"]["weight"], "lm_head": p["lm_head"]["kernel"]}
    for i in range(cfg.num_hidden_layers):
        lp, pre = p[f"layers_{i}"], f"layers.{i}."
        out[pre + "input_layernorm"] = lp["input_norm"]["weight"]
        out[pre + "post_attention_layernorm"] = lp["post_norm"]["weight"]
        if cfg.is_attention(i):
            a = lp["self_attn"]
            h, d = cfg.num_attention_heads, cfg.head_dim
            w = a["q_proj"]["kernel"]
            out[pre + "self_attn.q_proj"] = jnp.concatenate(
                [w[:, :h * d].reshape(-1, h, d), w[:, h * d:].reshape(
                    -1, h, d)], axis=-1).reshape(-1, 2 * h * d)
            for name in ("k_proj", "v_proj", "o_proj"):
                out[pre + "self_attn." + name] = a[name]["kernel"]
            out[pre + "self_attn.q_norm"] = a["q_norm"]["weight"]
            out[pre + "self_attn.k_norm"] = a["k_norm"]["weight"]
        else:
            m = lp["linear_attn"]
            w = m["in_proj_qkvz"]["kernel"]
            d_in = w.shape[0]
            cuts = (hk * dk, 2 * hk * dk, 2 * hk * dk + hv * dv)
            parts = [w[:, :cuts[0]].reshape(d_in, hk, dk),
                     w[:, cuts[0]:cuts[1]].reshape(d_in, hk, dk),
                     w[:, cuts[1]:cuts[2]].reshape(d_in, hk, per * dv),
                     w[:, cuts[2]:].reshape(d_in, hk, per * dv)]
            out[pre + "linear_attn.in_proj_qkvz"] = jnp.concatenate(
                parts, axis=-1).reshape(d_in, -1)
            w = m["in_proj_ba"]["kernel"]
            out[pre + "linear_attn.in_proj_ba"] = jnp.concatenate(
                [w[:, :hv].reshape(d_in, hk, per),
                 w[:, hv:].reshape(d_in, hk, per)], axis=-1).reshape(d_in, -1)
            out[pre + "linear_attn.conv1d"] = m["conv1d"]
            out[pre + "linear_attn.A_log"] = m["A_log"]
            out[pre + "linear_attn.dt_bias"] = m["dt_bias"]
            out[pre + "linear_attn.norm"] = m["norm"]
            out[pre + "linear_attn.out_proj"] = m["out_proj"]["kernel"]
        e = lp["mlp"]
        width = cfg.moe_intermediate_size
        out[pre + "mlp.gate"] = e["router"]
        out[pre + "mlp.experts.gate_proj"] = e["experts_gate_up"][..., :width]
        out[pre + "mlp.experts.up_proj"] = e["experts_gate_up"][..., width:]
        out[pre + "mlp.experts.down_proj"] = e["experts_down"]
        for ours, theirs in (("shared_gate", "gate_proj"),
                             ("shared_up", "up_proj"),
                             ("shared_down", "down_proj")):
            out[pre + "mlp.shared_expert." + theirs] = e[ours]["kernel"]
        out[pre + "mlp.shared_expert_gate"] = e["shared_expert_gate"]["kernel"]
    return out
