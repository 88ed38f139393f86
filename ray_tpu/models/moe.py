"""Sparse mixture-of-experts decoder (Mixtral-style), expert-parallel.

Third model family: a Llama-shaped decoder whose MLP is a top-k routed
mixture of SwiGLU experts. The reference has no MoE (Ray delegates model
parallelism to Alpa/DeepSpeed, `release/alpa_tests/train_opt_2_7b_minimum.py:39`);
this is net-new capability designed for the TPU from the start:

- **Static shapes everywhere.** Token-choice routing with a fixed expert
  capacity: dispatch and combine are dense one-hot einsums (the GSPMD MoE
  idiom), so XLA can tile them onto the MXU — no gather/scatter with
  data-dependent shapes, no host round-trips.
- **Experts shard over the `ep` mesh axis.** Expert weights are stacked
  `[n_experts, d, f]` tensors carrying the ("expert", ...) logical axis
  (rule "expert" -> ep in `parallel/sharding.DEFAULT_RULES`); dispatched
  activations are constrained to ("expert", None, "embed"), which makes XLA
  place the token all-to-all over the ep axis of the mesh (ICI).
- Router in float32 with an optional z-loss; Switch-style load-balancing
  auxiliary loss sown into a "losses" collection and added to the training
  objective by `make_moe_train_step`.

Attention/norm/embedding reuse the flax family's components
(`models/_nn.py`) so tp/sp/fsdp behave exactly as in the dense families.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models._nn import RMSNorm, apply_rope, dense
from ray_tpu.models.gpt2 import next_token_loss


@dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    n_positions: int = 2048
    n_embd: int = 1024
    n_layer: int = 8
    n_head: int = 16
    n_kv_head: int = 8
    intermediate: int = 2816         # per-expert SwiGLU width
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25    # slots per expert = ceil(T*k*cf/E)
    aux_coef: float = 0.01           # Switch load-balance loss weight
    router_z_coef: float = 1e-3      # router logit magnitude control
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_flash: bool = True
    remat: bool = False

    @staticmethod
    def small() -> "MoEConfig":
        return MoEConfig()

    @staticmethod
    def tiny(seq: int = 128) -> "MoEConfig":
        return MoEConfig(vocab_size=512, n_positions=seq, n_embd=128,
                         n_layer=2, n_head=4, n_kv_head=2, intermediate=256,
                         n_experts=4, top_k=2, use_flash=False)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


def expert_capacity(cfg: MoEConfig, n_tokens: int) -> int:
    import math

    cap = math.ceil(n_tokens * cfg.top_k * cfg.capacity_factor
                    / cfg.n_experts)
    return max(cap, cfg.top_k)


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU experts with fixed capacity.

    Input/output [b, s, d]. Tokens overflowing an expert's capacity fall
    through the residual (their MLP contribution is zero) — standard
    Switch/GShard behavior that keeps every shape static.
    """

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        t = b * s
        e, k = cfg.n_experts, cfg.top_k
        cap = expert_capacity(cfg, t)

        wr = self.param(
            "router",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("embed", None)),
            (d, e), jnp.float32)
        # Stacked expert weights: leading dim carries the "expert" axis.
        def ew(name, shape_in, shape_out):
            return self.param(
                name,
                nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                             ("expert", "embed", "mlp")
                                             if shape_out == cfg.intermediate
                                             else ("expert", "mlp", "embed")),
                (e, shape_in, shape_out), cfg.param_dtype)

        w_gate = ew("w_gate", d, cfg.intermediate)
        w_up = ew("w_up", d, cfg.intermediate)
        w_down = ew("w_down", cfg.intermediate, d)

        xt = x.reshape(t, d)
        logits = xt.astype(jnp.float32) @ wr                   # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)          # [T, k]
        # Mixtral renormalizes the selected gates to sum to one.
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

        # Capacity-bounded dispatch/combine tensors [T, E, C], built one
        # routing choice at a time so earlier choices fill slots first.
        dispatch = jnp.zeros((t, e, cap), jnp.float32)
        combine = jnp.zeros((t, e, cap), jnp.float32)
        fill = jnp.zeros((e,), jnp.int32)                       # slots used
        for j in range(k):
            onehot = jax.nn.one_hot(gate_idx[:, j], e)          # [T, E] f32
            pos = (jnp.cumsum(onehot, axis=0) - 1.0
                   + fill[None, :].astype(jnp.float32))         # queue slot
            keep = (pos < cap) * onehot                         # dropped past C
            slot = jax.nn.one_hot(pos.astype(jnp.int32), cap)   # [T, E, C]
            dispatch = dispatch + keep[..., None] * slot
            combine = combine + (keep * gate_vals[:, j:j + 1])[..., None] * slot
            fill = fill + jnp.sum(onehot, axis=0).astype(jnp.int32)

        # Switch aux loss: E * sum_e(token_frac_e * mean_prob_e) over the
        # top-1 assignment; z-loss controls router logit growth.
        top1 = jax.nn.one_hot(gate_idx[:, 0], e)
        token_frac = jnp.mean(top1, axis=0)
        prob_mean = jnp.mean(probs, axis=0)
        aux = cfg.aux_coef * e * jnp.sum(token_frac * prob_mean)
        z = cfg.router_z_coef * jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        self.sow("losses", "aux_loss", aux + z)
        self.sow("intermediates", "dispatch", dispatch)
        self.sow("intermediates", "combine", combine)

        xd = jnp.einsum("tec,td->ecd", dispatch,
                        xt.astype(jnp.float32)).astype(cfg.dtype)
        xd = nn.with_logical_constraint(xd, ("expert", None, "embed"))
        gate = jnp.einsum("ecd,edf->ecf", xd, w_gate.astype(cfg.dtype))
        up = jnp.einsum("ecd,edf->ecf", xd, w_up.astype(cfg.dtype))
        h = nn.silu(gate) * up
        h = nn.with_logical_constraint(h, ("expert", None, "mlp"))
        out_e = jnp.einsum("ecf,efd->ecd", h, w_down.astype(cfg.dtype))
        y = jnp.einsum("tec,ecd->td", combine,
                       out_e.astype(jnp.float32)).astype(cfg.dtype)
        return y.reshape(b, s, d)


class MoEBlock(nn.Module):
    cfg: MoEConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        hd = cfg.head_dim
        b, s, _ = x.shape
        h = RMSNorm(cfg, name="attn_norm")(x)
        q = dense(cfg.n_head * hd, ("embed", "heads"), cfg, "wq")(h)
        k = dense(cfg.n_kv_head * hd, ("embed", "heads"), cfg, "wk")(h)
        v = dense(cfg.n_kv_head * hd, ("embed", "heads"), cfg, "wv")(h)
        q = q.reshape(b, s, cfg.n_head, hd).transpose(0, 2, 1, 3)
        k = k.reshape(b, s, cfg.n_kv_head, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, cfg.n_kv_head, hd).transpose(0, 2, 1, 3)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        groups = cfg.n_head // cfg.n_kv_head
        kf = jnp.repeat(k, groups, axis=1)
        vf = jnp.repeat(v, groups, axis=1)
        if cfg.use_flash:
            from ray_tpu.ops.attention import flash_attention_sharded

            attn = flash_attention_sharded(
                q, kf, vf, nn.logical_to_mesh_axes(
                    ("batch", "heads", None, None)))
        else:
            from ray_tpu.ops.attention import mha_reference

            attn = mha_reference(q, kf, vf, causal=True)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_head * hd)
        x = x + dense(cfg.n_embd, ("heads", "embed"), cfg, "wo")(attn)
        h2 = RMSNorm(cfg, name="mlp_norm")(x)
        x = x + MoEMLP(cfg, name="moe")(h2)
        return nn.with_logical_constraint(x, ("batch", "seq", "embed"))


class MoE(nn.Module):
    config: MoEConfig

    def setup(self):
        cfg = self.config
        self.embed = self.param(
            "embed",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.vocab_size, cfg.n_embd), cfg.param_dtype)
        block = MoEBlock
        if cfg.remat:
            block = nn.remat(MoEBlock, static_argnums=())
        self.blocks = [block(cfg, name=f"layer_{i}")
                       for i in range(cfg.n_layer)]
        self.final_norm = RMSNorm(cfg, name="final_norm")
        self.lm_head = dense(cfg.vocab_size, ("embed", "vocab"), cfg,
                             "lm_head")

    def __call__(self, input_ids):
        cfg = self.config
        b, s = input_ids.shape
        x = self.embed.astype(cfg.dtype)[input_ids]
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        positions = jnp.arange(s)
        for blk in self.blocks:
            x = blk(x, positions)
        x = self.final_norm(x)
        logits = self.lm_head(x)
        return nn.with_logical_constraint(logits, ("batch", "seq", "vocab"))


def make_moe_train_step(model: MoE, optimizer, mesh=None,
                        donate: bool = True):
    """gpt2.make_train_step with an objective that adds the sown router
    losses (load balance + z) to the next-token cross-entropy (the
    displayed loss stays the plain CE so curves are comparable)."""
    from ray_tpu.models.gpt2 import make_train_step

    def loss_fn(p, batch):
        logits, aux_cols = model.apply(
            p, batch["input_ids"], mutable=["losses"])
        ce = next_token_loss(logits, batch["labels"])
        aux = sum(jax.tree.leaves(aux_cols.get("losses", {})),
                  jnp.float32(0.0))
        return ce + aux, ce

    return make_train_step(model, optimizer, mesh=mesh, donate=donate,
                           loss_fn=loss_fn)


def count_active_params(cfg: MoEConfig) -> int:
    """Parameters touched per token (dense weights + top_k experts)."""
    attn = cfg.n_embd * (cfg.n_head + 2 * cfg.n_kv_head) * cfg.head_dim \
        + cfg.n_head * cfg.head_dim * cfg.n_embd
    expert = 3 * cfg.n_embd * cfg.intermediate
    per_layer = attn + cfg.top_k * expert + cfg.n_embd * cfg.n_experts
    return cfg.n_layer * per_layer + 2 * cfg.vocab_size * cfg.n_embd


def flops_per_token(cfg: MoEConfig, seq_len: int) -> float:
    """Training FLOPs/token: 6x active params + attention term."""
    attn = 12 * cfg.n_layer * cfg.n_embd * seq_len
    return 6.0 * count_active_params(cfg) + 2.0 * attn
