"""GPT-2 in flax, sharding-annotated for dp/fsdp/tp/sp meshes.

The flagship model (BASELINE.json: "JaxTrainer — GPT-2-small"). Every
parameter carries logical axes (mapped to mesh axes by
`ray_tpu.parallel.sharding.DEFAULT_RULES`): embeddings shard vocab over tp
and embed over fsdp; attention/MLP matmuls are Megatron-style column-then-row
parallel over tp so each block needs one psum on tp; activations are
constrained to ("batch", "seq", ...) so dp/fsdp shard the batch and sp shards
the sequence (ring attention).

bfloat16 compute, float32 params/optimizer: MXU-friendly without loss-scale
bookkeeping.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import flash_attention_bse_sharded


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304          # padded to a multiple of 128 for the MXU
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_flash: bool = True
    use_ring: bool = False           # sequence parallelism (sp axis)
    remat: bool = False              # jax.checkpoint each block
    flash_block_q: int = 0   # 0 = pick_block_sizes auto heuristic
    flash_block_k: int = 0

    @staticmethod
    def small() -> "GPT2Config":
        return GPT2Config()

    @staticmethod
    def medium() -> "GPT2Config":
        return GPT2Config(n_embd=1024, n_layer=24, n_head=16)

    @staticmethod
    def tiny(seq: int = 128) -> "GPT2Config":
        return GPT2Config(vocab_size=512, n_positions=seq, n_embd=128,
                          n_layer=2, n_head=4)


def _dense(features: int, logical_axes: Tuple[str, ...], config: GPT2Config,
           name: str, use_bias: bool = True):
    return nn.Dense(
        features,
        use_bias=use_bias,
        dtype=config.dtype,
        param_dtype=config.param_dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(0.02), logical_axes),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros, (logical_axes[-1],)),
        name=name,
    )


class Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        head_dim = cfg.n_embd // cfg.n_head
        h = nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="ln_1")(x)
        # Column-parallel QKV (tp shards heads), row-parallel output proj.
        qkv = _dense(3 * cfg.n_embd, ("embed", "mlp"), cfg, "c_attn")(h)
        b, s, _ = qkv.shape
        if cfg.use_flash and not cfg.use_ring:
            # The kernels read c_attn's output where it lies and write what
            # c_proj reads: no split, no [b, heads, s, d] in between.
            attn = flash_attention_bse_sharded(
                qkv, head_dim,
                nn.logical_to_mesh_axes(("batch", None, "heads")),
                True, None, cfg.flash_block_q, cfg.flash_block_k)
        else:
            def heads(t):
                t = t.reshape(b, s, cfg.n_head, head_dim)
                t = nn.with_logical_constraint(
                    t, ("batch", "seq", "heads", None))
                return t.transpose(0, 2, 1, 3)  # [b, heads, seq, d]

            q, k, v = (heads(t) for t in jnp.split(qkv, 3, axis=-1))
            if cfg.use_ring:
                from ray_tpu.ops.ring_attention import ring_attention

                attn = ring_attention(q, k, v, axis_name="sp", causal=True)
            else:
                from ray_tpu.ops.attention import mha_reference

                attn = mha_reference(q, k, v, causal=True)
            attn = attn.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_embd)
        attn = _dense(cfg.n_embd, ("mlp", "embed"), cfg, "c_proj")(attn)
        if cfg.dropout:
            attn = nn.Dropout(cfg.dropout)(attn, deterministic=deterministic)
        x = x + attn
        h2 = nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                          name="ln_2")(x)
        h2 = _dense(4 * cfg.n_embd, ("embed", "mlp"), cfg, "c_fc")(h2)
        h2 = nn.gelu(h2)
        h2 = _dense(cfg.n_embd, ("mlp", "embed"), cfg, "mlp_proj")(h2)
        if cfg.dropout:
            h2 = nn.Dropout(cfg.dropout)(h2, deterministic=deterministic)
        x = x + h2
        return nn.with_logical_constraint(x, ("batch", "seq", "embed"))


class GPT2(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True):
        cfg = self.config
        b, s = input_ids.shape
        wte = self.param(
            "wte",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.vocab_size, cfg.n_embd), cfg.param_dtype)
        wpe = self.param(
            "wpe",
            nn.with_logical_partitioning(nn.initializers.normal(0.01),
                                         (None, "embed")),
            (cfg.n_positions, cfg.n_embd), cfg.param_dtype)
        x = wte.astype(cfg.dtype)[input_ids] + wpe.astype(cfg.dtype)[None, :s]
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        block = Block
        if cfg.remat:
            block = nn.remat(Block, static_argnums=(2,))
        for i in range(cfg.n_layer):
            x = block(cfg, name=f"h_{i}")(x, deterministic)
        x = nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="ln_f")(x)
        # Tied output head: logits over the sharded vocab.
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("bse,ve->bsv", x, wte.astype(cfg.dtype))
        return nn.with_logical_constraint(logits, ("batch", "seq", "vocab"))


# --------------------------------------------------------------------------- #
# Sharded init / loss / train-step factory
# --------------------------------------------------------------------------- #


def logical_param_specs(model: nn.Module, sample_shape: Tuple[int, int]):
    """Abstract-eval the model and return the logical PartitionSpec pytree."""
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros(sample_shape, jnp.int32)))
    return nn.get_partition_spec(abstract)


def mesh_shardings_for(model: nn.Module, mesh,
                       sample_shape: Tuple[int, int],
                       rules: Optional[Dict[str, Any]] = None):
    """NamedSharding pytree for the model params on `mesh`."""
    from ray_tpu.parallel.sharding import logical_axis_rules

    logical = logical_param_specs(model, sample_shape)
    rule_list = logical_axis_rules(rules, mesh_axes=mesh.axis_names)
    return nn.logical_to_mesh_sharding(logical, mesh, rule_list)


def init_sharded(model: nn.Module, mesh, sample_shape: Tuple[int, int],
                 seed: int = 0):
    """Initialize parameters directly into their mesh shardings (no host
    round-trip: init is jitted with out_shardings)."""
    shardings = mesh_shardings_for(model, mesh, sample_shape)

    def init_fn():
        return model.init(jax.random.PRNGKey(seed),
                          jnp.zeros(sample_shape, jnp.int32))

    return jax.jit(init_fn, out_shardings=shardings)()


def next_token_loss(logits, targets, ignore_index: int = -100):
    """Shifted cross-entropy in float32.

    nll = logsumexp(logits) - logits[target] rather than log_softmax +
    gather: identical math, but XLA only materializes the [b, s] reduce
    and gather instead of a normalized [b, s, vocab] float32 tensor —
    measured ~4% step-time win on v5e (the vocab dim dominates HBM
    traffic for small models)."""
    logits = logits[:, :-1].astype(jnp.float32)
    targets = targets[:, 1:]
    mask = targets != ignore_index
    targets = jnp.where(mask, targets, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


def make_train_step(model: nn.Module, optimizer, mesh=None,
                    donate: bool = True, loss_fn=None):
    """Jitted (params, opt_state, batch) -> (params, opt_state, loss).

    With a mesh: the step is traced with it as the context mesh
    (`parallel.mesh.MeshBound`), so the logical axis rules resolve the
    with_logical_constraint annotations against it; data enters sharded
    ("batch" over dp+fsdp, "seq" over sp); XLA places the psums over tp/sp
    on ICI.

    `loss_fn(params, batch) -> (objective, displayed_loss)` customizes the
    training objective (MoE adds router losses to the cross-entropy); the
    default is next-token cross-entropy for both.
    """
    from flax.linen import logical_axis_rules as flax_rules

    from ray_tpu.parallel.sharding import logical_axis_rules

    rules = logical_axis_rules(
        mesh_axes=mesh.axis_names if mesh is not None else None)

    if loss_fn is None:
        def loss_fn(p, batch):
            logits = model.apply(p, batch["input_ids"])
            with jax.named_scope("head_loss"):
                ce = next_token_loss(logits, batch["labels"])
            return ce, ce

    def step(params, opt_state, batch):
        (_, shown), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch), has_aux=True)(params)
        import optax

        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, shown

    def step_with_rules(params, opt_state, batch):
        with flax_rules(rules):
            return step(params, opt_state, batch)

    jitted = jax.jit(step_with_rules,
                     donate_argnums=(0, 1) if donate else ())
    if mesh is None:
        return jitted
    from ray_tpu.parallel.mesh import MeshBound

    return MeshBound(jitted, mesh)


def make_eval_step(model: nn.Module):
    @jax.jit
    def eval_step(params, batch):
        logits = model.apply(params, batch["input_ids"])
        return next_token_loss(logits, batch["labels"])

    return eval_step


def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def flops_per_token(cfg: GPT2Config, seq_len: int) -> float:
    """Approximate training FLOPs per token (6N + attention)."""
    n = (12 * cfg.n_layer * cfg.n_embd ** 2
         + cfg.vocab_size * cfg.n_embd)
    attn = 12 * cfg.n_layer * cfg.n_embd * seq_len
    return 6.0 * n + 2.0 * attn
