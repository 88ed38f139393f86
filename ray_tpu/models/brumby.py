"""Brumby (`model_type` `brumby`): a pre-norm decoder at the widths of a
14B GQA family whose attention was replaced, layer for layer, by POWER
RETENTION (Manifest AI, arXiv:2507.04239): no softmax, no keys or values
kept, one recurrent state per KV head that the group's query heads share.
Served through the engine (`inference/engine.py`): this file answers the
model contract (docs/INFERENCE.md) and nothing else is asked of it.

The equations (d = `head_dim`, degree 2, `rep` = query heads a KV head):

    h = RMSNorm(x)                                        eps rms_norm_eps
    q = h W_q [heads, d], k = h W_k, v = h W_v [kv_heads, d]     no bias
    q, k <- RMSNorm over d with a learned weight (q_norm, k_norm), then
            rotary over the whole head (rotate-half, rope_theta)
    gamma = logsigmoid(h W_g + b_g)           one a KV head, float32
    a_{t,u} = exp(c_t - c_u) (q_t[i] . k_u[j])^2 / d,  c the running gamma
    y_t[i] = sum_u a_{t,u} v_u[j] / (sum_u a_{t,u} + eps_r)      u <= t
    x = x + concat(y) W_o
    x = x + W_down(silu(W_gate n) * W_up n),  n = RMSNorm(x)
    logits = W_head RMSNorm(x_last)                         untied head

The retention runs in its STATE form from the first token (`ops/
power_retention.py`: S_t = e^{gamma_t} S_{t-1} + phi(k_t) v_t^T with the
key sum beside it). The published inference path also has an attention
form below a switch-over length; it computes the same function and is
left out, an implementation choice and not a departure in the mathematics.

What the published config does not hold (`assumed` in the benchmark's
configuration file, each with its origin): the degree (2), the gate (a
projection to one value a KV head with a bias, through logsigmoid: the
state is a function of K, V and the gate alone, so they share a head
count), the q/k norms and the full-width rotary (the family the widths
come from has both), `eps_r`, the float32 state.

What a slot holds (`paged_cache`): per layer the state [slots, kv_heads,
d/2 + 1, d, d] and the key sum [slots, kv_heads, d/2 + 1, d], float32, and
NOTHING ELSE: no keys, no values, no paged arena. `pageless_context` tells
the engine so (it then counts no block against admission and ships no
block table) and is the context bound, the published 32,768 positions. A
row of a step is held where its `write_mask` has no live position and
starts from zero state where its first live position is 0. A prefix of
blocks restores nothing (`prefix_restores` False).

Precision: parameters and matmul operands bf16 (the published dtype) into
f32 accumulation; norms, the rotary, the gate and the carried state are
f32; q, k and v enter the retention in the activations' dtype.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models._nn import (RowsOfTransposed, apply_rope, normal,
                                rms_norm)
from ray_tpu.models._served import PagedModel
from ray_tpu.ops import power_retention as retention


@dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 17408
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    retention_degree: int = 2          # assumed: the mechanism as published
    eps_r: float = 1e-6                # assumed: the normaliser's epsilon
    dtype: Any = jnp.bfloat16          # parameters and activations
    state_dtype: Any = jnp.float32     # the carried state and key sum

    def __post_init__(self):
        if self.retention_degree != 2:
            raise ValueError("ops/power_retention.py holds degree 2 only")

    @staticmethod
    def from_published(cfg: Dict[str, Any], **overrides) -> "BrumbyConfig":
        """From the keys of a published `config.json` and the `assumed`
        ones beside them (further keys are ignored)."""
        names = set(BrumbyConfig.__dataclass_fields__) - {"dtype",
                                                          "state_dtype"}
        kw = {k: cfg[k] for k in names if k in cfg}
        kw["rope_theta"] = float(kw.get("rope_theta", 1e6))
        return BrumbyConfig(**{**kw, **overrides})

    @staticmethod
    def tiny(**overrides) -> "BrumbyConfig":
        """A few thousand parameters, every mechanism present (CPU tests):
        4 query heads on 2 states of 16."""
        return BrumbyConfig(**{**dict(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            intermediate_size=48, max_position_embeddings=128,
            dtype=jnp.float32), **overrides})


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

GATE_KEEP = (0.95, 0.999)      # sigmoid(b_g) is drawn uniform in this range


def init_params(cfg: BrumbyConfig, key) -> Dict[str, Any]:
    """Seeded parameters: products normal(std 0.02) in `cfg.dtype`, norms
    one; the gate's projection normal(std 0.002) and its bias drawn so that
    sigmoid(b_g) is uniform in `GATE_KEEP`: the state then carries hundreds
    of positions (a gate near 0.5 would forget in a few tokens and hide a
    wrong state). Each leaf is made by one jitted draw in blocks."""
    e, dt = cfg.hidden_size, cfg.dtype
    qd, kvd = (cfg.num_attention_heads * cfg.head_dim,
               cfg.num_key_value_heads * cfg.head_dim)
    draw = jax.jit(normal, static_argnums=(1, 2, 3))
    keys = iter(jax.random.split(key, 2 + 9 * cfg.num_hidden_layers))
    params = {"embed": draw(next(keys), (cfg.vocab_size, e), dt),
              "lm_head": draw(next(keys), (e, cfg.vocab_size), dt),
              "final_norm": jnp.ones((e,), dt), "layers": []}
    for _ in range(cfg.num_hidden_layers):
        keep = jax.random.uniform(next(keys), (cfg.num_key_value_heads,),
                                  jnp.float32, *GATE_KEEP)
        params["layers"].append({
            "w_gate": draw(next(keys), (e, cfg.intermediate_size), dt),
            "w_up": draw(next(keys), (e, cfg.intermediate_size), dt),
            "w_down": draw(next(keys), (cfg.intermediate_size, e), dt),
            "wq": draw(next(keys), (e, qd), dt),
            "wk": draw(next(keys), (e, kvd), dt),
            "wv": draw(next(keys), (e, kvd), dt),
            "wo": draw(next(keys), (qd, e), dt),
            "wg": draw(next(keys), (e, cfg.num_key_value_heads), dt, 0.002),
            "bg": jnp.log(keep) - jnp.log1p(-keep),
            "q_norm": jnp.ones((cfg.head_dim,), dt),
            "k_norm": jnp.ones((cfg.head_dim,), dt),
            "input_norm": jnp.ones((e,), dt),
            "mlp_norm": jnp.ones((e,), dt),
        })
    return params


def published_weights(params) -> Tuple[Dict[str, Any], Any]:
    """(the top-level tensors, a function layer index -> that layer's
    tensors) under the published names and layouts: products [out, in].
    The program fuses nothing, so the map is names and a transpose."""
    top = {"model.embed_tokens.weight": params["embed"],
           "model.norm.weight": params["final_norm"],
           "lm_head.weight": RowsOfTransposed(params["lm_head"])}
    products = {"wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
                "wv": "self_attn.v_proj", "wg": "self_attn.g_proj",
                "wo": "self_attn.o_proj", "w_gate": "mlp.gate_proj",
                "w_up": "mlp.up_proj", "w_down": "mlp.down_proj"}
    vectors = {"bg": "self_attn.g_proj.bias",
               "q_norm": "self_attn.q_norm.weight",
               "k_norm": "self_attn.k_norm.weight",
               "input_norm": "input_layernorm.weight",
               "mlp_norm": "post_attention_layernorm.weight"}

    def layer(i: int) -> Dict[str, Any]:
        lp = params["layers"][i]
        out = {f"{pub}.weight": lp[ours].T for ours, pub in products.items()}
        out.update({pub: lp[ours] for ours, pub in vectors.items()})
        return out

    return top, layer


# --------------------------------------------------------------------------- #
# The block
# --------------------------------------------------------------------------- #


def _retention_layer(cfg, lp, h, state, sums, positions, slots, fresh, live):
    """Power retention on h [b, s, hidden] (normed). `state`, `sums` hold
    every slot's; `slots` [b] (None: row i is slot i, one token a row);
    `fresh` [b] the rows that start from zero state, `live` [b, s] the
    positions that advance it (a prefix of each row). Returns (out [b, s,
    hidden], state, sums)."""
    f32 = jnp.float32
    b, s, _ = h.shape
    hq, hk, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)

    def heads(t, n, norm):
        t = rms_norm(t.reshape(b, s, n, d), norm, cfg.rms_norm_eps)
        t = apply_rope(t.transpose(0, 2, 1, 3), positions, cfg.rope_theta)
        return t.transpose(0, 2, 1, 3).astype(cfg.dtype)

    with jax.named_scope("retention_proj"):
        q = heads(h @ lp["wq"], hq, lp["q_norm"])
        k = heads(h @ lp["wk"], hk, lp["k_norm"])
        v = (h @ lp["wv"]).reshape(b, s, hk, d)
    with jax.named_scope("retention_gate"):
        log_g = jax.nn.log_sigmoid(
            jnp.dot(h, lp["wg"], preferred_element_type=f32)
            + lp["bg"].astype(f32))
    with jax.named_scope("retention"):
        if slots is None:
            y, state, sums = retention.retention_step(
                q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], state, sums, fresh,
                live[:, 0], cfg.eps_r)
            y = y[:, None]
        else:
            y, state, sums = retention.retention_chunk_fwd(
                q, k, v, log_g, state, sums, slots, fresh, live, cfg.eps_r)
    with jax.named_scope("retention_out"):
        out = y.astype(cfg.dtype).reshape(b, s, hq * d) @ lp["wo"]
    return out, state, sums


def _block(cfg, lp, x, state, sums, positions, slots, fresh, live):
    dt = cfg.dtype
    h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps).astype(dt)
    out, state, sums = _retention_layer(cfg, lp, h, state, sums, positions,
                                        slots, fresh, live)
    x = x + out
    with jax.named_scope("mlp"):
        n = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps).astype(dt)
        x = x + (jax.nn.silu(n @ lp["w_gate"]) * (n @ lp["w_up"])) \
            @ lp["w_down"]
    return x, state, sums


class Brumby(PagedModel):
    """The model the engine is handed: its configuration and what of the
    model contract differs from `PagedModel`'s defaults. Parameters are a
    plain pytree (`init_params`)."""

    # There are no blocks: nothing a prefix could restore.
    prefix_restores = False

    def __init__(self, config: BrumbyConfig):
        self.config = config

    def init(self, key):
        return init_params(self.config, key)

    @property
    def pageless_context(self) -> int:
        """The cache has no paged part; a sequence may reach this many
        positions (the published bound: the state's size does not grow)."""
        return self.config.max_position_embeddings

    @property
    def slot_state_bytes(self) -> int:
        """What one slot holds, all layers: the state and the key sum."""
        cfg = self.config
        state, sums = retention.state_shapes(1, cfg.num_key_value_heads,
                                             cfg.head_dim)
        return cfg.num_hidden_layers * (math.prod(state) + math.prod(sums)) \
            * jnp.dtype(cfg.state_dtype).itemsize

    def paged_cache(self, num_blocks: int, block_size: int, mesh=None,
                    batch_slots: Optional[int] = None):
        """Per layer the state and the key sum of every batch slot
        (`ops/power_retention.py`'s layout) and no arena: `num_blocks` and
        `block_size` are not looked at."""
        if mesh is not None:
            raise ValueError("Brumby serves on one device (tp = 1)")
        if not batch_slots:
            raise ValueError("Brumby's cache is state per batch slot: "
                             "paged_cache needs batch_slots")
        cfg = self.config
        state, sums = retention.state_shapes(
            batch_slots, cfg.num_key_value_heads, cfg.head_dim)
        layers = range(cfg.num_hidden_layers)
        return {"state": [jnp.zeros(state, cfg.state_dtype) for _ in layers],
                "sums": [jnp.zeros(sums, cfg.state_dtype) for _ in layers]}

    def paged_step(self, params, ids, cache, block_tables, row_pos,
                   write_mask, adapters=None, slots=None, last_idx=None):
        """One step: ids [b, s] at positions row_pos[b] + arange(s).
        `slots` [b] is each row's batch slot; None means row i is slot i
        and b is every slot (the decode step). `block_tables` is not looked
        at (the engine hands a zero-width one). Returns (logits [b, s,
        vocab], or [b, vocab] at `last_idx` [b]; the cache)."""
        if adapters is not None:
            raise ValueError("Brumby has no adapter banks")
        cfg = self.config
        b, s = ids.shape
        if slots is None and (s != 1 or b != cache["state"][0].shape[0]):
            raise ValueError("a step that names no slots is one token of "
                             "every slot")
        positions = row_pos[:, None] + jnp.arange(s)[None, :]
        fresh, live = self.state_rows(row_pos, write_mask)
        x = params["embed"][ids]
        states, sums = [], []
        for i, lp in enumerate(params["layers"]):
            x, state, total = _block(cfg, lp, x, cache["state"][i],
                                     cache["sums"][i], positions, slots,
                                     fresh, live)
            states.append(state)
            sums.append(total)
        if last_idx is not None:
            x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        logits = jnp.dot(x.astype(cfg.dtype), params["lm_head"],
                         preferred_element_type=jnp.float32)
        return logits, {"state": states, "sums": sums}

    def state_rows(self, row_pos, write_mask):
        """(fresh [b], live [b, s]): the rows that start from zero state
        and the positions that advance it. Hold before reset: a row with
        no live position keeps its state whatever its position says (the
        engine hands idle rows position 0)."""
        return write_mask[:, 0] & (row_pos == 0), write_mask
