"""`model_type` `deepseek_v3`: a pre-norm decoder with multi-head LATENT
attention (MLA) and, after `first_k_dense_replace` dense layers, a routed
expert layer (sigmoid scores, a selection bias, shared experts). Served
through the engine (`inference/engine.py`): this file answers the model
contract (docs/INFERENCE.md) and nothing else is asked of it.

The equations (H heads, n = `qk_nope_head_dim`, r = `qk_rope_head_dim`, v =
`v_head_dim`, L = `kv_lora_rank`; `q_lora_rank` null; h the RMS-normed input
of a sub-block, eps `rms_norm_eps`; residuals pre-norm):

    q = h W_q -> H x [q_nope n | q_rope r]
    [c | k_r] = h W_kva -> L + r;  c <- RMSNorm_L(c) with a learned weight
    rotary (rope_theta, no scaling) on q_rope and on k_r, ONE head shared
    [k_nope | v]_head = c W_kvb                      H x (n + v)
    score = (q_nope . k_nope + q_rope . k_r) / sqrt(n + r), causal softmax
    y = (sum p v) W_o

    layer < first_k_dense_replace:  W_d (silu(h W_g) * (h W_u))
    else: s = sigmoid(h W_r) in f32; E = top-k of (s + b), b the selection
          bias; w_e = routed_scaling_factor * s_e / (sum_{e in E} s_e +
          1e-20); y = sum_{e in E} w_e expert_e(h) + shared(h)
    logits = W_head RMSNorm(x)                              untied head

What is run is the ABSORBED form, for every step (a decode step and a
prefill chunk alike, and the two as ONE execution where the engine has a
chunk to run while rows decode: `paged_step_with_chunk`, the contract's
optional fused step; `ops/latent_attention.py`): with W_kvb split a head
into W^K [n, L] and W^V [L, v],

    q_lat = q_nope W^K;  score = ([q_lat | q_rope] . [c | k_r]) / sqrt(n + r)
    o_lat = sum p c;     o = o_lat W^V

so that a token's cache row is `[c (normed) | k_r (rotated)]`, L + r values
(padded with zero lanes to `latent_page_width`, a multiple of 128), ONE
arena a layer of [blocks, block, width] with no heads and no separate V,
and a slot's H heads read each page once for both products. The expanded
form over a long cached prefix would expand k_nope and v of every cached
token a chunk (2 L H (n + v) FLOP a token against the absorbed form's 2 H
(L + r + L) a query-token pair): at 256-token chunks over 8k tokens the two
are within a third of each other, and one kernel serves both.

The rope lanes: the published code takes q_rope and k_r INTERLEAVED
(`rope_interleave`: pairs (x_2i, x_2i+1)), de-interleaves them to the
half-split layout and rotates halves. This program's W_q and W_kva hold
their rope columns ALREADY in the half-split order and it rotates halves
directly: the same function under one fixed permutation of the r lanes
applied to q and k alike, which no score sees. The cache holds k_r
half-split. `published_weights` hands the columns back interleaved.

Counters: the expert layers' loads (`held_expert_forward`'s `counts`) are
added into `cache["moe"]` on the device, decode steps and steps that held
a chunk apart (a chunk alone, or a decode step with a chunk aboard,
counted WHOLE: its experts are read once for all its rows), and read when
`stats()` is asked (`cache_counters`, `counter_stats`: finding (f) of
docs/INFERENCE.md). Idle rows (batch and chunk padding) are routed to no
expert and count for nothing. Beside them
`cache["latent_walk"]` counts what the attention kernel's tile rule walks
(`ops/latent_attention.py tile_walk`, asked once a step: every layer's call
has the step's positions): grid steps, those that copy anything, the KV
chunks they copy and of those the ones that run the masked body, decode
steps and chunks apart; `stats()["latent_walk"]`. Beside the latent rows
the cache keeps the ROUTING RECORD, `cache["routing"]` f32
[2k, blocks x block]: the FIRST expert layer's chosen experts (k rows, as
floats) and their gates (k rows) of every cached token, written by the step
that routed it at the token's own cache location, so that what a served
step chose can be read afterwards block by block like the latent rows (a
comparison with a reference; "which experts did this prompt take"). 48 B a
token, 32 or 256 columns written a step.

Precision: parameters and matmul operands bf16 into f32 accumulation; the
router, the norms, the rotary and the softmax f32; the selection bias f32;
the cache bf16.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# kanana2_controls.py replaces this module's _rms_norm (and latent_rows).
from ray_tpu.models._nn import (RowsOfTransposed, cache_locations, normal,
                                rms_norm as _rms_norm)
from ray_tpu.models._served import PagedModel
from ray_tpu.ops import held_experts as moe
from ray_tpu.ops.latent_attention import (WALK_COUNTS, latent_attention,
                                          tile_walk)

_LANES = 128
KINDS = ("decode", "prefill")


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 6144
    first_k_dense_replace: int = 1
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 768
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.448
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    dtype: Any = jnp.bfloat16          # parameters, activations, the cache

    @staticmethod
    def from_published(cfg: Dict[str, Any], **overrides
                       ) -> "DeepseekV3Config":
        """From the keys of a published `config.json` (further keys are
        ignored). What this file does not hold is refused, not dropped."""
        if cfg.get("q_lora_rank") is not None:
            raise ValueError("q_lora_rank: only the full-rank query is held")
        if cfg.get("rope_scaling") is not None:
            raise ValueError("rope_scaling: only the plain rotary is held")
        if (cfg.get("n_group", 1), cfg.get("topk_group", 1)) != (1, 1):
            raise ValueError("n_group / topk_group: no group limit is held")
        if cfg.get("scoring_func", "sigmoid") != "sigmoid" \
                or not cfg.get("norm_topk_prob", True):
            raise ValueError("only sigmoid scores renormalised are held")
        names = set(DeepseekV3Config.__dataclass_fields__) - {"dtype"}
        kw = {k: cfg[k] for k in names if k in cfg}
        kw["rope_theta"] = float(kw.get("rope_theta", 1e6))
        return DeepseekV3Config(**{**kw, **overrides})

    @staticmethod
    def tiny(**overrides) -> "DeepseekV3Config":
        """A few tens of thousands of parameters, every mechanism present
        (CPU tests): 4 heads, a latent of 128 beside a rope key of 32 (a
        page of 256 lanes, which the kernel takes under the interpreter),
        one dense layer and two of 8 experts, top-2, one shared."""
        return DeepseekV3Config(**{**dict(
            vocab_size=96, hidden_size=32, num_hidden_layers=3,
            num_attention_heads=4, kv_lora_rank=128, qk_nope_head_dim=16,
            qk_rope_head_dim=32, v_head_dim=16, intermediate_size=48,
            first_k_dense_replace=1, n_routed_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=16,
            n_shared_experts=1, max_position_embeddings=256,
            dtype=jnp.float32), **overrides})

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """What a token's cache row holds: the latent and the rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_page_width(self) -> int:
        """The row padded to whole lane tiles (512 + 64 -> 640)."""
        return -(-self.latent_row // _LANES) * _LANES

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

BIAS_STD = 0.1        # the selection bias: seeded normal of this spread


def init_params(cfg: DeepseekV3Config, key) -> Dict[str, Any]:
    """Seeded parameters: products normal(std 0.02) in `cfg.dtype`, norms
    one, the selection bias normal(std `BIAS_STD`) in float32: the seeded
    router's logits spread ~0.02 x sqrt(hidden) (0.9 at 2,048), so its
    sigmoid scores spread ~0.2 and a bias of a tenth is a size that
    matters (zeroing it changes at least one chosen expert of most tokens)
    without deciding the choice alone; the gates never see it. Each leaf
    is one jitted draw in blocks."""
    e, dt, heads = cfg.hidden_size, cfg.dtype, cfg.num_attention_heads
    n, r, v, lat = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                    cfg.v_head_dim, cfg.kv_lora_rank)
    experts, f = cfg.n_routed_experts, cfg.moe_intermediate_size
    draw = jax.jit(normal, static_argnums=(1, 2, 3))
    keys = iter(jax.random.split(key, 2 + 12 * cfg.num_hidden_layers))
    params = {"embed": draw(next(keys), (cfg.vocab_size, e), dt),
              "lm_head": draw(next(keys), (e, cfg.vocab_size), dt),
              "final_norm": jnp.ones((e,), dt), "layers": []}
    for i in range(cfg.num_hidden_layers):
        lp = {"wq": draw(next(keys), (e, heads * (n + r)), dt),
              "wkva": draw(next(keys), (e, lat + r), dt),
              "kv_norm": jnp.ones((lat,), dt),
              # W_kvb a head: W^K [n, L] into the latent, W^V [L, v] out.
              "w_uk": draw(next(keys), (heads, n, lat), dt),
              "w_uv": draw(next(keys), (heads, lat, v), dt),
              "wo": draw(next(keys), (heads * v, e), dt),
              "input_norm": jnp.ones((e,), dt),
              "mlp_norm": jnp.ones((e,), dt)}
        if i < cfg.first_k_dense_replace:
            lp.update(
                w_gate=draw(next(keys), (e, cfg.intermediate_size), dt),
                w_up=draw(next(keys), (e, cfg.intermediate_size), dt),
                w_down=draw(next(keys), (cfg.intermediate_size, e), dt))
        else:
            lp.update(
                router=draw(next(keys), (e, experts), dt),
                router_bias=jax.random.normal(
                    next(keys), (experts,), jnp.float32) * BIAS_STD,
                # an expert's gate projection beside its up projection
                w_gate_up=draw(next(keys), (experts, e, 2 * f), dt),
                w_down=draw(next(keys), (experts, f, e), dt),
                shared_gate=draw(next(keys), (e, cfg.shared_width), dt),
                shared_up=draw(next(keys), (e, cfg.shared_width), dt),
                shared_down=draw(next(keys), (cfg.shared_width, e), dt))
        params["layers"].append(lp)
    return params


def rope_interleave_order(r: int) -> np.ndarray:
    """`order[j]` = the interleaved (published) lane that half-split lane j
    holds: evens then odds."""
    return np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2)])


def published_weights(cfg: DeepseekV3Config, params
                      ) -> Tuple[Dict[str, Any], Any]:
    """(the top-level tensors, a function layer index -> that layer's
    tensors) under the published names and layouts: products [out, in],
    `kv_b_proj` [H (n + v), L] a head's k_nope rows above its v rows, the
    rope columns of `q_proj` and `kv_a_proj_with_mqa` INTERLEAVED,
    `mlp.experts.<e>.*` an expert at a time out of the stacked arrays."""
    heads, n, r = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                   cfg.qk_rope_head_dim)
    lat = cfg.kv_lora_rank
    back = np.argsort(rope_interleave_order(r))   # published lane -> ours
    top = {"model.embed_tokens.weight": params["embed"],
           "model.norm.weight": params["final_norm"],
           "lm_head.weight": RowsOfTransposed(params["lm_head"])}

    def layer(i: int) -> Dict[str, Any]:
        lp = params["layers"][i]
        wq = lp["wq"].reshape(-1, heads, n + r)
        wq = jnp.concatenate([wq[..., :n], wq[..., n:][..., back]], axis=-1)
        wkva = jnp.concatenate([lp["wkva"][:, :lat],
                                lp["wkva"][:, lat:][:, back]], axis=-1)
        kvb = jnp.concatenate([jnp.swapaxes(lp["w_uk"], 1, 2),
                               lp["w_uv"]], axis=-1)        # [H, L, n + v]
        out = {
            "self_attn.q_proj.weight": wq.reshape(-1, heads * (n + r)).T,
            "self_attn.kv_a_proj_with_mqa.weight": wkva.T,
            "self_attn.kv_a_layernorm.weight": lp["kv_norm"],
            "self_attn.kv_b_proj.weight":
                kvb.transpose(0, 2, 1).reshape(-1, lat),
            "self_attn.o_proj.weight": lp["wo"].T,
            "input_layernorm.weight": lp["input_norm"],
            "post_attention_layernorm.weight": lp["mlp_norm"]}
        if "router" not in lp:
            out.update({"mlp.gate_proj.weight": lp["w_gate"].T,
                        "mlp.up_proj.weight": lp["w_up"].T,
                        "mlp.down_proj.weight": lp["w_down"].T})
            return out
        out.update({
            "mlp.gate.weight": lp["router"].T,
            "mlp.gate.e_score_correction_bias": lp["router_bias"],
            # [experts, in, 2F] and [experts, F, out]: expert e's three
            # published products are w_gate_up[e][:, :F].T,
            # w_gate_up[e][:, F:].T and w_down[e].T
            "mlp.experts.gate_up": lp["w_gate_up"],
            "mlp.experts.down": lp["w_down"],
            "mlp.shared_experts.gate_proj.weight": lp["shared_gate"].T,
            "mlp.shared_experts.up_proj.weight": lp["shared_up"].T,
            "mlp.shared_experts.down_proj.weight": lp["shared_down"].T})
        return out

    return top, layer


# --------------------------------------------------------------------------- #
# The block
# --------------------------------------------------------------------------- #


def _rope(x, positions, theta: float):
    """Rotate the two HALVES of x [b, s, heads, r] at positions [b, s];
    float32 in and out."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def latent_rows(cfg, lp, h, positions):
    """A token's cache row [b, s, latent_page_width]: the normed latent,
    the rotated rope key, zero lanes."""
    f32 = jnp.float32
    lat = cfg.kv_lora_rank
    ckr = jnp.dot(h, lp["wkva"], preferred_element_type=f32)
    c = _rms_norm(ckr[..., :lat], lp["kv_norm"], cfg.rms_norm_eps)
    k_r = _rope(ckr[..., None, lat:], positions, cfg.rope_theta)[:, :, 0]
    pad = jnp.zeros(c.shape[:-1] + (cfg.latent_page_width - cfg.latent_row,),
                    f32)
    return jnp.concatenate([c, k_r, pad], axis=-1).astype(cfg.dtype)


def absorbed_query(cfg, lp, h, positions):
    """[q_lat | q_rope] [b, s, H, L + r] in the activations' dtype."""
    f32 = jnp.float32
    b, s, _ = h.shape
    n = cfg.qk_nope_head_dim
    q = jnp.dot(h, lp["wq"], preferred_element_type=f32).reshape(
        b, s, cfg.num_attention_heads, cfg.qk_head_dim)
    q_rope = _rope(q[..., n:], positions, cfg.rope_theta)
    q_lat = jnp.einsum("bshn,hnl->bshl", q[..., :n].astype(cfg.dtype),
                       lp["w_uk"], preferred_element_type=f32)
    return jnp.concatenate([q_lat, q_rope], axis=-1).astype(cfg.dtype)


def _a_group_at_a_time(groups, fn, *arrays):
    """`fn(block_tables, *arrays)` for each row group. One group: the
    arrays are its own [b, s, ..]. Several: they are [1, T, ..], the
    groups' rows laid end to end, and are cut apart for `fn` and its
    results laid back the same way."""
    if len(groups) == 1:
        return fn(groups[0][0], *arrays)
    outs, at = [], 0
    for block_tables, s in groups:
        b = block_tables.shape[0]
        out = fn(block_tables, *(a[0, at:at + b * s].reshape(
            (b, s) + a.shape[2:]) for a in arrays))
        outs.append(out.reshape((1, b * s) + out.shape[2:]))
        at += b * s
    return jnp.concatenate(outs, axis=1)


def _attention(cfg, lp, h, arena, groups, positions, write_mask, flat):
    b, s, _ = h.shape
    nb, bsz, width = arena.shape
    with jax.named_scope("mla_q"):
        q = absorbed_query(cfg, lp, h, positions)
    with jax.named_scope("mla_kv"):
        rows = latent_rows(cfg, lp, h, positions)
        arena = arena.reshape(nb * bsz, width).at[flat].set(
            rows.reshape(-1, width)).reshape(nb, bsz, width)
    with jax.named_scope("latent_attn"):
        # Every group's rows are in the arena by now; each group is one
        # call of the kernel at its own shape.
        o_lat = _a_group_at_a_time(
            groups, lambda block_tables, q, positions, live:
            latent_attention(
                q, arena, block_tables, positions, live,
                latent=cfg.kv_lora_rank,
                scale=1.0 / math.sqrt(cfg.qk_head_dim)),
            q, positions, write_mask)
    with jax.named_scope("mla_out"):
        o = jnp.einsum("bshl,hlv->bshv", o_lat, lp["w_uv"],
                       preferred_element_type=jnp.float32).astype(cfg.dtype)
        return o.reshape(b, s, -1) @ lp["wo"], arena


def _swiglu(n, w_gate, w_up, w_down):
    return (jax.nn.silu(n @ w_gate) * (n @ w_up)) @ w_down


def routed_experts(cfg, lp, n, live, held=None):
    """The routed part of an expert layer on n [T, hidden] (normed):
    (y [T, hidden] f32, counts, routing). `live` [T] marks the rows that
    are real tokens; the others are routed to no expert. `held` = (first,
    count) computes that share alone (all of them by default). `routing`
    f32 [2k, T] is what the experts were HANDED: the chosen experts above
    their gates (the routing record's columns)."""
    experts = cfg.n_routed_experts
    with jax.named_scope("moe_route"):
        _, gates, index = moe.route_sigmoid(
            n, lp["router"], lp["router_bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor)
        index = jnp.where(live[:, None], index, experts)
        routing = jnp.concatenate(
            [index.astype(jnp.float32), gates], axis=-1).T
    first, count = held or (0, experts)
    with jax.named_scope("moe_experts"):
        y, counts = moe.held_expert_forward(
            n, gates, index, lp["w_gate_up"][first:first + count],
            lp["w_down"][first:first + count], (first, count), experts)
    return y, counts, routing


def _block(cfg, lp, x, arena, groups, positions, write_mask, flat):
    """One block on x [b, s, hidden] of one row group, or [1, T, hidden] of
    several laid end to end (`groups`: each one's (block tables, s)): (x
    after it, the arena, an expert layer's (counts, routing) or None).
    Everything but the attention is a token's own and reads its weights
    once for all T."""
    dt = cfg.dtype
    h = _rms_norm(x, lp["input_norm"], cfg.rms_norm_eps).astype(dt)
    out, arena = _attention(cfg, lp, h, arena, groups, positions,
                            write_mask, flat)
    x = x + out
    n = _rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps).astype(dt)
    if "router" not in lp:
        with jax.named_scope("mlp"):
            return x + _swiglu(n, lp["w_gate"], lp["w_up"],
                               lp["w_down"]), arena, None
    b, s, e = n.shape
    rows = n.reshape(b * s, e)
    routed, counts, routing = routed_experts(cfg, lp, rows,
                                             write_mask.reshape(-1))
    with jax.named_scope("moe_shared"):
        shared = _swiglu(rows, lp["shared_gate"], lp["shared_up"],
                         lp["shared_down"])
    y = (routed + shared.astype(jnp.float32)).astype(dt)
    return x + y.reshape(b, s, e), arena, (counts, routing)


def _count(moe_counters, kind: int, per_layer):
    """The counters with one step's counts (a list, an expert layer each)
    added under `kind` (0 a decode step, 1 a prefill chunk)."""
    load = jnp.stack([c["load"] for c in per_layer])          # [L, E]
    total = jnp.sum(load, axis=1)
    mean = jnp.maximum(total, 1).astype(jnp.float32) / load.shape[1]
    add = {"steps": jnp.int32(1),
           "assigned": jnp.stack([c["assigned"] for c in per_layer]),
           "placed": jnp.stack([c["placed"] for c in per_layer]),
           "tiles": jnp.stack([c["tiles"] for c in per_layer]),
           "drew": jnp.sum(load > 0, axis=1, dtype=jnp.int32),
           "max_over_mean": jnp.max(load, axis=1).astype(jnp.float32) / mean,
           "load": load}
    return {k: v.at[kind].add(add[k]) for k, v in moe_counters.items()}


class DeepseekV3(PagedModel):
    """The model the engine is handed: its configuration and what of the
    model contract differs from `PagedModel`'s defaults (a prefix of latent
    blocks alone restores a sequence; no slot state). Parameters are a
    plain pytree (`init_params`)."""

    def __init__(self, config: DeepseekV3Config):
        self.config = config

    def init(self, key):
        return init_params(self.config, key)

    def paged_cache(self, num_blocks: int, block_size: int, mesh=None,
                    batch_slots: Optional[int] = None):
        """ONE arena a layer, [num_blocks, block_size, latent_page_width]
        (block 0 the trash block), the routing record (module docstring)
        and the expert layers' counters."""
        if mesh is not None:
            raise ValueError("DeepseekV3 serves on one device (tp = 1)")
        cfg = self.config
        layers, experts = cfg.n_moe_layers, cfg.n_routed_experts
        shape = (num_blocks, block_size, cfg.latent_page_width)
        i32 = jnp.int32
        return {
            "latent": [jnp.zeros(shape, cfg.dtype)
                       for _ in range(cfg.num_hidden_layers)],
            "routing": jnp.zeros((2 * cfg.num_experts_per_tok,
                                  num_blocks * block_size), jnp.float32),
            "moe": {"steps": jnp.zeros((2,), i32),
                    "assigned": jnp.zeros((2, layers), i32),
                    "placed": jnp.zeros((2, layers), i32),
                    "tiles": jnp.zeros((2, layers), i32),
                    "drew": jnp.zeros((2, layers), i32),
                    "max_over_mean": jnp.zeros((2, layers), jnp.float32),
                    "load": jnp.zeros((2, layers, experts), i32)},
            "latent_walk": {k: jnp.zeros((2,), i32) for k in WALK_COUNTS}}

    def paged_step(self, params, ids, cache, block_tables, row_pos,
                   write_mask, adapters=None, slots=None, last_idx=None):
        """One step: ids [b, s] at positions row_pos[b] + arange(s).
        Returns (logits [b, s, vocab], or [b, vocab] at `last_idx` [b];
        the cache). `slots` is not looked at: nothing is kept per slot."""
        if adapters is not None:
            raise ValueError("DeepseekV3 has no adapter banks")

        def read(x):
            if last_idx is None:
                return x
            return jnp.take_along_axis(x, last_idx[:, None, None],
                                       axis=1)[:, 0]

        return self._step(
            params, cache, [(ids, block_tables, row_pos, write_mask)], read)

    def paged_step_with_chunk(self, params, tokens, chunk_ids, cache,
                              block_tables, row_pos, write_mask, chunk_bt,
                              chunk_pos, chunk_wmask, chunk_slot, last_idx):
        """A decode step with one sequence's prefill chunk aboard (the
        contract's optional answer): `paged_step` of tokens [b, 1] and
        `paged_step` of chunk_ids [1, c] at `last_idx` [1] as ONE
        execution, in which every weight is read once for the b + c rows
        and only the attention is two calls. The engine masks the chunk's
        own slot among the decode rows. Returns (the decode rows' logits
        [b, vocab], the chunk's [1, vocab], the cache). `chunk_slot` is not
        looked at: nothing is kept per slot."""
        b = tokens.shape[0]
        logits, cache = self._step(
            params, cache,
            [(tokens, block_tables, row_pos, write_mask),
             (chunk_ids, chunk_bt, chunk_pos, chunk_wmask)],
            lambda x: jnp.concatenate([x[0, :b], x[0, b + last_idx]]))
        return logits[:b], logits[b:], cache

    def _step(self, params, cache, groups, read):
        """The one body of a step over ROW GROUPS, each (ids [b, s],
        block_tables, row_pos, write_mask): (the logits of the rows `read`
        picks from the last block's x, the cache). One group is a decode
        step or a prefill chunk, x [b, s, hidden]. Several are laid end to
        end, x [1, T, hidden], through everything that is a token's own
        (the embedding, the norms, the products, the expert layer: T rows
        against one read of the weights) and cut apart for the attention
        alone (`_a_group_at_a_time`). Counted whole under kind 1, "a step
        that held a chunk", where any group is longer than one token."""
        cfg = self.config
        bsz = cache["latent"][0].shape[1]
        placed = []              # a group's (positions [b, s], flat [b * s])
        for ids, block_tables, row_pos, write_mask in groups:
            positions = row_pos[:, None] + jnp.arange(ids.shape[1])[None, :]
            placed.append((positions, cache_locations(
                block_tables, positions, write_mask, bsz)))
        if len(groups) > 1:
            ids, positions, write_mask = (
                jnp.concatenate([a.reshape(1, -1) for a in arrays], axis=1)
                for arrays in ([g[0] for g in groups],
                               [p for p, _ in placed],
                               [g[3] for g in groups]))
            flat = jnp.concatenate([f for _, f in placed])
        else:
            (ids, _, _, write_mask), (positions, flat) = groups[0], placed[0]
        shapes = [(g[1], g[0].shape[1]) for g in groups]
        x = params["embed"][ids]
        arenas, counts, record = [], [], cache["routing"]
        for lp, arena in zip(params["layers"], cache["latent"]):
            x, arena, routed = _block(cfg, lp, x, arena, shapes, positions,
                                      write_mask, flat)
            arenas.append(arena)
            if routed is None:
                continue
            if not counts:                     # the first expert layer
                with jax.named_scope("moe_record"):
                    record = record.at[:, flat].set(routed[1])
            counts.append(routed[0])
        x = _rms_norm(read(x), params["final_norm"], cfg.rms_norm_eps)
        logits = jnp.dot(x.astype(cfg.dtype), params["lm_head"],
                         preferred_element_type=jnp.float32)
        kind = int(any(s > 1 for _, s in shapes))
        counters = cache["moe"]
        if counts:
            counters = _count(counters, kind, counts)
        walk = cache["latent_walk"]
        for (_, block_tables, _, live), (at, _) in zip(groups, placed):
            walked = tile_walk(
                at, live, heads=cfg.num_attention_heads, block_size=bsz,
                max_ctx=block_tables.shape[1] * bsz, dtype=cfg.dtype)[2]
            walk = {k: v.at[kind].add(walked[k]) for k, v in walk.items()}
        return logits, {"latent": arenas, "routing": record,
                        "moe": counters, "latent_walk": walk}

    # ------------------------------------------------- counters (finding f)

    def cache_counters(self, cache):
        """The part of the cache the host may read when `stats()` is
        asked: small device arrays, cumulative since the cache was made."""
        return {"moe": cache["moe"], "latent_walk": cache["latent_walk"]}

    def counter_stats(self, host) -> Dict[str, Any]:
        """`stats()["moe"]` and `stats()["latent_walk"]` from a host copy
        of `cache_counters`: cumulative sums over the expert layers for
        decode steps and prefill chunks apart (the difference of two reads
        is a window's), the per-step per-layer means over the cache's life,
        and every expert's cumulative load; and what an attention call of
        each kind of step walked (`WALK_COUNTS`: the same for each of a
        step's layers, counted once a step), cumulative."""
        cfg = self.config
        walk = {kind: {name: int(host["latent_walk"][name][k])
                       for name in WALK_COUNTS}
                for k, kind in enumerate(KINDS)}
        host = host["moe"]
        out: Dict[str, Any] = {"layers": cfg.n_moe_layers,
                               "experts": cfg.n_routed_experts,
                               "top_k": cfg.num_experts_per_tok}
        for k, kind in enumerate(KINDS):
            steps = int(host["steps"][k])
            sums = {name: float(np.sum(host[name][k]))
                    for name in ("assigned", "placed", "tiles", "drew",
                                 "max_over_mean")}
            calls = max(1, steps * cfg.n_moe_layers)
            out[kind] = {
                "steps": steps, **sums,
                "assignments_per_step": sums["assigned"] / calls,
                "experts_drawn_per_step": sums["drew"] / calls,
                "load_max_over_mean": sums["max_over_mean"] / calls}
        out["load"] = [int(v) for v in np.sum(host["load"], axis=(0, 1))]
        return {"moe": out, "latent_walk": walk}
