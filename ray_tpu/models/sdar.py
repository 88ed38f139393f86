"""SDAR (`model_type` `sdar_moe`): a decoder that is autoregressive ACROSS
blocks of `block_length` positions and a masked discrete-DIFFUSION model
INSIDE one (JetLM, "SDAR: A Synergistic Diffusion-AutoRegression Paradigm
for Scalable Sequence Generation", 2025-10, and its published `generate.py`
/ `modeling_sdar_moe.py`). Served through the engine
(`inference/engine.py`): this file answers the model contract
(docs/INFERENCE.md), with the contract's optional `decode_block`, and
nothing else is asked of it.

The equations, per layer (x the residual stream, N RMSNorm with a learned
weight, eps `rms_norm_eps`; L = `block_length`; vis(p) = (p // L + 1) L - 1):

    h   = N_1(x)
    q   = RoPE(N_q(W_q h));  k = RoPE(N_k(W_k h));  v = W_v h
          `num_attention_heads` query heads on `num_key_value_heads` KV
          heads of `head_dim`, no bias; N_q, N_k over the `head_dim` of each
          head; rotate-half rotary over all of it at `rope_theta`
    a_p = sum_{j <= vis(p)} softmax_j(q_p . k_j / sqrt(head_dim)) v_j
          BLOCK-causal: p sees its whole block and every block before it
    x   = x + W_o a
    n   = N_2(x)
    s   = softmax(W_r n) over `num_experts`, float32 at HIGHEST
    idx = top-k(s);  g = s[idx] / sum(s[idx])          (`norm_topk_prob`)
    x   = x + sum_{e in idx} g_e W_down^e(silu(W_gate^e n) * W_up^e n)

every layer an expert layer, no shared expert; logits_p = W_head N_f(x_p) is
the distribution of the token AT p: there is no next-token shift.

GENERATION (`block_diffusion_generate` upstream, greedy): the prompt's whole
blocks are prefilled under the mask above and their keys and values kept; a
generated block starts as the prompt's tail, if the prompt ends inside it,
beside `[MASK]` ids; a DENOISE pass runs the block (its keys and values
made from the buffer as it stands, so provisional), takes at each masked
position the argmax and its float32 softmax probability as confidence, and
commits the n positions of largest confidence (`block_select`: n from the
static schedule, `block_length / denoising_steps` a pass with the remainder
on the first passes; under `low_confidence_dynamic` every position whose
confidence passes `confidence_threshold` where those are more); when none is
masked a COMMIT pass runs the block once more from its final tokens and its
keys and values stay. `decode_block` tells the engine that, and the engine
runs the passes: the model's step is the same `paged_step` for a chunk of
prompt, a denoise pass and a commit pass. A commit pass chooses nothing, so
the engine lets it RIDE with the next block's first denoise pass: one step
over both blocks of the row (`paged_step`'s `read_from`), which is the two
steps apart because every layer scatters the call's keys and values before
it attends and the later block sees the earlier one under vis(p). It does
not ride where the block is the request's last or the next has no page; a
ROW-PASS is one block of one row through one step, five a block either
way, and the engine's books count row-passes.

What the published config does not hold (`assumed` in the benchmark's
configuration file): the q/k norms, the absent shift, L, the steps, the
remasking rules and the mask id are the family's published code, not keys of
`config.json`.

THE MASK needs nothing of the kernel (`ops/paged_attention.py`): the call
takes a position a query only as the bound of what it sees, so it is handed
vis(p) where the scatter and the rotary are handed p
(`llama.paged_write_and_attend`'s `sees`).

Counters (`cache_counters` / `counter_stats`, docs/INFERENCE.md finding
(f)): `cache["moe"]` holds, for block steps and prefill chunks apart, the
steps, and summed over them a layer: the assignments, the experts that drew
a row, the fullest expert's load and the row tiles the products ran;
`stats()["moe"]`. `cache["routing"]` f32 [2k, blocks x block] is the FIRST
layer's chosen experts and gates of every cached token, written by the step
that routed it at the token's own cache location (a denoise pass's are
overwritten by the commit pass's like its keys and values).

Precision: parameters, cache and matmul operands in `dtype` (bf16 served)
into f32 accumulation; router, norms, rotary, softmax statistics and the
confidence f32; the residual stream f32 ([rows, hidden]: nothing beside the
weights).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# sdar_controls.py replaces _rms_norm, paged_write_and_attend, moe.route.
from ray_tpu.models._nn import (RowsOfTransposed, cache_locations, normal,
                                paged_write_and_attend, product,
                                rms_norm as _rms_norm, rotary_tables, rotate)
from ray_tpu.models._served import PagedModel
from ray_tpu.ops import held_experts as moe

KINDS = ("decode", "prefill")
RULES = ("low_confidence_static", "low_confidence_dynamic")


@dataclass(frozen=True)
class SDARConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    # the family's published code, not keys of `config.json`
    block_length: int = 4
    denoising_steps: int = 4
    remasking_strategy: str = "low_confidence_static"
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669
    dtype: Any = jnp.bfloat16          # parameters, matmul operands, cache

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        if self.remasking_strategy not in RULES:
            raise ValueError(f"remasking_strategy {self.remasking_strategy!r}"
                             f": one of {RULES}")
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError("denoising_steps lies in 1..block_length: a "
                             "pass commits at least one position")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError("mask_token_id is a row of the embedding")

    @staticmethod
    def from_published(cfg: Dict[str, Any], **overrides) -> "SDARConfig":
        """From the keys of a published `config.json` (further keys are
        ignored). What this file does not hold is refused, not dropped."""
        if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
            raise ValueError("every layer is an expert layer here")
        if cfg.get("rope_scaling") is not None or cfg.get("attention_bias"):
            raise ValueError("only the plain rotary and no bias are held")
        if cfg.get("use_sliding_window"):
            raise ValueError("no sliding window is held")
        names = set(SDARConfig.__dataclass_fields__) - {"dtype"}
        kw = {k: cfg[k] for k in names if k in cfg}
        kw["rope_theta"] = float(kw.get("rope_theta", 1e6))
        return SDARConfig(**{**kw, **overrides})

    @staticmethod
    def tiny(**overrides) -> "SDARConfig":
        """A few thousand parameters, every mechanism present (CPU tests):
        2 layers, 4 query heads on 2 KV heads of 16, 8 experts top-2."""
        return SDARConfig(**{**dict(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
            max_position_embeddings=128, mask_token_id=95,
            dtype=jnp.float32), **overrides})

    @property
    def schedule(self) -> Tuple[int, ...]:
        """Positions a denoise pass commits under the static rule:
        `block_length / denoising_steps`, the remainder on the first passes
        (upstream's `get_num_transfer_tokens`)."""
        base, rest = divmod(self.block_length, self.denoising_steps)
        return tuple(base + (t < rest) for t in range(self.denoising_steps))

    @property
    def kv_bytes_per_token(self) -> int:
        return (self.num_hidden_layers * 2 * self.num_key_value_heads
                * self.head_dim * jnp.dtype(self.dtype).itemsize)


@dataclass(frozen=True)
class BlockDecode:
    """The contract's `decode_block`: what the engine needs to run a row's
    block through its passes (module docstring, GENERATION). The engine's
    block program is two blocks wide (a commit pass rides with the next
    block's first denoise pass) and hands `paged_step` `read_from`; `select`
    sees the one block whose logits were read."""
    length: int                  # L: positions a block, tokens a commit
    mask_id: int                 # the id a masked position is fed as
    schedule: Tuple[int, ...]    # positions denoise pass t commits
    threshold: Optional[float]   # the dynamic rule's, None under the static
    # (logits [b, L, vocab], masked [b, L], n [b]) -> (x0 [b, L] int32,
    # chosen [b, L] bool): this pass's tokens and the positions it commits
    select: Callable


def block_select(logits, masked, n, threshold: Optional[float] = None):
    """One denoise pass's choice, a row: x0 the argmax at each position,
    its confidence the float32 softmax probability of it, -inf where the
    position is not masked; the `n` masked positions of largest confidence
    are chosen (never more than are masked, never an unmasked one), or under
    the dynamic rule every position over `threshold` where those are at
    least n. Ties go to the earlier position."""
    with jax.named_scope("block_select"):
        z = logits.astype(jnp.float32)
        x0 = jnp.argmax(z, axis=-1).astype(jnp.int32)
        top = jnp.max(z, axis=-1)
        conf = 1.0 / jnp.sum(jnp.exp(z - top[..., None]), axis=-1)
        conf = jnp.where(masked, conf, -jnp.inf)
        length = masked.shape[1]
        # rank of a position among its row's confidences, largest first
        ahead = (conf[:, None, :] > conf[:, :, None]) | (
            (conf[:, None, :] == conf[:, :, None])
            & (jnp.arange(length)[None, None, :]
               < jnp.arange(length)[None, :, None]))
        chosen = masked & (jnp.sum(ahead, axis=-1) < n[:, None])
        if threshold is not None:
            high = masked & (conf > threshold)
            chosen = jnp.where(
                (jnp.sum(high, axis=-1) >= n)[:, None] & (n > 0)[:, None],
                high, chosen)
        return x0, chosen


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #


def init_params(cfg: SDARConfig, key) -> Dict[str, Any]:
    """Seeded parameters: products normal(std 0.02) in `cfg.dtype`, norms
    one. Each tensor is made on the device by one jitted draw in blocks.
    q, k and v are stored side by side as `wqkv` and an expert's gate
    projection beside its up projection (`published_weights` cuts them
    apart again)."""
    e, dt, hd = cfg.hidden_size, cfg.dtype, cfg.head_dim
    qd, kvd = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    experts, f = cfg.num_experts, cfg.moe_intermediate_size
    draw = jax.jit(normal, static_argnums=(1, 2, 3))
    keys = iter(jax.random.split(key, 2 + 5 * cfg.num_hidden_layers))
    params = {"embed": draw(next(keys), (cfg.vocab_size, e), dt),
              "lm_head": draw(next(keys), (e, cfg.vocab_size), dt),
              "final_norm": jnp.ones((e,), dt), "layers": []}
    for _ in range(cfg.num_hidden_layers):
        params["layers"].append({
            "wqkv": draw(next(keys), (e, qd + 2 * kvd), dt),
            "wo": draw(next(keys), (qd, e), dt),
            "q_norm": jnp.ones((hd,), dt), "k_norm": jnp.ones((hd,), dt),
            "input_norm": jnp.ones((e,), dt),
            "mlp_norm": jnp.ones((e,), dt),
            "router": draw(next(keys), (e, experts), dt),
            "w_gate_up": draw(next(keys), (experts, e, 2 * f), dt),
            "w_down": draw(next(keys), (experts, f, e), dt)})
    return params


def published_weights(cfg: SDARConfig, params) -> Tuple[Dict[str, Any], Any]:
    """(the top-level tensors, a function layer index -> that layer's
    tensors) under the published names and layouts: products [out, in],
    an expert's three apart. The map is names, cuts and transposes."""
    qd = cfg.num_attention_heads * cfg.head_dim
    kvd = cfg.num_key_value_heads * cfg.head_dim
    f = cfg.moe_intermediate_size
    top = {"model.embed_tokens.weight": params["embed"],
           "model.norm.weight": params["final_norm"],
           "lm_head.weight": RowsOfTransposed(params["lm_head"])}

    def layer(i: int) -> Dict[str, Any]:
        lp = params["layers"][i]
        q, k, v = jnp.split(lp["wqkv"], [qd, qd + kvd], axis=1)
        out = {"self_attn.q_proj.weight": q.T, "self_attn.k_proj.weight": k.T,
               "self_attn.v_proj.weight": v.T,
               "self_attn.o_proj.weight": lp["wo"].T,
               "self_attn.q_norm.weight": lp["q_norm"],
               "self_attn.k_norm.weight": lp["k_norm"],
               "input_layernorm.weight": lp["input_norm"],
               "post_attention_layernorm.weight": lp["mlp_norm"],
               "mlp.gate.weight": lp["router"].T,
               # [experts, out, in], an expert a row
               "mlp.experts.gate_proj.weight":
                   lp["w_gate_up"][:, :, :f].transpose(0, 2, 1),
               "mlp.experts.up_proj.weight":
                   lp["w_gate_up"][:, :, f:].transpose(0, 2, 1),
               "mlp.experts.down_proj.weight":
                   lp["w_down"].transpose(0, 2, 1)}
        return out

    return top, layer


# --------------------------------------------------------------------------- #
# The layer
# --------------------------------------------------------------------------- #


def routed_experts(cfg: SDARConfig, lp, n, live, live_tokens=None):
    """The expert layer on n [T, hidden] (normed): (y [T, hidden] f32,
    counts, routing). `live` [T] marks the rows that are real tokens; the
    others are routed to no expert, and `live_tokens` is how many the
    caller expects of them (None: all; `held_expert_forward`). `routing`
    f32 [2k, T] is what the experts were HANDED: the chosen experts above
    their gates."""
    experts = cfg.num_experts
    with jax.named_scope("moe_route"):
        probs, gates, index = moe.route(n, lp["router"],
                                        cfg.num_experts_per_tok)
        if not cfg.norm_topk_prob:
            gates = jnp.take_along_axis(probs, index, axis=-1)
        index = jnp.where(live[:, None], index, experts).astype(jnp.int32)
        routing = jnp.concatenate(
            [index.astype(jnp.float32), gates], axis=-1).T
    with jax.named_scope("moe_experts"):
        y, counts = moe.held_expert_forward(
            n, gates, index, lp["w_gate_up"], lp["w_down"], (0, experts),
            experts, live_tokens)
    return y, counts, routing


def _layer(cfg: SDARConfig, lp, x, k_arena, v_arena, tables, positions, sees,
           rotary, write_mask, live_tokens=None):
    """One layer on the residual stream x [b, s, hidden] (f32): (x, k_arena,
    v_arena, the expert layer's counts, its routing)."""
    dt, eps = cfg.dtype, cfg.rms_norm_eps
    b, s, e = x.shape
    heads, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
    with jax.named_scope("sdar_attn_proj"):
        n = _rms_norm(x, lp["input_norm"], eps).astype(dt)
        q, k, v = jnp.split(product(n, lp["wqkv"]),
                            [heads * hd, (heads + kvh) * hd], axis=-1)
        q = _rms_norm(q.reshape(b, s, heads, hd), lp["q_norm"], eps)
        k = _rms_norm(k.reshape(b, s, kvh, hd), lp["k_norm"], eps)
        q = rotate(q.transpose(0, 2, 1, 3), *rotary).astype(dt)
        k = rotate(k.transpose(0, 2, 1, 3), *rotary).astype(dt)
        v = v.astype(dt).reshape(b, s, kvh, hd).transpose(0, 2, 1, 3)
    attn, k_arena, v_arena = paged_write_and_attend(
        q, k, v, k_arena, v_arena, tables, positions, write_mask, sees)
    with jax.named_scope("sdar_attn_proj"):
        attn = attn.transpose(0, 2, 1, 3).reshape(b, s, heads * hd)
        x = x + product(attn, lp["wo"])
    n = _rms_norm(x, lp["mlp_norm"], eps).astype(dt)
    y, counts, routing = routed_experts(cfg, lp, n.reshape(b * s, e),
                                        write_mask.reshape(-1), live_tokens)
    return x + y.reshape(b, s, e), k_arena, v_arena, counts, routing


def _count(counters, kind: int, per_layer):
    """The counters with one step's counts (a list, a layer each) added
    under `kind` (0 a block step, 1 a prefill chunk)."""
    load = jnp.stack([c["load"] for c in per_layer])          # [layers, E]
    add = {"steps": jnp.int32(1),
           "assigned": jnp.stack([c["assigned"] for c in per_layer]),
           "tiles": jnp.stack([c["tiles"] for c in per_layer]),
           "drew": jnp.sum(load > 0, axis=1, dtype=jnp.int32),
           "max_load": jnp.max(load, axis=1)}
    return {k: v.at[kind].add(add[k]) for k, v in counters.items()}


class SDAR(PagedModel):
    """The model the engine is handed: its configuration and what of the
    model contract differs from `PagedModel`'s defaults. Parameters are a
    plain pytree (`init_params`). A prefix of blocks restores a sequence
    (the default) at any multiple of `block_length`: keys and values of a
    position depend on its whole diffusion block, and the engine holds
    `block_size % block_length == 0`, so every page boundary is one."""

    def __init__(self, config: SDARConfig):
        self.config = config

    def init(self, key):
        return init_params(self.config, key)

    @property
    def decode_block(self) -> BlockDecode:
        cfg = self.config
        threshold = cfg.confidence_threshold \
            if cfg.remasking_strategy == "low_confidence_dynamic" else None

        def select(logits, masked, n):
            return block_select(logits, masked, n, threshold)

        return BlockDecode(cfg.block_length, cfg.mask_token_id, cfg.schedule,
                           threshold, select)

    def paged_cache(self, num_blocks: int, block_size: int, mesh=None,
                    batch_slots: Optional[int] = None):
        """One (k, v) arena pair a layer, [num_blocks, block_size, kv_heads,
        head_dim] (block 0 the trash block), the first layer's routing
        record and the expert layers' counters (module docstring)."""
        if mesh is not None:
            raise ValueError("SDAR serves on one device (tp = 1)")
        cfg = self.config
        shape = (num_blocks, block_size, cfg.num_key_value_heads,
                 cfg.head_dim)
        layers, i32 = cfg.num_hidden_layers, jnp.int32
        return {"kv": [(jnp.zeros(shape, cfg.dtype),
                        jnp.zeros(shape, cfg.dtype)) for _ in range(layers)],
                "routing": jnp.zeros((2 * cfg.num_experts_per_tok,
                                      num_blocks * block_size), jnp.float32),
                "moe": {"steps": jnp.zeros((2,), i32),
                        **{name: jnp.zeros((2, layers), i32) for name in (
                            "assigned", "tiles", "drew", "max_load")}}}

    def paged_step(self, params, ids, cache, block_tables, row_pos,
                   write_mask, adapters=None, slots=None, last_idx=None,
                   read_from=None):
        """One step: ids [b, s] at positions row_pos[b] + arange(s), each
        seeing its whole diffusion block and all before it. Returns (logits
        [b, s, vocab] of the tokens AT those positions, or [b, vocab] at
        `last_idx` [b], or [b, block_length, vocab] of the block that
        begins `read_from` [b] positions into its row; the cache). `slots`
        is not looked at: nothing is kept per slot.

        `read_from` is the engine's block program's (the contract's
        `decode_block`): its rows are TWO blocks wide, a block whose keys
        and values become final beside the next one's first denoise pass
        (GENERATION above), and the head runs where logits are read. Every
        layer scatters the call's keys and values before it attends, so the
        later block reads the earlier one's final keys and values, as after
        a commit pass of its own. A row's two blocks are rarely both live,
        which the expert layer's tile is told (`routed_experts`)."""
        if adapters is not None:
            raise ValueError("SDAR has no adapter banks")
        cfg = self.config
        length = cfg.block_length
        b, s = ids.shape
        # positions expected live of a block program's b x 2 x length: a
        # block a row, and both in one of a block's `len(schedule)`
        # executions (the commit aboard)
        live_tokens = None if read_from is None else \
            b * length + -(-b * length // len(cfg.schedule))
        positions = row_pos[:, None] + jnp.arange(s)[None, :]
        # ... as far as the call's live positions go (a chunk or a block
        # step covers whole blocks: the bound then changes nothing)
        sees = jnp.minimum(
            (positions // length + 1) * length - 1,
            jnp.max(jnp.where(write_mask, positions, 0), axis=1,
                    keepdims=True))
        rotary = rotary_tables(positions, cfg.head_dim, cfg.rope_theta)
        flat = cache_locations(block_tables, positions, write_mask,
                               cache["kv"][0][0].shape[1])
        x = params["embed"][ids].astype(jnp.float32)
        kv, counts, record = [], [], cache["routing"]
        for lp, (k_arena, v_arena) in zip(params["layers"], cache["kv"]):
            x, k_arena, v_arena, count, routing = _layer(
                cfg, lp, x, k_arena, v_arena, block_tables, positions, sees,
                rotary, write_mask, live_tokens)
            kv.append((k_arena, v_arena))
            if not counts:                         # the first layer
                with jax.named_scope("moe_record"):
                    record = record.at[:, flat].set(routing)
            counts.append(count)
        if last_idx is not None:
            x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
        elif read_from is not None:
            x = jnp.take_along_axis(x, (read_from[:, None] + jnp.arange(
                length)[None, :])[:, :, None], axis=1)
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
            logits = product(x.astype(cfg.dtype), params["lm_head"])
        # a prefill chunk reads logits at one position (the engine's reads
        # none of them); a block step reads a block's, whatever its width
        return logits, {"kv": kv, "routing": record, "moe": _count(
            cache["moe"], int(last_idx is not None), counts)}

    # ------------------------------------------------- counters (finding f)

    def cache_counters(self, cache):
        """The part of the cache the host may read when `stats()` is
        asked: small device arrays, cumulative since the cache was made."""
        return cache["moe"]

    def counter_stats(self, host) -> Dict[str, Any]:
        """`stats()["moe"]` from a host copy of `cache_counters`: for block
        steps (`decode`) and prefill chunks apart, the steps and the sums
        over the layers (the difference of two reads is a window's), and a
        layer a step: the assignments, the experts that drew a row, the
        fullest expert's load."""
        cfg = self.config
        out: Dict[str, Any] = {"layers": cfg.num_hidden_layers,
                               "experts": cfg.num_experts,
                               "top_k": cfg.num_experts_per_tok}
        for k, kind in enumerate(KINDS):
            steps = int(host["steps"][k])
            sums = {name: float(np.sum(host[name][k].astype(np.int64)))
                    for name in ("assigned", "tiles", "drew", "max_load")}
            calls = max(1, steps * cfg.num_hidden_layers)
            out[kind] = {"steps": steps, **sums,
                         "assignments_per_step": sums["assigned"] / calls,
                         "experts_drawn_per_step": sums["drew"] / calls,
                         "max_load_per_step": sums["max_load"] / calls}
        return {"moe": out}
