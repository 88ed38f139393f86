"""Ouro (`model_type` `ouro`): a LOOPED decoder. One stack of layers is
applied `total_ut_steps` times with the SAME weights (ByteDance Seed,
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741);
the hidden state that leaves pass u, after the model's final norm, enters
pass u + 1, and logits are read after the last pass. Served through the
engine (`inference/engine.py`): this file answers the model contract
(docs/INFERENCE.md) and nothing else is asked of it.

The equations, per layer (x the residual stream, N_i RMSNorm with a learned
weight, eps `rms_norm_eps`; d = `head_dim`):

    a = Attn(N_1(x))      q, k, v, o without bias, plain multi-head (a KV
                          head a query head), rotate-half rotary on all d
                          dims at `rope_theta`, the SAME positions in every
                          pass, causal, scale 1/sqrt(d)
    x = x + N_2(a)        sandwich: a norm after the sub-layer too
    m = W_down(silu(W_gate N_3(x)) * W_up N_3(x))
    x = x + N_4(m)

and over the model, h_0 = E[ids], U = `total_ut_steps`:

    for u in 1..U:  h_u = N_f(Layers(h_{u-1}))      N_f after EVERY pass
                    lambda_u = sigmoid(w_g . h_u + b_g)       the exit gate
    logits = W_head h_U
    p_exit(u) = lambda_u prod_{j<u} (1 - lambda_j), the rest on u = U;
    a token exits at the first u whose CDF reaches `early_exit_threshold`

What the published config does not hold (`assumed` in the benchmark's
configuration file): the sandwich norms, N_f between passes and the gate
are the family's published form (the paper's architecture section and the
published `modeling_ouro.py`), not keys of `config.json`.

What is served: every token takes all U passes (the published threshold is
1). The gate is in the parameters and in `forward`'s exit distribution; the
served step does not read it. Rows of one batch leaving the loop after
different passes need a step that does not do the same work for every row:
ROADMAP, Reach.

THE CACHE. Keys and values differ by pass, so a token holds U x layers
sets of them. `paged_cache` makes ONE arena pair a layer, [U x num_blocks,
block_size, kv_heads, d]: pass u of logical block b lives at physical block
u x num_blocks + b (`_pass_tables`). The engine's `BlockManager` keeps one
table a sequence and hands out blocks 1..num_blocks-1 as for any model; a
block it gives, frees, or restores from the radix prefix cache brings all U
passes' pages with it, so `prefix_restores` holds. Block 0 (of pass 0's
range) is the trash block of every pass; blocks u x num_blocks, u > 0, are
never addressed. `ops/paged_attention.py` is handed the arena and
`block_tables + u x num_blocks` and knows nothing of passes.

THE STEP. The layers are unrolled ONCE, inside a `lax.scan` over the passes
that carries the hidden state and the arenas: a step program holds one
traced stack of layers (one paged-attention call a layer, whatever U), and
the arenas are written in place through the loop. `wq`, `wk`, `wv` are
stored side by side as `wqkv`, `w_gate` and `w_up` as `w_gate_up`: one
product where the equations have three and two (`published_weights` cuts
them apart again).

Counters (`cache_counters` / `counter_stats`, docs/INFERENCE.md finding
(f)): the cache carries `passes`, the passes executed summed over live
tokens, counted inside the loop, and `tokens`; `stats()["loop"]` says both,
and with them the passes a token (U today: the number adaptive exit would
move) and the layer applications. `decode_steps` and `decode_blocks` count
the one-token steps and the blocks their live rows held: how much of the
arena was at work. `stats()["kv_layout"]` says what a token holds in the
cache.

Precision: parameters and matmul operands in `dtype` (bf16 served) into
f32 accumulation; norms, the rotary and the softmax statistics f32; and
the RESIDUAL STREAM in `stream_dtype`, f32 served, through the layers and
from pass to pass. It is [rows, hidden] and costs nothing beside the
weights, and in bf16 (the published activations' type) its rounding alone
(it grows to ~10 a pass while every sub-layer adds 1) put the last pass's
last layer 23-37% from the float32 reference on the chip, where no check
can tell a fault from rounding (PERF.md section 6, PR 60).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ouro_controls.py replaces _rms_norm, paged_write_and_attend, _pass_tables.
from ray_tpu.models._nn import (RowsOfTransposed, normal,
                                paged_write_and_attend, product,
                                rms_norm as _rms_norm, rotary_tables, rotate)
from ray_tpu.models._served import PagedModel


@dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    dtype: Any = jnp.bfloat16          # parameters and matmul operands
    stream_dtype: Any = jnp.float32    # the residual stream (docstring)

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("Ouro is plain multi-head attention: a KV head "
                             "a query head")
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps must be at least 1")

    @staticmethod
    def from_published(cfg: Dict[str, Any], **overrides) -> "OuroConfig":
        """From the keys of a published `config.json` (further keys are
        ignored)."""
        names = set(OuroConfig.__dataclass_fields__) - {"dtype",
                                                        "stream_dtype"}
        kw = {k: cfg[k] for k in names if k in cfg}
        kw["rope_theta"] = float(kw.get("rope_theta", 1e6))
        return OuroConfig(**{**kw, **overrides})

    @staticmethod
    def tiny(**overrides) -> "OuroConfig":
        """A few thousand parameters, every mechanism present (CPU tests):
        2 layers run 4 times, 4 heads of 16."""
        return OuroConfig(**{**dict(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, head_dim=16,
            intermediate_size=48, max_position_embeddings=128,
            dtype=jnp.float32), **overrides})

    @property
    def kv_bytes_per_token(self) -> int:
        """What one token holds in the cache: K and V of every layer of
        every pass."""
        return (self.total_ut_steps * self.num_hidden_layers * 2
                * self.num_key_value_heads * self.head_dim
                * jnp.dtype(self.dtype).itemsize)


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

# a layer's four norms N_1..N_4: ours -> the published name
NORMS = {"input_norm": "input_layernorm",
         "attn_post_norm": "input_layernorm_2",
         "mlp_norm": "post_attention_layernorm",
         "mlp_post_norm": "post_attention_layernorm_2"}


def init_params(cfg: OuroConfig, key) -> Dict[str, Any]:
    """Seeded parameters: products normal(std 0.02) in `cfg.dtype`, norms
    one, the exit gate's projection normal(std 0.02) and its bias zero.
    Each tensor is made on the device by one jitted draw."""
    e, dt = cfg.hidden_size, cfg.dtype
    qd = cfg.num_attention_heads * cfg.head_dim
    draw = jax.jit(normal, static_argnums=(1, 2, 3))
    keys = iter(jax.random.split(key, 3 + 4 * cfg.num_hidden_layers))
    params = {"embed": draw(next(keys), (cfg.vocab_size, e), dt),
              "lm_head": draw(next(keys), (e, cfg.vocab_size), dt),
              "final_norm": jnp.ones((e,), dt),
              "exit_gate": {"w": draw(next(keys), (e, 1), dt)[:, 0],
                            "b": jnp.zeros((), dt)},
              "layers": []}
    for _ in range(cfg.num_hidden_layers):
        params["layers"].append({
            "wqkv": draw(next(keys), (e, 3 * qd), dt),
            "wo": draw(next(keys), (qd, e), dt),
            "w_gate_up": draw(next(keys), (e, 2 * cfg.intermediate_size), dt),
            "w_down": draw(next(keys), (cfg.intermediate_size, e), dt),
            **{name: jnp.ones((e,), dt) for name in NORMS}})
    return params


def published_weights(params) -> Tuple[Dict[str, Any], Any]:
    """(the top-level tensors, a function layer index -> that layer's
    tensors) under the published names and layouts: products [out, in].
    The fused products are cut apart: the map is names, a cut and a
    transpose."""
    top = {"model.embed_tokens.weight": params["embed"],
           "model.norm.weight": params["final_norm"],
           "model.early_exit_gate.weight": params["exit_gate"]["w"][None, :],
           "model.early_exit_gate.bias": params["exit_gate"]["b"][None],
           "lm_head.weight": RowsOfTransposed(params["lm_head"])}
    def layer(i: int) -> Dict[str, Any]:
        lp = params["layers"][i]
        q, k, v = jnp.split(lp["wqkv"], 3, axis=1)
        gate, up = jnp.split(lp["w_gate_up"], 2, axis=1)
        out = {"self_attn.q_proj.weight": q.T, "self_attn.k_proj.weight": k.T,
               "self_attn.v_proj.weight": v.T,
               "self_attn.o_proj.weight": lp["wo"].T,
               "mlp.gate_proj.weight": gate.T, "mlp.up_proj.weight": up.T,
               "mlp.down_proj.weight": lp["w_down"].T}
        out.update({f"{pub}.weight": lp[ours] for ours, pub in NORMS.items()})
        return out

    return top, layer


# --------------------------------------------------------------------------- #
# The layer and the loop
# --------------------------------------------------------------------------- #


def _pass_tables(block_tables, u, num_blocks: int):
    """The block tables of pass `u`: logical block b's pages of that pass
    lie `u x num_blocks` blocks up the arena."""
    return block_tables + u * num_blocks


def _layer(cfg: OuroConfig, lp, x, k_arena, v_arena, tables, positions,
           rotary, write_mask):
    """One layer on the residual stream x [b, s, hidden] against one
    pass's pages (`tables`): (x, k_arena, v_arena)."""
    dt, eps, stream = cfg.dtype, cfg.rms_norm_eps, cfg.stream_dtype
    b, s, _ = x.shape
    heads, hd = cfg.num_attention_heads, cfg.head_dim
    with jax.named_scope("ouro_attn_proj"):
        n = _rms_norm(x, lp["input_norm"], eps).astype(dt)
        q, k, v = (t.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
                   for t in jnp.split(product(n, lp["wqkv"]).astype(dt), 3,
                                      axis=-1))
        q, k = rotate(q, *rotary), rotate(k, *rotary)
    attn, k_arena, v_arena = paged_write_and_attend(
        q, k, v, k_arena, v_arena, tables, positions, write_mask)
    with jax.named_scope("ouro_attn_proj"):
        attn = attn.transpose(0, 2, 1, 3).reshape(b, s, heads * hd)
        a = product(attn, lp["wo"])
        x = (x + _rms_norm(a, lp["attn_post_norm"], eps)).astype(stream)
    with jax.named_scope("ouro_mlp"):
        n = _rms_norm(x, lp["mlp_norm"], eps).astype(dt)
        gate, up = jnp.split(product(n, lp["w_gate_up"]), 2, axis=-1)
        m = product((jax.nn.silu(gate) * up).astype(dt), lp["w_down"])
        x = (x + _rms_norm(m, lp["mlp_post_norm"], eps)).astype(stream)
    return x, k_arena, v_arena


def exit_distribution(gates):
    """p_exit over the passes [..., U] from the gates lambda [..., U]:
    lambda_u prod_{j<u} (1 - lambda_j), the rest on the last pass."""
    gates = gates.astype(jnp.float32)
    stay = jnp.cumprod(1.0 - gates, axis=-1)
    before = jnp.concatenate([jnp.ones_like(stay[..., :1]), stay[..., :-1]],
                             axis=-1)
    return jnp.concatenate([(gates * before)[..., :-1], before[..., -1:]],
                           axis=-1)


def exit_pass(p_exit, threshold: float):
    """The pass (1-based) a token exits at: the first whose CDF reaches
    `threshold` (1.0: the last)."""
    cdf = jnp.cumsum(p_exit, axis=-1)
    last = p_exit.shape[-1]
    reached = cdf[..., :-1] >= threshold
    return jnp.where(jnp.any(reached, axis=-1),
                     jnp.argmax(reached, axis=-1) + 1, last)


class Ouro(PagedModel):
    """The model the engine is handed: its configuration and what of the
    model contract differs from `PagedModel`'s defaults (a prefix of blocks
    alone restores a sequence, since a block brings every pass's pages; no
    slot state). Parameters are a plain pytree (`init_params`)."""

    def __init__(self, config: OuroConfig):
        self.config = config

    def init(self, key):
        return init_params(self.config, key)

    def paged_cache(self, num_blocks: int, block_size: int, mesh=None,
                    batch_slots: Optional[int] = None):
        """One (k, v) arena pair a layer, [passes x num_blocks, block_size,
        kv_heads, head_dim] (module docstring), and the loop's counters."""
        if mesh is not None:
            raise ValueError("Ouro serves on one device (tp = 1)")
        cfg = self.config
        shape = (cfg.total_ut_steps * num_blocks, block_size,
                 cfg.num_key_value_heads, cfg.head_dim)
        return {"kv": [(jnp.zeros(shape, cfg.dtype),
                        jnp.zeros(shape, cfg.dtype))
                       for _ in range(cfg.num_hidden_layers)],
                "loop": {name: jnp.zeros((), jnp.int32) for name in (
                    "passes", "tokens", "decode_steps", "decode_blocks")}}

    def paged_step(self, params, ids, cache, block_tables, row_pos,
                   write_mask, adapters=None, slots=None, last_idx=None):
        """One step: ids [b, s] at positions row_pos[b] + arange(s), every
        pass of every layer. Returns (logits [b, s, vocab], or [b, vocab]
        at `last_idx` [b]; the cache). `slots` is not looked at: nothing
        is kept per slot."""
        if adapters is not None:
            raise ValueError("Ouro has no adapter banks")
        x, cache, _ = self._passes(params, ids, cache, block_tables,
                                   row_pos, write_mask, with_gates=False)
        if last_idx is not None:
            x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
        with jax.named_scope("lm_head"):
            return product(x.astype(self.config.dtype),
                           params["lm_head"]), cache

    def _passes(self, params, ids, cache, block_tables, row_pos, write_mask,
                with_gates: bool):
        """The loop: (h_U [b, s, hidden] in f32, the cache, the gates
        lambda [U, b, s] or None)."""
        cfg = self.config
        positions = row_pos[:, None] + jnp.arange(ids.shape[1])[None, :]
        rotary = rotary_tables(positions, cfg.head_dim, cfg.rope_theta)
        num_blocks = cache["kv"][0][0].shape[0] // cfg.total_ut_steps
        live = jnp.sum(write_mask, dtype=jnp.int32)

        def one_pass(carry, u):
            x, kv, done = carry
            tables = _pass_tables(block_tables, u, num_blocks)
            out = []
            for lp, (k_arena, v_arena) in zip(params["layers"], kv):
                x, k_arena, v_arena = _layer(
                    cfg, lp, x, k_arena, v_arena, tables, positions, rotary,
                    write_mask)
                out.append((k_arena, v_arena))
            with jax.named_scope("ouro_pass_norm"):
                x = _rms_norm(x, params["final_norm"],
                              cfg.rms_norm_eps).astype(cfg.stream_dtype)
            gate = None
            if with_gates:
                gate = jax.nn.sigmoid(
                    product(x.astype(cfg.dtype), params["exit_gate"]["w"])
                    + params["exit_gate"]["b"].astype(jnp.float32))
            return (x, out, done + live), gate

        (x, kv, done), gates = jax.lax.scan(
            one_pass, (params["embed"][ids].astype(cfg.stream_dtype),
                       cache["kv"], cache["loop"]["passes"]),
            jnp.arange(cfg.total_ut_steps, dtype=jnp.int32))
        loop = {**cache["loop"], "passes": done,
                "tokens": cache["loop"]["tokens"] + live}
        if ids.shape[1] == 1:       # a decode step: the blocks its rows hold
            block = cache["kv"][0][0].shape[1]
            loop["decode_steps"] = loop["decode_steps"] + 1
            loop["decode_blocks"] = loop["decode_blocks"] + jnp.sum(
                jnp.where(write_mask[:, 0], row_pos // block + 1, 0),
                dtype=jnp.int32)
        return x, {"kv": kv, "loop": loop}, gates

    # ------------------------------------------------- counters (finding f)

    def cache_counters(self, cache):
        """The part of the cache the host may read when `stats()` is
        asked: four scalars, cumulative since the cache was made (int32:
        they wrap, and a difference of two reads is still a window's)."""
        return cache["loop"]

    def counter_stats(self, host) -> Dict[str, Any]:
        """`stats()["loop"]` and `stats()["kv_layout"]` from a host copy
        of `cache_counters`."""
        cfg = self.config
        read = {k: int(np.asarray(v).astype(np.uint32))
                for k, v in host.items()}
        passes, tokens = read["passes"], read["tokens"]
        return {
            "loop": {**read,
                     "layer_passes": passes * cfg.num_hidden_layers,
                     "passes_per_token": passes / tokens if tokens else None},
            "kv_layout": {"bytes_per_token": cfg.kv_bytes_per_token,
                          "passes": cfg.total_ut_steps,
                          "layers": cfg.num_hidden_layers}}

    # ---------------------------------------------------------- the rest

    def forward(self, params, ids):
        """(logits [b, s, vocab], p_exit [b, s, U]) of whole sequences
        from position 0: the loop over a cache of its own, 16-token
        blocks (tests, offline scoring)."""
        b, s = ids.shape
        per_row = -(-s // 16)
        tables = 1 + jnp.arange(b * per_row, dtype=jnp.int32).reshape(
            b, per_row)
        x, _, gates = self._passes(
            params, ids, self.paged_cache(1 + b * per_row, 16), tables,
            jnp.zeros((b,), jnp.int32), jnp.ones((b, s), bool),
            with_gates=True)
        return product(x.astype(self.config.dtype), params["lm_head"]), \
            exit_distribution(jnp.moveaxis(gates, 0, -1))

    def early_exit_draft(self, params):
        """(draft model, its params) for speculation when none was
        injected: the same weights run half as many passes, every leaf
        shared by reference. (Its cache is its own, of its own passes.)"""
        cfg = self.config
        return Ouro(replace(cfg, total_ut_steps=max(
            1, cfg.total_ut_steps // 2))), params
