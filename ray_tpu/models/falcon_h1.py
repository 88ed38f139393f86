"""Falcon-H1 (`model_type` `falcon_h1`): a decoder whose every block runs a
Mamba-2 state-space mixer IN PARALLEL with grouped-query attention on the
same normed input, then a SwiGLU MLP, with the family's fixed muP scalars
on every branch. Served through the paged engine (`inference/engine.py`):
this file answers the model contract (docs/INFERENCE.md) and nothing else
is asked of it.

The equations, each scalar a key of the published config:

    x0 = embed[ids] * embedding_multiplier
    h  = RMSNorm(x)                                           eps rms_norm_eps
    x  = x + attention_out_multiplier * Attn(attention_in_multiplier * h)
           + ssm_out_multiplier * SSM(ssm_in_multiplier * h)
    h2 = RMSNorm(x)
    x  = x + mlp_multipliers[1] * W_down(W_up h2 * silu(mlp_multipliers[0] * W_gate h2))
    logits = lm_head_multiplier * W_head RMSNorm(x_last)      untied head

`Attn`: `num_attention_heads` query / `num_key_value_heads` KV heads of
`head_dim`, k = key_multiplier * W_k u, rotary over the whole head
(rotate-half, `rope_theta`, no scaling), causal softmax at head_dim^-0.5,
no bias. K/V live in the paged arenas (`models/_nn.py
paged_write_and_attend`, `ops/paged_attention.py`).

`SSM` (Mamba-2; `mamba_d_ssm` = heads x `mamba_d_head`, which overrides
`mamba_expand`): p = W_in u, multiplied element-wise by a fixed vector
that holds `ssm_multipliers[0..4]` on its five segments [z d_ssm | x d_ssm
| B groups*d_state | C groups*d_state | dt heads]; (x, B, C) =
silu(conv1d_causal_depthwise([x|B|C]; width `mamba_d_conv`, with bias));
dt = softplus(dt + dt_bias); a = -exp(A_log) a head; per head the
recurrence of `ops/ssd.py` (H_t = exp(dt_t a) H_{t-1} + dt_t x_t B_t^T,
y_t = H_t C_t + D x_t; the `heads // groups` heads of a group share B and
C); then (`mamba_rms_norm`, not `mamba_norm_before_gate`) y =
GroupRMSNorm(y * silu(z)) * w_norm with the variance over each of the
`mamba_n_groups` groups; then W_out y.

Departures from the published model: none in the equations. The instruct
model's chat template and tokenizer are not part of it. Parameters are
[in, out] (the published layout is [out, in]; `published_weights` maps them
back for the plain reference).

What a slot holds beside the paged blocks (`paged_cache`): per layer the
recurrent state [slots, heads, d_state, d_head] float32 (TRANSPOSED, the
head's width on the lanes: `ops/ssd.py`) and the convolution's tail
[slots, d_conv - 1, d_ssm + 2 groups d_state] in the activations' dtype
(an exact copy of the bf16 inputs it repeats). A row of a step is held
(state untouched) where its `write_mask` has no live position, and starts
from zero state where its first live position is 0: a fresh request, one
re-queued by a preemption, or one admitted into a slot another left.
A prefix of KV blocks does NOT restore a sequence (`prefix_restores`
False): the engine adopts nothing from the radix cache and refuses
speculation until state snapshots exist.

A step runs over ROW GROUPS (`FalconH1._step`): one, a decode step or a
prefill chunk (`paged_step`), or a decode step with a sequence's chunk
aboard (`paged_step_with_chunk`, docs/INFERENCE.md finding (g)), the two laid
end to end through everything that is a token's own and cut apart for
attention, convolution and recurrence. The chunk's slot is a held row of
the decode group, whose kernels hand the state on to the chunk group's,
which resets or carries it: each group by its own `state_rows`.

Precision: parameters and matmul operands bf16 (the published dtype) into
f32 accumulation; norms, the in-projection's output, the convolution, dt,
the gate and the carried state are f32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

# tests/benchmarks/test_bench_kanana2.py imports `_normal` from here.
from ray_tpu.models._nn import (RowsOfTransposed, apply_rope,
                                normal as _normal, paged_write_and_attend,
                                rms_norm)
from ray_tpu.models._served import PagedModel
from ray_tpu.ops import ssd


@dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    dtype: Any = jnp.bfloat16          # parameters and activations
    state_dtype: Any = jnp.float32     # the carried recurrent state

    @staticmethod
    def from_published(cfg: Dict[str, Any], **overrides) -> "FalconH1Config":
        """From the keys of a published `config.json` (further keys are
        ignored)."""
        names = set(FalconH1Config.__dataclass_fields__) - {"dtype",
                                                            "state_dtype"}
        kw = {k: cfg[k] for k in names if k in cfg}
        for key in ("ssm_multipliers", "mlp_multipliers"):
            if key in kw:
                kw[key] = tuple(float(v) for v in kw[key])
        kw["rope_theta"] = float(kw.get("rope_theta", 1e11))
        if cfg.get("mamba_chunk_size", ssd.CHUNK) != ssd.CHUNK:
            raise ValueError(f"mamba_chunk_size must be {ssd.CHUNK}")
        return FalconH1Config(**{**kw, **overrides})

    @staticmethod
    def tiny(**overrides) -> "FalconH1Config":
        """A few thousand parameters, every mechanism present (CPU tests):
        heads of 16, 2 groups of 2 heads, state 8."""
        return FalconH1Config(**{**dict(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            intermediate_size=48, mamba_d_ssm=64, mamba_n_heads=4,
            mamba_d_head=16, mamba_d_state=8, mamba_n_groups=2,
            dtype=jnp.float32), **overrides})

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads


def mup_vector(cfg: FalconH1Config) -> jnp.ndarray:
    """The fixed f32 vector on the in-projection's output: one of
    `ssm_multipliers` on each segment [z | x | B | C | dt]."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    widths = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads)
    return jnp.concatenate([jnp.full((w,), m, jnp.float32)
                            for w, m in zip(widths, cfg.ssm_multipliers)])


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

def init_params(cfg: FalconH1Config, key) -> Dict[str, Any]:
    """Seeded parameters: products normal(std 0.02) in `cfg.dtype`, norms
    one, the convolution's weight normal(std 0.02) and its bias zero, and
    the recurrence's three vectors as Mamba-2 initialises them: `A_log` =
    log of uniform(1, 16), `dt_bias` the inverse softplus of a dt drawn
    log-uniform in [1e-3, 1e-1], `D` one. Each leaf is made by one jitted
    draw, the largest first."""
    e, dt = cfg.hidden_size, cfg.dtype
    qd, kvd = (cfg.num_attention_heads * cfg.head_dim,
               cfg.num_key_value_heads * cfg.head_dim)
    f32 = jnp.float32
    heads = cfg.mamba_n_heads
    draw = jax.jit(_normal, static_argnums=(1, 2))
    keys = iter(jax.random.split(key, 3 + 12 * cfg.num_hidden_layers))
    params = {"embed": draw(next(keys), (cfg.vocab_size, e), dt),
              "lm_head": draw(next(keys), (e, cfg.vocab_size), dt),
              "final_norm": jnp.ones((e,), dt), "layers": []}
    for _ in range(cfg.num_hidden_layers):
        dt0 = jnp.exp(jax.random.uniform(
            next(keys), (heads,), f32, math.log(1e-3), math.log(1e-1)))
        params["layers"].append({
            "w_gate": draw(next(keys), (e, cfg.intermediate_size), dt),
            "w_up": draw(next(keys), (e, cfg.intermediate_size), dt),
            "w_down": draw(next(keys), (cfg.intermediate_size, e), dt),
            "in_proj": draw(next(keys), (e, cfg.in_proj_dim), dt),
            "out_proj": draw(next(keys), (cfg.mamba_d_ssm, e), dt),
            "wq": draw(next(keys), (e, qd), dt),
            "wk": draw(next(keys), (e, kvd), dt),
            "wv": draw(next(keys), (e, kvd), dt),
            "wo": draw(next(keys), (qd, e), dt),
            "conv_w": draw(next(keys), (cfg.conv_dim, cfg.mamba_d_conv), dt),
            "conv_b": jnp.zeros((cfg.conv_dim,), dt),
            "A_log": jnp.log(jax.random.uniform(next(keys), (heads,), f32,
                                                1.0, 16.0)),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "D": jnp.ones((heads,), f32),
            "ssm_norm": jnp.ones((cfg.mamba_d_ssm,), dt),
            "input_norm": jnp.ones((e,), dt),
            "mlp_norm": jnp.ones((e,), dt),
        })
    return params


def published_weights(params) -> Tuple[Dict[str, Any], Any]:
    """(the top-level tensors, a function layer index -> that layer's
    tensors) under the published names and layouts: products [out, in],
    `conv1d.weight` [channels, 1, width]."""
    top = {"model.embed_tokens.weight": params["embed"],
           "model.final_layernorm.weight": params["final_norm"],
           "lm_head.weight": RowsOfTransposed(params["lm_head"])}
    names = {"wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
             "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
             "in_proj": "mamba.in_proj", "out_proj": "mamba.out_proj",
             "w_gate": "feed_forward.gate_proj",
             "w_up": "feed_forward.up_proj",
             "w_down": "feed_forward.down_proj"}

    def layer(i: int) -> Dict[str, Any]:
        lp = params["layers"][i]
        out = {f"{pub}.weight": lp[ours].T for ours, pub in names.items()}
        out.update({
            "mamba.conv1d.weight": lp["conv_w"][:, None, :],
            "mamba.conv1d.bias": lp["conv_b"],
            "mamba.A_log": lp["A_log"], "mamba.D": lp["D"],
            "mamba.dt_bias": lp["dt_bias"],
            "mamba.norm.weight": lp["ssm_norm"],
            "input_layernorm.weight": lp["input_norm"],
            "pre_ff_layernorm.weight": lp["mlp_norm"]})
        return out

    return top, layer


# --------------------------------------------------------------------------- #
# The block
# --------------------------------------------------------------------------- #


class _Rows(NamedTuple):
    """One ROW GROUP of a step: b sequences of s positions each (a decode
    step's [slots, 1], a prefill chunk's [1, c]) and what belongs to them
    as sequences: where they attend and write, and whose state they
    advance. `slots` [b] is each row's batch slot (None: row i is slot i);
    `fresh` [b] the rows that start from zero state, `live` [b, s] the
    positions that advance it (a prefix of each row)."""
    block_tables: Any
    positions: Any
    write_mask: Any
    slots: Any
    fresh: Any
    live: Any


def _cut(t, groups):
    """t [b, s, ...] of one group as it is, or [1, T, ...] of several laid
    end to end cut into each group's [b, s, ...]: slices of rows, the
    products before them run once over all T."""
    if len(groups) == 1:
        return [t]
    out, start = [], 0
    for rows in groups:
        b, s = rows.positions.shape
        out.append(t[0, start:start + b * s].reshape(b, s, *t.shape[2:]))
        start += b * s
    return out


def _joined(parts):
    """The groups' [b, s, n] laid end to end again, [1, T, n]."""
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate([p.reshape(1, -1, p.shape[-1]) for p in parts],
                           axis=1)


def _attention(cfg, lp, u, kv, groups):
    hd = cfg.head_dim

    def heads(t, n):
        return [p.reshape(*p.shape[:2], n, hd).transpose(0, 2, 1, 3)
                for p in _cut(t, groups)]

    q = heads(u @ lp["wq"], cfg.num_attention_heads)
    k = heads((u @ lp["wk"]) * jnp.asarray(cfg.key_multiplier, u.dtype),
              cfg.num_key_value_heads)
    v = heads(u @ lp["wv"], cfg.num_key_value_heads)
    k_arena, v_arena = kv
    out = []
    # A group at a time, each call at its own program's shape.
    for rows, qr, kr, vr in zip(groups, q, k, v):
        qr = apply_rope(qr, rows.positions, cfg.rope_theta)
        kr = apply_rope(kr, rows.positions, cfg.rope_theta)
        attn, k_arena, v_arena = paged_write_and_attend(
            qr, kr, vr, k_arena, v_arena, rows.block_tables, rows.positions,
            rows.write_mask)
        b, _, s, _ = attn.shape
        out.append(attn.transpose(0, 2, 1, 3).reshape(b, s, -1))
    return _joined(out) @ lp["wo"], (k_arena, v_arena)


def _mixer(cfg, lp, u, state, tail, groups):
    """The Mamba-2 mixer on u (already times `ssm_in_multiplier`), [b, s,
    hidden] of one group or [1, T, hidden] of several laid end to end: the
    in-projection, the gate, the group norm and the out-projection run once
    over all T; the convolution and the recurrence a group at a time, in
    order, each from the state the one before it left. `state` [slots,
    heads, N, P] and `tail` [slots, d_conv - 1, conv_dim] hold every
    slot's. Returns (out like u, state, tail)."""
    f32 = jnp.float32
    heads, p, n, g = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                      cfg.mamba_n_groups)
    d_ssm = cfg.mamba_d_ssm
    with jax.named_scope("ssm_mixer"):
        proj = jnp.dot(u, lp["in_proj"], preferred_element_type=f32) \
            * mup_vector(cfg)
        z = proj[..., :d_ssm]
        xbc = proj[..., d_ssm:d_ssm + cfg.conv_dim].astype(cfg.dtype)
        dt = jax.nn.softplus(proj[..., d_ssm + cfg.conv_dim:]
                             + lp["dt_bias"].astype(f32))
        convs = []
        with jax.named_scope("ssm_conv"):
            for rows, part in zip(groups, _cut(xbc, groups)):
                before = tail if rows.slots is None else tail[rows.slots]
                before = jnp.where(rows.fresh[:, None, None], 0, before)
                conv, after = ssd.causal_conv1d_carried(
                    part, lp["conv_w"], lp["conv_b"], before, rows.live)
                tail = after if rows.slots is None \
                    else tail.at[rows.slots].set(after)
                convs.append(jax.nn.silu(conv).astype(cfg.dtype))
        ys = []
        for rows, conv, dts in zip(groups, convs, _cut(dt, groups)):
            b, s, _ = conv.shape
            x = conv[..., :d_ssm].reshape(b, s, heads, p)
            bm = conv[..., d_ssm:d_ssm + g * n].reshape(b, s, g, n)
            cm = conv[..., d_ssm + g * n:].reshape(b, s, g, n)
            # (traced a group, after the cut, as the one-group programs
            # always have: their lowered text is held to the parent's)
            a = -jnp.exp(lp["A_log"].astype(f32))
            with jax.named_scope("ssm_scan"):
                if rows.slots is None:
                    y, state = ssd.ssd_step(
                        x[:, 0], dts[:, 0], a, bm[:, 0], cm[:, 0], lp["D"],
                        state, rows.fresh, rows.live[:, 0])
                    y = y[:, None]
                else:
                    y, state = ssd.ssd_chunk_fwd(
                        x, dts, a, bm, cm, lp["D"], state, rows.slots,
                        rows.fresh, rows.live)
            ys.append(y.reshape(b, s, d_ssm))
        y = _joined(ys) * jax.nn.silu(z)
        y = rms_norm(y, lp["ssm_norm"], cfg.rms_norm_eps, groups=g)
        return y.astype(cfg.dtype) @ lp["out_proj"], state, tail


def _block(cfg, lp, x, cache, groups):
    """One block on x [b, s, hidden] of one row group, or [1, T, hidden] of
    several laid end to end. Everything but the attention, the convolution
    and the recurrence is a token's own and reads its weights once for all
    T."""
    kv, state, tail = cache
    dt = cfg.dtype

    def scaled(t, m):
        return t if m == 1 else t * jnp.asarray(m, dt)

    h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps).astype(dt)
    attn, kv = _attention(cfg, lp, scaled(h, cfg.attention_in_multiplier),
                          kv, groups)
    mix, state, tail = _mixer(cfg, lp, scaled(h, cfg.ssm_in_multiplier),
                              state, tail, groups)
    x = x + scaled(attn, cfg.attention_out_multiplier) \
        + scaled(mix, cfg.ssm_out_multiplier)
    h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps).astype(dt)
    gate = jax.nn.silu(scaled(h2 @ lp["w_gate"], cfg.mlp_multipliers[0]))
    x = x + scaled(((h2 @ lp["w_up"]) * gate) @ lp["w_down"],
                   cfg.mlp_multipliers[1])
    return x, (kv, state, tail)


class FalconH1(PagedModel):
    """The model the engine is handed: its configuration and what of the
    model contract differs from `PagedModel`'s defaults. Parameters are a
    plain pytree (`init_params`)."""

    # A prefix of KV blocks alone does not restore a sequence: the
    # recurrent state at its end is not in them.
    prefix_restores = False

    def __init__(self, config: FalconH1Config):
        self.config = config

    def init(self, key):
        return init_params(self.config, key)

    @property
    def slot_state_bytes(self) -> int:
        """What one slot holds beside the paged blocks, all layers."""
        cfg = self.config
        state = (cfg.mamba_n_heads * cfg.mamba_d_state * cfg.mamba_d_head
                 * jnp.dtype(cfg.state_dtype).itemsize)
        tail = ((cfg.mamba_d_conv - 1) * cfg.conv_dim
                * jnp.dtype(cfg.dtype).itemsize)
        return cfg.num_hidden_layers * (state + tail)

    def paged_cache(self, num_blocks: int, block_size: int, mesh=None,
                    batch_slots: Optional[int] = None):
        """Per layer the (k, v) arenas [num_blocks, block_size, kv_heads,
        head_dim] (block 0 the trash block), the recurrent state
        [batch_slots, heads, d_state, d_head] and the convolution's tail
        [batch_slots, d_conv - 1, conv_dim]: one pytree, opaque to the
        engine."""
        if mesh is not None:
            raise ValueError("FalconH1 serves on one device (tp = 1)")
        if not batch_slots:
            raise ValueError("FalconH1's cache holds state per batch slot: "
                             "paged_cache needs batch_slots")
        cfg = self.config
        arena = (num_blocks, block_size, cfg.num_key_value_heads,
                 cfg.head_dim)
        state = (batch_slots, cfg.mamba_n_heads, cfg.mamba_d_state,
                 cfg.mamba_d_head)
        tail = (batch_slots, cfg.mamba_d_conv - 1, cfg.conv_dim)
        layers = range(cfg.num_hidden_layers)
        return {"kv": [(jnp.zeros(arena, cfg.dtype),
                        jnp.zeros(arena, cfg.dtype)) for _ in layers],
                "ssm": [jnp.zeros(state, cfg.state_dtype) for _ in layers],
                "conv": [jnp.zeros(tail, cfg.dtype) for _ in layers]}

    def paged_step(self, params, ids, cache, block_tables, row_pos,
                   write_mask, adapters=None, slots=None, last_idx=None):
        """One step: ids [b, s] at positions row_pos[b] + arange(s).
        `slots` [b] is each row's batch slot; None means row i is slot i
        and b is every slot (the decode step). Returns (logits [b, s,
        vocab], or [b, vocab] at `last_idx` [b]; the cache)."""
        if adapters is not None:
            raise ValueError("FalconH1 has no adapter banks")

        def read(x):
            if last_idx is None:
                return x
            return jnp.take_along_axis(x, last_idx[:, None, None],
                                       axis=1)[:, 0]

        return self._step(
            params, cache, [(ids, block_tables, row_pos, write_mask, slots)],
            read)

    def paged_step_with_chunk(self, params, tokens, chunk_ids, cache,
                              block_tables, row_pos, write_mask, chunk_bt,
                              chunk_pos, chunk_wmask, chunk_slot, last_idx):
        """A decode step with one sequence's prefill chunk aboard (the
        contract's optional answer): `paged_step` of tokens [b, 1] and
        `paged_step` of chunk_ids [1, c] in slot `chunk_slot` [1] at
        `last_idx` [1] as ONE execution, in which every weight is read once
        for the b + c rows and only what belongs to a sequence (attention,
        convolution, recurrence) is two calls. The engine masks the chunk's
        own slot among the decode rows: the decode group holds it and the
        chunk group resets or carries it, each by its own `state_rows`.
        Returns (the decode rows' logits [b, vocab], the chunk's [1,
        vocab], the cache)."""
        b = tokens.shape[0]
        logits, cache = self._step(
            params, cache,
            [(tokens, block_tables, row_pos, write_mask, None),
             (chunk_ids, chunk_bt, chunk_pos, chunk_wmask, chunk_slot)],
            lambda x: jnp.concatenate([x[0, :b], x[0, b + last_idx]]))
        return logits[:b], logits[b:], cache

    def _step(self, params, cache, groups, read):
        """The one body of a step over ROW GROUPS, each (ids [b, s],
        block_tables, row_pos, write_mask, slots): (the logits of the rows
        `read` picks from the last block's x, the cache). One group is a
        decode step or a prefill chunk, x [b, s, hidden]. Several are laid
        end to end, x [1, T, hidden], through everything that is a token's
        own (T rows against one read of the weights) and cut apart for
        what belongs to a sequence (`_Rows`), a later group from the state
        an earlier one left."""
        cfg = self.config
        rows = []
        for ids, block_tables, row_pos, write_mask, slots in groups:
            b, s = ids.shape
            if slots is None and (s != 1 or b != cache["ssm"][0].shape[0]):
                raise ValueError("a step that names no slots is one token "
                                 "of every slot")
            positions = row_pos[:, None] + jnp.arange(s)[None, :]
            rows.append(_Rows(block_tables, positions, write_mask, slots,
                              *self.state_rows(row_pos, write_mask)))
        ids = groups[0][0] if len(groups) == 1 else jnp.concatenate(
            [g[0].reshape(1, -1) for g in groups], axis=1)
        x = params["embed"][ids] * jnp.asarray(cfg.embedding_multiplier,
                                               cfg.dtype)
        kvs, states, tails = [], [], []
        for i, lp in enumerate(params["layers"]):
            x, (kv, state, tail) = _block(
                cfg, lp, x, (cache["kv"][i], cache["ssm"][i],
                             cache["conv"][i]), rows)
            kvs.append(kv)
            states.append(state)
            tails.append(tail)
        x = rms_norm(read(x), params["final_norm"], cfg.rms_norm_eps)
        logits = jnp.dot(x.astype(cfg.dtype), params["lm_head"],
                         preferred_element_type=jnp.float32) \
            * cfg.lm_head_multiplier
        return logits, {"kv": kvs, "ssm": states, "conv": tails}

    def state_rows(self, row_pos, write_mask):
        """(fresh [b], live [b, s]): the rows that start from zero state
        and the positions that advance it. Hold before reset: a row with
        no live position keeps its state whatever its position says (the
        engine hands idle rows position 0)."""
        return write_mask[:, 0] & (row_pos == 0), write_mask
