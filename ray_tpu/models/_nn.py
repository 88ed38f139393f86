"""What the model files share, owned by none of them: seeded draws, norms,
the rotate-half rotary, the flax family's projection, and the paged cache's
write-and-attend. Every model file imports from here, from `_served` and
from `ray_tpu.ops`, and from no other model file
(`tests/test_models_layering.py` holds the arrows); this file imports jax,
flax and `ray_tpu.ops` only. A line that moves here is on the call stack of
every cell that calls it: the compile cache's key of those programs moves
with it (ROADMAP, caveat 3).
"""

from __future__ import annotations

import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.paged_attention import paged_attention

# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

# A product's leaf is drawn in blocks of at most this many elements, so that
# the f32 draw of a [261120, 5120] table is never whole in memory.
_DRAW_BLOCK = 1 << 26


def normal(key, shape, dtype, std: float = 0.02):
    rows = shape[0]
    blocks = max(1, -(-math.prod(shape) // _DRAW_BLOCK))
    while rows % blocks:
        blocks += 1
    block = (rows // blocks,) + tuple(shape[1:])

    def draw(i):
        return (jax.random.normal(jax.random.fold_in(key, i), block,
                                  jnp.float32) * std).astype(dtype)

    return jax.lax.map(draw, jnp.arange(blocks)).reshape(shape)


class RowsOfTransposed:
    """`a.T` read in row blocks (`t[r0:r1]`), never whole: the head is
    2.7 GB and its transpose would be a second copy."""

    def __init__(self, a):
        self._a = a
        self.shape = a.shape[::-1]

    def __getitem__(self, rows: slice):
        return self._a[:, rows].T


# --------------------------------------------------------------------------- #
# Norms and products
# --------------------------------------------------------------------------- #


def rms_norm(x, weight, eps: float, groups: int = 1):
    """RMSNorm in f32 over the last dim, or over each of `groups` equal
    parts of it; returns f32."""
    xf = x.astype(jnp.float32)
    shape = xf.shape
    xg = xf.reshape(shape[:-1] + (groups, shape[-1] // groups))
    xg = xg * jax.lax.rsqrt(jnp.mean(jnp.square(xg), axis=-1, keepdims=True)
                            + eps)
    return xg.reshape(shape) * weight.astype(jnp.float32)


def product(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def dense(features: int, axes: Tuple[str, ...], cfg: Any, name: str):
    """The flax family's projection (`cfg.dtype`, `cfg.param_dtype`): no
    bias, the kernel annotated with its logical axes."""
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    kernel_init=nn.with_logical_partitioning(
                        nn.initializers.normal(0.02), axes),
                    name=name)


class RMSNorm(nn.Module):
    """The flax family's norm (`cfg.rms_eps`, `cfg.dtype`,
    `cfg.param_dtype`): a scale from ones, the result in `cfg.dtype`."""
    cfg: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale",
                           nn.with_logical_partitioning(
                               nn.initializers.ones, ("embed",)),
                           (x.shape[-1],), self.cfg.param_dtype)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        out = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.cfg.rms_eps)
        return (out * scale).astype(self.cfg.dtype)


# --------------------------------------------------------------------------- #
# The rotate-half rotary
# --------------------------------------------------------------------------- #


def rotary_tables(positions, head_dim: int, theta: float):
    """(cos, sin) [b, 1, s, d/2] in f32 at positions [b, s]: the same in
    every pass and every layer, so made once a step."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, cos, sin):
    """Rotate-half rotary on x [b, heads, s, d], in f32, back in x's type."""
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """Rotary embedding on [b, heads, s, d] with per-token positions [b, s]
    (or [s]); rotates feature pairs (even, odd) halves-style."""
    if positions.ndim == 1:
        positions = positions[None, :]
    return rotate(x, *rotary_tables(positions, x.shape[-1], theta))


# --------------------------------------------------------------------------- #
# The paged cache
# --------------------------------------------------------------------------- #


def cache_locations(block_tables, positions, write_mask, block_size: int):
    """Where each token's row lies in an arena taken as [blocks x block,
    ..]: [b * s]. Masked tokens (batch and chunk padding) land in trash
    block 0."""
    blk = jnp.clip(positions // block_size, 0, block_tables.shape[1] - 1)
    phys = jnp.where(write_mask,
                     jnp.take_along_axis(block_tables, blk, axis=1), 0)
    return (phys * block_size + positions % block_size).reshape(-1)


def paged_write_and_attend(q, k, v, k_arena, v_arena, block_tables,
                           positions, write_mask, sees=None):
    """Scatter this call's K/V ([b, kv_heads, s, d], after RoPE) into the
    paged arenas and attend q [b, heads, s, d] over them. Returns (attn
    [b, heads, s, d], k_arena, v_arena). `sees` [b, s] is the last logical
    position each query attends to, where that is not its own (a model
    whose mask is not causal token by token: `models/sdar.py`); the scatter
    goes by `positions` either way."""
    hd = q.shape[-1]
    # Named for the profiler: device ops of the paged path carry
    # `paged_attn` in their op_name (PERF.md, Open questions).
    with jax.named_scope("paged_attn"):
        nb, bsz, kvh, _ = k_arena.shape
        # Scatter this call's K/V into the arena. Physical slot
        # of logical position p in row i: block_tables[i, p // bsz]
        # * bsz + p % bsz. Masked tokens (batch padding, chunk
        # padding) are pointed at physical block 0 — reserved as a
        # trash block the manager never allocates — so one
        # fixed-shape scatter handles every mix of active/idle
        # slots without recompiling.
        kw = k.transpose(0, 2, 1, 3).astype(
            k_arena.dtype)                        # [b,s,kvh,d]
        vw = v.transpose(0, 2, 1, 3).astype(v_arena.dtype)
        flat = cache_locations(block_tables, positions, write_mask, bsz)
        k_flat = k_arena.reshape(nb * bsz, kvh, hd)
        v_flat = v_arena.reshape(nb * bsz, kvh, hd)
        k_flat = k_flat.at[flat].set(kw.reshape(-1, kvh, hd))
        v_flat = v_flat.at[flat].set(vw.reshape(-1, kvh, hd))
        k_arena = k_flat.reshape(nb, bsz, kvh, hd)
        v_arena = v_flat.reshape(nb, bsz, kvh, hd)
        # Read: each row's live blocks straight out of the arena
        # (ops/paged_attention.py: the Pallas kernel where the
        # dispatch rule gives it the call, the dense reference
        # elsewhere). The scatter above comes first, so the call's
        # own K/V are in the arena it reads.
        attn = paged_attention(
            q.transpose(0, 2, 1, 3), k_arena, v_arena, block_tables,
            positions if sees is None else sees,
            write_mask).transpose(0, 2, 1, 3)
    return attn, k_arena, v_arena
