"""Ring attention: sequence/context parallelism over the `sp` mesh axis.

Net-new capability vs the reference (no sequence parallelism anywhere in it
— SURVEY.md §5.7). Each device holds a sequence shard of Q/K/V; K/V shards
rotate around the ring via `jax.lax.ppermute` (compiled to ICI neighbor
transfers) while each device folds every K/V chunk into its local Q's online
softmax statistics. Peak memory is O(S/sp * S/sp) per step instead of
O(S^2), and the rotation overlaps with compute under XLA's async
collectives.

Training-ready: a custom VJP runs the ring AGAIN for the backward —
gradients dK/dV ride the rotating ring alongside their chunks (each chunk
returns home after a full cycle carrying its accumulated gradient), so
rotated K/V are never materialized across steps the way differentiating
through the forward's fori_loop would.

Use inside shard_map/pjit with `q,k,v` sharded over `axis_name` on the
sequence dimension (logical axis "seq" -> mesh axis "sp").
"""

from __future__ import annotations

import functools
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _chunk_scores(q, k, q_offset, k_offset, causal: bool, scale: float):
    """Masked scores of local q against one k chunk. [b,h,sq,sk] f32."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    return s


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Attention over a ring of sequence shards.

    Must run inside a mapped context (shard_map / pjit-manual) where
    `axis_name` is a mesh axis and q/k/v carry this device's sequence shard:
    [batch, heads, seq_shard, head_dim].
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _ring_attention(q, k, v, axis_name, causal, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_attention(q, k, v, axis_name, causal, scale):
    out, _ = _ring_fwd_impl(q, k, v, axis_name, causal, scale)
    return out


def _ring_fwd_impl(q, k, v, axis_name, causal, scale):
    ring_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    seq_shard = q.shape[2]
    q_offset = my_idx * seq_shard

    m0 = jnp.full(q.shape[:3] + (1,), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros(q.shape[:3] + (1,), dtype=jnp.float32)
    acc0 = jnp.zeros(q.shape, dtype=jnp.float32)

    def step(i, carry):
        m, l, acc, kv = carry
        k_cur, v_cur = kv
        # Chunk j currently held = (my_idx - i) mod ring  (kv rotates +1).
        src_idx = (my_idx - i) % ring_size
        k_offset = src_idx * seq_shard
        s = _chunk_scores(q, k_cur, q_offset, k_offset, causal, scale)
        m_c = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), _NEG_INF / 2)
        p = jnp.exp(s - m_c)
        l_c = jnp.sum(p, axis=-1, keepdims=True)
        acc_c = jnp.einsum("bhqk,bhkd->bhqd", p,
                           v_cur.astype(jnp.float32))
        m_new = jnp.maximum(m, m_c)
        corr_prev = jnp.exp(m - m_new)
        corr_c = jnp.exp(m_c - m_new)
        l_new = l * corr_prev + l_c * corr_c
        acc_new = acc * corr_prev + acc_c * corr_c
        rot = [(j, (j + 1) % ring_size) for j in range(ring_size)]
        k_next = jax.lax.ppermute(k_cur, axis_name, rot)
        v_next = jax.lax.ppermute(v_cur, axis_name, rot)
        return m_new, l_new, acc_new, (k_next, v_next)

    m, l, acc, _ = jax.lax.fori_loop(0, ring_size, step, (m0, l0, acc0, (k, v)))
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe).astype(q.dtype)
    lse = m + jnp.log(l_safe)                        # [b,h,sq,1]
    return out, lse


def _ring_fwd(q, k, v, axis_name, causal, scale):
    out, lse = _ring_fwd_impl(q, k, v, axis_name, causal, scale)
    return out, (q, k, v, out, lse)


def _ring_bwd(axis_name, causal, scale, residuals, g):
    """Second ring pass: dK/dV accumulate on the rotating chunks and return
    home after a full cycle; dQ accumulates locally."""
    q, k, v, out, lse = residuals
    ring_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    seq_shard = q.shape[2]
    q_offset = my_idx * seq_shard
    do = g.astype(jnp.float32)
    # Softmax-jacobian diagonal term: delta_i = rowsum(dO * O).
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1, keepdims=True)

    dq0 = jnp.zeros(q.shape, dtype=jnp.float32)
    dk0 = jnp.zeros(k.shape, dtype=jnp.float32)
    dv0 = jnp.zeros(v.shape, dtype=jnp.float32)

    def step(i, carry):
        dq, ring = carry
        k_cur, v_cur, dk_cur, dv_cur = ring
        src_idx = (my_idx - i) % ring_size
        k_offset = src_idx * seq_shard
        s = _chunk_scores(q, k_cur, q_offset, k_offset, causal, scale)
        p = jnp.exp(s - lse)                          # [b,h,sq,sk]
        dv_cur = dv_cur + jnp.einsum("bhqk,bhqd->bhkd", p, do)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, v_cur.astype(jnp.float32))
        ds = p * (dp - delta) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds,
                             k_cur.astype(jnp.float32))
        dk_cur = dk_cur + jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
        rot = [(j, (j + 1) % ring_size) for j in range(ring_size)]
        ring_next = tuple(jax.lax.ppermute(t, axis_name, rot)
                          for t in (k_cur, v_cur, dk_cur, dv_cur))
        return dq, ring_next

    dq, ring = jax.lax.fori_loop(0, ring_size, step,
                                 (dq0, (k, v, dk0, dv0)))
    _, _, dk, dv = ring
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_attention.defvjp(_ring_fwd, _ring_bwd)


def ring_attention_sharded(q, k, v, mesh, causal: bool = True,
                           scale: Optional[float] = None,
                           sp_axis: str = "sp") -> jax.Array:
    """Convenience wrapper: shard_map ring_attention over the mesh's sp axis.

    q,k,v: global [batch, heads, seq, head_dim] arrays (sharded or not);
    output matches the input sharding convention (seq over sp).
    """
    from jax.sharding import PartitionSpec as P

    if sp_axis not in mesh.axis_names or mesh.shape[sp_axis] == 1:
        from ray_tpu.ops.attention import flash_attention

        return flash_attention(q, k, v, causal, scale)
    data_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names) or None
    # No trailing None for head_dim: unspecified trailing dims are
    # replicated anyway, and a trailing-None spec produces a different
    # jit cache key than the normalized one (RL023; the PR-8 recompile).
    spec = P(data_axes, None, sp_axis)
    body = partial(ring_attention, axis_name=sp_axis, causal=causal,
                   scale=scale)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
