"""Flash attention: Pallas TPU kernels, forward AND backward.

Net-new TPU capability (the reference has no kernel code — SURVEY.md §5.7).
Forward: blocked online softmax, never materializing the S x S score
matrix; saves per-row logsumexp for the backward. Backward: two blocked
kernels (dQ with K/V streaming; dK/dV with Q streaming) recomputing
probabilities from the saved logsumexp — memory stays O(block^2) for
training too, which is the whole point for long context.

Layout: q,k,v [batch, heads, seq, head_dim]; grids put batch*heads and the
output-block dim as parallel dimensions and stream the contraction dim as
the innermost "arbitrary" dim with VMEM scratch accumulators.

Dispatch is a rule, not a fallback: on platform `tpu` a call whose shape
the kernels take goes to the kernels, and a kernel the compiler refuses
fails the caller's compile. Calls the rule sends to the XLA reference
(another platform, a shape the kernels do not take) are recorded with the
reason; `pallas_status()` lists the path of every traced call.

Set RAY_TPU_PALLAS_INTERPRET=1 to run the kernels in interpreter mode on
CPU (used by tests to cover kernel logic without a chip). It is a CPU
switch and is refused on platform `tpu`.
"""

from __future__ import annotations

import collections
import functools
import math
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30
_STATS_LANES = 128  # TPU lane width: stats scratch is (block_q, 128)


def _interpret() -> bool:
    return os.environ.get("RAY_TPU_PALLAS_INTERPRET") == "1"


def _platform() -> str:
    return jax.default_backend()


def mha_reference(q, k, v, causal: bool = True,
                  scale: Optional[float] = None) -> jax.Array:
    """XLA reference attention. q,k,v: [batch, heads, seq, head_dim]."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        qs, ks = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((qs, ks), dtype=bool), k=ks - qs)
        logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# --------------------------------------------------------------------------- #
# Forward kernel
# --------------------------------------------------------------------------- #


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, block_q: int, block_k: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body():
        q = q_ref[0].astype(jnp.float32)              # [bq, d]
        k = k_ref[0].astype(jnp.float32)              # [bk, d]
        v = v_ref[0].astype(jnp.float32)              # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_scr[:, :1]                         # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)     # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # [bq, bk]
        correction = jnp.exp(m_prev - m_new)          # [bq, 1]
        l_new = correction * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # Skip blocks entirely above the diagonal.
        @pl.when(ki * block_k <= qi * block_q + (block_q - 1))
        def _run():
            body()
    else:
        body()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        # Row stats kept lane-broadcast: lse is (bh, seq, LANES) in HBM so
        # its blocks are (8, 128)-tileable on TPU; the backward kernels read
        # lane 0. Costs seq*LANES*4B per (b,h) — negligible vs the KV cache
        # and the price of a layout XLA can tile.
        lse_ref[0] = m_scr[...] + jnp.log(
            jnp.maximum(l_scr[...], 1e-30))


def _flash_forward(q, k, v, causal: bool, scale: float,
                   block_q: int, block_k: int):
    """Returns (out [b,h,sq,d], lse [bh, sq, 1]).

    The kernel writes lse lane-broadcast as (bh, sq, LANES) so its blocks
    are (8,128)-tileable, but only lane 0 is returned — the saved training
    residual stays O(seq), not O(seq*128); the backward re-broadcasts
    transiently."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, seq_q, d = q.shape
    seq_k = k.shape[2]
    bh = batch * heads
    q3 = q.reshape(bh, seq_q, d)
    k3 = k.reshape(bh, seq_k, d)
    v3 = v.reshape(bh, seq_k, d)
    nq = pl.cdiv(seq_q, block_q)
    nk = pl.cdiv(seq_k, block_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _STATS_LANES),
                         lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, _STATS_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="flash_fwd",
    )(q3, k3, v3)
    return out.reshape(batch, heads, seq_q, d), lse[..., :1]


# --------------------------------------------------------------------------- #
# Backward kernels
# --------------------------------------------------------------------------- #


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale: float, causal: bool,
                   block_q: int, block_k: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]                        # [bq, 1] (lane 0)
        delta = delta_ref[0][:, :1]                    # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                           # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                  # [bq, bk]
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ki * block_k <= qi * block_q + (block_q - 1))
        def _run():
            body()
    else:
        body()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    causal: bool, block_q: int, block_k: int):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]                        # lane 0
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                           # [bq, bk]
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),           # p^T @ do -> [bk, d]
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                  # [bq, bk]
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),           # ds^T @ q -> [bk, d]
            preferred_element_type=jnp.float32)

    if causal:
        # Q blocks strictly above the diagonal contribute nothing to this
        # K block: skip when the last q row < first k row.
        @pl.when(qi * block_q + (block_q - 1) >= ki * block_k)
        def _run():
            body()
    else:
        body()

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal: bool, scale: float,
                    block_q: int, block_k: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, seq_q, d = q.shape
    seq_k = k.shape[2]
    bh = batch * heads
    q3 = q.reshape(bh, seq_q, d)
    k3 = k.reshape(bh, seq_k, d)
    v3 = v.reshape(bh, seq_k, d)
    do3 = g.reshape(bh, seq_q, d)
    # delta_i = rowsum(dO * O) (the softmax-jacobian diagonal term),
    # broadcast over stats lanes like lse. Both broadcasts are transient
    # kernel inputs, not saved residuals.
    delta = jnp.sum(do3.astype(jnp.float32)
                    * out.reshape(bh, seq_q, d).astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (bh, seq_q, _STATS_LANES))
    lse = jnp.broadcast_to(lse, (bh, seq_q, _STATS_LANES))
    nq = pl.cdiv(seq_q, block_q)
    nk = pl.cdiv(seq_k, block_k)

    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                  block_q=block_q, block_k=block_k)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _STATS_LANES),
                         lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _STATS_LANES),
                         lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q3, k3, v3, do3, lse, delta)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale,
                                   causal=causal, block_q=block_q,
                                   block_k=block_k)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _STATS_LANES),
                         lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _STATS_LANES),
                         lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_k, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, lse, delta)

    shape_q = (batch, heads, seq_q, d)
    shape_k = (batch, heads, seq_k, d)
    return (dq.reshape(shape_q), dk.reshape(shape_k), dv.reshape(shape_k))


# --------------------------------------------------------------------------- #
# Dispatch + custom VJP
# --------------------------------------------------------------------------- #


def pick_block_sizes(seq: int, d: int) -> tuple:
    """Block-size heuristic: biggest blocks that fit VMEM comfortably.
    VMEM budget ~16 MiB; fwd scratch ~ block_q*(2*LANES + d)*4B plus the
    q/k/v/o blocks. Asymmetric q=512/k=1024 measured fastest on v5e for
    d<=128 (fewer grid steps on the streamed contraction dim); shrink for
    bigger heads."""
    if d <= 128:
        bq, bk = 512, 1024
    elif d <= 256:
        bq, bk = 256, 256
    else:
        bq, bk = 128, 128
    while seq % bq and bq > 128:
        bq //= 2
    while seq % bk and bk > 128:
        bk //= 2
    return bq, bk


# (pass, path, reason, shape, dtype, block_q, block_k) -> traced calls
_CALLS: collections.Counter = collections.Counter()
_CALLS_LOCK = threading.Lock()


def pallas_status() -> list:
    """Which path every traced attention call of this process took: one
    entry per distinct (pass, shape, dtype, blocks) with `path` "pallas"
    or "reference", the dispatch rule's `reason` for a reference call, and
    the number of traced calls. A caller that asked for flash and needs to
    know it got flash (chip_smoke.py, bench.py) reads this."""
    with _CALLS_LOCK:
        items = list(_CALLS.items())
    return [{"pass": p, "path": path, "reason": reason, "shape": list(shape),
             "dtype": dtype, "block_q": bq, "block_k": bk, "calls": n}
            for (p, path, reason, shape, dtype, bq, bk), n in items]


def reset_pallas_status() -> None:
    """Forget the calls traced so far (a caller that asserts on one
    program's calls clears what model init traced before it)."""
    with _CALLS_LOCK:
        _CALLS.clear()


def _dispatch(pass_: str, q, k, block_q: int, block_k: int) -> bool:
    """True when the Pallas kernels take this call. Records the decision."""
    platform = _platform()
    seq_q, d = q.shape[2], q.shape[3]
    seq_k = k.shape[2]
    if _interpret() and platform == "tpu":
        raise RuntimeError(
            "RAY_TPU_PALLAS_INTERPRET=1 is a CPU test switch; on platform "
            "tpu it would run the interpreter under the kernels' name")
    if platform != "tpu" and not _interpret():
        reason = f"platform {platform}"
    elif seq_q != seq_k:
        # The kernel's causal mask assumes q and k positions share origin
        # 0, while mha_reference aligns sequence *ends* (tril k=ks-qs).
        reason = "seq_q != seq_k"
    elif seq_q % block_q or seq_k % block_k:
        reason = "seq not a multiple of the block"
    elif d % 64:
        reason = "head_dim not a multiple of 64"
    else:
        reason = ""
    key = (pass_, "reference" if reason else "pallas", reason,
           tuple(q.shape), jnp.dtype(q.dtype).name, block_q, block_k)
    with _CALLS_LOCK:
        _CALLS[key] += 1
    return not reason


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = 0, block_k: int = 0) -> jax.Array:
    """Blocked attention. q,k,v: [batch, heads, seq, head_dim].

    Dispatches to the Pallas kernels on TPU (shapes permitting; block size 0
    = auto) and the XLA reference elsewhere. Fully differentiable with a
    flash backward — training memory stays O(seq * block).
    """
    out, _ = _attn_fwd_impl(q, k, v, causal, scale, block_q, block_k)
    return out


def _resolve(q, scale, block_q, block_k):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    seq = q.shape[2]
    if not block_q or not block_k:
        block_q, block_k = pick_block_sizes(seq, q.shape[-1])
    return scale, min(block_q, seq), min(block_k, seq)


def _attn_fwd_impl(q, k, v, causal, scale, block_q, block_k):
    scale, bq, bk = _resolve(q, scale, block_q, block_k)
    if _dispatch("fwd", q, k, bq, bk):
        return _flash_forward(q, k, v, causal, scale, bq, bk)
    return mha_reference(q, k, v, causal=causal, scale=scale), None


def _attn_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _attn_fwd_impl(q, k, v, causal, scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _attn_bwd(causal, scale, block_q, block_k, residuals, g):
    q, k, v, out, lse = residuals
    scale_v, bq, bk = _resolve(q, scale, block_q, block_k)
    if _dispatch("bwd", q, k, bq, bk):
        return _flash_backward(q, k, v, out, lse, g, causal, scale_v, bq, bk)
    _, vjp = jax.vjp(lambda q, k, v: mha_reference(q, k, v, causal, scale),
                     q, k, v)
    return vjp(g)


flash_attention.defvjp(_attn_fwd, _attn_bwd)


def flash_attention_sharded(q, k, v, spec, causal: bool = True,
                            scale: Optional[float] = None,
                            block_q: int = 0, block_k: int = 0) -> jax.Array:
    """`flash_attention` inside a partitioned jit.

    The partitioner cannot split a Pallas custom call: left bare it
    gathers q/k/v and every device runs the whole batch. `spec` is the
    PartitionSpec of the [batch, heads, seq, head_dim] operands over the
    context mesh (`jax.set_mesh`); each device then runs the kernels on its
    own [b/dp, h/tp, s, d] shard. The sequence dim stays whole — sharding
    it is ring attention's job. Without a context mesh, or with nothing to
    shard, this is `flash_attention`."""
    def local(q, k, v):
        return flash_attention(q, k, v, causal, scale, block_q, block_k)

    if jax.sharding.get_abstract_mesh().empty or not any(spec):
        return local(q, k, v)
    return jax.shard_map(local, in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)(q, k, v)
