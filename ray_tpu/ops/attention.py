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
# The causal tile schedule
# --------------------------------------------------------------------------- #
#
# A grid block is what one DMA brings into VMEM; the score square is counted
# in (tile_q, tile_k) tiles. Inside a grid block the kernels compute STRIPES:
# the forward and dQ kernels take tile_q rows at a time against exactly the
# columns those rows may see, the dK/dV kernel takes tile_k columns against
# the rows that may see them. A stripe's extent is static: how the diagonal
# crosses a grid block depends only on `off`, the block's first row minus its
# first column, which takes a handful of values over the grid; the kernels
# hold one specialisation of their body for each. Only the tiles the
# diagonal crosses pay for the mask. Why stripes and not a loop over tiles:
# every row of a tile pays for its softmax statistics, lane broadcasts and
# accumulator updates once per tile it is in, and a 256-wide tile is too
# narrow to carry that (PERF.md section 6, PR 30).

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _masked(s, lo: int, hi: int, shift: int, q_axis: int = 0):
    """Scores with the causal mask on columns [lo, hi) only. Along
    `q_axis` run query positions, along the other key positions; the first
    query position of the piece sits `shift` after its first key position,
    and a score stays where q_pos >= k_pos."""
    if lo == hi:
        return s
    shape = (s.shape[0], hi - lo)
    keep = (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
            ) >= -shift
    parts = [s[:, :lo], jnp.where(keep, s[:, lo:hi], _NEG_INF), s[:, hi:]]
    parts = [x for x in parts if x.shape[1]]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _eye(rows: int, cols: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _column(row):
    """[1, n] -> [n, 1] without a transpose: the diagonal of the row's
    sublane broadcast, summed over lanes."""
    n = row.shape[1]
    return jnp.sum(jnp.where(_eye(n, n), jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


def _store_row(ref, stat):
    """Write per-row statistics held lane-replicated, stat [n, LANES] with
    stat[i, :] == x_i, as the row ref[0] = [1, n]: the diagonal of each
    LANES-row chunk, summed over sublanes."""
    n = stat.shape[0]
    for lo in range(0, n, _STATS_LANES):
        m = min(_STATS_LANES, n - lo)
        chunk = jnp.where(_eye(m, _STATS_LANES), stat[lo:lo + m], 0.0)
        ref[0, :, lo:lo + m] = jnp.sum(chunk, axis=0, keepdims=True)[:, :m]


def _row_stripes(off, block_q: int, block_k: int, tile_q: int, tile_k: int):
    """[(row, full, live)]: the tile_q rows from `row` see the block's
    columns [0, live); columns [0, full) need no mask. `off` None: no
    diagonal in this block."""
    out = []
    for row in range(0, block_q, tile_q):
        if off is None:
            out.append((row, block_k, block_k))
            continue
        last = off + row + tile_q - 1        # last column the stripe sees
        live = min(block_k, max(0, (last // tile_k + 1) * tile_k))
        full = min(live, max(0, (off + row + 1) // tile_k * tile_k))
        if live:
            out.append((row, full, live))
    return out


def _col_stripes(off, block_q: int, block_k: int, tile_q: int, tile_k: int):
    """[(col, first, full)]: the tile_k columns from `col` are seen by the
    block's rows [first, block_q); rows [full, block_q) need no mask."""
    out = []
    for col in range(0, block_k, tile_k):
        if off is None:
            out.append((col, 0, 0))
            continue
        first = min(block_q, max(0, (col - off) // tile_q * tile_q))
        full = min(block_q,
                   max(first, -((off - col - tile_k + 1) // tile_q) * tile_q))
        if first < block_q:
            out.append((col, first, full))
    return out


def _by_offset(causal: bool, off, block_q: int, block_k: int, n_q: int,
               n_k: int, run):
    """Call `run(static off)` under a guard for every way the diagonal
    crosses a block of this grid, `run(None)` for the blocks below it (or
    when there is no mask); blocks above it run nothing."""
    from jax.experimental import pallas as pl

    if not causal:
        return run(None)
    offs = {i * block_q - j * block_k for i in range(n_q) for j in range(n_k)}
    if any(o >= block_k - 1 for o in offs):
        pl.when(off >= block_k - 1)(lambda: run(None))
    for o in sorted(o for o in offs if -block_q < o < block_k - 1):
        pl.when(off == o)(functools.partial(run, o))


def _tile_counts(seq: int, tile_q: int, tile_k: int, causal: bool) -> tuple:
    """(tiles in the seq x seq square, tiles the stripes cover), per
    (batch, head)."""
    nq, nk = seq // tile_q, seq // tile_k
    if not causal:
        return nq * nk, nq * nk
    return nq * nk, sum(min(((i + 1) * tile_q - 1) // tile_k + 1, nk)
                        for i in range(nq))


# --------------------------------------------------------------------------- #
# Forward kernel
# --------------------------------------------------------------------------- #


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                tile_q: int, tile_k: int, n_q: int, n_k: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def run(off):
        for row, full, live in _row_stripes(off, block_q, block_k, tile_q,
                                            tile_k):
            rows = pl.ds(row, tile_q)
            q = q_ref[0, rows, :].astype(jnp.float32)         # [tq, d]
            k = k_ref[0, :live, :].astype(jnp.float32)        # [live, d]
            v = v_ref[0, :live, :].astype(jnp.float32)
            s = _dot(q, k, _NT) * scale                       # [tq, live]
            if off is not None:
                s = _masked(s, full, live, off + row - full)
            m_prev = m_scr[rows, :][:, :1]                    # [tq, 1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)                            # [tq, live]
            correction = jnp.exp(m_prev - m_new)              # [tq, 1]
            l_new = (correction * l_scr[rows, :][:, :1]
                     + jnp.sum(p, axis=1, keepdims=True))
            acc_scr[rows, :] = (acc_scr[rows, :] * correction
                                + _dot(p, v, _NN))
            m_scr[rows, :] = jnp.broadcast_to(m_new, (tile_q, _STATS_LANES))
            l_scr[rows, :] = jnp.broadcast_to(l_new, (tile_q, _STATS_LANES))

    _by_offset(causal, qi * block_q - ki * block_k, block_q, block_k, n_q,
               n_k, run)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        # lse leaves as a row, (bh, 1, seq) in HBM: lane-dense, O(seq), and
        # the layout both backward kernels read.
        _store_row(lse_ref, m_scr[...] + jnp.log(
            jnp.maximum(l_scr[...], 1e-30)))


# The two wrappers are jitted on their own so that a model's layers share ONE
# trace and ONE lowering of each kernel: 24 layers otherwise lower the three
# kernels to Mosaic 24 times on every process start, compile-cache hit or
# not (PERF.md section 6, PR 25 and PR 30). `interpret` is an argument so
# that the trace is keyed by it.
_STATIC = ("causal", "scale", "block_q", "block_k", "tile_q", "tile_k",
           "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_forward(q, k, v, causal: bool, scale: float,
                   block_q: int, block_k: int, tile_q: int, tile_k: int,
                   interpret: bool = False):
    """Returns (out [b,h,sq,d], lse [bh, 1, sq]): the per-row logsumexp as
    a row per (batch, head), which is the saved training residual (O(seq))
    and what the backward kernels read as it is."""
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
                               block_q=block_q, block_k=block_k,
                               tile_q=tile_q, tile_k=tile_k, n_q=nq, n_k=nk)
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
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)
    return out.reshape(batch, heads, seq_q, d), lse


# --------------------------------------------------------------------------- #
# Backward kernels
# --------------------------------------------------------------------------- #


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale: float, causal: bool,
                   block_q: int, block_k: int, tile_q: int, tile_k: int,
                   n_q: int, n_k: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def run(off):
        for row, full, live in _row_stripes(off, block_q, block_k, tile_q,
                                            tile_k):
            rows = pl.ds(row, tile_q)
            q = q_ref[0, rows, :].astype(jnp.float32)      # [tq, d]
            do = do_ref[0, rows, :].astype(jnp.float32)
            k = k_ref[0, :live, :].astype(jnp.float32)     # [live, d]
            v = v_ref[0, :live, :].astype(jnp.float32)
            lse = _column(lse_ref[0, :, rows])             # [tq, 1]
            delta = _column(delta_ref[0, :, rows])
            s = _dot(q, k, _NT) * scale                    # [tq, live]
            if off is not None:
                s = _masked(s, full, live, off + row - full)
            p = jnp.exp(s - lse)
            dp = _dot(do, v, _NT)
            ds = p * (dp - delta) * scale                  # [tq, live]
            dq_scr[rows, :] += _dot(ds, k, _NN)

    _by_offset(causal, qi * block_q - ki * block_k, block_q, block_k, n_q,
               n_k, run)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    causal: bool, block_q: int, block_k: int,
                    tile_q: int, tile_k: int, n_q: int, n_k: int):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def run(off):
        # Transposed throughout, s^T = K Q^T: P^T dO and dS^T Q are then
        # plain products, and lse / delta are wanted as the rows they are.
        for col, first, full in _col_stripes(off, block_q, block_k, tile_q,
                                             tile_k):
            cols = pl.ds(col, tile_k)
            k = k_ref[0, cols, :].astype(jnp.float32)        # [tk, d]
            v = v_ref[0, cols, :].astype(jnp.float32)
            q = q_ref[0, first:, :].astype(jnp.float32)      # [rows, d]
            do = do_ref[0, first:, :].astype(jnp.float32)
            lse = lse_ref[0, :, first:]                      # [1, rows]
            delta = delta_ref[0, :, first:]
            st = _dot(k, q, _NT) * scale                     # [tk, rows]
            if off is not None:
                st = _masked(st, 0, full - first, off + first - col, q_axis=1)
            pt = jnp.exp(st - lse)
            dv_scr[cols, :] += _dot(pt, do, _NN)             # [tk, d]
            dpt = _dot(v, do, _NT)
            dst = pt * (dpt - delta) * scale                 # [tk, rows]
            dk_scr[cols, :] += _dot(dst, q, _NN)

    _by_offset(causal, qi * block_q - ki * block_k, block_q, block_k, n_q,
               n_k, run)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_backward(q, k, v, out, lse, g, causal: bool, scale: float,
                    block_q: int, block_k: int, tile_q: int, tile_k: int,
                    interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, seq_q, d = q.shape
    seq_k = k.shape[2]
    bh = batch * heads
    q3 = q.reshape(bh, seq_q, d)
    k3 = k.reshape(bh, seq_k, d)
    v3 = v.reshape(bh, seq_k, d)
    do3 = g.reshape(bh, seq_q, d)
    # delta_i = rowsum(dO * O) (the softmax-jacobian diagonal term), a row
    # per (batch, head) like lse.
    delta = jnp.sum(do3.astype(jnp.float32)
                    * out.reshape(bh, seq_q, d).astype(jnp.float32),
                    axis=-1).reshape(bh, 1, seq_q)
    nq = pl.cdiv(seq_q, block_q)
    nk = pl.cdiv(seq_k, block_k)

    tiles = dict(block_q=block_q, block_k=block_k, tile_q=tile_q,
                 tile_k=tile_k, n_q=nq, n_k=nk)
    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                  **tiles)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, do3, lse, delta)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale,
                                   causal=causal, **tiles)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
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
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, lse, delta)

    shape_q = (batch, heads, seq_q, d)
    shape_k = (batch, heads, seq_k, d)
    return (dq.reshape(shape_q), dk.reshape(shape_k), dv.reshape(shape_k))


# --------------------------------------------------------------------------- #
# Dispatch + custom VJP
# --------------------------------------------------------------------------- #


def pick_block_sizes(seq: int, d: int, causal: bool = True) -> tuple:
    """(block_q, block_k, tile_q, tile_k) for a [*, *, seq, d] call: a pure
    function of the shape and the mask.

    Grid blocks stay as large as VMEM takes comfortably. Measured on one
    v5e at [8,16,1024,64] bf16 causal (my chip runs, PR 30; us a call, fwd /
    dq / dkv, same tiles): q blocks of 256 / 512 / 1024 rows against a
    1024-row k block took 782 / 678 / 563, 500 / 424 / 391, 925 / 717 /
    631: a grid step costs 0.4-0.9 us, about what a 256 x 256 tile's
    matmuls take. So at d <= 128 one block covers 1024 positions both ways
    (with k = 1024 the 512 x 1024 blocks of before PR 30 computed the whole
    square at seq 1024: the grid-level skip never fired), and the causal
    skip happens inside a block, on tiles.

    Tiles are what the causal schedule counts in and the finest extent a
    stripe is cut to (see "The causal tile schedule"). tile_k = 128 lets
    the dK/dV stripes start at the diagonal to the lane tile. tile_q is the
    height of a forward / dQ stripe: 128 where every grid block sits on the
    diagonal (seq within one block: 464 against 563 us forward at
    [8,16,1024,64], and 36 of 64 tiles run against 40), 256 where most
    blocks lie below it or there is no mask, and a stripe is as wide as the
    block ([1,12,8192,64]: 2,203 against 2,304 us; non-causal
    [8,16,1024,64]: 604 against 706). Wider heads keep the blocks they had
    (not measured), one tile each."""
    if d <= 128:
        bq = bk = 1024
        tq, tk = (128 if causal and seq <= bk else 256), 128
    elif d <= 256:
        bq = bk = tq = tk = 256
    else:
        bq = bk = tq = tk = 128
    while seq % bq and bq > 128:
        bq //= 2
    while seq % bk and bk > 128:
        bk //= 2
    return bq, bk, min(tq, bq), min(tk, bk)


# (pass, path, reason, shape, dtype, block_q, block_k) -> traced calls; the
# flash passes append (causal, tiles, tiles_live) to theirs
_CALLS: collections.Counter = collections.Counter()
_CALLS_LOCK = threading.Lock()


def pallas_status() -> list:
    """Which path every traced attention call of this process took: one
    entry per distinct (pass, shape, dtype, blocks) with `path` "pallas"
    or "reference", the dispatch rule's `reason` for a reference call, and
    the number of traced calls. A caller that asked for flash and needs to
    know it got flash (chip_smoke.py, bench.py) reads this.

    Entries of the flash passes (`fwd`, `bwd`) also say `causal`, and on
    the Pallas path `tiles` and `tiles_live`: the (tile_q, tile_k) tiles in
    one (batch, head)'s score square and those whose body the kernels run
    (None for a reference call). `tiles_live / tiles` near 1.0 on a causal
    call means the causal skip is dead at that shape."""
    with _CALLS_LOCK:
        items = list(_CALLS.items())
    out = []
    for (p, path, reason, shape, dtype, bq, bk, *schedule), n in items:
        out.append({"pass": p, "path": path, "reason": reason,
                    "shape": list(shape), "dtype": dtype, "block_q": bq,
                    "block_k": bk, "calls": n,
                    **dict(zip(("causal", "tiles", "tiles_live"), schedule))})
    return out


def reset_pallas_status() -> None:
    """Forget the calls traced so far (a caller that asserts on one
    program's calls clears what model init traced before it)."""
    with _CALLS_LOCK:
        _CALLS.clear()


def _dispatch(pass_: str, q, k, causal: bool, blocks: tuple) -> bool:
    """True when the Pallas kernels take this call. Records the decision."""
    platform = _platform()
    seq_q, d = q.shape[2], q.shape[3]
    seq_k = k.shape[2]
    block_q, block_k, tile_q, tile_k = blocks
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
    tiles = (None, None) if reason else _tile_counts(seq_q, tile_q, tile_k,
                                                     causal)
    key = (pass_, "reference" if reason else "pallas", reason,
           tuple(q.shape), jnp.dtype(q.dtype).name, block_q, block_k,
           causal, *tiles)
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


def _resolve(q, causal, scale, block_q, block_k):
    """(scale, (block_q, block_k, tile_q, tile_k)): explicit blocks keep
    the rule's tiles, cut to the block."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    seq = q.shape[2]
    bq, bk, tq, tk = pick_block_sizes(seq, q.shape[-1], causal)
    if block_q and block_k:
        bq, bk = block_q, block_k
    bq, bk = min(bq, seq), min(bk, seq)
    return scale, (bq, bk, min(tq, bq), min(tk, bk))


def _attn_fwd_impl(q, k, v, causal, scale, block_q, block_k):
    scale, blocks = _resolve(q, causal, scale, block_q, block_k)
    if _dispatch("fwd", q, k, causal, blocks):
        return _flash_forward(q, k, v, causal, scale, *blocks,
                              interpret=_interpret())
    return mha_reference(q, k, v, causal=causal, scale=scale), None


def _attn_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _attn_fwd_impl(q, k, v, causal, scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _attn_bwd(causal, scale, block_q, block_k, residuals, g):
    q, k, v, out, lse = residuals
    scale_v, blocks = _resolve(q, causal, scale, block_q, block_k)
    if _dispatch("bwd", q, k, causal, blocks):
        return _flash_backward(q, k, v, out, lse, g, causal, scale_v,
                               *blocks, interpret=_interpret())
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
