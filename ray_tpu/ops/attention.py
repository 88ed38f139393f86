"""Flash attention: Pallas TPU kernels, forward AND backward.

Net-new TPU capability (the reference has no kernel code — SURVEY.md §5.7).
Forward: blocked online softmax, never materializing the S x S score
matrix; saves per-row logsumexp for the backward. Backward: two blocked
kernels (dQ with K/V streaming; dK/dV with Q streaming) recomputing
probabilities from the saved logsumexp — memory stays O(block^2) for
training too, which is the whole point for long context.

Layout: the kernels read and write [batch, seq, heads*head_dim] arrays, the
layout the projections beside attention produce and consume, and find a
head by a BlockSpec COLUMN block: `flash_attention_bse` takes a layer's
fused [batch, seq, 3*heads*head_dim] projection as three views of one
array (or q, k, v apart) and returns [batch, seq, heads*head_dim], with no
split, reshape or transpose around the kernels. A column block is
max(128, head_dim) lanes: at head_dim 64 two adjacent heads, which the
bodies tell apart by a lane mask (no operand is ever 64 lanes wide in HBM
or cut at lane 64 in VMEM). `flash_attention` on [batch, heads, seq,
head_dim] is the same kernels on the free reshape [batch*heads, seq,
head_dim], one head a column block: for callers whose q, k, v are already
per head (ring attention's rotating chunks, GQA after its repeat).
`flash_attention_bse` also takes k and v of FEWER heads than q (grouped
queries): where a column block is one head, the query head in column block
c reads column block c // group of k and v, and dK / dV are summed over
the group in VMEM and written once at the KV heads' width. Grids
put batch, the column block and the output-block dim as parallel
dimensions and stream the contraction dim as the innermost "arbitrary" dim
with VMEM scratch accumulators.

Dispatch is a rule, not a fallback: on platform `tpu` a call whose shape
the kernels take goes to the kernels, and a kernel the compiler refuses
fails the caller's compile. Calls the rule sends to the XLA reference
(another platform, a shape the kernels do not take) are recorded with the
reason; `pallas_status()` lists the path of every traced call.

Under a remat: the backward reads the operands, `out` and the logsumexp,
and only the forward kernel can make the last two. `_flash_fwd` gives them
the names `flash_out` and `flash_lse` (`jax.ad_checkpoint.checkpoint_name`).
A caller's `jax.checkpoint` whose policy keeps both
(`save_only_these_names`) makes the operands again from its own input and
does NOT run `flash_fwd` a second time; one that keeps neither runs it
twice, as every policy-less checkpoint does. A name is the identity
anywhere else: outside such a checkpoint a call compiles to the program it
compiled to without the names.

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
from jax.ad_checkpoint import checkpoint_name

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


def _store_row(ref, h: int, stat):
    """Write per-row statistics held lane-replicated, stat [n, LANES] with
    stat[i, :] == x_i, as the row ref[0, h] = [1, n]: the diagonal of each
    LANES-row chunk, summed over sublanes."""
    n = stat.shape[0]
    for lo in range(0, n, _STATS_LANES):
        m = min(_STATS_LANES, n - lo)
        chunk = jnp.where(_eye(m, _STATS_LANES), stat[lo:lo + m], 0.0)
        ref[0, h, :, lo:lo + m] = jnp.sum(chunk, axis=0,
                                          keepdims=True)[:, :m]


# Heads in a column block. A block of `lanes` lanes holds lanes // d heads
# side by side. The bodies never slice it at a head's edge: a head's scores
# are the product of the block with the OTHER heads' lanes of one small
# operand zeroed (the contraction runs over all the lanes, which costs the
# 128-deep MXU no more than 64 of them), and a product that writes head
# columns is taken for the whole block and kept on that head's lanes.


def _head_lanes(n: int, lanes: int, d: int, h: int):
    """bool [n, lanes]: the lanes of the block's head h. Compares, not
    `lane // d == h`: Mosaic lowers every integer division through a traced
    helper, 5 ms of each process start apiece (PERF.md section 6, PR 33)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, lanes), 1)
    lo, hi = h * d, (h + 1) * d
    if hi == lanes:
        return lane >= lo
    return lane < hi if lo == 0 else (lane >= lo) & (lane < hi)


def _only_head(x, d: int, h: int):
    """x [n, lanes] with every lane outside head h zeroed."""
    if x.shape[1] == d:
        return x
    return jnp.where(_head_lanes(*x.shape, d, h), x, 0.0)


def _by_head(parts, n: int, lanes: int, d: int):
    """[n, lanes] that reads parts[h] ([n, lanes] or [n, 1]) on head h's
    lanes."""
    out = parts[-1]
    for h in range(len(parts) - 2, -1, -1):
        out = jnp.where(_head_lanes(n, lanes, d, h), parts[h], out)
    return jnp.broadcast_to(out, (n, lanes))


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
#
# Every kernel sees [batch, seq, width] operands through column blocks of
# `lanes` lanes (grid axis 1) that hold lanes // d heads; lse and delta are
# rows, [batch, heads, 1, seq], a block of them the rows of the column
# block's heads.


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                d: int, scale: float, causal: bool, block_q: int,
                block_k: int, tile_q: int, tile_k: int, n_q: int, n_k: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    ki = pl.program_id(3)
    lanes = acc_scr.shape[1]
    heads = range(lanes // d)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def run(off):
        for row, full, live in _row_stripes(off, block_q, block_k, tile_q,
                                            tile_k):
            rows = pl.ds(row, tile_q)
            q = q_ref[0, rows, :].astype(jnp.float32)         # [tq, lanes]
            k = k_ref[0, :live, :].astype(jnp.float32)        # [live, lanes]
            v = v_ref[0, :live, :].astype(jnp.float32)
            corrections, pvs = [], []
            for h in heads:
                s = _dot(_only_head(q, d, h), k, _NT) * scale  # [tq, live]
                if off is not None:
                    s = _masked(s, full, live, off + row - full)
                m_prev = m_scr[h, rows, :][:, :1]             # [tq, 1]
                m_cur = jnp.max(s, axis=1, keepdims=True)
                m_new = jnp.maximum(m_prev, m_cur)
                p = jnp.exp(s - m_new)                        # [tq, live]
                correction = jnp.exp(m_prev - m_new)          # [tq, 1]
                l_new = (correction * l_scr[h, rows, :][:, :1]
                         + jnp.sum(p, axis=1, keepdims=True))
                corrections.append(correction)
                pvs.append(_dot(p, v, _NN))                   # [tq, lanes]
                m_scr[h, rows, :] = jnp.broadcast_to(
                    m_new, (tile_q, _STATS_LANES))
                l_scr[h, rows, :] = jnp.broadcast_to(
                    l_new, (tile_q, _STATS_LANES))
            acc_scr[rows, :] = (
                acc_scr[rows, :] * _by_head(corrections, tile_q, lanes, d)
                + _by_head(pvs, tile_q, lanes, d))

    _by_offset(causal, qi * block_q - ki * block_k, block_q, block_k, n_q,
               n_k, run)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        denom = _by_head([jnp.maximum(l_scr[h][:, :1], 1e-30) for h in heads],
                         block_q, lanes, d)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        # lse leaves as a row per head: lane-dense, O(seq), and the layout
        # both backward kernels read.
        for h in heads:
            _store_row(lse_ref, h, m_scr[h] + jnp.log(
                jnp.maximum(l_scr[h], 1e-30)))


def _column_block(e: int, d: int) -> int:
    """Lanes of a column block on operands that hold e // d heads of d
    lanes side by side: max(128, d), so two heads at d = 64; a single head
    is its own block whatever its width."""
    return d if e == d else max(_STATS_LANES, d)


def _specs(e: int, d: int, fused: bool, block_q: int, block_k: int, where,
           group: int = 1):
    """BlockSpecs of a kernel's operands on [batch, seq, width] arrays of
    e // d query heads, by name. `where` takes a grid step to (batch, column
    block, q block, k block). `fused`: q, k and v are the thirds of ONE
    [batch, seq, 3e] array, taken as three views of it with the column
    index moved on by a third. `group` > 1: k and v are [batch, seq,
    e // group] arrays of one KV head a column block, and the query head in
    column block c reads (and "kv" writes) column block c // group of them:
    a KV head is read in place by its whole group, never repeated."""
    from jax.experimental import pallas as pl

    lanes = _column_block(e, d)
    third = e // lanes if fused else 0

    def block(rows, pick, shift=0, share=1):
        def index(*step):
            b, c, i, j = where(*step)
            if share > 1:
                c = jax.lax.div(c, share)
            return b, (i, j)[pick], c + shift
        return pl.BlockSpec((1, rows, lanes), index)

    def stat_index(*step):
        b, c, i, _ = where(*step)
        return b, c, 0, i

    return {"q": block(block_q, 0), "k": block(block_k, 1, third, group),
            "v": block(block_k, 1, 2 * third, group),
            # a [batch, seq, e] array by q blocks (o, dO, dQ); dK and dV,
            # [batch, seq, e // group], by k blocks
            "rows": block(block_q, 0), "kv": block(block_k, 1, 0, group),
            "stat": pl.BlockSpec((1, lanes // d, 1, block_q), stat_index),
            "lanes": lanes}


def _live(causal: bool, block_q: int, block_k: int, n_q: int, n_k: int,
          streams_q: bool = False):
    """Takes the (q block, k block) of a grid step to the blocks its
    operands are read at: its own, except that a step above the diagonal
    (whose body runs nothing) names the streamed operand's nearest live
    block again, the one the pipeline holds already, so that no block is
    fetched for it (at [1,16,8192,256] a dead step's 1 MB of k and v cost
    the forward 0.45 ms of 4.63: my chip runs, PR 44). `streams_q`: the q
    blocks are the streamed axis (dK/dV); else the k blocks are."""
    if not causal or n_q == n_k == 1:
        return lambda i, j: (i, j)
    if streams_q:
        return lambda i, j: (
            jnp.maximum(i, jax.lax.div(j * block_k, block_q)), j)
    return lambda i, j: (
        i, jnp.minimum(j, jax.lax.div((i + 1) * block_q - 1, block_k)))


# The two wrappers are jitted on their own so that a model's layers share ONE
# trace and ONE lowering of each kernel: 24 layers otherwise lower the three
# kernels to Mosaic 24 times on every process start, compile-cache hit or
# not (PERF.md section 6, PR 25 and PR 30). `interpret` is an argument so
# that the trace is keyed by it.
_STATIC = ("d", "fused", "causal", "scale", "block_q", "block_k", "tile_q",
           "tile_k", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_forward(q, k, v, d: int, fused: bool, causal: bool, scale: float,
                   block_q: int, block_k: int, tile_q: int, tile_k: int,
                   interpret: bool = False):
    """q [batch, seq, e], k and v [batch, seq, e // group] (`fused`: the
    same [batch, seq, 3e] array three times). Returns (out [batch, seq,
    e], lse [batch, heads, 1, seq]): the per-row logsumexp as a row per
    (batch, head), which is the saved training residual (O(seq)) and what
    the backward kernels read as it is."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, width = q.shape
    seq_k = k.shape[1]
    e = width // 3 if fused else width
    nq = pl.cdiv(seq_q, block_q)
    nk = pl.cdiv(seq_k, block_k)
    live = _live(causal, block_q, block_k, nq, nk)
    sp = _specs(e, d, fused, block_q, block_k,
                lambda b, c, i, j: (b, c, *live(i, j)),
                group=1 if fused else e // k.shape[2])
    lanes = sp["lanes"]
    kernel = functools.partial(_fwd_kernel, d=d, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               tile_q=tile_q, tile_k=tile_k, n_q=nq, n_k=nk)
    return pl.pallas_call(
        kernel,
        grid=(batch, e // lanes, nq, nk),
        in_specs=[sp["q"], sp["k"], sp["v"]],
        out_specs=[sp["rows"], sp["stat"]],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq_q, e), q.dtype),
            jax.ShapeDtypeStruct((batch, e // d, 1, seq_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((lanes // d, block_q, _STATS_LANES), jnp.float32),
            pltpu.VMEM((lanes // d, block_q, _STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# --------------------------------------------------------------------------- #
# Backward kernels
# --------------------------------------------------------------------------- #


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
                   delta_ref, dq_scr, delta_scr, *, d: int, scale: float,
                   causal: bool, block_q: int, block_k: int, tile_q: int,
                   tile_k: int, n_q: int, n_k: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    ki = pl.program_id(3)
    lanes = dq_scr.shape[1]
    heads = range(lanes // d)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # delta_i = rowsum(dO * O) over a head's lanes (the softmax
        # jacobian's diagonal term): kept lane-replicated for this kernel's
        # columns, and written as a row per head, like lse, for dK/dV.
        prod = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        for h in heads:
            delta = jnp.broadcast_to(
                jnp.sum(_only_head(prod, d, h), axis=1, keepdims=True),
                (block_q, _STATS_LANES))
            delta_scr[h] = delta
            _store_row(delta_ref, h, delta)

    def run(off):
        for row, full, live in _row_stripes(off, block_q, block_k, tile_q,
                                            tile_k):
            rows = pl.ds(row, tile_q)
            q = q_ref[0, rows, :].astype(jnp.float32)      # [tq, lanes]
            do = do_ref[0, rows, :].astype(jnp.float32)
            k = k_ref[0, :live, :].astype(jnp.float32)     # [live, lanes]
            v = v_ref[0, :live, :].astype(jnp.float32)
            dqs = []
            for h in heads:
                lse = _column(lse_ref[0, h, :, rows])      # [tq, 1]
                delta = delta_scr[h, rows, :][:, :1]
                s = _dot(_only_head(q, d, h), k, _NT) * scale  # [tq, live]
                if off is not None:
                    s = _masked(s, full, live, off + row - full)
                p = jnp.exp(s - lse)
                dp = _dot(_only_head(do, d, h), v, _NT)
                ds = p * (dp - delta) * scale              # [tq, live]
                dqs.append(_dot(ds, k, _NN))               # [tq, lanes]
            dq_scr[rows, :] += _by_head(dqs, tile_q, lanes, d)

    _by_offset(causal, qi * block_q - ki * block_k, block_q, block_k, n_q,
               n_k, run)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, d: int, scale: float,
                    causal: bool, block_q: int, block_k: int,
                    tile_q: int, tile_k: int, n_q: int, n_k: int,
                    group: int = 1):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    # The streamed axis: the q blocks, and around them the `group` query
    # heads that read this KV head, summed into the one dK / dV it has.
    turn = pl.program_id(3)
    qi = turn if group == 1 else jax.lax.rem(turn, n_q)
    lanes = dk_scr.shape[1]
    heads = range(lanes // d)

    @pl.when(turn == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def run(off):
        # Transposed throughout, s^T = K Q^T: P^T dO and dS^T Q are then
        # plain products, and lse / delta are wanted as the rows they are.
        for col, first, full in _col_stripes(off, block_q, block_k, tile_q,
                                             tile_k):
            cols = pl.ds(col, tile_k)
            k = k_ref[0, cols, :].astype(jnp.float32)        # [tk, lanes]
            v = v_ref[0, cols, :].astype(jnp.float32)
            q = q_ref[0, first:, :].astype(jnp.float32)      # [rows, lanes]
            do = do_ref[0, first:, :].astype(jnp.float32)
            dks, dvs = [], []
            for h in heads:
                lse = lse_ref[0, h, :, first:]               # [1, rows]
                delta = delta_ref[0, h, :, first:]
                st = _dot(_only_head(k, d, h), q, _NT) * scale  # [tk, rows]
                if off is not None:
                    st = _masked(st, 0, full - first, off + first - col,
                                 q_axis=1)
                pt = jnp.exp(st - lse)
                dvs.append(_dot(pt, do, _NN))                # [tk, lanes]
                dpt = _dot(_only_head(v, d, h), do, _NT)
                dst = pt * (dpt - delta) * scale             # [tk, rows]
                dks.append(_dot(dst, q, _NN))
            dv_scr[cols, :] += _by_head(dvs, tile_k, lanes, d)
            dk_scr[cols, :] += _by_head(dks, tile_k, lanes, d)

    _by_offset(causal, qi * block_q - ki * block_k, block_q, block_k, n_q,
               n_k, run)

    @pl.when(turn == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_backward(q, k, v, out, lse, g, d: int, fused: bool, causal: bool,
                    scale: float, block_q: int, block_k: int, tile_q: int,
                    tile_k: int, interpret: bool = False):
    """(dq, dk, dv), each the shape of its operand, of the operands
    `_flash_forward` took; `out`, `g` [batch, seq, e], `lse` as it returned
    it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, e = out.shape
    seq_k = k.shape[1]
    nq = pl.cdiv(seq_q, block_q)
    nk = pl.cdiv(seq_k, block_k)
    lanes = _column_block(e, d)
    group = 1 if fused else e // k.shape[2]
    stats = jax.ShapeDtypeStruct((batch, e // d, 1, seq_q), jnp.float32)
    tiles = dict(d=d, scale=scale, causal=causal, block_q=block_q,
                 block_k=block_k, tile_q=tile_q, tile_k=tile_k, n_q=nq,
                 n_k=nk)
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))

    live = _live(causal, block_q, block_k, nq, nk)
    sp = _specs(e, d, fused, block_q, block_k,
                lambda b, c, i, j: (b, c, *live(i, j)), group=group)
    dq, delta = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **tiles),
        grid=(batch, e // lanes, nq, nk),
        in_specs=[sp["q"], sp["k"], sp["v"], sp["rows"], sp["rows"],
                  sp["stat"]],
        out_specs=[sp["rows"], sp["stat"]],
        out_shape=[jax.ShapeDtypeStruct((batch, seq_q, e), q.dtype), stats],
        scratch_shapes=[
            pltpu.VMEM((block_q, lanes), jnp.float32),
            pltpu.VMEM((lanes // d, block_q, _STATS_LANES), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, g, out, lse)

    # dK / dV: grid axis 1 counts the column blocks of k and v, and the
    # streamed axis takes a KV head's `group` query heads in turn, each
    # over all its q blocks, so that the group's sum is made in the VMEM
    # accumulators and written once at the KV heads' width.
    live = _live(causal, block_q, block_k, nq, nk, streams_q=True)
    if group == 1:
        def where(b, c, j, i):
            return (b, c, *live(i, j))
    else:
        def where(b, c, j, turn):
            return (b, c * group + jax.lax.div(turn, nq),
                    *live(jax.lax.rem(turn, nq), j))
    sp = _specs(e, d, fused, block_q, block_k, where, group)
    grads = jax.ShapeDtypeStruct((batch, seq_k, e // group), k.dtype)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **tiles, group=group),
        grid=(batch, e // lanes // group, nk, group * nq),
        in_specs=[sp["q"], sp["k"], sp["v"], sp["rows"], sp["stat"],
                  sp["stat"]],
        out_specs=[sp["kv"], sp["kv"]],
        out_shape=[grads, grads],
        scratch_shapes=[pltpu.VMEM((block_k, lanes), jnp.float32),
                        pltpu.VMEM((block_k, lanes), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# Dispatch + custom VJP
# --------------------------------------------------------------------------- #


def pick_block_sizes(seq: int, d: int, causal: bool = True) -> tuple:
    """(block_q, block_k, tile_q, tile_k) for a call of heads `d` wide over
    `seq` positions: a pure function of the shape and the mask.

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
    [8,16,1024,64]: 604 against 706).

    Heads of 256 take the same 1024 x 1024 block with the causal stripes
    inside it, in tiles of 256 x 256. Measured on one v5e at
    [1,16,8192,256] bf16 causal, the kernels alone (my chip runs, PR 44; us
    a call, fwd / dq / dkv; k and v repeated 8x for 16 query heads unless
    it says 2 KV heads):

        (bq, bk, tq, tk)           fwd     dq    dkv
        256, 256, 256, 256      13,918 10,667 11,833   (PR 34 to PR 43)
        512, 512, 256, 256       6,840  6,887  8,076
        512, 1024, 256, 128      5,485  6,522  7,236
        1024, 512, 256, 256      5,995  5,794  7,711
        1024, 1024, 128, 128     5,298  5,575  6,831
        1024, 1024, 256, 128     4,640  5,600  6,913
        1024, 1024, 256, 256     4,642  5,607  6,871
        1024, 1024, 512, 256     4,476  5,721  7,050
        the same at 256 / 256, 2 KV heads read in place, dK / dV summed
        over the group in VMEM     4,632  5,595  6,696
        and no fetch for a step above the diagonal (`_live`): the rule
                                   4,185  5,155  6,077

    A 256 x 256 block paid a grid step (and the statistics' and the
    accumulator's round trip through VMEM) for 0.34 us of matmuls; 2048 x
    1024 wants 19.9 MB of VMEM against the compiler's 16. dK / dV written
    a query head and summed by XLA took 6,886 us and 0.38 ms of XLA ops
    more. Measured at that one shape: seq 1024 and the non-causal call
    take its tiles unmeasured, and heads of 512 keep the one-tile 128 x
    128 blocks they had (no cell runs them, not measured)."""
    if d <= 128:
        bq = bk = 1024
        tq, tk = (128 if causal and seq <= bk else 256), 128
    elif d <= 256:
        bq = bk = 1024
        tq = tk = 256
    else:
        bq = bk = tq = tk = 128
    while seq % bq and bq > 128:
        bq //= 2
    while seq % bk and bk > 128:
        bk //= 2
    return bq, bk, min(tq, bq), min(tk, bk)


# (pass, path, reason, shape, dtype, block_q, block_k) -> traced calls; the
# flash passes append _FLASH_FIELDS to theirs, `paged_decode` and
# `paged_prefill` their `tile`
_CALLS: collections.Counter = collections.Counter()
_CALLS_LOCK = threading.Lock()
_FLASH_FIELDS = ("causal", "tiles", "tiles_live", "layout", "heads_per_block",
                 "kv_heads")


def pallas_status() -> list:
    """Which path every traced attention call of this process took: one
    entry per distinct (pass, shape, dtype, blocks) with `path` "pallas"
    or "reference", the dispatch rule's `reason` for a reference call, and
    the number of traced calls. A caller that asked for flash and needs to
    know it got flash (chip_smoke.py, bench.py) reads this.

    Entries of the flash passes (`fwd`, `bwd`) give `shape` as the call's
    [batch, heads, seq, head_dim] whatever arrays carried it, and also say
    `causal`, `kv_heads` (the heads k and v hold: fewer than the shape's
    where a group of query heads reads one KV head in place), `layout`
    ("bse": `flash_attention_bse` on [batch, seq, heads*head_dim] arrays;
    "bhsd": `flash_attention`), and on the Pallas
    path `heads_per_block` (heads in one column block of the kernels'
    operands) and `tiles`, `tiles_live`: the (tile_q, tile_k) tiles in one
    (batch, head)'s score square and those whose body the kernels run (None
    for a reference call). `tiles_live / tiles` near 1.0 on a causal call
    means the causal skip is dead at that shape.

    Entries of the paged kernel (`paged_decode`, `paged_prefill`) say
    `tile`: which of the kernel's two tiles the call's shape was given
    (`ops/paged_attention.py`; empty for a reference call)."""
    with _CALLS_LOCK:
        items = list(_CALLS.items())
    out = []
    for (p, path, reason, shape, dtype, bq, bk, *flash), n in items:
        out.append({"pass": p, "path": path, "reason": reason,
                    "shape": list(shape), "dtype": dtype, "block_q": bq,
                    "block_k": bk, "calls": n,
                    **dict(zip(("tile",) if p.startswith("paged_")
                               else _FLASH_FIELDS, flash))})
    return out


def reset_pallas_status() -> None:
    """Forget the calls traced so far (a caller that asserts on one
    program's calls clears what model init traced before it)."""
    with _CALLS_LOCK:
        _CALLS.clear()


def _operands(qkv) -> tuple:
    """(q, k, v), or (qkv,) for one fused array."""
    return tuple(qkv) if isinstance(qkv, (tuple, list)) else (qkv,)


def _views(operands) -> tuple:
    """(q, k, v, fused): a fused array stands for all three of its
    thirds."""
    fused = len(operands) == 1
    return (*(operands * 3 if fused else operands), fused)


def _dispatch(pass_: str, operands, d: int, fold: int, causal: bool,
              blocks: tuple) -> bool:
    """True when the Pallas kernels take this call. Records the decision."""
    platform = _platform()
    q, k, _, fused = _views(operands)
    batch, seq_q, seq_k = q.shape[0], q.shape[1], k.shape[1]
    e = q.shape[2] // 3 if fused else q.shape[2]
    lanes = _column_block(e, d)
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
    elif e % lanes or (lanes % _STATS_LANES and (fused or e != lanes)):
        # e.g. three heads of 64 side by side: the last column block would
        # be half a lane tile
        reason = "heads do not fill whole column blocks"
    else:
        reason = ""
    tiles, tiles_live, heads_per_block = (None, None, None) if reason else (
        *_tile_counts(seq_q, tile_q, tile_k, causal), lanes // d)
    shape = (batch // fold, fold, seq_q, d) if fold else (
        batch, e // d, seq_q, d)
    kv_heads = shape[1] if fused or fold else k.shape[2] // d
    key = (pass_, "reference" if reason else "pallas", reason, shape,
           jnp.dtype(q.dtype).name, block_q, block_k, causal, tiles,
           tiles_live, "bhsd" if fold else "bse", heads_per_block, kv_heads)
    with _CALLS_LOCK:
        _CALLS[key] += 1
    return not reason


def _resolve(seq: int, d: int, causal, scale, block_q, block_k):
    """(scale, (block_q, block_k, tile_q, tile_k)): explicit blocks keep
    the rule's tiles, cut to the block."""
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq, bk, tq, tk = pick_block_sizes(seq, d, causal)
    if block_q and block_k:
        bq, bk = block_q, block_k
    bq, bk = min(bq, seq), min(bk, seq)
    return scale, (bq, bk, min(tq, bq), min(tk, bk))


def _reference(operands, d: int, causal: bool, scale):
    """`mha_reference` on the kernels' operands: [batch, seq, e] in and
    out, a KV head repeated for the query heads that share it."""
    q, k, v = (operands if len(operands) == 3
               else jnp.split(operands[0], 3, axis=-1))
    group = q.shape[2] // k.shape[2]

    def apart(t, repeat=1):
        b, s, e = t.shape
        t = t.reshape(b, s, e // d, d).transpose(0, 2, 1, 3)
        return t if repeat == 1 else jnp.repeat(t, repeat, axis=1)

    out = mha_reference(apart(q), apart(k, group), apart(v, group), causal,
                        scale)
    b, h, s, _ = out.shape
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _flash(operands, d: int, fold: int, causal: bool, scale, block_q: int,
           block_k: int):
    """Attention over [batch, seq, heads*d] arrays. `operands` is (q, k,
    v), k and v [batch, seq, kv_heads*d] with kv_heads dividing heads, or
    (qkv,): one [batch, seq, 3*heads*d] array whose thirds they are.
    `fold`: heads the caller folded into `batch` (`flash_attention`),
    for the records only."""
    out, _ = _flash_fwd_impl(operands, d, fold, causal, scale, block_q,
                             block_k)
    return out


def _flash_fwd_impl(operands, d, fold, causal, scale, block_q, block_k):
    scale, blocks = _resolve(operands[0].shape[1], d, causal, scale, block_q,
                             block_k)
    if _dispatch("fwd", operands, d, fold, causal, blocks):
        q, k, v, fused = _views(operands)
        return _flash_forward(q, k, v, d, fused, causal, scale, *blocks,
                              interpret=_interpret())
    return _reference(operands, d, causal, scale), None


def _flash_fwd(operands, d, fold, causal, scale, block_q, block_k):
    out, lse = _flash_fwd_impl(operands, d, fold, causal, scale, block_q,
                               block_k)
    # The two residuals only the kernel can make, by name: a caller's
    # `jax.checkpoint` whose policy keeps both has no forward call to
    # repeat in its backward (module docstring, "Under a remat").
    out = checkpoint_name(out, "flash_out")
    if lse is not None:
        lse = checkpoint_name(lse, "flash_lse")
    return out, (operands, out, lse)


def _flash_bwd(d, fold, causal, scale, block_q, block_k, residuals, g):
    operands, out, lse = residuals
    scale_v, blocks = _resolve(operands[0].shape[1], d, causal, scale,
                               block_q, block_k)
    if not _dispatch("bwd", operands, d, fold, causal, blocks):
        _, vjp = jax.vjp(lambda ops: _reference(ops, d, causal, scale),
                         operands)
        return vjp(g)
    q, k, v, fused = _views(operands)
    grads = _flash_backward(q, k, v, out, lse, g, d, fused, causal, scale_v,
                            *blocks, interpret=_interpret())
    if not fused:
        return (grads,)
    # Three in-place slice updates of ~7 us each in the GPT-2-medium step.
    # The kernels writing one [batch, seq, 3e] array themselves (dK/dV in a
    # second turn of each grid step) cost dK/dV 127 us a call: PERF.md
    # section 6, PR 33.
    return ((jnp.concatenate(grads, axis=-1),),)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_bse(qkv, head_dim: int, causal: bool = True,
                        scale: Optional[float] = None,
                        block_q: int = 0, block_k: int = 0) -> jax.Array:
    """Blocked attention on the layout the projections around it use.
    `qkv`: one [batch, seq, 3*heads*head_dim] array holding q, k and v
    side by side (a fused projection's output, read in place as three
    views), or a (q, k, v) tuple of [batch, seq, heads*head_dim] arrays.
    Returns [batch, seq, heads*head_dim]: no split, reshape or transpose
    for XLA to turn into copies of whole activations.

    Grouped queries: k and v may be [batch, seq, kv_heads*head_dim] as they
    leave their projections, every KV head serving heads // kv_heads
    adjacent query heads. Where a column block is one head (head_dim >=
    128) the kernels read a KV head in place for its whole group and write
    ONE dK / dV a KV head, the group's sum made in VMEM; where a block
    holds two heads (head_dim 64) k and v are repeated here, as a caller
    would. Which of the two is the shape's choice, not the caller's.

    Dispatches to the Pallas kernels on TPU (shapes permitting; block size 0
    = auto) and the XLA reference elsewhere. Fully differentiable with a
    flash backward — training memory stays O(seq * block).
    """
    operands = _operands(qkv)
    if len(operands) == 3 and head_dim < _STATS_LANES:
        q, k, v = operands
        group = q.shape[2] // k.shape[2]
        if group > 1:
            operands = (q, *(jnp.repeat(
                t.reshape(*t.shape[:2], -1, head_dim), group,
                axis=2).reshape(q.shape) for t in (k, v)))
    return _flash(operands, head_dim, 0, causal, scale, block_q, block_k)


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = 0, block_k: int = 0) -> jax.Array:
    """Blocked attention. q,k,v: [batch, heads, seq, head_dim], for callers
    whose operands are per head already: the same kernels and dispatch rule
    as `flash_attention_bse`, on [batch*heads, seq, head_dim] (a reshape
    that moves nothing), every head its own column block."""
    b, h, s, d = q.shape
    out = _flash(tuple(t.reshape(b * h, t.shape[2], d) for t in (q, k, v)),
                 d, h, causal, scale, block_q, block_k)
    return out.reshape(b, h, s, d)


def _sharded(local, operands, spec):
    if jax.sharding.get_abstract_mesh().empty or not any(spec):
        return local(*operands)
    return jax.shard_map(local, in_specs=(spec,) * len(operands),
                         out_specs=spec, check_vma=False)(*operands)


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

    return _sharded(local, (q, k, v), spec)


def flash_attention_bse_sharded(qkv, head_dim: int, spec,
                                causal: bool = True,
                                scale: Optional[float] = None,
                                block_q: int = 0,
                                block_k: int = 0) -> jax.Array:
    """`flash_attention_bse` inside a partitioned jit, as
    `flash_attention_sharded` is `flash_attention`. `spec` is the
    PartitionSpec of the [batch, seq, heads*head_dim] arrays: (batch axes,
    None, heads axis); each device runs the kernels on its [b/dp, s,
    (h/tp)*d]. Whole heads shard with the last dim only once q, k and v are
    apart, so where `spec` names a heads axis a fused `qkv` is split first
    (XLA makes the thirds three outputs of the projection, not copies)."""
    operands = _operands(qkv)
    heads_sharded = (not jax.sharding.get_abstract_mesh().empty
                     and len(spec) > 2 and spec[2])
    if len(operands) == 1 and heads_sharded:
        operands = tuple(jnp.split(operands[0], 3, axis=-1))

    def local(*operands):
        return flash_attention_bse(operands, head_dim, causal, scale,
                                   block_q, block_k)

    return _sharded(local, operands, spec)
