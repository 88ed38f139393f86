"""Paged attention: a Pallas TPU kernel that reads only live blocks.

The continuous-batching engine keeps K/V in a block arena
[num_blocks, block_size, kv_heads, head_dim]; a row's block table maps its
logical blocks to physical ones (`models/llama.py decode_paged`). Attention
over that cache used to gather every row's WHOLE table out of the arena,
repeat it over the query-head groups and upcast it to f32, in every layer
of every step, whatever the context a row really had. The kernel here
walks each row's block table instead: it copies only the pages the table
maps below the row's live length, straight out of the arena (which stays
in HBM, in its own dtype), into a double-buffered VMEM scratch, and runs
an online softmax over them. Grouped-query attention happens inside: the
`groups` query heads of one KV head are rows of one operand against that
head's K chunk, so nothing is repeated.

The arena's layout is relied on as it is, 4-D, and never reshaped: on the
TPU [num_blocks * block_size, kv_heads * head_dim] tiles differently from
[num_blocks, block_size, kv_heads, head_dim] (whose minor tile IS one
token's [kv_heads, head_dim]), so the 2-D view that is a bitcast in
row-major memory costs a copy of the whole arena there. A page
`arena[block]` is `block_size` whole tiles, contiguous, and one DMA; in
VMEM, KV head h of a chunk is every kv_heads-th row of the chunk seen as
[chunk * kv_heads, head_dim], a strided load (`_heads`, below). One grid
step serves one (row, query tile); the KV chunks are an in-kernel loop
whose trip count is the tile's own bound, so a chunk past the live length
costs neither a copy nor a grid step.

The kernel has two tiles, and which one a call gets is its SHAPE's to say
(`_tiles`: query tokens x groups, never a flag or a model's name):

- MANY ROWS (more than 128 query rows a KV head: a prefill chunk's tiles of
  up to 512) is bound by its products: a grid step walks its own chunks of
  256 tokens, the next chunk's copies in flight under this chunk's
  products, head by head.
- FEW ROWS (up to 128: every decode step, 1 to 5 query rows a KV head in
  the serve cells, and a speculative round's verify) is bound by its pages'
  bytes and by what each copy and each loop iteration costs beside them
  (`_few_rows_kernel`): (a) a row's first chunk is started under the LAST
  chunk of the row before it that had anything live (the grid is
  sequential, scratch persists across grid steps, every row's table and
  length are in SMEM), so only the call's very first copy is exposed; an
  idle row starts nothing, waits for nothing and hands the chain on; (b)
  the products run over sub-blocks of 512 tokens up to the row's live
  length, inside chunks of 1,024; (c) the KV heads of a sub-block are the
  batch dimension of ONE `dot_general` for the scores and one for P x V,
  and the f32 statistics, probabilities and result hold 8 query rows a
  tile, not the 16 of the bf16 operand; a chunk's copies are waited for in
  powers of two of pages, not page by page.

Arithmetic is the reference's in another order of summation: scores are
q . k of the arena's values accumulated in f32, mask, softmax and the
running statistics are f32, probabilities stay f32 into P x V with V
upcast per chunk in VMEM.

Dispatch is `ops/attention.py`'s rule: platform `tpu` and a shape the
kernel takes -> kernel (a kernel the compiler refuses fails the caller's
compile); anything else -> `paged_attention_reference`, recorded with its
reason. `pallas_status()` lists every traced call under the passes
`paged_decode` (one query token a row) and `paged_prefill` (more).
RAY_TPU_PALLAS_INTERPRET=1 runs the kernel in the interpreter on CPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as _attn
from ray_tpu.ops.attention import _NEG_INF

_LANES = 128
# The most query rows (query tokens x groups) one grid step holds, the
# tokens a chunk of pages holds and the tokens a few-rows tile multiplies at
# a time. Measured on the v5e with the kernel alone
# (`scripts/time_paged_kernels.py`; PERF.md section 5, PR 61; us a call).
# A few-rows tile at the ninth cell's shape (8 rows x 16 KV heads x 1 query
# row, 284 tokens a row / contexts U(64,576) / 576), Mistral's (16 rows x 8
# x 4, ~350 / ~900) and Falcon-H1's (64 rows x 4 x 5, ~290), parent first:
#   head by head, whole 512-token chunks      70.4  84.1 128.9  79.6 135.5 182.0
#   + the next row's first chunk in flight    50.8  59.0  98.7  58.3 123.5 150.3
#   + live 128-token sub-blocks, 8-row f32    63.1  74.8 120.5  79.5 188.5 200.4
#   + heads batched, chunks of 1,024          27.9  40.8  54.0  46.2 109.0 146.8
#   + waits in powers of two of pages         27.9  40.8  54.0  43.9 102.3 138.3
#   + sub-blocks of 512 (the tree)            28.1  41.0  55.0  34.4  82.1  95.4
# Sub-blocks of 128 under a dynamic bound cost more than the dead tokens
# they skip wherever a row has more than one (256: 27.9 39.5 53.7 36.0 84.2
# 113.5; 1,024, a whole chunk: 44.2 48.2 55.2 43.6 81.9 115.6): an
# iteration's fixed cost, not its width, is what a few-rows tile pays. Head
# by head they lose to whole chunks outright (71.9 85.0 131.8 92.9 219.1
# 214.3 with 16-row tiles). Chunks of 2,048 read as 1,024 do, chunks of 512
# make a 576-token row wait for a second one (66.7). A many-rows
# tile of 512 rows is bound by its matmuls and what the mask wastes of them
# (PR 25, at Mistral's widths: chunks of 256 72 and 394 us at 512 and 3,072
# positions; 512: 89 and 412), and none of the above moved it (18.9 / 90.3 /
# 35.5 us a chunk of the three models before and after). 1,024 tokens x 16
# heads x 128 of bf16 K and V, double-buffered, are 16 MB of VMEM.
_MAX_Q_ROWS = 512
_CHUNK_TOKENS_FEW_ROWS = 1024      # tiles of up to _FEW_ROWS query rows
_CHUNK_TOKENS = 256
_FEW_ROWS = 128
_SUB_TOKENS = 512                  # ... multiply this many at a time
_VMEM_LIMIT = 64 * 1024 * 1024


def paged_attention_reference(q, k_arena, v_arena, block_tables, positions):
    """The dense math the kernel is tested against, and the path of every
    call the rule does not give the kernel: gather each row's whole
    logical context out of the arena, repeat it over the query groups,
    f32 scores, mask, softmax, P x V. q [b, s, n_head, d]; returns the
    same shape in q's dtype."""
    b, s, n_head, hd = q.shape
    nb, bsz, kvh, _ = k_arena.shape
    groups = n_head // kvh
    max_ctx = block_tables.shape[1] * bsz
    k_flat = k_arena.reshape(nb * bsz, kvh, hd)
    v_flat = v_arena.reshape(nb * bsz, kvh, hd)
    slot = (block_tables * bsz)[:, :, None] + jnp.arange(bsz)[None, None, :]
    slot = slot.reshape(b, max_ctx)
    kf = jnp.repeat(k_flat[slot], groups, axis=2)        # [b, ctx, h, d]
    vf = jnp.repeat(v_flat[slot], groups, axis=2)
    # Causal over LOGICAL positions: arena slot (j, o) of a row holds
    # logical position j*bsz+o; unwritten slots sit past every query's
    # position (or behind trash-padded table entries) and are masked out.
    kv_pos = jnp.arange(max_ctx)
    mask = kv_pos[None, None, :] <= positions[:, :, None]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kf.astype(jnp.float32)) / (hd ** 0.5)
    scores = jnp.where(mask[:, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      vf.astype(jnp.float32)).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Kernel
# --------------------------------------------------------------------------- #


def _live_pages(buf, hi, c):
    """The live pages of chunk c of a walk to `hi`: a prefix of the chunk."""
    pages, block_size = buf.shape[1:3]
    chunk = pages * block_size
    return (jnp.minimum(hi - c * chunk, chunk) + block_size - 1) \
        // block_size


def _for_live_pages(bt_ref, hbm, bufs, sems, row, hi, c, slot, do: str):
    """`do` ("start" or "wait") the K and the V copy of every live page of
    chunk c of a walk of `row` to `hi` into `slot`: a loop with a dynamic
    bound.
    Unrolled and guarded page by page, tracing and lowering the copies was
    most of what a process paid for the kernel at start-up (PERF.md, PR
    25)."""
    from jax.experimental.pallas import tpu as pltpu

    pages = bufs[0].shape[1]

    def body(p, carry):
        phys = bt_ref[row, c * pages + p]
        for i, (arena, buf) in enumerate(zip(hbm, bufs)):
            getattr(pltpu.make_async_copy(arena.at[phys], buf.at[slot, p],
                                          sems.at[i, slot]), do)()
        return carry

    jax.lax.fori_loop(0, _live_pages(bufs[0], hi, c), body, 0)


def _wait_live_pages(bufs, sems, hi, c, slot):
    """Wait for every copy `_for_live_pages` started for chunk c into
    `slot`, a power of two of pages at a time: a DMA semaphore counts what
    has landed, whichever copies brought it, so the waits for 1, 2, 4, ...
    pages that add up to the chunk's live pages take the place of one wait
    a page (no copy is made: the descriptor only says how much)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    live = _live_pages(bufs[0], hi, c)
    n = 1
    while n <= bufs[0].shape[1]:
        @pl.when((live & n) != 0)
        def _(n=n):
            for i, buf in enumerate(bufs):
                landed = buf.at[slot, pl.ds(0, n)]
                pltpu.make_async_copy(landed, landed, sems.at[i, slot]).wait()
        n *= 2


def _heads(view, live=None):
    """The pages `view` [pages, block, kv_heads, d] of a chunk buffer as one
    exact f32 [tokens, d] per KV head. A token's heads are the sublanes of
    one tile, so head h is every kv_heads-th row of the pages seen as
    [tokens * kv_heads, d]: a strided load. 16-bit rows come packed in
    pairs, two heads to a 32-bit word, and are taken apart by shift and
    mask (the upper half of an f32 IS the bf16), as jax's
    ragged_paged_attention kernel reads its pages. `live` [tokens, d]
    marks the rows to keep; the others come back as zeros."""
    from jax.experimental.pallas import tpu as pltpu

    pages, block_size, kv_heads, head_dim = view.shape
    flat = view.reshape(pages * block_size * kv_heads, head_dim)

    def kept(x):
        return x if live is None else jnp.where(live, x, jnp.zeros_like(x))

    if view.dtype == jnp.float32:
        return [kept(flat[h::kv_heads, :]) for h in range(kv_heads)]
    words = flat.bitcast(jnp.uint32)
    out = []
    for pair in range(kv_heads // 2):
        w = kept(words[pair::kv_heads // 2, :])
        out.append(pltpu.bitcast(w << 16, jnp.float32))
        out.append(pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32))
    return out


def _kernel(hi_ref, bt_ref, q_ref, qpos_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, m_scr, l_scr, acc_scr, *, scale: float):
    """A tile of many query rows (a prefill chunk's): bound by its products.
    One grid step walks its own chunks, the next one's copy in flight under
    this one's products, and multiplies every chunk whole."""
    from jax.experimental import pallas as pl

    row = pl.program_id(0)
    tile = pl.program_id(1)
    _, pages, block_size, kv_heads, head_dim = k_buf.shape
    chunk = pages * block_size
    # Logical positions [0, hi) are all this tile can see: the row's live
    # length, or less where the tile's last query sits below it.
    hi = hi_ref[row, tile]
    n_chunks = (hi + chunk - 1) // chunk

    def copies(c, slot, do):
        _for_live_pages(bt_ref, (k_hbm, v_hbm), (k_buf, v_buf), sems, row,
                        hi, c, slot, do)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(n_chunks > 0)
    def _():
        copies(0, 0, "start")

    def body(c, carry):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            copies(c + 1, 1 - slot, "start")

        copies(c, slot, "wait")
        q_pos = qpos_ref[0]                                  # [rows, 1]
        rows = q_pos.shape[0]
        k_pos = c * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (rows, chunk), 1)
        mask = (k_pos <= q_pos) & (k_pos < hi)
        # Rows of the buffer at or past `hi` hold whatever was there (the
        # tail of the last live page, an earlier chunk): K is masked
        # through the scores, V has to be zeroed, or 0 x NaN gets in.
        v_live = c * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (chunk, head_dim), 0) < hi
        for h, (k, v) in enumerate(zip(_heads(k_buf.at[slot]),
                                       _heads(v_buf.at[slot], v_live))):
            s = jax.lax.dot_general(
                q_ref[0, h], k.astype(q_ref.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [rows, chunk]
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_scr[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            correction = jnp.exp(m_prev - m_new)
            l_new = correction * l_scr[h][:, :1] + jnp.sum(
                p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * correction + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])
        return carry

    jax.lax.fori_loop(0, n_chunks, body, 0)

    # A row with nothing live (an idle slot, a padded query) has l = 0 and
    # a zero accumulator: it writes zeros, never 0/0.
    for h in range(kv_heads):
        denom = jnp.maximum(l_scr[h][:, :1], 1e-30)
        o_ref[0, h] = (acc_scr[h] / denom).astype(o_ref.dtype)


# Rows of the few-rows tile's walk array (scalar prefetch, a column a row).
_HI, _BEFORE, _NEXT = range(3)


def _walk(hi, chunk: int):
    """[3, b] int32 for a call of one tile a row: `_HI` a row's live length
    (0: an idle row, which copies and multiplies nothing); `_BEFORE` the
    chunks of all earlier rows (its parity is the buffer slot the row's
    first chunk lands in, and 0 says nobody has started it); `_NEXT` the
    next row with anything live, -1 for none."""
    b = hi.shape[0]
    chunks = (hi + chunk - 1) // chunk
    before = jnp.cumsum(chunks) - chunks
    row = jnp.arange(b, dtype=jnp.int32)
    later = jnp.where(chunks > 0, row, b)
    nxt = jnp.flip(jax.lax.cummin(jnp.flip(later)))          # at or after
    nxt = jnp.concatenate([nxt[1:], jnp.full((1,), b, jnp.int32)])
    return jnp.stack([hi, before, jnp.where(nxt < b, nxt, -1)]).astype(
        jnp.int32)


def _few_rows_kernel(walk_ref, bt_ref, q_ref, qpos_ref, k_hbm, v_hbm, o_ref,
                     k_buf, v_buf, sems, m_scr, l_scr, acc_scr, *,
                     scale: float, sub_pages: int):
    """A tile of few query rows (a decode step's, a speculative round's):
    bound by its pages' bytes and by what every loop iteration costs beside
    them. Its first chunk is started under the last chunk of the row before
    (the grid is sequential, scratch persists, every row's table is in SMEM
    already), and it multiplies a chunk in sub-blocks of `sub_pages` pages
    up to the row's live length and no further."""
    from jax.experimental import pallas as pl

    row = pl.program_id(0)
    _, pages, block_size, _, head_dim = k_buf.shape
    chunk = pages * block_size
    sub = sub_pages * block_size
    hi = walk_ref[_HI, row]
    before = walk_ref[_BEFORE, row]
    nxt = walk_ref[_NEXT, row]
    n_chunks = (hi + chunk - 1) // chunk
    rows = m_scr.shape[1]              # whole f32 tiles of the query rows

    def start(row, hi, c, slot):
        _for_live_pages(bt_ref, (k_hbm, v_hbm), (k_buf, v_buf), sems, row,
                        hi, c, slot, "start")

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    # The first row that walks starts its own first chunk; every later one
    # finds it started under its predecessor's last chunk.
    @pl.when((n_chunks > 0) & (before == 0))
    def _():
        start(row, hi, 0, 0)

    q = q_ref[0]                                     # [kv_heads, ., d]
    q_pos = qpos_ref[0][:rows]                       # [rows, 1]
    batched = (((2,), (2,)), ((0,), (0,)))

    def chunk_step(c, carry):
        slot = (before + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            start(row, hi, c + 1, 1 - slot)

        @pl.when((c + 1 == n_chunks) & (nxt >= 0))
        def _():
            start(nxt, walk_ref[_HI, nxt], 0, 1 - slot)

        _wait_live_pages((k_buf, v_buf), sems, hi, c, slot)

        def sub_step(j, carry):
            first = c * chunk + j * sub
            k_pos = first + jax.lax.broadcasted_iota(
                jnp.int32, (rows, sub), 1)
            mask = ((k_pos <= q_pos) & (k_pos < hi))[None]
            # Rows of the buffer at or past `hi` hold whatever was there
            # (the tail of the last live page, another row's pages): K is
            # masked through the scores, V has to be zeroed, or 0 x NaN
            # gets in.
            v_live = first + jax.lax.broadcasted_iota(
                jnp.int32, (sub, head_dim), 0) < hi
            at = pl.ds(j * sub_pages, sub_pages)
            k = jnp.stack(_heads(k_buf.at[slot, at]))    # [kv_heads, sub, d]
            v = jnp.stack(_heads(v_buf.at[slot, at], v_live))
            s = jax.lax.dot_general(
                q, k.astype(q.dtype), batched,
                preferred_element_type=jnp.float32)[:, :rows] * scale
            s = jnp.where(mask, s, _NEG_INF)             # [kv_heads, rows, sub]
            m_prev = m_scr[...][..., :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            correction = jnp.exp(m_prev - m_new)
            l_new = correction * l_scr[...][..., :1] + jnp.sum(
                p, axis=2, keepdims=True)
            acc_scr[...] = acc_scr[...] * correction + jax.lax.dot_general(
                p, v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
            return carry

        live = jnp.minimum(hi - c * chunk, chunk)
        jax.lax.fori_loop(0, (live + sub - 1) // sub, sub_step, 0)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_step, 0)

    # A row with nothing live (an idle slot, a padded query) has l = 0 and
    # a zero accumulator: it writes zeros, never 0/0.
    denom = jnp.maximum(l_scr[...][..., :1], 1e-30)
    o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _tiles(n_rows: int, block_size: int, dtype) -> tuple:
    """(query rows a grid step, pages a KV chunk, pages a sub-block) for
    this call's shape; no sub-blocks (0) says the many-rows tile."""
    sublanes = 32 // jnp.dtype(dtype).itemsize     # rows of one packed tile
    rows = min(_MAX_Q_ROWS, -(-n_rows // sublanes) * sublanes)
    if rows > _FEW_ROWS:
        return rows, max(1, _CHUNK_TOKENS // block_size), 0
    sub = max(1, _SUB_TOKENS // block_size)
    return rows, max(1, _CHUNK_TOKENS_FEW_ROWS // (sub * block_size)) * sub, \
        sub


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_attention_pallas(q, k_arena, v_arena, block_tables, positions,
                            lengths, interpret: bool = False):
    # Jitted on its own so that a model's layers share ONE trace and ONE
    # lowering of the kernel: a program of 16 layers otherwise lowers the
    # kernel to Mosaic 16 times, on every process start, cache hit or not
    # (the lowering is part of the compile cache's key). That was 19 s of
    # the serve cells' set-up (PERF.md, PR 25).
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, n_head, hd = q.shape
    nb, bsz, kvh, _ = k_arena.shape
    groups = n_head // kvh
    max_ctx = block_tables.shape[1] * bsz
    n_rows = s * groups
    rows, pages, sub_pages = _tiles(n_rows, bsz, q.dtype)
    n_tiles = -(-n_rows // rows)
    pad = n_tiles * rows - n_rows
    # The query heads of one KV head become rows of one operand: row
    # t*groups + g of KV head j is query token t, head j*groups + g.
    qg = q.reshape(b, s, kvh, groups, hd).transpose(0, 2, 1, 3, 4)
    qg = jnp.pad(qg.reshape(b, kvh, n_rows, hd),
                 ((0, 0), (0, 0), (0, pad), (0, 0)))
    q_pos = jnp.pad(jnp.repeat(positions.astype(jnp.int32), groups, axis=1),
                    ((0, 0), (0, pad)), constant_values=-1)
    hi = jnp.minimum(q_pos.reshape(b, n_tiles, rows).max(axis=-1) + 1,
                     lengths[:, None])
    hi = jnp.clip(hi, 0, max_ctx).astype(jnp.int32)
    if sub_pages:
        # One tile a row. The statistics, the probabilities and the result
        # are f32: their tile is 8 rows, so up to 8 query rows of a 16-row
        # bf16 operand cost one tile of them, not two.
        out_rows, out_dtype = -(-n_rows // 8) * 8, jnp.float32
        kernel = functools.partial(_few_rows_kernel, sub_pages=sub_pages)
        scalars = _walk(hi[:, 0], pages * bsz)
    else:
        out_rows, out_dtype, kernel, scalars = rows, q.dtype, _kernel, hi

    def q_map(i, t, *_):
        return (i, 0, t, 0)

    out = pl.pallas_call(
        functools.partial(kernel, scale=1.0 / math.sqrt(hd)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_tiles),
            in_specs=[
                pl.BlockSpec((1, kvh, rows, hd), q_map),
                pl.BlockSpec((1, rows, 1), lambda i, t, *_: (i, t, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, kvh, out_rows, hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((2, pages, bsz, kvh, hd), k_arena.dtype),
                pltpu.VMEM((2, pages, bsz, kvh, hd), v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kvh, out_rows, _LANES), jnp.float32),
                pltpu.VMEM((kvh, out_rows, _LANES), jnp.float32),
                pltpu.VMEM((kvh, out_rows, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(
            (b, kvh, n_tiles * out_rows, hd), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="paged_attention",
    )(scalars, block_tables.astype(jnp.int32), qg, q_pos[..., None], k_arena,
      v_arena)
    out = out[:, :, :n_rows].astype(q.dtype).reshape(b, kvh, s, groups, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(b, s, n_head, hd)


# --------------------------------------------------------------------------- #
# Dispatch
# --------------------------------------------------------------------------- #


def _tiles_fully(kv_heads: int, packing: int) -> bool:
    """XLA lays [.., kv_heads, d] out without padding when the packed
    sublanes (kv_heads / packing) are 1, 2, 4 or a multiple of 8."""
    sublanes, odd = divmod(kv_heads, packing)
    return not odd and (sublanes in (1, 2, 4) or sublanes % 8 == 0)


def _dispatch(q, k_arena) -> bool:
    """True when the kernel takes this call. Records the decision beside
    the flash kernels' (`ops.attention.pallas_status`)."""
    platform = _attn._platform()
    b, s, n_head, hd = q.shape
    _, bsz, kvh, _ = k_arena.shape
    dtype = jnp.dtype(k_arena.dtype)
    if _attn._interpret() and platform == "tpu":
        raise RuntimeError(
            "RAY_TPU_PALLAS_INTERPRET=1 is a CPU test switch; on platform "
            "tpu it would run the interpreter under the kernel's name")
    if platform != "tpu" and not _attn._interpret():
        reason = f"platform {platform}"
    elif hd % _LANES:
        # A KV head is a static lane slice of a page in VMEM.
        reason = "head_dim not a multiple of 128"
    elif dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)) \
            or jnp.dtype(q.dtype) != dtype:
        reason = "q and arena not both bfloat16 or both float32"
    elif bsz % (32 // dtype.itemsize):
        # A page lands in the chunk buffer on whole packed sublane tiles.
        reason = "block_size not a multiple of the dtype's sublane tile"
    elif not _tiles_fully(kvh, 4 // dtype.itemsize):
        # The kernel picks a head out of a chunk by a strided load over
        # the arena's own tiles ([kv_heads, d] minor): they must hold no
        # padding, and 16-bit heads come in whole pairs.
        reason = "kv_heads do not fill the arena's tiles"
    else:
        reason = ""
    rows, pages, sub_pages = _tiles(s * (n_head // kvh), bsz, q.dtype)
    if reason:
        tile = ""
    elif sub_pages:
        tile = (f"few rows: the next row's first chunk in flight, products "
                f"over live sub-blocks of {sub_pages * bsz}, heads batched")
    else:
        tile = "many rows: whole chunks, head by head"
    key = ("paged_decode" if s == 1 else "paged_prefill",
           "reference" if reason else "pallas", reason, tuple(q.shape),
           jnp.dtype(q.dtype).name, rows, pages * bsz, tile)
    with _attn._CALLS_LOCK:
        _attn._CALLS[key] += 1
    return not reason


def paged_calls(field: str = "path") -> dict:
    """Traced paged-attention calls of this process so far: ((pass,
    "pallas" | "reference: <reason>") -> count), out of `pallas_status()`;
    with `field` "tile", ((pass, the kernel's tile for the call's shape)
    -> count). The difference of two reads says which path a trace in
    between took and which tile (`InferenceEngine.stats()["paged_attn"]`,
    `["paged_attn_tile"]`)."""
    out: dict = {}
    for r in _attn.pallas_status():
        if r["pass"].startswith("paged_"):
            said = r.get(field) or ""
            if field == "path" and r["reason"]:
                said += f": {r['reason']}"
            key = (r["pass"], said)
            out[key] = out.get(key, 0) + r["calls"]
    return out


def paged_attention(q, k_arena, v_arena, block_tables, positions,
                    write_mask=None) -> jax.Array:
    """Attention of q [b, s, n_head, d] (after RoPE) over the paged cache.

    `k_arena`/`v_arena` [num_blocks, block_size, kv_heads, d] are the
    arenas as they are AFTER this call's scatter; query (i, t) sees
    logical positions <= positions[i, t] of row i, logical position p
    living at arena slot block_tables[i, p // bs] * bs + p % bs. Returns
    [b, s, n_head, d] in q's dtype.

    `write_mask` [b, s] marks the queries whose output is used (the
    engine's batch and chunk padding is False). The kernel reads a row's
    pages only up to its last such query, so a row with none (an idle
    slot) reads nothing and gets zeros; the output of a masked query is
    finite and otherwise unspecified, on either path.

    Under a context mesh with a "tp" axis (`jax.set_mesh`) the arena is
    sharded on its kv-head axis and the partitioner cannot split a custom
    call: the op then runs inside a `shard_map` over that axis, q heads
    and kv heads split together, block tables and positions replicated."""
    def kernel(q, k_arena, v_arena, block_tables, positions, write_mask):
        live = jnp.where(write_mask, positions + 1, 0).max(axis=1)
        return _paged_attention_pallas(q, k_arena, v_arena, block_tables,
                                       positions, live,
                                       interpret=_attn._interpret())

    def reference(q, k_arena, v_arena, block_tables, positions, write_mask):
        return paged_attention_reference(q, k_arena, v_arena, block_tables,
                                         positions)

    if write_mask is None:
        write_mask = jnp.ones(positions.shape, bool)
    mesh = jax.sharding.get_abstract_mesh()
    tp = 1 if mesh.empty else dict(mesh.shape_tuple).get("tp", 1)
    # The rule sees what one device will run: its own heads.
    b, s, n_head, hd = q.shape
    nb, bsz, kvh, _ = k_arena.shape
    local = kernel if _dispatch(
        jax.ShapeDtypeStruct((b, s, n_head // tp, hd), q.dtype),
        jax.ShapeDtypeStruct((nb, bsz, kvh // tp, hd), k_arena.dtype)) \
        else reference
    if tp == 1:
        return local(q, k_arena, v_arena, block_tables, positions,
                     write_mask)
    from jax.sharding import PartitionSpec as P

    heads = P(None, None, "tp")
    return jax.shard_map(
        local, in_specs=(heads, heads, heads, P(), P(), P()),
        out_specs=heads, check_vma=False)(
        q, k_arena, v_arena, block_tables, positions, write_mask)
