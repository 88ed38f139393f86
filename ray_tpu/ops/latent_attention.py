"""Latent (MLA) paged attention in its ABSORBED form: a slot's query heads
all read ONE shared latent page, once, for the scores and for the values.

A latent cache keeps, a token a layer, the normed latent `c` [latent] and
the one rotated rope key `k_r` [rope] that every head shares, side by side
in a headless page: the arena is [num_blocks, block_size, width], width >=
latent + rope (the lanes past latent + rope are zero: 512 + 64 is padded to
640 = 5 x 128, which is what a [.., 576] bf16 array occupies in the TPU's
tiled memory anyway, so the padding is said and not hidden). With the
key's up-projection absorbed into the query (`q_lat = q_nope W_kvb^K`, a
model's business) a head's score against a cached token is

    score = (q_lat . c + q_rope . k_r) * scale = ([q_lat | q_rope] . page row)

and its output in latent space `o_lat = sum p c`: the VALUES ARE THE FIRST
`latent` LANES OF THE SAME PAGE. `ops/paged_attention.py` wants whole KV
heads of 128 a tile and a separate V arena; neither exists here.

The kernel walks each row's block table as that one does: it copies only
the pages the table maps below the row's live length, straight out of the
arena in HBM into a double-buffered VMEM scratch, once a (row, query tile),
and runs an online softmax in float32 over them. The query rows of a tile
are (token, head) pairs, token-major: a decode step's tile is a slot's 32
heads, a prefill chunk's tiles are 32 tokens x 32 heads each. Scores are
one product of depth `width`, probabilities go into P x V in the arena's
dtype (bf16 on the chip) with float32 accumulation.

What a tile walks is `tile_walk`'s to say (the TILE RULE), from its LIVE
queries alone (`write_mask`): up to its last live position + 1, in chunks
of 1,024 tokens under a decode tile and 512 under a prefill tile.
- A tile with no live query (a chunk's padding past the question, an idle
  slot) copies nothing, multiplies nothing and writes zeros.
- Chunks that lie whole at or below the tile's FIRST live position hold no
  key that some live query may not see and no row past the walk's end: they
  run the loop's body with no mask on scores or probabilities and no select
  over the page buffer. That is the same arithmetic in the same order
  (`where(True, x, .)` is x; outputs on live queries are bit-equal to the
  all-masked loop's on the chip); only the chunks that reach the tile's
  own queries, and the walk's last, run the masked body.
- A grid step's first chunk is started under the last chunk of the grid
  step before it that walked (the grid is sequential; the next table row
  is already in SMEM), so only the call's very first copy is exposed.

`latent_attention_reference` is the `jax.numpy` definition: the fallback,
and the CPU tests' yardstick. Dispatch is `ops/attention.py`'s rule;
`latent_attention_status()` lists every traced call (passes
`paged_latent_decode`, one query token a row, kernel `latent_decode`, and
`paged_latent_prefill`, kernel `latent_prefill`; path `pallas` or
`reference` with the reason), and the same records are in `pallas_status()` and
`paged_attention.paged_calls()`, so `InferenceEngine.stats()["paged_attn"]`
says which path an engine's programs took. RAY_TPU_PALLAS_INTERPRET=1 runs
the kernel in the interpreter on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as _attn
from ray_tpu.ops.attention import _NEG_INF

_LANES = 128
# Query rows a grid step and tokens copied and multiplied a loop iteration,
# taken on the chip at 32 heads behind ~8k cached tokens (PERF.md section 6,
# PR 55). A decode tile (a slot's heads, few rows) is bound by the pages'
# bytes and the loop's fixed costs: a longer chunk has fewer of those, and
# past 1,024 what the walk's last chunk multiplies for nothing costs more
# than they (512: 0.565 ms a call, 1,024: 0.504, 2,048: 0.520). A prefill
# tile is bound by its products: 1,024 rows feed the MXU better than 512 and
# a 512-token chunk rescales the accumulator half as often as 256 (a full
# chunk 1.33 -> 0.99 ms); 2,048 rows are as fast a row and skip less of a
# half-filled chunk.
_MAX_Q_ROWS = 1024
_CHUNK_TOKENS_FEW_ROWS = 1024
_CHUNK_TOKENS = 512
_FEW_ROWS = 128
_VMEM_LIMIT = 64 * 1024 * 1024
PASSES = ("paged_latent_decode", "paged_latent_prefill")
KERNELS = ("latent_decode", "latent_prefill")


def latent_attention_reference(q, arena, block_tables, positions, *,
                               latent: int, scale: float, window=None):
    """The dense definition: gather each row's whole logical context out
    of the arena, float32 scores over the page's full width, mask (causal,
    and with `window` the last `window` keys alone), softmax, values = the
    first `latent` lanes. q [b, s, heads, width]; returns [b, s, heads,
    latent] in q's dtype."""
    nb, bsz, width = arena.shape
    max_ctx = block_tables.shape[1] * bsz
    slot = (block_tables * bsz)[:, :, None] + jnp.arange(bsz)[None, None, :]
    ctx = arena.reshape(nb * bsz, width)[slot.reshape(-1, max_ctx)]
    ctx = ctx.astype(jnp.float32)                         # [b, ctx, width]
    k_pos = jnp.arange(max_ctx)[None, None, :]
    mask = k_pos <= positions[:, :, None]
    if window is not None:
        mask &= k_pos > positions[:, :, None] - window
    scores = jnp.einsum("bqhw,bkw->bhqk", q.astype(jnp.float32), ctx,
                        precision=jax.lax.Precision.HIGHEST) * scale
    scores = jnp.where(mask[:, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkl->bqhl", probs, ctx[..., :latent],
                      precision=jax.lax.Precision.HIGHEST).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Kernel
# --------------------------------------------------------------------------- #


# Rows of the walk array (scalar prefetch, one column a grid step).
_HI, _PLAIN, _BEFORE, _NEXT, _BASE = range(5)
WALK_COUNTS = ("tiles", "tiles_walked", "kv_chunks", "kv_chunks_masked")


def tile_walk(positions, write_mask, *, heads: int, block_size: int,
              max_ctx: int, dtype, window=None):
    """THE TILE RULE: what the kernel walks for queries at `positions`
    [b, s] of which `write_mask` marks the live ones. Returns

      q_pos [b, tiles * rows] int32: a query row's position, -1 for a
        masked query and for the padding of the last tile;
      walk [4, b * tiles] int32, a column a grid step in grid order (row-
        major (row, tile)): `_HI` the walk's end, the tile's last LIVE
        position + 1 (0: nothing is copied and zeros are written);
        `_PLAIN` the leading chunks that lie whole at or below the tile's
        FIRST live position (and below `_HI`), which no mask can touch;
        `_BEFORE` the chunks of all earlier grid steps (its parity is the
        buffer a step's first chunk lands in, 0 says nobody started it);
        `_NEXT` the next grid step that walks anything, -1 for none;
      counts, four int32 scalars (`WALK_COUNTS`): grid steps, those that
        walk, the chunks they copy and multiply, and of those the ones
        that run the masked body.

    With `window` (a query sees its last `window` keys alone) a walk has a
    LOWER bound too: a fifth row `_BASE`, the first token of the page that
    holds the oldest key the tile's first live query may see; pages wholly
    below it are neither copied nor multiplied, chunks are counted from
    it, and every chunk runs the masked body (`_PLAIN` 0).

    The wrapper builds the kernel's scalars from it and a model counts a
    step's walk with it: one definition."""
    b, s = positions.shape
    rows, pages = _tiles(s * heads, block_size, dtype)
    chunk = pages * block_size
    n_rows = s * heads
    tiles = -(-n_rows // rows)
    # Row t * heads + h of a slot is query token t, head h.
    q_pos = jnp.repeat(jnp.where(write_mask, positions, -1).astype(jnp.int32),
                       heads, axis=1)
    q_pos = jnp.pad(q_pos, ((0, 0), (0, tiles * rows - n_rows)),
                    constant_values=-1)
    by_tile = q_pos.reshape(b * tiles, rows)
    hi = jnp.clip(by_tile.max(axis=-1) + 1, 0, max_ctx)
    first = jnp.where(by_tile < 0, max_ctx, by_tile).min(axis=-1)
    if window is None:
        plain = jnp.minimum(first + 1, hi) // chunk
        chunks = (hi + chunk - 1) // chunk
    else:
        base = jnp.clip(first - (window - 1), 0, max_ctx) \
            // block_size * block_size
        base = jnp.minimum(base, hi // block_size * block_size)
        plain = jnp.zeros_like(hi)
        chunks = (hi - base + chunk - 1) // chunk
    before = jnp.cumsum(chunks) - chunks
    step = jnp.arange(b * tiles, dtype=jnp.int32)
    later = jnp.where(chunks > 0, step, b * tiles)
    nxt = jnp.flip(jax.lax.cummin(jnp.flip(later)))        # at or after
    nxt = jnp.concatenate([nxt[1:], jnp.full((1,), b * tiles, jnp.int32)])
    nxt = jnp.where(nxt < b * tiles, nxt, -1)
    rows_ = [hi, plain, before, nxt] + ([] if window is None else [base])
    walk = jnp.stack(rows_).astype(jnp.int32)
    counts = (jnp.int32(b * tiles), jnp.sum(chunks > 0, dtype=jnp.int32),
              jnp.sum(chunks, dtype=jnp.int32),
              jnp.sum(chunks - plain, dtype=jnp.int32))
    return q_pos, walk, dict(zip(WALK_COUNTS, counts))


def _kernel(walk_ref, bt_ref, q_ref, qpos_ref, kv_hbm, o_ref, kv_buf, sems,
            m_scr, l_scr, acc_scr, *, scale: float, latent: int,
            window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    row = pl.program_id(0)
    step = row * pl.num_programs(1) + pl.program_id(1)
    _, pages, block_size, width = kv_buf.shape
    chunk = pages * block_size
    hi = walk_ref[_HI, step]
    n_plain = walk_ref[_PLAIN, step]
    before = walk_ref[_BEFORE, step]
    nxt = walk_ref[_NEXT, step]
    # A windowed walk begins at `base` (a page's first token), not at 0.
    base = None if window is None else walk_ref[_BASE, step]
    span = hi if window is None else hi - base
    n_chunks = (span + chunk - 1) // chunk

    def for_live_pages(row, span, c, slot, do, base=None):
        """`do(copy)` for every live page of chunk c of a walk of `row`
        over `span` tokens (from `base`, or from 0) into `slot`: ONE copy a
        page, which serves the scores and the values."""
        def body(p, carry):
            page = c * pages + p
            if base is not None:
                page = base // block_size + page
            phys = bt_ref[row, page]
            do(pltpu.make_async_copy(kv_hbm.at[phys], kv_buf.at[slot, p],
                                     sems.at[slot]))
            return carry

        live = (jnp.minimum(span - c * chunk, chunk) + block_size - 1) \
            // block_size
        jax.lax.fori_loop(0, live, body, 0)

    def start(copy):
        copy.start()

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    # The first grid step that walks starts its own first chunk; every
    # later one finds it started under its predecessor's last chunk.
    @pl.when((n_chunks > 0) & (before == 0))
    def _():
        for_live_pages(row, span, 0, 0, start, base)

    def chunk_step(c, masked: bool):
        slot = (before + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            for_live_pages(row, span, c + 1, 1 - slot, start, base)

        @pl.when((c + 1 == n_chunks) & (nxt >= 0))
        def _():
            if window is None:
                for_live_pages(nxt // pl.num_programs(1),
                               walk_ref[_HI, nxt], 0, 1 - slot, start)
            else:
                for_live_pages(nxt // pl.num_programs(1),
                               walk_ref[_HI, nxt] - walk_ref[_BASE, nxt], 0,
                               1 - slot, start, walk_ref[_BASE, nxt])

        for_live_pages(row, span, c, slot, lambda copy: copy.wait(), base)
        kv = kv_buf[slot].reshape(chunk, width)
        if masked:
            q_pos = qpos_ref[0]                              # [rows, 1]
            k_pos = c * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (q_pos.shape[0], chunk), 1)
            if window is not None:
                k_pos = base + k_pos
            mask = (k_pos <= q_pos) & (k_pos < hi)
            if window is not None:
                mask &= k_pos > q_pos - window
            # Rows of the buffer at or past `hi` hold whatever was there;
            # they are the VALUES too, so they are zeroed, or 0 x NaN gets
            # in.
            kv_at = c * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (chunk, width), 0)
            if window is not None:
                kv_at = base + kv_at
            kv_live = kv_at < hi
            kv = jnp.where(kv_live, kv, jnp.zeros_like(kv))
        s = jax.lax.dot_general(
            q_ref[0], kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [rows, chunk]
        if masked:
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_new = correction * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * correction + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :latent], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # Chunks whole below every live query of the tile (and so below `hi`)
    # need no mask: `where(True, x, .)` is x, the same arithmetic in the
    # same order. Only the chunks that reach the tile's queries, and the
    # walk's last, can hold a key some query may not see or a row past
    # `hi`.
    jax.lax.fori_loop(0, n_plain, lambda c, _: chunk_step(c, False), None)
    jax.lax.fori_loop(n_plain, n_chunks, lambda c, _: chunk_step(c, True),
                      None)

    # A row with nothing live (an idle slot, a padded query, a tile whose
    # queries are all masked) has l = 0 and a zero accumulator: it writes
    # zeros, never 0/0.
    denom = jnp.maximum(l_scr[:, :1], 1e-30)
    o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _tiles(n_rows: int, block_size: int, dtype) -> tuple:
    """(query rows a grid step, pages a chunk) for this call's shape."""
    sublanes = 32 // jnp.dtype(dtype).itemsize
    rows = min(_MAX_Q_ROWS, -(-n_rows // sublanes) * sublanes)
    chunk = _CHUNK_TOKENS_FEW_ROWS if rows <= _FEW_ROWS else _CHUNK_TOKENS
    return rows, max(1, chunk // block_size)


@functools.partial(jax.jit, static_argnames=("latent", "scale", "interpret",
                                             "window"))
def _latent_attention_pallas(q, arena, block_tables, positions, write_mask,
                             *, latent: int, scale: float,
                             interpret: bool = False, window=None):
    # Jitted on its own so that a model's layers share one trace and one
    # lowering of the kernel (ops/paged_attention.py says what that saved).
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, heads, width = q.shape
    nb, bsz, _ = arena.shape
    n_rows = s * heads
    rows, pages = _tiles(n_rows, bsz, q.dtype)
    q_pos, walk, _ = tile_walk(
        positions, write_mask, heads=heads, block_size=bsz,
        max_ctx=block_tables.shape[1] * bsz, dtype=q.dtype,
        **({} if window is None else {"window": window}))
    n_tiles = q_pos.shape[1] // rows
    qr = jnp.pad(q.reshape(b, n_rows, width),
                 ((0, 0), (0, n_tiles * rows - n_rows), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, latent=latent,
                          **({} if window is None else {"window": window})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_tiles),
            in_specs=[
                pl.BlockSpec((1, rows, width), lambda i, t, *_: (i, t, 0)),
                pl.BlockSpec((1, rows, 1), lambda i, t, *_: (i, t, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, rows, latent),
                                   lambda i, t, *_: (i, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, bsz, width), arena.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, latent), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, n_tiles * rows, latent), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        # Two stable names the device trace finds: a decode step's calls
        # are bound by the pages' bytes, a prefill chunk's by its products.
        name=KERNELS[0] if s == 1 else KERNELS[1],
    )(walk, block_tables.astype(jnp.int32), qr, q_pos[..., None], arena)
    return out[:, :n_rows].reshape(b, s, heads, latent)


# --------------------------------------------------------------------------- #
# Dispatch
# --------------------------------------------------------------------------- #


def _dispatch(q, arena, latent: int) -> bool:
    """True when the kernel takes this call. Recorded beside the paged
    kernel's calls (`ops.attention.pallas_status`, `paged_calls`)."""
    platform = _attn._platform()
    b, s, heads, width = q.shape
    _, bsz, _ = arena.shape
    dtype = jnp.dtype(arena.dtype)
    if _attn._interpret() and platform == "tpu":
        raise RuntimeError(
            "RAY_TPU_PALLAS_INTERPRET=1 is a CPU test switch; on platform "
            "tpu it would run the interpreter under the kernel's name")
    if platform != "tpu" and not _attn._interpret():
        reason = f"platform {platform}"
    elif width % _LANES or latent % _LANES:
        # A page is whole lane tiles and the values a static slice of them.
        reason = "page width or latent not a multiple of 128"
    elif dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)) \
            or jnp.dtype(q.dtype) != dtype:
        reason = "q and arena not both bfloat16 or both float32"
    elif bsz % (32 // dtype.itemsize):
        reason = "block_size not a multiple of the dtype's sublane tile"
    else:
        reason = ""
    rows, pages = _tiles(s * heads, bsz, q.dtype)
    key = (PASSES[0] if s == 1 else PASSES[1],
           "reference" if reason else "pallas", reason, tuple(q.shape),
           dtype.name, rows, pages * bsz)
    with _attn._CALLS_LOCK:
        _attn._CALLS[key] += 1
    return not reason


def latent_attention_status() -> list:
    """One entry per distinct traced call of `latent_attention`: `pass`,
    `path` ("pallas" or "reference"), `reason`, `shape` (q's), `dtype`,
    `block_q` (query rows a grid step), `block_k` (tokens a chunk),
    `calls`."""
    return [r for r in _attn.pallas_status() if r["pass"] in PASSES]


def latent_attention(q, arena, block_tables, positions, write_mask=None, *,
                     latent: int, scale: float, window=None) -> jax.Array:
    """Absorbed latent attention of q [b, s, heads, latent + rope] (the
    absorbed query beside the rotated rope query) over the paged latent
    cache `arena` [num_blocks, block_size, width] AS IT IS AFTER this
    call's scatter: query (i, t) sees logical positions <= positions[i, t]
    of row i, position p living at block_tables[i, p // bs], offset p % bs.
    Returns o_lat [b, s, heads, latent] in q's dtype.

    `write_mask` [b, s] marks the queries whose output is used; the kernel
    reads a row's pages only up to its last such query, a query tile at a
    time (`tile_walk`): an idle slot reads nothing and gets zeros; a masked
    query's output is finite and otherwise unspecified, on either path, and
    on the kernel's zero where its whole tile is masked.

    `window` (None: every earlier key) keeps a query to its last `window`
    keys, its own among them: the table's entries for pages wholly behind
    a row's window are never read, so they may name any block (the engine
    gives such pages back: docs/INFERENCE.md finding (j))."""
    width = arena.shape[-1]
    if q.shape[-1] > width:
        raise ValueError(f"q is {q.shape[-1]} wide, a page {width}")
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, width - q.shape[-1]),))
    if write_mask is None:
        write_mask = jnp.ones(positions.shape, bool)
    if _dispatch(q, arena, latent):
        return _latent_attention_pallas(
            q, arena, block_tables, positions, write_mask, latent=latent,
            scale=float(scale), interpret=_attn._interpret(),
            **({} if window is None else {"window": int(window)}))
    return latent_attention_reference(q, arena, block_tables, positions,
                                      latent=latent, scale=scale,
                                      window=window)
