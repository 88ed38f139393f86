"""Learned sparse attention over a paged latent cache: an INDEXER scores
every cached token of a row for each query, the `topk` best are SELECTED,
and latent attention (`ops/latent_attention.py`, the absorbed form) reads
those rows alone.

Three pieces behind one entry, `sparse_latent_attention`:

`index_scores` (scope `dsa_index`, kernel `dsa_index`). A full layer keeps,
beside a token's latent row, its INDEX KEY `kI` [d] in an arena of its own,
[num_blocks, block_size, d], addressed by the same block table. A query
token has `n` index heads `qI` [n, d] and a weight a head `w` [n] (float32,
the model's scale folded in):

    I(p, j) = sum_i w_i(p) * relu(qI^i(p) . kI(j)),   j <= p

The kernel walks a row's index pages as the latent kernel walks its latent
pages (only the pages below the tile's last live query, straight out of
HBM into a double-buffered scratch), a tile of query tokens a grid step:
one product [tokens x n, d] x [d, chunk] into float32, ReLU, the weighted
sum over a token's heads in float32. A position no live query of the tile
may see reads -inf.

`select_topk` (scope `dsa_select`). The EXACT top-`k` of I(p, :) by
COUNTING, not by sorting: the k-th largest score is found bit by bit (32
counts over the row), ties at it go to the LOWER positions, as
`jax.lax.top_k` breaks them, and the chosen positions are laid out in
ASCENDING order by dense arithmetic (a cumulative count a 128-lane group,
a one-hot product a slot): no sort, no scatter, no gather of scalars. A
query with at most `k` visible keys chooses them all.

`gather + attend` (scopes `dsa_gather`, `dsa_attend`). The chosen rows of
every query are gathered token by token out of the latent arena into a
private buffer [queries x k / block, block, width], and the latent kernel
reads it through an identity block table, a query token a row: exactly
`latent_attention` over what was chosen. Where nothing is left out (a
context of at most `k`) those are the sums of plain latent attention, in
another order.

`index_scores_reference`, `select_topk_reference` and
`sparse_latent_attention_reference` are the `jax.numpy` definitions: the
fallback, and the CPU tests' yardstick. Dispatch is `ops/attention.py`'s
rule; every traced call is recorded beside the latent kernel's
(`sparse_status()`, `pallas_status()`, `paged_calls()`: pass
`paged_dsa_index`), and RAY_TPU_PALLAS_INTERPRET=1 runs the kernel in the
interpreter on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as _attn
from ray_tpu.ops.attention import _NEG_INF
from ray_tpu.ops.latent_attention import latent_attention

_LANES = 128
# Query tokens a grid step and tokens copied and multiplied a loop
# iteration: a decode step's tile is one token (its heads are the rows), a
# chunk's tiles hold `_TILE_TOKENS` tokens.
_TILE_TOKENS = 16
_CHUNK_TOKENS_FEW_ROWS = 2048
_CHUNK_TOKENS = 512
_VMEM_LIMIT = 64 * 1024 * 1024
PASS = "paged_dsa_index"
KERNEL = "dsa_index"


# --------------------------------------------------------------------------- #
# The indexer's scores
# --------------------------------------------------------------------------- #


def index_scores_reference(q_idx, w, arena, block_tables, positions,
                           write_mask):
    """The dense definition: I [b, s, max_ctx] float32, -inf where j >
    positions or the query is masked. q_idx [b, s, n, d], w [b, s, n]."""
    nb, bsz, d = arena.shape
    max_ctx = block_tables.shape[1] * bsz
    slot = (block_tables * bsz)[:, :, None] + jnp.arange(bsz)[None, None, :]
    keys = arena.reshape(nb * bsz, d)[slot.reshape(-1, max_ctx)]
    dots = jnp.einsum("bsnd,bkd->bsnk", q_idx.astype(jnp.float32),
                      keys.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    scores = jnp.sum(jax.nn.relu(dots) * w.astype(jnp.float32)[..., None],
                     axis=2)
    seen = (jnp.arange(max_ctx)[None, None, :] <= positions[:, :, None]) \
        & write_mask[:, :, None]
    return jnp.where(seen, scores, _NEG_INF)


def _tiles(s: int, block_size: int) -> tuple:
    """(query tokens a grid step, pages a chunk) for a call of s tokens a
    row."""
    tokens = 1 if s == 1 else _TILE_TOKENS
    chunk = _CHUNK_TOKENS_FEW_ROWS if s == 1 else _CHUNK_TOKENS
    return tokens, max(1, chunk // block_size)


def _index_kernel(hi_ref, bt_ref, q_ref, w_ref, qpos_ref, k_hbm, o_ref,
                  k_buf, sems, *, heads: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    row = pl.program_id(0)
    step = row * pl.num_programs(1) + pl.program_id(1)
    _, pages, block_size, _ = k_buf.shape
    chunk = pages * block_size
    tokens = o_ref.shape[1]
    hi = hi_ref[step]
    n_chunks = (hi + chunk - 1) // chunk

    def for_live_pages(c, slot, do):
        def body(p, carry):
            phys = bt_ref[row, c * pages + p]
            do(pltpu.make_async_copy(k_hbm.at[phys], k_buf.at[slot, p],
                                     sems.at[slot]))
            return carry

        live = (jnp.minimum(hi - c * chunk, chunk) + block_size - 1) \
            // block_size
        jax.lax.fori_loop(0, live, body, 0)

    o_ref[...] = jnp.full(o_ref.shape, _NEG_INF, o_ref.dtype)

    @pl.when(n_chunks > 0)
    def _():
        for_live_pages(0, 0, lambda copy: copy.start())

    def chunk_step(c, _):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            for_live_pages(c + 1, 1 - slot, lambda copy: copy.start())

        for_live_pages(c, slot, lambda copy: copy.wait())
        keys = k_buf[slot].reshape(chunk, k_buf.shape[-1])
        dots = jax.lax.dot_general(
            q_ref[0], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [tokens * heads, chunk]
        weighted = jnp.maximum(dots, 0.0) * w_ref[0]
        scores = jnp.sum(weighted.reshape(tokens, heads, chunk), axis=1)
        k_pos = c * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (tokens, chunk), 1)
        # Pages past `hi` hold whatever was there: selected away, never
        # added.
        scores = jnp.where(k_pos <= qpos_ref[0], scores, _NEG_INF)
        o_ref[0, :, pl.ds(pl.multiple_of(c * chunk, chunk), chunk)] = scores
        return _

    jax.lax.fori_loop(0, n_chunks, chunk_step, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_scores_pallas(q_idx, w, arena, block_tables, positions,
                         write_mask, *, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, heads, d = q_idx.shape
    nb, bsz, _ = arena.shape
    tokens, pages = _tiles(s, bsz)
    chunk = pages * bsz
    n_tiles = -(-s // tokens)
    pad = n_tiles * tokens - s
    max_ctx = block_tables.shape[1] * bsz
    out_ctx = -(-max_ctx // chunk) * chunk
    q_pos = jnp.pad(jnp.where(write_mask, positions, -1).astype(jnp.int32),
                    ((0, 0), (0, pad)), constant_values=-1)
    hi = jnp.clip(q_pos.reshape(b * n_tiles, tokens).max(axis=-1) + 1, 0,
                  max_ctx)
    qr = jnp.pad(q_idx, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, n_tiles * tokens * heads, d)
    wr = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, pad), (0, 0))).reshape(
        b, n_tiles * tokens * heads, 1)
    out = pl.pallas_call(
        functools.partial(_index_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_tiles),
            in_specs=[
                pl.BlockSpec((1, tokens * heads, d),
                             lambda i, t, *_: (i, t, 0)),
                pl.BlockSpec((1, tokens * heads, 1),
                             lambda i, t, *_: (i, t, 0)),
                pl.BlockSpec((1, tokens, 1), lambda i, t, *_: (i, t, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, tokens, out_ctx),
                                   lambda i, t, *_: (i, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, bsz, d), arena.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, n_tiles * tokens, out_ctx),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL,
    )(hi, block_tables.astype(jnp.int32), qr, wr, q_pos[..., None], arena)
    return out[:, :s, :max_ctx]


def _dispatch(q_idx, arena) -> bool:
    platform = _attn._platform()
    b, s, heads, d = q_idx.shape
    _, bsz, _ = arena.shape
    dtype = jnp.dtype(arena.dtype)
    if _attn._interpret() and platform == "tpu":
        raise RuntimeError(
            "RAY_TPU_PALLAS_INTERPRET=1 is a CPU test switch; on platform "
            "tpu it would run the interpreter under the kernel's name")
    if platform != "tpu" and not _attn._interpret():
        reason = f"platform {platform}"
    elif d % _LANES:
        reason = "index keys not a multiple of 128 wide"
    elif dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)) \
            or jnp.dtype(q_idx.dtype) != dtype:
        reason = "queries and keys not both bfloat16 or both float32"
    elif bsz % (32 // dtype.itemsize) or heads % 8:
        reason = "block_size or index heads not whole sublane tiles"
    else:
        reason = ""
    tokens, pages = _tiles(s, bsz)
    key = (PASS, "reference" if reason else "pallas", reason,
           tuple(q_idx.shape), dtype.name, tokens * heads, pages * bsz)
    with _attn._CALLS_LOCK:
        _attn._CALLS[key] += 1
    return not reason


def sparse_status() -> list:
    """One entry per distinct traced call of `index_scores` (`pass`
    `paged_dsa_index`; the fields of `latent_attention_status()`). The
    gathered attention's calls are the latent kernel's own records."""
    return [r for r in _attn.pallas_status() if r["pass"] == PASS]


def index_scores(q_idx, w, arena, block_tables, positions, write_mask=None):
    """I [b, s, max_ctx] float32 of q_idx [b, s, n, d] and head weights w
    [b, s, n] against the paged index keys `arena` [num_blocks, block, d]
    of each row's table, as they are AFTER this call's scatter: query (i,
    t) scores positions <= positions[i, t]; every other entry, and every
    entry of a masked query, is -inf."""
    if write_mask is None:
        write_mask = jnp.ones(positions.shape, bool)
    if _dispatch(q_idx, arena):
        return _index_scores_pallas(q_idx, w, arena, block_tables, positions,
                                    write_mask,
                                    interpret=_attn._interpret())
    return index_scores_reference(q_idx, w, arena, block_tables, positions,
                                  write_mask)


# --------------------------------------------------------------------------- #
# The selection
# --------------------------------------------------------------------------- #


def select_topk_reference(scores, k: int):
    """(positions [.., k] int32 ASCENDING, count [..]): `jax.lax.top_k`'s
    choice (ties to the lower position) of the entries above -inf, the
    chosen first and in ascending order, the rest 0."""
    ctx = scores.shape[-1]
    if ctx < k:
        scores = jnp.pad(scores, ((0, 0),) * (scores.ndim - 1)
                         + ((0, k - ctx),), constant_values=_NEG_INF)
    top, idx = jax.lax.top_k(scores, k)
    live = top > _NEG_INF
    count = jnp.sum(live, axis=-1, dtype=jnp.int32)
    idx = jnp.sort(jnp.where(live, idx, scores.shape[-1]), axis=-1)
    return jnp.where(jnp.arange(k) < count[..., None], idx,
                     0).astype(jnp.int32), count


def _ordered_keys(scores):
    """uint32 keys in the order of the float32 scores (-inf lowest of the
    finite ones' neighbours; the masked entries are told apart by `seen`,
    not by their key)."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.uint32)
    flip = jnp.where(bits >> 31 == 1, jnp.uint32(0xFFFFFFFF),
                     jnp.uint32(0x80000000))
    return bits ^ flip


def select_topk(scores, k: int):
    """The exact top-`k` of scores [.., ctx] (float32, -inf = not a
    candidate) by counting: (positions [.., k] int32 ascending, the chosen
    first, the rest 0; count [..] = min(k, candidates))."""
    lead, ctx = scores.shape[:-1], scores.shape[-1]
    groups = -(-ctx // _LANES)
    flat = jnp.pad(scores.reshape(-1, ctx),
                   ((0, 0), (0, groups * _LANES - ctx)),
                   constant_values=_NEG_INF)
    rows = flat.shape[0]
    seen = flat > _NEG_INF
    keys = jnp.where(seen, _ordered_keys(flat), jnp.uint32(0))
    # The k-th largest key, a bit a count: the largest T that at least k
    # keys reach (0 where fewer than k are candidates: all are chosen).
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        reach = jnp.sum(keys >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(reach >= k, cand, t)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros((rows,), jnp.uint32))
    above = seen & (keys > thr[:, None])
    ties = seen & (keys == thr[:, None])
    need = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    # Ties go to the lower positions: the first `need` of them, counted a
    # lane group at a time (a cumulative sum over ~ctx/128 groups and one
    # over a group's 128 lanes, as a product with a triangle).
    # (0 / 1 in bfloat16 into float32 sums of at most 128: exact)
    lower = jnp.tril(jnp.ones((_LANES, _LANES), jnp.bfloat16)).T
    t3 = ties.reshape(rows, groups, _LANES)
    t_before = jnp.cumsum(jnp.sum(t3, axis=-1, dtype=jnp.int32), axis=-1)
    t_before = t_before - jnp.sum(t3, axis=-1, dtype=jnp.int32)
    t_rank = t_before[..., None] + jnp.einsum(
        "rgl,lm->rgm", t3.astype(jnp.bfloat16), lower,
        preferred_element_type=jnp.float32).astype(jnp.int32)
    chosen = above.reshape(rows, groups, _LANES) \
        | (t3 & (t_rank <= need[:, None, None]))
    # Slot t of the output holds the (t + 1)-th chosen position: its lane
    # group by the groups' cumulative counts, its lane by the group's own.
    per_group = jnp.sum(chosen, axis=-1, dtype=jnp.int32)         # [r, g]
    upto = jnp.cumsum(per_group, axis=-1)
    count = upto[:, -1]
    slot = jnp.arange(k, dtype=jnp.int32)
    group = jnp.sum(upto[:, None, :] <= slot[None, :, None], axis=-1,
                    dtype=jnp.int32)                              # [r, k]
    hit = group[..., None] == jnp.arange(groups, dtype=jnp.int32)
    start = jnp.sum(jnp.where(hit, (upto - per_group)[:, None, :], 0),
                    axis=-1)
    lanes = jnp.einsum("rkg,rgl->rkl", hit.astype(jnp.bfloat16),
                       chosen.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)        # 0 / 1
    within = jnp.einsum("rkl,lm->rkm", lanes.astype(jnp.bfloat16), lower,
                        preferred_element_type=jnp.float32)       # inclusive
    rank = (slot[None, :] - start).astype(jnp.float32)
    lane = jnp.sum(within <= rank[..., None], axis=-1, dtype=jnp.int32)
    pos = jnp.where(slot[None, :] < count[:, None],
                    group * _LANES + lane, 0)
    return pos.reshape(lead + (k,)).astype(jnp.int32), \
        jnp.minimum(count, k).reshape(lead)


# --------------------------------------------------------------------------- #
# The entry
# --------------------------------------------------------------------------- #


def gathered_attention(q, arena, block_tables, chosen, count, write_mask, *,
                       latent: int, scale: float):
    """Latent attention of q [b, s, heads, width'] over the `count` first
    of the `chosen` [b, s, k] logical positions of each query's row: the
    rows gathered out of `arena` into a private paged buffer, a query token
    a row of the latent kernel."""
    b, s, heads, _ = q.shape
    nb, bsz, width = arena.shape
    k = chosen.shape[-1]
    per = -(-k // bsz)
    with jax.named_scope("dsa_gather"):
        # A position's page out of its row's table as a masked sum over
        # the table's entries (a gather of scalars is a slow thing to ask
        # of the TPU: 1.3 ms for 131,072 of them; PERF.md section 6, PR 66).
        at = (chosen // bsz)[..., None] == jnp.arange(
            block_tables.shape[1], dtype=jnp.int32)
        blk = jnp.sum(jnp.where(at, block_tables[:, None, None, :], 0),
                      axis=-1)
        flat = blk * bsz + chosen % bsz
        rows = arena.reshape(nb * bsz, width)[flat.reshape(-1)]
        rows = rows.reshape(b * s, k, width)
        rows = jnp.pad(rows, ((0, 0), (0, per * bsz - k), (0, 0)))
        private = rows.reshape(b * s * per, bsz, width)
        tables = jnp.arange(b * s * per, dtype=jnp.int32).reshape(b * s, per)
    with jax.named_scope("dsa_attend"):
        live = write_mask.reshape(b * s, 1) & (count.reshape(b * s, 1) > 0)
        out = latent_attention(
            q.reshape(b * s, 1, heads, q.shape[-1]), private, tables,
            count.reshape(b * s, 1) - 1, live, latent=latent, scale=scale)
    return out.reshape(b, s, heads, latent)


def sparse_latent_attention(q, q_idx, w_idx, arena, index_arena,
                            block_tables, positions, write_mask=None, *,
                            latent: int, scale: float, topk: int,
                            given=None):
    """Latent attention of q [b, s, heads, latent + rope] over the `topk`
    cached tokens of its row that the indexer scores highest for it (all
    of them while positions + 1 <= topk). q_idx [b, s, n, d] and w_idx [b,
    s, n] are the indexer's query heads and their weights, `index_arena`
    the paged index keys beside the latent `arena`, both addressed by
    `block_tables` and both as they are AFTER this call's scatter.

    Returns (o_lat [b, s, heads, latent], chosen [b, s, topk] int32: the
    logical positions read, ascending, count [b, s] of them valid).
    `given` = (chosen, count) skips the indexer and reads those."""
    if write_mask is None:
        write_mask = jnp.ones(positions.shape, bool)
    if given is None:
        with jax.named_scope("dsa_index"):
            scores = index_scores(q_idx, w_idx, index_arena, block_tables,
                                  positions, write_mask)
        with jax.named_scope("dsa_select"):
            chosen, count = select_topk(scores, topk)
    else:
        chosen, count = given
    out = gathered_attention(q, arena, block_tables, chosen, count,
                             write_mask, latent=latent, scale=scale)
    return out, chosen, count


def sparse_latent_attention_reference(q, q_idx, w_idx, arena, index_arena,
                                      block_tables, positions, write_mask=None,
                                      *, latent: int, scale: float,
                                      topk: int):
    """The dense definition: the reference's scores, `top_k`'s choice as a
    MASK over the row's whole context, a masked softmax. Returns (o_lat,
    the mask [b, s, max_ctx])."""
    if write_mask is None:
        write_mask = jnp.ones(positions.shape, bool)
    nb, bsz, width = arena.shape
    max_ctx = block_tables.shape[1] * bsz
    scores = index_scores_reference(q_idx, w_idx, index_arena, block_tables,
                                    positions, write_mask)
    chosen, count = select_topk_reference(scores, topk)
    valid = jnp.arange(topk) < count[..., None]
    mask = jnp.any((chosen[..., None] == jnp.arange(max_ctx))
                   & valid[..., None], axis=-2)
    slot = (block_tables * bsz)[:, :, None] + jnp.arange(bsz)[None, None, :]
    ctx = arena.reshape(nb * bsz, width)[slot.reshape(-1, max_ctx)]
    ctx = ctx.astype(jnp.float32)
    qf = jnp.pad(q, ((0, 0),) * 3 + ((0, width - q.shape[-1]),))
    s_ = jnp.einsum("bqhw,bkw->bhqk", qf.astype(jnp.float32), ctx,
                    precision=jax.lax.Precision.HIGHEST) * scale
    s_ = jnp.where(mask[:, None], s_, _NEG_INF)
    probs = jnp.where(mask[:, None], jax.nn.softmax(s_, axis=-1), 0.0)
    out = jnp.einsum("bhqk,bkl->bqhl", probs, ctx[..., :latent],
                     precision=jax.lax.Precision.HIGHEST).astype(q.dtype)
    return out, mask
