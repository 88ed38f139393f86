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
heads, a prefill chunk's tiles are 16 tokens x 32 heads each. Scores are
one product of depth `width`, probabilities go into P x V in the arena's
dtype (bf16 on the chip) with float32 accumulation.

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
_MAX_Q_ROWS = 512
# Tokens copied and multiplied a loop iteration: a decode tile (a slot's
# heads, few rows) is bound by the loop's fixed costs and wants long
# chunks; a prefill tile of 512 rows by its products.
_CHUNK_TOKENS_FEW_ROWS = 512
_CHUNK_TOKENS = 256
_FEW_ROWS = 128
_VMEM_LIMIT = 64 * 1024 * 1024
PASSES = ("paged_latent_decode", "paged_latent_prefill")
KERNELS = ("latent_decode", "latent_prefill")


def latent_attention_reference(q, arena, block_tables, positions, *,
                               latent: int, scale: float):
    """The dense definition: gather each row's whole logical context out
    of the arena, float32 scores over the page's full width, mask,
    softmax, values = the first `latent` lanes. q [b, s, heads, width];
    returns [b, s, heads, latent] in q's dtype."""
    nb, bsz, width = arena.shape
    max_ctx = block_tables.shape[1] * bsz
    slot = (block_tables * bsz)[:, :, None] + jnp.arange(bsz)[None, None, :]
    ctx = arena.reshape(nb * bsz, width)[slot.reshape(-1, max_ctx)]
    ctx = ctx.astype(jnp.float32)                         # [b, ctx, width]
    mask = jnp.arange(max_ctx)[None, None, :] <= positions[:, :, None]
    scores = jnp.einsum("bqhw,bkw->bhqk", q.astype(jnp.float32), ctx,
                        precision=jax.lax.Precision.HIGHEST) * scale
    scores = jnp.where(mask[:, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkl->bqhl", probs, ctx[..., :latent],
                      precision=jax.lax.Precision.HIGHEST).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Kernel
# --------------------------------------------------------------------------- #


def _kernel(hi_ref, bt_ref, q_ref, qpos_ref, kv_hbm, o_ref, kv_buf, sems,
            m_scr, l_scr, acc_scr, *, scale: float, latent: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    row = pl.program_id(0)
    tile = pl.program_id(1)
    _, pages, block_size, width = kv_buf.shape
    chunk = pages * block_size
    hi = hi_ref[row, tile]
    n_chunks = (hi + chunk - 1) // chunk

    def for_live_pages(c, slot, do):
        """`do(copy)` for every live page of chunk c into `slot`: ONE copy
        a page, which serves the scores and the values."""
        def body(p, carry):
            phys = bt_ref[row, c * pages + p]
            do(pltpu.make_async_copy(kv_hbm.at[phys], kv_buf.at[slot, p],
                                     sems.at[slot]))
            return carry

        live = (jnp.minimum(hi - c * chunk, chunk) + block_size - 1) \
            // block_size
        jax.lax.fori_loop(0, live, body, 0)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(n_chunks > 0)
    def _():
        for_live_pages(0, 0, lambda copy: copy.start())

    def body(c, carry):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            for_live_pages(c + 1, 1 - slot, lambda copy: copy.start())

        for_live_pages(c, slot, lambda copy: copy.wait())
        q_pos = qpos_ref[0]                                  # [rows, 1]
        rows = q_pos.shape[0]
        k_pos = c * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (rows, chunk), 1)
        mask = (k_pos <= q_pos) & (k_pos < hi)
        # Rows of the buffer at or past `hi` hold whatever was there; they
        # are the VALUES too, so they are zeroed, or 0 x NaN gets in.
        kv = kv_buf[slot].reshape(chunk, width)
        kv_live = c * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (chunk, width), 0) < hi
        kv = jnp.where(kv_live, kv, jnp.zeros_like(kv))
        s = jax.lax.dot_general(
            q_ref[0], kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [rows, chunk]
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_new = correction * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * correction + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :latent], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(0, n_chunks, body, 0)

    # A row with nothing live (an idle slot, a padded query) has l = 0 and
    # a zero accumulator: it writes zeros, never 0/0.
    denom = jnp.maximum(l_scr[:, :1], 1e-30)
    o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _tiles(n_rows: int, block_size: int, dtype) -> tuple:
    """(query rows a grid step, pages a chunk) for this call's shape."""
    sublanes = 32 // jnp.dtype(dtype).itemsize
    rows = min(_MAX_Q_ROWS, -(-n_rows // sublanes) * sublanes)
    chunk = _CHUNK_TOKENS_FEW_ROWS if rows <= _FEW_ROWS else _CHUNK_TOKENS
    return rows, max(1, chunk // block_size)


@functools.partial(jax.jit,
                   static_argnames=("latent", "scale", "interpret"))
def _latent_attention_pallas(q, arena, block_tables, positions, lengths, *,
                             latent: int, scale: float,
                             interpret: bool = False):
    # Jitted on its own so that a model's layers share one trace and one
    # lowering of the kernel (ops/paged_attention.py says what that saved).
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, heads, width = q.shape
    nb, bsz, _ = arena.shape
    max_ctx = block_tables.shape[1] * bsz
    n_rows = s * heads
    rows, pages = _tiles(n_rows, bsz, q.dtype)
    n_tiles = -(-n_rows // rows)
    pad = n_tiles * rows - n_rows
    # Row t * heads + h of a slot is query token t, head h.
    qr = jnp.pad(q.reshape(b, n_rows, width), ((0, 0), (0, pad), (0, 0)))
    q_pos = jnp.pad(jnp.repeat(positions.astype(jnp.int32), heads, axis=1),
                    ((0, 0), (0, pad)), constant_values=-1)
    hi = jnp.minimum(q_pos.reshape(b, n_tiles, rows).max(axis=-1) + 1,
                     lengths[:, None])
    hi = jnp.clip(hi, 0, max_ctx).astype(jnp.int32)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, latent=latent),
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
    )(hi, block_tables.astype(jnp.int32), qr, q_pos[..., None], arena)
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
                     latent: int, scale: float) -> jax.Array:
    """Absorbed latent attention of q [b, s, heads, latent + rope] (the
    absorbed query beside the rotated rope query) over the paged latent
    cache `arena` [num_blocks, block_size, width] AS IT IS AFTER this
    call's scatter: query (i, t) sees logical positions <= positions[i, t]
    of row i, position p living at block_tables[i, p // bs], offset p % bs.
    Returns o_lat [b, s, heads, latent] in q's dtype.

    `write_mask` [b, s] marks the queries whose output is used; the kernel
    reads a row's pages only up to its last such query (an idle slot reads
    nothing and gets zeros; a masked query's output is finite and
    otherwise unspecified, on either path)."""
    width = arena.shape[-1]
    if q.shape[-1] > width:
        raise ValueError(f"q is {q.shape[-1]} wide, a page {width}")
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, width - q.shape[-1]),))
    if write_mask is None:
        write_mask = jnp.ones(positions.shape, bool)
    if _dispatch(q, arena, latent):
        live = jnp.where(write_mask, positions + 1, 0).max(axis=1)
        return _latent_attention_pallas(
            q, arena, block_tables, positions, live, latent=latent,
            scale=float(scale), interpret=_attn._interpret())
    return latent_attention_reference(q, arena, block_tables, positions,
                                      latent=latent, scale=scale)
