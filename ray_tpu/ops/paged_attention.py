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
[chunk * kv_heads, head_dim], a strided load (`heads`, below). One grid
step serves one (row, query tile); the KV chunks are an in-kernel loop
whose trip count is the tile's own bound, so a chunk past the live length
costs neither a copy nor a grid step.

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
# The most query rows (query tokens x groups) one grid step holds, and the
# tokens copied and multiplied per loop iteration. Measured on the v5e at
# Mistral widths (PERF.md, PR 25): a decode tile (16 rows) is bound by the
# loop's fixed costs and wants long chunks (512: 92 us a call at ~350 live
# tokens a row, 497 at 4,096; 256: 107 and 772), a prefill tile of 512 rows
# by its matmuls and what the mask wastes of them (256: 72 and 394 us at 512
# and 3,072 positions; 512: 89 and 412). 512 tokens x 8 heads x 128 of bf16
# K and V, double-buffered, are 4 MB of VMEM; 512 rows of f32 statistics
# and accumulator for 8 KV heads 6 MB.
_MAX_Q_ROWS = 512
_CHUNK_TOKENS_FEW_ROWS = 512        # tiles of up to _FEW_ROWS query rows
_CHUNK_TOKENS = 256
_FEW_ROWS = 128
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


def _kernel(hi_ref, bt_ref, q_ref, qpos_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, m_scr, l_scr, acc_scr, *, scale: float):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    row = pl.program_id(0)
    tile = pl.program_id(1)
    _, pages, block_size, kv_heads, head_dim = k_buf.shape
    chunk = pages * block_size
    # Logical positions [0, hi) are all this tile can see: the row's live
    # length, or less where the tile's last query sits below it.
    hi = hi_ref[row, tile]
    n_chunks = (hi + chunk - 1) // chunk

    def for_live_pages(c, slot, do):
        """`do(copy)` for the K and the V copy of every live page of chunk
        c into `slot`. The live pages of a chunk are a prefix of it, so
        this is a loop with a dynamic bound: unrolled and guarded page by
        page, tracing and lowering the copies was most of what a process
        paid for the kernel at start-up (PERF.md, PR 25)."""
        def body(p, carry):
            phys = bt_ref[row, c * pages + p]
            do(pltpu.make_async_copy(k_hbm.at[phys], k_buf.at[slot, p],
                                     sems.at[0, slot]))
            do(pltpu.make_async_copy(v_hbm.at[phys], v_buf.at[slot, p],
                                     sems.at[1, slot]))
            return carry

        live = (jnp.minimum(hi - c * chunk, chunk) + block_size - 1) \
            // block_size
        jax.lax.fori_loop(0, live, body, 0)

    def heads(buf, slot):
        """The chunk in `slot` as one exact f32 [chunk, d] per KV head.
        A token's heads are the sublanes of one tile, so head h is every
        kv_heads-th row of the chunk seen as [chunk * kv_heads, d]: a
        strided load. 16-bit rows come packed in pairs, two heads to a
        32-bit word, and are taken apart by shift and mask (the upper
        half of an f32 IS the bf16), as jax's ragged_paged_attention
        kernel reads its pages."""
        flat = buf.at[slot].reshape(chunk * kv_heads, head_dim)
        if buf.dtype == jnp.float32:
            return [flat[h::kv_heads, :] for h in range(kv_heads)]
        words = flat.bitcast(jnp.uint32)
        out = []
        for pair in range(kv_heads // 2):
            w = words[pair::kv_heads // 2, :]
            out.append(pltpu.bitcast(w << 16, jnp.float32))
            out.append(pltpu.bitcast(w & jnp.uint32(0xFFFF0000),
                                     jnp.float32))
        return out

    def start(c, slot):
        for_live_pages(c, slot, lambda copy: copy.start())

    def wait(c, slot):
        for_live_pages(c, slot, lambda copy: copy.wait())

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(n_chunks > 0)
    def _():
        start(0, 0)

    def body(c, carry):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
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
        for h, (k, v) in enumerate(zip(heads(k_buf, slot),
                                       heads(v_buf, slot))):
            v = jnp.where(v_live, v, 0.0)
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


def _tiles(n_rows: int, block_size: int, dtype) -> tuple:
    """(query rows a grid step, pages a KV chunk) for this call's shape."""
    sublanes = 32 // jnp.dtype(dtype).itemsize     # rows of one packed tile
    rows = min(_MAX_Q_ROWS, -(-n_rows // sublanes) * sublanes)
    chunk = _CHUNK_TOKENS_FEW_ROWS if rows <= _FEW_ROWS else _CHUNK_TOKENS
    return rows, max(1, chunk // block_size)


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
    rows, pages = _tiles(n_rows, bsz, q.dtype)
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

    def q_map(i, t, *_):
        return (i, 0, t, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(hd)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_tiles),
            in_specs=[
                pl.BlockSpec((1, kvh, rows, hd), q_map),
                pl.BlockSpec((1, rows, 1), lambda i, t, *_: (i, t, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, kvh, rows, hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((2, pages, bsz, kvh, hd), k_arena.dtype),
                pltpu.VMEM((2, pages, bsz, kvh, hd), v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((kvh, rows, _LANES), jnp.float32),
                pltpu.VMEM((kvh, rows, _LANES), jnp.float32),
                pltpu.VMEM((kvh, rows, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="paged_attention",
    )(hi, block_tables.astype(jnp.int32), qg, q_pos[..., None], k_arena,
      v_arena)
    out = out[:, :, :n_rows].reshape(b, kvh, s, groups, hd)
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
    rows, pages = _tiles(s * (n_head // kvh), bsz, q.dtype)
    key = ("paged_decode" if s == 1 else "paged_prefill",
           "reference" if reason else "pallas", reason, tuple(q.shape),
           jnp.dtype(q.dtype).name, rows, pages * bsz)
    with _attn._CALLS_LOCK:
        _attn._CALLS[key] += 1
    return not reason


def paged_calls() -> dict:
    """Traced paged-attention calls of this process so far: ((pass,
    "pallas" | "reference: <reason>") -> count), out of `pallas_status()`.
    The difference of two reads says which path a trace in between took
    (`InferenceEngine.stats()["paged_attn"]`)."""
    out: dict = {}
    for r in _attn.pallas_status():
        if r["pass"].startswith("paged_"):
            path = r["path"] + (f": {r['reason']}" if r["reason"] else "")
            key = (r["pass"], path)
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
