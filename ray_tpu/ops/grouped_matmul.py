"""Grouped matrix products over row tiles: Pallas TPU kernels for the
experts a chip holds, forward, data gradient and weight gradient.

    out[rows of tile i] = lhs[rows of tile i] @ rhs[tile_group[i]]

`lhs` [m, k] holds the rows of every group back to back, each group padded
to whole row tiles (zero rows), so that a row tile belongs to ONE group and
the kernels are plain tiled products whose weight block is chosen by a
scalar-prefetched table; no tile straddles two groups, so nothing is
masked. `rhs` is [groups, k, n]. Consecutive tiles of one group keep the
weight block's index, so a group's weights are read once a column block
whatever its load.

Which rule belongs to which path. TRAINED (`grouped_matmul`, a
`custom_vjp` over all three kernels): tiles of `TILE` = 128 rows, and every
group owns at least one (the caller's layout, `held_experts.py`): the
weight gradient writes each group's block exactly once, zeros for a group
nothing was routed to, and needs no zero-filled buffer to accumulate into.
FORWARD ONLY (`grouped_matmul_forward`, `moe_gmm` alone): the row tile is
the caller's (a multiple of 16, bf16's sublane tile), `tile_group` names
only the groups that own a tile and the buffer's tail repeats the last of
them, so a group nothing was routed to is never read. That layout would
leave `moe_gmm_drhs`'s blocks of the absent groups unwritten, which is why
the forward product has no gradient and says so when asked.

Three kernels with stable names the device trace finds: `moe_gmm` (lhs @
rhs[g]), `moe_gmm_dlhs` (dout @ rhs[g]^T) and `moe_gmm_drhs` (lhs_g^T @
dout_g, accumulated in f32 over the group's tiles). Tiles past `n_used`
(the static buffer's unused tail) skip their product and write zeros.
jax's `megablox.gmm` does the general case (tiles that straddle groups,
masked stores) under names the trace cannot tell apart from any other
`kernel`; the aligned layout costs at most one tile of padding a group and
buys kernels a third the size.

The kernels' operands are bf16 (`grouped_matmul` casts the f32 master
weights on the way in), products accumulate in f32, and `moe_gmm_drhs`
returns f32: the weights' gradient never passes through bf16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _interpret, _platform

TILE = 128
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _block(n: int, want: int) -> int:
    """The largest multiple of 128 that divides n and is <= want (n itself
    when n is smaller than a lane tile: CPU test sizes)."""
    if n <= 128 or n % 128:
        return n
    b = min(want, n)
    while n % b:
        b -= 128
    return b


def _column_block(k: int, n: int, tile: int) -> int:
    """Columns of `rhs` a grid step: 512 under a 128-row tile, and as many
    more as the row tile is narrower (the out block keeps its 64K elements)
    while the weight block stays under 8 MB. A 16-row tile takes an
    expert's whole [2048, 1536] or [768, 2048] block in one contiguous
    copy, its rows are fetched once and not once a column block, and a
    call has a third or a quarter of the grid steps (measured on the
    layer alone, PERF.md section 6, PR 51: 4.5% of a decode step's layer,
    10.6% of a chunk's)."""
    room = max(512, (8 << 20) // (2 * k) // 128 * 128)
    return _block(n, min(512 * TILE // tile, room))


def _gmm_kernel(groups_ref, used_ref, lhs_ref, rhs_ref, out_ref, *, dims):
    from jax.experimental import pallas as pl

    del groups_ref
    live = pl.program_id(1) < used_ref[0]

    @pl.when(live)
    def _product():
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _unused_tail():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit,
                   static_argnames=("transpose_rhs", "interpret", "tile"))
def _gmm(lhs, rhs, tile_group, n_used, transpose_rhs: bool = False,
         interpret: bool = False, tile: int = TILE):
    """lhs [m, k] @ rhs[g] ([groups, k, n], or [groups, n, k] with
    `transpose_rhs`) -> [m, n] in lhs's dtype, over row tiles of `tile`
    rows (a multiple of 16, bf16's sublane tile)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _column_block(k, n, tile)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((1, tn, k), lambda j, i, g, u: (g[i], j, 0))
    else:
        rhs_spec = pl.BlockSpec((1, k, tn), lambda j, i, g, u: (g[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, dims=_NT if transpose_rhs else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, m // tile),
            in_specs=[pl.BlockSpec((tile, k), lambda j, i, g, u: (i, 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tile, tn), lambda j, i, g, u: (i, j))),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="moe_gmm_dlhs" if transpose_rhs else "moe_gmm",
    )(tile_group, n_used, lhs, rhs)


def _drhs_kernel(groups_ref, used_ref, lhs_ref, dout_ref, out_ref, acc, *,
                 tiles: int):
    from jax.experimental import pallas as pl

    i = pl.program_id(2)
    here = groups_ref[i]
    before = groups_ref[jnp.maximum(i - 1, 0)]
    after = groups_ref[jnp.minimum(i + 1, tiles - 1)]

    @pl.when((i == 0) | (here != before))
    def _first_tile_of_group():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(i < used_ref[0])
    def _product():
        acc[...] += jax.lax.dot_general(lhs_ref[...], dout_ref[...], _TN,
                                        preferred_element_type=jnp.float32)

    @pl.when((i == tiles - 1) | (here != after))
    def _last_tile_of_group():
        out_ref[0] = acc[...]


@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def _gmm_drhs(lhs, dout, tile_group, n_used, groups: int,
              interpret: bool = False):
    """lhs [m, k], dout [m, n] -> [groups, k, n] f32: each group's
    lhs_g^T @ dout_g."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    n = dout.shape[1]
    tk, tn = _block(k, 512), _block(n, 1024)
    tiles = m // TILE
    return pl.pallas_call(
        functools.partial(_drhs_kernel, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(k // tk, n // tn, tiles),
            in_specs=[
                pl.BlockSpec((TILE, tk), lambda a, b, i, g, u: (i, a)),
                pl.BlockSpec((TILE, tn), lambda a, b, i, g, u: (i, b))],
            out_specs=pl.BlockSpec((1, tk, tn),
                                   lambda a, b, i, g, u: (g[i], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="moe_gmm_drhs",
    )(tile_group, n_used, lhs, dout)


def _interpret_here() -> bool:
    """Off the TPU the kernels run in the interpreter (there is no second
    path); on it never, as `ops/attention.py` and `ops/gated_delta.py`
    refuse."""
    platform = _platform()
    if _interpret() and platform == "tpu":
        raise RuntimeError(
            "RAY_TPU_PALLAS_INTERPRET=1 is a CPU test switch; on platform "
            "tpu it would run the interpreter under the kernels' name")
    return platform != "tpu"


def grouped_matmul_path() -> str:
    """"pallas" where the kernels are compiled for the chip or the test
    switch asked for the interpreter, as the other kernels' status words
    it; "interpret" where they fell to the interpreter unasked."""
    return "interpret" if _interpret_here() and not _interpret() else "pallas"


@jax.custom_vjp
def grouped_matmul(lhs, rhs, tile_group, n_used):
    """out[tile i] = lhs[tile i] @ rhs[tile_group[i]] over row tiles of
    `TILE` rows. lhs [m, k] bf16, rhs [groups, k, n] in its own dtype (cast
    to lhs's for the product; its gradient comes back in f32); tile_group
    [m // TILE] int32, non-decreasing, naming every group at least once;
    n_used [1] int32: tiles from there on are the buffer's unused tail
    (their rows of lhs are zeros, their rows of the result are zeros). The
    Pallas kernels everywhere (the interpreter off the TPU): the layout is
    the kernels' own, there is no second path to dispatch to."""
    return _gmm(lhs, rhs.astype(lhs.dtype), tile_group, n_used,
                interpret=_interpret_here())


def grouped_matmul_forward(lhs, rhs, tile_group, n_used, tile: int):
    """`grouped_matmul`'s product for a step that is never differentiated:
    `moe_gmm` alone, over row tiles of `tile` rows, and `tile_group` names
    only the groups that own a tile (a group without one is never read).
    No `custom_vjp`: `moe_gmm_drhs` would leave an absent group's block
    unwritten, and a bare `pallas_call` with scalar prefetch refuses to be
    differentiated."""
    return _gmm(lhs, rhs.astype(lhs.dtype), tile_group, n_used,
                interpret=_interpret_here(), tile=tile)


def _fwd(lhs, rhs, tile_group, n_used):
    return grouped_matmul.fun(lhs, rhs, tile_group, n_used), (
        lhs, rhs, tile_group, n_used)


def _bwd(residuals, dout):
    lhs, rhs, tile_group, n_used = residuals
    interpret = _interpret_here()
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm(dout, rhs.astype(lhs.dtype), tile_group, n_used,
                transpose_rhs=True, interpret=interpret)
    drhs = _gmm_drhs(lhs, dout, tile_group, n_used, groups=rhs.shape[0],
                     interpret=interpret)
    return dlhs, drhs.astype(rhs.dtype), None, None


grouped_matmul.defvjp(_fwd, _bwd)
