"""Power retention (degree 2, gated, normalised) for the serving path: a
chunked Pallas TPU kernel that takes a slot's state and returns it
(prefill), a one-token kernel that updates every slot's state in place
(decode), and the token-by-token scan that defines both.

Per KV head, with keys and values of width d and the `rep` query heads of
its group (Manifest AI, arXiv:2507.04239; the degree is 2 throughout):

    a_{t,u} = exp(c_t - c_u) (q_t . k_u)^2 / d      u <= t, c the running
    y_t     = sum_u a_{t,u} v_u / (sum_u a_{t,u} + eps)    sum of log-gates

The degree is even, so every weight is non-negative and the normaliser is
a plain sum. The same function as a recurrence: with phi(x) the D =
d(d+1)/2 values x_a^2/sqrt(d) and sqrt(2) x_a x_b/sqrt(d) (a < b), so that
phi(x) . phi(y) = (x . y)^2 / d,

    S_t = e^{gamma_t} S_{t-1} + phi(k_t) v_t^T        [D, d]
    z_t = e^{gamma_t} z_{t-1} + phi(k_t)              [D]
    y_t[i] = phi(q_t[i])^T S_t / (phi(q_t[i])^T z_t + eps)

and for a chunk that starts from (S_0, z_0), with in-chunk running gates b:

    num_t = e^{b_t} phi(q_t)^T S_0 + sum_{u<=t} e^{b_t-b_u} (q_t.k_u)^2/d v_u
    den_t = the same with z_0 and without v
    S_end = e^{b_C} S_0 + sum_u e^{b_C-b_u} phi(k_u) v_u^T

The state is per KV head: it is a function of K, V and the gate alone, and
the group's query heads all read it. `retention_scan` is exactly the
recurrence, one position at a time under `lax.scan`: the definition, the
fallback, and what the tests hold the kernels to.

THE LAYOUT. The D pairs (a, b), a <= b, are indexed by (delta, a) with b =
(a + delta) mod d: delta = 0 holds the d squares, delta = 1 .. d/2 - 1 each
hold d distinct pairs, and delta = d/2 holds each of its pairs twice, so
only a < d/2 is kept there. That is d/2 + 1 tiles of d: D live values in
(d/2 + 1) d stored ones (8,256 in 8,320 at d = 128: the last tile's upper
half is always zero). In this order a tile of phi(x) is `x * roll(x,
-delta)` times a constant: a lane rotation, so phi is made in VMEM a tile
at a time and never exists in HBM. The state is kept TRANSPOSED and tiled,

    states [slots, kv_heads, d/2 + 1, d (v's dim), d (a)]   float32
    sums   [slots, kv_heads, d/2 + 1, d (a)]                float32

with the feature's `a` on the lanes: the decode step's update is then
`S = g S + v_col * phi_k_row` with v along sublanes and a tile of phi(k)
along lanes, and its readout multiplies the same vreg by a tile of phi(q_i)
for each of the group's heads and adds vregs: one state tile in VMEM
serves all five query heads. Both kernels alias the two arrays to their
outputs, so a step touches only the blocks it is given.

`retention_chunk_fwd` (kernel `retention_chunk_fwd`): one grid step is one
(row, KV head). Inside the chunk the weights are the masked square of Q K^T
(bf16 operands: exact products, f32 sums) and `A V` runs in f32; the start
state's readout `phi(Q) S_0` and the update `V^T phi(K)` are f32 matmuls at
the highest precision, a tile of delta at a time, the group's query heads
stacked into one operand. The row's slot picks its blocks through a
scalar-prefetched index; a fresh row starts from zero whatever the slot
held.

`retention_step` (kernel `retention_step`): one token of every slot, a grid
step a (slot, KV head): 4.3 MB of state in and out. Everything is f32 on
the VPU. The tiles of phi(q_i) and phi(k) are made once a grid step
(rotations of one [8, d] vreg that holds the group's q rows, k, v and the
gate), broadcast over sublanes into VMEM, and the state is walked 8 rows of
v's dim at a time over every delta.

Hold comes before reset on every path: a held row (`active` false, or no
`valid` position) keeps its state exactly (gate 1, nothing added), whatever
`fresh` says; a live row that is `fresh` starts from zero.

Dispatch is a rule, as in `ops/ssd.py`: on platform `tpu` a call the
kernels take goes to the kernels; every other call runs the scan and is
recorded with the reason. `retention_status()` lists the path of every
traced call. RAY_TPU_PALLAS_INTERPRET=1 runs the kernels in the
interpreter on the CPU (tests).

The published inference path also has an attention form below a
switch-over length (keys and values are smaller than the state there); it
computes the same function and is left out: this file holds the state form
from the first token. There is no backward.
"""

from __future__ import annotations

import collections
import functools
import math
import threading

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _interpret, _platform
from ray_tpu.ops.gated_delta import _NN, _NT, _TN, _dot, _dot32, _iotas

EPS = 1e-6
_VMEM_LIMIT = 64 * 1024 * 1024
_ROWS = 8                      # a sublane tile: q heads, k, v, gate


def tiles(d: int) -> int:
    """Tiles of d features a state holds: delta = 0 .. d/2."""
    if d % 2:
        raise ValueError(f"power retention wants an even head width, not {d}")
    return d // 2 + 1


def state_shapes(slots: int, kv_heads: int, d: int):
    """(states, sums) shapes of the module docstring's layout."""
    t = tiles(d)
    return (slots, kv_heads, t, d, d), (slots, kv_heads, t, d)


def _phi_tile(x, delta, rotate):
    """Tile `delta` (a Python int or a traced index) of phi of x [.., d]
    f32: x * x[(a + delta) mod d] along the lanes, times phi's constant
    there. `rotate(x, delta)` turns the lanes."""
    d = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    c = jnp.where(delta == 0, 1.0 / math.sqrt(d), math.sqrt(2.0 / d))
    # The last tile holds each pair twice: its upper half stays zero.
    return x * rotate(x, delta) * jnp.where(
        (delta < d // 2) | (lane < d // 2), c, 0.0)


def _roll(x, delta):
    return jnp.roll(x, -delta, axis=-1)


def _lane_rotate(x, delta):
    """`_roll` as the chip's lane rotation (inside a kernel)."""
    from jax.experimental.pallas import tpu as pltpu

    d = x.shape[-1]
    return pltpu.roll(x, (d - delta) % d, x.ndim - 1)


def phi(x):
    """The degree-2 feature map in the module's layout: x [.., d] ->
    [.., d/2 + 1, d] f32 with phi(x) . phi(y) = (x . y)^2 / d. The
    definition (tests, the scan); the kernels make it a tile at a time."""
    xf = x.astype(jnp.float32)
    return jnp.stack([_phi_tile(xf, delta, _roll)
                      for delta in range(tiles(xf.shape[-1]))], axis=-2)


# --------------------------------------------------------------------------- #
# The definition: one position at a time
# --------------------------------------------------------------------------- #


def retention_scan(q, k, v, log_g, states, sums, live=None, eps: float = EPS):
    """The recurrence of the module docstring under `lax.scan`, in f32.
    q [batch, seq, heads, d], k, v [batch, seq, kv_heads, d], log_g [batch,
    seq, kv_heads] (<= 0), states [batch, kv_heads, d/2+1, d, d], sums
    [batch, kv_heads, d/2+1, d], `live` [batch, seq] bool the positions
    that advance the state (None: all). Returns (y [batch, seq, heads, d]
    f32, states, sums after the last position)."""
    f32 = jnp.float32
    batch, seq, heads, d = q.shape
    kvh = k.shape[2]
    rep = heads // kvh
    if live is None:
        live = jnp.ones((batch, seq), bool)
    qg = q.astype(f32).reshape(batch, seq, kvh, rep, d)

    def step(carry, xs):
        s, z = carry
        q_t, k_t, v_t, g_t, on = xs
        on = on[:, None]
        decay = jnp.where(on, jnp.exp(g_t), 1.0)
        pk = jnp.where(on[..., None, None], phi(k_t), 0.0)   # [b, h, t, d]
        s = decay[..., None, None, None] * s \
            + pk[..., None, :] * v_t[..., None, :, None]
        z = decay[..., None, None] * z + pk
        pq = phi(q_t)                                        # [b, h, r, t, d]
        num = jnp.einsum("bhrta,bhtea->bhre", pq, s,
                         precision=jax.lax.Precision.HIGHEST)
        den = jnp.einsum("bhrta,bhta->bhr", pq, z,
                         precision=jax.lax.Precision.HIGHEST)
        return (s, z), num / (den[..., None] + eps)

    xs = (jnp.moveaxis(qg, 1, 0), jnp.moveaxis(k.astype(f32), 1, 0),
          jnp.moveaxis(v.astype(f32), 1, 0),
          jnp.moveaxis(log_g.astype(f32), 1, 0), jnp.moveaxis(live, 1, 0))
    (s, z), y = jax.lax.scan(step, (states.astype(f32), sums.astype(f32)),
                             xs)
    return (jnp.moveaxis(y, 0, 1).reshape(batch, seq, heads, d),
            s.astype(states.dtype), z.astype(sums.dtype))


# --------------------------------------------------------------------------- #
# Kernels
# --------------------------------------------------------------------------- #


def _chunk_kernel(slots_ref, keep_ref, q_ref, k_ref, v_ref, bcol_ref,
                  brow_ref, wcol_ref, s_ref, z_ref, y_ref, so_ref, zo_ref, *,
                  rep: int, eps: float, interpret: bool):
    from jax.experimental import pallas as pl

    rotate = _roll if interpret else _lane_rotate
    del slots_ref                      # read by the state's index maps
    f32 = jnp.float32
    seq, d = k_ref.shape[1:]
    keep = keep_ref[pl.program_id(0)] > 0
    kc, vf = k_ref[0], v_ref[0].astype(f32)
    bcol, brow, wcol = bcol_ref[0, 0], brow_ref[0, 0], wcol_ref[0, 0]
    r, col = _iotas((seq, seq))
    # exp() only of what is <= 0; a position that is not valid has +inf in
    # `brow` and weighs nothing.
    decay = jnp.exp(jnp.where(r >= col, bcol - brow, -jnp.inf))
    grow = jnp.exp(bcol)                                   # [seq, 1]
    qs = [q_ref[0, :, i * d:(i + 1) * d] for i in range(rep)]
    nums, dens = [], []
    # bf16 operands multiply exactly into the f32 sums; f32 ones (the CPU
    # rehearsal's) need the passes.
    qk = _dot if kc.dtype == jnp.bfloat16 else _dot32
    for qi in qs:
        a = qk(qi, kc, _NT)
        a = a * a * (1.0 / d) * decay
        nums.append(_dot32(a, vf, _NN))
        dens.append(jnp.sum(a, axis=-1, keepdims=True))
    q_all = jnp.concatenate([qi.astype(f32) for qi in qs], axis=0)
    kf = kc.astype(f32)
    vw = vf * wcol
    # The whole chunk's decay, over the lanes first ([1, 1] does not
    # broadcast both ways at once).
    total = jnp.exp(jnp.broadcast_to(bcol[seq - 1:seq, :], (1, d)))

    def tile(delta, carry):
        num0, den0 = carry
        s0 = jnp.where(keep, s_ref[0, 0, delta], 0.0)      # [d (e), d (a)]
        z0 = jnp.where(keep, z_ref[0, 0, pl.ds(delta, 1), :], 0.0)
        pq = _phi_tile(q_all, delta, rotate)
        pk = _phi_tile(kf, delta, rotate)
        so_ref[0, 0, delta] = (total * s0 + _dot32(vw, pk, _TN)).astype(
            so_ref.dtype)
        zo_ref[0, 0, pl.ds(delta, 1), :] = (
            total * z0 + jnp.sum(pk * wcol, axis=0, keepdims=True)).astype(
                zo_ref.dtype)
        return (num0 + _dot32(pq, s0, _NT),
                den0 + jnp.sum(pq * z0, axis=-1, keepdims=True))

    num0, den0 = jax.lax.fori_loop(
        0, s_ref.shape[2], tile, (jnp.zeros((rep * seq, d), f32),
                                  jnp.zeros((rep * seq, 1), f32)))
    for i in range(rep):
        rows = slice(i * seq, (i + 1) * seq)
        num = nums[i] + grow * num0[rows]
        den = dens[i] + grow * den0[rows]
        y_ref[0, :, i * d:(i + 1) * d] = (num / (den + eps)).astype(
            y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _retention_chunk_pallas(q, k, v, bcol, brow, wcol, states, sums, slots,
                            keep, eps: float, interpret: bool = False):
    """q [batch, seq, heads*d], k, v [batch, seq, kv_heads*d]; bcol,
    wcol [batch, kv_heads, seq, 1], brow [batch, kv_heads, 1, seq] f32 (the
    running log-gate as a column and, +inf where a position is not valid,
    as a row; a position's weight in the end state); states, sums as in
    the module docstring; slots, keep [batch] int32. Returns (y [batch,
    seq, heads*d] f32, states, sums)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, _ = q.shape
    _, kvh, t, d, _ = states.shape
    rep = q.shape[2] // (kvh * d)
    wide = pl.BlockSpec((1, seq, rep * d), lambda i, h, *_: (i, 0, h))
    narrow = pl.BlockSpec((1, seq, d), lambda i, h, *_: (i, 0, h))
    column = pl.BlockSpec((1, 1, seq, 1), lambda i, h, *_: (i, h, 0, 0))
    row = pl.BlockSpec((1, 1, 1, seq), lambda i, h, *_: (i, h, 0, 0))
    state = pl.BlockSpec((1, 1, t, d, d),
                         lambda i, h, slots, keep: (slots[i], h, 0, 0, 0))
    total = pl.BlockSpec((1, 1, t, d),
                         lambda i, h, slots, keep: (slots[i], h, 0, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, rep=rep, eps=eps,
                          interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(batch, kvh),
            in_specs=[wide, narrow, narrow, column, row, column, state,
                      total],
            out_specs=[wide, state, total]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype),
                   jax.ShapeDtypeStruct(sums.shape, sums.dtype)],
        # The state in place: operands 8 and 9 (after the two prefetched
        # scalars).
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="retention_chunk_fwd",
    )(slots, keep, q, k, v, bcol, brow, wcol, states, sums)


def _step_kernel(x_ref, s_ref, z_ref, y_ref, so_ref, zo_ref, pq_ref, pk_ref,
                 vcol_ref, acc_ref, *, rep: int, eps: float, interpret: bool):
    from jax.experimental import pallas as pl

    rotate = _roll if interpret else _lane_rotate
    f32 = jnp.float32
    t, d = s_ref.shape[2], s_ref.shape[4]
    x = x_ref[0, 0]                                        # [8, d]
    row, _ = _iotas((_ROWS, d))

    def over_sublanes(i: int):
        return jnp.broadcast_to(x[i:i + 1, :], (_ROWS, d))

    gate = over_sublanes(_ROWS - 1)
    # v along sublanes, the same over the lanes: the row against a one-hot
    # row, contracted over 8 (exact).
    vcol_ref[...] = jax.lax.dot_general(
        over_sublanes(_ROWS - 2), (row == 0).astype(f32), _TN,
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=f32)
    # Every tile of phi of the group's q rows and of k, each broadcast over
    # sublanes; the key sum's update and the denominators ride along.
    dens = [jnp.zeros((_ROWS, d), f32)] * rep
    for delta in range(t):
        p = _phi_tile(x, delta, rotate)
        pk = jnp.broadcast_to(p[rep:rep + 1, :], (_ROWS, d))
        pk_ref[delta] = pk
        z = gate * jnp.broadcast_to(z_ref[0, 0, delta:delta + 1, :],
                                    (_ROWS, d)) + pk
        zo_ref[0, 0, delta:delta + 1, :] = z[:1].astype(zo_ref.dtype)
        for i in range(rep):
            pq = jnp.broadcast_to(p[i:i + 1, :], (_ROWS, d))
            pq_ref[i * t + delta] = pq
            dens[i] = dens[i] + pq * z

    def rows_of_v(j, carry):
        rows = pl.ds(pl.multiple_of(j * _ROWS, _ROWS), _ROWS)
        vj = vcol_ref[rows, :]
        accs = [jnp.zeros((_ROWS, d), f32)] * rep
        for delta in range(t):
            s = gate * s_ref[0, 0, delta, rows, :] + vj * pk_ref[delta]
            so_ref[0, 0, delta, rows, :] = s.astype(so_ref.dtype)
            for i in range(rep):
                accs[i] = accs[i] + s * pq_ref[i * t + delta]
        for i in range(rep):
            acc_ref[i, rows, :] = accs[i]
        return carry

    jax.lax.fori_loop(0, d // _ROWS, rows_of_v, 0)
    ones = jnp.ones((_ROWS, d), f32)
    y_ref[0, 0] = jnp.zeros((_ROWS, d), y_ref.dtype)
    for i in range(rep):
        # The lanes' sum of [d (e), d] as a row over e: ones against it.
        num = jax.lax.dot_general(
            ones, acc_ref[i], _NT, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32)[:1]
        den = jnp.sum(dens[i][:1], axis=-1, keepdims=True)
        y_ref[0, 0, i:i + 1, :] = (num / (den + eps)).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rep", "eps", "interpret"))
def _retention_step_pallas(x, states, sums, rep: int, eps: float,
                           interpret: bool = False):
    """x [slots, kv_heads, 8, d] f32: rows 0 .. rep-1 the group's q, row
    `rep` k (zero where the slot is held), row 6 v, row 7 the gate e^gamma
    over the lanes (1 where held, 0 where fresh). Returns (y [slots,
    kv_heads, 8, d] f32 with the group's heads in rows 0 .. rep-1, states,
    sums)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, kvh, t, d, _ = states.shape
    rows = pl.BlockSpec((1, 1, _ROWS, d), lambda s, h: (s, h, 0, 0))
    state = pl.BlockSpec((1, 1, t, d, d), lambda s, h: (s, h, 0, 0, 0))
    total = pl.BlockSpec((1, 1, t, d), lambda s, h: (s, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_step_kernel, rep=rep, eps=eps,
                          interpret=interpret),
        grid=(slots, kvh),
        in_specs=[rows, state, total],
        out_specs=[rows, state, total],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype),
                   jax.ShapeDtypeStruct(sums.shape, sums.dtype)],
        scratch_shapes=[pltpu.VMEM((rep * t, _ROWS, d), jnp.float32),
                        pltpu.VMEM((t, _ROWS, d), jnp.float32),
                        pltpu.VMEM((d, d), jnp.float32),
                        pltpu.VMEM((rep, d, d), jnp.float32)],
        input_output_aliases={1: 1, 2: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="retention_step",
    )(x, states, sums)


# --------------------------------------------------------------------------- #
# Dispatch
# --------------------------------------------------------------------------- #

# (pass, path, reason, shape, dtype) -> traced calls
_CALLS: collections.Counter = collections.Counter()
_CALLS_LOCK = threading.Lock()


def retention_status() -> list:
    """Which path every traced retention call of this process took: one
    entry per distinct (pass, shape) with `pass` "chunk_fwd" or "step",
    `path` "pallas" or "scan", the dispatch rule's `reason` for a scan
    call, `shape` [batch, seq, heads, kv_heads, d] and the number of traced
    calls."""
    with _CALLS_LOCK:
        items = list(_CALLS.items())
    return [{"pass": p, "path": path, "reason": reason, "shape": list(shape),
             "dtype": dtype, "calls": n}
            for (p, path, reason, shape, dtype), n in items]


def reset_retention_status() -> None:
    with _CALLS_LOCK:
        _CALLS.clear()


def _dispatch(pass_: str, q, k) -> bool:
    """True when the kernels take this call. Records the decision."""
    platform = _platform()
    batch, seq, heads, d = q.shape
    kvh = k.shape[2]
    if _interpret() and platform == "tpu":
        raise RuntimeError(
            "RAY_TPU_PALLAS_INTERPRET=1 is a CPU test switch; on platform "
            "tpu it would run the interpreter under the kernels' name")
    if platform != "tpu" and not _interpret():
        reason = f"platform {platform}"
    elif d != 128:
        reason = "head width is not the lane width (128)"
    elif heads % kvh or heads // kvh > _ROWS - 3:
        reason = "a group's query heads, k, v and the gate do not fit a " \
                 f"sublane tile ({_ROWS})"
    elif pass_ == "chunk_fwd" and seq % 128:
        reason = "seq not a multiple of 128"
    else:
        reason = ""
    key = (pass_, "scan" if reason else "pallas", reason,
           (batch, seq, heads, kvh, d), jnp.dtype(q.dtype).name)
    with _CALLS_LOCK:
        _CALLS[key] += 1
    return not reason


def retention_chunk_fwd(q, k, v, log_g, states, sums, slots, fresh, valid,
                        eps: float = EPS):
    """A chunk of positions of `batch` rows, each from its slot's state.
    q [batch, seq, heads, d], k, v [batch, seq, kv_heads, d], log_g [batch,
    seq, kv_heads] f32, `states`, `sums` every slot's (module docstring),
    `slots` [batch] int32 each row's slot, `fresh` [batch] bool the rows
    that start from zero state, `valid` [batch, seq] bool the real
    positions, a prefix of each row (the rest leave the state as it was).
    Returns (y [batch, seq, heads, d] f32, states, sums with the rows'
    final states written at their slots and every other slot as it was)."""
    f32 = jnp.float32
    batch, seq, heads, d = q.shape
    slots = slots.astype(jnp.int32)
    fresh = fresh & jnp.any(valid, axis=1)         # hold before reset
    if not _dispatch("chunk_fwd", q, k):
        zero = fresh[:, None, None, None]
        y, s, z = retention_scan(
            q, k, v, log_g, jnp.where(zero[..., None], 0.0, states[slots]),
            jnp.where(zero, 0.0, sums[slots]), valid, eps)
        return y, states.at[slots].set(s), sums.at[slots].set(z)
    gates = jnp.where(valid[..., None], log_g.astype(f32), 0.0)
    b = jnp.cumsum(gates, axis=1).transpose(0, 2, 1)       # [b, kvh, seq]
    on = valid[:, None, :]
    weight = jnp.where(on, jnp.exp(b[..., -1:] - b), 0.0)
    y, states, sums = _retention_chunk_pallas(
        q.reshape(batch, seq, -1), k.reshape(batch, seq, -1),
        v.reshape(batch, seq, -1),
        b[..., None], jnp.where(on, b, jnp.inf)[:, :, None, :],
        weight[..., None], states, sums, slots,
        1 - fresh.astype(jnp.int32), eps=eps, interpret=_interpret())
    return y.reshape(q.shape), states, sums


def retention_step(q, k, v, log_g, states, sums, fresh, active,
                   eps: float = EPS):
    """One token of every slot. q [slots, heads, d], k, v [slots, kv_heads,
    d], log_g [slots, kv_heads] f32, `states`, `sums` updated in place,
    `fresh` [slots] bool the rows that start from zero state, `active`
    [slots] bool the rows that have a token (the others keep their state).
    Returns (y [slots, heads, d] f32, states, sums)."""
    f32 = jnp.float32
    n, heads, d = q.shape
    kvh = k.shape[1]
    rep = heads // kvh
    if not _dispatch("step", q[:, None], k[:, None]):
        zero = (fresh & active)[:, None, None, None]
        y, s, z = retention_scan(
            q[:, None], k[:, None], v[:, None], log_g[:, None],
            jnp.where(zero[..., None], 0.0, states),
            jnp.where(zero, 0.0, sums), active[:, None], eps)
        return y[:, 0], s, z
    on = active[:, None]
    gate = jnp.where(on, jnp.where(fresh[:, None], 0.0,
                                   jnp.exp(log_g.astype(f32))), 1.0)
    x = jnp.concatenate([
        q.astype(f32).reshape(n, kvh, rep, d),
        jnp.where(on[..., None], k.astype(f32), 0.0)[:, :, None, :],
        jnp.zeros((n, kvh, _ROWS - 3 - rep, d), f32),
        v.astype(f32)[:, :, None, :],
        jnp.broadcast_to(gate[..., None, None], (n, kvh, 1, d))], axis=2)
    y, states, sums = _retention_step_pallas(
        x, states, sums, rep=rep, eps=eps, interpret=_interpret())
    return y[:, :, :rep].reshape(n, heads, d), states, sums
