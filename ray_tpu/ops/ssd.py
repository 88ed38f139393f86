"""Mamba-2's state-space recurrence (SSD) for the serving path: a chunked
Pallas TPU kernel that takes an initial state and returns the final one
(prefill), a one-token kernel that updates every slot's state in place
(decode), and the short causal convolution before them, with its carried
tail.

Per head, with a state `H` in R^{P x N} (P the head's width, N the state's):

    H_t = exp(dt_t a) H_{t-1} + dt_t x_t B_t^T        a < 0, dt_t >= 0
    y_t = H_t C_t + D x_t

`B_t`, `C_t` in R^N are shared by the `heads // groups` adjacent heads of a
group. `ssd_scan` is exactly that, one position at a time under `lax.scan`:
the definition, the fallback, and what the tests hold the kernels to. A
position with `dt_t = 0` leaves the state as it was, exactly (exp(0) H + 0):
that is how padded positions of a chunk and held rows of a decode step are
masked, on every path.

The state is kept TRANSPOSED, `[slots, heads, N, P]` float32: the head's
width (128) is the lane dimension, so that the decode step's readout
`sum_n H[n, p] C[n]` adds vregs along sublanes and `y` comes out as a row,
the layout the projections around it use; and the chunk's state products
are `B^T X` and `C H` with no transpose. One array holds every slot's state
of a layer. Both kernels alias it to their output, so a step touches only
the blocks it is given: the other slots' states are not copied.

`ssd_chunk_fwd` (kernel `ssd_chunk_fwd`; chunk 128, the family's): one grid
step is one (row, head) and walks the row's chunks in order with the state
as a value. With g the running sum of `dt a` from the chunk's start:

    M[t, j] = exp(g_t - g_j) dt_j C_t.B_j             j <= t
    Y = M X + exp(g) * (C H_0)
    H_end = exp(g_L) H_0 + (exp(g_L - g) dt * B)^T X

The row's slot picks its block of the state through a scalar-prefetched
index; a fresh row (first position 0) starts from zero whatever the slot
held. Matmul operands are bf16 into f32 accumulation, as everywhere else;
the carried state, every exp() and the running sums are f32.

`ssd_step` (kernel `ssd_step`): one token of every slot. A grid step is one
(slot, group): 16 heads' states, 2 MB in and 2 MB out. Everything is f32 on
the VPU: `H = decay * H + B (dt x)^T`, `y = sum_n H * C`. B and C arrive as
rows and are needed along sublanes: one K=8 product against a one-hot row
broadcasts each over the lanes, once a grid step, exact.

Dispatch is a rule, as in `ops/gated_delta.py`: on platform `tpu` a call the
kernels take goes to the kernels; every other call runs the scan and is
recorded with the reason. `ssd_status()` lists the path of every traced
call. RAY_TPU_PALLAS_INTERPRET=1 runs the kernels in the interpreter on
the CPU (tests). Each kernel wrapper is jitted on its own, so a model's
layers share one trace and one lowering of it.
"""

from __future__ import annotations

import collections
import functools
import threading

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _interpret, _platform
from ray_tpu.ops.gated_delta import _NN, _NT, _TN, _column, _dot, _iotas

CHUNK = 128
_VMEM_LIMIT = 48 * 1024 * 1024


# --------------------------------------------------------------------------- #
# The definition: one position at a time
# --------------------------------------------------------------------------- #


def ssd_scan(x, dt, a, b, c, d, state):
    """The recurrence of the module docstring under `lax.scan`, in f32.
    x [batch, seq, heads, P], dt [batch, seq, heads] (after softplus; 0
    where a position is padding), a, d [heads], b, c [batch, seq, groups,
    N], state [batch, heads, N, P]. Returns (y [batch, seq, heads, P] f32,
    the state after the last position)."""
    f32 = jnp.float32
    rep = x.shape[2] // b.shape[2]
    xf, dtf = x.astype(f32), dt.astype(f32)
    bf, cf = (jnp.repeat(t.astype(f32), rep, axis=2) for t in (b, c))
    af = a.astype(f32)

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs                    # [b, h, .], [b, h]
        h = h * jnp.exp(dt_t * af)[..., None, None] + jnp.einsum(
            "bhn,bhp->bhnp", b_t, dt_t[..., None] * x_t)
        return h, jnp.einsum("bhnp,bhn->bhp", h, c_t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xf, dtf, bf, cf))
    state, y = jax.lax.scan(step, state.astype(f32), xs)
    return jnp.moveaxis(y, 0, 1) + d.astype(f32)[:, None] * xf, state


def causal_conv1d_carried(x, w, bias, tail, valid):
    """Depthwise causal convolution with a bias and a carried tail.
    x [batch, seq, channels], w [channels, width], bias [channels], `tail`
    [batch, width-1, channels] the inputs before this call's first (zeros
    at a sequence's start), `valid` [batch, seq] a PREFIX mask of the real
    positions. y_t = bias + sum_j w[:, j] x_{t-(width-1)+j}, in f32.
    Returns (y f32, the new tail: the last width-1 inputs up to the last
    valid position, in the tail's dtype; a row with no valid position keeps
    its tail)."""
    f32 = jnp.float32
    keep = w.shape[1] - 1
    seq = x.shape[1]
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    pf, wf = padded.astype(f32), w.astype(f32)
    y = sum(pf[:, j:j + seq] * wf[:, j] for j in range(keep + 1))
    n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
    new_tail = jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(
        p, n, keep, axis=0))(padded, n_valid)
    return y + bias.astype(f32), new_tail.astype(tail.dtype)


# --------------------------------------------------------------------------- #
# Kernels
# --------------------------------------------------------------------------- #


def _chunk_kernel(slots_ref, keep_ref, x_ref, b_ref, c_ref, g_ref, dt_ref,
                  s_ref, y_ref, so_ref, *, chunks: int, chunk: int):
    from jax.experimental import pallas as pl

    del slots_ref                      # read by the state's index map
    f32, bf16 = jnp.float32, jnp.bfloat16
    state = jnp.where(keep_ref[pl.program_id(0)] > 0, s_ref[0, 0], 0.0)
    r, col = _iotas((chunk, chunk))
    last = (col == chunk - 1)[:1, :]
    for i in range(chunks):
        rows = slice(i * chunk, (i + 1) * chunk)
        xc, bc, cc = x_ref[0, rows, :], b_ref[0, rows, :], c_ref[0, rows, :]
        g_row, dt_row = g_ref[0, 0, i:i + 1, :], dt_ref[0, 0, i:i + 1, :]
        g_col, dt_col = _column(g_row), _column(dt_row)
        # exp() only of what is <= 0: above the diagonal g_t - g_j > 0 is
        # not part of the chunk.
        decay = jnp.exp(jnp.where(r >= col, g_col - g_row, -jnp.inf))
        m = decay * _dot(cc, bc, _NT) * dt_row
        y = _dot(m.astype(bf16), xc, _NN) + jnp.exp(g_col) * _dot(
            cc, state.astype(bf16), _NN)
        y_ref[0, rows, :] = y.astype(y_ref.dtype)
        g_last = jnp.sum(jnp.where(last, g_row, 0.0), axis=-1, keepdims=True)
        weighted = bc.astype(f32) * (jnp.exp(g_last - g_col) * dt_col)
        state = jnp.exp(g_last) * state + _dot(weighted.astype(bf16), xc, _TN)
    so_ref[0, 0] = state.astype(so_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_chunk_pallas(x, b, c, gcum, dt, states, slots, keep,
                      interpret: bool = False):
    """x [batch, seq, heads*P] bf16, b, c [batch, seq, groups*N] bf16,
    gcum, dt [batch, heads, chunks, CHUNK] f32 (gcum the sum of dt*a from
    its chunk's start), states [slots, heads, N, P] f32, slots, keep
    [batch] int32. Returns (y [batch, seq, heads*P] f32, states)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, _ = x.shape
    _, heads, n, p = states.shape
    rep = heads // (b.shape[2] // n)
    chunks = seq // CHUNK
    gate = pl.BlockSpec((1, 1, chunks, CHUNK), lambda i, h, *_: (i, h, 0, 0))
    group = pl.BlockSpec((1, seq, n), lambda i, h, *_: (i, 0, h // rep))
    wide = pl.BlockSpec((1, seq, p), lambda i, h, *_: (i, 0, h))
    state = pl.BlockSpec((1, 1, n, p),
                         lambda i, h, slots, keep: (slots[i], h, 0, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, chunks=chunks, chunk=CHUNK),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(batch, heads),
            in_specs=[wide, group, group, gate, gate, state],
            out_specs=[wide, state]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # The state in place: operand 7 (after the two prefetched scalars).
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssd_chunk_fwd",
    )(slots, keep, x, b, c, gcum, dt, states)


def _step_kernel(xdt_ref, dec_ref, b_ref, c_ref, s_ref, y_ref, so_ref, *,
                 rep: int):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    n, p = s_ref.shape[2:]
    r, _ = _iotas((8, p))
    first = (r == 0).astype(f32)

    def over_lanes(row):
        """[1, N] -> [N, P] with the row's values along sublanes: the
        row against a one-hot row, contracted over 8 (exact)."""
        return jax.lax.dot_general(
            jnp.broadcast_to(row, (8, n)), first, _TN,
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=f32)

    b_mat, c_mat = over_lanes(b_ref[0, 0]), over_lanes(c_ref[0, 0])

    def body(j, carry):
        row = pl.ds(j, 1)
        h = s_ref[0, j] * dec_ref[0, row, :] + b_mat * xdt_ref[0, row, :]
        so_ref[0, j] = h.astype(so_ref.dtype)
        y_ref[0, row, :] = jnp.sum(h * c_mat, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, rep, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_step_pallas(xdt, dec, b, c, states, interpret: bool = False):
    """xdt (dt * x), dec (the decay over the lanes) [slots, heads, P] f32,
    b, c [slots, groups, 1, N] f32, states [slots, heads, N, P] f32.
    Returns (y [slots, heads, P] f32, states)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, n, p = states.shape
    groups = b.shape[1]
    rep = heads // groups
    wide = pl.BlockSpec((1, rep, p), lambda t, g: (t, g, 0))
    group = pl.BlockSpec((1, 1, 1, n), lambda t, g: (t, g, 0, 0))
    state = pl.BlockSpec((1, rep, n, p), lambda t, g: (t, g, 0, 0))
    return pl.pallas_call(
        functools.partial(_step_kernel, rep=rep),
        grid=(slots, groups),
        in_specs=[wide, wide, group, group, state],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct(xdt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssd_step",
    )(xdt, dec, b, c, states)


# --------------------------------------------------------------------------- #
# Dispatch
# --------------------------------------------------------------------------- #

# (pass, path, reason, shape, dtype) -> traced calls
_CALLS: collections.Counter = collections.Counter()
_CALLS_LOCK = threading.Lock()


def ssd_status() -> list:
    """Which path every traced recurrence call of this process took: one
    entry per distinct (pass, shape) with `pass` "chunk_fwd" or "step",
    `path` "pallas" or "scan", the dispatch rule's `reason` for a scan
    call, `shape` [batch, seq, heads, P, N] and the number of traced
    calls."""
    with _CALLS_LOCK:
        items = list(_CALLS.items())
    return [{"pass": p, "path": path, "reason": reason, "shape": list(shape),
             "dtype": dtype, "calls": n}
            for (p, path, reason, shape, dtype), n in items]


def reset_ssd_status() -> None:
    with _CALLS_LOCK:
        _CALLS.clear()


def _dispatch(pass_: str, x, b, states) -> bool:
    """True when the kernels take this call. Records the decision."""
    platform = _platform()
    batch, seq, heads, p = x.shape
    n = states.shape[2]
    if _interpret() and platform == "tpu":
        raise RuntimeError(
            "RAY_TPU_PALLAS_INTERPRET=1 is a CPU test switch; on platform "
            "tpu it would run the interpreter under the kernels' name")
    if platform != "tpu" and not _interpret():
        reason = f"platform {platform}"
    elif p != 128 or n % 128:
        reason = "head width is not the lane width (128) or the state's " \
                 "not a multiple of it"
    elif heads % b.shape[2]:
        reason = "heads not a multiple of groups"
    elif pass_ == "chunk_fwd" and seq % CHUNK:
        reason = f"seq not a multiple of the chunk ({CHUNK})"
    elif pass_ == "step" and (heads // b.shape[2]) % 8:
        reason = "a group's heads do not fill a sublane tile (8)"
    else:
        reason = ""
    key = (pass_, "scan" if reason else "pallas", reason,
           (batch, seq, heads, p, n), jnp.dtype(x.dtype).name)
    with _CALLS_LOCK:
        _CALLS[key] += 1
    return not reason


def ssd_chunk_fwd(x, dt, a, b, c, d, states, slots, fresh, valid):
    """A chunk of positions of `batch` rows, each from its slot's state.
    x [batch, seq, heads, P], dt [batch, seq, heads] f32 (after softplus),
    a, d [heads], b, c [batch, seq, groups, N], `states` [slots, heads, N,
    P] f32 every slot's state, `slots` [batch] int32 each row's slot,
    `fresh` [batch] bool the rows that start from zero state, `valid`
    [batch, seq] bool the real positions (the rest leave the state as it
    was). Returns (y [batch, seq, heads, P] f32, states with the rows'
    final states written at their slots and every other slot as it was)."""
    f32 = jnp.float32
    batch, seq, heads, p = x.shape
    dt = jnp.where(valid[..., None], dt.astype(f32), 0.0)
    slots = slots.astype(jnp.int32)
    if not _dispatch("chunk_fwd", x, b, states):
        start = jnp.where(fresh[:, None, None, None], 0.0, states[slots])
        y, end = ssd_scan(x, dt, a, b, c, d, start)
        return y, states.at[slots].set(end.astype(states.dtype))
    la = dt * a.astype(f32)                               # [b, s, h]

    def gates(t):
        return t.reshape(batch, seq // CHUNK, CHUNK, heads).transpose(
            0, 3, 1, 2)

    xw = x.reshape(batch, seq, -1).astype(jnp.bfloat16)
    y, states = _ssd_chunk_pallas(
        xw, b.reshape(batch, seq, -1).astype(jnp.bfloat16),
        c.reshape(batch, seq, -1).astype(jnp.bfloat16),
        jnp.cumsum(gates(la), axis=-1), gates(dt), states, slots,
        1 - fresh.astype(jnp.int32), interpret=_interpret())
    y = y.reshape(x.shape) + d.astype(f32)[:, None] * x.astype(f32)
    return y, states


def ssd_step(x, dt, a, b, c, d, states, fresh, active):
    """One token of every slot. x [slots, heads, P], dt [slots, heads] f32
    (after softplus), a, d [heads], b, c [slots, groups, N], `states`
    [slots, heads, N, P] f32 updated in place, `fresh` [slots] bool the
    rows that start from zero state, `active` [slots] bool the rows that
    have a token (the others keep their state). Returns (y [slots, heads,
    P] f32, states)."""
    f32 = jnp.float32
    dt = jnp.where(active[:, None], dt.astype(f32), 0.0)
    if not _dispatch("step", x[:, None], b[:, None], states):
        start = jnp.where(fresh[:, None, None, None], 0.0, states)
        y, end = ssd_scan(x[:, None], dt[:, None], a, b[:, None],
                          c[:, None], d, start)
        return y[:, 0], end.astype(states.dtype)
    xf = x.astype(f32)
    decay = jnp.where(fresh[:, None], 0.0, jnp.exp(dt * a.astype(f32)))
    y, states = _ssd_step_pallas(
        dt[..., None] * xf, jnp.broadcast_to(decay[..., None], xf.shape),
        b.astype(f32)[:, :, None, :], c.astype(f32)[:, :, None, :], states,
        interpret=_interpret())
    return y + d.astype(f32)[:, None] * xf, states
