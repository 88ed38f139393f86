"""Gated delta rule (Gated DeltaNet's recurrence): chunked Pallas TPU
kernels, forward AND backward, and its elementwise neighbours as two fused
ops with their own backward: `gdn_prep` (the short causal convolution, SiLU
and the L2 norms before it) and `gdn_gate` (the gated norm after it).

Per head, with a state `S` in R^{dk x dv} that starts at zero:

    S' = exp(g_t) S_{t-1}            decay, g_t <= 0
    u_t = beta_t (v_t - S'^T k_t)    the delta rule's correction
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

`gated_delta_scan` is exactly that, one position at a time under
`lax.scan`: the definition, the fallback, and what the tests hold the
kernels to. The kernels compute the same thing a chunk of `CHUNK` = 64
positions at a time (the family's chunk). Inside a chunk, with G the
running sum of g from the chunk's start (kept in f32) and S the state at
its start:

    A[t, j] = beta_t exp(G_t - G_j) k_t.k_j        j < t
    T = (I + A)^-1                                 unit lower triangular
    W = T (beta exp(G) * K),  U0 = T (beta * V)
    U = U0 - W S                                   every u_t of the chunk
    O = (exp(G) * Q) S + (exp(G_t - G_j) q_t.k_j)[j <= t] U
    S_end = exp(G_C) S + (exp(G_C - G) * K)^T U

`gdn_chunk_fwd` walks one (batch, value head)'s chunks in order with the
[dk, dv] state carried in f32; with `save` it also writes the state at every
chunk's start, which is the residual the backward needs (O(seq/64) states,
not O(seq)). `gdn_chunk_bwd` walks the chunks in reverse carrying dS,
recomputes each chunk's T, W, U from its inputs and the saved state, and
writes dq, dk, dv, dG and dbeta. Matmul operands are bf16 with f32
accumulation (as the flash kernels), except the triangular inverse, which
is f32 at `Precision.HIGHEST`; the carried state and every exp() are f32.

A grid step holds `_CHUNKS_PER_STEP` = 8 chunks (512 positions) and walks
them in two phases. Nothing above but the lines that name S needs the
state, and that half is a chain about eleven products deep (K K^T, the
series, W, U0), so a loop that ran whole chunks one after another waited on
the depth of one chunk's chain (2.09 us a chunk forward, PERF.md PR 35).
(1) ABREAST: `_chunk_parts` on [8, 64, d] operands, the chunk index the
batch dimension of every `dot_general` (lowered once, eight independent
chains for the scheduler): decay, A, T, W, U0, P, Kd, Qe, exp(G_C). What
the walk reads of it goes to VMEM scratch: W, Kd, Qe [8, 64, 128] and P
[8, 64, 64] in bf16, U0 in f32, exp(G_C): 88 KB a chunk, 0.7 MB a step.
(2) SERIAL: a `fori_loop` with the state as the loop's VALUE holds only the
products that read it: U = U0 - W S, S_end = exp(G_C) S + Kd^T U, and
O = Qe S + P U beside them. The carry runs through two products a chunk
(W S, then Kd^T U), which is why the loop holds no more. The backward has
three phases: (1) abreast, the same `_chunk_parts` again, and from the
saved states U, P^T dO and Qe^T dO (`_chunk_backward_before`); scratch: Kd,
W, exp(G_C), P^T dO [8, 64, 128] f32 and Qe^T dO [8, 128, 128] f32; (2)
serial in reverse, dU = Kd dS_end + P^T dO and dS = exp(G_C) dS_end +
Qe^T dO - W^T dU (`_chunk_backward_carry`: again two products deep),
leaving every chunk's dS_end (f32) and dU (bf16) in scratch, 1.7 MB a step
in all; (3) abreast again, everything else (`_chunk_backward_after`: dKd,
dQe, dP, dW, dT, dA through the two `HIGHEST` products, the gate and beta
sums), which touches no carry.

The triangular solve is an inverse built from products (the MXU has no
substitution): 16-row diagonal blocks by the nilpotent series (I + D)^-1 =
(I - D)(I + D^2)(I + D^4)(I + D^8), then the blocks joined the same way one
level up ((I + A) = (I + D)(I + N), N^4 = 0; over all 64 rows at once terms
grow like (64 c)^n / n! before they cancel). Ten products a chunk, two
chunks to a product ([64, 128] @ a block-diagonal [128, 128]; the series'
six on [16, 128] rows): only exact zeros differ, so bit-equal to [64, 64]s.

Layout: q, k [batch, seq, key_heads*dk], v [batch, seq, value_heads*dv],
the layout the projections produce; a head is a BlockSpec column block
(dk = dv = 128 = the lane width). A key head serves `value_heads //
key_heads` adjacent value heads through the index map: the repeat is never
materialised, and the caller gets dq, dk per key head. g and beta are
[batch, seq, value_heads] in f32.

Dispatch is a rule, as in `ops/attention.py`: on platform `tpu` a call the
kernels take goes to the kernels; every other call runs the scan and is
recorded with the reason. `gated_delta_status()` lists the path of every
traced call.

Beside the recurrence: a Gated DeltaNet layer projects to qkvz [batch,
seq, q | k | v | z] in bf16, and between that product and the kernels above
stands a chain of elementwise f32 work that jax's own rules turn into five
passes over f32 [batch, seq, channels] arrays through HBM, forward, again in
a rematerialised forward and a third time in the backward (30 ms of a 310 ms
step at [1, 8192, 12288], PERF.md PR 42). `gdn_prep(qkvz, conv_w, head_dim)
-> q, k, v, z_in` and `gdn_gate(o, z_in, norm_w, eps)` are that chain as
`custom_vjp`s with bf16 at their borders and f32 only inside a block in
VMEM; their backward reads the SAME bf16 input and makes the chain again in
VMEM, so no f32 intermediate exists in HBM and the one large residual is
the projection itself. A grid step is one head's [rows, 128] block (`_ROWS`
= 1024 positions, the head's dims on the lanes; a sequence shorter than
that takes as many adjacent heads a block as keep it a block's worth of
work), read from qkvz IN PLACE by
a column offset in the BlockSpec (no slice, no convert); the convolution's
`width - 1` rows before the block come through a second view of the same
array, the 16-row tile that ends where the block starts, and its transpose
in the backward reads d(conv out) of the rows AFTER the block, so the
backward makes the chain for 8 positions more from a third view. `conv_w`'s
gradient [channels, width] and `norm_w`'s [128] are summed in f32 in an
output block the sequence axis revisits. `gdn_prep` is one `pallas_call` a
kind of head (q, k: L2 norm over the lanes, q times head_dim^-0.5; v: none)
writing q, k [batch, seq, key_w], v [batch, seq, val_w]: the layout above,
nothing between it and the recurrence. Its fourth output is the projection
itself, for `gdn_gate` to read z from (the LAST val_w columns, in place):
handed on like that, d z comes back as the cotangent of that output, inside
a [batch, seq, columns] buffer of which `gdn_gate_bwd` wrote only the z
columns and which `gdn_prep_bwd` takes as its own output
(`input_output_aliases`) and completes: the projection's gradient is
written once, by the two backwards, and never summed or concatenated. The
same rule as above decides: platform `tpu`, heads of 128 lanes, the
sequence whole blocks (one block of whole 16-row tiles under 1024
positions), else the chain of jax primitives (`_prep_chain`,
`_gate_chain`: the definition, the fallback, the tests' reference),
recorded as passes `prep_fwd`, `prep_bwd`, `gate_fwd`, `gate_bwd` with path
`pallas` or `xla` beside the recurrence's `fwd` and `bwd`.

Under a remat: `gated_delta_rule` is a `custom_vjp` whose backward reads
q, k, v, g, beta and the chunk-start states. Its forward rule gives what
only the kernel can make the names `gdn_out` and `gdn_states`
(`jax.ad_checkpoint.checkpoint_name`). A caller's `jax.checkpoint` whose
policy keeps both (`save_only_these_names`; at [1, 8192] x 32 heads of 128
that is 67 MB + 268 MB a layer) makes the five operands again from its own
input and runs `gdn_chunk_fwd` ONCE a layer; a policy-less checkpoint runs
it twice (once for the output alone, once more in its backward for the
output and the states). The neighbours' residuals have names too: `gdn_in`
(the bf16 projection, 201 MB a layer: kept, the backward makes neither the
product nor anything of the chain's f32 again), `gdn_qkv` (q, k, v as the
recurrence's backward reads them, 134 MB: kept, `gdn_prep_fwd` runs once),
`gdn_gated` (`gdn_gate`'s output, 67 MB, which the next product's backward
reads: kept, `gdn_gate_fwd` runs once); `gdn_gate`'s own copy of o goes by
`gdn_out` (`_vjp_fwd` says why a name sits on a residual and not on an
output). A name is the identity anywhere else. On the scan path there are
no states to keep and the backward differentiates the scan anew whatever
is kept. RAY_TPU_PALLAS_INTERPRET=1 runs the kernels in the interpreter on
the CPU (tests).
"""

from __future__ import annotations

import collections
import functools
import threading

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import _interpret, _platform

CHUNK = 64
_SUB = 16            # rows of a diagonal block of the triangular inverse
_CHUNKS_PER_STEP = 8  # chunks one grid step walks (512 positions)
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


# --------------------------------------------------------------------------- #
# The definition: one position at a time
# --------------------------------------------------------------------------- #


def gated_delta_scan(q, k, v, g, beta):
    """The recurrence of the module docstring under `lax.scan`, in f32.
    q, k [batch, seq, key_heads, dk], v [batch, seq, value_heads, dv],
    g, beta [batch, seq, value_heads]. Returns [batch, seq, value_heads,
    dv] in v's dtype. Differentiable by jax (the scan keeps a state per
    position: a reference and a fallback, not a training path)."""
    rep = v.shape[2] // k.shape[2]
    f32 = jnp.float32
    qf, kf = (jnp.repeat(t.astype(f32), rep, axis=2) for t in (q, k))
    vf, gf, bf = v.astype(f32), g.astype(f32), beta.astype(f32)

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs                # [b, h, d], [b, h]
        state = state * jnp.exp(g_t)[..., None, None]
        pred = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        u = b_t[..., None] * (v_t - pred)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, u)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    b, _, h, dv = vf.shape
    state0 = jnp.zeros((b, h, qf.shape[-1], dv), f32)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (qf, kf, vf, gf, bf))
    _, out = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)


def causal_conv1d(x, w):
    """Depthwise causal convolution, no bias: x [batch, seq, channels], w
    [channels, width]; y_t = sum_j w[:, j] x_{t - (width-1) + j}, zeros
    before the sequence's start (the published conv1d's weight [channels,
    1, width] with left padding width-1)."""
    width = w.shape[1]
    seq = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j:j + seq] * w[:, j].astype(x.dtype)
               for j in range(width))


# --------------------------------------------------------------------------- #
# The chunk's mathematics, on one chunk's [C, .] values or on [n, C, .]
# values of n chunks abreast (kernel bodies and nothing else)
# --------------------------------------------------------------------------- #


def _dims(dims, rank: int):
    """A 2D product's dimension numbers, or those of the same product of
    every chunk with the chunk index as the batch dimension."""
    if rank == 2:
        return dims
    ((lhs,), (rhs,)), _ = dims
    return (((lhs + 1,), (rhs + 1,)), ((0,), (0,)))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, _dims(dims, a.ndim),
                               preferred_element_type=jnp.float32)


def _dot32(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, _dims(dims, a.ndim),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _iotas(shape):
    """The row and the column index of `shape`'s last two dims."""
    rank = len(shape)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, rank - 2),
            jax.lax.broadcasted_iota(jnp.int32, shape, rank - 1))


def _square(t):
    n = max(t.shape[-2:])
    return t.shape[:-2] + (n, n)


def _column(row):
    """[.., 1, n] -> [.., n, 1]: the diagonal of the row's sublane
    broadcast."""
    r, c = _iotas(_square(row))
    return jnp.sum(jnp.where(r == c, jnp.broadcast_to(row, r.shape), 0.0),
                   axis=-1, keepdims=True)


def _row(col):
    """[.., n, 1] -> [.., 1, n]: the diagonal of the column's lane
    broadcast."""
    r, c = _iotas(_square(col))
    return jnp.sum(jnp.where(r == c, jnp.broadcast_to(col, r.shape), 0.0),
                   axis=-2, keepdims=True)


def _total(m):
    """The sum over the last two dims, kept: [.., 1, 1]."""
    return jnp.sum(jnp.sum(m, axis=-1, keepdims=True), axis=-2,
                   keepdims=True)


# The `HIGHEST` products of the triangular inverse, laid on the 128 x 128
# MXU. A [64, 64] f32 product fills a quarter of it and pushes 64 rows, so
# (1) where `_chunk_parts` holds several chunks they go TWO CHUNKS A
# PRODUCT: the left operands side by side on the lanes (`_pair`, [n, 2n]),
# the right operands as the two diagonal blocks of a [2n, 2n] (`_blocks`):
# [x1 | x2] @ diag(y1, y2) = [x1 y1 | x2 y2]; and (2) the series of the
# `_SUB`-row diagonal blocks, block diagonal itself, runs on the blocks side
# by side, [_SUB, width] rows against all of them as diagonal blocks: a
# quarter of the rows pushed. Both are the same sums with exact zeros added
# or left out: bit-equal to the plain [n, n] products, chunk by chunk.


def _chunks_a_product(abreast: int) -> int:
    """The chunks one MXU product of the triangular inverse holds: two, side
    by side, wherever a grid step runs more than one chunk abreast."""
    return 2 if abreast > 1 else 1


def _pair(mats):
    """Each [chunks, n, n] of `mats` as [pairs, n, 2n], chunk i and chunk
    pairs + i side by side on the lanes (zeros beside the last chunk of an
    odd count), and the way back; a bare [n, n] or one chunk stays as it
    is."""
    chunks = mats[0].shape[0] if mats[0].ndim == 3 else 1
    if _chunks_a_product(chunks) == 1:
        return mats, lambda w: w
    pairs = -(-chunks // 2)

    def pair(x):
        if chunks % 2:
            x = jnp.concatenate([x, jnp.zeros_like(x[:1])], axis=0)
        return jnp.concatenate([x[:pairs], x[pairs:]], axis=-1)

    def unpair(w):
        n = w.shape[-2]
        return jnp.concatenate([w[..., :n], w[..., n:]], axis=0)[:chunks]

    return [pair(x) for x in mats], unpair


def _block_of(index, m: int):
    """index // m of an iota (>= 0, so `lax.div` is the floor; `//` and `%`
    lower through a sign correction a use, and this file's masks are many:
    2 s of a start-up in `jax.lower`)."""
    return jax.lax.div(index, jnp.int32(m))


def _blocks(w):
    """[.., m, width] -> [.., width, width]: w's [m, m] blocks, side by
    side on the lanes, as the diagonal blocks of a square, zeros off them
    (so x @ _blocks(y) is every block of x times its block of y)."""
    m, width = w.shape[-2:]
    if m == width:
        return w
    r, c = _iotas(w.shape[:-2] + (width, width))
    return jnp.where(_block_of(r, m) == _block_of(c, m),
                     jnp.concatenate([w] * (width // m), axis=-2), 0.0)


def _block_eye(shape):
    """The identity in every [m, m] block of a [.., m, width]."""
    r, c = _iotas(shape)
    return (r == jax.lax.rem(c, jnp.int32(shape[-2]))).astype(jnp.float32)


def _block_dot32(x, y):
    return _dot32(x, _blocks(y))


def _nilpotent_inverse(m, order: int):
    """(I + m)^-1 = (I - m)(I + m^2)(I + m^4).. block by block, where every
    [rows, rows] block of m [.., rows, width] has m^order = 0."""
    eye = _block_eye(m.shape)
    inv, power, reach = eye - m, m, 2
    while reach < order:
        power = _block_dot32(power, power)
        inv = _block_dot32(inv, eye + power)
        reach *= 2
    return inv


def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower triangular [.., n, n] f32 `a`, n a
    multiple of `_SUB`, from products alone (module docstring)."""
    n = a.shape[-1]
    (a,), unpair = _pair([a])
    r, c = _iotas(a.shape)
    same = _block_of(r, _SUB) == _block_of(jax.lax.rem(c, jnp.int32(n)), _SUB)
    diag, low = jnp.where(same, a, 0.0), jnp.where(same, 0.0, a)
    rows = [diag[..., i:i + _SUB, :] for i in range(0, n, _SUB)]
    inv = _nilpotent_inverse(sum(rows[1:], rows[0]), _SUB)
    if n > _SUB:
        inv_diag = jnp.where(same, jnp.concatenate([inv] * len(rows),
                                                   axis=-2), 0.0)
        inv = _block_dot32(
            _nilpotent_inverse(_block_dot32(inv_diag, low), n // _SUB),
            inv_diag)
    return unpair(inv)


def _inverse_cotangent(t, dt):
    """da = -t^T dt t^T, what t = (I + a)^-1 hands `a` of its own cotangent
    ([.., n, n] f32 both): t^T as eye @ t^T, which the MXU does in the
    layout it already reads, then two products, laid out as the inverse's
    own."""
    (t, dt), unpair = _pair([t, dt])
    tt = _dot32(_block_eye(t.shape), _blocks(t), _NT)
    return unpair(-_block_dot32(_block_dot32(tt, dt), tt))


def _chunk_parts(q, k, v, g_row, b_row):
    """What forward and backward both need of a chunk and that does not
    depend on the state. q, k, v [.., C, d] bf16; g_row (the running sum of
    log decay), b_row [.., 1, C] f32. With a leading dim every product is
    one batched `dot_general` over the chunks: the body is lowered once
    and the chunks' chains are independent work."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    n = q.shape[-2]
    r, c = _iotas(q.shape[:-2] + (n, n))
    g_col, b_col = _column(g_row), _column(b_row)
    # exp() only of what is <= 0: above the diagonal G_t - G_j > 0 may
    # overflow, and is not part of the chunk.
    decay = jnp.exp(jnp.where(r >= c, g_col - g_row, -jnp.inf))
    e_col = jnp.exp(g_col)
    kk = _dot(k, k, _NT)
    a = jnp.where(r > c, b_col * decay * kk, 0.0)
    t = _unit_lower_inverse(a)
    kf, vf, qf = k.astype(f32), v.astype(f32), q.astype(f32)
    kb = (kf * (b_col * e_col)).astype(bf16)
    vb = (vf * b_col).astype(bf16)
    tb = t.astype(bf16)
    last = (c == n - 1)[..., :1, :]
    g_last = jnp.sum(jnp.where(last, g_row, 0.0), axis=-1,
                     keepdims=True)                  # [.., 1, 1]
    kd = (kf * jnp.exp(g_last - g_col)).astype(bf16)
    qe = (qf * e_col).astype(bf16)
    p = decay * _dot(q, k, _NT)                      # zero above diagonal
    return dict(g_col=g_col, b_col=b_col, e_col=e_col, decay=decay, kk=kk,
                a=a, t=t, tb=tb, kb=kb, vb=vb, kd=kd, qe=qe, p=p,
                g_last=g_last, e_last=jnp.exp(g_last),
                w=_dot(tb, kb, _NN), u0=_dot(tb, vb, _NN),
                strict=r > c, lower=r >= c, last=last)


def _chunk_u(parts, sb):
    """Every u_t of the chunk, bf16, from the bf16 state at its start."""
    return (parts["u0"] - _dot(parts["w"].astype(jnp.bfloat16), sb, _NN)
            ).astype(jnp.bfloat16)


def _chunk_forward(parts, state):
    """(o [C, dv] f32, state at the chunk's end [dk, dv] f32): the products
    that read the state. The carry runs through two of them, W S and
    Kd^T U."""
    sb = state.astype(jnp.bfloat16)
    u = _chunk_u(parts, sb)
    o = _dot(parts["qe"], sb, _NN) + _dot(parts["p"].astype(jnp.bfloat16),
                                          u, _NN)
    end = parts["e_last"] * state + _dot(parts["kd"], u, _TN)
    return o, end


# A chunk's backward in three parts, which together follow `_chunk_parts`
# and `_chunk_forward` backwards, line for line: what needs no carry and
# the serial walk reads (`_before`), what the carried dS runs through
# (`_carry`), and everything else (`_after`).


def _chunk_backward_before(parts, state, do):
    """From the state at the chunk's start and do [C, dv] bf16: u, and
    the terms of du and d_state that `o = qe state + p u` gives."""
    sb = state.astype(jnp.bfloat16)
    pb = parts["p"].astype(jnp.bfloat16)
    return dict(sb=sb, u=_chunk_u(parts, sb),
                du_o=_dot(pb, do, _TN), ds_o=_dot(parts["qe"], do, _TN))


def _chunk_backward_carry(x, d_end):
    """(du bf16, d_state f32) from the gradient `d_end` of the state at
    the chunk's end; x holds the chunk's kd, w (bf16), e_last, du_o, ds_o.
    The carry runs through two products, Kd dS and W^T dU."""
    # end = e_last * state + kd^T u;  o = qe state + p u
    du = _dot(x["kd"], d_end.astype(jnp.bfloat16), _NN) + x["du_o"]
    # u = u0 - w state
    dub = du.astype(jnp.bfloat16)
    d_state = x["e_last"] * d_end + x["ds_o"] - _dot(x["w"], dub, _TN)
    return dub, d_state


def _chunk_backward_after(q, k, v, parts, before, state, do, d_end, dub):
    """(dq, dk, dv [C, d] f32, dG_row, db_row [1, C] f32) once the walk
    has left the chunk's `d_end` and `dub`."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    x = parts
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    sb, u = before["sb"], before["u"]
    deb = d_end.astype(bf16)
    # end = e_last * state + kd^T u
    dkd = _dot(u, deb, _NT)
    kd_term = jnp.sum(dkd * x["kd"].astype(f32), axis=-1, keepdims=True)
    d_last = x["e_last"] * _total(d_end * state) + _total(kd_term)
    dk = dkd * jnp.exp(x["g_last"] - x["g_col"])
    dg_col = -kd_term
    # o = qe state + p u
    dqe = _dot(do, sb, _NT)
    dp = jnp.where(x["lower"], _dot(do, u, _NT), 0.0)
    dq = dqe * x["e_col"]
    dg_col = dg_col + jnp.sum(dqe * x["qe"].astype(f32), axis=-1,
                              keepdims=True)
    # p = decay * (q k^T)
    dqk = (dp * x["decay"]).astype(bf16)
    dq = dq + _dot(dqk, k, _NN)
    dk = dk + _dot(dqk, q, _TN)
    r1 = dp * x["p"]
    dg_col = dg_col + jnp.sum(r1, axis=-1, keepdims=True)
    dg_row = -jnp.sum(r1, axis=-2, keepdims=True)
    # u = u0 - w state
    dw = -_dot(dub, sb, _NT)
    # w = t kb, u0 = t vb
    dwb = dw.astype(bf16)
    dt = _dot(dwb, x["kb"], _NT) + _dot(dub, x["vb"], _NT)
    dkb = _dot(x["tb"], dwb, _TN)
    dvb = _dot(x["tb"], dub, _TN)
    # kb = k * (beta e^G), vb = v * beta
    dv = dvb * x["b_col"]
    db_col = jnp.sum(dvb * vf, axis=-1, keepdims=True)
    dk = dk + dkb * (x["b_col"] * x["e_col"])
    kb_term = jnp.sum(dkb * kf, axis=-1, keepdims=True) * x["e_col"]
    db_col = db_col + kb_term
    dg_col = dg_col + kb_term * x["b_col"]
    # t = (I + a)^-1: da = -t^T dt t^T
    da = jnp.where(x["strict"], _inverse_cotangent(x["t"], dt), 0.0)
    # a = strict * beta_t * decay * (k k^T)
    dkk = (da * x["b_col"] * x["decay"]).astype(bf16)
    dk = dk + _dot(dkk, k, _NN) + _dot(dkk, k, _TN)
    db_col = db_col + jnp.sum(da * x["decay"] * x["kk"], axis=-1,
                              keepdims=True)
    r2 = da * x["a"]
    dg_col = dg_col + jnp.sum(r2, axis=-1, keepdims=True)
    dg_row = dg_row - jnp.sum(r2, axis=-2, keepdims=True)
    dg_row = dg_row + _row(dg_col) + jnp.where(x["last"], d_last, 0.0)
    return dq, dk, dv, dg_row, _row(db_col)


# --------------------------------------------------------------------------- #
# Kernels
# --------------------------------------------------------------------------- #

# What the serial walk reads of a chunk, kept in VMEM scratch between the
# phases: name -> (trailing shape in units of (chunk, d), dtype); and what
# the backward's walk leaves of a chunk for the third phase.
_FWD_KEPT = {"w": ("cd", jnp.bfloat16), "u0": ("cd", jnp.float32),
             "p": ("cc", jnp.bfloat16), "kd": ("cd", jnp.bfloat16),
             "qe": ("cd", jnp.bfloat16), "e_last": ("11", jnp.float32)}
_BWD_KEPT = {"kd": ("cd", jnp.bfloat16), "w": ("cd", jnp.bfloat16),
             "e_last": ("11", jnp.float32), "du_o": ("cd", jnp.float32),
             "ds_o": ("dd", jnp.float32)}
_BWD_LEFT = {"d_end": ("dd", jnp.float32), "dub": ("cd", jnp.bfloat16)}


def _scratch(kept, steps: int, chunk: int, d: int):
    from jax.experimental.pallas import tpu as pltpu

    size = {"c": chunk, "d": d, "1": 1}
    return [pltpu.VMEM((d, d), jnp.float32)] + [
        pltpu.VMEM((steps, size[dims[0]], size[dims[1]]), dtype)
        for dims, dtype in kept.values()]


def _abreast(refs, gate_refs, steps: int, chunk: int):
    """A grid step's operands with the chunk index in front: [steps, chunk,
    d] of every ref of `refs`, [steps, 1, chunk] of every gate."""
    return ([ref[0].reshape(steps, chunk, ref.shape[-1]) for ref in refs]
            + [ref[0, 0][:, None, :] for ref in gate_refs])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, chunk: int,
                steps: int, save: bool):
    from jax.experimental import pallas as pl

    h_ref, state, *kept = rest if save else (None, *rest)
    kept = dict(zip(_FWD_KEPT, kept))

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    # abreast: every chunk's state-independent part
    parts = _chunk_parts(*_abreast((q_ref, k_ref, v_ref), (g_ref, b_ref),
                                   steps, chunk))
    for name, ref in kept.items():
        ref[...] = parts[name].astype(ref.dtype)

    # serial: the products that read the state, the state the loop's value
    def body(i, start):
        rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        if save:
            h_ref[0, 0, i] = start
        o, end = _chunk_forward({name: ref[i] for name, ref in kept.items()},
                                start)
        o_ref[0, rows, :] = o.astype(o_ref.dtype)
        return end

    state[...] = jax.lax.fori_loop(0, steps, body, state[...])


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, h_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, d_state, *rest, chunk: int,
                steps: int):
    from jax.experimental import pallas as pl

    kept = dict(zip(_BWD_KEPT, rest))
    d_ends, dubs = rest[len(kept):]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        d_state[...] = jnp.zeros_like(d_state)

    # abreast: the forward's state-independent part again, and u
    q, k, v, do, g_row, b_row = _abreast((q_ref, k_ref, v_ref, do_ref),
                                         (g_ref, b_ref), steps, chunk)
    parts = _chunk_parts(q, k, v, g_row, b_row)
    states = h_ref[0, 0]
    before = _chunk_backward_before(parts, states, do)
    made = {**parts, **before}
    for name, ref in kept.items():
        ref[...] = made[name].astype(ref.dtype)

    # serial, in reverse: what the carried dS runs through
    def body(j, d_end):
        i = steps - 1 - j
        d_ends[i] = d_end
        dubs[i], start = _chunk_backward_carry(
            {name: ref[i] for name, ref in kept.items()}, d_end)
        return start

    d_state[...] = jax.lax.fori_loop(0, steps, body, d_state[...])

    # abreast: everything else
    dq, dk, dv, dg, db = _chunk_backward_after(
        q, k, v, parts, before, states, do, d_ends[...], dubs[...])
    for ref, t in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv)):
        ref[0] = t.reshape(steps * chunk, -1).astype(ref.dtype)
    dg_ref[0, 0] = dg.reshape(steps, chunk)
    db_ref[0, 0] = db.reshape(steps, chunk)


def _specs(rep: int, d: int, chunk: int, steps: int, n_blocks: int,
           reverse: bool):
    from jax.experimental import pallas as pl

    def at(t):
        return n_blocks - 1 - t if reverse else t

    rows = steps * chunk
    return {
        "key": pl.BlockSpec((1, rows, d), lambda b, h, t: (b, at(t),
                                                           h // rep)),
        "value": pl.BlockSpec((1, rows, d), lambda b, h, t: (b, at(t), h)),
        "gate": pl.BlockSpec((1, 1, steps, chunk),
                             lambda b, h, t: (b, h, at(t), 0)),
        "states": pl.BlockSpec((1, 1, steps, d, d),
                               lambda b, h, t: (b, h, at(t), 0, 0)),
    }


@functools.partial(jax.jit, static_argnames=("chunk", "steps", "save",
                                             "interpret"))
def _gdn_forward(q, k, v, gcum, beta, chunk: int, steps: int, save: bool,
                 interpret: bool = False):
    """q, k [batch, seq, key_heads*d] bf16, v [batch, seq, value_heads*d]
    bf16, gcum, beta [batch, value_heads, chunks, chunk] f32 (gcum the sum
    of g from its chunk's start). Returns (o like v, states [batch,
    value_heads, chunks, d, d] f32 at every chunk's start, or None)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq, _ = v.shape
    heads, chunks = gcum.shape[1], gcum.shape[2]
    d = v.shape[2] // heads
    rep = heads // (k.shape[2] // d)
    n_blocks = chunks // steps
    sp = _specs(rep, d, chunk, steps, n_blocks, False)
    out_specs = [sp["value"]]
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if save:
        out_specs.append(sp["states"])
        out_shape.append(jax.ShapeDtypeStruct((batch, heads, chunks, d, d),
                                              jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, steps=steps, save=save),
        grid=(batch, heads, n_blocks),
        in_specs=[sp["key"], sp["key"], sp["value"], sp["gate"], sp["gate"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=_scratch(_FWD_KEPT, steps, chunk, d),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_chunk_fwd",
    )(q, k, v, gcum, beta)
    return (out[0], out[1]) if save else (out[0], None)


@functools.partial(jax.jit, static_argnames=("chunk", "steps", "interpret"))
def _gdn_backward(q, k, v, gcum, beta, states, do, chunk: int, steps: int,
                  interpret: bool = False):
    """Returns (dq, dk [batch, seq, value_heads*d]: per VALUE head, the
    caller sums the heads a key head served), dv, dgcum, dbeta)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, chunks = gcum.shape[1], gcum.shape[2]
    batch = v.shape[0]
    d = v.shape[2] // heads
    rep = heads // (k.shape[2] // d)
    n_blocks = chunks // steps
    sp = _specs(rep, d, chunk, steps, n_blocks, True)
    wide = jax.ShapeDtypeStruct(v.shape, v.dtype)
    gate = jax.ShapeDtypeStruct(gcum.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, steps=steps),
        grid=(batch, heads, n_blocks),
        in_specs=[sp["key"], sp["key"], sp["value"], sp["gate"], sp["gate"],
                  sp["states"], sp["value"]],
        out_specs=[sp["value"], sp["value"], sp["value"], sp["gate"],
                   sp["gate"]],
        out_shape=[wide, wide, wide, gate, gate],
        scratch_shapes=_scratch({**_BWD_KEPT, **_BWD_LEFT}, steps, chunk,
                                d),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_chunk_bwd",
    )(q, k, v, gcum, beta, states, do)


# --------------------------------------------------------------------------- #
# The recurrence's elementwise neighbours: `gdn_prep` before it, `gdn_gate`
# after it (module docstring, "Beside the recurrence")
# --------------------------------------------------------------------------- #

_ROWS = 1024  # positions a grid step of the elementwise kernels holds
_HALO = 16    # rows of the view that brings a neighbouring block's edge
_EDGE = 8     # of which the kernels read the nearest (an f32 tile)


def _unit(t):
    return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)


def _widths(qkvz, conv_w):
    """(key_w, val_w) of a projection [.., 2 key_w + 2 val_w] whose first
    2 key_w + val_w columns the convolution's weight covers."""
    return _split(qkvz.shape[-1], conv_w.shape[0])


def _split(columns: int, channels: int):
    val_w = columns - channels
    return (channels - val_w) // 2, val_w


def _prep_chain(qkvz, conv_w, head_dim: int):
    """`gdn_prep` as jax primitives: the definition, the fallback, and what
    the tests hold the kernels to. Returns q, k, v."""
    b, s, _ = qkvz.shape
    key_w, val_w = _widths(qkvz, conv_w)
    mixed = jax.nn.silu(causal_conv1d(
        qkvz[..., :2 * key_w + val_w].astype(jnp.float32), conv_w))
    q = mixed[..., :key_w].reshape(b, s, -1, head_dim)
    k = mixed[..., key_w:2 * key_w].reshape(b, s, -1, head_dim)
    q = (_unit(q) * head_dim ** -0.5).astype(qkvz.dtype)
    k = _unit(k).astype(qkvz.dtype)
    return (q.reshape(b, s, key_w), k.reshape(b, s, key_w),
            mixed[..., 2 * key_w:].astype(qkvz.dtype))


def _gate_chain(o, qkvz, norm_w, eps: float):
    """`gdn_gate` as jax primitives (definition, fallback, reference)."""
    b, s, val_w = o.shape
    d = norm_w.shape[0]
    of = o.astype(jnp.float32).reshape(b, s, -1, d)
    z = qkvz[..., qkvz.shape[-1] - val_w:].reshape(of.shape)
    rms = of * jax.lax.rsqrt(jnp.mean(jnp.square(of), axis=-1,
                                      keepdims=True) + eps)
    out = norm_w * rms * jax.nn.silu(z.astype(jnp.float32))
    return out.astype(o.dtype).reshape(b, s, val_w)


# Kernel bodies. A block is [rows, 128 h]: positions on the sublanes, the
# dims of h adjacent heads on the lanes. h is 1 where the sequence fills a
# block's `_ROWS`; a shorter sequence takes as many heads a block as keep
# rows x h within `_ROWS` (`_heads_a_block`), so that a grid step stays a
# block's worth of work.


def _head_sum(x):
    """The sum over each head's 128 lanes of x [n, 128 h], in place of the
    head's lanes (broadcasts against x)."""
    if x.shape[-1] == 128:
        return jnp.sum(x, axis=-1, keepdims=True)
    return jnp.concatenate(
        [jnp.broadcast_to(jnp.sum(x[:, i:i + 128], axis=-1, keepdims=True),
                          (x.shape[0], 128))
         for i in range(0, x.shape[-1], 128)], axis=-1)


def _conv(ext, w, n: int):
    """c_t = sum_j w[j] x_{t-(width-1)+j} for the n positions that follow
    the first `_EDGE` rows of ext (f32); w [width, 128]."""
    width = w.shape[0]
    first = _EDGE - (width - 1)
    return sum(w[j:j + 1] * ext[first + j:first + j + n]
               for j in range(width))


def _edge(ref, there, part):
    """The `_EDGE` rows of a neighbouring block's view nearest this block,
    in f32; zeros where the sequence has no such neighbour."""
    return jnp.where(there, ref[0].astype(jnp.float32)[part], 0.0)


_BEFORE, _AFTER = slice(_HALO - _EDGE, _HALO), slice(0, _EDGE)


def _prep_fwd_kernel(x_ref, before_ref, w_ref, o_ref, *, scale):
    from jax.experimental import pallas as pl

    rows = x_ref.shape[1]
    ext = jnp.concatenate(
        [_edge(before_ref, pl.program_id(2) > 0, _BEFORE),
         x_ref[0].astype(jnp.float32)], axis=0)
    c = _conv(ext, w_ref[...], rows)
    a = c * jax.nn.sigmoid(c)
    if scale is not None:       # a q or k head: the L2 norm over its lanes
        a = a * (jax.lax.rsqrt(_head_sum(a * a) + 1e-6) * scale)
    o_ref[0] = a.astype(o_ref.dtype)


def _prep_bwd_kernel(x_ref, before_ref, after_ref, w_ref, dn_ref,
                     dn_after_ref, _, dx_ref, dw_ref, *, scale):
    """d(input) of one block and its term of d(conv_w). The convolution's
    transpose reads d(conv out) of the `width - 1` positions AFTER the
    block, so the chain is made again for `_EDGE` positions more."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    t, rows = pl.program_id(2), x_ref.shape[1]
    more = t < pl.num_programs(2) - 1
    w = w_ref[...]
    width = w.shape[0]
    ext = jnp.concatenate([_edge(before_ref, t > 0, _BEFORE),
                           x_ref[0].astype(f32),
                           _edge(after_ref, more, _AFTER)], axis=0)
    dn = jnp.concatenate([dn_ref[0].astype(f32),
                          _edge(dn_after_ref, more, _AFTER)], axis=0)
    c = _conv(ext, w, rows + _EDGE)
    sig = jax.nn.sigmoid(c)
    if scale is None:
        da = dn
    else:                       # n = scale a r, r = (a.a + 1e-6)^-1/2
        a = c * sig
        r = jax.lax.rsqrt(_head_sum(a * a) + 1e-6)
        da = (scale * r) * dn - a * (scale * r * r * r * _head_sum(a * dn))
    dc = da * (sig * (1.0 + c * (1.0 - sig)))
    dx = sum(w[j:j + 1] * dc[width - 1 - j:width - 1 - j + rows]
             for j in range(width))
    dx_ref[0] = dx.astype(dx_ref.dtype)
    first = _EDGE - (width - 1)
    dw = jnp.concatenate(
        [jnp.sum(dc[:rows] * ext[first + j:first + j + rows], axis=0,
                 keepdims=True) for j in range(width)], axis=0)

    @pl.when(t == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[0] += dw


def _gate_fwd_kernel(o_ref, z_ref, w_ref, out_ref, *, eps: float):
    o, z = o_ref[0].astype(jnp.float32), z_ref[0].astype(jnp.float32)
    r = jax.lax.rsqrt(_head_sum(o * o) / 128 + eps)
    out_ref[0] = (w_ref[...] * (o * r) * (z * jax.nn.sigmoid(z))).astype(
        out_ref.dtype)


def _gate_bwd_kernel(o_ref, z_ref, w_ref, dg_ref, do_ref, dz_ref, dw_ref, *,
                     eps: float):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    o, z, dg = (ref[0].astype(f32) for ref in (o_ref, z_ref, dg_ref))
    w = w_ref[...]
    r = jax.lax.rsqrt(_head_sum(o * o) / 128 + eps)
    sig = jax.nn.sigmoid(z)
    n, s = o * r, z * sig
    dgn = dg * n
    dz_ref[0] = (dgn * w * (sig * (1.0 + z * (1.0 - sig)))).astype(
        dz_ref.dtype)
    dn = dg * w * s             # n = o r, r = (mean(o o) + eps)^-1/2
    mean = _head_sum(o * dn) / 128
    do_ref[0] = (r * dn - o * (r * r * r * mean)).astype(do_ref.dtype)
    dw = jnp.sum(dgn * s, axis=0, keepdims=True)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[0, 0] += dw


def _heads_a_block(rows: int, *columns: int) -> int:
    """The heads one block holds: as many as keep rows x heads within
    `_ROWS` and divide every one of `columns` (widths and first columns)."""
    heads = max(1, _ROWS // rows)
    while any(c % (128 * heads) for c in columns):
        heads -= 1
    return heads


def _block_specs(rows: int, n_blocks: int, lanes: int = 128, first: int = 0,
                 taps: int = 1):
    """A [rows, lanes] block of a [batch, seq, columns] array whose heads
    start at column `first`, the `_HALO`-row views of the same array that
    end where the block starts and start where it ends, and the block's
    columns of a [taps, columns] weight."""
    from jax.experimental import pallas as pl

    per, at = rows // _HALO, first // lanes
    return {
        "taps": pl.BlockSpec((taps, lanes), lambda b, h, t: (0, at + h)),
        "block": pl.BlockSpec((1, rows, lanes),
                              lambda b, h, t: (b, t, at + h)),
        "before": pl.BlockSpec(
            (1, _HALO, lanes),
            lambda b, h, t: (b, jnp.maximum(t * per - 1, 0), at + h)),
        "after": pl.BlockSpec(
            (1, _HALO, lanes),
            lambda b, h, t: (b, jnp.minimum(t + 1, n_blocks - 1) * per,
                             at + h)),
    }


def _every_block(lanes: int):
    """The [1, lanes] norm weight (tiled over a block's heads) that every
    block of `gdn_gate` shares."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((1, lanes), lambda b, h, t: (0, 0))


def _elementwise_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _kinds(key_w: int, val_w: int, head_dim: int):
    """q, k, v: (first column of the kind's heads in the projection, their
    width, what the L2 norm is scaled by: None for v, which has none)."""
    return ((0, key_w, head_dim ** -0.5), (key_w, key_w, 1.0),
            (2 * key_w, val_w, None))


@functools.partial(jax.jit, static_argnames=("head_dim", "rows",
                                             "interpret"))
def _gdn_prep_forward(qkvz, conv_w, head_dim: int, rows: int,
                      interpret: bool = False):
    """q, k [batch, seq, key_w], v [batch, seq, val_w] in qkvz's dtype:
    one call a kind of head, each reading its columns of qkvz in place."""
    from jax.experimental import pallas as pl

    batch, seq, _ = qkvz.shape
    n_blocks = seq // rows
    taps = conv_w.T                                   # [width, channels]
    out = []
    for first, width, scale in _kinds(
            *_split(qkvz.shape[-1], conv_w.shape[0]), head_dim):
        lanes = 128 * _heads_a_block(rows, width, first)
        at = _block_specs(rows, n_blocks, lanes, first, taps.shape[0])
        out.append(pl.pallas_call(
            functools.partial(_prep_fwd_kernel, scale=scale),
            grid=(batch, width // lanes, n_blocks),
            in_specs=[at["block"], at["before"], at["taps"]],
            out_specs=_block_specs(rows, n_blocks, lanes)["block"],
            out_shape=jax.ShapeDtypeStruct((batch, seq, width), qkvz.dtype),
            compiler_params=_elementwise_params(),
            interpret=interpret, name="gdn_prep_fwd",
        )(qkvz, qkvz, taps))
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("head_dim", "rows",
                                             "interpret"))
def _gdn_prep_backward(qkvz, conv_w, dq, dk, dv, d_in, head_dim: int,
                       rows: int, interpret: bool = False):
    """(d qkvz, d conv_w). `d_in` [batch, seq, columns] is the cotangent
    of the projection handed on to `gdn_gate`, whose backward wrote its z
    columns: every call below writes its own columns INTO that buffer."""
    from jax.experimental import pallas as pl

    batch, seq, _ = qkvz.shape
    n_blocks = seq // rows
    taps = conv_w.T
    d_outs, d_taps = (dq, dk, dv), []
    for kind, (first, width, scale) in enumerate(_kinds(
            *_split(qkvz.shape[-1], conv_w.shape[0]), head_dim)):
        dn = d_outs[kind]
        lanes = 128 * _heads_a_block(rows, width, first)
        at = _block_specs(rows, n_blocks, lanes, first, taps.shape[0])
        here = _block_specs(rows, n_blocks, lanes)
        d_in, d_tap = pl.pallas_call(
            functools.partial(_prep_bwd_kernel, scale=scale),
            grid=(batch, width // lanes, n_blocks),
            in_specs=[at["block"], at["before"], at["after"], at["taps"],
                      here["block"], here["after"],
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[at["block"],
                       pl.BlockSpec((1, taps.shape[0], lanes),
                                    lambda b, h, t: (b, 0, h))],
            out_shape=[jax.ShapeDtypeStruct(d_in.shape, d_in.dtype),
                       jax.ShapeDtypeStruct((batch, taps.shape[0], width),
                                            jnp.float32)],
            input_output_aliases={6: 0},
            compiler_params=_elementwise_params(),
            interpret=interpret, name="gdn_prep_bwd",
        )(qkvz, qkvz, qkvz, taps, dn, dn, d_in)
        d_taps.append(d_tap)
    d_w = jnp.concatenate(d_taps, axis=-1).sum(axis=0).T
    return d_in, d_w.astype(conv_w.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "rows", "interpret"))
def _gdn_gate_forward(o, qkvz, norm_w, eps: float, rows: int,
                      interpret: bool = False):
    from jax.experimental import pallas as pl

    batch, seq, val_w = o.shape
    n_blocks = seq // rows
    first = qkvz.shape[-1] - val_w
    heads = _heads_a_block(rows, val_w, first)
    here = _block_specs(rows, n_blocks, 128 * heads)
    z = _block_specs(rows, n_blocks, 128 * heads, first)
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, eps=eps),
        grid=(batch, val_w // (128 * heads), n_blocks),
        in_specs=[here["block"], z["block"], _every_block(128 * heads)],
        out_specs=here["block"],
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=_elementwise_params(),
        interpret=interpret, name="gdn_gate_fwd",
    )(o, qkvz, jnp.tile(norm_w.astype(jnp.float32), heads)[None])


@functools.partial(jax.jit, static_argnames=("eps", "rows", "interpret"))
def _gdn_gate_backward(o, qkvz, norm_w, dg, eps: float, rows: int,
                       interpret: bool = False):
    """(do, d qkvz with ONLY its z columns written, d norm_w)."""
    from jax.experimental import pallas as pl

    batch, seq, val_w = o.shape
    n_blocks = seq // rows
    first = qkvz.shape[-1] - val_w
    heads = _heads_a_block(rows, val_w, first)
    lanes = 128 * heads
    here = _block_specs(rows, n_blocks, lanes)["block"]
    z = _block_specs(rows, n_blocks, lanes, first)
    do, d_in, d_w = pl.pallas_call(
        functools.partial(_gate_bwd_kernel, eps=eps),
        grid=(batch, val_w // lanes, n_blocks),
        in_specs=[here, z["block"], _every_block(lanes), here],
        out_specs=[here, z["block"],
                   pl.BlockSpec((1, 1, 1, lanes),
                                lambda b, h, t: (b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(qkvz.shape, qkvz.dtype),
                   jax.ShapeDtypeStruct((batch, val_w // lanes, 1, lanes),
                                        jnp.float32)],
        compiler_params=_elementwise_params(),
        interpret=interpret, name="gdn_gate_bwd",
    )(o, qkvz, jnp.tile(norm_w.astype(jnp.float32), heads)[None], dg)
    return do, d_in, d_w.reshape(-1, 128).sum(axis=0).astype(norm_w.dtype)


# --------------------------------------------------------------------------- #
# Dispatch + custom VJP
# --------------------------------------------------------------------------- #

# (pass, path, reason, shape, dtype, chunk, chunks_abreast,
#  chunks_a_product) -> traced calls
_CALLS: collections.Counter = collections.Counter()
_CALLS_LOCK = threading.Lock()


def gated_delta_status() -> list:
    """Which path every traced call of this process took: one entry per
    distinct (pass, shape). The recurrence's passes `fwd` and `bwd`: `path`
    "pallas" or "scan", the dispatch rule's `reason` for a scan call,
    `shape` [batch, value_heads, seq, head_dim], the `chunk`,
    `chunks_abreast` (how many chunks' state-independent parts one grid
    step of the kernels computes side by side; None on the scan path),
    `chunks_a_product` (how many of them one MXU product of the triangular
    inverse holds: 2 wherever more than one runs abreast, 1 for a single
    chunk, None on the scan path) and the number of traced calls. Its
    neighbours' passes `prep_fwd`, `prep_bwd`, `gate_fwd`, `gate_bwd`:
    `path` "pallas" or "xla" (the chain of jax primitives) with the
    `reason`, `shape` [batch, seq, columns] of the array the op reads,
    `chunk` the positions of a block (None on the xla path),
    `chunks_abreast` and `chunks_a_product` None."""
    with _CALLS_LOCK:
        items = list(_CALLS.items())
    return [{"pass": p, "path": path, "reason": reason, "shape": list(shape),
             "dtype": dtype, "chunk": chunk, "chunks_abreast": abreast,
             "chunks_a_product": a_product, "calls": n}
            for (p, path, reason, shape, dtype, chunk, abreast,
                 a_product), n in items]


def reset_gated_delta_status() -> None:
    with _CALLS_LOCK:
        _CALLS.clear()


def _off_platform() -> str:
    """Why no kernel of this file runs here, whatever the shapes: "" on
    platform `tpu` and under the interpreter switch on any other."""
    platform = _platform()
    if _interpret() and platform == "tpu":
        raise RuntimeError(
            "RAY_TPU_PALLAS_INTERPRET=1 is a CPU test switch; on platform "
            "tpu it would run the interpreter under the kernels' name")
    return "" if platform == "tpu" or _interpret() else f"platform {platform}"


def _dispatch(pass_: str, q, k, v) -> bool:
    """True when the kernels take this call. Records the decision."""
    b, s, hv, dv = v.shape
    reason = _off_platform()
    if reason:
        pass
    elif q.shape[-1] != 128 or dv != 128:
        reason = "head_dim is not the lane width (128)"
    elif hv % k.shape[2]:
        reason = "value heads not a multiple of key heads"
    abreast = None if reason else _steps(_padded_len(s) // CHUNK)
    key = (pass_, "scan" if reason else "pallas", reason, (b, hv, s, dv),
           jnp.dtype(v.dtype).name, CHUNK, abreast,
           abreast and _chunks_a_product(abreast))
    with _CALLS_LOCK:
        _CALLS[key] += 1
    return not reason


def _steps(chunks: int) -> int:
    """The chunks one grid step holds, and runs abreast."""
    return min(_CHUNKS_PER_STEP, chunks)


def _padded_len(seq: int) -> int:
    """Whole chunks, and whole grid steps once there is more than one."""
    chunks = -(-seq // CHUNK)
    if chunks > _CHUNKS_PER_STEP:
        chunks = -(-chunks // _CHUNKS_PER_STEP) * _CHUNKS_PER_STEP
    return chunks * CHUNK


def _kernel_operands(q, k, v, g, beta):
    """The kernels' layout: heads folded into the last dim, bf16; g summed
    within its chunk in f32, g and beta as [batch, heads, chunks, chunk].
    Positions past the sequence's end get k = v = 0, g = 0, beta = 0: they
    leave the state as it is."""
    b, s, hv, _ = v.shape
    pad = _padded_len(s) - s

    def wide(t):
        t = t.reshape(b, s, -1).astype(jnp.bfloat16)
        return jnp.pad(t, ((0, 0), (0, pad), (0, 0)))

    def gates(t):
        t = jnp.pad(t.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
        return t.reshape(b, -1, CHUNK, hv).transpose(0, 3, 1, 2)

    gcum = jnp.cumsum(gates(g), axis=-1)
    steps = _steps(gcum.shape[2])
    return (wide(q), wide(k), wide(v), gcum, gates(beta)), steps


@jax.custom_vjp
def gated_delta_rule(q, k, v, g, beta):
    """The gated delta rule over whole sequences from a zero state.
    q, k [batch, seq, key_heads, dk] (already L2-normalised and scaled, as
    the model wants them), v [batch, seq, value_heads, dv], g (log decay,
    <= 0), beta [batch, seq, value_heads]. Returns [batch, seq,
    value_heads, dv] in v's dtype."""
    return _forward(q, k, v, g, beta, False)[0]


def _forward(q, k, v, g, beta, save: bool):
    if not _dispatch("fwd", q, k, v):
        return gated_delta_scan(q, k, v, g, beta), None
    operands, steps = _kernel_operands(q, k, v, g, beta)
    out, states = _gdn_forward(*operands, chunk=CHUNK, steps=steps,
                               save=save, interpret=_interpret())
    return out[:, :v.shape[1]].reshape(v.shape).astype(v.dtype), states


def _vjp_fwd(q, k, v, g, beta):
    out, states = _forward(q, k, v, g, beta, True)
    # What only the kernel can make, by name: kept both, a surrounding
    # checkpoint's backward has no forward call to repeat (module
    # docstring, "Under a remat"). The scan path has no states.
    out = checkpoint_name(out, "gdn_out")
    if states is not None:
        states = checkpoint_name(states, "gdn_states")
    # The operands `gdn_prep` made, by name too, on copies that ONLY the
    # backward reads: jax rounds every kept array the forward also reads
    # through an identity (`reduce_precision`, against XLA's excess
    # precision inside fusions), and between two kernels, which round where
    # they write, that identity is a pass over the array through HBM.
    q, k, v = (checkpoint_name(t, "gdn_qkv") for t in (q, k, v))
    return out, (q, k, v, g, beta, states)


def _vjp_bwd(residuals, do):
    q, k, v, g, beta, states = residuals
    if not _dispatch("bwd", q, k, v):
        _, vjp = jax.vjp(gated_delta_scan, q, k, v, g, beta)
        return vjp(do)
    b, s, hv, _ = v.shape
    hk = k.shape[2]
    operands, steps = _kernel_operands(q, k, v, g, beta)
    pad = operands[0].shape[1] - s
    do_w = jnp.pad(do.reshape(b, s, -1).astype(jnp.bfloat16),
                   ((0, 0), (0, pad), (0, 0)))
    dq, dk, dv, dgcum, dbeta = _gdn_backward(
        *operands, states, do_w, chunk=CHUNK, steps=steps,
        interpret=_interpret())

    def keys(t, like):
        t = t[:, :s].reshape(b, s, hk, hv // hk, -1).astype(jnp.float32)
        return t.sum(axis=3).astype(like.dtype)

    def gates(t, like):
        t = t.transpose(0, 2, 3, 1).reshape(b, -1, hv)[:, :s]
        return t.astype(like.dtype)

    # gcum = cumsum(g) within the chunk, so dg_t = sum over j >= t of dG_j
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgcum, -1), axis=-1), -1)
    return (keys(dq, q), keys(dk, k),
            dv[:, :s].reshape(v.shape).astype(v.dtype),
            gates(dg, g), gates(dbeta, beta))


gated_delta_rule.defvjp(_vjp_fwd, _vjp_bwd)


def _dispatch_beside(pass_: str, x, head_dim: int, widths,
                     taps: int = 1) -> int:
    """The rows of a block when the elementwise kernels take this call on
    x [batch, seq, columns], else 0. Records the decision beside the
    recurrence's (`gated_delta_status()`)."""
    b, s, columns = x.shape
    rows = min(_ROWS, s)
    reason = _off_platform()
    if reason:
        pass
    elif head_dim != 128:
        reason = "head_dim is not the lane width (128)"
    elif any(w % 128 for w in widths):
        reason = "heads do not fill whole 128-lane column blocks"
    elif s % rows or rows % _HALO:
        reason = (f"sequence is not whole blocks of {_ROWS} rows (or one "
                  f"block of whole {_HALO}-row tiles)")
    elif taps - 1 > _EDGE:
        reason = f"convolution reaches over more than {_EDGE} positions"
    key = (pass_, "xla" if reason else "pallas", reason, (b, s, columns),
           jnp.dtype(x.dtype).name, None if reason else rows, None, None)
    with _CALLS_LOCK:
        _CALLS[key] += 1
    return 0 if reason else rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def gdn_prep(qkvz, conv_w, head_dim: int):
    """What stands between a Gated DeltaNet layer's projection and its
    recurrence, fused: of qkvz [batch, seq, 2 key_w + 2 val_w] (columns
    [q | k | v | z]) the first 2 key_w + val_w columns go through the causal
    depthwise convolution conv_w [channels, width] and SiLU in f32; q and
    k are L2-normalised per head of `head_dim` (q also times head_dim^-0.5).
    Returns (q, k [batch, seq, key_w], v [batch, seq, val_w], z_in) in
    qkvz's dtype: the layout the recurrence kernels read. `z_in` is the
    projection itself, for `gdn_gate` and nothing else: only its z columns
    may be read (the cotangent of its other columns is dropped), and in
    exchange d z arrives here inside the buffer this op's backward
    completes, instead of as a second [batch, seq, columns] array to add."""
    return (*_prep(qkvz, conv_w, head_dim), qkvz)


def _prep(qkvz, conv_w, head_dim: int):
    rows = _dispatch_beside("prep_fwd", qkvz, head_dim,
                            _widths(qkvz, conv_w), conv_w.shape[1])
    if not rows:
        return _prep_chain(qkvz, conv_w, head_dim)
    return _gdn_prep_forward(qkvz, conv_w, head_dim=head_dim, rows=rows,
                             interpret=_interpret())


def _prep_vjp_fwd(qkvz, conv_w, head_dim: int):
    # The one large residual, by name, and the forward reads the NAMED
    # array: kept, a surrounding checkpoint's backward makes neither the
    # projection nor (with `gdn_qkv`) this op's outputs again. Its
    # producer is XLA's product, which takes jax's identity into its own
    # fusion (`_vjp_fwd` has the other case).
    qkvz = checkpoint_name(qkvz, "gdn_in")
    outs = _prep(qkvz, conv_w, head_dim)
    return (*outs, qkvz), (qkvz, conv_w)


def _prep_vjp_bwd(head_dim: int, residuals, cotangents):
    qkvz, conv_w = residuals
    dq, dk, dv, d_in = cotangents
    rows = _dispatch_beside("prep_bwd", qkvz, head_dim,
                            _widths(qkvz, conv_w), conv_w.shape[1])
    if rows:
        return _gdn_prep_backward(qkvz, conv_w, dq, dk, dv, d_in,
                                  head_dim=head_dim, rows=rows,
                                  interpret=_interpret())
    _, vjp = jax.vjp(lambda x, w: _prep_chain(x, w, head_dim), qkvz, conv_w)
    dx, dw = vjp((dq, dk, dv))
    channels = conv_w.shape[0]
    return jnp.concatenate([dx[..., :channels], d_in[..., channels:]],
                           axis=-1), dw


gdn_prep.defvjp(_prep_vjp_fwd, _prep_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gdn_gate(o, z_in, norm_w, eps: float):
    """The gated norm after the recurrence, fused: per head of
    norm_w.shape[0] dims, norm_w * o * rsqrt(mean(o^2) + eps) * silu(z) in
    f32, z the LAST o.shape[-1] columns of z_in (`gdn_prep`'s fourth
    output), read in place. o [batch, seq, val_w]; returns the same shape
    and dtype."""
    return _gate(o, z_in, norm_w, eps)


def _gate(o, z_in, norm_w, eps: float):
    rows = _dispatch_beside("gate_fwd", o, norm_w.shape[0],
                            (o.shape[-1], z_in.shape[-1]))
    if not rows:
        return _gate_chain(o, z_in, norm_w, eps)
    return _gdn_gate_forward(o, z_in, norm_w, eps=eps, rows=rows,
                             interpret=_interpret())


def _gate_vjp_fwd(o, z_in, norm_w, eps: float):
    out = checkpoint_name(_gate(o, z_in, norm_w, eps), "gdn_gated")
    # o comes out of a kernel and goes into one: its name on a copy that
    # only the backward reads (`_vjp_fwd`)
    return out, (checkpoint_name(o, "gdn_out"), z_in, norm_w)


def _gate_vjp_bwd(eps: float, residuals, dg):
    o, z_in, norm_w = residuals
    rows = _dispatch_beside("gate_bwd", o, norm_w.shape[0],
                            (o.shape[-1], z_in.shape[-1]))
    if rows:
        return _gdn_gate_backward(o, z_in, norm_w, dg, eps=eps, rows=rows,
                                  interpret=_interpret())
    _, vjp = jax.vjp(lambda *a: _gate_chain(*a, eps), o, z_in, norm_w)
    return vjp(dg)


gdn_gate.defvjp(_gate_vjp_fwd, _gate_vjp_bwd)
