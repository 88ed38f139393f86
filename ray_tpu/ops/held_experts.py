"""A dropless expert layer's local part: the chip is TOLD which experts it
holds, the router's choice is over all of them, and this computes what the
held experts add for the tokens routed to them. What the absent experts
would have added is not computed, approximated or stood in for.

    y_t = sum over the token's top-k choices e that are held of
          gate_{t,e} * down_e( silu(gate_proj_e x_t) * up_proj_e x_t )

No token is dropped and no capacity is set: every (token, choice) pair
whose expert is held gets a row. Shapes are static all the same, so the
rows are laid out in blocks of `block` assignments (3 x what even routing
gives, by default): the first block always runs; the blocks after it run
under one `lax.cond` that is false unless the held experts drew more than a
block's worth, and then run exactly as many as are needed (slow, and
exact). The worst case, every choice of every token held, is `ceil(T *
min(k, held) / block)` blocks.

Inside a block the assignments are sorted by expert, each expert's rows
padded to whole row tiles (`grouped_matmul`'s layout; 128 rows trained),
the tokens' activations gathered into the rows, two grouped products run
(gate|up fused, then down), and the rows summed back to their tokens. Both
directions of that plumbing are GATHERS, forward and backward (XLA's
scatter on TPU is a serial loop over rows): rows <- tokens is `x[token of
row]`, tokens <- rows is a sum over the token's k slots of `y[row of
slot]`, and each is the other's transpose, so each one's backward is the
other. The slots of a token are sorted held-first; the first few
(`slots_always`: seven of ten at 32 held of 512) are gathered whatever they
hold and the rest only if some token has a row there, which is rare, so a
step's time does not move with the luck of its batch.

Under a remat: what is dear to make here carries a name
(`jax.ad_checkpoint.checkpoint_name`) that a caller's `jax.checkpoint` can
keep by policy (`save_only_these_names`): `moe_plan`, the router's logits
and its top-k, the two sorts' results and the first block's six layout
arrays (integers but for the logits and the top-k's values: ~19 MB a layer
at 8,192 tokens over 512 experts); `moe_h` and `moe_y`, the first block's
two grouped products. Kept all three, the backward runs no sort, no
`searchsorted` and no forward `moe_gmm` again; it still makes the gathers,
the activation and the softmax again, which are cheap. The later blocks
carry no names: a policy reaches through their `cond`, `scan` and inner
checkpoint and would keep every trip's rows. A name is the identity
anywhere else.

Two router rules, both over ALL experts in float32 at `Precision.HIGHEST`:
`route` (softmax, top-k of the probabilities, gates renormalised to one) and
`route_sigmoid` (sigmoid scores, top-k of `scores + bias` where the bias is
a selection bias that no gate sees, gates = the chosen scores renormalised,
times a scaling factor).

Two entries over one body, because their layouts' needs conflict and
nothing in the operands tells a differentiated call from a plain one:
`held_expert_mlp` is the TRAINED path (128-row tiles, every held expert
owning at least one so that `moe_gmm_drhs` writes each expert's gradient
block exactly once, the remat names above, both gradients; the right tile
where an expert draws 160 rows). `held_expert_forward` is a SERVED step's:
never differentiated (`jax.grad` through it raises), no names, and a layout
of the rows the call HAS: the row tile is `serve_tile`'s by the call's
static shapes (16 rows while even routing gives an expert less than that),
an expert that drew no row owns NO tile, so `moe_gmm`'s weight index map
never reaches it, and a block's buffer is `block + min(held, assignments) *
tile` rows. At 32 rows x 6 choices over 128 held experts that is 2,304 rows
and the ~64 experts that drew a row, where the trained layout has 16,640
and reads all 128.

`held_experts_status()` lists the traced calls (path, held range, tokens,
top-k, block, blocks, tile, rows, forward_only); the loads are outputs of
the call (`counts`), so that nothing syncs to read them: a train step
returns them beside the loss; a
SERVED step (`models/deepseek_v3.py`) adds them into counters it keeps on
the device in the cache pytree it is donated, and the host reads the sums
when `stats()` is asked, not a step. A served step also routes its idle
rows (batch and chunk padding) to expert number `experts`, which no chip
holds: they get no row and count for nothing.
"""

from __future__ import annotations

import collections
import functools
import math
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.grouped_matmul import (TILE, grouped_matmul,
                                        grouped_matmul_forward,
                                        grouped_matmul_path)

_CALLS: collections.Counter = collections.Counter()
_CALLS_LOCK = threading.Lock()


def held_experts_status() -> list:
    """One entry per distinct traced call of `held_expert_mlp` or
    `held_expert_forward`: `path` ("pallas", or "interpret" where the
    grouped products fell to the interpreter unasked), `held` [first,
    count], `experts` (the router's width), `tokens`, `top_k`, `block`
    (assignments a block), `blocks` (the worst case's count; the first
    always runs), `tile` (rows a row tile: 128 trained, `serve_tile`'s
    served), `rows` (a block's static buffer), `forward_only`, and the
    number of traced calls."""
    with _CALLS_LOCK:
        items = list(_CALLS.items())
    return [{"path": path, "held": list(held), "experts": experts,
             "tokens": tokens, "top_k": k, "block": block, "blocks": blocks,
             "tile": tile, "rows": rows, "forward_only": forward_only,
             "calls": n}
            for (path, held, experts, tokens, k, block, blocks, tile, rows,
                 forward_only), n in items]


def reset_held_experts_status() -> None:
    with _CALLS_LOCK:
        _CALLS.clear()


def default_block(tokens: int, top_k: int, held: int, experts: int) -> int:
    """3 x the assignments even routing sends to the held experts, in
    whole tiles, and never more than the worst case. (1.5 x was met by two
    seeds of six within a hundred steps from a random router, and the
    later blocks cost them 1.4% of a step; with the learning rate warmed
    up, 1.5 x and gathers counted by the batch spread `train_tok_s_chip`
    0.635% over six seeds, this and `slots_always` 0.046%: PERF.md section
    6, PR 34.)"""
    even = tokens * top_k * held / experts
    worst = tokens * min(top_k, held)
    return min(-(-int(3 * even) // TILE) * TILE, -(-worst // TILE) * TILE)


def serve_tile(tokens: int, top_k: int, experts: int) -> int:
    """Rows a row tile of a FORWARD-ONLY call: bf16's smallest (16) while
    even routing gives an expert less than one such tile, the trained
    path's 128 from there on. A served step's experts draw a row or two
    each (32 rows x 6 over 128: 1.5; a 256-token chunk: 12), and a tile is
    what an expert with one row pays for. Measured on the layer alone at
    those two shapes (PERF.md section 6, PR 51): 16 / 32 / 64 / 128 rows a
    tile take 1.01 / 1.14 / 1.61 / 2.27 ms a decode step's layer and 1.75 /
    1.79 / 2.13 / 2.86 ms a chunk's."""
    return 16 if tokens * top_k < 16 * experts else TILE


def _lookup(table, index):
    """table[index] for a table of a few dozen entries, as a masked sum (a
    gather of scalars is a slow thing to ask of the TPU)."""
    n = table.shape[0]
    hit = index[..., None] == jnp.arange(n, dtype=index.dtype)
    return jnp.sum(jnp.where(hit, table, 0), axis=-1)


def slots_always(tokens: int, top_k: int, held: int, experts: int) -> int:
    """How many of a token's sorted slots `_sum_rows` gathers without
    asking: one for an expert every token may choose, and then the least
    j such that, were a token's other held choices Poisson at even
    routing's mean, fewer than one call in twenty would meet a token with
    more than j. A step's time then does not move with the luck of its
    batch (PERF.md section 6, PR 34: asked slot by slot, 3 to 7 were
    gathered and `train_tok_s_chip` spread 0.6%)."""
    mean = top_k * held / experts
    term = tail = math.exp(-mean)           # P(X = 0), then P(X <= j)
    for j in range(1, top_k + 1):
        if tokens * (1.0 - tail) < 5e-2:
            return min(j, top_k)
        term *= mean / j
        tail += term
    return top_k


def _sum_rows(rows, slots, always: int):
    """out[t] = sum_j rows_ext[slots[t, j]] in f32, where index len(rows)
    is a row of zeros and each token's slots are sorted, so that the real
    rows come first. The first `always` slots are gathered whatever they
    hold; the rest together, and only if some token has a real row there
    (rare by `slots_always`, exact either way)."""
    n, width = rows.shape
    ext = jnp.concatenate([rows, jnp.zeros((1, width), rows.dtype)])

    def gathered(cols, out):
        for j in range(cols.shape[1]):
            out = out + ext[cols[:, j]].astype(jnp.float32)
        return out

    head, tail = slots[:, :always], slots[:, always:]
    out = gathered(head[:, 1:], ext[head[:, 0]].astype(jnp.float32))
    if tail.shape[1]:
        out = jax.lax.cond(jnp.any(tail < n), gathered,
                           lambda cols, out: out, tail, out)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_from_tokens(x, row_token, slots, always):
    """rows[r] = x[row_token[r]] (zeros where row_token is len(x))."""
    ext = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    return ext[row_token]


_rows_from_tokens.defvjp(
    lambda x, r, s, always: (_rows_from_tokens(x, r, s, always), (s,)),
    lambda always, res, d: (_sum_rows(d, res[0], always).astype(d.dtype),
                            None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _tokens_from_rows(y, gates, row_slot, slot_row, slots, always):
    """out[t] = sum over the token's held choices of gate * y[row]: y [R,
    D], gates [T, k] f32, row_slot [R] the flat (token * k + choice) of a
    row or T * k, slot_row [T, k] the row of each choice or R, slots the
    same with each token's sorted."""
    del slot_row
    return _combine(y, gates, row_slot, slots, always)[0]


def _combine(y, gates, row_slot, slots, always):
    flat = jnp.concatenate([gates.reshape(-1), jnp.zeros((1,), gates.dtype)])
    gate_row = flat[row_slot]
    weighted = (y.astype(jnp.float32) * gate_row[:, None]).astype(y.dtype)
    return _sum_rows(weighted, slots, always), gate_row


def _tokens_fwd(y, gates, row_slot, slot_row, slots, always):
    out, gate_row = _combine(y, gates, row_slot, slots, always)
    return out, (y, gate_row, row_slot, slot_row)


def _tokens_bwd(always, residuals, d_out):
    y, gate_row, row_slot, slot_row = residuals
    tokens, k = slot_row.shape
    ext = jnp.concatenate([d_out.astype(y.dtype),
                           jnp.zeros((1, d_out.shape[1]), y.dtype)])
    d_rows = ext[jnp.minimum(row_slot // k, tokens)].astype(jnp.float32)
    d_y = (d_rows * gate_row[:, None]).astype(y.dtype)
    d_gate_row = jnp.sum(d_rows * y.astype(jnp.float32), axis=1)
    # back to [T, k]: the gate of choice (t, j) met row slot_row[t, j]
    d_gates = jnp.concatenate([d_gate_row, jnp.zeros((1,))])[slot_row]
    return d_y, d_gates, None, None, None


_tokens_from_rows.defvjp(_tokens_fwd, _tokens_bwd)


def _named(made, name: str):
    """Every array of `made` under `name`, for a surrounding remat's policy
    (module docstring, "Under a remat")."""
    return jax.tree.map(lambda a: checkpoint_name(a, name), made)


def _unnamed(made, name: str):
    """No name: a later block's arrays, and all of a forward-only call's."""
    del name
    return made


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def _top_k(probs, k: int):
    """`lax.top_k` whose tangent rule reads the index under its NAME: jax's
    own rule reads the index it made inside the rule, which no name outside
    reaches, so a remat that kept the named copy would still sort again."""
    return tuple(jax.lax.top_k(probs, k))


@_top_k.defjvp
def _top_k_jvp(k, primals, tangents):
    top, index = _named(tuple(jax.lax.top_k(primals[0], k)), "moe_plan")
    d_top = jnp.take_along_axis(tangents[0], index, axis=-1)
    return (top, index), (d_top, np.zeros(index.shape, jax.dtypes.float0))


def route(x, w_router, top_k: int):
    """(probs [T, E] f32 over ALL experts, gates [T, k] f32 renormalised to
    sum to one, index [T, k] int32). Float32 at `Precision.HIGHEST`: a
    bf16 product here moves which experts a token gets. Named for a
    surrounding remat: the logits (the softmax's own rule reads what IT
    made, so a kept `probs` would save nothing) and both results of the
    top-k (`_top_k`); the softmax and the renormalisation are made again
    from them."""
    logits = _named(jax.lax.dot_general(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32), "moe_plan")
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, index = _top_k(probs, top_k)
    return probs, top_p / jnp.sum(top_p, axis=-1, keepdims=True), index


def route_sigmoid(x, w_router, bias, top_k: int, scaling: float):
    """The second published rule (`scoring_func` sigmoid, `topk_method`
    noaux_tc with one group, `norm_topk_prob`): (scores [T, E] f32 over ALL
    experts, gates [T, k] f32, index [T, k] int32). The selection is the
    top-k of `scores + bias`; the gates are the chosen SCORES, without the
    bias, renormalised (+1e-20) and times `scaling`. Float32 at
    `Precision.HIGHEST` like `route`, whose lowering this leaves alone."""
    logits = jax.lax.dot_general(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    _, index = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(scores, index, axis=-1)
    gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return scores, gates * scaling, index.astype(jnp.int32)


def _block_layout(key, order, position, starts, loads, lo, block: int,
                  held: int, top_k: int, tile: int, rows: int,
                  forward_only: bool):
    """Where block [lo, lo + block) of the sorted assignments puts its
    rows: a buffer of `rows` in tiles of `tile`. Trained, every group owns
    at least one tile (the weight gradient writes each group's block
    exactly once); `forward_only`, a group without a row owns none,
    `tile_group` names only the groups that drew one and the buffer's tail
    repeats the last of them, so that no weight block is fetched for
    nothing. Returns (tile_group, n_used, row_token, row_slot, slot_row,
    slots)."""
    n_slots = key.shape[0]
    tokens = n_slots // top_k
    first = jnp.clip(starts - lo, 0, block)          # in-block start, per e
    mine = jnp.clip(starts + loads - lo, 0, block) - first
    tiles = -(-mine // tile)
    last = held - 1
    if forward_only:                                 # the last that drew
        last = jnp.max(jnp.where(tiles > 0, jnp.arange(held), 0))
    else:
        tiles = jnp.maximum(tiles, 1)                # every group >= 1 tile
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(rows // tile), side="right"),
        last).astype(jnp.int32)
    n_used = tile_end[-1:].astype(jnp.int32)
    # rows -> assignments
    r = jnp.arange(rows)
    e = tile_group[r // tile]
    rank = r - _lookup(tile_start, e) * tile
    real = rank < _lookup(mine, e)
    at = jnp.clip(lo + _lookup(first, e) + rank, 0, n_slots - 1)
    slot = jnp.where(real, order[at], n_slots)
    row_slot = slot.astype(jnp.int32)
    row_token = jnp.minimum(slot // top_k, tokens).astype(jnp.int32)
    # assignments -> rows
    rank = position - lo - _lookup(first, jnp.minimum(key, held - 1))
    inside = (key < held) & (position >= lo) & (position < lo + block)
    row = _lookup(tile_start, jnp.minimum(key, held - 1)) * tile + rank
    slot_row = jnp.where(inside, row, rows).reshape(tokens, top_k).astype(
        jnp.int32)
    return (tile_group, n_used, row_token, row_slot, slot_row,
            jnp.sort(slot_row, axis=-1))


def held_expert_mlp(x, gates, index, w_gate_up, w_down, held, experts: int):
    """The held experts' part of a routed SwiGLU layer, TRAINED: 128-row
    tiles, every held expert owning one (module docstring), the names a
    surrounding remat keeps, both gradients.

    x [T, D] (bf16), gates [T, k] f32 and index [T, k] int32 from `route`
    (over all `experts`), w_gate_up [count, D, 2F] (an expert's gate
    projection beside its up projection), w_down [count, F, D], held =
    (first, count): this chip holds experts first .. first + count - 1.

    Returns (y [T, D] f32, counts): counts = {"load": [count] assignments
    each held expert received, "assigned": their sum, "placed": the rows
    the blocks really computed (== assigned: nothing is dropped; reported,
    not assumed)}."""
    return _held_experts(x, gates, index, w_gate_up, w_down, held, experts,
                         forward_only=False)


def held_expert_forward(x, gates, index, w_gate_up, w_down, held,
                        experts: int, live_tokens: Optional[int] = None):
    """`held_expert_mlp` for a step that is never differentiated (a served
    step): the same arguments, the same sums, and counts gains "tiles", the
    row tiles the products ran. Its layout is the rows the call HAS: the
    row tile is `serve_tile`'s by the call's static shapes, an expert that
    drew no row owns no tile (its weights are never read), and the buffer
    is `block + min(held, assignments) * tile` rows. No remat names; the
    products are `moe_gmm` alone, so `jax.grad` through it raises.

    `live_tokens` is how many of the T rows the caller expects to be real
    tokens where it can say so statically (None: all of them): `serve_tile`
    is asked with it, since an idle row draws no expert and what an expert
    draws is what its tile should fit (a block step of `models/sdar.py`
    holds 256 rows of which its schedule keeps ~160 live). The sums do not
    depend on it."""
    return _held_experts(x, gates, index, w_gate_up, w_down, held, experts,
                         forward_only=True, live_tokens=live_tokens)


def _held_experts(x, gates, index, w_gate_up, w_down, held, experts: int,
                  forward_only: bool, live_tokens: Optional[int] = None):
    first_held, count = held
    if not (0 <= first_held and count >= 1
            and first_held + count <= experts):
        raise ValueError(f"held {held} does not lie inside {experts} experts")
    tokens, top_k = index.shape
    width = w_gate_up.shape[2] // 2
    block = default_block(tokens, top_k, count, experts)
    always = slots_always(tokens, top_k, count, experts)
    blocks = -(-tokens * min(top_k, count) // block)
    # A block's buffer: its assignments and a tile of padding for every
    # expert that can own one there (trained: each of them; forward only:
    # no more than there are assignments).
    if forward_only:
        tile = serve_tile(tokens if live_tokens is None
                          else min(tokens, int(live_tokens)), top_k, experts)
        buffer = block + min(count, tokens * min(top_k, count)) * tile
        product = functools.partial(grouped_matmul_forward, tile=tile)
        named = _unnamed
    else:
        tile, buffer = TILE, block + count * TILE
        product, named = grouped_matmul, _named
    with _CALLS_LOCK:
        _CALLS[(grouped_matmul_path(), (first_held, count), experts, tokens,
                top_k, block, blocks, tile, buffer, forward_only)] += 1

    local = index - first_held
    key = jnp.where((local >= 0) & (local < count), local,
                    count).reshape(-1).astype(jnp.int32)
    n_slots = key.shape[0]
    iota = jnp.arange(n_slots, dtype=jnp.int32)
    _, order = jax.lax.sort((key, iota), num_keys=1)     # sorted -> slot
    _, position = jax.lax.sort((order, iota), num_keys=1)  # slot -> sorted
    order, position = named((order, position), "moe_plan")
    loads = jnp.sum(key[:, None] == jnp.arange(count, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(loads) - loads
    assigned = jnp.sum(loads)

    def one_block(lo, name=_unnamed):
        tile_group, n_used, row_token, row_slot, slot_row, slots = name(
            _block_layout(key, order, position, starts, loads, lo, block,
                          count, top_k, tile, buffer, forward_only),
            "moe_plan")
        rows = _rows_from_tokens(x, row_token, slots, always)
        h = name(product(rows, w_gate_up, tile_group, n_used), "moe_h")
        act = (jax.nn.silu(h[:, :width].astype(jnp.float32))
               * h[:, width:].astype(jnp.float32)).astype(x.dtype)
        y = name(product(act, w_down, tile_group, n_used), "moe_y")
        tally = {"placed": jnp.sum(row_slot < n_slots, dtype=jnp.int32)}
        if forward_only:
            tally["tiles"] = n_used[0]
        return _tokens_from_rows(y, gates, row_slot, slot_row, slots,
                                 always), tally

    # The first block's layout and products go by name; the later blocks'
    # do not (a policy reaches through the `cond`, the `scan` and its
    # checkpoint, and would keep every trip's).
    out, tally = one_block(jnp.int32(0), named)
    if blocks > 1:
        zero = (jnp.zeros_like(out), {k: jnp.int32(0) for k in tally})

        def later_blocks():
            @jax.checkpoint
            def step(acc, b):
                lo = b * block
                more = jax.lax.cond(lo < assigned, one_block,
                                    lambda lo: zero, lo)
                return jax.tree.map(jnp.add, acc, more), None

            return jax.lax.scan(step, zero,
                                jnp.arange(1, blocks, dtype=jnp.int32))[0]

        more = jax.lax.cond(assigned > block, later_blocks, lambda: zero)
        out, tally = jax.tree.map(jnp.add, (out, tally), more)
    return out, {"load": loads, "assigned": assigned, **tally}


def load_balance_loss(probs, index, experts: int):
    """The family's auxiliary loss over ALL experts, as its published
    implementation sums it: experts * sum_e (assignments e received /
    tokens) * (mean router probability of e). `top_k` at perfectly even
    routing."""
    tokens = index.shape[0]
    hits = jnp.sum(index[..., None] == jnp.arange(experts, dtype=index.dtype),
                   axis=(0, 1), dtype=jnp.float32)
    return experts * jnp.sum(hits / tokens * jnp.mean(probs, axis=0))
