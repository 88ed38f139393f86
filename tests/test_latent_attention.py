"""Latent paged attention (`ray_tpu/ops/latent_attention.py`): the Pallas
kernel, run in the interpreter on the CPU (RAY_TPU_PALLAS_INTERPRET=1),
against its `jax.numpy` definition; the dispatch rule's records; and the
absorbed form against the expanded one.

The kernel must read a row's pages only below its live length: every arena
slot that is not live for some row holds NaN in the kernel's arena and zero
in the definition's, so a read past the length shows as a NaN."""

import numpy as np
import pytest

LATENT, ROPE, WIDTH, HEADS, BS = 128, 32, 256, 8, 16
SCALE = 0.11


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _case(s, lens, max_blocks, dtype, seed=0, share=None, queries=None,
          heads=HEADS):
    """An arena with shuffled physical blocks and trash-padded table
    tails. `lens[i]` is row i's live length AFTER this call (0: an idle
    row); its last `queries[i]` (min(s, len) by default) positions are this
    call's live queries, the chunk's other positions follow them, masked.
    `share` = (i, j): row j's first blocks ARE row i's (a shared prefix)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    b = len(lens)
    nb = 1 + b * max_blocks
    perm = rng.permutation(np.arange(1, nb))
    bt = np.zeros((b, max_blocks), np.int32)
    arena = rng.standard_normal((nb, BS, WIDTH)).astype(np.float32)
    arena[..., LATENT + ROPE:] = 0.0
    live = np.zeros((nb, BS), bool)
    pos = np.zeros((b, s), np.int32)
    wmask = np.zeros((b, s), bool)
    off = 0
    for i, n in enumerate(lens):
        if not n:
            continue
        blocks = perm[off:off + -(-n // BS)]
        off += len(blocks)
        if share and share[1] == i:
            common = min(len(blocks), lens[share[0]] // BS)
            blocks = np.concatenate([bt[share[0], :common], blocks[common:]])
        bt[i, :len(blocks)] = blocks
        p = np.arange(n)
        live[blocks[p // BS], p % BS] = True
        q_n = min(s, n) if queries is None else queries[i]
        pos[i] = n - q_n + np.arange(s)
        wmask[i, :q_n] = True
    q = rng.standard_normal((b, s, heads, LATENT + ROPE)).astype(np.float32)
    mask = live[:, :, None]
    return (jnp.asarray(q, dtype),
            jnp.asarray(np.where(mask, arena, np.nan), dtype),
            jnp.asarray(np.where(mask, arena, 0.0), dtype),
            jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(wmask))


# Ragged lengths in one batch: 1, one ending on a block edge, one in the
# middle of a block, one filling the whole table, an idle row.
MAX_BLOCKS = 70           # 1,120 positions: more than one chunk at any s
LENS = (1, 2 * BS, 5 * BS + 3, MAX_BLOCKS * BS, 0, 37)


def _both(q, nan_arena, arena, bt, pos, wmask):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.latent_attention import (latent_attention,
                                              latent_attention_reference)

    out = jax.jit(lambda *a: latent_attention(
        *a, latent=LATENT, scale=SCALE))(q, nan_arena, bt, pos, wmask)
    width = arena.shape[-1]
    ref = latent_attention_reference(
        jnp.pad(q, ((0, 0),) * 3 + ((0, width - q.shape[-1]),)), arena, bt,
        pos, latent=LATENT, scale=SCALE)
    return np.asarray(out, np.float32), np.asarray(ref, np.float32), \
        np.asarray(wmask)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("s", [1, 5, 160])
def test_kernel_matches_its_definition(interpret, s, dtype, tol):
    """Ragged lengths, a masked (idle) row, padded chunk positions; at s =
    160 a row's 1,280 query rows are two grid steps."""
    import jax.numpy as jnp

    from ray_tpu.ops.latent_attention import (latent_attention_status,
                                              PASSES)

    out, ref, wmask = _both(*_case(s, LENS, MAX_BLOCKS, jnp.dtype(dtype),
                                   seed=s))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[wmask], ref[wmask], atol=tol, rtol=tol)
    assert (out[4] == 0).all()              # the idle row read nothing
    took = [r for r in latent_attention_status()
            if r["shape"][:2] == [len(LENS), s] and r["dtype"] == dtype]
    assert took and all(r["path"] == "pallas" for r in took)
    assert {r["pass"] for r in took} == {PASSES[0] if s == 1 else PASSES[1]}


def _walk(case, heads=HEADS):
    """The tile rule's `walk` array for a case, as numpy."""
    from ray_tpu.ops.latent_attention import tile_walk

    q, _, arena, bt, pos, wmask = case
    return np.asarray(tile_walk(
        pos, wmask, heads=heads, block_size=BS,
        max_ctx=bt.shape[1] * BS, dtype=q.dtype)[1])


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_a_wholly_masked_tile_walks_nothing(interpret, dtype, tol):
    """A chunk of 160 positions at 32 heads is five tiles of 32 tokens; of
    rows with 1, 32, 33 and 160 live queries behind a prefix, the tiles
    that hold no live query copy nothing (whatever their masked queries'
    positions would reach is NaN, or trash) and write exactly zero; the
    half-masked ones match the definition on their live queries."""
    import jax.numpy as jnp

    live = (1, 32, 33, 160)
    lens = tuple(300 + n for n in live)
    case = _case(160, lens, MAX_BLOCKS, jnp.dtype(dtype), seed=3,
                 queries=live, heads=32)
    walk = _walk(case, heads=32).reshape(4, len(live), 5)
    assert [int((row > 0).sum()) for row in walk[0]] == [1, 1, 2, 5]
    out, ref, wmask = _both(*case)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[wmask], ref[wmask], atol=tol, rtol=tol)
    for i, n in enumerate(live):
        assert (out[i, -(-n // 32) * 32:] == 0).all()


# (live length after the call, live queries): the split loop's edges. At s
# = 1 a chunk is 1,024 tokens (a tile of few rows), at s = 160 it is 512
# and a tile 128 tokens; `hi` and `plain` are what the tile rule must say,
# a (row, tile) at a time.
EDGES = {
    1: dict(
        rows=[(1024, 1), (1025, 1), (1023, 1), (1500, 1), (2048, 1), (0, 0)],
        hi=[1024, 1025, 1023, 1500, 2048, 0],
        # hi on a chunk edge: no masked chunk at all; one past it: the
        # query's own position opens a chunk of one live row
        plain=[1, 1, 0, 1, 2, 0]),
    160: dict(
        rows=[(1024, 160), (1025, 160), (1023, 160), (672, 160), (512, 1),
              (512, 160), (1400, 17), (0, 0)],
        hi=[992, 1024, 993, 1025, 991, 1023, 640, 672, 512, 0, 480, 512,
            1400, 0, 0, 0],
        # (672, 160): its first live position IS a chunk edge; (512, 1): a
        # walk of exactly one chunk, and that one plain
        plain=[1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 2, 0, 0, 0]),
}


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("s", sorted(EDGES))
def test_the_split_loops_edges(interpret, s, dtype, tol):
    """Chunks whole below a tile's first live query run the body with no
    mask, the others today's: the seam between them at every place it can
    fall, against the definition; the arena past each row's length is NaN."""
    import jax.numpy as jnp

    edges = EDGES[s]
    lens, queries = zip(*edges["rows"])
    case = _case(s, lens, 128, jnp.dtype(dtype), seed=11 + s,
                 queries=queries)
    walk = _walk(case)
    assert walk[0].tolist() == edges["hi"]
    assert walk[1].tolist() == edges["plain"]
    out, ref, wmask = _both(*case)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[wmask], ref[wmask], atol=tol, rtol=tol)
    assert (out[-1] == 0).all()


@pytest.mark.parametrize("s", [1, 160])
def test_the_first_copy_is_handed_over_idle_steps(interpret, s):
    """Six slots of which the 1st, 3rd and last are idle: a grid step's
    last chunk starts the first chunk of the NEXT step that walks (idle
    ones read nothing: NaN arena), the first live step starts its own, and
    the last live one starts none."""
    import jax.numpy as jnp

    lens = (0, 40, 0, 1100, 37, 0)
    case = _case(s, lens, MAX_BLOCKS, jnp.float32, seed=5)
    hi, _, before, nxt = _walk(case).reshape(4, len(lens), -1)
    live = np.flatnonzero(hi.reshape(-1))
    steps = hi.size
    assert [int(i) for i in live // hi.shape[1]] == sorted(
        {1, 3, 4} if s == 1 else [1, 3, 3, 4])
    # each step names the live step after it, the last live one nobody
    want = [next((int(j) for j in live if j > g), -1) for g in range(steps)]
    assert nxt.reshape(-1).tolist() == want and want[live[-1]] == -1
    assert before.reshape(-1)[live[0]] == 0
    assert (before.reshape(-1)[live[1:]] > 0).all()
    out, ref, wmask = _both(*case)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[wmask], ref[wmask], atol=2e-5, rtol=2e-5)
    for idle in (0, 2, 5):
        assert (out[idle] == 0).all()


def test_the_wrapper_walks_what_the_rule_says(interpret, monkeypatch):
    """One definition: the kernel's scalars come from `tile_walk`, the
    function a model counts a step's walk with."""
    import jax.numpy as jnp

    from ray_tpu.ops import latent_attention as la

    seen = []

    def spy(positions, write_mask, **kw):
        seen.append((positions.shape, kw["heads"], kw["block_size"]))
        return rule(positions, write_mask, **kw)

    rule = la.tile_walk
    monkeypatch.setattr(la, "tile_walk", spy)
    # a shape no other test traces, so the wrapper is traced here
    case = _case(3, (20, 7), 3, jnp.float32, seed=9)
    out, ref, wmask = _both(*case)
    np.testing.assert_allclose(out[wmask], ref[wmask], atol=2e-5, rtol=2e-5)
    assert seen == [((2, 3), HEADS, BS)]


def test_two_slots_share_pages(interpret):
    """Two rows whose tables name the same physical blocks for a common
    prefix (an adopted document) each read them as their own."""
    import jax.numpy as jnp

    lens = (6 * BS + 5, 6 * BS + 9, 3 * BS)
    case = _case(1, lens, 8, jnp.float32, seed=7, share=(0, 1))
    bt = np.asarray(case[3])
    assert (bt[0, :6] == bt[1, :6]).all() and bt[0, 6] != bt[1, 6]
    out, ref, wmask = _both(*case)
    np.testing.assert_allclose(out[wmask], ref[wmask], atol=2e-5, rtol=2e-5)


def test_off_the_kernel_the_definition_answers_and_says_why():
    import jax.numpy as jnp

    from ray_tpu.ops.attention import reset_pallas_status
    from ray_tpu.ops.latent_attention import latent_attention_status
    from ray_tpu.ops.paged_attention import paged_calls

    reset_pallas_status()
    q, _, arena, bt, pos, wmask = _case(1, (5, 0, 40), 4, jnp.float32)
    # (the definition gathers a row's whole table: no NaN arena for it)
    out, ref, wmask = _both(q, arena, arena, bt, pos, wmask)
    np.testing.assert_array_equal(out[wmask], ref[wmask])
    (rec,) = latent_attention_status()
    assert rec["path"] == "reference" and rec["reason"] == "platform cpu"
    assert paged_calls() == {
        ("paged_latent_decode", "reference: platform cpu"): 1}


def test_absorbed_equals_expanded():
    """The absorbed form over a latent cache against the expanded one
    (every cached token's k_nope and v made from its latent), float32."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.latent_attention import latent_attention

    n, v, t = 16, 24, 3 * BS + 5
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    c = jax.random.normal(keys[0], (t, LATENT))
    k_r = jax.random.normal(keys[1], (t, ROPE))
    q_nope = jax.random.normal(keys[2], (t, HEADS, n))
    q_rope = jax.random.normal(keys[3], (t, HEADS, ROPE))
    w_uk = jax.random.normal(keys[4], (HEADS, n, LATENT)) * 0.1
    w_uv = jax.random.normal(keys[5], (HEADS, LATENT, v)) * 0.1
    with jax.default_matmul_precision("highest"):
        # expanded
        k_nope = jnp.einsum("tl,hnl->thn", c, w_uk)
        val = jnp.einsum("tl,hlv->thv", c, w_uv)
        scores = (jnp.einsum("qhn,khn->hqk", q_nope, k_nope)
                  + jnp.einsum("qhr,kr->hqk", q_rope, k_r)) * SCALE
        causal = jnp.tril(jnp.ones((t, t), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        expanded = jnp.einsum("hqk,khv->qhv", probs, val)
        # absorbed, through the op, over a paged cache of the same rows
        blocks = -(-t // BS)
        rows = jnp.zeros((1 + blocks, BS, WIDTH)).reshape(-1, WIDTH)
        rows = rows.at[BS:BS + t, :LATENT + ROPE].set(
            jnp.concatenate([c, k_r], axis=-1))
        q = jnp.concatenate(
            [jnp.einsum("qhn,hnl->qhl", q_nope, w_uk), q_rope], axis=-1)
        o_lat = latent_attention(
            q[None], rows.reshape(1 + blocks, BS, WIDTH),
            1 + jnp.arange(blocks, dtype=jnp.int32)[None],
            jnp.arange(t)[None], latent=LATENT, scale=SCALE)[0]
        absorbed = jnp.einsum("qhl,hlv->qhv", o_lat, w_uv)
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5, rtol=2e-5)
