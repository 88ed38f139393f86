"""Latent paged attention (`ray_tpu/ops/latent_attention.py`): the Pallas
kernel, run in the interpreter on the CPU (RAY_TPU_PALLAS_INTERPRET=1),
against its `jax.numpy` definition; the dispatch rule's records; and the
absorbed form against the expanded one.

The kernel must read a row's pages only below its live length: every arena
slot that is not live for some row holds NaN in the kernel's arena and zero
in the definition's, so a read past the length shows as a NaN."""

import numpy as np
import pytest

LATENT, ROPE, WIDTH, HEADS, BS = 128, 32, 256, 4, 16
SCALE = 0.11


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _case(s, lens, max_blocks, dtype, seed=0, share=None):
    """An arena with shuffled physical blocks and trash-padded table
    tails. `lens[i]` is row i's live length AFTER this call (0: an idle
    row); its last min(s, len) positions are this call's queries. `share`
    = (i, j): row j's first blocks ARE row i's (a shared prefix)."""
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
        q_n = min(s, n)
        pos[i] = n - q_n + np.arange(s)
        wmask[i, :q_n] = True
    q = rng.standard_normal((b, s, HEADS, LATENT + ROPE)).astype(np.float32)
    mask = live[:, :, None]
    return (jnp.asarray(q, dtype),
            jnp.asarray(np.where(mask, arena, np.nan), dtype),
            jnp.asarray(np.where(mask, arena, 0.0), dtype),
            jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(wmask))


# Ragged lengths in one batch: 1, one ending on a block edge, one in the
# middle of a block, one filling the whole table, an idle row.
MAX_BLOCKS = 34           # 544 positions: more than one chunk at any s
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
    160 a row's 640 query rows are two grid steps."""
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
