"""Learned sparse attention over a paged latent cache
(`ray_tpu/ops/sparse_latent_attention.py`): the indexer's kernel in the
interpreter against its `jax.numpy` definition, the selection by counting
against `jax.lax.top_k` (ties, rows with fewer candidates than k), the
gathered attention against the dense masked definition and against plain
latent attention where nothing is left out; and `latent_attention`'s lower
bound (a window) on both of its tiles."""

import numpy as np
import pytest

BS, D, HEADS_I = 16, 128, 8
LATENT, ROPE, WIDTH, HEADS = 128, 32, 256, 4


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _case(b, s, lens, seed=0, blocks=12):
    """Shuffled tables; row i's queries are the last s of its lens[i]
    positions (0: an idle row)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    nb = 1 + b * blocks
    perm = rng.permutation(np.arange(1, nb)).reshape(b, blocks)
    bt = np.where(np.arange(blocks)[None, :] * BS < np.asarray(lens)[:, None],
                  perm, 0).astype(np.int32)
    pos = np.stack([max(n - s, 0) + np.arange(s) for n in lens])
    live = np.stack([np.arange(s) < min(s, n) for n in lens])
    f = jnp.float32
    return {"bt": jnp.asarray(bt), "pos": jnp.asarray(pos, jnp.int32),
            "live": jnp.asarray(live),
            "keys": jnp.asarray(rng.standard_normal((nb, BS, D)), f),
            "arena": jnp.asarray(rng.standard_normal((nb, BS, WIDTH)),
                                 f).at[..., LATENT + ROPE:].set(0.0),
            "q_idx": jnp.asarray(rng.standard_normal((b, s, HEADS_I, D)), f),
            "w": jnp.asarray(rng.standard_normal((b, s, HEADS_I)), f),
            "q": jnp.asarray(rng.standard_normal(
                (b, s, HEADS, LATENT + ROPE)) * 0.3, f)}


@pytest.mark.parametrize("s,lens", [(1, [150, 33, 0]), (20, [150, 40, 20]),
                                    (16, [16, 192, 0])])
def test_index_kernel_matches_its_definition(interpret, s, lens):
    from ray_tpu.ops import sparse_latent_attention as sp

    c = _case(len(lens), s, lens)
    got = np.asarray(sp.index_scores(c["q_idx"], c["w"], c["keys"], c["bt"],
                                     c["pos"], c["live"]))
    want = np.asarray(sp.index_scores_reference(
        c["q_idx"], c["w"], c["keys"], c["bt"], c["pos"], c["live"]))
    seen = np.isfinite(want)
    assert (np.isfinite(got) == seen).all()
    np.testing.assert_allclose(got[seen], want[seen], rtol=2e-5, atol=2e-5)
    assert [r["path"] for r in sp.sparse_status()
            if r["shape"][1] == s][-1] == "pallas"


def test_off_the_kernel_the_definition_answers_and_says_why(monkeypatch):
    from ray_tpu.ops import attention, sparse_latent_attention as sp

    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    attention.reset_pallas_status()
    c = _case(2, 1, [40, 7])
    sp.index_scores(c["q_idx"], c["w"], c["keys"], c["bt"], c["pos"])
    (rec,) = sp.sparse_status()
    assert rec["path"] == "reference" and "platform" in rec["reason"]


@pytest.mark.parametrize("k", [1, 16, 64, 700, 900])
def test_selection_by_counting_is_top_k(k):
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_latent_attention as sp

    rng = np.random.default_rng(k)
    scores = rng.standard_normal((3, 5, 700)).astype(np.float32)
    scores[0, 0, :] = 1.0                    # every entry tied
    scores[0, 1, ::3] = 0.5                  # ties across the k-th
    scores[1, 1, 100:] = -np.inf             # fewer candidates than k
    scores[2, 2, 10:] = -np.inf
    scores[2, 3, :] = -np.inf                # none at all
    scores[1, 0] = np.round(scores[1, 0], 1)   # many ties everywhere
    got, n = sp.select_topk(jnp.asarray(scores), k)
    want, m = sp.select_topk_reference(jnp.asarray(scores), k)
    np.testing.assert_array_equal(np.asarray(n), np.asarray(m))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(n[2, 3]) == 0 and int(n[0, 0]) == min(k, 700)
    # ties go to the LOWER positions
    np.testing.assert_array_equal(np.asarray(got[0, 0, :min(k, 700)]),
                                  np.arange(min(k, 700)))


@pytest.mark.parametrize("s,lens", [(1, [150, 33, 9]), (12, [150, 40, 12])])
def test_sparse_attention_matches_the_masked_definition(interpret, s, lens):
    from ray_tpu.ops import sparse_latent_attention as sp

    c = _case(len(lens), s, lens, seed=3)
    args = (c["q"], c["q_idx"], c["w"], c["arena"], c["keys"], c["bt"],
            c["pos"], c["live"])
    got, chosen, count = sp.sparse_latent_attention(
        *args, latent=LATENT, scale=0.1, topk=32)
    want, mask = sp.sparse_latent_attention_reference(
        *args, latent=LATENT, scale=0.1, topk=32)
    live = np.asarray(c["live"])
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-4, atol=1e-5)
    # what was chosen is what the definition's mask holds
    np.testing.assert_array_equal(np.asarray(count)[live],
                                  np.asarray(mask).sum(-1)[live])
    assert (np.asarray(count)[live]
            == np.minimum(np.asarray(c["pos"])[live] + 1, 32)).all()


@pytest.mark.parametrize("s", [1, 12])
def test_with_nothing_left_out_it_is_latent_attention(interpret, s):
    from ray_tpu.ops import sparse_latent_attention as sp
    from ray_tpu.ops.latent_attention import latent_attention

    c = _case(3, s, [150, 40, 12], seed=5)
    got, _, count = sp.sparse_latent_attention(
        c["q"], c["q_idx"], c["w"], c["arena"], c["keys"], c["bt"], c["pos"],
        c["live"], latent=LATENT, scale=0.1, topk=256)
    want = latent_attention(c["q"], c["arena"], c["bt"], c["pos"], c["live"],
                            latent=LATENT, scale=0.1)
    live = np.asarray(c["live"])
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-4, atol=1e-5)
    assert (np.asarray(count)[live] == np.asarray(c["pos"])[live] + 1).all()


def test_a_given_selection_is_read_as_given(interpret):
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_latent_attention as sp
    from ray_tpu.ops.latent_attention import latent_attention_reference

    c = _case(2, 1, [90, 50], seed=7)
    chosen = jnp.asarray([[[3, 17, 40, 88]], [[0, 1, 2, 0]]], jnp.int32)
    count = jnp.asarray([[4], [3]], jnp.int32)
    got, _, _ = sp.sparse_latent_attention(
        c["q"], None, None, c["arena"], None, c["bt"], c["pos"], c["live"],
        latent=LATENT, scale=0.1, topk=4, given=(chosen, count))
    # the same four (three) rows laid out as a sequence of their own
    rows = []
    for i, picks in enumerate(([3, 17, 40, 88], [0, 1, 2])):
        flat = np.asarray(c["bt"])[i][np.asarray(picks) // BS] * BS \
            + np.asarray(picks) % BS
        page = np.zeros((BS, WIDTH), np.float32)
        page[:len(picks)] = np.asarray(c["arena"]).reshape(-1, WIDTH)[flat]
        rows.append(page)
    arena = jnp.asarray(np.stack([np.zeros((BS, WIDTH), np.float32)] + rows))
    q = jnp.pad(c["q"], ((0, 0),) * 3 + ((0, WIDTH - LATENT - ROPE),))
    want = latent_attention_reference(
        q, arena, jnp.asarray([[1], [2]], jnp.int32), count - 1,
        latent=LATENT, scale=0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


# ------------------------------------------ latent attention's lower bound


@pytest.mark.parametrize("s,lens,window", [
    (1, [150, 33, 0], 17), (1, [192, 16, 5], 40), (20, [150, 40, 20], 17),
    (24, [190, 100, 24], 33), (20, [150, 40, 20], 1000)])
def test_a_window_bounds_the_walk_from_below(interpret, s, lens, window):
    """Both tiles (a decode tile: s = 1; a prefill tile) against the
    definition, with the pages wholly behind every live query's window
    NaN in the kernel's arena: a read below the bound shows."""
    import jax.numpy as jnp

    from ray_tpu.ops import attention
    from ray_tpu.ops.latent_attention import (latent_attention,
                                              latent_attention_reference)

    c = _case(len(lens), s, lens, seed=11)
    arena = np.asarray(c["arena"]).copy()
    poisoned = arena.copy()
    for i, n in enumerate(lens):
        first = max(n - min(s, n), 0)          # the row's first live query
        for blk in range((max(first - (window - 1), 0)) // BS):
            poisoned[np.asarray(c["bt"])[i, blk]] = np.nan
    attention.reset_pallas_status()
    got = latent_attention(c["q"], jnp.asarray(poisoned), c["bt"], c["pos"],
                           c["live"], latent=LATENT, scale=0.1, window=window)
    q = jnp.pad(c["q"], ((0, 0),) * 3 + ((0, WIDTH - LATENT - ROPE),))
    want = latent_attention_reference(q, jnp.asarray(arena), c["bt"],
                                      c["pos"], latent=LATENT, scale=0.1,
                                      window=window)
    live = np.asarray(c["live"])
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-4, atol=1e-5)
    assert {r["path"] for r in attention.pallas_status()} == {"pallas"}


def test_the_windowed_walk_copies_what_the_window_needs():
    """`tile_walk` with a window: a decode tile at position p starts at the
    page that holds p - (window - 1) and counts its chunks from there."""
    import jax.numpy as jnp

    from ray_tpu.ops.latent_attention import _BASE, _HI, tile_walk

    pos = jnp.asarray([[1000], [40], [0]], jnp.int32)
    live = jnp.asarray([[True], [True], [False]])
    _, walk, counts = tile_walk(pos, live, heads=4, block_size=16,
                                max_ctx=2048, dtype=jnp.float32, window=100)
    walk = np.asarray(walk)
    assert walk[_HI].tolist() == [1001, 41, 0]
    assert walk[_BASE].tolist() == [(1000 - 99) // 16 * 16, 0, 0]
    assert int(counts["kv_chunks"]) == 2 and int(counts["tiles_walked"]) == 2
    # without one, the walk is the parent's: four rows, from zero
    _, plain, _ = tile_walk(pos, live, heads=4, block_size=16, max_ctx=2048,
                            dtype=jnp.float32)
    assert plain.shape[0] == 4
