"""Kernel numerics: flash attention (fwd+bwd) and ring attention vs XLA
reference, ring over 8 virtual CPU devices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import flash_attention, mha_reference


def _qkv(rng, b=2, h=4, s=128, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, h, s, d), dtype)
    k = jax.random.normal(kk, (b, h, s, d), dtype)
    v = jax.random.normal(kv, (b, h, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference_forward(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    out = flash_attention(q, k, v, causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_grads_match_reference():
    q, k, v = _qkv(jax.random.PRNGKey(1), s=64)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_cross_attention_seq_mismatch_uses_reference_convention():
    # seq_q != seq_k must agree with mha_reference (pallas path is gated off).
    rng = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (1, 2, 32, 64))
    k = jax.random.normal(kk, (1, 2, 128, 64))
    v = jax.random.normal(kv, (1, 2, 128, 64))
    out = flash_attention(q, k, v, True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_dispatch_rule_is_visible_and_never_a_fallback(monkeypatch):
    """The path of every traced call is in pallas_status(); on platform
    tpu a kernel that fails to lower fails the caller, and the interpret
    switch is refused."""
    from ray_tpu.ops import attention

    q, k, v = _qkv(jax.random.PRNGKey(3), b=1, h=1, s=128)
    attention.reset_pallas_status()
    flash_attention(q, k, v, True)
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    flash_attention(q[:, :, :32], k, v, True)   # gated: seq_q != seq_k
    flash_attention(q, k, v, True, None, 128, 128)
    seen = {(e["path"], e["reason"], tuple(e["shape"]))
            for e in attention.pallas_status()}
    assert seen == {("reference", "platform cpu", (1, 1, 128, 64)),
                    ("reference", "seq_q != seq_k", (1, 1, 32, 64)),
                    ("pallas", "", (1, 1, 128, 64))}

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU test switch"):
        flash_attention(q, k, v, True)
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")

    def refused(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(attention, "_flash_forward", refused)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        flash_attention(q, k, v, True)


def test_pallas_kernels_interpret_mode(monkeypatch):
    """Run the actual Pallas fwd+bwd kernels (interpreter) vs XLA."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v = _qkv(jax.random.PRNGKey(7), b=1, h=2, s=256, d=64)
    for causal in (True, False):
        out = flash_attention(q, k, v, causal, None, 128, 128)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal, None,
                                           128, 128) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

        g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)


def _flash_and_reference(q, k, v, causal, *blocks):
    """(out, dq, dk, dv) of the kernels and of mha_reference on the same
    inputs, as float32 arrays."""
    def run(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)
        return [np.asarray(x, np.float32) for x in
                (fn(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))]

    return (run(lambda q, k, v: flash_attention(q, k, v, causal, None,
                                                *blocks)),
            run(lambda q, k, v: mha_reference(q, k, v, causal=causal)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_parity_at_the_train_cells_schedule(monkeypatch, causal, dtype):
    """Forward and the three gradients at the schedule the train cells run
    (seq 1024, d 64, auto blocks and tiles), against mha_reference on the
    same dtype."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v = _qkv(jax.random.PRNGKey(11), b=1, h=1, s=1024, d=64,
                   dtype=jnp.dtype(dtype))
    got, want = _flash_and_reference(q, k, v, causal)
    if dtype == "float32":
        tols = [(2e-5, 2e-5)] + [(5e-4, 5e-4)] * 3
    else:
        # bf16 keeps 8 bits: a rounding is 2^-8 relative. Kernel and
        # reference each round their result once and differ in where they
        # round inside (the reference rounds the probabilities to bf16
        # before P x V; the kernels keep f32 statistics), so two results
        # that are both right differ by a few roundings of the largest
        # values they sum: 4 x 2^-8 of the result's largest magnitude.
        tols = [(4 * 2.0 ** -8 * float(np.abs(w).max()), 0.0) for w in want]
    for a, b, (atol, rtol) in zip(got, want, tols):
        np.testing.assert_allclose(a, b, atol=atol, rtol=rtol)


def test_flash_causal_over_several_grid_blocks_and_tiles(monkeypatch):
    """seq 2048 in 512 x 1024 grid blocks of 256 x 128 tiles: blocks above
    the diagonal run nothing, blocks below it run whole, and each of the
    two ways the diagonal crosses a block has its own stripes."""
    from ray_tpu.ops import attention

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v = _qkv(jax.random.PRNGKey(12), b=1, h=1, s=2048, d=64)
    attention.reset_pallas_status()
    got, want = _flash_and_reference(q, k, v, True, 512, 1024)
    for a, b, tol in zip(got, want, [2e-5, 5e-4, 5e-4, 5e-4]):
        np.testing.assert_allclose(a, b, atol=tol, rtol=tol)
    assert {(e["pass"], e["block_q"], e["block_k"], e["tiles"],
             e["tiles_live"]) for e in attention.pallas_status()} == {
        ("fwd", 512, 1024, 128, 72), ("bwd", 512, 1024, 128, 72)}


def test_pallas_status_counts_the_tiles_that_run(monkeypatch):
    """`tiles_live / tiles` says whether the causal skip engages: at most
    62.5% of the square at the train cells' shape, all of it non-causal."""
    from ray_tpu.ops import attention

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v = _qkv(jax.random.PRNGKey(13), b=1, h=1, s=1024, d=64,
                   dtype=jnp.bfloat16)
    attention.reset_pallas_status()
    jax.grad(lambda q: flash_attention(q, k, v, True).sum())(q)
    jax.grad(lambda q: flash_attention(q, k, v, False).sum())(q)
    flash_attention(q[:, :, :32], k, v, True)   # the rule's reference path
    share = {}
    for e in attention.pallas_status():
        assert e["shape"][-1] == 64 and e["dtype"] == "bfloat16"
        if e["path"] == "reference":
            assert (e["causal"], e["tiles"], e["tiles_live"]) == (
                True, None, None)
        else:
            share[e["pass"], e["causal"]] = e["tiles_live"] / e["tiles"]
    assert set(share) == {("fwd", True), ("bwd", True), ("fwd", False),
                          ("bwd", False)}
    assert share["fwd", True] == share["bwd", True] <= 0.625
    assert share["fwd", False] == share["bwd", False] == 1.0


@pytest.mark.parametrize("seq, d, causal, want", [
    # d <= 128: one 1024 x 1024 grid block; forward / dQ stripes 128 rows
    # high where the whole row is one block, 256 otherwise
    (1024, 64, True, (1024, 1024, 128, 128)),
    (1024, 64, False, (1024, 1024, 256, 128)),
    (1024, 128, True, (1024, 1024, 128, 128)),
    (4096, 64, True, (1024, 1024, 256, 128)),
    (4096, 128, True, (1024, 1024, 256, 128)),
    (8192, 64, True, (1024, 1024, 256, 128)),
    (8192, 128, False, (1024, 1024, 256, 128)),
    # wider heads keep their small blocks, one tile each
    (1024, 256, True, (256, 256, 256, 256)),
    (4096, 256, True, (256, 256, 256, 256)),
    (8192, 256, False, (256, 256, 256, 256)),
    (1024, 512, True, (128, 128, 128, 128)),
    # a sequence the big block does not divide gets the largest that does
    (1536, 64, True, (512, 512, 256, 128)),
    (384, 64, True, (128, 128, 128, 128)),
])
def test_pick_block_sizes(seq, d, causal, want):
    from ray_tpu.ops.attention import _tile_counts, pick_block_sizes

    got = pick_block_sizes(seq, d, causal)
    assert got == want
    bq, bk, tq, tk = got
    assert seq % bq == 0 and seq % bk == 0 and bq % tq == 0 and bk % tk == 0
    tiles, live = _tile_counts(seq, tq, tk, causal)
    if not causal:
        assert live == tiles
    elif seq >= 1024:      # the skip engages to the tile
        assert live / tiles <= 0.625


def test_ring_attention_matches_full_on_8_devices():
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    mesh = build_mesh(MeshSpec({"dp": 2, "sp": 4}))
    q, k, v = _qkv(jax.random.PRNGKey(3), b=4, h=2, s=256, d=32)
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_ring_attention_non_causal():
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec({"sp": 8}))
    q, k, v = _qkv(jax.random.PRNGKey(4), b=1, h=2, s=128, d=32)
    out = ring_attention_sharded(q, k, v, mesh, causal=False)
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_ring_attention_grads_close_to_reference():
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec({"dp": 2, "sp": 4}))
    q, k, v = _qkv(jax.random.PRNGKey(5), b=2, h=2, s=64, d=32)

    def f_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, causal=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)
