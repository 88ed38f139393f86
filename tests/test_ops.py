"""Kernel numerics: flash attention (fwd+bwd) and ring attention vs XLA
reference, ring over 8 virtual CPU devices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import (flash_attention, flash_attention_bse,
                                   mha_reference)


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


def _rows(t):
    """[b, h, s, d] -> [b, s, h*d], the layout of the projections."""
    b, h, s, d = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _bse_and_reference(q, k, v, causal, fused, blocks=0):
    """(out, dq, dk, dv) of `flash_attention_bse` on the [b, s, h*d] forms
    of q, k, v (`fused`: on their concatenation, one array read as three
    views; `blocks`: explicit grid blocks) and of mha_reference, all as
    [b, s, h*d] float32 arrays. k and v may hold fewer heads than q: the
    entry takes them as they are, the reference their repeat, and dk, dv
    come back at the width k and v went in at."""
    d = q.shape[-1]
    group = q.shape[1] // k.shape[1]

    def bse(q, k, v):
        q, k, v = _rows(q), _rows(k), _rows(v)
        qkv = jnp.concatenate([q, k, v], axis=-1) if fused else (q, k, v)
        return flash_attention_bse(qkv, d, causal, block_q=blocks,
                                   block_k=blocks)

    def reference(q, k, v):
        if group > 1:
            k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
        return _rows(mha_reference(q, k, v, causal=causal))

    def run(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)
        out, grads = fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return [np.asarray(out, np.float32)] + [
            np.asarray(_rows(g), np.float32) for g in grads]

    return run(bse), run(reference)


@pytest.mark.parametrize("seq, heads, d, causal, fused", [
    # d = 64: a 128-lane column block holds two heads
    (256, 2, 64, True, True),
    (256, 4, 64, False, False),
    (1024, 2, 64, True, True),       # the train cells' schedule
    (1024, 2, 64, False, True),
    (1024, 2, 64, True, False),
    (2048, 2, 64, True, True),       # several grid blocks
    # d = 128: one head a block
    (256, 2, 128, True, True),
    (256, 2, 128, False, False),
    (1024, 1, 128, True, True),
    (2048, 1, 128, False, True),
])
def test_flash_bse_parity(monkeypatch, seq, heads, d, causal, fused):
    """Forward and the three gradients of the [batch, seq, heads*d] entry
    against mha_reference: q, k, v apart, and as three views of one fused
    array."""
    from ray_tpu.ops import attention

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v = _qkv(jax.random.PRNGKey(21), b=1, h=heads, s=seq, d=d)
    attention.reset_pallas_status()
    got, want = _bse_and_reference(q, k, v, causal, fused)
    for a, b, tol in zip(got, want, [2e-5, 5e-4, 5e-4, 5e-4]):
        np.testing.assert_allclose(a, b, atol=tol, rtol=tol)
    assert {(e["pass"], e["path"], e["layout"], e["heads_per_block"],
             tuple(e["shape"])) for e in attention.pallas_status()} == {
        (p, "pallas", "bse", max(1, 128 // d), (1, heads, seq, d))
        for p in ("fwd", "bwd")}


@pytest.mark.parametrize("seq, heads, kv, d, blocks", [
    (1024, 16, 2, 256, 0),      # the Qwen3-Next layer's heads, one block
    (512, 16, 2, 256, 256),     # 2 x 2 blocks: dead steps, a group's turns
    (512, 4, 2, 128, 256),
    (256, 4, 2, 64, 0),         # two heads a column block: the entry repeats
])
def test_flash_bse_grouped_queries_read_a_kv_head_in_place(
        monkeypatch, seq, heads, kv, d, blocks):
    """k and v at [batch, seq, kv_heads*d]: value and the three gradients
    against mha_reference on repeated k and v, with dK / dV compared at
    the width they went in at (the group's sum is the kernel's own where a
    column block is one head, autodiff's of the entry's repeat at d = 64)."""
    from ray_tpu.ops import attention

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, _, _ = _qkv(jax.random.PRNGKey(25), b=1, h=heads, s=seq, d=d)
    _, k, v = _qkv(jax.random.PRNGKey(26), b=1, h=kv, s=seq, d=d)
    attention.reset_pallas_status()
    got, want = _bse_and_reference(q, k, v, True, False, blocks)
    assert got[2].shape == got[3].shape == (1, seq, kv * d)
    for a, b, tol in zip(got, want, [2e-5, 5e-4, 5e-4, 5e-4]):
        np.testing.assert_allclose(a, b, atol=tol, rtol=tol)
    in_place = d >= 128
    assert {(e["pass"], e["path"], tuple(e["shape"]), e["kv_heads"],
             e["heads_per_block"]) for e in attention.pallas_status()} == {
        (p, "pallas", (1, heads, seq, d), kv if in_place else heads,
         max(1, 128 // d)) for p in ("fwd", "bwd")}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_bhsd_wrapper_runs_the_same_kernels(monkeypatch, dtype):
    """`flash_attention` on [b, h, s, d] and `flash_attention_bse` on the
    same values as [b, s, h*d] are one set of kernels: the same numbers,
    to the order of a sum (a head alone in its block against two heads a
    block), out and gradients."""
    from ray_tpu.ops import attention

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v = _qkv(jax.random.PRNGKey(22), b=2, h=2, s=256, d=64,
                   dtype=jnp.dtype(dtype))
    attention.reset_pallas_status()
    bse, _ = _bse_and_reference(q, k, v, True, True)
    bhsd, _ = _flash_and_reference(q, k, v, True)
    tol = 1e-5 if dtype == "float32" else 2.0 ** -7
    for a, b in zip(bse, [np.asarray(_rows(x)) for x in bhsd]):
        np.testing.assert_allclose(a, b, atol=tol * np.abs(b).max(), rtol=0)
    assert {(e["pass"], e["layout"], e["heads_per_block"])
            for e in attention.pallas_status() if e["path"] == "pallas"} == {
        ("fwd", "bse", 2), ("bwd", "bse", 2),
        ("fwd", "bhsd", 1), ("bwd", "bhsd", 1)}


def test_flash_bse_takes_the_reference_where_the_rule_says(monkeypatch):
    """Three heads of 64 do not fill whole 128-lane column blocks: the
    rule sends the call to the reference and says so; the numbers are
    mha_reference's either way, as off the chip."""
    from ray_tpu.ops import attention

    q, k, v = _qkv(jax.random.PRNGKey(23), b=1, h=3, s=128, d=64)
    attention.reset_pallas_status()
    cpu, want = _bse_and_reference(q, k, v, True, True)
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    odd, _ = _bse_and_reference(q, k, v, True, True)
    for a, b, c in zip(cpu, odd, want):
        np.testing.assert_allclose(a, c, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(b, c, atol=2e-5, rtol=2e-5)
    assert {(e["path"], e["reason"], e["layout"], e["heads_per_block"])
            for e in attention.pallas_status()} == {
        ("reference", "platform cpu", "bse", None),
        ("reference", "heads do not fill whole column blocks", "bse", None)}


@pytest.mark.parametrize("heads_axis", [None, "tp"])
def test_flash_bse_sharded_matches_the_unsharded_entry(monkeypatch,
                                                       heads_axis):
    """`flash_attention_bse_sharded` under a dp x tp mesh: each device
    runs the kernels on its own [b/dp, s, (h/tp)*d]. With no heads axis
    the fused array goes in as it is; with one it is split first, since
    whole heads shard with the last dim only once q, k and v are apart.
    Out and the gradient of the fused array equal the unsharded entry's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.ops import attention
    from ray_tpu.ops.attention import flash_attention_bse_sharded

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    q, k, v = _qkv(jax.random.PRNGKey(24), b=4, h=4, s=128, d=64)
    qkv = jnp.concatenate([_rows(q), _rows(k), _rows(v)], axis=-1)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))

    def loss(fn):
        return jax.value_and_grad(
            lambda x: jnp.sum(fn(x).astype(jnp.float32) ** 2))

    want = loss(lambda x: flash_attention_bse(x, 64, True))(qkv)
    attention.reset_pallas_status()
    with jax.set_mesh(mesh):
        got = jax.jit(loss(lambda x: flash_attention_bse_sharded(
            x, 64, P("dp", None, heads_axis), True)))(
            jax.device_put(qkv, NamedSharding(mesh, P("dp"))))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)
    local_heads = 2 if heads_axis else 4
    assert {(e["pass"], e["path"], e["layout"], tuple(e["shape"]))
            for e in attention.pallas_status()} == {
        (p, "pallas", "bse", (2, local_heads, 128, 64))
        for p in ("fwd", "bwd")}


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
        assert e["shape"] == [1, 1, e["shape"][2], 64]
        assert e["dtype"] == "bfloat16" and e["layout"] == "bhsd"
        if e["path"] == "reference":
            assert (e["causal"], e["tiles"], e["tiles_live"],
                    e["heads_per_block"]) == (True, None, None, None)
        else:
            assert e["heads_per_block"] == 1    # one head, its own block
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
    # d = 256: the same block in tiles of 256 (measured at 8192 causal,
    # PR 44); d = 512 keeps its one-tile blocks (not measured)
    (1024, 256, True, (1024, 1024, 256, 256)),
    (4096, 256, True, (1024, 1024, 256, 256)),
    (8192, 256, False, (1024, 1024, 256, 256)),
    (1024, 512, True, (128, 128, 128, 128)),
    # a sequence the big block does not divide gets the largest that does
    (1536, 64, True, (512, 512, 256, 128)),
    (384, 64, True, (128, 128, 128, 128)),
    (1536, 256, True, (512, 512, 256, 256)),
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
