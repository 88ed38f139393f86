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


def test_pick_block_sizes():
    from ray_tpu.ops.attention import pick_block_sizes

    assert pick_block_sizes(4096, 64) == (512, 1024)
    assert pick_block_sizes(4096, 256) == (256, 256)
    bq, bk = pick_block_sizes(384, 64)
    assert 384 % bq == 0


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
