"""What Qwen3-Next brought under `ops/`, on the CPU at small sizes: the
chunked gated delta rule (Pallas kernels in the interpreter, and the scan
fallback) against the step-by-step scan; its fused elementwise neighbours
(`gdn_prep`, `gdn_gate`) against the chain of jax primitives they replaced;
the grouped products and the
held-expert layer against dense arithmetic, dropless under a skewed
router. The model itself is in `test_qwen3_next_model.py`.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.ops import gated_delta as gd  # noqa: E402
from ray_tpu.ops import grouped_matmul as gm  # noqa: E402
from ray_tpu.ops import held_experts as he  # noqa: E402


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def recurrence_inputs(seq, key_heads=1, value_heads=2, d=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (1, seq, key_heads, d))
    k = jax.random.normal(ks[1], (1, seq, key_heads, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (1, seq, value_heads, d))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3],
                                                 (1, seq, value_heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, seq, value_heads)))
    do = jax.random.normal(ks[5], (1, seq, value_heads, d))
    # what both sides see is what bf16 holds
    q, k, v, do = (t.astype(jnp.bfloat16).astype(jnp.float32)
                   for t in (q, k, v, do))
    return (q, k, v, g, beta), do


def close(a, b, rel):
    scale = float(jnp.max(jnp.abs(b))) + 1e-12
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) <= rel * scale


# seq 128: two whole chunks; 100: not a multiple of the chunk; 192: three
# chunks, an odd count abreast, so the inverse's last pair is a chunk and
# zeros; 640: ten chunks, padded to two grid steps of eight; 1100: eighteen
# chunks with a ragged end, padded to three grid steps, so the carry crosses
# two grid steps in both directions. One key head serves two value heads in
# all.
@pytest.mark.parametrize("seq", [128, 100, 192, 640, 1100])
def test_chunked_kernels_match_the_step_by_step_scan(interpret, seq):
    args, do = recurrence_inputs(seq)
    gd.reset_gated_delta_status()
    want = gd.gated_delta_scan(*args)
    got = gd.gated_delta_rule(*args)
    assert close(got, want, 1e-2)
    grads = jax.grad(lambda *a: jnp.sum(gd.gated_delta_rule(*a) * do),
                     argnums=range(5))(*args)
    wants = jax.grad(lambda *a: jnp.sum(gd.gated_delta_scan(*a) * do),
                     argnums=range(5))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads, wants):
        # bf16 operands inside the kernels: 2^-8 of the largest entry
        assert close(a, b, 2e-2), name
    status = gd.gated_delta_status()
    assert {c["pass"] for c in status} == {"fwd", "bwd"}
    assert all(c["path"] == "pallas" and c["chunk"] == 64
               and c["shape"] == [1, 2, seq, 128]
               and c["chunks_abreast"] == min(8, -(-seq // 64))
               and c["chunks_a_product"] == 2
               for c in status)


@pytest.mark.parametrize("chunks", [1, 2, 3, 8])
def test_chunks_abreast_equal_the_chunks_one_by_one(chunks):
    """`_chunk_parts` on [chunks, 64, d] operands (every product one
    batched `dot_general`, what a grid step runs) against the same function
    on each chunk's [64, d] alone. The inverse, two chunks to a product
    there, is the same to the bit."""
    (q, k, v, g, beta), _ = recurrence_inputs(chunks * gd.CHUNK, 1, 1)
    q, k, v = (t[0, :, 0].astype(jnp.bfloat16).reshape(chunks, gd.CHUNK, -1)
               for t in (q, k, v))
    g_row = jnp.cumsum(g[0, :, 0].reshape(chunks, 1, gd.CHUNK), axis=-1)
    b_row = beta[0, :, 0].reshape(chunks, 1, gd.CHUNK)
    abreast = gd._chunk_parts(q, k, v, g_row, b_row)
    for i in range(chunks):
        alone = gd._chunk_parts(q[i], k[i], v[i], g_row[i], b_row[i])
        for name in ("t", "w", "u0", "p", "kd", "qe", "e_last"):
            assert abreast[name][i].shape == alone[name].shape, name
            assert close(abreast[name][i], alone[name].astype(jnp.float32),
                         1e-6), (name, i)
        assert np.array_equal(abreast["t"][i], alone["t"]), i


# the chunk count under one grid step's eight, eight from there on, and of
# those the inverse's products hold two each (an odd count's last beside
# zeros) unless there is one; the scan has no grid step
@pytest.mark.parametrize("seq,d,abreast,a_product", [
    (128, 128, 2, 2), (100, 128, 2, 2), (512, 128, 8, 2), (8192, 128, 8, 2),
    (192, 128, 3, 2), (64, 128, 1, 1), (128, 16, None, None)])
def test_status_says_how_many_chunks_run_abreast(interpret, seq, d, abreast,
                                                 a_product):
    args, _ = recurrence_inputs(seq, d=d)
    gd.reset_gated_delta_status()
    jax.eval_shape(jax.grad(lambda *a: jnp.sum(gd.gated_delta_rule(*a))),
                   *args)
    status = gd.gated_delta_status()
    assert {c["pass"] for c in status} == {"fwd", "bwd"}
    assert all(c["chunks_abreast"] == abreast
               and c["chunks_a_product"] == a_product
               and c["path"] == ("pallas" if abreast else "scan")
               for c in status)


@pytest.mark.parametrize("seq", [64, 37])
def test_the_fallback_is_the_scan_and_says_why(seq):
    args, do = recurrence_inputs(seq, d=16)
    gd.reset_gated_delta_status()
    got = gd.gated_delta_rule(*args)
    assert close(got, gd.gated_delta_scan(*args), 1e-6)
    grads = jax.grad(lambda *a: jnp.sum(gd.gated_delta_rule(*a) * do),
                     argnums=range(5))(*args)
    wants = jax.grad(lambda *a: jnp.sum(gd.gated_delta_scan(*a) * do),
                     argnums=range(5))(*args)
    assert all(close(a, b, 1e-5) for a, b in zip(grads, wants))
    assert all(c["path"] == "scan" and c["reason"].startswith("platform")
               and c["chunks_abreast"] is None
               and c["chunks_a_product"] is None
               for c in gd.gated_delta_status())


def test_the_scan_is_the_recurrence_written_out():
    (q, k, v, g, beta), _ = recurrence_inputs(5, d=8)
    state = np.zeros((2, 8, 8))
    for t in range(5):
        for h in range(2):
            s = state[h] * np.exp(g[0, t, h])
            u = beta[0, t, h] * (v[0, t, h] - s.T @ k[0, t, 0])
            state[h] = s + np.outer(k[0, t, 0], u)
    out = gd.gated_delta_scan(q, k, v, g, beta)
    np.testing.assert_allclose(out[0, 4, 1], state[1].T @ q[0, 4, 0],
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("n", [16, 64])
def test_unit_lower_inverse_from_products(n):
    a = np.tril(np.random.default_rng(n).normal(size=(n, n)) * 0.3, -1)
    got = gd._unit_lower_inverse(jnp.asarray(a, jnp.float32))
    np.testing.assert_allclose(got, np.linalg.inv(np.eye(n) + a), atol=2e-4)


def strictly_lower(chunks, seed):
    a = np.random.default_rng(seed).normal(size=(chunks, 64, 64)) * 0.3
    return jnp.asarray(np.tril(a, -1), jnp.float32)


def ten_plain_products(a):
    """(I + a)^-1 of one [64, 64] as the module docstring defines it: ten
    [64, 64] x [64, 64] `HIGHEST` products, nothing side by side."""
    eye = jnp.eye(64, dtype=jnp.float32)
    same = jnp.kron(eye[:4, :4], jnp.ones((16, 16))) > 0
    diag, low = jnp.where(same, a, 0.0), jnp.where(same, 0.0, a)

    def series(m, order):
        inv, power, reach = eye - m, m, 2
        while reach < order:
            power = gd._dot32(power, power)
            inv = gd._dot32(inv, eye + power)
            reach *= 2
        return inv

    inv_diag = series(diag, 16)
    return gd._dot32(series(gd._dot32(inv_diag, low), 4), inv_diag)


# one chunk goes as a bare [64, 64] does; two and eight go in pairs, [64,
# 128] against a block-diagonal [128, 128]; three leave one beside zeros
@pytest.mark.parametrize("chunks", [1, 2, 3, 8])
def test_the_inverse_of_chunks_abreast_is_each_chunks_own_to_the_bit(chunks):
    a = strictly_lower(chunks, chunks)
    got = gd._unit_lower_inverse(a)
    assert got.shape == a.shape
    for i in range(chunks):
        assert np.array_equal(got[i], gd._unit_lower_inverse(a[i])), i
        assert np.array_equal(got[i], ten_plain_products(a[i])), i
        np.testing.assert_allclose(got[i], np.linalg.inv(np.eye(64) + a[i]),
                                   atol=2e-4)


@pytest.mark.parametrize("chunks", [2, 3, 8])
def test_the_inverses_cotangent_in_pairs_is_the_three_products(chunks):
    """da = -t^T dt t^T two chunks to a product against the transpose and
    the two [64, 64] products written out for each chunk: exact."""
    t = gd._unit_lower_inverse(strictly_lower(chunks, 7))
    dt = jnp.asarray(np.random.default_rng(chunks).normal(
        size=(chunks, 64, 64)), jnp.float32)
    got = gd._inverse_cotangent(t, dt)
    assert got.shape == t.shape
    for i in range(chunks):
        tt = gd._dot32(jnp.eye(64, dtype=jnp.float32), t[i], gd._NT)
        assert np.array_equal(tt, t[i].T)
        assert np.array_equal(got[i], -gd._dot32(gd._dot32(tt, dt[i]), tt)), i


def test_causal_conv1d_is_the_published_left_padded_convolution():
    rng = np.random.default_rng(0)
    x, w = rng.normal(size=(2, 9, 3)), rng.normal(size=(3, 4))
    want = np.zeros_like(x)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += w[:, j] * x[:, t - 3 + j]
    np.testing.assert_allclose(
        gd.causal_conv1d(jnp.asarray(x), jnp.asarray(w)), want, atol=1e-6)


# --------------------------------------------------------------------------- #
# The recurrence's elementwise neighbours: `gdn_prep` and `gdn_gate`
# --------------------------------------------------------------------------- #


def beside_inputs(seq, key_w=128, val_w=256, d=128, dtype=jnp.bfloat16):
    """A projection [q | k | v | z] of one key head and two value heads,
    the convolution's and the norm's weights, the recurrence's output and a
    cotangent for everything the two ops return."""
    ks = jax.random.split(jax.random.PRNGKey(seq), 9)
    rand = lambda k, w: jax.random.normal(k, (1, seq, w)).astype(dtype)
    return dict(
        qkvz=rand(ks[0], 2 * key_w + 2 * val_w),
        conv_w=jax.random.uniform(ks[1], (2 * key_w + val_w, 4),
                                  jnp.float32, -0.5, 0.5),
        norm_w=1.0 + 0.1 * jax.random.normal(ks[2], (d,)),
        o=rand(ks[3], val_w),
        cts=(rand(ks[4], key_w), rand(ks[5], key_w), rand(ks[6], val_w)),
        dg=rand(ks[7], val_w), d=d, channels=2 * key_w + val_w)


def dotted(outs, cts):
    return sum(jnp.sum(a.astype(jnp.float32) * c.astype(jnp.float32))
               for a, c in zip(outs, cts))


def old_mixer_chain(qkvz, conv_w, norm_w, o, d, eps=1e-6):
    """`GatedDeltaNet.__call__` around its recurrence as it stood before
    the fused ops, line for line (q, k, v, gated)."""
    b, s, _ = qkvz.shape
    val_w = qkvz.shape[-1] - conv_w.shape[0]
    key_w = (conv_w.shape[0] - val_w) // 2
    mixed = jax.nn.silu(gd.causal_conv1d(
        qkvz[..., :2 * key_w + val_w].astype(jnp.float32), conv_w))
    z = qkvz[..., 2 * key_w + val_w:].reshape(b, s, -1, d)
    q = mixed[..., :key_w].reshape(b, s, -1, d)
    k = mixed[..., key_w:2 * key_w].reshape(b, s, -1, d)
    v = mixed[..., 2 * key_w:].reshape(b, s, -1, d)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                 + 1e-6)

    q = (unit(q) * d ** -0.5).astype(qkvz.dtype)
    k = unit(k).astype(qkvz.dtype)
    v = v.astype(qkvz.dtype)
    of = o.reshape(b, s, -1, d).astype(jnp.float32)
    rms = of * jax.lax.rsqrt(jnp.mean(jnp.square(of), axis=-1, keepdims=True)
                             + eps)
    gated = norm_w * rms * jax.nn.silu(z.astype(jnp.float32))
    return (q.reshape(b, s, key_w), k.reshape(b, s, key_w),
            v.reshape(b, s, val_w),
            gated.astype(qkvz.dtype).reshape(b, s, val_w))


# seq 128: one block of fewer rows than the kernels' own 1024; 1024: one
# whole block; 3072: three, so a block has a neighbour on both sides and
# the convolution's three rows cross a border forward, its transpose's
# three backward.
@pytest.mark.parametrize("seq", [128, 1024, 3072])
def test_gdn_prep_matches_the_chain_it_fuses(interpret, seq):
    x = beside_inputs(seq)
    gd.reset_gated_delta_status()
    got = gd.gdn_prep(x["qkvz"], x["conv_w"], x["d"])
    want = gd._prep_chain(x["qkvz"], x["conv_w"], x["d"])
    assert got[3] is x["qkvz"]
    for name, a, b in zip("qkv", got, want):
        # bf16 out of f32 arithmetic in another order: an ulp of bf16
        assert a.dtype == b.dtype == jnp.bfloat16 and a.shape == b.shape
        assert close(a, b.astype(jnp.float32), 4e-3), name
    grads = jax.grad(lambda a, w: dotted(gd.gdn_prep(a, w, x["d"]),
                                         x["cts"]), (0, 1))(
        x["qkvz"], x["conv_w"])
    wants = jax.grad(lambda a, w: dotted(gd._prep_chain(a, w, x["d"]),
                                         x["cts"]), (0, 1))(
        x["qkvz"], x["conv_w"])
    assert grads[0].dtype == jnp.bfloat16
    assert grads[1].dtype == jnp.float32 and grads[1].shape == (512, 4)
    assert close(grads[0], wants[0].astype(jnp.float32), 4e-3)
    assert close(grads[1], wants[1], 1e-4)      # summed in f32 in-kernel
    # nothing of z reaches q, k, v
    assert not np.asarray(grads[0][..., x["channels"]:]).any()
    assert all(c["path"] == "pallas" and c["chunk"] == min(seq, 1024)
               and c["shape"] == [1, seq, 768]
               for c in gd.gated_delta_status())


@pytest.mark.parametrize("seq", [128, 1024, 3072])
def test_gdn_gate_matches_the_chain_it_fuses(interpret, seq):
    x = beside_inputs(seq)
    args = (x["o"], x["qkvz"], x["norm_w"])
    gd.reset_gated_delta_status()
    got = gd.gdn_gate(*args, 1e-6)
    want = gd._gate_chain(*args, 1e-6)
    assert got.dtype == jnp.bfloat16
    assert close(got, want.astype(jnp.float32), 4e-3)
    grads = jax.grad(lambda *a: dotted([gd.gdn_gate(*a, 1e-6)], [x["dg"]]),
                     (0, 1, 2))(*args)
    wants = jax.grad(lambda *a: dotted([gd._gate_chain(*a, 1e-6)],
                                       [x["dg"]]), (0, 1, 2))(*args)
    assert grads[0].dtype == grads[1].dtype == jnp.bfloat16
    assert close(grads[0], wants[0].astype(jnp.float32), 4e-3)
    # d z lies in the projection's LAST columns; the others are not the
    # gate's to write (`gdn_prep`'s backward fills them)
    z = slice(x["channels"], None)
    assert close(grads[1][..., z], wants[1][..., z].astype(jnp.float32),
                 4e-3)
    assert grads[2].dtype == jnp.float32 and close(grads[2], wants[2], 1e-4)
    assert {c["pass"] for c in gd.gated_delta_status()} == {"gate_fwd",
                                                             "gate_bwd"}
    assert all(c["path"] == "pallas" for c in gd.gated_delta_status())


# the two ops as the model chains them (v stands for the recurrence's
# output): d z, written by the gate's backward, arrives inside the buffer
# `gdn_prep`'s backward completes
@pytest.mark.parametrize("seq", [128, 2048])
def test_both_ops_give_the_projection_one_whole_gradient(interpret, seq):
    x = beside_inputs(seq)

    def through(prep, gate):
        def loss(qkvz, conv_w, norm_w):
            q, k, v, z_in = prep(qkvz, conv_w)
            return dotted((q, k, gate(v, z_in, norm_w)),
                          (*x["cts"][:2], x["dg"]))
        return jax.grad(loss, (0, 1, 2))(x["qkvz"], x["conv_w"], x["norm_w"])

    got = through(lambda a, w: gd.gdn_prep(a, w, x["d"]),
                  lambda o, z, w: gd.gdn_gate(o, z, w, 1e-6))
    want = through(lambda a, w: (*gd._prep_chain(a, w, x["d"]), a),
                   lambda o, z, w: gd._gate_chain(o, z, w, 1e-6))
    assert got[0].shape == x["qkvz"].shape
    for a, b, rel in zip(got, want, (4e-3, 1e-3, 1e-3)):
        assert close(a, b.astype(jnp.float32), rel)


# 100: no whole 16-row tile; 1536: more than a block and not whole blocks
@pytest.mark.parametrize("seq", [100, 1536])
def test_a_sequence_of_part_blocks_falls_back_and_says_why(interpret, seq):
    x = beside_inputs(seq)
    gd.reset_gated_delta_status()
    q, k, v, z_in = gd.gdn_prep(x["qkvz"], x["conv_w"], x["d"])
    gated = gd.gdn_gate(x["o"], z_in, x["norm_w"], 1e-6)
    old = old_mixer_chain(x["qkvz"], x["conv_w"], x["norm_w"], x["o"],
                          x["d"])
    for a, b in zip((q, k, v, gated), old):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))
    status = gd.gated_delta_status()
    assert {c["pass"] for c in status} == {"prep_fwd", "gate_fwd"}
    assert all(c["path"] == "xla" and "whole blocks" in c["reason"]
               and c["chunk"] is None for c in status)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_fallback_equals_the_old_code_bit_for_bit(dtype):
    """Off the TPU, and for heads that are not 128 wide, the ops ARE the
    chain of jax primitives the model held before them: values and every
    gradient, to the bit, heads of 16 as in `Qwen3NextConfig.tiny`."""
    x = beside_inputs(96, key_w=32, val_w=64, d=16, dtype=dtype)

    def new(qkvz, conv_w, norm_w, o):
        q, k, v, z_in = gd.gdn_prep(qkvz, conv_w, x["d"])
        return q, k, v, gd.gdn_gate(o, z_in, norm_w, 1e-6)

    def old(qkvz, conv_w, norm_w, o):
        return old_mixer_chain(qkvz, conv_w, norm_w, o, x["d"])

    args = (x["qkvz"], x["conv_w"], x["norm_w"], x["o"])
    cts = (*x["cts"], x["dg"])
    gd.reset_gated_delta_status()
    for a, b in zip(new(*args), old(*args)):
        assert a.dtype == b.dtype == dtype and bool(jnp.all(a == b))
    grads = jax.grad(lambda *a: dotted(new(*a), cts), range(4))(*args)
    wants = jax.grad(lambda *a: dotted(old(*a), cts), range(4))(*args)
    for name, a, b in zip(("qkvz", "conv_w", "norm_w", "o"), grads, wants):
        assert a.dtype == b.dtype and bool(jnp.all(a == b)), name
    status = gd.gated_delta_status()
    assert {c["pass"] for c in status} == {"prep_fwd", "prep_bwd",
                                           "gate_fwd", "gate_bwd"}
    assert all(c["path"] == "xla" and c["reason"].startswith("platform")
               and c["calls"] >= 1 for c in status)


@pytest.mark.parametrize("d,key_w,reason", [
    (16, 32, "head_dim is not the lane width"),
    (128, 128, "")])
def test_status_lists_the_four_passes_with_path_and_reason(interpret, d,
                                                           key_w, reason):
    x = beside_inputs(128, key_w=key_w, val_w=2 * key_w, d=d)
    gd.reset_gated_delta_status()

    def loss(qkvz, conv_w, norm_w):
        q, k, v, z_in = gd.gdn_prep(qkvz, conv_w, d)
        return dotted((q, k, gd.gdn_gate(v, z_in, norm_w, 1e-6)),
                      (*x["cts"][:2], x["dg"]))

    jax.eval_shape(jax.grad(loss, (0, 1, 2)), x["qkvz"], x["conv_w"],
                   x["norm_w"])
    status = {c["pass"]: c for c in gd.gated_delta_status()}
    assert sorted(status) == ["gate_bwd", "gate_fwd", "prep_bwd",
                              "prep_fwd"]
    for c in status.values():
        assert c["path"] == ("xla" if reason else "pallas")
        assert c["reason"].startswith(reason) and c["calls"] == 1
        assert c["chunks_abreast"] is None


# (row tile, forward only): the trained product and both its gradients; the
# served step's product at the smallest bf16 tile and at the trained one,
# where two of five groups own NO tile and the tail repeats the last that
# does: their weights are NaN, and nothing reads them.
@pytest.mark.parametrize("tile,forward_only", [(128, False), (16, True),
                                               (128, True)])
def test_grouped_matmul_and_both_gradients(tile, forward_only):
    k, n = 256, 384
    groups = 5 if forward_only else 3
    tile_group = jnp.array([0, 0, 2, 4, 4, 4] if forward_only
                           else [0, 0, 1, 2, 2, 2], jnp.int32)
    used = jnp.array([5], jnp.int32)          # the sixth tile is the tail
    m = 6 * tile
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(ks[0], (m, k)).astype(jnp.bfloat16)
    lhs = lhs.at[5 * tile:].set(0)
    rhs = (0.1 * jax.random.normal(ks[1], (groups, k, n))).astype(
        jnp.bfloat16).astype(jnp.float32)
    do = jax.random.normal(ks[2], (m, n)).astype(jnp.bfloat16)
    live = (jnp.arange(m) < 5 * tile)[:, None]

    def dense(lhs, rhs):
        w = rhs[jnp.repeat(tile_group, tile)]
        return jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32), w) * live

    if forward_only:
        absent = rhs.at[jnp.array([1, 3])].set(jnp.nan)
        out = gm.grouped_matmul_forward(lhs, absent, tile_group, used, tile)
    else:
        assert tile == gm.TILE
        out = gm.grouped_matmul(lhs, rhs, tile_group, used)
    assert out.dtype == jnp.bfloat16 and close(out, dense(lhs, rhs), 1e-2)
    assert not np.asarray(out[5 * tile:]).any()
    if forward_only:
        return
    f32 = jnp.float32
    got = jax.grad(lambda l, r: jnp.sum(
        gm.grouped_matmul(l, r, tile_group, used).astype(f32)
        * do.astype(f32)), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(lambda l, r: jnp.sum(dense(l, r) * do.astype(f32)),
                    argnums=(0, 1))(lhs, rhs)
    assert got[1].dtype == jnp.float32       # never through bf16
    assert close(got[0], want[0].astype(f32), 1e-2)
    assert close(got[1], want[1], 1e-3)


def expert_problem(tokens=300, d=64, width=32, experts=16, skew=0.0):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (tokens, d))
    w_router = 0.5 * jax.random.normal(ks[1], (d, experts))
    if skew:        # one feature every token has, and expert 5 loves
        x = x.at[:, 0].set(3.0)
        w_router = w_router.at[0, 5].add(5.0 * skew)
    w_gate_up = 0.2 * jax.random.normal(ks[2], (experts, d, 2 * width))
    w_down = 0.2 * jax.random.normal(ks[3], (experts, width, d))
    return x, w_router, w_gate_up, w_down, jax.random.normal(ks[4],
                                                             (tokens, d))


def dense_routed(x, gates, index, w_gate_up, w_down, held):
    """Every token through every held expert, masked by its gate."""
    first, count = held
    width = w_down.shape[1]
    bf = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    out = jnp.zeros_like(x)
    for e in range(first, first + count):
        h = bf(x) @ bf(w_gate_up[e])
        y = (jax.nn.silu(h[:, :width]) * h[:, width:]) @ bf(w_down[e])
        out = out + jnp.sum(jnp.where(index == e, gates, 0), -1)[:, None] * y
    return out


def dense_experts(x, w_router, w_gate_up, w_down, held, top_k):
    _, gates, index = he.route(x, w_router, top_k)
    return dense_routed(x, gates, index, w_gate_up, w_down, held)


def held_part(x, w_router, w_gate_up, w_down, held, top_k):
    first, count = held
    _, gates, index = he.route(x, w_router, top_k)
    return he.held_expert_mlp(
        x.astype(jnp.bfloat16), gates, index,
        w_gate_up[first:first + count], w_down[first:first + count], held,
        w_router.shape[1])


SERVED_TOKENS = {"decode": 32, "chunk": 256}     # the Kanana-2 cell's shapes


def served_routing(index, experts, routing):
    """The router's choice bent to a served step's hard cases: `few`, nine
    experts of 128 draw every row and the tail of the rows is idle;
    `one`, expert 5 draws every row; `idle`, every row is padding."""
    if routing == "few":
        index = jnp.array([3, 40, 41, 42, 90, 100, 101, 126, 127])[index % 9]
        return index.at[-index.shape[0] // 3:].set(experts)
    return jnp.full_like(index, 5 if routing == "one" else experts)


# (held, block): one block; a block so small that the later blocks run;
# every expert held. (program, routing): the forward-only entry at the
# served cell's two shapes, 6 choices over 128 experts at small widths.
@pytest.mark.parametrize("held,block,served", [
    ((4, 4), 0, None), ((4, 4), 128, None), ((0, 16), 256, None),
    *(((0, 128), 0, (program, routing)) for program in SERVED_TOKENS
      for routing in ("few", "one", "idle"))])
def test_held_experts_match_dense_arithmetic(held, block, served,
                                             monkeypatch):
    if block:
        monkeypatch.setattr(he, "default_block", lambda *sizes: block)
    if served:
        return served_matches_dense_and_trained(*served)
    x, w_router, w_gate_up, w_down, do = expert_problem()
    args = (x, w_router, w_gate_up, w_down)
    got, counts = held_part(*args, held, 4)
    want = dense_experts(*args, held, 4)
    assert close(got, want, 2e-2)
    assert int(counts["placed"]) == int(counts["assigned"]) \
        == int(jnp.sum(counts["load"])) > block
    grads = jax.grad(lambda *a: jnp.sum(held_part(*a, held, 4)[0] * do),
                     argnums=range(4))(*args)
    wants = jax.grad(lambda *a: jnp.sum(dense_experts(*a, held, 4) * do),
                     argnums=range(4))(*args)
    for name, a, b in zip(("x", "router", "gate_up", "down"), grads, wants):
        assert close(a, b, 3e-2), name


def served_matches_dense_and_trained(program, routing):
    experts, top_k, tokens = 128, 6, SERVED_TOKENS[program]
    x, w_router, w_gate_up, w_down, _ = expert_problem(
        tokens=tokens, d=32, width=16, experts=experts)
    _, gates, index = he.route(x, w_router, top_k)
    index = served_routing(index, experts, routing)
    call = (x.astype(jnp.bfloat16), gates, index, w_gate_up, w_down,
            (0, experts), experts)
    he.reset_held_experts_status()
    got, counts = he.held_expert_forward(*call)
    (line,) = he.held_experts_status()
    trained, trained_counts = he.held_expert_mlp(*call)
    want = dense_routed(x, gates, index, w_gate_up, w_down, (0, experts))
    # the same sums in the same order as the trained path's forward
    np.testing.assert_array_equal(np.asarray(got), np.asarray(trained))
    assert close(got, want, 2e-2) if routing != "idle" \
        else not np.asarray(got).any()
    live = int(jnp.sum(index < experts))
    drew = int(jnp.sum(counts["load"] > 0))
    assert int(counts["placed"]) == int(counts["assigned"]) == live \
        == int(trained_counts["placed"])
    assert drew == {"few": 9, "one": 1, "idle": 0}[routing]
    # an expert without a row has no tile; one with rows has what they fill
    assert int(counts["tiles"]) == int(jnp.sum(-(-counts["load"] // 16)))
    assert drew <= int(counts["tiles"]) <= line["rows"] // line["tile"]
    assert "tiles" not in trained_counts
    assert (line["tile"], line["blocks"], line["forward_only"]) \
        == (16, 1, True)


def test_the_forward_only_entry_has_no_gradient():
    """A served step never differentiates; a caller that tries is told so
    and is not handed a weight gradient with unwritten blocks."""
    x, w_router, w_gate_up, w_down, _ = expert_problem(tokens=32)
    _, gates, index = he.route(x, w_router, 4)

    def part(x, w_gate_up):
        return jnp.sum(he.held_expert_forward(
            x, gates, index, w_gate_up, w_down, (0, 16), 16)[0])

    for argnum in (0, 1):
        with pytest.raises(NotImplementedError):
            jax.grad(part, argnums=argnum)(x.astype(jnp.bfloat16), w_gate_up)


# Under a checkpoint whose policy keeps the expert layer's names, only the
# FIRST block's products and layout are kept: a policy reaches through the
# later blocks' `cond`, `scan` and inner checkpoint, and names there would
# keep every trip's rows (at the cell's sizes 200 + 400 MB a layer).
@pytest.mark.parametrize("block,blocks", [(0, 2), (128, 10)])
def test_a_policy_keeps_the_first_block_by_name_and_no_later_one(
        block, blocks, monkeypatch):
    from jax._src.ad_checkpoint import saved_residuals

    if block:
        monkeypatch.setattr(he, "default_block", lambda *sizes: block)
    x, w_router, w_gate_up, w_down, do = expert_problem()
    keep = jax.checkpoint_policies.save_only_these_names(
        "moe_plan", "moe_h", "moe_y")

    def part(*args):
        return jnp.sum(held_part(*args, (4, 4), 4)[0] * do)

    he.reset_held_experts_status()
    kept = saved_residuals(jax.checkpoint(part, policy=keep), x, w_router,
                           w_gate_up, w_down)
    (call,) = he.held_experts_status()
    assert call["blocks"] == blocks
    rows = call["block"] + 4 * he.TILE
    made = [(aval.dtype.name, aval.shape) for aval, why in kept
            if "argument" not in why]
    width, d = w_down.shape[1], x.shape[1]
    # logits, h, y (of one shape here) and the layout's two row tables:
    # once each
    assert 2 * width == d
    for want, times in ((("float32", (300, 16)), 1),
                        (("bfloat16", (rows, d)), 2),
                        (("int32", (rows,)), 2)):
        assert made.count(want) == times, (want, made)
    assert not [s for _, s in made if len(s) > 2], made   # no trip-stacked
    assert not [why for _, why in kept if "output of cond" in why]
    # and the gradients are those of the layer without a checkpoint
    args = (x, w_router, w_gate_up, w_down)
    got = jax.grad(jax.checkpoint(part, policy=keep), argnums=range(4))(*args)
    want = jax.grad(part, argnums=range(4))(*args)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dropless_when_one_expert_takes_most_tokens():
    x, w_router, w_gate_up, w_down, _ = expert_problem(skew=1.0)
    held = (5, 1)
    _, _, index = he.route(x, w_router, 4)
    taken = int(jnp.sum(index == 5))
    assert taken > 0.9 * x.shape[0]          # the skew is real
    he.reset_held_experts_status()
    got, counts = held_part(x, w_router, w_gate_up, w_down, held, 4)
    assert int(counts["load"][0]) == taken
    assert int(counts["placed"]) == int(counts["assigned"])
    assert close(got, dense_experts(x, w_router, w_gate_up, w_down, held, 4),
                 2e-2)
    (call,) = he.held_experts_status()
    assert call["held"] == [5, 1] and call["experts"] == 16
    # the default block is 3 x even routing: this load needs the later ones
    assert int(counts["assigned"]) > call["block"] and call["blocks"] > 1




@pytest.mark.parametrize("always", [1, 2, 4])
def test_sum_rows_is_exact_whatever_it_gathers_unasked(always):
    rng = np.random.default_rng(always)
    rows = jnp.asarray(rng.normal(size=(40, 8)), jnp.float32)
    # sorted slots, 40 = "no row"; one token has a row in every slot
    slots = np.sort(np.where(rng.random((25, 4)) < 0.4,
                             rng.integers(0, 40, (25, 4)), 40), axis=-1)
    slots[3] = [1, 5, 7, 9]
    ext = np.concatenate([np.asarray(rows), np.zeros((1, 8))])
    got = he._sum_rows(rows, jnp.asarray(slots, jnp.int32), always)
    np.testing.assert_allclose(got, ext[slots].sum(axis=1), atol=1e-5)
    # nothing beyond the slots gathered unasked: the same, by the other branch
    few = np.where(np.arange(4) < always, slots, 40)
    got = he._sum_rows(rows, jnp.asarray(few, jnp.int32), always)
    np.testing.assert_allclose(got, ext[few].sum(axis=1), atol=1e-5)


def test_slots_always_by_the_rule():
    # 10 x 32 / 512 = 0.625 held choices a token: one + six slots unasked
    assert he.slots_always(8192, 10, 32, 512) == 7
    assert he.slots_always(300, 4, 4, 16) == 4      # never more than top_k
    assert 1 <= he.slots_always(8, 10, 1, 512) <= 2


# The status line of a traced call, by path: the fifth cell's trained step
# (8,192 tokens, top-10, 32 held of 512) reads what it read before the
# served path had a layout of its own; the Kanana-2 cell's decode step and
# prefill chunk read the served rule's tile and rows.
@pytest.mark.parametrize("entry,tokens,top_k,held,experts,want", [
    ("held_expert_mlp", 8192, 10, (0, 32), 512,
     dict(tile=128, block=15360, blocks=6, rows=15360 + 32 * 128)),
    ("held_expert_mlp", 32, 6, (0, 128), 128,
     dict(tile=128, block=256, blocks=1, rows=16640)),
    ("held_expert_forward", 32, 6, (0, 128), 128,
     dict(tile=16, block=256, blocks=1, rows=2304)),
    ("held_expert_forward", 256, 6, (0, 128), 128,
     dict(tile=16, block=1536, blocks=1, rows=3584)),
    # 8 held of 128: fewer owners than assignments, two blocks
    ("held_expert_forward", 256, 6, (8, 8), 128,
     dict(tile=16, block=384, blocks=4, rows=384 + 8 * 16)),
    # an expert's even share reaches a tile: the trained path's 128
    ("held_expert_forward", 512, 4, (0, 16), 16,
     dict(tile=128, block=2048, blocks=1, rows=2048 + 16 * 128))])
def test_default_block_and_the_status_line(entry, tokens, top_k, held,
                                           experts, want):
    # 8192 x 10 x 32 / 512 = 5120 assignments at even routing; 3 x that
    assert he.default_block(8192, 10, 32, 512) == 15360
    assert he.default_block(64, 4, 1, 16) == 128    # whole tiles
    assert he.default_block(8, 4, 16, 16) == 128    # never past the worst
    assert he.serve_tile(32, 6, 128) == he.serve_tile(256, 6, 128) == 16
    assert he.serve_tile(512, 4, 16) == gm.TILE == 128
    d, width, count = 64, 32, held[1]
    shape = jax.ShapeDtypeStruct
    he.reset_held_experts_status()
    jax.eval_shape(                       # traced, and nothing run
        lambda *a: getattr(he, entry)(*a, held, experts),
        shape((tokens, d), jnp.bfloat16), shape((tokens, top_k), jnp.float32),
        shape((tokens, top_k), jnp.int32),
        shape((count, d, 2 * width), jnp.float32),
        shape((count, width, d), jnp.float32))
    (call,) = he.held_experts_status()
    # off the TPU, and not asked for: the interpreter, and it says so
    assert call["path"] == ("pallas" if os.environ.get(
        "RAY_TPU_PALLAS_INTERPRET") == "1" else "interpret")
    assert call["block"] == he.default_block(tokens, top_k, count, experts)
    assert {k: call[k] for k in want} == want
    assert call["forward_only"] == (entry == "held_expert_forward")
    assert (call["held"], call["experts"], call["tokens"], call["top_k"],
            call["calls"]) == (list(held), experts, tokens, top_k, 1)
