"""`ops/power_retention.py`: the feature map's identity, the scan against
the quadratic form it folds, the chunked and one-token paths against the
scan (the dispatch rule's fallback at a head of 16, the Pallas kernels in
the interpreter at a head of 128), a group's shared state, hold and reset,
and what `retention_status()` says. CPU, float32."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import power_retention as pr

F32 = jnp.float32


def quadratic(q, k, v, log_g, eps=pr.EPS):
    """The retention as a [t, t] weight matrix a head (float64 numpy)."""
    q, k, v, log_g = (np.asarray(t, np.float64) for t in (q, k, v, log_g))
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    c = np.cumsum(log_g, axis=1)
    causal = np.tril(np.ones((s, s)))
    out = np.zeros((b, s, h, d))
    for i in range(h):
        j = i // rep
        a = np.einsum("btd,bud->btu", q[:, :, i], k[:, :, j]) ** 2 / d
        a = a * causal * np.exp(
            np.where(causal > 0, c[:, :, None, j] - c[:, None, :, j], 0.0))
        out[:, :, i] = np.einsum("btu,bud->btd", a, v[:, :, j]) \
            / (a.sum(-1, keepdims=True) + eps)
    return out


def draw(seed, batch, seq, heads, kv_heads, d, keep=(0.9, 0.999)):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((batch, seq, n, d)), F32)
               for n in (heads, kv_heads, kv_heads))
    log_g = jnp.log(jnp.asarray(
        rng.uniform(*keep, (batch, seq, kv_heads)), F32))
    return q, k, v, log_g


def zeros(slots, kv_heads, d):
    return tuple(jnp.zeros(s, F32) for s in pr.state_shapes(slots, kv_heads,
                                                            d))


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    pr.reset_retention_status()
    yield
    pr.reset_retention_status()


@pytest.mark.parametrize("d", [16, 128])
def test_phi_dot_phi_is_the_squared_dot_over_d(d):
    rng = np.random.default_rng(d)
    x, y = (rng.standard_normal((5, d)) for _ in range(2))
    px, py = (np.asarray(pr.phi(jnp.asarray(t, F32)), np.float64)
              for t in (x, y))
    assert px.shape == (5, d // 2 + 1, d)
    np.testing.assert_allclose((px * py).sum((-1, -2)),
                               (x * y).sum(-1) ** 2 / d, rtol=1e-4, atol=1e-4)
    # d(d+1)/2 live values; the last tile's upper half is always zero
    assert (px != 0).sum() == 5 * d * (d + 1) // 2
    assert not px[:, -1, d // 2:].any()


@pytest.mark.parametrize("chunk", [4, 5, 13])
def test_scan_chunked_and_quadratic_agree_at_a_head_of_16(chunk):
    """Chunks that do (4) and do not (5, 13 > the tail) divide the length,
    each padded to the chunk's width and masked, through the dispatch
    rule's fallback."""
    batch, seq, heads, kvh, d = 2, 12, 4, 2, 16
    q, k, v, log_g = draw(0, batch, seq, heads, kvh, d)
    want = quadratic(q, k, v, log_g)
    states, sums = zeros(batch, kvh, d)
    y, s_end, z_end = pr.retention_scan(q, k, v, log_g, states, sums)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
    slots = jnp.arange(batch)
    got = []
    for at in range(0, seq, chunk):
        n = min(chunk, seq - at)
        pad = lambda t: jnp.pad(t[:, at:at + n], [(0, 0), (0, chunk - n)]
                                + [(0, 0)] * (t.ndim - 2))
        valid = jnp.broadcast_to(jnp.arange(chunk) < n, (batch, chunk))
        out, states, sums = pr.retention_chunk_fwd(
            pad(q), pad(k), pad(v), pad(log_g), states, sums, slots,
            jnp.full((batch,), at == 0), valid)
        got.append(out[:, :n])
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(states, s_end, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sums, z_end, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", ["chunk_fwd", "step"])
def test_five_query_heads_on_one_state_equal_five_separate_states(path):
    batch, seq, rep, d = 2, 6, 5, 16
    q, k, v, log_g = draw(1, batch, seq, rep, 1, d)
    fresh = jnp.ones((batch,), bool)

    def run(q, k, v, log_g, kvh):
        states, sums = zeros(batch, kvh, d)
        if path == "chunk_fwd":
            return pr.retention_chunk_fwd(
                q, k, v, log_g, states, sums, jnp.arange(batch), fresh,
                jnp.ones((batch, seq), bool))
        ys = []
        for t in range(seq):
            y, states, sums = pr.retention_step(
                q[:, t], k[:, t], v[:, t], log_g[:, t], states, sums,
                fresh & (t == 0), fresh)
            ys.append(y)
        return jnp.stack(ys, 1), states, sums

    shared, s1, z1 = run(q, k, v, log_g, 1)
    apart, s5, z5 = run(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2),
                        jnp.repeat(log_g, rep, 2), rep)
    # (the readout's sums run in another order: the first positions, which
    # divide by a single weight, show it)
    np.testing.assert_allclose(shared, apart, rtol=2e-3, atol=2e-4)
    for j in range(rep):        # five copies of the one state
        np.testing.assert_array_equal(s5[:, j], s1[:, 0])
        np.testing.assert_array_equal(z5[:, j], z1[:, 0])


def _hold_and_reset(d, heads, kvh, seq):
    """Slots 0..3: live and carried, live and fresh, held, held AND fresh
    (hold comes first). Returns what to compare, per pass."""
    slots = 4
    rng = np.random.default_rng(2)
    states, sums = (jnp.asarray(rng.standard_normal(s), F32) * 0.1
                    for s in pr.state_shapes(slots, kvh, d))
    q, k, v, log_g = draw(3, slots, seq, heads, kvh, d)
    fresh = jnp.asarray([False, True, False, True])
    active = jnp.asarray([True, True, False, False])
    start = jnp.where(jnp.asarray([False, True, False, False])[
        :, None, None, None, None], 0.0, states)
    start_z = jnp.where(jnp.asarray([False, True, False, False])[
        :, None, None, None], 0.0, sums)
    live = jnp.broadcast_to(active[:, None], (slots, seq))
    want = pr.retention_scan(q, k, v, log_g, start, start_z, live)
    return (q, k, v, log_g, states, sums, fresh, active, live), want


@pytest.mark.parametrize("d,mode", [(16, "scan"), (128, "pallas")])
@pytest.mark.parametrize("pass_", ["step", "chunk_fwd"])
def test_hold_is_bit_for_bit_reset_at_fresh_and_hold_comes_first(
        d, mode, pass_, monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET",
                       "1" if mode == "pallas" else "0")
    pr.reset_retention_status()
    heads, kvh = (5, 1) if d == 128 else (4, 2)
    seq = 1 if pass_ == "step" else 128
    (q, k, v, log_g, states, sums, fresh, active, live), want = \
        _hold_and_reset(d, heads, kvh, seq)
    if pass_ == "step":
        y, s, z = pr.retention_step(q[:, 0], k[:, 0], v[:, 0], log_g[:, 0],
                                    states, sums, fresh, active)
        y = y[:, None]
    else:
        y, s, z = pr.retention_chunk_fwd(q, k, v, log_g, states, sums,
                                         jnp.arange(4), fresh, live)
    for held in (2, 3):
        np.testing.assert_array_equal(s[held], states[held])
        np.testing.assert_array_equal(z[held], sums[held])
    np.testing.assert_allclose(y[:2], want[0][:2], rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(s[:2], want[1][:2], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z[:2], want[2][:2], rtol=1e-5, atol=1e-5)
    assert {(c["pass"], c["path"]) for c in pr.retention_status()} \
        == {(pass_, mode)}


def test_a_chunk_then_steps_equal_one_long_scan_through_the_kernels(
        interpret):
    """Both kernels in the interpreter at a head of 128 (the shapes of the
    test above, so that nothing compiles twice), bf16 operands as the model
    hands them: a chunk of 128, a second whose rows have 128, 100, 0 and 128
    real positions, then two steps in which the second and third rows are
    held; every output and every row's state against the scan."""
    slots, kvh, rep, d = 4, 1, 5, 128
    seq = 128 + 128 + 2
    q, k, v, log_g = draw(4, slots, seq, kvh * rep, kvh, d)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    states, sums = zeros(slots, kvh, d)
    upto = jnp.asarray([seq, 128 + 100, 128, seq])
    live = jnp.arange(seq)[None, :] < upto[:, None]
    want, s_end, z_end = pr.retention_scan(q, k, v, log_g, states, sums, live)
    rows = jnp.arange(slots)
    got = []
    for at in (0, 128):
        cut = slice(at, at + 128)
        y, states, sums = pr.retention_chunk_fwd(
            q[:, cut], k[:, cut], v[:, cut], log_g[:, cut], states, sums,
            rows, jnp.full((slots,), at == 0), live[:, cut])
        got.append(y)
    for t in range(256, seq):
        y, states, sums = pr.retention_step(
            q[:, t], k[:, t], v[:, t], log_g[:, t], states, sums,
            jnp.zeros((slots,), bool), live[:, t])
        got.append(y[:, None])
    got = jnp.concatenate(got, 1)
    # the first positions divide by a single weight, (q . k)^2 / d: there
    # the two paths' roundings show, a few 1e-4
    for row, n in enumerate(upto):
        np.testing.assert_allclose(got[row, :n], want[row, :n], rtol=1e-3,
                                   atol=5e-4)
    scale = float(jnp.max(jnp.abs(s_end)))
    np.testing.assert_allclose(states, s_end, atol=2e-6 * scale)
    np.testing.assert_allclose(sums, z_end, rtol=1e-5,
                               atol=2e-6 * float(jnp.max(z_end)))
    status = pr.retention_status()
    assert {(c["pass"], c["path"], c["dtype"]) for c in status} == {
        ("chunk_fwd", "pallas", "bfloat16"), ("step", "pallas", "bfloat16")}
    assert {tuple(c["shape"]) for c in status} == {
        (4, 128, 5, 1, 128), (4, 1, 5, 1, 128)}
    assert sum(c["calls"] for c in status if c["pass"] == "step") == 2


@pytest.mark.parametrize("shape,pass_,reason", [
    ((1, 128, 4, 2, 16), "chunk_fwd", "lane width"),
    ((1, 128, 6, 1, 128), "chunk_fwd", "sublane tile"),
    ((1, 100, 5, 1, 128), "chunk_fwd", "multiple of 128"),
    ((2, 1, 4, 2, 16), "step", "lane width"),
])
def test_the_dispatch_rule_sends_what_the_kernels_do_not_take_to_the_scan(
        shape, pass_, reason, interpret):
    batch, seq, heads, kvh, d = shape
    q, k, v, log_g = draw(5, batch, seq, heads, kvh, d)
    states, sums = zeros(batch, kvh, d)
    on = jnp.ones((batch,), bool)
    if pass_ == "step":
        pr.retention_step(q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], states,
                          sums, on, on)
    else:
        pr.retention_chunk_fwd(q, k, v, log_g, states, sums,
                               jnp.arange(batch), on,
                               jnp.ones((batch, seq), bool))
    (call,) = pr.retention_status()
    assert (call["pass"], call["path"], call["calls"]) == (pass_, "scan", 1)
    assert reason in call["reason"] and call["shape"] == list(shape)


def test_off_the_chip_and_out_of_the_interpreter_every_call_is_the_scan(
        monkeypatch):
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    pr.reset_retention_status()
    q, k, v, log_g = draw(6, 1, 128, 5, 1, 128)
    states, sums = zeros(1, 1, 128)
    jax.eval_shape(lambda: pr.retention_chunk_fwd(
        q, k, v, log_g, states, sums, jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), bool), jnp.ones((1, 128), bool)))
    (call,) = pr.retention_status()
    assert call["path"] == "scan" and call["reason"] == "platform cpu"
    pr.reset_retention_status()
    assert pr.retention_status() == []
