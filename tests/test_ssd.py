"""`ops/ssd.py` on the CPU at small sizes: the chunked kernel and the
one-token kernel (Pallas, in the interpreter) and their scan fallback
against the token-by-token scan, with a state that is carried, held and
reset; and the convolution's carried tail."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.ops import ssd  # noqa: E402

H, P, N, G, SLOTS = 16, 128, 128, 2, 4


@pytest.fixture(params=["pallas", "scan"])
def path(request, monkeypatch):
    """Both paths of the dispatch rule: the kernels in the interpreter,
    and the scan every other call runs."""
    if request.param == "pallas":
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    ssd.reset_ssd_status()
    yield request.param
    assert {r["path"] for r in ssd.ssd_status()} == {request.param}


def inputs(seq, batch=1, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (batch, seq, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, seq, H)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7))
    b = jax.random.normal(k[3], (batch, seq, G, N), jnp.bfloat16) * 0.3
    c = jax.random.normal(k[4], (batch, seq, G, N), jnp.bfloat16) * 0.3
    states = jax.random.normal(k[5], (SLOTS, H, N, P))
    return x, dt, a, b, c, jnp.ones((H,)), states


def chunk(args, states, slot, fresh, n_valid, rows=slice(None)):
    x, dt, a, b, c, d, _ = args
    seq = x[:, rows].shape[1]
    return ssd.ssd_chunk_fwd(
        x[:, rows], dt[:, rows], a, b[:, rows], c[:, rows], d, states,
        jnp.array([slot]), jnp.array([fresh]),
        jnp.arange(seq)[None] < n_valid)


CHUNK_CASES = {
    # (seq, slot, fresh, valid positions)
    "carried": (256, 2, False, 256),
    "fresh_ignores_the_slot": (256, 1, True, 256),
    "padded_tail": (256, 3, False, 200),
    "one_chunk": (128, 0, False, 77),
}


@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
def test_chunk_fwd_is_the_scan(path, case):
    seq, slot, fresh, n_valid = CHUNK_CASES[case]
    args = inputs(seq)
    x, dt, a, b, c, d, states = args
    y, out = chunk(args, states, slot, fresh, n_valid)
    valid = jnp.arange(seq)[None] < n_valid
    start = jnp.zeros_like(states[slot]) if fresh else states[slot]
    want_y, want = ssd.ssd_scan(x, jnp.where(valid[..., None], dt, 0.0), a,
                                b, c, d, start[None])
    scale = float(jnp.abs(want_y).max())
    # bf16 operands on the kernel path; the scan path IS the definition
    tol = 4e-3 if path == "pallas" else 1e-6
    assert float(jnp.abs(y - want_y)[:, :n_valid].max()) <= tol * scale
    assert float(jnp.abs(out[slot] - want[0]).max()) \
        <= tol * float(jnp.abs(want).max())
    others = jnp.array([s for s in range(SLOTS) if s != slot])
    np.testing.assert_array_equal(out[others], states[others])


def test_two_calls_with_the_state_carried_are_one_call(path):
    args = inputs(256)
    states = args[-1]
    y_whole, whole = chunk(args, states, 1, True, 256)
    y_a, half = chunk(args, states, 1, True, 128, rows=slice(0, 128))
    y_b, both = chunk(args, half, 1, False, 128, rows=slice(128, 256))
    np.testing.assert_allclose(jnp.concatenate([y_a, y_b], axis=1), y_whole,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(both[1], whole[1], atol=1e-5, rtol=1e-5)


def test_a_chunk_of_padding_leaves_the_state_as_it_was(path):
    args = inputs(128)
    _, out = chunk(args, args[-1], 2, False, 0)
    np.testing.assert_array_equal(out, args[-1])


def test_step_updates_active_rows_holds_masked_ones_and_resets_fresh(path):
    x, dt, a, b, c, d, states = inputs(SLOTS)
    xs, dts, bs, cs = x[0], dt[0], b[0], c[0]        # a token a slot
    active = jnp.array([True, False, True, True])
    fresh = jnp.array([False, False, True, False])
    y, out = ssd.ssd_step(xs, dts, a, bs, cs, d, states, fresh, active)
    start = jnp.where(fresh[:, None, None, None], 0.0, states)
    want_y, want = ssd.ssd_scan(
        xs[:, None], jnp.where(active[:, None], dts, 0.0)[:, None], a,
        bs[:, None], cs[:, None], d, start)
    np.testing.assert_allclose(y[active], want_y[:, 0][active], atol=1e-4,
                               rtol=1e-5)
    np.testing.assert_allclose(out[active], want[active], atol=1e-5,
                               rtol=1e-6)
    np.testing.assert_array_equal(out[1], states[1])      # held, exactly


def test_a_shape_the_kernels_refuse_runs_the_scan_and_says_why(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    ssd.reset_ssd_status()
    x, dt, a, b, c, d, states = inputs(100)
    ssd.ssd_chunk_fwd(x, dt, a, b, c, d, states, jnp.array([0]),
                      jnp.array([True]), jnp.ones((1, 100), bool))
    (record,) = ssd.ssd_status()
    assert record["path"] == "scan" and "chunk" in record["reason"]
    assert record["shape"] == [1, 100, H, P, N]


def test_interpret_is_refused_on_the_tpu(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(ssd, "_platform", lambda: "tpu")
    x, dt, a, b, c, d, states = inputs(128)
    with pytest.raises(RuntimeError, match="CPU test switch"):
        ssd.ssd_chunk_fwd(x, dt, a, b, c, d, states, jnp.array([0]),
                          jnp.array([True]), jnp.ones((1, 128), bool))


CONV_CASES = {"whole": 12, "padded": 7, "held": 0}


@pytest.mark.parametrize("case", sorted(CONV_CASES))
def test_conv_with_a_carried_tail_is_the_conv_of_the_whole(case):
    n_valid = CONV_CASES[case]
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    channels, width, seq = 24, 4, 12
    w = jax.random.normal(k[0], (channels, width))
    bias = jax.random.normal(k[1], (channels,))
    x = jax.random.normal(k[2], (2, 20 + seq, channels))
    zeros = jnp.zeros((2, width - 1, channels))
    whole, _ = ssd.causal_conv1d_carried(x, w, bias, zeros,
                                         jnp.ones(x.shape[:2], bool))
    _, tail = ssd.causal_conv1d_carried(x[:, :20], w, bias, zeros,
                                        jnp.ones((2, 20), bool))
    np.testing.assert_array_equal(tail, x[:, 17:20])
    valid = jnp.broadcast_to(jnp.arange(seq)[None] < n_valid, (2, seq))
    y, new_tail = ssd.causal_conv1d_carried(x[:, 20:], w, bias, tail, valid)
    np.testing.assert_allclose(y[:, :n_valid], whole[:, 20:20 + n_valid],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(
        new_tail, x[:, 17 + n_valid:20 + n_valid])
