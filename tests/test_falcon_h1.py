"""`models/falcon_h1.py` on the CPU at a tiny size: the full forward and the
engine's prefill-then-decode against the plain float32 reference
(`benchmarks/reference/falcon_h1_plain.py`), and what per-slot state asks
of the engine: hold, reset, no prefix adoption, no speculation."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import falcon_h1_plain as plain  # noqa: E402
from ray_tpu.inference.engine import (EngineConfig,  # noqa: E402
                                      InferenceEngine)
from ray_tpu.models.falcon_h1 import (FalconH1, FalconH1Config,  # noqa: E402
                                      published_weights)

# float32 parameters at the tiny size: the served path and the reference
# differ by the order of summation alone. Logits are ~3e-3 there.
TOL = 2e-7
# Beside states and tails of ~1 (`test_the_fused_step_is_the_two_steps`
# fills them with a normal draw) where logits are ~3e-3.
STATE_TOL = 5e-6


@pytest.fixture(scope="module")
def tiny():
    cfg = FalconH1Config.tiny()
    model = FalconH1(cfg)
    params = model.init(jax.random.PRNGKey(1))
    # norms, biases and D away from their trivial initial values
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))

    def jitter(tree):
        return {k: (v + 0.1 * jax.random.normal(next(keys), v.shape, v.dtype)
                    if k.endswith("norm") or k in ("conv_b", "D") else v)
                for k, v in tree.items()}

    params = {**jitter({k: v for k, v in params.items() if k != "layers"}),
              "layers": [jitter(lp) for lp in params["layers"]]}
    pub = {k: (list(v) if isinstance(v, tuple) else v)
           for k, v in dataclasses.asdict(cfg).items()}
    return model, params, pub


def reference_logits(tiny, ids):
    _, params, pub = tiny
    top, layer = published_weights(params)
    return plain.forward(top, layer, jnp.asarray(ids, jnp.int32), pub)


def engine_of(tiny, **kwargs):
    model, params, _ = tiny
    cfg = dict(batch_slots=3, block_size=4, num_blocks=64,
               max_blocks_per_seq=16, prefill_chunk=8)
    cfg.update(kwargs)
    return InferenceEngine(EngineConfig(**cfg), model=model, params=params)


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 96, n)]


def assert_served_as_the_reference(tiny, reqs):
    """Every served token is the reference's own greedy choice given the
    tokens before it, to within `TOL` of its best logit."""
    for req in reqs:
        assert req.state == "FINISHED", req.error
        ids = [req.prompt + req.generated[:-1]]
        rows = reference_logits(tiny, ids)[0][len(req.prompt) - 1:]
        gaps = plain.chosen_token_gaps(rows, req.generated)
        assert float(gaps.max()) <= TOL, (req.request_id, gaps)


def test_full_forward_is_the_plain_reference(tiny):
    model, params, _ = tiny
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 37), 0, 96)
    got = model.forward(params, ids, block_size=8)
    want = reference_logits(tiny, ids)
    assert float(jnp.abs(got - want).max()) <= TOL
    assert float(jnp.abs(want).max()) > 1e-3


def test_logits_at_last_idx_are_the_rows_of_the_full_logits(tiny):
    model, params, _ = tiny
    ids = jax.random.randint(jax.random.PRNGKey(6), (2, 9), 0, 96)
    args = (jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.ones((2, 9), bool), None,
            jnp.arange(2, dtype=jnp.int32))
    full, _ = model.paged_step(params, ids, model.paged_cache(8, 4, None, 2),
                               *args)
    last = jnp.asarray([8, 3], jnp.int32)
    some, _ = model.paged_step(params, ids, model.paged_cache(8, 4, None, 2),
                               *args, last)
    np.testing.assert_allclose(some, full[jnp.arange(2), last], atol=1e-7)


def _case_three_rows_interleaved(tiny):
    """A prompt of three chunks prefills while two rows decode: its state
    and convolution tail sit in its slot across those decode steps."""
    engine = engine_of(tiny)
    mix = [(prompt(5, 1), 9), (prompt(3, 2), 8), (prompt(20, 3), 6)]
    reqs = [engine.add_request(p, n) for p, n in mix]
    engine.run_until_idle()
    # five chunks: the first finds no row decoding, the others ride
    steps = engine.step_stats()
    assert (steps["prefill"], steps["chunks_aboard"]) == (1, 1 + 3)
    assert engine.stats()["state"]["resets"] == 3
    return engine, reqs


def _case_reused_slots(tiny):
    """More requests than slots: a slot's next owner starts from zero."""
    engine = engine_of(tiny, batch_slots=2)
    reqs = [engine.add_request(prompt(4 + 3 * i, 10 + i), 3 + i)
            for i in range(5)]
    engine.run_until_idle()
    assert engine.stats()["state"]["resets"] == 5
    return engine, reqs


def _case_preempted_and_requeued(tiny):
    """A pool too small for both rows: the victim's blocks go, it is
    queued again and prefills prompt + generated from position 0."""
    engine = engine_of(tiny, batch_slots=2, block_size=2, num_blocks=9,
                       max_blocks_per_seq=8, prefill_chunk=4)
    reqs = [engine.add_request(prompt(3, 20 + i), 10) for i in range(2)]
    engine.run_until_idle()
    stats = engine.stats()
    assert stats["preemptions"] >= 1
    assert stats["state"]["resets"] == 2 + stats["preemptions"]
    return engine, reqs


def _case_a_shared_prefix_is_not_adopted(tiny):
    """The prefix cache is asked for and the model refuses it: the second
    request prefills its whole prompt and nothing stays in the arena."""
    engine = engine_of(tiny, prefix_cache_enabled=True)
    shared = prompt(16, 30)
    first = engine.add_request(shared + [7, 8], 5)
    engine.run_until_idle()
    second = engine.add_request(shared + [7, 8, 9], 4)
    engine.run_until_idle()
    stats = engine.stats()
    assert second.cached_tokens == 0
    assert stats["state"]["prefix_adoptions_refused"] == 2
    assert stats["prefix_cache"]["cached_blocks"] == 0
    assert stats["kv"]["blocks_in_use"] == 0
    return engine, [first, second]


ENGINE_CASES = {
    "three_rows_interleaved": _case_three_rows_interleaved,
    "reused_slots": _case_reused_slots,
    "preempted_and_requeued": _case_preempted_and_requeued,
    "a_shared_prefix_is_not_adopted": _case_a_shared_prefix_is_not_adopted,
}


@pytest.mark.parametrize("case", sorted(ENGINE_CASES))
def test_the_engine_serves_it_as_the_reference_computes_it(tiny, case):
    engine, reqs = ENGINE_CASES[case](tiny)
    assert_served_as_the_reference(tiny, reqs)
    assert not engine.has_work()
    engine.check_no_leaks()
    stats = engine.stats()
    assert stats["prefill_compiles"] == stats["decode_compiles"] == 1
    model = tiny[0]
    assert stats["state"]["slots"] == engine.config.batch_slots
    assert stats["state"]["bytes"] == model.slot_state_bytes \
        * engine.config.batch_slots == sum(
            a.size * a.dtype.itemsize
            for a in engine._arenas["ssm"] + engine._arenas["conv"])


# --------------------------------------------------------------------------- #
# a chunk aboard the decode step
# --------------------------------------------------------------------------- #


class _NoFusedStep(FalconH1):
    """The model with its fused step hidden (`PagedModel`'s "not offered"):
    an engine over it keeps the two programs."""

    paged_step_with_chunk = None


class _NoReset(FalconH1):
    """`benchmarks/falconh1_controls.py`'s first planted fault: a row that
    starts at position 0 inherits its slot's state."""

    def state_rows(self, row_pos, write_mask):
        return jnp.zeros(row_pos.shape, bool), write_mask


class _AdvanceMasked(FalconH1):
    """Its second: masked positions advance the state."""

    def state_rows(self, row_pos, write_mask):
        fresh, _ = super().state_rows(row_pos, write_mask)
        return fresh, jnp.ones_like(write_mask)


# (the chunk's first position, its live positions of 8)
CHUNKS_ABOARD = {
    # position 0, into a slot that holds another request's leavings
    "fresh": (0, 5),
    # a later chunk of a long prompt: the slot's state is its own
    "carried": (8, 7),
}


@pytest.mark.parametrize("case", sorted(CHUNKS_ABOARD))
def test_the_fused_step_is_the_two_steps(tiny, case):
    """`paged_step_with_chunk` against `paged_step` called twice, chunk
    first as the engine's two programs run: the logits, the KV arenas and
    EVERY slot's recurrent state and convolution tail, with a dead slot
    among the decode rows (the chunk's own) and a padded chunk."""
    model, params, _ = tiny
    slots, chunk, bsz, width = 3, 8, 4, 12
    start, n_live = CHUNKS_ABOARD[case]
    cache = model.paged_cache(1 + (slots + 1) * width, bsz, None, slots)
    # what came before: rows of K/V, states and tails, whatever they hold
    leaves, tree = jax.tree.flatten(cache)
    keys = jax.random.split(jax.random.PRNGKey(8), len(leaves))
    cache = jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])
    tables = 1 + jnp.arange((slots + 1) * width, dtype=jnp.int32).reshape(
        slots + 1, width)
    tokens = jnp.array([[5], [9], [0]], jnp.int32)
    pos, wmask = jnp.array([21, 37, 0]), jnp.array([[True], [True], [False]])
    chunk_live = jnp.arange(chunk)[None] < n_live
    chunk_ids = jnp.where(chunk_live, jax.random.randint(
        jax.random.PRNGKey(7), (1, chunk), 1, 96), 0)
    chunk_pos, last = jnp.array([start]), jnp.array([n_live - 1])
    chunk_slot = jnp.array([2], jnp.int32)

    @jax.jit
    def twice(cache):
        chunk_logits, cache = model.paged_step(
            params, chunk_ids, cache, tables[3:], chunk_pos, chunk_live,
            None, chunk_slot, last)
        logits, cache = model.paged_step(params, tokens, cache, tables[:3],
                                         pos, wmask)
        return logits[:, -1], chunk_logits, cache

    @jax.jit
    def fused(cache):
        return model.paged_step_with_chunk(
            params, tokens, chunk_ids, cache, tables[:3], pos, wmask,
            tables[3:], chunk_pos, chunk_live, chunk_slot, last)

    want, got = twice(cache), fused(cache)
    assert got[0].shape == (slots, 96) and got[1].shape == (1, 96)
    for a, b in zip(want[:2], got[:2]):
        assert float(jnp.abs(a[:2] - b[:2]).max()) <= TOL
    assert float(jnp.abs(want[0]).max()) > 1e-3
    # the arenas everywhere but the trash block, where both write their
    # dead rows; the state and the tail of every slot
    for (wk, wv), (gk, gv) in zip(want[2]["kv"], got[2]["kv"]):
        np.testing.assert_allclose(gk[1:], wk[1:], atol=TOL)
        np.testing.assert_allclose(gv[1:], wv[1:], atol=TOL)
    for name in ("ssm", "conv"):
        for was, a, b in zip(cache[name], want[2][name], got[2][name]):
            np.testing.assert_allclose(b, a, atol=STATE_TOL)
            # and every slot's moved: two rows decoded, the third took
            # the chunk
            assert all(float(jnp.abs(a[i] - was[i]).max()) > 1e-3
                       for i in range(slots))


def _state_gaps(engine, other, name):
    """A layer each: the largest distance between the two engines' `name`
    ("ssm" or "conv") of any slot, as a share of the first's largest."""
    return [float(np.abs(np.asarray(a) - np.asarray(b)).max()
                  / np.abs(np.asarray(a)).max())
            for a, b in zip(engine._arenas[name], other._arenas[name])]


def test_a_chunk_aboard_changes_nothing_that_is_served(tiny):
    """The same engine over the model and over the model with its fused
    step hidden: the same greedy tokens for a mix whose chunks land while
    others decode (prompts of one to three chunks; an early leaver whose
    slot the last request is admitted into), and the same state left in
    every slot. The planted faults of the state's bookkeeping change that
    state through the fused path as they do through the two programs."""
    _, params, _ = tiny
    mix = [(prompt(5, 1), 12), (prompt(12, 2), 2), (prompt(20, 3), 9),
           (prompt(7, 4), 6)]

    def serve(cls):
        engine = engine_of((cls(tiny[0].config), params, None))
        reqs = [engine.add_request(p, n) for p, n in mix]
        engine.run_until_idle()
        engine.check_no_leaks()
        return engine, reqs

    (fused, got), (plain_engine, want) = serve(FalconH1), serve(_NoFusedStep)
    for a, b in zip(got, want):
        assert a.state == b.state == "FINISHED", (a.error, b.error)
        assert a.generated == b.generated
    assert_served_as_the_reference(tiny, got)
    for name in ("ssm", "conv"):
        assert max(_state_gaps(fused, plain_engine, name)) <= 1e-5
    steps, plain_steps = fused.step_stats(), plain_engine.step_stats()
    # 1 + 2 + 3 + 1 chunks: all but the first, which found no row
    # decoding, rode (the fourth request's into the slot the second left)
    assert (steps["prefill"], steps["chunks_aboard"]) == (1, 6)
    assert (plain_steps["prefill"], plain_steps["chunks_aboard"]) == (7, 0)
    stats = fused.stats()
    assert stats["prefill_compiles"] == stats["decode_compiles"] \
        == stats["decode_with_chunk_compiles"] == 1
    assert stats["state"]["resets"] == 4
    assert plain_engine.stats()["decode_with_chunk_compiles"] == 0
    # the planted faults, through the fused program
    for fault in (_NoReset, _AdvanceMasked):
        engine, _ = serve(fault)
        assert engine.step_stats()["chunks_aboard"] == 6
        gaps = _state_gaps(fused, engine, "ssm")
        assert min(gaps) > 0.05, (fault.__name__, gaps)


def test_a_model_without_slot_state_reports_none():
    engine = InferenceEngine(EngineConfig())
    assert engine.stats()["state"] == {
        "slots": 0, "bytes": 0, "resets": 0, "prefix_adoptions_refused": 0}


REFUSALS = {
    "speculation": lambda m, p: InferenceEngine(
        EngineConfig(spec_decode_draft_len=2), model=m, params=p),
    "a_draft": lambda m, p: m.early_exit_draft(p),
    "adapter_banks": lambda m, p: m.adapter_banks(4, 8),
    "adapters_in_a_step": lambda m, p: m.paged_step(
        p, jnp.zeros((1, 1), jnp.int32), None, None, None, None, ((), ())),
    "a_cache_without_slots": lambda m, p: m.paged_cache(8, 4),
    "a_tp_mesh": lambda m, p: m.place_on_mesh(p, _TwoWayTp()),
}


class _TwoWayTp:
    axis_names = ("tp",)
    devices = np.zeros((2,))


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_it_does_not_have_it_says_plainly(tiny, what):
    model, params, _ = tiny
    with pytest.raises(ValueError):
        REFUSALS[what](model, params)


def test_published_keys_make_the_configuration():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "falcon-h1-34b-l6-serve.json")) as f:
        published = json.load(f)
    cfg = FalconH1Config.from_published(published)
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size) \
        == (6, 5120, 261120)
    assert cfg.in_proj_dim == 9248 and cfg.conv_dim == 5120
    assert cfg.rope_theta == 1e11 and cfg.mlp_multipliers[1] \
        == published["mlp_multipliers"][1]
    # per slot: 6 x (32 x 256 x 128 f32 + 3 x 5120 bf16)
    assert FalconH1(cfg).slot_state_bytes == 6 * (4194304 + 30720)
    with pytest.raises(ValueError, match="mamba_chunk_size"):
        FalconH1Config.from_published({**published, "mamba_chunk_size": 256})
