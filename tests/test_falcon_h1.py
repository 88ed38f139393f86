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
    assert engine.step_stats()["prefill"] == 1 + 1 + 3
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
