"""`models/brumby.py` on the CPU at a tiny size: the full forward and the
engine's prefill-then-decode against the plain float32 reference
(`benchmarks/reference/brumby_plain.py`, the quadratic form), and what a
cache that is per-slot state and nothing else asks of the engine: no arena,
no block counted against admission, a zero-width block table, the model's
own context bound; a model that pages keeps the programs it had."""

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

from benchmarks.reference import brumby_plain as plain  # noqa: E402
from ray_tpu.inference.engine import (EngineConfig,  # noqa: E402
                                      InferenceEngine)
from ray_tpu.inference.kv_cache import NoBlocks  # noqa: E402
from ray_tpu.models.brumby import (Brumby, BrumbyConfig,  # noqa: E402
                                   published_weights)
from ray_tpu.ops import power_retention  # noqa: E402

# float32 parameters at the tiny size: the served path (the state form) and
# the reference (the quadratic form) differ by the order of summation
# alone. Logits are ~0.5 there. A sequence's FIRST position divides by a
# single weight, (q . k)^2 / d, which a head of 16 can bring near eps_r:
# the seeds below keep it away.
TOL = 5e-6


@pytest.fixture(scope="module")
def tiny():
    cfg = BrumbyConfig.tiny()
    model = Brumby(cfg)
    params = model.init(jax.random.PRNGKey(1))
    # norms away from their trivial initial values
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))

    def jitter(tree):
        return {k: (v + 0.1 * jax.random.normal(next(keys), v.shape, v.dtype)
                    if k.endswith("norm") else v) for k, v in tree.items()}

    params = {**jitter({k: v for k, v in params.items() if k != "layers"}),
              "layers": [jitter(lp) for lp in params["layers"]]}
    return model, params, dataclasses.asdict(cfg)


def reference_logits(tiny, ids, **kwargs):
    _, params, pub = tiny
    top, layer = published_weights(params)
    return plain.forward(top, layer, jnp.asarray(ids, jnp.int32), pub,
                         **kwargs)


def engine_of(tiny, **kwargs):
    model, params, _ = tiny
    cfg = dict(batch_slots=3, block_size=4, prefill_chunk=8)
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
    got = model.forward(params, ids)
    want = reference_logits(tiny, ids)
    assert float(jnp.abs(got - want).max()) <= TOL
    assert float(jnp.abs(want).max()) > 1e-2


def test_prefill_in_chunks_then_decode_is_the_full_forward(tiny):
    """A prompt of 19 in chunks of 8 (the last padded and masked), then 5
    single tokens through the decode spelling (`slots` None): the logits of
    every step against the reference's rows, and what probes read of the
    final state against the reference's stateless answer."""
    model, params, pub = tiny
    ids = jax.random.randint(jax.random.PRNGKey(7), (1, 24), 1, 96)
    probes = jax.random.normal(jax.random.PRNGKey(8), (6, 16))
    want, reads = reference_logits(tiny, ids, probes=probes)
    cache = model.paged_cache(0, 4, None, 1)
    none = jnp.zeros((1, 0), jnp.int32)
    step = jax.jit(model.paged_step)
    for at in range(0, 19, 8):
        n = min(8, 19 - at)
        chunk = jnp.zeros((1, 8), jnp.int32).at[0, :n].set(ids[0, at:at + n])
        logits, cache = step(
            params, chunk, cache, none, jnp.asarray([at]),
            (jnp.arange(8) < n)[None], None, jnp.asarray([0]))
        assert float(jnp.abs(logits[0, :n] - want[0, at:at + n]).max()) <= TOL
    for at in range(19, 24):
        logits, cache = step(
            params, ids[:, at:at + 1], cache, none, jnp.asarray([at]),
            jnp.ones((1, 1), bool))
        assert float(jnp.abs(logits[0, 0] - want[0, at]).max()) <= TOL
    pp = power_retention.phi(probes)
    for layer, (want_v, want_z) in enumerate(reads):
        got_v = jnp.einsum("nta,sjtea->sjne", pp, cache["state"][layer])
        got_z = jnp.einsum("nta,sjta->sjn", pp, cache["sums"][layer])
        np.testing.assert_allclose(got_v, want_v, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got_z, want_z, rtol=1e-4, atol=1e-5)


def test_logits_at_last_idx_are_the_rows_of_the_full_logits(tiny):
    model, params, _ = tiny
    ids = jax.random.randint(jax.random.PRNGKey(6), (2, 9), 0, 96)
    args = (jnp.zeros((2, 0), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.ones((2, 9), bool), None, jnp.arange(2, dtype=jnp.int32))
    full, _ = model.paged_step(params, ids, model.paged_cache(0, 4, None, 2),
                               *args)
    last = jnp.asarray([8, 3], jnp.int32)
    some, _ = model.paged_step(params, ids, model.paged_cache(0, 4, None, 2),
                               *args, last)
    np.testing.assert_allclose(some, full[jnp.arange(2), last], atol=1e-7)


def _case_three_rows_interleaved(tiny):
    """A prompt of three chunks prefills while two rows decode: its state
    sits in its slot, held, across those decode steps."""
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
    """No block ever runs out, so the engine never preempts this model on
    its own; a preemption from outside (what a scheduler above may do)
    frees the slot, queues the victim again, and it prefills prompt +
    generated from position 0 into whatever slot it is given."""
    engine = engine_of(tiny, batch_slots=2, prefill_chunk=4)
    reqs = [engine.add_request(prompt(3, 20 + i), 10) for i in range(2)]
    for _ in range(6):
        engine.step()
    with engine._lock:
        assert engine._preempt_one()
    engine.run_until_idle()
    stats = engine.stats()
    assert stats["preemptions"] == 1 and stats["state"]["resets"] == 3
    assert max(r.preemptions for r in reqs) == 1
    return engine, reqs


def _case_many_more_tokens_than_blocks_would_allow(tiny):
    """`num_blocks` 2 and `max_blocks_per_seq` 1 would hold 4 tokens a
    sequence and one sequence in all: neither is looked at."""
    engine = engine_of(tiny, num_blocks=2, max_blocks_per_seq=1)
    reqs = [engine.add_request(prompt(30, 40 + i), 12) for i in range(4)]
    engine.run_until_idle()
    assert engine.stats()["preemptions"] == 0
    return engine, reqs


ENGINE_CASES = {
    "three_rows_interleaved": _case_three_rows_interleaved,
    "reused_slots": _case_reused_slots,
    "preempted_and_requeued": _case_preempted_and_requeued,
    "many_more_tokens_than_blocks_would_allow":
        _case_many_more_tokens_than_blocks_would_allow,
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
            for a in jax.tree.leaves(engine._arenas))


def test_the_cache_is_per_slot_state_and_nothing_else(tiny):
    """No arena leaf, no block, 0 KV bytes; admission is by free slots
    alone; the block table the programs are handed is zero blocks wide."""
    engine = engine_of(tiny, prefix_cache_enabled=True)
    model = tiny[0]
    assert sorted(engine._arenas) == ["state", "sums"]
    shapes = {a.shape for a in jax.tree.leaves(engine._arenas)}
    assert shapes == {(3, 2, 9, 16, 16), (3, 2, 9, 16)}
    assert isinstance(engine._bm, NoBlocks)
    assert engine._bm.fits(10 ** 9) and engine._bm.capacity == 0
    reqs = [engine.add_request(prompt(100, i), 20) for i in range(7)]
    engine.step()
    stats = engine.stats()
    assert stats["running"] == 3 and stats["queue_depth"] == 4
    assert stats["kv"] == {"num_blocks": 0, "block_size": 4,
                           "blocks_in_use": 0, "blocks_free": 0,
                           "peak_blocks_in_use": 0, "sequences": 3,
                           "bytes": 0}
    assert stats["prefix_cache"]["enabled"] is False
    assert engine._block_table_rows(reqs[:3]).shape == (3, 0)
    engine.run_until_idle()
    assert all(r.state == "FINISHED" for r in reqs)
    assert engine.stats()["preemptions"] == 0
    assert model.pageless_context == 128


@pytest.mark.parametrize("total,fits", [(128, True), (129, False)])
def test_the_context_bound_is_the_models_own(tiny, total, fits):
    """`max_position_embeddings` positions (32,768 as published, 128 at
    the tiny size), whatever `max_blocks_per_seq` x `block_size` says."""
    engine = engine_of(tiny, max_blocks_per_seq=2)
    assert Brumby(BrumbyConfig()).pageless_context == 32768
    if fits:
        engine.add_request(prompt(total - 8, 0), 8)
        engine.run_until_idle()
        assert engine.stats()["requests_finished"] == 1
    else:
        with pytest.raises(ValueError, match="context is 128 positions"):
            engine.add_request(prompt(total - 8, 0), 8)


def test_a_chunk_wider_than_the_context_is_refused(tiny):
    with pytest.raises(ValueError, match="prefill_chunk"):
        engine_of(tiny, prefill_chunk=256)


def _paging_models():
    from ray_tpu.inference.api import preset_model
    from ray_tpu.models.falcon_h1 import FalconH1, FalconH1Config

    falcon = FalconH1(FalconH1Config.tiny())
    return {"llama": lambda: preset_model("tiny", 256),
            "falcon_h1": lambda: (falcon,
                                  falcon.init(jax.random.PRNGKey(0)))}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("family", ["llama", "falcon_h1"])
def test_a_model_that_pages_keeps_the_programs_it_had(family, program):
    """For a model with no `pageless_context` the engine builds what it
    built before: a `BlockManager` over `num_blocks`, a table
    `max_blocks_per_seq` wide, and programs whose lowered text is that of
    the same step spelled directly against `model.paged_step`."""
    from ray_tpu.inference.kv_cache import BlockManager

    model, params = _paging_models()[family]()
    engine = InferenceEngine(
        EngineConfig(batch_slots=3, block_size=4, num_blocks=32,
                     max_blocks_per_seq=16, prefill_chunk=8),
        model=model, params=params)
    assert type(engine._bm) is BlockManager
    assert engine._bm.num_blocks == 32 and engine._max_context == 64
    assert engine._block_table_rows([None] * 3).shape == (3, 16)
    assert engine.stats()["kv"]["bytes"] > 0

    def prefill_fn(params, arenas, adapters, tokens, ids, bt, pos, wmask,
                   last_idx, slot):
        logits, arenas = model.paged_step(params, ids, arenas, bt, pos,
                                          wmask, adapters, slot, last_idx)
        nxt = jnp.argmax(logits, axis=-1)
        return tokens.at[slot].set(nxt.astype(jnp.int32)), arenas

    def decode_fn(params, arenas, adapters, tokens, bt, pos, wmask):
        logits, arenas = model.paged_step(params, tokens[:, None], arenas,
                                          bt, pos, wmask, adapters)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return jnp.where(wmask[:, 0], nxt, tokens), arenas

    b, s = {"decode": (3, 1), "prefill": (1, 8)}[program]
    bt, pos = np.zeros((b, 16), np.int32), np.zeros(b, np.int32)
    wmask = np.zeros((b, s), bool)
    tail = {"decode": (bt, pos, wmask),
            "prefill": (np.zeros((b, s), np.int32), bt, pos, wmask,
                        np.zeros(1, np.int32), np.zeros(1, np.int32))}
    args = (engine._params, engine._arenas, None, engine._tokens,
            *tail[program])
    mine = {"decode": engine._decode_fn, "prefill": engine._prefill_fn}
    direct = {"decode": decode_fn, "prefill": prefill_fn}
    got = mine[program].lower(*args).as_text()
    want = jax.jit(direct[program], donate_argnums=(1,)).lower(
        *args).as_text()
    assert f"module @jit_{program}_fn" in got and got == want


REFUSALS = {
    "speculation": lambda m, p: InferenceEngine(
        EngineConfig(spec_decode_draft_len=2), model=m, params=p),
    "a_draft": lambda m, p: m.early_exit_draft(p),
    "adapter_banks": lambda m, p: m.adapter_banks(4, 8),
    "adapters_in_a_step": lambda m, p: m.paged_step(
        p, jnp.zeros((1, 1), jnp.int32), None, None, None, None, ((), ())),
    "a_cache_without_slots": lambda m, p: m.paged_cache(0, 4),
    "a_tp_mesh": lambda m, p: m.place_on_mesh(p, _TwoWayTp()),
    "another_degree": lambda m, p: BrumbyConfig.tiny(retention_degree=4),
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
                           "brumby-14b-l8-serve.json")) as f:
        published = json.load(f)
    cfg = BrumbyConfig.from_published(published)
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size,
            cfg.intermediate_size) == (8, 5120, 151936, 17408)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) \
        == (40, 8, 128)
    assert cfg.rope_theta == 1e6 and cfg.rms_norm_eps == 1e-6
    assert (cfg.retention_degree, cfg.eps_r) == (2, 1e-6)
    assert cfg.max_position_embeddings == 32768
    # per slot and layer: 8 KV heads x 65 tiles x 128 x 128 f32 and the
    # key sum (8,320 stored rows a head, of which 8,256 are live)
    assert Brumby(cfg).slot_state_bytes == 8 * (8 * 65 * 128 * 129 * 4)
    shapes = jax.eval_shape(lambda: Brumby(cfg).init(jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(shapes)
    assert sum(a.size for a in leaves) == 4_198_652_992
    layer = shapes["layers"][0]
    assert sum(a.size for a in jax.tree.leaves(layer)) == 330_352_904
    assert layer["wg"].shape == (5120, 8) and layer["bg"].shape == (8,)


def test_the_gates_bias_keeps_the_state_for_hundreds_of_positions(tiny):
    _, params, _ = tiny
    for lp in params["layers"]:
        keep = jax.nn.sigmoid(lp["bg"])
        assert float(keep.min()) >= 0.95 and float(keep.max()) <= 0.999
