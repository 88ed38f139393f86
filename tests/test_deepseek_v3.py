"""`models/deepseek_v3.py` on the CPU at a tiny size: the full forward and
the engine's chunked prefill, decode, prefix adoption, preemption and
rebuild against the plain float32 reference
(`benchmarks/reference/deepseek_v3_plain.py`: the expanded attention form,
a loop over the experts); the router's rule against a hand-written one; the
selection bias; and the shares of the expert layer against the whole."""

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

from benchmarks.reference import deepseek_v3_plain as plain  # noqa: E402
from ray_tpu.inference.engine import (EngineConfig,  # noqa: E402
                                      InferenceEngine)
from ray_tpu.models import deepseek_v3 as dsv3  # noqa: E402
from ray_tpu.models.deepseek_v3 import (DeepseekV3,  # noqa: E402
                                        DeepseekV3Config, published_weights)
from ray_tpu.ops import held_experts as moe  # noqa: E402

# float32 parameters at the tiny size: the served path (absorbed, paged)
# and the reference (expanded, dense) differ by the order of summation.
TOL = 5e-6


def _model(**overrides):
    cfg = DeepseekV3Config.tiny(**overrides)
    model = DeepseekV3(cfg)
    params = model.init(jax.random.PRNGKey(1))
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))

    def jitter(tree):      # norms away from their trivial initial values
        return {k: (v + 0.1 * jax.random.normal(next(keys), v.shape, v.dtype)
                    if k.endswith("norm") else v) for k, v in tree.items()}

    params = {**jitter({k: v for k, v in params.items() if k != "layers"}),
              "layers": [jitter(lp) for lp in params["layers"]]}
    pub = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    return model, params, pub


@pytest.fixture(scope="module")
def tiny():
    return _model()


@pytest.fixture(scope="module")
def two_layers():
    """One dense layer and one of experts: half of `tiny`'s time to
    compile, for the tests that build engines of their own."""
    return _model(num_hidden_layers=2)


def reference_logits(tiny, ids, **kwargs):
    model, params, pub = tiny
    top, layer = published_weights(model.config, params)
    return plain.forward(top, layer, jnp.asarray(ids, jnp.int32), pub,
                         **kwargs)


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 96, n)]


def settled_stats(engine):
    """`stats()` of an idle engine with its device counters as of now: a
    call dispatches their copy and a LATER call reads it (the first read
    may be of a copy an earlier call left)."""
    for _ in range(2):
        engine.stats()
        jax.block_until_ready(engine._counters_pending)
    return engine.stats()


def gaps_of(tiny, req):
    """How far each served token lies under the reference's best logit
    given the tokens before it."""
    ids = [req.prompt + req.generated[:-1]]
    rows = reference_logits(tiny, ids)[0][len(req.prompt) - 1:]
    return float(plain.chosen_token_gaps(rows, req.generated).max())


def test_full_forward_is_the_plain_reference(tiny):
    model, params, _ = tiny
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 37), 0, 96)
    want = reference_logits(tiny, ids)
    got = jax.jit(model.forward)(params, ids)
    assert float(jnp.abs(got - want).max()) <= TOL
    assert float(jnp.abs(want).max()) > 1e-2


def test_the_cache_row_is_the_normed_latent_and_the_rotated_key(tiny):
    """One arena a layer of [blocks, block, 256] with no heads; a token's
    row is the reference's `[c | k_r]`, then zero lanes; and at the same
    location the routing record holds the first expert layer's chosen
    experts above their gates."""
    model, params, _ = tiny
    cfg = model.config
    k = cfg.num_experts_per_tok
    ids = jax.random.randint(jax.random.PRNGKey(6), (1, 24), 1, 96)
    _, taps = reference_logits(tiny, ids[:, :21], with_taps=True)
    cache = model.paged_cache(3, 16)
    assert [a.shape for a in cache["latent"]] == [(3, 16, 256)] * 3
    assert cache["routing"].shape == (2 * k, 48)
    live = jnp.arange(24)[None, :] < 21          # three rows of padding
    _, cache = jax.jit(model.paged_step)(
        params, ids, cache, jnp.asarray([[1, 2]], jnp.int32),
        jnp.zeros((1,), jnp.int32), live)
    rows = cache["latent"][0].reshape(-1, 256)[16:16 + 21]
    np.testing.assert_allclose(rows[:, :cfg.latent_row],
                               taps["latent_rows"][0], atol=2e-6)
    assert not np.asarray(rows[:, cfg.latent_row:]).any()
    # the padding's rows (positions 21..23) went to the trash block
    assert not np.asarray(cache["latent"][0][0, :5]).any()
    assert not np.asarray(cache["latent"][0][0, 8:]).any()
    record = np.asarray(cache["routing"])
    order = np.argsort(record[:k, 16:16 + 21], axis=0)
    np.testing.assert_array_equal(
        np.take_along_axis(record[:k, 16:16 + 21], order, 0).T,
        taps["experts"][0])
    np.testing.assert_allclose(
        np.take_along_axis(record[k:, 16:16 + 21], order, 0).T,
        taps["gates"][0], rtol=1e-5)
    # padding is routed to expert number `experts`, into the trash block
    assert (record[:k, 5:8] == cfg.n_routed_experts).all()
    assert not record[:, :5].any() and not record[:, 8:16].any() \
        and not record[:, 16 + 21:].any()


def _by_hand(x, w, bias, k, scaling):
    """The published rule in numpy float64, a token at a time."""
    x, w, bias = (np.asarray(a, np.float64) for a in (x, w, bias))
    index, gates = [], []
    for row in x:
        scores = 1.0 / (1.0 + np.exp(-(row @ w)))
        chosen = np.argsort(-(scores + bias), kind="stable")[:k]
        weights = scores[chosen] / (scores[chosen].sum() + 1e-20) * scaling
        index.append(chosen)
        gates.append(weights)
    return np.asarray(index), np.asarray(gates)


def test_the_router_is_the_published_rule():
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (50, 32))
    w = jax.random.normal(keys[1], (32, 16)) * 0.3
    bias = jax.random.normal(keys[2], (16,)) * 0.1
    scores, gates, index = moe.route_sigmoid(x, w, bias, 3, 2.448)
    want_index, want_gates = _by_hand(x, w, bias, 3, 2.448)
    np.testing.assert_array_equal(index, want_index)
    np.testing.assert_allclose(gates, want_gates, rtol=1e-5)
    assert scores.shape == (50, 16) and scores.dtype == jnp.float32
    np.testing.assert_allclose(gates.sum(-1), 2.448, rtol=1e-5)


def test_the_bias_changes_selections_and_never_the_gates(tiny):
    """At the seeded size (`BIAS_STD`) zeroing the bias changes at least
    one chosen expert of more than half the tokens; an expert chosen with
    and without it carries a gate made of the same scores."""
    model, params, _ = tiny
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(9), (400, 32))
    k, scale = model.config.num_experts_per_tok, 2.448
    scores, gates, index = moe.route_sigmoid(x, lp["router"],
                                             lp["router_bias"], k, scale)
    _, gates0, index0 = moe.route_sigmoid(x, lp["router"],
                                          jnp.zeros_like(lp["router_bias"]),
                                          k, scale)
    changed = np.mean(np.sort(index, -1) != np.sort(index0, -1), axis=-1) > 0
    assert changed.mean() > 0.5, changed.mean()
    # the gates are the chosen SCORES renormalised: no bias in them
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(index), -1)
    np.testing.assert_allclose(
        gates, scale * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    same = ~changed
    assert same.any()
    np.testing.assert_allclose(np.sort(np.asarray(gates)[same], -1),
                               np.sort(np.asarray(gates0)[same], -1),
                               rtol=1e-6)


def test_the_shares_add_up_to_the_whole_layer(tiny):
    """`held = (i, 1)` for each of the 8 experts (the tiny size's `(16 i,
    16)` for i in 0..7 of 128) and the shared expert counted once: the
    eight parts add up to the uncut reference's expert layer."""
    model, params, pub = tiny
    cfg, lp = model.config, params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 48, 32))
    live = jnp.ones((48,), bool)
    _, layer = published_weights(cfg, params)
    with jax.default_matmul_precision("highest"):
        want, _ = plain._experts(pub, layer(1), x)
    part = jax.jit(lambda first: dsv3.routed_experts(
        cfg, lp, x[0], live, (first, 1)), static_argnums=0)
    total = dsv3._swiglu(x[0], lp["shared_gate"], lp["shared_up"],
                         lp["shared_down"])
    assigned = 0
    for first in range(cfg.n_routed_experts):
        y, counts, _ = part(first)
        total = total + y
        assigned += int(counts["assigned"])
        assert int(counts["placed"]) == int(counts["assigned"])
    assert assigned == 48 * cfg.num_experts_per_tok
    np.testing.assert_allclose(total, want[0], atol=2e-6)
    with pytest.raises(ValueError, match="does not lie inside"):
        dsv3.routed_experts(cfg, lp, x[0], live, (7, 2))


# --------------------------------------------------------------------------- #
# through the engine: one engine, its two programs compiled once
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def served(tiny):
    model, params, _ = tiny
    engine = InferenceEngine(
        EngineConfig(batch_slots=3, block_size=16, num_blocks=20,
                     max_blocks_per_seq=6, prefill_chunk=16),
        model=model, params=params)
    out = {"engine": engine}

    # a prompt of three chunks prefills while two rows decode
    mix = [(prompt(5, 1), 9), (prompt(3, 2), 8), (prompt(40, 3), 6)]
    out["interleaved"] = [engine.add_request(p, n) for p, n in mix]
    engine.run_until_idle()
    out["after_interleaved"] = settled_stats(engine)

    # a document donated once, then two questions behind it
    doc = prompt(32, 4)
    engine.add_request(doc, 1)
    engine.run_until_idle()
    before = engine.stats()["prefix_cache"]
    out["adopters"] = [engine.add_request(doc + prompt(7 + i, 5 + i), 5)
                       for i in range(2)]
    engine.run_until_idle()
    after = engine.stats()["prefix_cache"]
    out["prefix"] = {k: after[k] - before[k]
                     for k in ("hits", "hit_tokens", "lookups")}

    # a preemption from outside: the victim prefills prompt + generated
    # again, into blocks others left
    out["preempted"] = [engine.add_request(prompt(20, 20 + i), 10)
                        for i in range(3)]
    for _ in range(6):
        engine.step()
    with engine._lock:
        assert engine._preempt_one()
    engine.run_until_idle()
    out["preemptions"] = engine.stats()["preemptions"]
    engine.check_no_leaks()

    # fail_all rebuilds the cache: what it held is gone, counters and all
    doomed = [engine.add_request(prompt(9, 30 + i), 30) for i in range(2)]
    for _ in range(4):
        engine.step()
    out["failed"] = engine.fail_all("the device went away")
    out["doomed"] = doomed
    out["after_rebuild_cached"] = engine.stats()["prefix_cache"][
        "cached_blocks"]
    out["rebuilt"] = [engine.add_request(doc + prompt(6, 40), 4),
                      engine.add_request(prompt(18, 41), 6)]
    engine.run_until_idle()
    out["final"] = settled_stats(engine)
    return out


@pytest.mark.parametrize("which", ["interleaved", "adopters", "preempted",
                                   "rebuilt"])
def test_the_engine_serves_it_as_the_reference_computes_it(tiny, served,
                                                           which):
    for req in served[which]:
        assert req.state == "FINISHED", req.error
        assert gaps_of(tiny, req) <= TOL, req.request_id


def test_a_prefix_hit_adopts_the_documents_blocks(served):
    assert served["prefix"] == {"hits": 2, "hit_tokens": 64, "lookups": 2}
    assert [r.cached_tokens for r in served["adopters"]] == [32, 32]


def test_preemption_and_the_rebuild(served):
    assert served["preemptions"] == 1
    assert max(r.preemptions for r in served["preempted"]) == 1
    assert served["failed"] == 2
    assert all(r.state == "FAILED" for r in served["doomed"])
    assert served["after_rebuild_cached"] == 0
    # the document's blocks went with the arena: nothing to adopt
    assert [r.cached_tokens for r in served["rebuilt"]] == [0, 0]
    engine = served["engine"]
    assert not engine.has_work()
    engine.check_no_leaks()
    stats = served["final"]
    assert stats["prefill_compiles"] == stats["decode_compiles"] == 1
    assert stats["paged_attn"] == {
        "decode": "reference: platform cpu",
        "prefill": "reference: platform cpu"}
    assert stats["state"] == {"slots": 0, "bytes": 0, "resets": 0,
                              "prefix_adoptions_refused": 0}


def test_the_expert_layers_counters_reach_stats(tiny, served):
    """Accumulated on the device in the cache, read when `stats()` is
    asked: every live token's k assignments, none dropped, idle rows
    counted for nothing; a rebuilt cache counts from zero."""
    cfg = tiny[0].config
    first, final = served["after_interleaved"]["moe"], served["final"]["moe"]
    assert first["layers"] == 2 and first["experts"] == 8
    per_token = cfg.num_experts_per_tok * cfg.n_moe_layers
    # "prefill" is every step that held a chunk, one a chunk: the three
    # prompts' tokens and, since a chunk rides in the decode step where
    # rows decode, those rows' too. "decode" is the plain decode steps.
    # Every token but a request's first (which its last chunk gives)
    # passes through as a decode row once.
    assert first["prefill"]["steps"] == 1 + 1 + 3
    steps = served["after_interleaved"]["steps"]
    assert steps["chunks_aboard"] + steps["prefill"] == 1 + 1 + 3
    assert first["decode"]["steps"] == steps["decode"] \
        - steps["chunks_aboard"]
    assert steps["chunks_aboard"] >= 3     # the long prompt's, at least
    assert first["prefill"]["assigned"] >= (5 + 3 + 40) * per_token
    assert first["prefill"]["assigned"] + first["decode"]["assigned"] \
        == (5 + 3 + 40 + 8 + 7 + 5) * per_token
    for kind in ("decode", "prefill"):
        assert {"steps", "assigned", "placed", "tiles", "drew",
                "max_over_mean", "assignments_per_step",
                "experts_drawn_per_step", "load_max_over_mean"} \
            <= set(first[kind])
        assert first[kind]["placed"] == first[kind]["assigned"]
        assert 1.0 <= first[kind]["load_max_over_mean"] <= 8.0
        assert 0 < first[kind]["experts_drawn_per_step"] <= 8
        # an expert that drew a row ran a tile, and one that drew none did
        # not: at most a tile for each assignment
        assert first[kind]["drew"] <= first[kind]["tiles"] \
            <= first[kind]["assigned"]
    # a decode step's experts draw a row or two: one 16-row tile each
    assert first["decode"]["tiles"] == first["decode"]["drew"]
    assert sum(first["load"]) == first["decode"]["assigned"] \
        + first["prefill"]["assigned"]
    # after fail_all: only what the rebuilt cache saw
    assert final["prefill"]["assigned"] + final["decode"]["assigned"] \
        == (38 + 18 + 3 + 5) * per_token


def test_the_latent_walk_reaches_stats(tiny):
    """`stats()["latent_walk"]`: what the kernel's tile rule walks a step,
    decode steps and chunks apart. Four heads and chunks of 512 positions
    are two tiles of 256 tokens a chunk, KV chunks of 512 tokens under
    them and of 1,024 under a decode step's tile; a prompt of 1,100 tokens
    is two full chunks and one of 76, whose second tile holds no live
    query."""
    from ray_tpu.ops import latent_attention as la

    model, params, _ = tiny
    engine = InferenceEngine(
        EngineConfig(batch_slots=2, block_size=16, num_blocks=80,
                     max_blocks_per_seq=72, prefill_chunk=512),
        model=model, params=params)
    req = engine.add_request(prompt(1100, 50), 4)
    engine.run_until_idle()
    assert req.state == "FINISHED", req.error
    walk = settled_stats(engine)["latent_walk"]
    # by hand. Chunks at 0, 512, 1024: a tile of queries at p .. p + 255
    # walks ceil((p + 256) / 512) chunks, those whole below p unmasked;
    # the last chunk's first tile stops at 1,100 and its second is skipped.
    assert walk["prefill"] == {"tiles": 6, "tiles_walked": 5,
                               "kv_chunks": 1 + 1 + 2 + 2 + 3,
                               "kv_chunks_masked": 5}
    # three decode steps at positions 1,100-1,102, two slots of which one
    # is live: two 1,024-token chunks, the first whole below the query
    assert walk["decode"] == {"tiles": 6, "tiles_walked": 3,
                              "kv_chunks": 6, "kv_chunks_masked": 3}
    # and the rule that counted is the one the kernel's wrapper walks by
    assert dsv3.tile_walk is la.tile_walk
    cfg = model.config
    rule = dict(heads=cfg.num_attention_heads, block_size=16,
                max_ctx=72 * 16, dtype=cfg.dtype)
    total = dict.fromkeys(la.WALK_COUNTS, 0)
    for start, live in ((0, 512), (512, 512), (1024, 76)):
        counts = la.tile_walk(start + jnp.arange(512)[None],
                              jnp.arange(512)[None] < live, **rule)[2]
        total = {k: total[k] + int(counts[k]) for k in total}
    assert total == walk["prefill"]
    counts = la.tile_walk(jnp.array([[1101], [0]]),
                          jnp.array([[True], [False]]), **rule)[2]
    assert {k: 3 * int(v) for k, v in counts.items()} == walk["decode"]


# --------------------------------------------------------------------------- #
# a chunk aboard the decode step
# --------------------------------------------------------------------------- #


class _NoFusedStep(DeepseekV3):
    """The model with its fused step hidden (`PagedModel`'s "not offered"):
    an engine over it keeps the two programs."""

    paged_step_with_chunk = None


def test_the_fused_step_is_the_two_steps(tiny):
    """`paged_step_with_chunk` against `paged_step` called twice, chunk
    first as the engine's two programs run: the logits and the whole
    cache, with a dead slot among the decode rows (the chunk's own), a
    padded chunk over a prefix of its own and counters that book the fused
    step whole as a step that held a chunk."""
    model, params, _ = tiny
    slots, chunk, bsz, width = 3, 16, 16, 4
    cache = model.paged_cache(1 + (slots + 1) * width, bsz)
    tables = 1 + jnp.arange((slots + 1) * width, dtype=jnp.int32).reshape(
        slots + 1, width)
    ids = jax.random.randint(jax.random.PRNGKey(7), (1, chunk), 1, 96)
    # what came before: rows of the latent's size, whatever they hold
    keys = jax.random.split(jax.random.PRNGKey(8), len(cache["latent"]))
    cache["latent"] = [jax.random.normal(k, a.shape, a.dtype)
                       for k, a in zip(keys, cache["latent"])]
    tokens = jnp.array([[5], [9], [0]], jnp.int32)
    pos, wmask = jnp.array([21, 37, 0]), jnp.array([[True], [True], [False]])
    chunk_ids = jnp.where(jnp.arange(chunk)[None] < 11, ids, 0)
    chunk_live = jnp.arange(chunk)[None] < 11
    chunk_pos, last = jnp.array([16]), jnp.array([10])
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
    assert float(jnp.abs(want[0]).max()) > 1e-2
    # the cache: latent rows and the routing record everywhere but the
    # trash block, where both write their dead rows
    for a, b in zip(want[2]["latent"], got[2]["latent"]):
        assert float(jnp.abs(a[1:] - b[1:]).max()) <= TOL
    np.testing.assert_allclose(want[2]["routing"][:, bsz:],
                               got[2]["routing"][:, bsz:], atol=TOL)
    # the counters: the same loads, under "a step that held a chunk"
    for name in ("assigned", "placed", "load"):
        a, b = want[2]["moe"][name], got[2]["moe"][name]
        np.testing.assert_array_equal(a.sum(0), b.sum(0))
        np.testing.assert_array_equal(b[0], cache["moe"][name][0])
    assert [int(v) for v in got[2]["moe"]["steps"]
            - cache["moe"]["steps"]] == [0, 1]
    for name in dsv3.WALK_COUNTS:
        a, b = want[2]["latent_walk"][name], got[2]["latent_walk"][name]
        assert int(a.sum()) == int(b.sum())
        assert int(b[0]) == int(cache["latent_walk"][name][0])


def _cached_rows(engine, req):
    """(first-layer latent rows, routing-record columns) of the request's
    whole blocks, found again through the radix cache it donated them to."""
    bsz = engine.config.block_size
    stream = req.prompt + req.generated
    blocks, _ = engine._prefix.match(stream[:len(stream) // bsz * bsz])
    assert len(blocks) == min(req.processed, len(stream)) // bsz
    at = (np.asarray(blocks)[:, None] * bsz + np.arange(bsz)).reshape(-1)
    arena = np.asarray(engine._arenas["latent"][0])
    return arena.reshape(-1, arena.shape[-1])[at], \
        np.asarray(engine._arenas["routing"])[:, at]


def test_a_chunk_aboard_changes_nothing_that_is_served(two_layers):
    """The same engine over the model and over the model with its fused
    step hidden: the same greedy tokens for a mix whose chunks land while
    others decode (prompts of one to three chunks, a prefix adoption), and
    the same rows left in the first layer's arena and the routing
    record."""
    model, params, _ = two_layers

    def serve(model):
        engine = InferenceEngine(
            EngineConfig(batch_slots=3, block_size=16, num_blocks=40,
                         max_blocks_per_seq=6, prefill_chunk=16),
            model=model, params=params)
        doc = prompt(32, 4)
        engine.add_request(doc, 1)
        engine.run_until_idle()
        mix = [(prompt(5, 1), 12), (prompt(40, 3), 9), (prompt(3, 2), 14),
               (doc + prompt(20, 6), 7), (prompt(17, 7), 6)]
        reqs = [engine.add_request(p, n) for p, n in mix]
        engine.run_until_idle()
        engine.check_no_leaks()
        assert reqs[3].cached_tokens == 32
        return engine, reqs

    (fused, got), (plain_engine, want) = serve(model), serve(
        _NoFusedStep(model.config))
    for a, b in zip(got, want):
        assert a.state == b.state == "FINISHED", (a.error, b.error)
        assert a.generated == b.generated
        for x, y in zip(_cached_rows(fused, a),
                        _cached_rows(plain_engine, b)):
            assert x.shape == y.shape and x.size and np.abs(x).max() > 0.1
            np.testing.assert_allclose(x, y, atol=TOL)
    assert len({tuple(r.generated[:4]) for r in got}) == len(got)
    steps, plain_steps = fused.step_stats(), plain_engine.step_stats()
    # 1 + 3 + 1 + 2 + 2 chunks of the mix: all but the first, which found
    # no row decoding, rode; the document's two ran alone before them
    assert (steps["prefill"], steps["chunks_aboard"]) == (3, 8)
    assert (plain_steps["prefill"], plain_steps["chunks_aboard"]) == (11, 0)
    stats = fused.stats()
    assert stats["prefill_compiles"] == stats["decode_compiles"] \
        == stats["decode_with_chunk_compiles"] == 1
    assert plain_engine.stats()["decode_with_chunk_compiles"] == 0
    assert set(stats["paged_attn"]) == {"decode", "prefill"}


def _case_a_row_takes_the_chunks_blocks(engine_of):
    """Three blocks: the decoding row's claim of a second finds none and
    preempts the request whose chunk was about to ride; the step is a
    plain decode step."""
    engine = engine_of(batch_slots=2, block_size=4, num_blocks=4,
                       max_blocks_per_seq=3, prefill_chunk=8)
    return engine, [engine.add_request(prompt(3, 1), 6),
                    engine.add_request(prompt(10, 2), 2)], 1, 0


def _case_the_chunk_takes_a_rows_blocks(engine_of):
    """The third chunk of an interactive request (two rode) claims its
    blocks from the batch request that decodes: it runs alone."""
    engine = engine_of(batch_slots=2, block_size=4, num_blocks=5,
                       max_blocks_per_seq=4, prefill_chunk=4)
    first = engine.add_request(prompt(3, 1), 9, slo_class="batch")
    for _ in range(2):
        engine.step()
    return engine, [first, engine.add_request(prompt(11, 2), 3)], 0, 2


PREEMPTIONS = {"a_row_takes_the_chunks_blocks":
               _case_a_row_takes_the_chunks_blocks,
               "the_chunk_takes_a_rows_blocks":
               _case_the_chunk_takes_a_rows_blocks}


@pytest.mark.parametrize("case", sorted(PREEMPTIONS))
def test_a_preemption_inside_a_fused_steps_claims_leaks_nothing(two_layers,
                                                                case):
    model, params, _ = two_layers

    def engine_of(**cfg):
        return InferenceEngine(EngineConfig(prefix_cache_enabled=False, **cfg),
                               model=model, params=params)

    engine, reqs, victim, rode = PREEMPTIONS[case](engine_of)
    engine.run_until_idle()
    assert engine.step_stats()["chunks_aboard"] == rode
    assert reqs[victim].preemptions >= 1
    assert reqs[1 - victim].preemptions == 0
    for req in reqs:
        assert req.state == "FINISHED", req.error
        assert len(req.generated) == req.max_new_tokens
        assert gaps_of(two_layers, req) <= TOL
    assert not engine.has_work() and not engine._inflight
    engine.check_no_leaks()
    assert engine.stats()["kv"]["blocks_in_use"] == 0


def test_published_keys_make_the_configuration():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kanana-2-30b-a3b-l8-serve.json")) as f:
        published = json.load(f)
    cfg = DeepseekV3Config.from_published(published)
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size) \
        == (8, 2048, 128256)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.qk_head_dim) == (512, 128, 64, 128, 192)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.shared_width) \
        == (128, 6, 768, 1536)
    assert (cfg.latent_row, cfg.latent_page_width) == (576, 640)
    shapes = jax.eval_shape(
        lambda: DeepseekV3(cfg).init(jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 5_069_642_624
    assert sum(a.size for a in jax.tree.leaves(shapes["layers"][0])) \
        == 64_098_816
    assert sum(a.size for a in jax.tree.leaves(shapes["layers"][1])) \
        == 640_029_312
    for refused in ({"q_lora_rank": 1536}, {"n_group": 8, "topk_group": 4},
                    {"rope_scaling": {"type": "yarn"}},
                    {"scoring_func": "softmax"}):
        with pytest.raises(ValueError):
            DeepseekV3Config.from_published({**published, **refused})


class _TwoWayTp:
    axis_names = ("tp",)
    devices = np.zeros((2,))


REFUSALS = {
    "a_draft": lambda m, p: m.early_exit_draft(p),
    "adapter_banks": lambda m, p: m.adapter_banks(4, 8),
    "adapters_in_a_step": lambda m, p: m.paged_step(
        p, jnp.zeros((1, 1), jnp.int32), None, None, None, None, ((), ())),
    "a_tp_mesh": lambda m, p: m.place_on_mesh(p, _TwoWayTp()),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_it_does_not_have_it_says_plainly(tiny, what):
    model, params, _ = tiny
    with pytest.raises(ValueError):
        REFUSALS[what](model, params)
