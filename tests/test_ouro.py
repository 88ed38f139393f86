"""`models/ouro.py` on the CPU at a tiny size: the full forward (logits and
the exit distribution) and the engine's chunked prefill, decode, prefix
adoption, preemption and rebuild against the plain float32 reference
(`benchmarks/reference/ouro_plain.py`: plain Python passes over plain
layers, no cache); the cache's pages a pass; the loop's counters; the one
traced stack of layers a program; and the paged kernel in the interpreter
at one query row a KV head."""

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

from benchmarks.reference import ouro_plain as plain  # noqa: E402
from ray_tpu.inference.engine import (EngineConfig,  # noqa: E402
                                      InferenceEngine)
from ray_tpu.models import ouro  # noqa: E402
from ray_tpu.models.ouro import (Ouro, OuroConfig,  # noqa: E402
                                 published_weights)

# float32 parameters at the tiny size: the served path (paged, a scan over
# the passes, fused products) and the reference (dense, plain loops, the
# products apart) differ by the order of summation alone. Four passes of
# two layers with a norm after every sub-layer keep that at a few 1e-7 on
# logits of scale 0.1; 5e-6 is ten times that and a hundred thousand times
# under what a wrong page, pass or norm does (1e-1 and more, below).
TOL = 5e-6


def _model(**overrides):
    cfg = OuroConfig.tiny(**overrides)
    model = Ouro(cfg)
    params = model.init(jax.random.PRNGKey(1))
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))

    def jitter(tree):      # norms away from their trivial initial values
        return {k: (v + 0.1 * jax.random.normal(next(keys), v.shape, v.dtype)
                    if k.endswith("norm") else v) for k, v in tree.items()}

    params = {**jitter({k: v for k, v in params.items() if k != "layers"}),
              "layers": [jitter(lp) for lp in params["layers"]]}
    params["exit_gate"] = {"w": params["exit_gate"]["w"] * 20.0,
                           "b": jnp.asarray(-0.5, cfg.dtype)}
    pub = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "dtype"}
    return model, params, pub


@pytest.fixture(scope="module")
def tiny():
    return _model()


def reference(tiny, ids, **kwargs):
    model, params, pub = tiny
    top, layer = published_weights(params)
    return plain.forward(top, layer, jnp.asarray(ids, jnp.int32), pub,
                         **kwargs)


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 96, n)]


def settled_stats(engine):
    """`stats()` of an idle engine with its device counters as of now: a
    call dispatches their copy and a LATER call reads it."""
    for _ in range(2):
        engine.stats()
        jax.block_until_ready(engine._counters_pending)
    return engine.stats()


def gaps_of(tiny, req):
    """How far each served token lies under the reference's best logit
    given the tokens before it."""
    ids = [req.prompt + req.generated[:-1]]
    rows = reference(tiny, ids)[0][0][len(req.prompt) - 1:]
    return float(plain.chosen_token_gaps(rows, req.generated).max())


@pytest.mark.parametrize("passes", [2, 4])
def test_full_forward_is_the_plain_reference(passes):
    tiny = _model(total_ut_steps=passes)
    model, params, _ = tiny
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 37), 0, 96)
    want, want_exit = reference(tiny, ids)
    got, got_exit = jax.jit(model.forward)(params, ids)
    assert float(jnp.abs(got - want).max()) <= TOL
    assert float(jnp.abs(want).max()) > 1e-2
    # the exit distribution: one value a pass, summing to one, and not the
    # trivial one (the gate was scaled up so that it says something)
    assert got_exit.shape == (2, 37, passes)
    assert float(jnp.abs(got_exit - want_exit).max()) <= TOL
    assert float(jnp.abs(got_exit.sum(-1) - 1.0).max()) <= 1e-6
    assert float(got_exit[..., 0].std()) > 1e-2
    # the published threshold, 1, exits at the last pass; a lower one at
    # the first pass whose CDF reaches it
    assert int(ouro.exit_pass(got_exit, 1.0).min()) == passes
    early = ouro.exit_pass(got_exit, 0.3)
    cdf = np.cumsum(np.asarray(got_exit), axis=-1)
    want_early = np.where((cdf[..., :-1] >= 0.3).any(-1),
                          (cdf[..., :-1] >= 0.3).argmax(-1) + 1, passes)
    assert (np.asarray(early) == want_early).all() and early.min() == 1


def test_the_passes_share_one_set_of_weights_and_every_pass_counts(tiny):
    """Fewer passes under the same parameters is another function: a pass
    dropped moves the logits by far more than TOL."""
    model, params, pub = tiny
    ids = jax.random.randint(jax.random.PRNGKey(6), (1, 20), 0, 96)
    full = reference(tiny, ids)[0]
    three = Ouro(dataclasses.replace(model.config, total_ut_steps=3))
    got = jax.jit(three.forward)(params, ids)[0]
    assert float(jnp.abs(got - full).max()) > 1e-2
    draft, draft_params = model.early_exit_draft(params)
    assert draft.config.total_ut_steps == 2 and draft_params is params


def test_the_residual_stream_has_a_type_of_its_own(tiny):
    """`stream_dtype` is float32 as served; in bfloat16 (the published
    activations' type) the same parameters give the same function to the
    stream's rounding, which is not nothing: it is what the benchmark's
    control `bf16_residual` is refused for."""
    model, params, _ = tiny
    assert model.config.stream_dtype == jnp.float32
    ids = jax.random.randint(jax.random.PRNGKey(8), (1, 24), 0, 96)
    full = reference(tiny, ids)[0]
    rounded = Ouro(dataclasses.replace(
        model.config, stream_dtype=jnp.bfloat16))
    got = jax.jit(rounded.forward)(params, ids)[0]
    err = float(jnp.abs(got - full).max())
    assert got.dtype == full.dtype and 10 * TOL < err < 0.1, err


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

    # a document donated once, then two questions behind it: a hit brings
    # back the pages of EVERY pass
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
    with engine._lock:
        out["doc_blocks"], _ = engine._prefix.match(doc)
    out["arena"] = jax.tree.map(np.asarray, engine._arenas["kv"][1])

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
    out["rebuilt"] = [engine.add_request(doc + prompt(6, 40), 4),
                      engine.add_request(prompt(18, 41), 6)]
    engine.run_until_idle()
    out["final"] = settled_stats(engine)
    return out


@pytest.mark.parametrize("which", ["interleaved", "adopters", "preempted",
                                   "rebuilt"])
def test_the_engine_serves_it_as_the_reference_computes_it(tiny, served,
                                                           which):
    """Chunked prefill and decode steps through the paged cache, a prefix
    hit, a preempt-and-resume and a rebuilt cache give the logits of the
    reference's full forward: all four passes' pages are written, read and
    restored."""
    for req in served[which]:
        assert req.state == "FINISHED", req.error
        assert gaps_of(tiny, req) <= TOL, req.request_id


def test_a_prefix_hit_and_a_preemption_happened(served):
    assert served["prefix"] == {"hits": 2, "hit_tokens": 64, "lookups": 2}
    assert [r.cached_tokens for r in served["adopters"]] == [32, 32]
    assert served["preemptions"] == 1
    assert max(r.preemptions for r in served["preempted"]) == 1
    assert served["failed"] == 2
    assert all(r.state == "FAILED" for r in served["doomed"])
    assert [r.cached_tokens for r in served["rebuilt"]] == [0, 0]
    engine = served["engine"]
    assert not engine.has_work()
    engine.check_no_leaks()
    stats = served["final"]
    assert stats["prefill_compiles"] == stats["decode_compiles"] == 1
    assert stats["state"] == {"slots": 0, "bytes": 0, "resets": 0,
                              "prefix_adoptions_refused": 0}


def test_a_block_holds_a_page_for_every_pass_and_they_differ(tiny, served):
    """One table addresses all four passes: pass u of logical block b is
    physical block u x num_blocks + b of the layer's arena, every pass's
    page is the reference's keys and values of THAT pass, and no two are
    alike."""
    model, _, _ = tiny
    passes, layer = model.config.total_ut_steps, 1
    k_arena, v_arena = served["arena"]
    assert k_arena.shape == (passes * 20, 16, 4, 16)
    blocks = np.asarray(served["doc_blocks"])
    assert len(blocks) == 2 and blocks.min() >= 1
    doc = prompt(32, 4)
    _, _, kept = reference(tiny, [doc],
                           keep=[(u, layer) for u in range(passes)])
    pages = []
    for u in range(passes):
        k = k_arena[blocks + u * 20].reshape(32, 4, 16)
        v = v_arena[blocks + u * 20].reshape(32, 4, 16)
        want_k, want_v = kept[(u, layer)]
        assert np.abs(k - np.asarray(want_k[0])).max() <= TOL
        assert np.abs(v - np.asarray(want_v[0])).max() <= TOL
        pages.append(k)
    for u in range(1, passes):
        assert np.abs(pages[u] - pages[u - 1]).max() > 1e-2
    # blocks u x num_blocks, u > 0, are never addressed
    assert not k_arena[[20, 40, 60]].any()


def test_the_loops_counters_reach_stats(tiny, served):
    """Counted on the device inside the loop, read when `stats()` is
    asked: every live token takes every pass of every layer; idle rows and
    padding count for nothing; a rebuilt cache counts from zero."""
    cfg = tiny[0].config
    first, final = (served[k]["loop"] for k in ("after_interleaved", "final"))
    # every token but a request's last generated passes through once
    tokens = (5 + 3 + 40) + (9 + 8 + 6) - 3
    # a decode step's live rows hold position // 16 + 1 blocks each: rows
    # at 5..12 and 3..9 one, the row at 40..44 three
    assert first == {"tokens": tokens, "passes": 4 * tokens,
                     "layer_passes": 4 * 2 * tokens, "passes_per_token": 4.0,
                     "decode_steps":
                         served["after_interleaved"]["steps"]["decode"],
                     "decode_blocks": 8 + 7 + 3 * 5}
    assert 8 <= first["decode_steps"] <= 8 + 7 + 5
    assert final["tokens"] == (38 + 18) + (4 + 6) - 2
    assert final["passes_per_token"] == 4.0
    layout = served["final"]["kv_layout"]
    assert layout == {"bytes_per_token": 4 * 2 * 2 * 4 * 16 * 4,
                      "passes": 4, "layers": 2}
    assert cfg.kv_bytes_per_token == layout["bytes_per_token"]
    # the engine sums the cache's leaves: the arena and the four counters
    kv = served["final"]["kv"]
    assert kv["bytes"] == 20 * 16 * layout["bytes_per_token"] + 16
    assert 0 < kv["peak_blocks_in_use"] <= 19


def test_the_published_sizes():
    """The defaults are the published `config.json`; a token holds 1.5 MiB
    of cache in bf16 and the model 2,667,974,657 parameters."""
    cfg = OuroConfig()
    assert (cfg.num_hidden_layers, cfg.total_ut_steps, cfg.hidden_size,
            cfg.intermediate_size, cfg.vocab_size) == (48, 4, 2048, 5632,
                                                       49152)
    assert cfg.kv_bytes_per_token == 1_572_864
    shapes = jax.eval_shape(Ouro(cfg).init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 2_667_974_657
    assert OuroConfig.from_published(
        {"total_ut_steps": 2, "rope_theta": 1000000, "model_type": "ouro",
         "layer_types": ["full_attention"]}).total_ut_steps == 2
    with pytest.raises(ValueError, match="a KV head a query head"):
        OuroConfig(num_key_value_heads=4)


REFUSALS = {
    "adapters": lambda m, p: m.paged_step(p, None, None, None, None, None,
                                          adapters=((), ())),
    "adapter_banks": lambda m, p: m.adapter_banks(4, 8),
    "a mesh": lambda m, p: m.paged_cache(4, 16, mesh=object()),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_it_does_not_have_it_says_plainly(tiny, what):
    model, params, _ = tiny
    with pytest.raises(ValueError, match="Ouro"):
        REFUSALS[what](model, params)


# --------------------------------------------------------------------------- #
# the kernel path: heads of 128, in the interpreter
# --------------------------------------------------------------------------- #


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    from ray_tpu.ops import attention

    with attention._CALLS_LOCK:
        before = dict(attention._CALLS)
        attention._CALLS.clear()
    yield attention
    with attention._CALLS_LOCK:
        attention._CALLS.clear()
        attention._CALLS.update(before)


def test_a_program_traces_one_paged_call_a_layer_whatever_the_passes(
        interpret):
    """Three layers run four times: each of the engine's two programs
    traces three paged-attention calls, not twelve, on the Pallas path,
    and what they serve is the reference's."""
    tiny = _model(num_hidden_layers=3, hidden_size=256,
                  num_attention_heads=2, num_key_value_heads=2, head_dim=128)
    model, params, _ = tiny
    engine = InferenceEngine(
        EngineConfig(batch_slots=2, block_size=16, num_blocks=12,
                     max_blocks_per_seq=4, prefill_chunk=32),
        model=model, params=params)
    reqs = [engine.add_request(prompt(20, 50), 5),
            engine.add_request(prompt(9, 51), 4)]
    engine.run_until_idle()
    assert engine.stats()["paged_attn"] == {"decode": "pallas",
                                            "prefill": "pallas"}
    calls = {(r["pass"], r["path"], tuple(r["shape"])): r["calls"]
             for r in interpret.pallas_status()}
    assert calls == {("paged_decode", "pallas", (2, 1, 2, 128)): 3,
                     ("paged_prefill", "pallas", (1, 32, 2, 128)): 3}
    for req in reqs:
        assert req.state == "FINISHED", req.error
        assert gaps_of(tiny, req) <= TOL
