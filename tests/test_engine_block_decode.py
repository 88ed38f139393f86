"""The engine over a model that decodes by BLOCKS (`model.decode_block`,
docs/INFERENCE.md finding (i)): a step that yields a block, not a token.
Whatever the mix, the preemptions and what leaves while an execution is in
flight, every request receives the tokens of the plain loop of full forward
passes (`benchmarks/reference/sdar_plain.py block_diffusion_generate`)."""

import jax
import numpy as np
import pytest

from benchmarks.reference import sdar_plain as plain
from ray_tpu.inference import engine as eng
from ray_tpu.inference.engine import (EngineConfig, EngineLoop,
                                      InferenceEngine)
from ray_tpu.models import sdar

PUBLISHED = ("num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
             "num_experts", "num_experts_per_tok", "norm_topk_prob",
             "block_length", "denoising_steps", "remasking_strategy",
             "confidence_threshold", "mask_token_id")


def as_dict(cfg):
    return {k: getattr(cfg, k) for k in PUBLISHED}


def _ids(n, seed=0, vocab=95):
    return [int(t) for t in np.random.default_rng(seed).integers(0, vocab, n)]


@pytest.fixture(scope="module")
def tiny():
    cfg = sdar.SDARConfig.tiny()
    model = sdar.SDAR(cfg)
    params = model.init(jax.random.PRNGKey(0))
    top, layer = sdar.published_weights(cfg, params)
    memo = {}

    def want(prompt, n):
        key = (tuple(prompt), n)
        if key not in memo:
            memo[key] = plain.block_diffusion_generate(
                top, layer, prompt, as_dict(cfg), n)[0]
        return memo[key]

    return model, params, want


def _engine(tiny, **kwargs):
    model, params, _ = tiny
    cfg = dict(batch_slots=3, block_size=8, num_blocks=40,
               max_blocks_per_seq=8, prefill_chunk=16)
    cfg.update(kwargs)
    return InferenceEngine(EngineConfig(**cfg), model=model, params=params)


MIX = ((7, 6), (16, 9), (21, 4), (3, 8), (18, 7))


def test_one_compile_a_program_rows_at_different_passes_dispatch_ahead(tiny):
    engine = _engine(tiny)
    reqs = [engine.add_request(_ids(n, n), k) for n, k in MIX]
    mixed = aboard = 0
    while engine.has_work():
        engine.step()
        for rec in engine._inflight:
            kinds = {commit is None for _, _, _, commit, _ in rec.passes}
            mixed += len(kinds) == 2
            # a commit aboard: its row is there twice, the commit first
            for (a, b), (one, two) in zip(
                    zip(rec.rows, rec.rows[1:]),
                    zip(rec.passes, rec.passes[1:])):
                if one[4]:
                    assert a == b and one[3] is not None and two[3] is None
                    assert two[1] == one[1] + 4 and two[2] == [-1] * 4
                    aboard += 1
    # a commit pass and a denoise pass rode in one execution
    assert mixed > 0 and aboard > 0
    for req, (n, k) in zip(reqs, MIX):
        assert req.state == eng.FINISHED
        assert req.generated == tiny[2](_ids(n, n), k)
    stats = engine.stats()
    assert stats["prefill_compiles"] == 1 and stats["decode_compiles"] == 1
    steps, book = stats["steps"], stats["diffusion"]
    # all but the first execution were dispatched with one in flight
    assert steps["decode_ahead"] >= steps["decode"] - 2
    assert steps["decode_rows"] == book["denoise_passes"] \
        + book["commit_passes"]
    assert book["commit_passes"] == book["blocks_committed"] == sum(
        r.blocks for r in reqs)
    # every commit but a request's last had the next block's first denoise
    # pass aboard: nothing was short of a page
    assert book["commits_aboard"] == book["commit_passes"] - len(reqs)
    assert sum(r.passes for r in reqs) == steps["decode_rows"]
    assert book["tokens_committed"] + book["given_tokens"] == \
        4 * book["blocks_committed"]
    emitted = sum(k for _, k in MIX)
    assert 4 * book["blocks_committed"] - book["given_tokens"] \
        - book["truncated_tokens"] == emitted == stats["tokens_emitted"]
    assert book["committed_hist"] == [0, book["denoise_passes"], 0, 0, 0]
    engine.check_no_leaks()


@pytest.mark.parametrize("tail", [0, 1, 2, 3])
def test_a_block_is_four_executions_and_five_row_passes(tiny, tail):
    """A request alone: its first block denoises alone (the prompt's tail
    given), every later block's first denoise pass rides with the commit
    pass before it, and the last commit runs alone: 4 B + 1 executions for
    B blocks of a prompt of whole blocks (5 B unfused). The books and the
    pass log count ROW-passes: five a block, a commit before the next
    block's denoise, and the tokens are the plain loop's."""
    engine = _engine(tiny)
    prompt, blocks = _ids(12 + tail, 80 + tail), 5
    new = 4 * blocks - tail
    req = engine.add_request(prompt, new, record_passes=True)
    engine.run_until_idle()
    assert req.generated == tiny[2](prompt, new)
    stats = engine.stats()
    steps, book = stats["steps"], stats["diffusion"]
    assert steps["decode"] == 4 * blocks + 1 - tail
    assert steps["decode_rows"] == req.passes == 5 * blocks - tail
    assert (book["denoise_passes"], book["commit_passes"],
            book["commits_aboard"]) == (4 * blocks - tail, blocks,
                                        blocks - 1)
    assert stats["prefill_compiles"] == stats["decode_compiles"] == 1
    log = req.pass_log
    assert len(log) == 5 * blocks - tail
    assert [r["start"] for r in log] == sorted(r["start"] for r in log)
    for b in range(blocks):
        mine = [r for r in log if r["start"] == 12 + 4 * b]
        assert len(mine) == 5 - (tail if b == 0 else 0)
        # its denoise passes leave one mask less each, then its commit
        assert [r["left"].count(-1) for r in mine] == \
            list(range(len(mine) - 2, -1, -1)) + [0]
        assert mine[-1]["entered"] == mine[-1]["left"] == mine[-2]["left"]
        assert mine[0]["entered"] == (prompt[12:] + [-1] * 4)[:4] if b == 0 \
            else mine[0]["entered"] == [-1] * 4
    stream = prompt + req.generated
    assert [t for r in log if -1 not in r["entered"]
            for t in r["left"]] == stream[12:]
    engine.check_no_leaks()


def test_no_page_for_the_next_block_is_a_plain_commit(tiny):
    """A page holds two blocks. With the pool hogged, the commit of a
    page's first block still has the next block's denoise aboard (it needs
    no page), the commit of its second runs alone, and nobody is preempted
    for it."""
    engine = _engine(tiny, prefix_cache_enabled=False)
    bm = engine._bm
    prompt = _ids(16, 90)
    req = engine.add_request(prompt, 24)
    while not (bm.registered(req.request_id)
               and len(bm.block_table(req.request_id)) == 3):
        engine.step()
    bm.register("hog")
    assert bm.ensure("hog", bm.num_free() * 8) and bm.num_free() == 0
    alone = 0
    while engine.has_work():
        engine.step()
        last = engine._inflight[-1].passes if engine._inflight else []
        if any(p[3] is not None and not p[4] for p in last) and \
                bm.registered("hog"):
            alone += 1
            bm.free("hog")
    assert alone == 1 and req.preemptions == 0
    assert req.generated == tiny[2](prompt, 24)
    stats = engine.stats()
    book = stats["diffusion"]
    # six blocks: the last commit and the one short of a page ran alone
    assert (book["commit_passes"], book["commits_aboard"]) == (6, 4)
    assert stats["steps"]["decode"] == 4 * 6 + 1 + 1
    engine.check_no_leaks()


@pytest.mark.parametrize("how", ["preempt", "cancel"])
def test_preemption_and_cancel_with_a_commit_aboard_in_flight(tiny, how):
    """The youngest request goes while the execution that holds its commit
    and the denoise pass aboard is in flight: both row-passes are dropped.
    Preempted, it is recomputed from its final tokens."""
    engine = _engine(tiny)
    prompts = [_ids(12, 100), _ids(16, 101)]
    reqs = [engine.add_request(p, 16) for p in prompts]
    victim = reqs[1]

    def aboard_in_flight():
        return any(row[0] is victim and p[4] for rec in engine._inflight
                   for row, p in zip(rec.rows, rec.passes))

    while not aboard_in_flight():
        engine.step()
    dropped = engine.stats()["steps"]["dropped_rows"]
    if how == "preempt":
        assert engine._preempt_one() and victim.state == eng.WAITING
    else:
        assert engine.cancel(victim.request_id)
    engine.run_until_idle()
    assert engine.stats()["steps"]["dropped_rows"] >= dropped + 2
    assert reqs[0].generated == tiny[2](prompts[0], 16)
    if how == "preempt":
        assert victim.generated == tiny[2](prompts[1], 16)
    else:
        assert victim.state == eng.FAILED
    engine.check_no_leaks()


def test_preemption_in_mid_block_then_recompute(tiny):
    """An arena too small for three rows: the youngest is preempted while
    its block is under way, and recomputed from its final tokens under the
    same mask."""
    engine = _engine(tiny, num_blocks=9, prefix_cache_enabled=False)
    mix = ((14, 18), (9, 22), (17, 14))
    reqs = [engine.add_request(_ids(n, 30 + n), k) for n, k in mix]
    engine.run_until_idle()
    assert engine.stats()["preemptions"] > 0
    assert engine.stats()["steps"]["dropped_rows"] > 0
    for req, (n, k) in zip(reqs, mix):
        assert req.generated == tiny[2](_ids(n, 30 + n), k)
    engine.check_no_leaks()


def test_adoption_lands_on_a_block_and_nothing_provisional_is_kept(tiny):
    engine = _engine(tiny)
    prompt = _ids(19, 5)
    first = engine.add_request(prompt, 13)
    engine.run_until_idle()
    # whole pages of FINAL tokens only: 19 + 13 = 32 tokens, four pages
    assert engine.stats()["prefix_cache"]["cached_blocks"] == 4
    # one that shares 21 tokens: two pages adopted, a multiple of L
    second = engine.add_request(prompt + first.generated[:2] + [1, 2, 3], 9)
    # one that was cancelled in mid-block donates nothing
    third = engine.add_request(_ids(16, 6), 12)
    while third.blocks < 1:
        engine.step()
    before = engine.stats()["prefix_cache"]["cached_blocks"]
    assert engine.cancel(third.request_id)
    assert engine.stats()["prefix_cache"]["cached_blocks"] == before
    engine.run_until_idle()
    assert second.cached_tokens == 16
    assert second.generated == tiny[2](second.prompt, 9)
    assert third.state == eng.FAILED
    engine.check_no_leaks()
    # a prompt whose whole blocks are all adopted has nothing to prefill
    prefills = engine.stats()["steps"]["prefill"]
    again = engine.add_request(prompt[:17], 5)
    engine.run_until_idle()
    assert again.cached_tokens == 16
    assert engine.stats()["steps"]["prefill"] == prefills
    assert again.generated == tiny[2](prompt[:17], 5)
    engine.check_no_leaks()


def test_eos_inside_a_block_ends_the_request_there(tiny):
    prompt = _ids(10, 7)
    full = tiny[2](prompt, 12)
    # a token whose first appearance is not a block's last position
    at = next(i for i, t in enumerate(full)
              if full.index(t) == i and (10 + i) % 4 != 3)
    engine = _engine(tiny, eos_id=full[at])
    req = engine.add_request(prompt, 12, record_passes=True)
    other = engine.add_request(_ids(8, 8), 12)
    engine.run_until_idle()
    assert req.generated == full[:at + 1]
    assert other.state == eng.FINISHED
    engine.check_no_leaks()
    # Where the block was not the request's last, its commit had the next
    # block's first denoise pass aboard: that row-pass is dropped with the
    # request (and the pass dispatched after it), never logged.
    last = req.pass_log[-1]
    assert -1 not in last["left"] and last["entered"] == last["left"]
    assert last["start"] == (10 + at) // 4 * 4
    if last["start"] + 4 < 10 + 12:
        assert engine.stats()["diffusion"]["commits_aboard"] >= 1
        assert engine.stats()["steps"]["dropped_rows"] >= 1


def test_cancel_and_fail_all_in_mid_block(tiny):
    engine = _engine(tiny)
    reqs = [engine.add_request(_ids(n, 40 + n), 16) for n in (6, 11, 13)]
    while not all(r.passes >= 2 for r in reqs):
        engine.step()
    assert engine.cancel(reqs[0].request_id)
    engine.step()
    assert engine.fail_all("boom") == 2
    assert all(r.state == eng.FAILED for r in reqs)
    engine.check_no_leaks()
    # and the engine serves on, from a fresh cache
    req = engine.add_request(_ids(9, 50), 6)
    engine.run_until_idle()
    assert req.generated == tiny[2](_ids(9, 50), 6)
    engine.check_no_leaks()


def test_through_the_engine_loop_streamed(tiny):
    engine = _engine(tiny)
    loop = EngineLoop(engine)
    seen = {}
    try:
        reqs = [loop.submit(_ids(n, 60 + n), k, on_token=lambda r, t:
                            seen.setdefault(r.request_id, []).append(t))
                for n, k in MIX]
        import time

        deadline = time.monotonic() + 60
        while not all(r.done for r in reqs) and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        loop.stop()
    for req, (n, k) in zip(reqs, MIX):
        assert req.generated == tiny[2](_ids(n, 60 + n), k)
        assert seen[req.request_id] == req.generated


def test_what_construction_refuses(tiny):
    model, params, _ = tiny
    with pytest.raises(ValueError):
        _engine(tiny, spec_decode_draft_len=2)
    with pytest.raises(ValueError, match="decodes by blocks"):
        InferenceEngine(
            EngineConfig(batch_slots=2, block_size=8, num_blocks=16,
                         max_blocks_per_seq=4, prefill_chunk=16,
                         spec_decode_draft_len=2),
            model=model, params=params, draft_model=model,
            draft_params=params)
    with pytest.raises(ValueError, match="multiples of the model's block"):
        _engine(tiny, prefill_chunk=18)
    with pytest.raises(ValueError, match="multiples of the model's block"):
        _engine(tiny, block_size=6, prefill_chunk=12)
    # the context a request may reach is whole blocks
    engine = _engine(tiny, max_blocks_per_seq=2)
    engine.add_request(_ids(9, 1), 7)
    with pytest.raises(ValueError):
        engine.add_request(_ids(9, 1), 8)


def test_a_one_token_model_keeps_the_class_path():
    """The block path is chosen where the programs are built, once: an
    engine over any other model has none of it bound (its programs'
    lowered text is held by tests/test_engine_model_contract.py and
    tests/test_brumby.py)."""
    engine = InferenceEngine(EngineConfig(batch_slots=2, num_blocks=16))
    assert engine._block is None and engine._token_block == 1
    for name in ("_decode_step", "_harvest", "_chunk_dispatched"):
        assert name not in vars(engine)
        assert getattr(engine, name).__func__ is getattr(InferenceEngine,
                                                         name)
    assert "diffusion" not in engine.stats()
    assert engine._tokens.shape == (2,)
