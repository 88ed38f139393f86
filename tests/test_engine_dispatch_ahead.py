"""Dispatch-ahead (docs/INFERENCE.md, "The step"): the engine keeps one
execution in flight and reads its tokens one step later. Nothing about
what is computed may change: every request receives the tokens, in the
order, of a plain one-row-at-a-time greedy loop over `Llama.decode_paged`,
whatever leaves the batch while a step is in flight."""

import functools
import random
import sys
import threading
import time

import numpy as np
import pytest

from ray_tpu.inference import engine as eng
from ray_tpu.inference.engine import (EngineConfig, EngineLoop,
                                      InferenceEngine)


@pytest.fixture(scope="module")
def tiny_llama():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama, LlamaConfig

    class TwoPrograms(Llama):
        """The fused step hidden (`PagedModel`'s "not offered"). These
        tests hold the engine's tokens EQUAL to `plain`'s, which runs the
        prefill and decode programs' own shapes; a chunk aboard a decode
        step is another shape, the tiny model's bf16 products round
        otherwise there (an argmax in ~200 moves), and which steps hold a
        chunk follows the cancelling threads' timing. The fused step's
        dispatch-ahead is `test_a_chunk_aboard_keeps_one_execution_in_flight`
        below (integers), its tokens `tests/test_llama.py`."""

        paged_step_with_chunk = None

    model = TwoPrograms(LlamaConfig.tiny(seq=256))
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))()
    return model, params


def _engine(tiny_llama, **kwargs):
    model, params = tiny_llama
    cfg = dict(batch_slots=3, block_size=4, num_blocks=64,
               max_blocks_per_seq=16, prefill_chunk=8,
               prefix_cache_enabled=False)
    cfg.update(kwargs)
    return InferenceEngine(EngineConfig(**cfg), model=model, params=params)


def _prompt(n, base):
    return [(base + 3 * i) % 200 + 1 for i in range(n)]


@pytest.fixture(scope="module")
def plain(tiny_llama):
    """`plain(engine, prompt, n)`: one request, one token at a time,
    through `Llama.decode_paged` on an arena of its own: no scheduler, no
    other row, nothing in flight. Compiled, and at the engine's shapes
    (the prompt in padded chunks, a decode batch of `batch_slots` rows of
    which one is live, the engine's context), because the tiny model
    computes in bf16 and another shape, or op-by-op execution, rounds
    differently: like this the arithmetic is the engine's own and the
    tokens must be equal, not merely close."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama, make_paged_arena

    model, params = tiny_llama
    apply = jax.jit(lambda *args: model.apply(
        *args, method=Llama.decode_paged))

    def greedy(engine, prompt, n, adapter_row=None):
        cfg = engine.config
        width = cfg.max_blocks_per_seq
        arenas = make_paged_arena(model.config, width + 1, cfg.block_size)

        def forward(ids, pos, rows, live):
            toks = np.zeros((rows, live), np.int32)
            toks[0, :len(ids)] = ids
            wmask = np.zeros((rows, live), bool)
            wmask[0, :len(ids)] = True
            bt = np.zeros((rows, width), np.int32)
            bt[0] = np.arange(1, width + 1)
            row_pos = np.zeros(rows, np.int32)
            row_pos[0] = pos
            lora = (None, None)
            if adapter_row is not None:
                aidx = np.zeros(rows, np.int32)
                aidx[0] = adapter_row
                lora = (engine._adapters.device_banks(), aidx)
            if live == 1:
                logits, new = apply(params, toks, arenas, bt, row_pos, wmask,
                                    *lora)
                return int(jnp.argmax(logits[0, 0])), new
            # A chunk's logits where the engine reads them: one position,
            # gathered before the final norm and the head.
            logits, new = apply(params, toks, arenas, bt, row_pos, wmask,
                                *lora, np.asarray([len(ids) - 1], np.int32))
            return int(jnp.argmax(logits[0])), new

        chunk = cfg.prefill_chunk
        for at in range(0, len(prompt), chunk):
            token, arenas = forward(prompt[at:at + chunk], at, 1, chunk)
        toks = [token]
        while len(toks) < n:
            token, arenas = forward(
                [toks[-1]], len(prompt) + len(toks) - 1, cfg.batch_slots, 1)
            toks.append(token)
        return toks

    return greedy


class _Streams:
    """What each request's client saw, in the order it saw it."""

    def __init__(self):
        self.tokens = {}
        self.finished = []

    def submit(self, engine, prompt, max_new_tokens, **kwargs):
        add = (engine.submit if isinstance(engine, EngineLoop)
               else engine.add_request)
        req = add(
            prompt, max_new_tokens, on_token=self._token,
            on_finish=lambda r: self.finished.append(r.request_id),
            **kwargs)
        self.tokens[req.request_id] = []
        return req

    def _token(self, req, token):
        self.tokens[req.request_id].append(token)


def _idle_and_clean(engine):
    assert not engine.has_work() and not engine._inflight
    assert all(slot is None for slot in engine._slots)
    engine.check_no_leaks()


# ------------------------------------------------------------- identity
#
# One case a behaviour. Each builds an engine and a mix, runs it dry and
# returns [(request, expected tokens)]; the test holds streams and
# `generated` to them.


def _case_eos(tiny_llama, plain):
    """`eos_id` arrives at a harvest while the next step, which holds
    the row, already runs: that row-step is computed and dropped."""
    shapes = _engine(tiny_llama)        # the same shapes, no eos_id yet
    prompt, other_prompt = _prompt(6, 40), _prompt(11, 90)
    whole = plain(shapes, prompt, 12)
    other_whole = plain(shapes, other_prompt, 9)
    # The first token that has not occurred before: the stream stops
    # there and nowhere earlier.
    cut = next(i for i in range(3, 12) if whole[i] not in whole[:i])
    other_cut = next((i for i, t in enumerate(other_whole)
                      if t == whole[cut]), 8)
    engine = _engine(tiny_llama, eos_id=whole[cut])
    streams = _Streams()
    stopped = streams.submit(engine, prompt, 12)
    other = streams.submit(engine, other_prompt, 9)
    engine.run_until_idle()
    assert engine.step_stats()["dropped_rows"] == 1 + (other_cut < 8)
    return engine, streams, [(stopped, whole[:cut + 1]),
                            (other, other_whole[:other_cut + 1])]


def _case_budgets(tiny_llama, plain, budget):
    """The budget ends with the step in flight: the row is left out of
    the next dispatch and nothing of it is dropped."""
    engine = _engine(tiny_llama)
    streams = _Streams()
    mix = [(_prompt(5, 10), budget), (_prompt(13, 60), budget),
           (_prompt(3, 120), 7), (_prompt(9, 33), budget)]
    reqs = [streams.submit(engine, p, m) for p, m in mix]
    engine.run_until_idle()
    steps = engine.step_stats()
    assert steps["dropped_rows"] == 0
    assert steps["decode_rows"] == sum(m - 1 for _, m in mix)
    return engine, streams, [
        (r, plain(engine, p, m)) for r, (p, m) in zip(reqs, mix)]


def _case_preemption(tiny_llama, plain):
    """A pool too small for both rows: the victim may hold a token in
    flight, which is dropped and computed again after its recompute."""
    engine = _engine(tiny_llama, batch_slots=2, block_size=2, num_blocks=9,
                     max_blocks_per_seq=8, prefill_chunk=4)
    streams = _Streams()
    mix = [([1, 2, 3], 10), ([4, 5, 6], 10)]
    reqs = [streams.submit(engine, p, m) for p, m in mix]
    engine.run_until_idle()
    assert reqs[0].preemptions == 0 and reqs[1].preemptions >= 1
    return engine, streams, [
        (r, plain(engine, p, m)) for r, (p, m) in zip(reqs, mix)]


def _case_cancel(tiny_llama, plain):
    """`cancel()` lands between a dispatch and its harvest."""
    engine = _engine(tiny_llama)
    streams = _Streams()
    mix = [(_prompt(5, 10), 12), (_prompt(7, 70), 12), (_prompt(4, 150), 6)]
    reqs = [streams.submit(engine, p, m) for p, m in mix]
    gone = reqs[1]
    while len(gone.generated) < 3:
        engine.step()
    assert gone.inflight == 1 and any(
        req is gone for rec in engine._inflight for req, _, _ in rec.rows)
    before = engine.step_stats()["dropped_rows"]
    assert engine.cancel(gone.request_id)
    seen = len(gone.generated)
    engine.run_until_idle()
    assert engine.step_stats()["dropped_rows"] == before + 1
    assert gone.error == "cancelled" and len(gone.generated) == seen
    whole = [plain(engine, p, m) for p, m in mix]
    return engine, streams, [(reqs[0], whole[0]), (gone, whole[1][:seen]),
                            (reqs[2], whole[2])]


def _case_adapter(tiny_llama, plain):
    from ray_tpu.models.llama import make_adapter_weights

    model, _ = tiny_llama
    engine = _engine(tiny_llama, max_adapters=2, lora_rank=8)
    seeds = {"m-a": 11, "m-b": 22}
    engine.register_adapter_source(lambda mid: make_adapter_weights(
        model.config, rank=8, seed=seeds[mid]))
    streams = _Streams()
    mix = [([1, 2, 3, 4, 5], 8, "m-a"), ([1, 2, 3, 4, 5], 8, "m-b"),
           ([7, 8, 9], 6, None)]
    reqs = [streams.submit(engine, p, m, model_id=mid) for p, m, mid in mix]
    engine.run_until_idle()
    expected = [(r, plain(engine, p, m, adapter_row=r.adapter_row))
                for r, (p, m, _) in zip(reqs, mix)]
    assert expected[0][1] != expected[1][1]     # the adapters steer
    return engine, streams, expected


def _case_prefix_cache(tiny_llama, plain):
    """The second request adopts the first one's blocks, among them the
    block whose last position was written by a step harvested late."""
    engine = _engine(tiny_llama, prefix_cache_enabled=True)
    streams = _Streams()
    shared = _prompt(16, 21)
    first = streams.submit(engine, shared + [7, 8], 7)
    engine.run_until_idle()
    grown = shared + [7, 8] + first.generated[:6]     # 24 = 6 whole blocks
    second = streams.submit(engine, grown + [9], 5)
    engine.run_until_idle()
    assert second.cached_tokens == 24
    return engine, streams, [
        (first, plain(engine, first.prompt, 7)),
        (second, plain(engine, second.prompt, 5))]


CASES = {
    "eos_with_a_step_in_flight": _case_eos,
    "max_new_tokens_1": lambda t, p: _case_budgets(t, p, 1),
    "max_new_tokens_2": lambda t, p: _case_budgets(t, p, 2),
    "preemption": _case_preemption,
    "cancel_between_dispatch_and_harvest": _case_cancel,
    "adapter_engine": _case_adapter,
    "prefix_cache_hit": _case_prefix_cache,
}


@pytest.mark.parametrize("case", list(CASES))
def test_tokens_and_order_are_those_of_a_plain_greedy_loop(tiny_llama, plain,
                                                           case):
    engine, streams, expected = CASES[case](tiny_llama, plain)
    for req, tokens in expected:
        assert req.generated == tokens, (case, req.request_id)
        assert streams.tokens[req.request_id] == tokens, req.request_id
        assert req.inflight == 0 or req.done
    assert sorted(streams.finished) == sorted(
        r.request_id for r, _ in expected)
    _idle_and_clean(engine)
    stats = engine.stats()
    assert stats["prefill_compiles"] == stats["decode_compiles"] == 1


# ---------------------------------------------------------------- order


class _Spy:
    """A program's token output that says when the host first reads it.
    Starting the copy is not a read."""

    def __init__(self, tokens, name, log):
        self.tokens, self.name, self.log = tokens, name, log
        self.shape = tokens.shape

    def copy_to_host_async(self):
        self.tokens.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        if ("read", self.name) not in self.log:
            self.log.append(("read", self.name))
        return np.asarray(self.tokens)


def _spy_on_programs(engine, log):
    """Wrap the programs: log ("call", n) per decode execution (with a
    chunk aboard or without), hand out spied token outputs, take them back
    as the next input."""
    decode_fn, prefill_fn = engine._decode_fn, engine._prefill_fn
    with_chunk_fn = engine._decode_with_chunk_fn

    def bare(args):
        return [a.tokens if isinstance(a, _Spy) else a for a in args]

    def decode(*args, fn=decode_fn):
        n = 1 + sum(1 for kind, _ in log if kind == "call")
        log.append(("call", n))
        tokens, arenas = fn(*bare(args))
        return _Spy(tokens, n, log), arenas

    def prefill(*args):
        tokens, arenas = prefill_fn(*bare(args))
        return _Spy(tokens, "prefill", log), arenas

    engine._decode_fn, engine._prefill_fn = decode, prefill
    if with_chunk_fn is not None:
        engine._decode_with_chunk_fn = functools.partial(decode,
                                                         fn=with_chunk_fn)


def test_next_decode_is_dispatched_before_the_last_one_is_read(tiny_llama,
                                                               plain):
    """Fails on a synchronous step, which reads decode n's tokens before
    it can build decode n+1's input."""
    engine = _engine(tiny_llama)
    log = []
    _spy_on_programs(engine, log)
    req = engine.add_request(_prompt(5, 10), max_new_tokens=10)
    engine.run_until_idle()
    assert req.generated == plain(engine, req.prompt, 10)
    calls = [n for kind, n in log if kind == "call"]
    assert calls == list(range(1, 10))      # the first token is the chunk's
    for n in calls[:-1]:
        assert log.index(("call", n + 1)) < log.index(("read", n)), (n, log)
    assert ("read", calls[-1]) in log
    steps = engine.step_stats()
    assert steps["decode"] == len(calls)
    assert steps["decode_ahead"] == steps["decode"] - 1
    assert steps["dropped_rows"] == 0
    _idle_and_clean(engine)


def test_a_chunk_aboard_keeps_one_execution_in_flight():
    """A model that offers the fused step (the contract test's toy): the
    row whose prompt ends aboard a decode step has its first token read a
    step later, with the rows' tokens and once, and decodes from the step
    after the one it rode in, which is dispatched before that read."""
    from test_engine_model_contract import BagModel, RidingBagModel

    def serve(model, spy):
        engine = InferenceEngine(
            EngineConfig(batch_slots=3, block_size=4, num_blocks=32,
                         max_blocks_per_seq=8, prefill_chunk=8,
                         prefix_cache_enabled=False),
            model=model, params=model.init(7))
        log = []
        if spy:
            _spy_on_programs(engine, log)
        streams = _Streams()
        first = streams.submit(engine, [t % 60 + 1 for t in _prompt(5, 10)],
                               14)
        while not first.generated:
            engine.step()
        rider = streams.submit(engine, [t % 60 + 1 for t in _prompt(11, 30)],
                               6)
        return engine, log, streams, first, rider

    engine, log, streams, first, rider = serve(RidingBagModel(), spy=True)
    assert engine.step()              # the first of its two chunks rides
    assert rider.state == eng.PREFILL and rider.inflight == 0
    before = engine.step_stats()
    assert engine.step()              # the second: its prompt ends aboard
    after = engine.step_stats()
    assert after["chunks_aboard"] - before["chunks_aboard"] == 1
    assert after["decode"] - before["decode"] == 1
    assert after["prefill"] == before["prefill"] == 1
    assert rider.state == eng.DECODE and rider.processed == 11
    assert rider.inflight == 1 and not rider.generated
    assert len(engine._inflight) == 1 and engine._inflight[0].decode
    assert engine._inflight[0].first is rider
    rode_in = max(n for kind, n in log if kind == "call")
    assert ("read", rode_in) not in log
    assert engine.step()              # it decodes; then its first is read
    assert rider.processed == 12 and rider.inflight == 1
    assert len(rider.generated) == 1 == len(streams.tokens[rider.request_id])
    assert log.index(("call", rode_in + 1)) < log.index(("read", rode_in))
    engine.run_until_idle()
    calls = [n for kind, n in log if kind == "call"]
    for n in calls[:-1]:
        assert log.index(("call", n + 1)) < log.index(("read", n)), (n, log)
    steps = engine.step_stats()
    assert steps["decode_ahead"] == steps["decode"] - 1 == len(calls) - 1
    assert steps["dropped_rows"] == 0
    assert steps["decode_rows"] == (14 - 1) + (6 - 1)
    _idle_and_clean(engine)
    # The tokens, and their order at the clients, are those of the two
    # programs apart.
    plain_engine, _, plain_streams, *want = serve(BagModel(), spy=False)
    plain_engine.run_until_idle()
    assert plain_engine.step_stats()["chunks_aboard"] == 0
    for got, req in zip((first, rider), want):
        assert streams.tokens[got.request_id] == got.generated \
            == req.generated == plain_streams.tokens[req.request_id]
        assert len(got.generated) == got.max_new_tokens
    assert sorted(streams.finished) == sorted(
        r.request_id for r in (first, rider))


def test_speculation_stays_synchronous(tiny_llama, plain):
    engine = _engine(tiny_llama, spec_decode_draft_len=2)
    req = engine.add_request(_prompt(5, 10), max_new_tokens=10)
    while engine.has_work():
        engine.step()
        assert not engine._inflight     # read in the step that dispatched
    assert req.generated == plain(engine, req.prompt, 10)
    steps = engine.step_stats()
    assert steps["decode"] > 0 and steps["decode_ahead"] == 0
    assert steps["dropped_rows"] == 0
    _idle_and_clean(engine)


def test_a_busy_batch_runs_ahead_on_almost_every_decode(tiny_llama):
    engine = _engine(tiny_llama)
    for i in range(7):     # one chunk each: a slot refills in one step
        engine.add_request(_prompt(3 + i % 5, 17 * i), max_new_tokens=6 + i)
    engine.run_until_idle()
    steps = engine.step_stats()
    # Back to back from the first decode to the last: slots given up at
    # dispatch are refilled without an idle step in between.
    assert steps["decode_ahead"] == steps["decode"] - 1
    assert steps["dropped_rows"] == 0
    _idle_and_clean(engine)


# ------------------------------------------------- in flight is work


def test_an_unread_execution_is_work_and_is_drained(tiny_llama, plain):
    engine = _engine(tiny_llama)
    seen = []
    req = engine.add_request(_prompt(5, 10), max_new_tokens=1,
                             on_token=lambda r, t: seen.append(t))
    assert engine.step()
    # Its only chunk is dispatched, its budget with it: the slot is free
    # again, nothing waits, and yet the engine is not idle.
    assert req.slot is None and req.inflight == 1 and not seen
    assert all(slot is None for slot in engine._slots)
    assert len(engine._inflight) == 1 and engine.has_work()
    assert engine.stats()["kv"]["blocks_in_use"] > 0    # kept to the end
    assert engine.step()                # nothing to dispatch: it drains
    assert seen == req.generated == plain(engine, req.prompt, 1)
    assert req.state == eng.FINISHED
    assert not engine.step()
    _idle_and_clean(engine)


def test_fail_all_fails_a_row_that_gave_up_its_slot(tiny_llama, plain):
    engine = _engine(tiny_llama, batch_slots=1)
    finished = []
    last = engine.add_request(_prompt(5, 10), max_new_tokens=1,
                              on_finish=finished.append)
    queued = engine.add_request(_prompt(6, 30), max_new_tokens=4,
                                on_finish=finished.append)
    engine.step()
    assert last.slot is None and last.inflight == 1
    assert engine.fail_all("injected") == 2
    assert finished == [last, queued]
    assert last.state == queued.state == eng.FAILED and not last.generated
    _idle_and_clean(engine)
    again = engine.add_request(_prompt(5, 10), max_new_tokens=3)
    engine.run_until_idle()
    assert again.generated == plain(engine, again.prompt, 3)
    _idle_and_clean(engine)


def test_loop_stop_leaves_nothing_in_flight(tiny_llama, plain):
    engine = _engine(tiny_llama)
    loop = EngineLoop(engine)
    finished = threading.Event()
    try:
        reqs = [loop.submit(_prompt(5 + i, 10 * i), 50,
                            on_finish=lambda r: finished.set())
                for i in range(2)]
        deadline = time.monotonic() + 120
        while not all(len(r.generated) >= 3 for r in reqs):
            assert time.monotonic() < deadline and loop._thread.is_alive()
            time.sleep(0.005)
    finally:
        loop.stop()
    assert not loop._thread.is_alive() and finished.is_set()
    assert all(r.state == eng.FAILED for r in reqs)
    for req in reqs:
        assert req.generated == plain(engine, req.prompt,
                                      len(req.generated))
    _idle_and_clean(engine)


def test_cancel_from_other_threads_while_the_loop_runs_ahead(tiny_llama,
                                                             plain):
    """Stress: more cancelling threads than cores would need, a short
    switch interval. Whatever the interleaving, a stream is a prefix of
    its plain stream, every request ends once, and nothing leaks."""
    engine = _engine(tiny_llama, batch_slots=3, num_blocks=40)
    loop = EngineLoop(engine)
    streams = _Streams()
    rng = random.Random(7)
    mix = [(_prompt(3 + i % 9, 13 * i), 4 + i % 11) for i in range(24)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reqs = [streams.submit(loop, p, m) for p, m in mix]
        doomed = rng.sample(reqs, 10)
        stop = threading.Event()

        def canceller(mine):
            for req in mine:
                while not req.generated and not req.done \
                        and not stop.is_set():
                    stop.wait(0.001)
                engine.cancel(req.request_id)

        threads = [threading.Thread(target=canceller, args=(doomed[i::5],),
                                    daemon=True) for i in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        deadline = time.monotonic() + 120
        while not all(r.done for r in reqs) and time.monotonic() < deadline:
            time.sleep(0.02)
        stop.set()
        assert all(not t.is_alive() for t in threads)
        assert all(r.done for r in reqs)
    finally:
        sys.setswitchinterval(interval)
        loop.stop()
    assert sorted(streams.finished) == sorted(r.request_id for r in reqs)
    for req, (prompt, budget) in zip(reqs, mix):
        whole = plain(engine, prompt, budget)
        got = streams.tokens[req.request_id]
        assert got == req.generated == whole[:len(got)], req.request_id
        if req not in doomed:
            assert req.state == eng.FINISHED and got == whole
    _idle_and_clean(engine)
