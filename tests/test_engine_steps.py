"""The engine's step timeline from inside the program: the phase clock
(`observability/phases.py`), the step ledger in `stats()["steps"]`, and
the delivery stamp on `Request` (docs/OBSERVABILITY.md, "Step phases")."""

import glob
import threading
import time

import pytest

from ray_tpu.inference import engine as eng
from ray_tpu.inference.engine import (EngineConfig, EngineLoop,
                                      InferenceEngine)
from ray_tpu.observability.phases import PhaseClock


@pytest.fixture(scope="module")
def tiny_llama():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama, LlamaConfig

    model = Llama(LlamaConfig.tiny(seq=256))
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))()
    return model, params


@pytest.fixture(scope="module")
def two_program_llama(tiny_llama):
    """The model with its fused step hidden (`PagedModel`'s "not
    offered"): an engine over it keeps the prefill and decode programs
    alone, for the tests that are about those two."""
    model, params = tiny_llama

    class TwoPrograms(type(model)):
        paged_step_with_chunk = None

    return TwoPrograms(model.config), params


def _engine(tiny_llama, **kwargs):
    model, params = tiny_llama
    kwargs.setdefault("prefix_cache_enabled", False)
    return InferenceEngine(EngineConfig(**kwargs), model=model,
                           params=params)


def _prompt(n, base):
    return [(base + i) % 200 + 1 for i in range(n)]


# ------------------------------------------------------------ the clock


def test_phase_clock_is_flat_and_accumulates():
    clock = PhaseClock(("a", "b"))
    t0 = time.perf_counter()
    clock.enter("a")
    time.sleep(0.01)
    clock.enter("b")            # leaves "a": never two at once
    time.sleep(0.02)
    clock.leave()
    clock.leave()               # idempotent
    elapsed = time.perf_counter() - t0
    a, b = clock.seconds["a"], clock.seconds["b"]
    assert a > 0.009 and b > 0.019 and a + b <= elapsed
    clock.enter("a")
    clock.leave()
    assert clock.seconds["a"] >= a and clock.seconds["b"] == b
    with pytest.raises(KeyError):
        clock.enter("c")
        clock.leave()


def test_phase_clock_costs_microseconds_a_step():
    """Ten phases to a step, as the engine has; no profile running.
    Measured here: ~10 us a step, against a 185 ms decode step on the
    chip. The bound is loose: it catches a clock that starts doing work
    per phase, not a slow test machine."""
    clock = PhaseClock(eng.PHASES)
    steps = 2000
    t0 = time.perf_counter()
    for _ in range(steps):
        for name in eng.PHASES:
            clock.enter(name)
        clock.leave()
    per_step_us = (time.perf_counter() - t0) / steps * 1e6
    print(f"phase clock: {per_step_us:.1f} us a step of "
          f"{len(eng.PHASES)} phases")
    assert per_step_us < 500


# ----------------------------------------------------------- the ledger


def test_ledger_counts_what_the_requests_imply(tiny_llama):
    engine = _engine(tiny_llama, batch_slots=4, prefill_chunk=16)
    decode_calls, fused_calls = [], []

    def counting(name, calls):
        fn = getattr(engine, name)

        def call(*args):
            calls.append(1)
            return fn(*args)

        setattr(engine, name, call)

    counting("_decode_fn", decode_calls)
    counting("_decode_with_chunk_fn", fused_calls)
    prompts, budgets = (5, 20, 33), (3, 4, 5)
    reqs = [engine.add_request(_prompt(n, 7 * n), max_new_tokens=m)
            for n, m in zip(prompts, budgets)]
    assert engine.step_stats()["n"] == 0
    ran = 0
    while engine.has_work():
        ran += bool(engine.step())
    assert all(r.state == eng.FINISHED for r in reqs)
    steps = engine.stats()["steps"]
    assert steps == engine.step_stats()
    assert steps["n"] == ran
    # One execution per chunk of 16. Each request's LAST chunk runs alone:
    # the first's finds no row decoding yet, and by the others' the one
    # request ahead has its whole budget dispatched (2, then 3 decode
    # rows, as many as the chunks before the last that rode with them).
    assert steps["prefill"] + steps["chunks_aboard"] == 1 + 2 + 3
    assert (steps["prefill"], steps["chunks_aboard"]) == (3, 1 + 2)
    assert steps["chunks_aboard"] == len(fused_calls)
    # A request's first token comes from its last chunk, the rest from
    # decode rows.
    assert steps["decode_rows"] == sum(m - 1 for m in budgets)
    assert steps["decode"] == len(decode_calls) + len(fused_calls)
    assert steps["decode_rows"] <= steps["decode"] * 4
    assert set(steps["phase_s"]) == set(eng.PHASES)
    in_step = sum(v for k, v in steps["phase_s"].items()
                  if k != eng.WAIT_WORK)
    # No loop: nothing was parked, and the phases are the step.
    assert steps["wait_work_s"] == 0.0 == steps["phase_s"][eng.WAIT_WORK]
    assert 0.95 * steps["wall_s"] <= in_step <= steps["wall_s"]
    engine.check_no_leaks()


@pytest.fixture(scope="module")
def tiny_deepseek():
    """A model that offers the fused step (docs/INFERENCE.md, "The model
    contract"): two layers, one of experts."""
    import jax

    from ray_tpu.models.deepseek_v3 import DeepseekV3, DeepseekV3Config

    model = DeepseekV3(DeepseekV3Config.tiny(num_hidden_layers=2))
    return model, model.init(jax.random.PRNGKey(0))


def test_ledger_counts_the_chunks_that_rode_and_those_that_ran_alone(
        tiny_deepseek):
    engine = _engine(tiny_deepseek, batch_slots=3, block_size=16,
                     prefill_chunk=16)
    calls = {"decode": 0, "decode_with_chunk": 0, "prefill": 0}

    def counting(name):
        fn = getattr(engine, f"_{name}_fn")

        def call(*args):
            calls[name] += 1
            return fn(*args)

        setattr(engine, f"_{name}_fn", call)

    for name in calls:
        counting(name)
    # Its chunk finds no row decoding: alone. Then it decodes, and every
    # chunk of the two that arrive (three and one) rides in its steps.
    first = engine.add_request(_prompt(5, 1), max_new_tokens=12)
    assert engine.step()
    steps = engine.step_stats()
    assert (steps["prefill"], steps["chunks_aboard"], steps["decode"]) \
        == (1, 0, 1)
    budgets = (12, 4, 5)
    reqs = [first, engine.add_request(_prompt(40, 9), max_new_tokens=4),
            engine.add_request(_prompt(3, 60), max_new_tokens=5)]
    engine.run_until_idle()
    assert all(r.state == eng.FINISHED for r in reqs)
    steps = engine.stats()["steps"]
    assert (steps["prefill"], steps["chunks_aboard"]) == (1, 4)
    assert calls["prefill"] == 1 and calls["decode_with_chunk"] == 4
    # A fused execution is a decode step with a chunk aboard, and no
    # prefill execution.
    assert steps["decode"] == calls["decode"] + calls["decode_with_chunk"]
    assert steps["decode_ahead"] == steps["decode"] - 1
    # A request's first token comes from its last chunk, aboard or alone,
    # the rest from decode rows.
    assert steps["decode_rows"] == sum(m - 1 for m in budgets)
    assert steps["dropped_rows"] == 0
    # With nobody decoding a chunk runs alone again.
    last = engine.add_request(_prompt(20, 5), max_new_tokens=2)
    engine.run_until_idle()
    steps = engine.step_stats()
    assert (steps["prefill"], steps["chunks_aboard"]) == (3, 4)
    assert len(last.generated) == 2
    stats = engine.stats()
    assert stats["prefill_compiles"] == stats["decode_compiles"] \
        == stats["decode_with_chunk_compiles"] == 1
    engine.check_no_leaks()


def test_ledger_counts_a_speculative_round_as_a_decode(tiny_llama):
    engine = _engine(tiny_llama, batch_slots=2, spec_decode_draft_len=3)
    req = engine.add_request(_prompt(6, 3), max_new_tokens=9)
    engine.run_until_idle()
    steps = engine.step_stats()
    assert len(req.generated) == 9
    assert steps["decode"] == engine.stats()["spec_decode"]["rounds"] > 0
    assert steps["decode_rows"] == steps["decode"]     # one row a round
    assert steps["phase_s"][eng.DECODE_SYNC] > 0


def test_phases_cover_the_engine_thread(tiny_llama):
    engine = _engine(tiny_llama, batch_slots=4)
    engine.add_request(_prompt(4, 1), max_new_tokens=2)
    engine.run_until_idle()             # compile outside the timed part
    before = engine.step_stats()
    t0 = time.perf_counter()
    loop = EngineLoop(engine)
    try:
        done = threading.Event()
        for i in range(3):
            loop.submit(_prompt(20 + i, 11 * i), 12,
                        on_finish=lambda r: done.set() if r.arrival == 3
                        else None)
            time.sleep(0.05)            # some of it parked, some working
        assert done.wait(60)
        time.sleep(0.2)                 # parked again
        t1 = time.perf_counter()
    finally:
        loop.stop()
    t2 = time.perf_counter()
    after = engine.step_stats()
    phase_s = {k: after["phase_s"][k] - before["phase_s"][k]
               for k in eng.PHASES}
    wall = after["wall_s"] - before["wall_s"]
    parked = after["wait_work_s"] - before["wait_work_s"]
    assert parked == phase_s[eng.WAIT_WORK] > 0.2
    assert sum(phase_s.values()) >= 0.95 * (wall + parked)
    # ... and the two together are the thread's whole life.
    assert 0.95 * (t1 - t0) <= wall + parked <= t2 - t0
    assert all(phase_s[k] > 0 for k in eng.PHASES)


def test_step_stats_does_not_wait_for_the_engine_lock(tiny_llama):
    engine = _engine(tiny_llama)
    engine.add_request(_prompt(4, 1), max_new_tokens=2)
    engine.run_until_idle()
    fresh = engine.stats()
    assert fresh["snapshot_age_s"] == 0.0 and fresh["steps"]["n"] >= 1
    held, release = threading.Event(), threading.Event()

    def hold():
        with engine._lock:
            held.set()
            release.wait(30)

    holder = threading.Thread(target=hold, daemon=True)
    holder.start()
    assert held.wait(10)
    try:
        time.sleep(0.05)
        steps = engine.step_stats()     # returns: it takes no lock
        assert steps == fresh["steps"]
        stale = engine.stats()          # gives up on the lock at 0.2 s
        assert stale["snapshot_age_s"] >= 0.05
        assert stale["steps"] == steps
        assert stale["tokens_emitted"] == fresh["tokens_emitted"]
    finally:
        release.set()
        holder.join(10)
    assert engine.stats()["snapshot_age_s"] == 0.0


# ----------------------------------------------------- the delivery stamp


def test_first_token_is_delivered_while_the_decode_of_its_step_runs(
        two_program_llama):
    engine = _engine(two_program_llama, batch_slots=4, prefill_chunk=16)
    seen = []
    first = engine.add_request(_prompt(5, 1), max_new_tokens=40)
    while not first.generated:
        engine.step()
    # `first` decodes, one execution in flight. `second`'s only chunk is
    # dispatched in a step with the decode it joins: the step reads the
    # decode before and the chunk, delivers `second`'s first token, and
    # leaves the decode of both rows in flight.
    second = engine.add_request(
        _prompt(9, 50), max_new_tokens=3,
        on_token=lambda r, t: seen.append((time.monotonic(), t)))
    before = engine.step_stats()
    assert engine.step()
    after = engine.step_stats()
    assert after["prefill"] - before["prefill"] == 1
    assert after["decode"] - before["decode"] == 1
    assert after["decode_ahead"] - before["decode_ahead"] == 1
    assert after["decode_rows"] - before["decode_rows"] == 1    # `first`'s
    assert after["phase_s"][eng.PREFILL_SYNC] \
        > before["phase_s"][eng.PREFILL_SYNC]
    assert [t for _, t in seen] == second.generated == second.generated[:1]
    assert second.inflight == first.inflight == 1
    assert len(engine._inflight) == 1 and engine._inflight[0].decode
    assert second.first_token_at <= second.first_token_delivered_at \
        <= seen[0][0]
    # The decode it joined is read a step later: its second token.
    assert engine.step()
    assert [t for _, t in seen] == second.generated[:2]
    assert engine.step_stats()["decode_rows"] - after["decode_rows"] == 2
    engine.run_until_idle()
    for req in (first, second):     # with a callback or without
        assert req.first_token_delivered_at >= req.first_token_at
        assert req.first_token_delivered_at <= req.finished_at + 1e-3
    engine.check_no_leaks()


def test_deliver_span_joins_the_request_trace(tiny_llama):
    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.observability import tracing

    GLOBAL_CONFIG._overrides["tracing_enabled"] = True
    tracing.refresh_from_config()
    tracing.RECORDER.drain()
    try:
        engine = _engine(tiny_llama)
        with tracing.get_tracer().start_span("client.request") as root:
            req = engine.add_request(_prompt(4, 1), max_new_tokens=1)
        engine.run_until_idle()
        spans, _ = tracing.RECORDER.drain()
    finally:
        GLOBAL_CONFIG._overrides.pop("tracing_enabled", None)
        tracing.refresh_from_config()
        tracing.RECORDER.drain()
    mine = {s["name"]: s for s in spans if s["trace_id"] == root.trace_id}
    # Its first token is its last: the span is there all the same.
    assert {"engine.queue", "engine.prefill", "engine.decode",
            "engine.deliver"} <= set(mine)
    deliver = mine["engine.deliver"]
    assert deliver["start"] == pytest.approx(
        tracing.epoch_of(req.first_token_at), abs=1e-6)
    assert deliver["end"] == pytest.approx(
        tracing.epoch_of(req.first_token_delivered_at), abs=1e-6)
    assert deliver["attrs"]["request"] == req.request_id


# ----------------------------------------------- on the profiler's clock


def test_profiler_trace_holds_flat_phases_with_the_documented_names(
        tiny_llama, tmp_path):
    import jax
    from jax.profiler import ProfileData

    engine = _engine(tiny_llama, batch_slots=4, prefill_chunk=16)
    engine.add_request(_prompt(4, 1), max_new_tokens=2)
    engine.run_until_idle()             # compiled before the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        loop = EngineLoop(engine)
        done = threading.Event()
        loop.submit(_prompt(20, 5), 4, on_finish=lambda r: done.set())
        assert done.wait(60)
        time.sleep(0.1)
        loop.stop()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    lines = [[(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for ev in line.events if ev.name.startswith("engine.")]
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines]
    lines = [sorted(evs, key=lambda e: e[1]) for evs in lines if evs]
    assert len(lines) == 1, "phases of one engine sit on one thread's line"
    events = lines[0]
    assert {name for name, _, _ in events} == set(eng.PHASES)
    for (_, _, end), (name, start, _) in zip(events, events[1:]):
        assert end <= start, f"{name} overlaps the phase before it"
