"""The program's own step phases (`engine.*`, docs/OBSERVABILITY.md) in
the trace reduction: on hand-made events, on a trace taken here on the
CPU, and on a cut of a serve trace recorded on the chip (PR 24). The
reduction (`xplane.digest`) needs nothing new for them; what keeps them
out of a benchmark run today is `xplane.load`'s default prefix
(PERF.md, Open questions)."""

import json
import os
import threading

import pytest

import _paths
from benchmarks import xplane
from ray_tpu.inference import engine as eng

MS = 1_000_000
PROGRAM_SPAN = "engine."


def ev(name, start_ms, dur_ms, detail=""):
    return [name, int(start_ms * MS), int(dur_ms * MS), detail]


def cut(name):
    with open(os.path.join(_paths.DATA, name)) as f:
        return json.load(f)


def host_events(trace):
    return [(line["name"], e) for p in trace["planes"]
            if not xplane.DEVICE_PLANE.match(p["name"])
            for line in p["lines"] for e in line["events"]]


def test_phases_name_the_idle_gaps_of_a_hand_made_trace():
    """Two decode programs 6 ms apart, a third 1 ms later: each gap takes
    the name of the phase that overlaps it most."""
    ops = [ev("marker", 0, 1), ev("fusion.1", 1, 99),
           ev("fusion.1", 106, 94), ev("fusion.1", 201, 98),
           ev("marker", 299, 1)]
    host = [ev("engine.decode.sync", 5, 95.2),
            ev("engine.decode.emit", 100.2, 1),
            ev("engine.callbacks", 101.2, 0.6), ev("engine.admit", 101.8, 0.2),
            ev("engine.decode.host", 102, 1.2),
            ev("engine.decode.dispatch", 103.2, 3.3),
            ev("engine.decode.sync", 106.5, 93.6),
            ev("engine.decode.emit", 200.1, 0.7),
            ev("engine.decode.dispatch", 200.8, 0.4)]
    dig = xplane.digest({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ev("jit_decode_fn(7)", 1, 99),
                ev("jit_decode_fn(7)", 106, 94),
                ev("jit_decode_fn(7)", 201, 98)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]})
    gaps = dict(dig["breakdown"]["idle_gaps"])
    assert gaps == {"engine.decode.dispatch": pytest.approx(0.006),
                    "engine.decode.emit": pytest.approx(0.001)}
    assert dig["idle_gap_counts"] == {"engine.decode.dispatch": 1,
                                      "engine.decode.emit": 1}


def test_load_keeps_the_phases_of_a_real_trace_when_asked(tmp_path):
    """A profile of a tiny engine taken here: `load` keeps `engine.*`
    host events under `host_prefixes`, and drops them by default."""
    import jax

    engine = eng.InferenceEngine(eng.EngineConfig(use_jit=False))
    jax.profiler.start_trace(str(tmp_path))
    try:
        loop = eng.EngineLoop(engine)
        done = threading.Event()
        loop.submit([1, 2, 3], 3, on_finish=lambda r: done.set())
        assert done.wait(120)
        loop.stop()
    finally:
        jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    kept = host_events(xplane.load(
        path, host_prefixes=(xplane.BENCH_SPAN, PROGRAM_SPAN)))
    assert {e[0] for _, e in kept} == set(eng.PHASES)
    assert len({line for line, _ in kept}) == 1
    assert host_events(xplane.load(path)) == []
    assert xplane.digest(xplane.load(path)) is None     # no TPU plane


def test_the_serve_cut_holds_flat_documented_phases():
    events = host_events(cut("serve_trace_sample.json"))
    phases = [e for _, e in events if e[0].startswith(PROGRAM_SPAN)]
    assert len(phases) > 20
    assert {e[0] for e in phases} <= set(eng.PHASES)
    assert len({line for line, e in events
                if e[0].startswith(PROGRAM_SPAN)}) == 1
    phases.sort(key=lambda e: e[1])
    for before, after in zip(phases, phases[1:]):
        assert before[1] + before[2] <= after[1], (before, after)


def test_the_serve_cut_reduces_to_idle_gaps_led_by_a_phase():
    dig = xplane.digest(cut("serve_trace_sample.json"))
    gaps = dig["breakdown"]["idle_gaps"]
    assert gaps[0][0].startswith(PROGRAM_SPAN)
    idle = sum(seconds for _, seconds in gaps)
    assert dict(gaps).get("unattributed", 0.0) < 0.10 * idle
    assert xplane.module_matching(dig, r"^jit_decode_fn$")[0] >= 1
