"""Lifecycle spans: the start-up timeline the program keeps itself
(ray_tpu/observability: `Tracer.lifecycle_span`, `startup_report`,
`compile_watch`; docs/OBSERVABILITY.md "Start-up timeline").

Recorded with `tracing_enabled` off, bounded, carried across a spawned
worker and an actor by `startup_id` / `parent_id`, written under the
session directory (and still there after `shutdown()`), read back as
self times, a critical path and uncovered time; jax's trace / lower /
compile per program; and what a span costs.
"""

import contextlib
import json
import os
import signal
import subprocess
import sys
import time

import pytest


def _tracing():
    from ray_tpu.observability import tracing

    return tracing


@contextlib.contextmanager
def time_limit(seconds: float):
    """The test's own limit on waiting for a cluster: a hang fails here,
    by name, and not at the suite's limit."""
    def _late(signum, frame):
        raise TimeoutError(f"no answer from the cluster in {seconds} s")

    old = signal.signal(signal.SIGALRM, _late)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture()
def fresh_ring():
    tracing = _tracing()
    tracing._forget_lifecycle_for_tests()
    yield tracing
    tracing._forget_lifecycle_for_tests()


# --------------------------------------------------------------------- #
# the record itself
# --------------------------------------------------------------------- #


def test_recorded_with_tracing_off_and_only_inside_a_startup(fresh_ring):
    tracing = fresh_ring
    assert not tracing.enabled()
    tracer = tracing.get_tracer()
    # No start-up in this context: an ordinary site records nothing and
    # allocates nothing (the shared no-op), a rare event records.
    assert tracer.lifecycle_span("serve.deploy") is tracing.NOOP_SPAN
    assert tracer.record_lifecycle("raylet.lease", 1.0, 2.0) is None
    assert tracing.startup_ctx() is None
    with tracer.lifecycle_span("worker.spawn", always=True,
                               role="raylet") as rare:
        assert tracing.startup_ctx() is None    # rare, yet no start-up
    t0 = time.monotonic()
    with tracer.lifecycle_span("serve.run", root=True,
                               attrs={"deployments": ["d"]}) as root:
        assert tracing.startup_ctx() == (root.startup_id, root.span_id)
        with tracer.lifecycle_span("serve.deploy") as child:
            child.set_attr("replicas", 1)
        waited = tracer.record_lifecycle(
            "serve.wait_ready", t0, time.monotonic(),
            attrs={"polls": 3, "slept_s": 0.1})
    assert tracing.startup_ctx() is None
    spans, dropped = tracing.LIFECYCLE.snapshot()
    assert dropped == 0
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"worker.spawn", "serve.run", "serve.deploy",
                            "serve.wait_ready"}
    assert by_name["worker.spawn"]["startup_id"] is None
    assert by_name["worker.spawn"]["role"] == "raylet"
    assert by_name["worker.spawn"]["span_id"] == rare.span_id
    run = by_name["serve.run"]
    assert run["parent_id"] is None and run["pid"] == os.getpid()
    assert run["role"] == tracing.role()
    for name in ("serve.deploy", "serve.wait_ready"):
        assert by_name[name]["startup_id"] == run["startup_id"]
        assert by_name[name]["parent_id"] == run["span_id"]
    assert by_name["serve.wait_ready"]["span_id"] == waited
    assert by_name["serve.deploy"]["attrs"] == {"replicas": 1}
    # time.monotonic(), the clock of every process of this host
    assert t0 <= run["start"] <= run["end"] <= time.monotonic()
    # Nothing went to the flight recorder: that is `tracing_enabled`'s.
    assert not any(s["name"] == "serve.run"
                   for s in tracing.RECORDER.drain()[0])


def test_a_root_inside_a_startup_is_a_child_of_it(fresh_ring):
    tracer = fresh_ring.get_tracer()
    with tracer.lifecycle_span("train.startup", root=True) as outer:
        with tracer.lifecycle_span("serve.run", root=True) as inner:
            pass
    assert inner.startup_id == outer.startup_id
    assert inner.parent_id == outer.span_id


def test_an_error_is_kept_on_the_span(fresh_ring):
    tracer = fresh_ring.get_tracer()
    with pytest.raises(RuntimeError):
        with tracer.lifecycle_span("serve.run", root=True):
            raise RuntimeError("no chip")
    (span,), _ = fresh_ring.LIFECYCLE.snapshot()
    assert span["error"] == "RuntimeError: no chip"


def test_ring_is_bounded_and_counts_what_it_drops(fresh_ring):
    tracing = fresh_ring
    ring = tracing.LifecycleRing(cap=8)
    for i in range(20):
        ring.record({"name": "x", "i": i})
    spans, dropped = ring.snapshot()
    assert len(spans) == 8 and dropped == 12
    assert [s["i"] for s in spans] == list(range(12, 20))   # drop-oldest
    # The process's own ring: 512, whatever is thrown at it.
    tracer = tracing.get_tracer()
    for _ in range(600):
        tracer.record_lifecycle("worker.spawn", 0.0, 1.0, always=True)
    spans, dropped = tracing.LIFECYCLE.snapshot()
    assert len(spans) == 512 and dropped == 88


def test_a_span_in_memory_costs_microseconds(fresh_ring):
    """In the manner of tests/test_engine_steps.py: measured here ~3 us a
    recorded span and ~0.15 us at a site outside any start-up; a process
    records tens of them in a start-up of tens of seconds."""
    tracer = fresh_ring.get_tracer()
    n = 5000
    t0 = time.perf_counter()
    for _ in range(n):
        with tracer.lifecycle_span("worker.spawn", always=True) as span:
            span.set_attr("kind", "forge")
    recorded_us = (time.perf_counter() - t0) / n * 1e6
    t0 = time.perf_counter()
    for _ in range(n):
        with tracer.lifecycle_span("raylet.lease"):
            pass
    outside_us = (time.perf_counter() - t0) / n * 1e6
    print(f"lifecycle span: {recorded_us:.2f} us recorded, "
          f"{outside_us:.2f} us outside a start-up")
    assert recorded_us < 20
    assert outside_us < 5


# --------------------------------------------------------------------- #
# reading: self time, critical path, uncovered time
# --------------------------------------------------------------------- #


def _span(name, start, end, span_id, parent=None, sid="s1", pid=1,
          role="driver", **attrs):
    return {"name": name, "start": float(start), "end": float(end),
            "span_id": span_id, "parent_id": parent, "startup_id": sid,
            "pid": pid, "role": role, "attrs": attrs or None,
            "error": None}


def test_self_time_critical_path_and_uncovered_on_hand_made_spans():
    from ray_tpu.observability import startup

    spans = [
        _span("serve.run", 0, 20, "root", deployments=["d"]),
        # 0-1 nobody; controller 1-3; 3-4 nobody; deploy 4-19; 19-20 nobody
        _span("serve.controller.start", 1, 3, "ctl"),
        _span("serve.deploy", 4, 19, "dep", "root", pid=2,
              role="controller"),
        # a pure wait beside the deployment: covers time, causes nothing
        _span("serve.wait_ready", 4, 19.05, "wait", "root", pid=2,
              role="controller", polls=300, slept_s=15.0),
        _span("serve.replica.start", 5, 19, "rep", "dep", pid=2,
              role="controller", polls=140, slept_s=13.9),
        _span("actor.create", 5, 18, "act", "rep", role="gcs"),
        _span("raylet.lease", 5, 11, "lease", "act", role="raylet",
              retries=1, slept_s=5.0, waited_for="chips_busy"),
        _span("worker.spawn", 6, 7, "spawn", "lease", role="raylet"),
        _span("worker.boot", 7, 10, "boot", "lease", pid=3, role="worker"),
        _span("serve.replica.ctor", 11, 18, "ctor", "act", pid=3,
              role="replica"),
        # recorded with no start-up of its own (a program compiled inside
        # the constructor): taken in through its parent
        _span("jax.compile", 12, 16, "jc", "ctor", sid=None, pid=3,
              role="replica", fun_name="decode_fn", cache="hit"),
        # another start-up's span, and one from before this one: not ours
        _span("serve.deploy", 2, 30, "other", None, sid="s2"),
        _span("worker.spawn", -5, -4, "old", None, sid=None),
    ]
    st = startup.analyse(spans, spans[0])
    assert st["duration_s"] == 20
    self_s = st["self_s"]
    # duration minus what the children cover
    assert self_s["root"] == pytest.approx(2.95)   # 0-1, 3-4, 19.05-20
    assert self_s["dep"] == pytest.approx(1.0)     # 4-5
    assert self_s["rep"] == pytest.approx(1.0)     # 18-19
    assert self_s["act"] == pytest.approx(0.0)
    assert self_s["lease"] == pytest.approx(2.0)   # 5-6, 10-11
    assert self_s["ctor"] == pytest.approx(3.0)    # 11-12, 16-18
    assert self_s["jc"] == pytest.approx(4.0)
    assert "other" not in self_s and "old" not in self_s
    # Seconds of the root no other span covers: 0-1, 3-4, 19.05-20.
    assert st["uncovered_s"] == pytest.approx(2.95)
    path = {p["span_id"]: p["path_s"] for p in st["critical_path"]}
    # The wait yields to the deployment it waited for; the controller
    # came before it on the way to the end.
    assert "wait" not in path
    assert list(p["span_id"] for p in st["critical_path"]) == [
        "root", "ctl", "dep", "rep", "act", "lease", "spawn", "boot",
        "ctor", "jc"]
    assert path["root"] == pytest.approx(3.0)      # 0-1, 3-4, 19-20
    assert path["lease"] == pytest.approx(2.0)
    assert path["boot"] == pytest.approx(3.0)
    assert path["jc"] == pytest.approx(4.0)
    # The path's seconds are the root's duration: every second of it
    # belongs to exactly one step.
    assert sum(path.values()) == pytest.approx(20.0)
    text = startup.format_waterfall(
        {"session_dir": "x", "processes": [], "spans": spans,
         "startups": [st]})
    assert "serve.run" in text and "uncovered 2.950 s" in text
    assert "'slept_s': 5.0" in text and "critical path" in text


def test_a_train_root_ends_at_the_latest_ranks_mark():
    from ray_tpu.observability import startup

    spans = [
        _span("train.startup", 0, 100, "root", workers=2, chips=2),
        _span("train.executor.start", 0, 4, "exec", "root"),
        _span("train.backend.on_start", 4, 9, "back", "root"),
        _span("train.loop.enter", 9.2, 9.2, "m0", "root", pid=2, rank=0),
        _span("train.loop.enter", 9.5, 9.5, "m1", "root", pid=3, rank=1),
    ]
    st = startup.analyse(spans, spans[0])
    # fit() returned at 100 (training over); the start-up ended at 9.5.
    assert st["root"]["end"] == 9.5 and st["duration_s"] == 9.5
    assert st["uncovered_s"] == pytest.approx(0.5)
    assert sum(p["path_s"] for p in st["critical_path"]) \
        == pytest.approx(9.5)


def test_files_of_a_session_merge_and_bad_lines_are_skipped(tmp_path):
    from ray_tpu.observability import startup_report

    folder = tmp_path / "lifecycle"
    folder.mkdir()
    root = _span("serve.run", 0, 2, "root")
    boot = _span("worker.boot", 0.6, 1.0, "boot", None, sid=None, pid=7,
                 role="worker", worker="w1")
    spawn = _span("worker.spawn", 0.5, 0.6, "spawn", "root", role="raylet",
                  worker="w1")
    (folder / "1-aa.jsonl").write_text("\n".join(json.dumps(x) for x in (
        {"kind": "process", "pid": 1, "role": "driver", "dropped": 2},
        root, spawn)) + "\n")
    (folder / "7-bb.jsonl").write_text(
        json.dumps({"kind": "process", "pid": 7, "role": "worker",
                    "dropped": 0}) + "\n" + json.dumps(boot)
        + "\n{\"name\": \"half a li")
    report = startup_report(str(tmp_path))
    assert {p["pid"] for p in report["processes"]} == {1, 7}
    assert len(report["spans"]) == 3
    (st,) = report["startups"]
    # The boot that was not told its cause is put beside its spawn by
    # the worker id both carry.
    linked = next(s for s in st["spans"] if s["name"] == "worker.boot")
    assert linked["parent_id"] == "root" and linked["startup_id"] == "s1"
    assert startup_report(str(tmp_path / "nowhere"))["startups"] == []


# --------------------------------------------------------------------- #
# across processes
# --------------------------------------------------------------------- #


class Held:
    """An actor whose constructor records a span of its own, as a replica
    does."""

    def __init__(self):
        from ray_tpu.observability import tracing

        with tracing.get_tracer().lifecycle_span("held.ctor", flush=True):
            self.ctx = tracing.startup_ctx()

    def startup_ctx(self):
        from ray_tpu.observability import tracing

        # The constructor's context; a later call carries none.
        return self.ctx, tracing.startup_ctx()


def _probe():
    from ray_tpu.observability import tracing

    with tracing.get_tracer().lifecycle_span("probe.task", flush=True):
        return os.getpid(), tracing.startup_ctx()


def test_startup_id_and_parent_cross_a_spawned_worker_and_an_actor():
    """One start-up, tracing off: the root in the driver, the actor's
    creation in the GCS, its lease and the spawn in the raylet, the boot
    and the constructor in the worker, a task in another worker: one
    `startup_id`, each span naming what caused it; the files are there
    after `shutdown()`, and the report needs no cluster."""
    import ray_tpu
    from ray_tpu.observability import format_waterfall, startup_report

    tracing = _tracing()
    with time_limit(10):
        ray_tpu.shutdown()
        ray_tpu.init(num_cpus=2)
        assert not tracing.enabled()
        tracer = tracing.get_tracer()
        with tracer.lifecycle_span("serve.run", root=True) as root:
            held = ray_tpu.remote(Held).remote()
            in_ctor, later = ray_tpu.get(held.startup_ctx.remote())
            task_pid, in_task = ray_tpu.get(
                ray_tpu.remote(_probe).remote())
        # Outside the start-up nothing is carried, so nothing records.
        _, after = ray_tpu.get(ray_tpu.remote(_probe).remote())
        ray_tpu.shutdown()
    sid = root.startup_id
    assert in_ctor[0] == sid and in_task[0] == sid
    assert later is None or later[0] == sid    # the call was made inside
    assert after is None
    session = tracing.session_dir()
    files = os.listdir(os.path.join(session, "lifecycle"))
    assert len(files) >= 3, files               # driver, actor, task worker
    report = startup_report()                   # no cluster, no argument
    assert report["session_dir"] == session
    (st,) = [s for s in report["startups"]
             if s["root"]["startup_id"] == sid]
    spans = {s["span_id"]: s for s in st["spans"]}
    by_name = {}
    for s in st["spans"]:
        by_name.setdefault(s["name"], []).append(s)

    def parent_of(s):
        return spans.get(s["parent_id"])

    ctor, = by_name["held.ctor"]
    create = parent_of(ctor)
    assert create["name"] == "actor.create" and create["role"] == "gcs"
    assert parent_of(create)["span_id"] == root.span_id
    lease = next(s for s in by_name["raylet.lease"]
                 if s["parent_id"] == create["span_id"])
    assert lease["role"] == "raylet"
    assert lease["attrs"]["worker_pid"] == ctor["pid"]
    spawn = next(s for s in by_name["worker.spawn"]
                 if s["parent_id"] == lease["span_id"])
    boot = next(s for s in by_name["worker.boot"]
                if s["pid"] == ctor["pid"])
    assert boot["parent_id"] == lease["span_id"]
    assert boot["attrs"]["worker"] == spawn["attrs"]["worker"]
    assert boot["role"] == "worker" and boot["attrs"]["import_s"] >= 0
    assert ctor["pid"] != os.getpid()
    # The task: its lease in the raylet, its span in another worker.
    probe, = by_name["probe.task"]
    assert probe["pid"] == task_pid != ctor["pid"]
    assert any(s["attrs"].get("task") for s in by_name["raylet.lease"]
               if s["parent_id"] == root.span_id)
    assert all(s["startup_id"] == sid for s in st["spans"])
    # One clock: every span of every process lies inside the root.
    for s in st["spans"]:
        assert root.start - 0.05 <= s["start"] <= s["end"], s
    assert sum(p["path_s"] for p in st["critical_path"]) \
        == pytest.approx(st["duration_s"])
    assert "held.ctor" in format_waterfall(report)
    # The CLI reads the same directory, in a process that never had a
    # cluster.
    env = {**os.environ, "PYTHONPATH": os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))}
    env.pop("RAY_TPU_SESSION_DIR", None)
    cli = subprocess.run(
        [sys.executable, "-m", "ray_tpu.observability", "startup",
         "--session", session], capture_output=True, text=True, env=env,
        timeout=30)
    assert cli.returncode == 0, cli.stderr[-2000:]
    assert "actor.create" in cli.stdout and "critical path" in cli.stdout


def test_with_tracing_on_the_same_spans_reach_the_timeline():
    import ray_tpu
    from ray_tpu.observability import chrome_trace_events

    tracing = _tracing()
    with time_limit(10):
        ray_tpu.shutdown()
        ray_tpu.init(num_cpus=1,
                     _system_config={"tracing_enabled": True,
                                     "trace_sample_rate": 1.0})
        try:
            tracer = tracing.get_tracer()
            with tracer.lifecycle_span("serve.run", root=True) as root:
                held = ray_tpu.remote(Held).remote()
                ray_tpu.get(held.startup_ctx.remote())
            rt = ray_tpu._global_runtime
            rt._metrics_pusher.flush()
            # What /api/timeline serves (dashboard.py): the GCS's spans
            # as Chrome trace events.
            got = rt.gcs.call("trace_timeline", {})["spans"]
        finally:
            ray_tpu.shutdown()
            from ray_tpu.core.config import GLOBAL_CONFIG

            GLOBAL_CONFIG._overrides.pop("tracing_enabled", None)
            GLOBAL_CONFIG._overrides.pop("trace_sample_rate", None)
            tracing.refresh_from_config()
            tracing.RECORDER.drain()
    mine = [s for s in got if s["trace_id"] == root.startup_id]
    names = {s["name"] for s in mine}
    assert {"serve.run", "actor.create", "raylet.lease",
            "worker.spawn"} <= names, names
    assert all(s["attrs"]["lifecycle"] for s in mine)
    # On the epoch line, like the request spans beside them.
    run = next(s for s in mine if s["name"] == "serve.run")
    assert abs(run["start"] - time.time()) < 60
    events = chrome_trace_events(mine)["traceEvents"]
    assert any(e.get("name") == "actor.create" for e in events)


# --------------------------------------------------------------------- #
# jax's own trace / lower / compile, per program
# --------------------------------------------------------------------- #

_COMPILE_SCRIPT = r"""
import json, os, sys, time
cache = sys.argv[1]
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", cache)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from ray_tpu.observability import compile as cw, compile_watch, tracing

assert compile_watch()["installed"] is False
assert cw.install() is True and cw.install() is False

def watched_program(x):
    for _ in range(40):
        x = jnp.tanh(x @ x) + 1.0
    return x

x = jnp.ones((64, 64))
before = compile_watch()
f = jax.jit(watched_program)
f(x).block_until_ready()
first = compile_watch()
f(x).block_until_ready()            # cached: nothing is listened to
again = compile_watch()
jax.clear_caches()                  # a new process, as far as jax knows
jax.jit(watched_program)(x).block_until_ready()
second = compile_watch()
spans = [s for s in tracing.LIFECYCLE.snapshot()[0]
         if (s["attrs"] or {}).get("fun_name") == "watched_program"]
print(json.dumps({"before": before, "first": first, "again": again,
                  "second": second, "spans": spans}))
"""


def test_compile_watch_counts_a_programs_trace_lower_and_compile(tmp_path):
    """A jitted function's trace, lowering and compile are counted once;
    a call of the cached program is not; the same program served by the
    persistent cache counts as `load_s`, not `cold_s`. In a process of
    its own: the listeners and the cache directory are process-wide."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, "-c", _COMPILE_SCRIPT, str(tmp_path / "cache")],
        capture_output=True, text=True, env=env, timeout=120)
    assert done.returncode == 0, done.stderr[-3000:]
    out = json.loads(done.stdout.splitlines()[-1])
    name = "watched_program"
    assert name not in out["before"]["by_fun"]
    first = out["first"]["by_fun"][name]
    assert first["programs"] == 1
    assert first["trace_s"] > 0 and first["lower_s"] > 0
    assert first["cold_s"] > 0 and first["load_s"] == 0
    assert out["first"]["misses"] - out["before"]["misses"] >= 1
    # The cached call moved nothing at all.
    assert out["again"]["by_fun"][name] == first
    assert out["again"]["programs"] == out["first"]["programs"]
    second = out["second"]["by_fun"][name]
    assert second["programs"] == 2
    assert second["load_s"] > 0                     # served by the cache
    assert second["cold_s"] == first["cold_s"]      # and not compiled
    assert second["trace_s"] > first["trace_s"]
    assert out["second"]["hits"] - out["first"]["hits"] == 1
    # The sums are those of the parts, and the spans name the program:
    # one `jax.compile` a trip through the backend, `cache` hit or miss.
    for key in ("trace_s", "lower_s", "load_s", "cold_s"):
        assert out["second"][key] >= second[key]
        assert out["second"]["in_spans"][key] <= out["second"][key] + 1e-9
    compiles = [s for s in out["spans"] if s["name"] == "jax.compile"]
    assert [s["attrs"]["cache"] for s in compiles] in (
        ["miss", "hit"], ["hit"], ["miss"], [])     # only those >= 10 ms
    assert all(s["startup_id"] is None and s["end"] >= s["start"]
               for s in out["spans"])
