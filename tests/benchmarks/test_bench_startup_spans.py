"""The nine per-layer metrics under `setup_s` that read the program's own
start-up timeline (`benchmarks/layer_metrics/_startup.py`,
`startup.*_s`, `compile.*_s`; docs/OBSERVABILITY.md "Start-up timeline"):
on a canned session directory, and in the labelled CPU rehearsal of one
train cell and one serve cell."""

import json
import os
import subprocess
import sys

import pytest

import _paths
from benchmarks import manifest as mf

NINE = ("startup.lease_s", "startup.spawn_s", "startup.backend_s",
        "startup.ready_lag_s", "startup.uncovered_s", "compile.trace_s",
        "compile.lower_s", "compile.load_s", "compile.cold_s")


def _span(name, start, end, span_id, parent=None, sid="s1", pid=1,
          role="driver", **attrs):
    return {"name": name, "start": float(start), "end": float(end),
            "span_id": span_id, "parent_id": parent, "startup_id": sid,
            "pid": pid, "role": role, "attrs": attrs or None,
            "error": None}


def _write(session, pid, role, spans, **header):
    folder = os.path.join(session, "lifecycle")
    os.makedirs(folder, exist_ok=True)
    with open(os.path.join(folder, f"{pid}-c0ffee.jsonl"), "w") as f:
        f.write(json.dumps({"kind": "process", "pid": pid, "role": role,
                            "dropped": 0, **header}) + "\n")
        for s in spans:
            f.write(json.dumps(s) + "\n")


WATCH = {"installed": True, "programs": 9, "hits": 7, "misses": 1,
         # everything the process timed, and the part its spans hold:
         # the rest are events under 10 ms, which only the counters keep
         "trace_s": 2.5, "lower_s": 2.25, "load_s": 4.0, "cold_s": 3.5,
         "in_spans": {"trace_s": 2.0, "lower_s": 1.5, "load_s": 3.5,
                      "cold_s": 3.0},
         "by_fun": {}}


@pytest.fixture()
def serve_session(tmp_path, monkeypatch):
    """serve.run 100 -> 120; the replica (pid 30) holds the chip; the
    first timed request at 140."""
    from ray_tpu.observability import tracing

    session = str(tmp_path / "session_serve")
    _write(session, 1, "driver", [
        _span("serve.run", 100, 120, "root", deployments=["d"]),
        _span("actor.create", 100.5, 118, "act", "rep", role="gcs"),
        # a refused attempt of the same actor, then the granted one
        _span("raylet.lease", 100.5, 100.75, "l0", "act", role="raylet",
              actor="a1", waited_for="chips_busy"),
        _span("raylet.lease", 101, 103, "l1", "act", role="raylet",
              actor="a1", worker_from="spawn", worker_pid=30),
        # another actor's lease (the controller's): not the holder's
        _span("raylet.lease", 100.1, 100.4, "l9", "ctl", role="raylet",
              actor="a0", worker_pid=20),
        _span("worker.spawn", 101.25, 101.5, "sp", "l1", role="raylet",
              worker="w1", kind="forge"),
    ])
    _write(session, 20, "controller", [
        _span("serve.deploy", 100.5, 119, "dep", "root", pid=20,
              role="controller"),
        _span("serve.replica.start", 100.5, 119, "rep", "dep", pid=20,
              role="controller", polls=180, slept_s=18.0),
    ])
    _write(session, 30, "replica", [
        _span("worker.boot", 101.5, 103, "boot", "l1", pid=30,
              role="worker", worker="w1", import_s=1.0),
        _span("serve.replica.ctor", 103, 118, "ctor", "act", pid=30,
              role="replica"),
        _span("jax.claim_devices", 103, 107, "claim", "ctor", pid=30,
              role="replica", platform="tpu", n_devices=1),
        _span("user.ctor", 107, 118, "user", "ctor", pid=30,
              role="replica"),
        _span("jax.trace", 108, 110, "t1", "user", sid=None, pid=30,
              role="replica", fun_name="decode_fn"),
        _span("jax.lower", 110, 111.5, "w1", "user", sid=None, pid=30,
              role="replica", fun_name="jit(decode_fn)"),
        _span("jax.compile", 111.5, 113, "c1", "user", sid=None, pid=30,
              role="replica", fun_name="jit(decode_fn)", cache="hit"),
        # after serve.run() returned, before the first timed request: the
        # warm-up's. Holds a nested event: counted without it (`self_s`).
        _span("jax.compile", 125, 129, "c2", None, sid=None, pid=30,
              role="replica", fun_name="jit(prefill_fn)", cache="miss",
              self_s=3.0),
        # inside the window: not the set-up's
        _span("jax.compile", 150, 152, "c3", None, sid=None, pid=30,
              role="replica", fun_name="jit(late)", cache="hit"),
    ], compile_watch=WATCH)
    monkeypatch.setattr(tracing, "_SESSION_DIR", session)
    return {"setup_end": 140.0}


def test_the_nine_readers_on_a_canned_serve_session(serve_session):
    got = {name: mf.reader_of(name)(serve_session) for name in NINE}
    assert got["startup.lease_s"] == pytest.approx(2.25)   # l1 + l0
    assert got["startup.spawn_s"] == pytest.approx(1.75)   # 101.25 -> 103
    assert got["startup.backend_s"] == pytest.approx(4.0)
    assert got["startup.ready_lag_s"] == pytest.approx(2.0)  # 118 -> 120
    # 100-100.1, 100.4-100.5 and 119-120: nobody but the root was there
    assert got["startup.uncovered_s"] == pytest.approx(1.2)
    # spans that end before setup_end + what only the counters hold
    assert got["compile.trace_s"] == pytest.approx(2.0 + 0.5)
    assert got["compile.lower_s"] == pytest.approx(1.5 + 0.75)
    assert got["compile.load_s"] == pytest.approx(1.5 + 0.5)   # c3 is late
    assert got["compile.cold_s"] == pytest.approx(3.0 + 0.5)   # c2's self_s


@pytest.fixture()
def train_session(tmp_path, monkeypatch):
    """fit() at 10; two ranks, rank 1 (pid 41) the later one; fit()
    returns at 90, long after the first timed step at 40."""
    from ray_tpu.observability import tracing

    session = str(tmp_path / "session_train")
    _write(session, 1, "driver", [
        _span("train.startup", 10, 90, "root", workers=2, chips=2),
        _span("train.executor.start", 10, 14, "exec", "root"),
        _span("raylet.lease", 10.5, 11, "l0", "a0", role="raylet",
              actor="a0", worker_pid=40),
        _span("raylet.lease", 10.5, 11.5, "l1", "a1", role="raylet",
              actor="a1", worker_pid=41),
        _span("worker.spawn", 10.5, 10.75, "sp1", "l1", role="raylet",
              worker="w41"),
        _span("train.backend.on_start", 14, 19, "back", "root", workers=2),
    ])
    for pid, rank, enter in ((40, 0, 19.25), (41, 1, 19.5)):
        _write(session, pid, "worker", [
            _span("worker.boot", 10.75, 13, f"b{pid}", None, sid=None,
                  pid=pid, role="worker", worker=f"w{pid}"),
            _span("jax.distributed", 14, 15, f"d{pid}", "back", pid=pid,
                  role="worker", rank=rank, world=2),
            _span("train.backend.on_start", 15, 19, f"o{pid}", "back",
                  pid=pid, role="worker", rank=rank),
            _span("jax.claim_devices", 15, 18.5, f"c{pid}", f"o{pid}",
                  pid=pid, role="worker"),
            _span("train.loop.enter", enter, enter, f"m{pid}", "root",
                  pid=pid, role="worker", rank=rank),
            _span("jax.trace", 25, 30, f"t{pid}", None, sid=None, pid=pid,
                  role="worker", fun_name="step"),
        ], compile_watch=dict(WATCH, trace_s=5.25,
                              in_spans=dict(WATCH["in_spans"],
                                            trace_s=5.0)))
    monkeypatch.setattr(tracing, "_SESSION_DIR", session)
    return {"setup_end": 40.0}


def test_the_nine_readers_on_a_canned_train_session(train_session):
    got = {name: mf.reader_of(name)(train_session) for name in NINE}
    assert got["startup.lease_s"] == pytest.approx(1.0)     # pid 41's
    assert got["startup.spawn_s"] == pytest.approx(2.5)     # 10.5 -> 13
    assert got["startup.backend_s"] == pytest.approx(4.5)   # 1 + 3.5
    assert got["startup.ready_lag_s"] == pytest.approx(0.5)  # 19 -> 19.5
    assert got["startup.uncovered_s"] == pytest.approx(0.5)  # 19 -> 19.5
    assert got["compile.trace_s"] == pytest.approx(5.25)
    assert got["compile.load_s"] == pytest.approx(0.5)      # counters only


def test_the_readers_say_nothing_where_there_is_nothing_to_read(
        serve_session, tmp_path, monkeypatch):
    from ray_tpu.observability import tracing

    for name in NINE:
        read = mf.reader_of(name)
        # No `setup_end`: no run to speak of, whatever sessions lie about.
        assert read({}) is None
        assert read({"spans": {}, "setup_end": None}) is None
    # A run whose start-up began after `setup_end` is another run's.
    for name in NINE:
        assert mf.reader_of(name)({"setup_end": 50.0}) is None
    # A program that wrote no lifecycle files (a parent from before them).
    monkeypatch.setattr(tracing, "_SESSION_DIR", str(tmp_path / "empty"))
    for name in NINE:
        assert mf.reader_of(name)({"setup_end": 140.0}) is None


def test_the_manifest_lists_the_nine_under_setup_s_in_every_cell():
    manifest = mf.load(_paths.ROOT)
    cells = {c["name"] for c in manifest["workloads"]}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NINE:
        m = by_name[name]
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["source"] == "program_span" and m["unit"] == "s"
        assert set(m["workloads"]) == cells
        assert m["layer"] == ("grant, spawn and process start"
                              if name.startswith("startup.")
                              else "model and step, compile")
    assert mf.validate(manifest, _paths.ROOT) == []


@pytest.mark.parametrize("cell", ["train_gpt2m_1chip",
                                  "serve_mistral7b_decode_heavy"])
def test_a_traced_rehearsal_reports_all_nine(cell, tmp_path):
    """The program's own spans, written by its own processes and read
    back after `shutdown()`, with no cluster up: end to end on the CPU."""
    env = {**os.environ, "PYTHONPATH": _paths.ROOT,
           "RAY_TPU_TMPDIR": str(tmp_path / "rt")}
    env.pop("RAY_TPU_SESSION_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(_paths.ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "2246822519", "--seconds", "2",
         "--trace", "1", "--rehearsal", "--out", str(tmp_path / "out")],
        capture_output=True, text=True, cwd=_paths.ROOT, env=env,
        timeout=240)
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert set(NINE) <= set(last["metrics_reported"]), last
    assert not set(NINE) & set(last["metrics_left_out"])
    # The roots reconcile with the stamps the builder takes from outside.
    with open(tmp_path / "out" / "run.json") as f:
        metrics = json.load(f)["result"]["metrics"]
    from ray_tpu.observability import startup_report

    (session,) = [d for d in os.listdir(tmp_path / "rt")
                  if d.startswith("session_")]
    report = startup_report(str(tmp_path / "rt" / session))
    st = report["startups"][-1]
    outside = metrics["setup.to_worker_s.train" if cell.startswith("train")
                      else "setup.deploy_s.serve"]["value"]
    assert st["duration_s"] == pytest.approx(outside, abs=0.3)
    assert sum(p["path_s"] for p in st["critical_path"]) \
        == pytest.approx(st["duration_s"])
    assert 0 <= metrics["startup.uncovered_s"]["value"] \
        <= st["self_s"][st["root"]["span_id"]] + 1e-9
