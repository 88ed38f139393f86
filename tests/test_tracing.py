"""Tracing plane tests (ray_tpu/observability/).

Covers the PR-7 acceptance surface: context propagation across task /
actor / serve-HTTP / collective boundaries (one trace_id end to end),
flight-recorder boundedness under span storms, the allocate-nothing
contract for sampled-out requests, Chrome trace-event export validity
(parent/child edges reconstructible), the GCS trace store window/limit
caps, and the metrics satellites (stale-reporter expiry, registry
re-register keeping accumulated series).
"""

import time

import numpy as np
import pytest


def _tracing():
    from ray_tpu.observability import tracing

    return tracing


def _enable_local(monkeypatch=None, rate=1.0, cap=4096):
    """Enable tracing for this process only (no cluster)."""
    from ray_tpu.core.config import GLOBAL_CONFIG

    tracing = _tracing()
    GLOBAL_CONFIG._overrides["tracing_enabled"] = True
    GLOBAL_CONFIG._overrides["trace_sample_rate"] = rate
    GLOBAL_CONFIG._overrides["trace_buffer_spans"] = cap
    tracing.refresh_from_config()
    tracing.RECORDER.drain()


def _disable_local():
    from ray_tpu.core.config import GLOBAL_CONFIG

    tracing = _tracing()
    for k in ("tracing_enabled", "trace_sample_rate", "trace_buffer_spans"):
        GLOBAL_CONFIG._overrides.pop(k, None)
    tracing.refresh_from_config()
    tracing.RECORDER.drain()


@pytest.fixture()
def local_tracing():
    _enable_local()
    yield _tracing()
    _disable_local()


# --------------------------------------------------------------------- #
# Tracer unit behavior
# --------------------------------------------------------------------- #


def test_disabled_path_is_shared_noop_singleton():
    tracing = _tracing()
    _disable_local()
    spans = [tracing.get_tracer().start_span(f"s{i}") for i in range(10)]
    assert all(s is tracing.NOOP_SPAN for s in spans)
    assert len(tracing.RECORDER) == 0


def test_sampled_out_requests_allocate_nothing():
    """With the sample rate at 0, every start_span returns the SAME
    no-op object and the recorder never grows — the sampled-out path
    provably allocates no span state."""
    tracing = _tracing()
    _enable_local(rate=0.0)
    try:
        for _ in range(100):
            span = tracing.get_tracer().start_span("req")
            assert span is tracing.NOOP_SPAN
            span.end()
        assert len(tracing.RECORDER) == 0
        # Spec contexts are minted (tasks need ids regardless) but marked
        # unsampled, so remote sides do not re-roll the decision.
        ctx = tracing.child_spec_ctx()
        assert ctx["sampled"] is False
    finally:
        _disable_local()


def test_span_nesting_and_context_restore(local_tracing):
    tracing = local_tracing
    tracer = tracing.get_tracer()
    with tracer.start_span("root") as root:
        assert tracing.capture()["span_id"] == root.span_id
        with tracer.start_span("child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
        # inner end restores the outer context
        assert tracing.capture()["span_id"] == root.span_id
    assert tracing.capture() is None
    spans, dropped = tracing.RECORDER.drain()
    assert [s["name"] for s in spans] == ["child", "root"]
    assert dropped == 0


def test_span_error_recorded_from_exception(local_tracing):
    tracing = local_tracing
    with pytest.raises(ValueError):
        with tracing.get_tracer().start_span("boom"):
            raise ValueError("nope")
    spans, _ = tracing.RECORDER.drain()
    assert spans[0]["error"] == "ValueError: nope"


def test_flight_recorder_bounded_under_span_storm(local_tracing):
    """Memory stays flat: the ring never exceeds its cap, drops are
    counted, and error spans survive drop-oldest."""
    tracing = local_tracing
    tracing.RECORDER.resize(64)
    err = tracing.get_tracer().start_span("err")
    err.end(error="kept")
    for i in range(5000):
        with tracing.get_tracer().start_span("storm"):
            pass
    stats = tracing.RECORDER.stats()
    assert stats["buffered"] <= 64 + tracing.FlightRecorder.ERROR_CAP
    assert stats["dropped"] >= 5000 - 64
    spans, dropped = tracing.RECORDER.drain()
    assert any(s["error"] == "kept" for s in spans)
    assert dropped >= 5000 - 64
    assert len(tracing.RECORDER) == 0  # drained: memory released


def test_traceparent_round_trip(local_tracing):
    tracing = local_tracing
    with tracing.get_tracer().start_span("r") as r:
        hdr = tracing.format_traceparent()
    ctx = tracing.parse_traceparent(hdr)
    assert ctx == {"trace_id": r.trace_id, "span_id": r.span_id,
                   "sampled": True}
    assert tracing.parse_traceparent(None) is None
    assert tracing.parse_traceparent("00-bad") is None
    assert tracing.parse_traceparent("00-zz-zz-zz") is None
    unsampled = tracing.format_traceparent(
        {"trace_id": "a" * 32, "span_id": "b" * 16, "sampled": False})
    assert unsampled.endswith("-00")
    assert tracing.parse_traceparent(unsampled)["sampled"] is False


# --------------------------------------------------------------------- #
# Chrome trace-event export
# --------------------------------------------------------------------- #


def _validate_chrome(obj):
    """Minimal trace-event schema check: the fields Perfetto's legacy
    JSON importer requires, typed correctly."""
    assert set(obj) >= {"traceEvents", "displayTimeUnit"}
    for ev in obj["traceEvents"]:
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["name"], str)
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], float) and ev["dur"] >= 0.0
            assert "args" in ev
        else:
            assert ev["name"] in ("process_name", "thread_name")
            assert "name" in ev["args"]


def test_chrome_export_schema_and_edges(local_tracing):
    import json

    from ray_tpu.observability import chrome_trace_events

    tracing = local_tracing
    with tracing.get_tracer().start_span("parent"):
        with tracing.get_tracer().start_span("kid"):
            pass
    spans, _ = tracing.RECORDER.drain()
    for s in spans:
        s["proc"] = "proc-a"
    out = chrome_trace_events(spans)
    json.dumps(out)  # encodable
    _validate_chrome(out)
    xs = {e["args"]["span_id"]: e for e in out["traceEvents"]
          if e["ph"] == "X"}
    kid = next(e for e in xs.values() if e["name"] == "kid")
    parent = xs[kid["args"]["parent_id"]]
    assert parent["name"] == "parent"
    assert parent["args"]["trace_id"] == kid["args"]["trace_id"]
    # one track per process: both spans share the pid, and a metadata
    # event names it
    assert parent["pid"] == kid["pid"]
    meta = [e for e in out["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "proc-a" for e in meta)


def test_span_tree_nesting(local_tracing):
    from ray_tpu.observability import span_tree

    tracing = local_tracing
    with tracing.get_tracer().start_span("a") as a:
        with tracing.get_tracer().start_span("b"):
            pass
        with tracing.get_tracer().start_span("c"):
            pass
    spans, _ = tracing.RECORDER.drain()
    tree = span_tree(spans, a.trace_id)
    assert tree["span_count"] == 3
    (root,) = tree["roots"]
    assert root["name"] == "a"
    assert [c["name"] for c in root["children"]] == ["b", "c"]


def test_failed_flush_restores_drained_spans(local_tracing):
    """A GCS hiccup during the pusher flush must not silently lose the
    drained spans (or their drop accounting): they go back into the
    recorder for the next period."""
    from ray_tpu.util.metrics import MetricsPusher

    tracing = local_tracing
    err = tracing.get_tracer().start_span("err")
    err.end(error="keep me")
    with tracing.get_tracer().start_span("ok"):
        pass

    class DeadGcs:
        def call(self, *a, **k):
            raise ConnectionError("gcs down")

    pusher = MetricsPusher(DeadGcs(), reporter_id="t")
    pusher.flush()  # swallows the failure...
    spans, dropped = tracing.RECORDER.drain()
    # ...but the spans survived for the next cadence.
    assert {s["name"] for s in spans} == {"err", "ok"}
    assert any(s["error"] == "keep me" for s in spans)


# --------------------------------------------------------------------- #
# Metrics satellites
# --------------------------------------------------------------------- #


def test_registry_reregister_keeps_accumulated_series():
    """Satellite regression: re-constructing a same-name same-type
    metric (a re-created deployment) must keep the accumulated series,
    not silently reset it."""
    from ray_tpu.util import metrics as m

    name = f"test_rereg_{time.monotonic_ns()}"
    c1 = m.Counter(name, "d")
    c1.inc(3)
    c2 = m.Counter(name, "d")  # re-construction
    c2.inc(4)
    snap = next(s for s in m.GLOBAL_REGISTRY.snapshot()
                if s["name"] == name)
    assert snap["series"][0][1] == 7.0  # 3 + 4 accumulated
    c1.inc(1)  # both instances share the same series
    snap = next(s for s in m.GLOBAL_REGISTRY.snapshot()
                if s["name"] == name)
    assert snap["series"][0][1] == 8.0
    with pytest.raises(ValueError):
        m.Gauge(name, "type mismatch")
    hname = f"test_rereg_h_{time.monotonic_ns()}"
    h1 = m.Histogram(hname, "d", boundaries=[1, 2])
    h1.observe(1.5)
    h2 = m.Histogram(hname, "d", boundaries=[1, 2])
    h2.observe(0.5)
    snap = next(s for s in m.GLOBAL_REGISTRY.snapshot()
                if s["name"] == hname)
    assert snap["series"][0][1]["count"] == 2
    with pytest.raises(ValueError):
        m.Histogram(hname, "d", boundaries=[1, 2, 3])


def _mini_gcs():
    from ray_tpu.core.gcs import GcsServer

    return GcsServer(port=0)


def test_gcs_expires_stale_and_dead_node_reporters():
    """Satellite regression: a reporter that stops flushing (or whose
    node died) must drop out of /metrics, and the expiry is counted by
    the metrics_stale_reporters gauge."""
    from ray_tpu.core.common import NodeInfo
    from ray_tpu.core.ids import NodeID

    gcs = _mini_gcs()
    try:
        snap = [{"name": "m", "kind": "gauge", "description": "",
                 "series": [[[], 1.0]]}]
        now = time.time()
        gcs.handle_metrics_report(None, {
            "reporter": "live", "metrics": snap, "ts": now,
            "period_s": 2.0})
        gcs.handle_metrics_report(None, {
            "reporter": "silent", "metrics": snap, "ts": now - 60,
            "period_s": 2.0})
        dead = NodeID.from_random()
        gcs.nodes[dead] = NodeInfo(node_id=dead, address="x",
                                   object_manager_address="x",
                                   session_suffix="s", state="DEAD")
        gcs.handle_metrics_report(None, {
            "reporter": "on-dead-node", "metrics": snap, "ts": now,
            "period_s": 2.0, "node": dead.hex()})
        live = gcs._live_metrics()
        assert "live" in live
        assert "silent" not in live          # stopped flushing
        assert "on-dead-node" not in live    # owning node is DEAD
        gauge = next(s for s in live["gcs"]
                     if s["name"] == "metrics_stale_reporters")
        assert gauge["series"][0][1] == 2.0
        # And the rendered exposition carries it.
        text = gcs.handle_metrics_prometheus(None)["text"]
        assert "metrics_stale_reporters" in text
    finally:
        gcs.stop()


def test_gcs_timeline_window_and_limit_caps():
    """/api/timeline's ?window= / ?limit= must bound what the JSON
    encoder sees, and GCS-side drop-oldest must bound the store."""
    from ray_tpu.core.config import GLOBAL_CONFIG

    gcs = _mini_gcs()
    try:
        now = time.time()
        spans = [{"name": f"s{i}", "trace_id": "t", "span_id": f"{i}",
                  "parent_id": None, "start": now - i, "end": now - i,
                  "thread": "main", "attrs": None, "error": None}
                 for i in range(100)]
        gcs.handle_metrics_report(None, {
            "reporter": "r", "metrics": [], "ts": now, "spans": spans})
        out = gcs.handle_trace_timeline(None, {})
        assert len(out["spans"]) == 100
        out = gcs.handle_trace_timeline(None, {"window_s": 10.5})
        assert all(s["end"] >= now - 10.5 for s in out["spans"])
        assert 0 < len(out["spans"]) < 100
        out = gcs.handle_trace_timeline(None, {"limit": 7})
        assert len(out["spans"]) == 7 and out["truncated"] == 93
        # store cap: drop-oldest with a counter
        GLOBAL_CONFIG._overrides["trace_gcs_max_spans"] = 50
        try:
            gcs.handle_metrics_report(None, {
                "reporter": "r", "metrics": [], "ts": now, "spans": spans})
            assert len(gcs.trace_spans) == 50
            assert gcs.trace_dropped >= 100
        finally:
            GLOBAL_CONFIG._overrides.pop("trace_gcs_max_spans", None)
    finally:
        gcs.stop()


# --------------------------------------------------------------------- #
# Cross-process propagation (cluster)
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def traced_cluster():
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4,
                 _system_config={"tracing_enabled": True,
                                 "trace_sample_rate": 1.0})
    created = ray_tpu._global_runtime
    yield
    if ray_tpu._global_runtime is created:
        ray_tpu.shutdown()
    _disable_local()


def _trace_spans(trace_id, want_names, timeout=25.0):
    """Flush the driver recorder and poll the GCS until every wanted
    span name is stored (worker pushers flush on a 2s cadence)."""
    import ray_tpu

    rt = ray_tpu._global_runtime
    deadline = time.time() + timeout
    spans = []
    while time.time() < deadline:
        rt._metrics_pusher.flush()
        spans = rt.gcs.call("trace_get", {"trace_id": trace_id})["spans"]
        if want_names <= {s["name"] for s in spans}:
            return spans
        time.sleep(0.4)
    raise AssertionError(
        f"wanted {want_names}, got {sorted({s['name'] for s in spans})}")


def test_task_propagation_one_trace(traced_cluster):
    import ray_tpu

    tracing = _tracing()

    @ray_tpu.remote
    def child():
        return tracing.current_ctx()

    @ray_tpu.remote
    def parent():
        return tracing.current_ctx(), ray_tpu.get(child.remote())

    with tracing.get_tracer().start_span("test.task.root") as root:
        pctx, cctx = ray_tpu.get(parent.remote())
    assert pctx["trace_id"] == root.trace_id
    assert cctx["trace_id"] == root.trace_id
    assert pctx["sampled"] and cctx["sampled"]
    # The two tasks ran in two workers, each flushing on its own 2 s
    # cadence: the first poll that sees one `task.run` need not see both.
    deadline = time.time() + 25.0
    while True:
        spans = _trace_spans(root.trace_id, {"test.task.root", "task.run"})
        runs = [s for s in spans if s["name"] == "task.run"]
        if len(runs) >= 2 or time.time() > deadline:
            break
        time.sleep(0.4)
    assert len(runs) >= 2  # parent and child tasks
    # parent edges resolve: the parent task's span hangs off the root
    by_id = {s["span_id"]: s for s in spans}
    assert any(by_id.get(s["parent_id"], {}).get("name")
               == "test.task.root" for s in runs)


def test_actor_propagation_one_trace(traced_cluster):
    import ray_tpu

    tracing = _tracing()

    @ray_tpu.remote
    class Probe:
        def ctx(self):
            return tracing.current_ctx()

    probe = Probe.remote()
    ray_tpu.get(probe.ctx.remote())  # actor up before the traced call
    with tracing.get_tracer().start_span("test.actor.root") as root:
        actx = ray_tpu.get(probe.ctx.remote())
    assert actx["trace_id"] == root.trace_id
    spans = _trace_spans(root.trace_id, {"actor.call"})
    call = next(s for s in spans if s["name"] == "actor.call")
    assert call["attrs"]["method"] == "ctx"


def test_collective_propagation_one_trace(traced_cluster):
    import ray_tpu

    tracing = _tracing()

    # Actors, not tasks: each rank needs its own worker process (two
    # plain tasks can pipeline onto ONE leased worker, and a collective
    # op parked on rank 0 would starve rank 1 queued behind it).
    @ray_tpu.remote
    class Member:
        def run(self, rank):
            from ray_tpu import collective

            group = collective.init_collective_group(
                2, rank, group_name="trace-grp")
            out = group.allreduce(np.ones(8, np.float32))
            group.leave()
            return float(np.sum(out))

    members = [Member.remote() for _ in range(2)]
    with tracing.get_tracer().start_span("test.coll.root") as root:
        totals = ray_tpu.get([m.run.remote(r)
                              for r, m in enumerate(members)], timeout=60)
    assert totals == [16.0, 16.0]
    # Both ranks flush on their own 2s cadence: poll until both arrive.
    import ray_tpu as _rt

    deadline = time.time() + 25
    ops = []
    while time.time() < deadline:
        spans = _rt._global_runtime.gcs.call(
            "trace_get", {"trace_id": root.trace_id})["spans"]
        ops = [s for s in spans if s["name"] == "collective.allreduce"]
        if {s["attrs"]["rank"] for s in ops} == {0, 1}:
            break
        time.sleep(0.4)
    assert {s["attrs"]["rank"] for s in ops} == {0, 1}
    assert {s["proc"] for s in ops if s["proc"]}  # recorded by workers
    assert all(s["trace_id"] == root.trace_id for s in ops)


def test_serve_http_llm_trace_spans_processes_and_ttft(traced_cluster):
    """The acceptance path: ONE HTTP request against the LLM deployment
    yields a single trace crossing the client/driver, proxy and replica
    processes (engine phases on their own thread track), with TTFT
    decomposed into queue/prefill/decode — exported as valid Chrome
    trace-event JSON."""
    import json
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.inference import LLMServer
    from ray_tpu.observability import chrome_trace_events

    tracing = _tracing()
    serve.run(LLMServer.options(num_replicas=1).bind(
        "tiny", 128, 4,
        engine_config={"use_jit": False, "batch_slots": 2,
                       "block_size": 8, "num_blocks": 32,
                       "max_blocks_per_seq": 8, "prefill_chunk": 8}))
    try:
        port = serve.http_port()
        with tracing.get_tracer().start_span("client.request") as root:
            hdr = tracing.format_traceparent()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/LLMServer",
            data=json.dumps({"ids": [1, 2, 3],
                             "max_new_tokens": 4}).encode(),
            headers={"Content-Type": "application/json",
                     "traceparent": hdr})
        with urllib.request.urlopen(req, timeout=120) as resp:
            body = json.loads(resp.read())
        assert len(body["result"]["ids"]) == 7

        # The fast data plane dispatches direct (serve.direct replaces
        # the classic serve.route/serve.dispatch pair on this path).
        # client.request is in the wait set on purpose: the driver's own
        # flush lands asynchronously, and the >=3-process assertion below
        # needs the driver's span stored, not merely flushed (the poll
        # returning on worker spans alone made this flake under load).
        want = {"client.request", "serve.http", "serve.direct",
                "serve.replica", "engine.queue", "engine.prefill",
                "engine.decode"}
        spans = _trace_spans(root.trace_id, want, timeout=40.0)
        assert all(s["trace_id"] == root.trace_id for s in spans)
        # ONE trace, >= 3 OS processes (driver client, proxy worker,
        # replica worker) and >= 4 tracks once the engine thread's is
        # counted — proxy, router (in-proxy), replica, engine.
        procs = {s["proc"] for s in spans}
        assert len(procs) >= 3, procs
        tracks = {(s["proc"], s["thread"]) for s in spans}
        assert len(tracks) >= 4, tracks
        # TTFT decomposition is contiguous: queue ends where prefill
        # begins; prefill ends where decode begins.
        phases = {s["name"]: s for s in spans
                  if s["name"].startswith("engine.")}
        assert phases["engine.queue"]["end"] == \
            pytest.approx(phases["engine.prefill"]["start"], abs=1e-6)
        assert phases["engine.prefill"]["end"] == \
            pytest.approx(phases["engine.decode"]["start"], abs=1e-6)
        assert phases["engine.decode"]["attrs"]["tokens"] == 4
        # Valid Chrome trace-event JSON with resolvable span edges.
        out = chrome_trace_events(spans)
        json.dumps(out)
        _validate_chrome(out)
        xs = {e["args"]["span_id"]: e for e in out["traceEvents"]
              if e["ph"] == "X"}
        http = next(e for e in xs.values() if e["name"] == "serve.http")
        assert xs[http["args"]["parent_id"]]["name"] == "client.request"
    finally:
        serve.shutdown()


def test_rpc_wire_ctx_suppresses_resampling(traced_cluster):
    """An unsampled context crosses the wire as the 0 marker: the far
    side must NOT root a fresh sampled trace mid-request."""
    import ray_tpu

    tracing = _tracing()

    @ray_tpu.remote
    def probe():
        ctx = tracing.current_ctx()
        return None if ctx is None else ctx.get("sampled")

    tok = tracing.activate({"trace_id": "f" * 32, "span_id": "e" * 16,
                            "sampled": False})
    try:
        sampled = ray_tpu.get(probe.remote())
    finally:
        tracing.deactivate(tok)
    assert sampled is False
