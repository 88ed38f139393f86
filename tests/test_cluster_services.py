"""Cluster services: metrics export, log streaming, job submission,
autoscaler.

Mirrors the reference's `test_metrics_agent.py`, `test_output.py`
(log_to_driver), `test_job_manager.py`, and `test_autoscaler.py` at the
behavior level.
"""

import sys
import time

import pytest

import ray_tpu
from conftest import pids_with_mark


# --------------------------------------------------------------------------- #
# Metrics
# --------------------------------------------------------------------------- #


def test_metrics_api_and_prometheus_render():
    from ray_tpu.util.metrics import (
        Counter,
        Gauge,
        Histogram,
        render_prometheus,
    )

    c = Counter("test_requests_total", "requests", tag_keys=("route",))
    c.inc(2, tags={"route": "/a"})
    c.inc(1, tags={"route": "/b"})
    g = Gauge("test_queue_depth", "depth")
    g.set(7)
    h = Histogram("test_latency_s", "latency", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)

    snaps = {"proc1": [c._snapshot(), g._snapshot(), h._snapshot()]}
    text = render_prometheus(snaps)
    assert 'test_requests_total{route="/a",proc="proc1"} 2.0' in text
    assert "test_queue_depth" in text and "} 7" in text
    assert 'test_latency_s_bucket' in text
    assert 'le="+Inf"} 3' in text
    assert "test_latency_s_count" in text

    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        Histogram("bad_hist", boundaries=[])


def test_metrics_flow_to_gcs(ray_start_regular):
    from ray_tpu.util.metrics import Counter

    @ray_tpu.remote
    def bump():
        from ray_tpu.util.metrics import Counter as C

        c = C("task_side_counter", "from a worker")
        c.inc(5)
        # Force a flush so the test doesn't wait for the 2s period.
        ray_tpu._global_runtime._metrics_pusher.flush()
        return True

    Counter("driver_side_counter", "from the driver").inc(3)
    ray_tpu._global_runtime._metrics_pusher.flush()
    assert ray_tpu.get(bump.remote())

    snap = ray_tpu._global_runtime.gcs.call("metrics_snapshot")
    names = {m["name"] for metrics in snap.values() for m in metrics}
    assert "driver_side_counter" in names
    assert "task_side_counter" in names
    text = ray_tpu._global_runtime.gcs.call("metrics_prometheus")["text"]
    assert "driver_side_counter" in text


# --------------------------------------------------------------------------- #
# Log streaming
# --------------------------------------------------------------------------- #


def test_worker_prints_stream_to_driver(ray_start_regular, capsys):
    @ray_tpu.remote
    def chatty(i):
        print(f"hello-from-task-{i}")
        sys.stdout.flush()
        import ray_tpu as rt

        # Push the batch now instead of waiting for the 0.25s flusher.
        return i

    ray_tpu.get([chatty.remote(i) for i in range(3)])
    deadline = time.monotonic() + 10
    seen = ""
    while time.monotonic() < deadline:
        seen += capsys.readouterr().err
        if all(f"hello-from-task-{i}" in seen for i in range(3)):
            break
        time.sleep(0.2)
    for i in range(3):
        assert f"hello-from-task-{i}" in seen, seen[-500:]
    assert "pid=" in seen  # worker prefix


# --------------------------------------------------------------------------- #
# Job submission
# --------------------------------------------------------------------------- #


def test_job_submission_end_to_end(ray_start_regular):
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient

    address = ray_tpu._global_runtime.gcs.address
    client = JobSubmissionClient(address)

    sid = client.submit_job(
        entrypoint=(
            f"{sys.executable} -c \""
            "import ray_tpu; ray_tpu.init()\n"
            "print('job says hi')\n"
            "ray_tpu.shutdown()\""),
        metadata={"owner": "test"})
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        status = client.get_job_status(sid)
        if status in JobStatus.TERMINAL:
            break
        time.sleep(0.5)
    logs = client.get_job_logs(sid)
    assert status == JobStatus.SUCCEEDED, f"status={status} logs={logs[-800:]}"
    assert "job says hi" in logs
    info = client.get_job_info(sid)
    assert info.metadata["owner"] == "test"
    assert any(j.submission_id == sid for j in client.list_jobs())
    client.close()


def test_job_stop_and_failure_status(ray_start_regular):
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient

    address = ray_tpu._global_runtime.gcs.address
    client = JobSubmissionClient(address)

    fail_id = client.submit_job(
        entrypoint=f"{sys.executable} -c 'raise SystemExit(3)'")
    deadline = time.monotonic() + 60
    while client.get_job_status(fail_id) not in JobStatus.TERMINAL and \
            time.monotonic() < deadline:
        time.sleep(0.2)
    assert client.get_job_status(fail_id) == JobStatus.FAILED

    slow_id = client.submit_job(
        entrypoint=f"{sys.executable} -c 'import time; time.sleep(60)'")
    while client.get_job_status(slow_id) == JobStatus.PENDING and \
            time.monotonic() < deadline:
        time.sleep(0.1)
    assert client.stop_job(slow_id)
    deadline = time.monotonic() + 30
    while client.get_job_status(slow_id) != JobStatus.STOPPED and \
            time.monotonic() < deadline:
        time.sleep(0.2)
    assert client.get_job_status(slow_id) == JobStatus.STOPPED
    assert client.delete_job(slow_id)
    client.close()


def test_job_manager_shutdown_kills_inflight_spawn(tmp_path):
    """shutdown() racing submit() must never orphan an entrypoint: a job
    still PENDING (spawn in flight on the runner thread) is marked
    STOPPED, and the runner's post-spawn handshake delivers the kill to
    the process group it just created (manager.py _run)."""
    import uuid

    from ray_tpu.job_submission import JobStatus
    from ray_tpu.job_submission.manager import JobManager

    mark = "jmorph_" + uuid.uuid4().hex[:12]
    jm = JobManager(gcs_address="127.0.0.1:1", log_dir=str(tmp_path))
    # First batch gets a head start (likely RUNNING when shutdown lands),
    # second batch is submitted immediately before it (likely still
    # PENDING mid-spawn) — both sides of the race in one pass.
    sids = [jm.submit(f"{sys.executable} -c "
                      f"'import time; time.sleep(45)  # {mark}'")
            for _ in range(2)]
    time.sleep(0.3)
    sids += [jm.submit(f"{sys.executable} -c "
                       f"'import time; time.sleep(45)  # {mark}'")
             for _ in range(2)]
    jm.shutdown()

    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        details = [jm.details(s) for s in sids]
        if all(d["status"] == JobStatus.STOPPED and d["end_time"]
               for d in details) and not pids_with_mark(mark):
            break
        time.sleep(0.2)
    details = [jm.details(s) for s in sids]
    assert all(d["status"] == JobStatus.STOPPED for d in details), details
    assert all(d["end_time"] for d in details), details
    assert pids_with_mark(mark) == []


def test_job_manager_shutdown_waits_for_kill_delivery(tmp_path):
    """shutdown() must not return while the off-thread kill handshake is
    still in flight: the caller (GcsServer.stop) exits the process right
    after, and an unjoined daemon killer dies with it — its SIGTERM
    never sent, the entrypoint orphaned. A TERM-trapping driver is the
    worst case: delivery needs the full grace period + SIGKILL."""
    import uuid

    from ray_tpu.job_submission import JobStatus
    from ray_tpu.job_submission.manager import JobManager

    mark = "jmjoin_" + uuid.uuid4().hex[:12]
    jm = JobManager(gcs_address="127.0.0.1:1", log_dir=str(tmp_path))
    sid = jm.submit(
        f'{sys.executable} -c "import signal, time; '
        f'signal.signal(signal.SIGTERM, signal.SIG_IGN); '
        f'time.sleep(60)  # {mark}"')
    deadline = time.monotonic() + 10
    while jm.details(sid)["status"] == JobStatus.PENDING and \
            time.monotonic() < deadline:
        time.sleep(0.05)
    # Give the driver time to install its SIGTERM trap — otherwise the
    # group TERM kills it before the trap exists and the escalation path
    # under test never has to fire.
    time.sleep(1.2)
    jm.shutdown()
    # No grace window here: by the time shutdown() returns, the group
    # must be dead and reaped (killer joined), not merely signaled.
    leaked = pids_with_mark(mark)
    assert leaked == [], f"entrypoint outlived shutdown(): {leaked}"


def test_job_manager_submit_after_shutdown_raises(tmp_path):
    """The GCS RPC server keeps serving submits while it tears down
    (server.stop() runs AFTER job_manager.shutdown()); a submit admitted
    then would spawn after the kill sweep and be orphaned on process
    exit. It must be refused instead."""
    import pytest

    from ray_tpu.job_submission.manager import JobManager

    jm = JobManager(gcs_address="127.0.0.1:1", log_dir=str(tmp_path))
    jm.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        jm.submit("echo too-late")


def test_job_manager_stop_escalates_past_sigterm_trap(tmp_path):
    """stop() on an entrypoint that ignores SIGTERM must escalate to a
    group SIGKILL after the grace period — otherwise the driver outlives
    its STOPPED status. The driver is a python GRANDCHILD under the
    sh -c wrapper: the shell dies on TERM, so the escalation must key on
    group liveness, not on the direct child — and the leak scan must
    look for the grandchild's argv (in-code mark), not the shell's."""
    import uuid

    from ray_tpu.job_submission import JobStatus
    from ray_tpu.job_submission.manager import JobManager

    mark = "jmtrap_" + uuid.uuid4().hex[:12]
    jm = JobManager(gcs_address="127.0.0.1:1", log_dir=str(tmp_path))
    sid = jm.submit(
        f'{sys.executable} -c "import signal, time; '
        f'signal.signal(signal.SIGTERM, signal.SIG_IGN); '
        f'time.sleep(60)  # {mark}"')
    deadline = time.monotonic() + 10
    while jm.details(sid)["status"] == JobStatus.PENDING and \
            time.monotonic() < deadline:
        time.sleep(0.05)
    time.sleep(1.2)  # let the driver install its trap before the TERM
    assert jm.stop(sid)

    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        d = jm.details(sid)
        if d["status"] == JobStatus.STOPPED and d["end_time"] \
                and not pids_with_mark(mark):
            break
        time.sleep(0.2)
    d = jm.details(sid)
    assert d["status"] == JobStatus.STOPPED, d
    assert d["end_time"], "runner never unparked: SIGKILL escalation missing"
    assert pids_with_mark(mark) == [], "TERM-trapping driver outlived the SIGKILL"


# --------------------------------------------------------------------------- #
# Task events / timeline / CLI
# --------------------------------------------------------------------------- #


def test_timeline_records_and_exports_chrome_trace(ray_start_regular,
                                                   tmp_path):
    import json

    @ray_tpu.remote
    def traced(i):
        time.sleep(0.05)
        return i

    ray_tpu.get([traced.remote(i) for i in range(4)])
    # Events flush with the raylet heartbeat (1s period).
    deadline = time.monotonic() + 15
    events = []
    while time.monotonic() < deadline:
        events = ray_tpu.timeline()
        if sum(1 for e in events if e.get("state") == "FINISHED") >= 4:
            break
        time.sleep(0.3)
    names = {e["name"] for e in events}
    assert any("traced" in n for n in names), names

    out = str(tmp_path / "trace.json")
    ray_tpu.timeline(filename=out)
    trace = json.loads(open(out).read())
    spans = [t for t in trace if "traced" in t["name"]]
    assert len(spans) >= 4
    assert all(t["ph"] == "X" and t["dur"] >= 0 for t in spans)


def test_state_cli(ray_start_regular, capsys):
    import json

    from ray_tpu.scripts.cli import main as cli_main

    @ray_tpu.remote
    def f():
        return 1

    ray_tpu.get(f.remote())
    address = ray_tpu._global_runtime.gcs.address
    cli_main(["--address", address, "status"])
    out = json.loads(capsys.readouterr().out)
    assert out["nodes"] >= 1 and "resources_total" in out
    cli_main(["--address", address, "list", "nodes"])
    nodes = json.loads(capsys.readouterr().out)
    assert any(n["Alive"] for n in nodes)


# --------------------------------------------------------------------------- #
# Dashboard
# --------------------------------------------------------------------------- #


def test_dashboard_routes(ray_start_regular):
    import json
    import urllib.request

    from ray_tpu.util.metrics import Gauge

    info = ray_tpu.init(ignore_reinit_error=True)
    url = info["dashboard_url"]
    assert url, "head node did not start a dashboard"

    Gauge("dash_test_gauge", "x").set(11)
    ray_tpu._global_runtime._metrics_pusher.flush()

    with urllib.request.urlopen(url + "/api/nodes", timeout=10) as r:
        nodes = json.loads(r.read())
    assert any(n["Alive"] for n in nodes)
    with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
        text = r.read().decode()
    assert "dash_test_gauge" in text
    # Structured twin of /metrics (the GCS metrics_snapshot endpoint's
    # consumer, wired by the RL014 dead-endpoint pass).
    with urllib.request.urlopen(url + "/api/metrics", timeout=10) as r:
        snap = json.loads(r.read())
    assert any(m["name"] == "dash_test_gauge"
               for series in snap.values() for m in series)
    with urllib.request.urlopen(url, timeout=10) as r:
        html = r.read().decode()
    assert "ray_tpu cluster" in html
    with urllib.request.urlopen(url + "/api/cluster_resources",
                                timeout=10) as r:
        res = json.loads(r.read())
    assert res  # totals present


# --------------------------------------------------------------------------- #
# Autoscaler
# --------------------------------------------------------------------------- #


def test_autoscaler_scales_up_on_demand_and_down_when_idle():
    from ray_tpu.autoscaler import (
        AutoscalerConfig,
        LocalNodeProvider,
        StandardAutoscaler,
    )
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    autoscaler = None
    try:
        cluster.connect()
        provider = LocalNodeProvider(cluster)
        autoscaler = StandardAutoscaler(
            cluster.gcs_address, provider,
            AutoscalerConfig(min_workers=0, max_workers=2,
                             node_resources={"CPU": 2, "pool": 2},
                             idle_timeout_s=3.0, update_period_s=0.5))
        autoscaler.start()

        @ray_tpu.remote
        def work(i):
            time.sleep(0.3)
            return i

        # Demand the head can never satisfy -> scale-up.
        refs = [work.options(resources={"pool": 1}).remote(i)
                for i in range(8)]
        out = ray_tpu.get(refs, timeout=120)
        assert out == list(range(8))
        assert autoscaler.num_launches >= 1
        assert len(provider.non_terminated_nodes()) >= 1

        # Idle -> scale back down to min_workers.
        deadline = time.monotonic() + 60
        while provider.non_terminated_nodes() and \
                time.monotonic() < deadline:
            time.sleep(0.5)
        assert not provider.non_terminated_nodes(), "idle nodes not reaped"
        assert autoscaler.num_terminations >= 1
    finally:
        if autoscaler is not None:
            autoscaler.stop()
        cluster.shutdown()


def test_request_resources_pins_capacity():
    from ray_tpu.autoscaler import (
        AutoscalerConfig,
        LocalNodeProvider,
        StandardAutoscaler,
        request_resources,
    )
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    autoscaler = None
    try:
        cluster.connect()
        provider = LocalNodeProvider(cluster)
        autoscaler = StandardAutoscaler(
            cluster.gcs_address, provider,
            AutoscalerConfig(min_workers=0, max_workers=3,
                             node_resources={"CPU": 4},
                             idle_timeout_s=300.0, update_period_s=0.5))
        autoscaler.start()
        # Ask for more CPUs than the head has: nodes appear without any
        # queued tasks.
        request_resources(num_cpus=6)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            total = sum(e["total"].get("CPU", 0) for e in
                        ray_tpu._global_runtime.gcs.call(
                            "get_resource_view").values() if e["alive"])
            if total >= 6:
                break
            time.sleep(0.5)
        assert total >= 6, f"cluster CPU total stuck at {total}"
        # Clearing the request allows (eventual) scale-down; just verify
        # the floor is lifted server-side.
        request_resources(bundles=[])
        resp = ray_tpu._global_runtime.gcs.call("resource_demand")
        assert resp["requests"] == []
    finally:
        if autoscaler is not None:
            autoscaler.stop()
        cluster.shutdown()
