"""Job tier (docs/JOBS.md): submission plane, runtime envs, jobs-as-
tenants, and job-scoped isolation/cleanup.

Mirrors the reference's `python/ray/tests/test_job_manager.py` +
runtime_env job tests, adapted to the agent-based submission plane
(GCS job table -> per-node agent -> driver subprocess).
"""

import os
import sys
import time
import uuid

import pytest

import ray_tpu
from conftest import pids_with_mark
from ray_tpu.job_submission import JobStatus, JobSubmissionClient


def _wait_terminal(client, sid, timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        status = client.get_job_status(sid)
        if status in JobStatus.TERMINAL:
            return status
        time.sleep(0.25)
    return client.get_job_status(sid)


def _client():
    return JobSubmissionClient(ray_tpu._global_runtime.gcs.address)


# --------------------------------------------------------------------------- #
# Submission plane: runtime envs and tenants ride the job record
# --------------------------------------------------------------------------- #


def test_submit_with_runtime_env_and_tenant(ray_start_regular):
    client = _client()
    sid = client.submit_job(
        entrypoint=(
            f"{sys.executable} -c \""
            "import os, ray_tpu; ray_tpu.init()\n"
            "print('MARKER=' + os.environ.get('JOB_MARKER', 'missing'))\n"
            "@ray_tpu.remote\n"
            "def probe():\n"
            "    return os.environ.get('JOB_MARKER', 'missing')\n"
            "print('TASK_SAW=' + ray_tpu.get(probe.remote()))\n"
            "ray_tpu.shutdown()\""),
        runtime_env={"env_vars": {"JOB_MARKER": "tenant-e2e"}},
        tenant={"name": "batch-team", "tier": "gold"},
        metadata={"owner": "jobs-test"})
    status = _wait_terminal(client, sid)
    logs = client.get_job_logs(sid)
    assert status == JobStatus.SUCCEEDED, f"status={status} logs={logs[-800:]}"
    # env_vars reach the driver process AND its workers (task-level
    # inheritance of the job runtime_env).
    assert "MARKER=tenant-e2e" in logs
    assert "TASK_SAW=tenant-e2e" in logs
    info = client.get_job_info(sid)
    assert info.status == JobStatus.SUCCEEDED
    assert info.tenant == "batch-team"
    assert info.runtime_env.get("env_vars") == {"JOB_MARKER": "tenant-e2e"}
    assert info.driver_job_id, "driver job never linked to the submission"
    assert info.node_id, "job record never recorded its agent node"
    client.close()


def test_submit_bad_tenant_rejected(ray_start_regular):
    client = _client()
    with pytest.raises(RuntimeError, match="tenant"):
        client.submit_job(entrypoint="true",
                          tenant={"name": "x", "tier": "platinum"})
    client.close()


def test_concurrent_jobs_with_distinct_envs(ray_start_regular):
    """Acceptance: N concurrent jobs with different runtime envs and
    tenant tiers share one cluster; each sees only its own env (worker
    isolation by job), and once they finish no driver process (or
    descendant carrying the mark) is left and nothing is unsealed."""
    client = _client()
    mark = "jobsenv_" + uuid.uuid4().hex[:12]
    sids = []
    for i, tier in enumerate(["gold", "silver", "bronze"]):
        sids.append(client.submit_job(
            entrypoint=(
                f"{sys.executable} -c \""
                f"_MARK = '{mark}'\n"
                "import os, ray_tpu; ray_tpu.init()\n"
                "@ray_tpu.remote\n"
                "def who():\n"
                "    return os.environ.get('JOB_COLOR', '?')\n"
                "got = ray_tpu.get([who.remote() for _ in range(4)])\n"
                "print('COLORS=' + ','.join(sorted(set(got))))\n"
                "ray_tpu.shutdown()\""),
            runtime_env={"env_vars": {"JOB_COLOR": f"color-{i}"}},
            tenant={"name": f"jobsenv-{tier}", "tier": tier}))
    for i, sid in enumerate(sids):
        status = _wait_terminal(client, sid)
        logs = client.get_job_logs(sid)
        assert status == JobStatus.SUCCEEDED, \
            f"job {i} status={status} logs={logs[-800:]}"
        assert f"COLORS=color-{i}" in logs, logs[-800:]
    store = ray_tpu._global_node.raylet.store
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and (
            pids_with_mark(mark) or store.stats()["num_unsealed"]):
        time.sleep(0.2)
    assert pids_with_mark(mark) == [], "a job's process outlived its job"
    assert store.stats()["num_unsealed"] == 0
    client.close()


# --------------------------------------------------------------------------- #
# Job-scoped isolation: KV purge, worker reclamation
# --------------------------------------------------------------------------- #


def test_job_scoped_kv_purged_on_finish(ray_start_regular):
    client = _client()
    sid = client.submit_job(
        entrypoint=(
            f"{sys.executable} -c \""
            "import ray_tpu; ray_tpu.init()\n"
            "ray_tpu.kv_put('state', b'job-private')\n"
            "print('KV=' + ray_tpu.kv_get('state').decode())\n"
            "ray_tpu.shutdown()\""))
    status = _wait_terminal(client, sid)
    logs = client.get_job_logs(sid)
    assert status == JobStatus.SUCCEEDED, logs[-800:]
    assert "KV=job-private" in logs
    job_hex = client.get_job_info(sid).driver_job_id
    gcs = ray_tpu._global_runtime.gcs
    # The whole job:<hex>: namespace died with the job.
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        resp = gcs.call("kv_get", {"namespace": f"job:{job_hex}:default",
                                   "key": b"state"})
        if resp.get("value") is None:
            break
        time.sleep(0.2)
    assert resp.get("value") is None, "job-scoped KV outlived its job"
    client.close()


def test_interactive_kv_is_job_scoped(ray_start_regular):
    ray_tpu.kv_put("k1", b"v1")
    assert ray_tpu.kv_get("k1") == b"v1"
    assert ray_tpu.kv_get("missing") is None
    ray_tpu.kv_del("k1")
    assert ray_tpu.kv_get("k1") is None
    # Scoping: the raw GCS key lives under this job's namespace.
    rt = ray_tpu._global_runtime
    ray_tpu.kv_put("k2", b"v2", namespace="ns")
    raw = rt.gcs.call("kv_get", {
        "namespace": f"job:{rt.job_id.hex()}:ns", "key": b"k2"})
    assert raw.get("value") == b"v2"


def test_job_workers_reclaimed_after_finish(ray_start_regular):
    """A finished job's workers (leased by its job-tagged env) retire:
    no orphan idle workers pin the pool for an env no task can want."""
    client = _client()
    sid = client.submit_job(
        entrypoint=(
            f"{sys.executable} -c \""
            "import ray_tpu; ray_tpu.init()\n"
            "@ray_tpu.remote\n"
            "def f(i):\n"
            "    return i\n"
            "print(sum(ray_tpu.get([f.remote(i) for i in range(8)])))\n"
            "ray_tpu.shutdown()\""))
    assert _wait_terminal(client, sid) == JobStatus.SUCCEEDED, \
        client.get_job_logs(sid)[-800:]
    job_hex = client.get_job_info(sid).driver_job_id
    raylet = ray_tpu._global_node.raylet  # in-process head node
    deadline = time.monotonic() + 20
    leftovers = None
    while time.monotonic() < deadline:
        with raylet.pool._lock:
            leftovers = [h for h in raylet.pool._workers.values()
                         if h.state not in ("dead",)
                         and h.granted_env.get("RAY_TPU_JOB_ID") == job_hex]
        if not leftovers:
            break
        time.sleep(0.5)
    assert not leftovers, \
        f"{len(leftovers)} workers survived their job's finish"
    client.close()


# --------------------------------------------------------------------------- #
# Detached actors: first-class lifetime, cross-job name resolution
# --------------------------------------------------------------------------- #


def test_detached_actor_survives_job(ray_start_regular):
    client = _client()
    sid = client.submit_job(
        entrypoint=(
            f"{sys.executable} -c \""
            "import ray_tpu; ray_tpu.init()\n"
            "@ray_tpu.remote\n"
            "class Keeper:\n"
            "    def __init__(self):\n"
            "        self.v = 0\n"
            "    def bump(self):\n"
            "        self.v += 1\n"
            "        return self.v\n"
            "d = Keeper.options(name='jobs-keeper', "
            "lifetime='detached').remote()\n"
            "e = Keeper.options(name='jobs-ephemeral').remote()\n"
            "print('BUMP=', ray_tpu.get(d.bump.remote()))\n"
            "print('EPH=', ray_tpu.get(e.bump.remote()))\n"
            "ray_tpu.shutdown()\""))
    status = _wait_terminal(client, sid)
    assert status == JobStatus.SUCCEEDED, client.get_job_logs(sid)[-800:]
    # Cross-job name resolution: this (interactive) driver is a different
    # job, yet the detached actor resolves by name and kept its state.
    handle = ray_tpu.get_actor("jobs-keeper")
    assert ray_tpu.get(handle.bump.remote(), timeout=30) == 2
    # The non-detached actor died with its owning job.
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        try:
            ray_tpu.get_actor("jobs-ephemeral")
        except ValueError:
            break
        time.sleep(0.25)
    with pytest.raises(ValueError):
        ray_tpu.get_actor("jobs-ephemeral")
    ray_tpu.kill(handle)
    client.close()


# --------------------------------------------------------------------------- #
# Working dir: prepared client-side, materialized on the agent node
# --------------------------------------------------------------------------- #


def test_working_dir_job(ray_start_regular, tmp_path):
    (tmp_path / "jobdata.txt").write_text("payload-42\n")
    (tmp_path / "jobmod.py").write_text(
        "def answer():\n    return open('jobdata.txt').read().strip()\n")
    client = _client()
    sid = client.submit_job(
        entrypoint=(
            f"{sys.executable} -c \""
            "import jobmod\n"
            "print('DATA=' + jobmod.answer())\""),
        runtime_env={"working_dir": str(tmp_path)})
    status = _wait_terminal(client, sid)
    logs = client.get_job_logs(sid)
    assert status == JobStatus.SUCCEEDED, logs[-800:]
    # The driver ran INSIDE the materialized working_dir (cwd on
    # sys.path + relative file reads both resolve), which the client
    # uploaded as a content-addressed zip — the record carries the URI,
    # never the client-local path.
    assert "DATA=payload-42" in logs
    assert client.get_job_info(sid).runtime_env["working_dir"].startswith(
        "kv://runtime_env/")
    client.close()


# --------------------------------------------------------------------------- #
# GCS failover: the job table is checkpointed state
# --------------------------------------------------------------------------- #


def test_job_table_survives_gcs_restart():
    import tempfile

    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    path = os.path.join(tempfile.mkdtemp(), "gcs_tables.bin")
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2},
                      gcs_storage_path=path)
    cluster.wait_for_nodes()
    cluster.connect()
    try:
        client = JobSubmissionClient(ray_tpu._global_runtime.gcs.address)
        done = client.submit_job(
            entrypoint=f"{sys.executable} -c \"print('done-job')\"",
            metadata={"k": "v"})
        assert _wait_terminal(client, done) == JobStatus.SUCCEEDED
        client.close()
        # Force a snapshot cycle to include the terminal record, then
        # fail the GCS over.
        cluster.gcs._persist_tables()
        cluster.restart_gcs()
        client = JobSubmissionClient(ray_tpu._global_runtime.gcs.address)
        deadline = time.monotonic() + 30
        info = None
        while time.monotonic() < deadline:
            try:
                info = client.get_job_info(done)
                break
            except (ValueError, OSError):
                time.sleep(0.5)
        assert info is not None, "job record lost across GCS restart"
        assert info.status == JobStatus.SUCCEEDED
        assert info.metadata == {"k": "v"}
        client.close()
    finally:
        cluster.shutdown()


# --------------------------------------------------------------------------- #
# JobAdmission: stride fairness + rate quotas (unit)
# --------------------------------------------------------------------------- #


def test_job_admission_stride_fairness():
    from ray_tpu.jobs.tenancy import JobAdmission

    adm = JobAdmission()
    adm.register("gold", {"name": "g", "tier": "gold"})     # weight 8
    adm.register("bronze", {"name": "b", "tier": "bronze"})  # weight 1
    grants = {"gold": 0, "bronze": 0}
    for _ in range(90):
        winner = adm.order(["gold", "bronze"])[0]
        assert adm.admit(winner) == 0.0
        grants[winner] += 1
    # ~8:1 split (stride scheduling): 80 vs 10 exactly for these weights.
    assert grants["gold"] == 80, grants
    assert grants["bronze"] == 10, grants


def test_job_admission_rate_quota_and_refund():
    from ray_tpu.jobs.tenancy import JobAdmission

    adm = JobAdmission()
    adm.register("metered", {"name": "m", "rps_limit": 1.0, "burst": 2.0})
    now = 100.0
    assert adm.admit("metered", now=now) == 0.0
    assert adm.admit("metered", now=now) == 0.0
    wait = adm.admit("metered", now=now)  # burst exhausted
    assert wait > 0.0
    # Refund restores the token: the next admit at the same instant works.
    adm.refund("metered")
    assert adm.admit("metered", now=now) == 0.0
    # Unknown jobs admit with defaults (lazy entry), and unregister drops
    # the entry outright.
    assert adm.admit("anon") == 0.0
    adm.unregister("anon")
    adm.unregister("metered")
    assert adm.snapshot() == {}


def test_env_hash_stability():
    from ray_tpu.core.runtime_env import env_hash

    assert env_hash(None) == ""
    assert env_hash({}) == ""
    a = env_hash({"env_vars": {"A": "1", "B": "2"}, "preimports": ["x", "y"]})
    b = env_hash({"preimports": ["y", "x"], "env_vars": {"B": "2", "A": "1"}})
    assert a == b, "env_hash must canonicalize ordering"
    assert a != env_hash({"env_vars": {"A": "1"}})
    assert len(a) == 16
