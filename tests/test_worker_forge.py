"""Worker forge: fork-safety contract, granted-env propagation, cold
fallback + background restart, event-driven death detection, and orphan
hygiene after node stop (the /proc-scan idiom from the JobManager tests).

Process model under test: ONE template per driver process (shared by
every in-process raylet, reused across clusters), carrying a
``--tag rtpuforge-<driver pid>`` argv marker that every forked worker
inherits. The template itself legitimately lingers after Node.stop (its
driver stops it at interpreter exit; it self-exits on idle or when the
driver was killed); its CHILDREN — the forked workers — must not, and
cold workers carry RAY_TPU_SESSION in their exec-time environ for the
same scan."""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.core.worker_forge import WorkerForge, process_tag


def _template_pids(tag: str):
    """Pids whose /proc cmdline carries the forge tag — the template plus
    any forked worker (children inherit argv). A zombie has an empty
    cmdline, so killed-but-unreaped processes cannot false-positive."""
    pids = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                if tag.encode() in f.read():
                    pids.append(int(pid))
        except OSError:
            continue  # exited while scanning
    return pids


def _children_of(ppids):
    """Pids whose parent is in `ppids` (forked workers are children of
    the template)."""
    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            if int(fields[1]) in ppids:
                out.append(int(pid))
        except (OSError, IndexError, ValueError):
            continue
    return out


def _session_worker_pids(mark: str):
    """Cold-exec workers: RAY_TPU_SESSION=<mark> in the exec-time
    environ."""
    pids = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if f"RAY_TPU_SESSION={mark}".encode() in f.read():
                    pids.append(int(pid))
        except OSError:
            continue
    return pids


@pytest.fixture(scope="module")
def forge_cluster():
    """Module-scoped single-node cluster with a ready forge."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    raylet = ray_tpu._global_node.raylet
    assert raylet.forge is not None, "forge should be enabled by default"
    assert raylet.forge.wait_ready(60), "forge template never became ready"
    created = ray_tpu._global_runtime
    yield raylet
    if ray_tpu._global_runtime is created:
        ray_tpu.shutdown()


def test_template_fork_safety(forge_cluster):
    """The template must be fork-safe at all times: exactly one thread
    (no RPC clients, no pools) and no initialized XLA backend client."""
    st = forge_cluster.forge.status()
    assert st["threads"] == 1, f"template grew threads: {st}"
    assert not st["xla_initialized"], "template initialized an XLA backend"
    assert "ray_tpu.core.worker" in st["preimported"]
    assert not st["import_errors"], st["import_errors"]


def test_forge_spawn_registers_and_serves(forge_cluster):
    """A forge fork registers like a cold worker and executes tasks; the
    fork path lands well under the cold exec path."""
    pool = forge_cluster.pool

    t0 = time.perf_counter()
    h = pool.spawn_worker(env_extra={}, kind="forge")
    assert h.registered.wait(30) and h.conn is not None
    forge_ms = (time.perf_counter() - t0) * 1e3
    assert h.spawn_kind == "forge"

    t0 = time.perf_counter()
    h2 = pool.spawn_worker(env_extra={}, kind="cold")
    assert h2.registered.wait(60) and h2.conn is not None
    cold_ms = (time.perf_counter() - t0) * 1e3
    assert h2.spawn_kind == "cold"

    # The mechanism claim, with CI-load headroom: fork skips the import
    # bill, so it must land under the exec path.
    assert forge_ms < cold_ms, (forge_ms, cold_ms)

    for h_ in (h, h2):
        pool.mark_dead(h_.worker_id)
        h_.proc.terminate()


def test_granted_env_reaches_forked_worker(forge_cluster):
    """runtime_env env_vars ride the granted env into the forked child
    (applied post-fork, before the worker connects)."""
    pool = forge_cluster.pool
    before = pool.spawn_counts["forge"]

    @ray_tpu.remote
    def read_env():
        return os.environ.get("FORGE_PROBE"), os.getpid()

    val, pid = ray_tpu.get(
        read_env.options(
            runtime_env={"env_vars": {"FORGE_PROBE": "x42"}}).remote(),
        timeout=60)
    assert val == "x42"
    assert pool.spawn_counts["forge"] > before, \
        "granted-env spawn should have taken the forge path"
    handles = [h for h in pool._workers.values() if h.pid == pid]
    assert handles and handles[0].spawn_kind == "forge"


@pytest.mark.parametrize("kind", ["forge", "cold"])
def test_dead_worker_detection_is_event_driven(forge_cluster, kind):
    """A killed worker is marked dead in well under the 2s reaper poll:
    forge forks via the template's exit-event stream, cold spawns via the
    per-process waiter thread (plus the connection-loss path for both)."""
    pool = forge_cluster.pool
    h = pool.spawn_worker(env_extra={}, kind=kind)
    assert h.registered.wait(60) and h.conn is not None
    t0 = time.perf_counter()
    h.proc.kill()  # SIGKILL: no graceful-exit help from the worker
    while h.state != "dead" and time.perf_counter() - t0 < 5:
        time.sleep(0.01)
    elapsed = time.perf_counter() - t0
    assert h.state == "dead"
    assert elapsed < 1.5, f"{kind} death took {elapsed:.2f}s (poll-bound?)"


def test_forge_death_falls_back_cold_then_restarts():
    """Killing the template must not fail spawns (cold fallback) and the
    forge must come back in the background; TPU-style grants always cold."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    try:
        raylet = ray_tpu._global_node.raylet
        forge = raylet.forge
        assert forge.wait_ready(60)
        assert not WorkerForge.compatible({"RAY_TPU_GRANTED_TPU": "1"})

        gen = forge.generation
        forge.proc.kill()
        # The first spawn may race the death notice; either way it must
        # produce a working worker (forge fork from the old incarnation or
        # cold fallback) and trigger the background restart.
        h = raylet.pool.spawn_worker(env_extra={})
        assert h.registered.wait(60) and h.conn is not None
        deadline = time.monotonic() + 60
        while not forge.alive and time.monotonic() < deadline:
            forge.restart_async()
            time.sleep(0.2)
        assert forge.alive and forge.generation >= gen, "forge never restarted"
        h2 = raylet.pool.spawn_worker(env_extra={})
        assert h2.registered.wait(60) and h2.spawn_kind == "forge"
    finally:
        ray_tpu.shutdown()


def test_no_orphan_workers_after_shutdown():
    """Node.stop() leaves no worker behind: no forked children of the
    template, no cold-exec workers for the session (JobManager orphan
    idiom, /proc scan). The template itself may linger — it is
    process-shared and self-reaps (see test_template_dies_with_driver)."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    raylet = ray_tpu._global_node.raylet
    assert raylet.forge.wait_ready(60)
    mark = raylet.session_suffix
    tag = process_tag()

    @ray_tpu.remote
    class Probe:
        def pid(self):
            return os.getpid()

    a = Probe.remote()
    ray_tpu.get(a.pid.remote(), timeout=60)

    @ray_tpu.remote
    def task_pid():
        return os.getpid()

    ray_tpu.get(task_pid.remote(), timeout=60)
    templates = _template_pids(tag)
    assert templates, "expected a live forge template"

    def leaked():
        return _children_of(set(templates)) + _session_worker_pids(mark)

    assert leaked(), "expected live workers while the cluster is up"
    ray_tpu.shutdown()
    deadline = time.monotonic() + 10
    while leaked() and time.monotonic() < deadline:
        time.sleep(0.2)
    assert leaked() == [], f"orphaned workers after shutdown: {leaked()}"


def test_template_dies_with_driver():
    """A lingering template must not outlive the process that spawned it
    (ppid guard): a short-lived driver's template self-reaps."""
    code = (
        "import ray_tpu, os\n"
        "ray_tpu.init(num_cpus=1)\n"
        "ray_tpu._global_node.raylet.forge.wait_ready(60)\n"
        "from ray_tpu.core.worker_forge import process_tag\n"
        "print(process_tag(), flush=True)\n"
        # exit WITHOUT shutdown: the hard case — nobody detaches cleanly
        "os._exit(0)\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    tag = proc.stdout.strip().splitlines()[-1]
    assert tag.startswith("rtpuforge-"), proc.stderr[-500:]
    deadline = time.monotonic() + 10
    while _template_pids(tag) and time.monotonic() < deadline:
        time.sleep(0.25)
    assert _template_pids(tag) == [], \
        "template outlived its driver process"


def test_no_process_left_when_driver_exits():
    """A driver that ends normally has stopped everything it started by
    the time it is gone (kill_templates at interpreter exit): nothing with
    its tag or its session is alive at the instant it returns — not a
    second later, which is all the ppid guard alone could promise."""
    code = (
        "import ray_tpu\n"
        "ray_tpu.init(num_cpus=1)\n"
        "raylet = ray_tpu._global_node.raylet\n"
        "raylet.forge.wait_ready(60)\n"
        "@ray_tpu.remote\n"
        "def f():\n"
        "    return 1\n"
        "assert ray_tpu.get(f.remote(), timeout=60) == 1\n"
        "from ray_tpu.core.worker_forge import process_tag\n"
        "print(process_tag(), raylet.session_suffix, flush=True)\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-500:]
    tag, mark = proc.stdout.strip().splitlines()[-1].split()
    assert _template_pids(tag) == [] and _session_worker_pids(mark) == [], \
        "a process outlived its driver's normal exit"
