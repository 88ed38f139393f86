"""Test harness config.

JAX runs on an 8-device virtual CPU platform (mirrors how the reference
exercises multi-node logic on one machine via `cluster_utils.Cluster`); env
must be set before the first jax import anywhere in the process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Worker subprocesses must resolve functions defined in test modules (pytest
# puts tests/ on the driver's sys.path; spawned workers inherit PYTHONPATH).
_tests_dir = os.path.dirname(os.path.abspath(__file__))
_pp = os.environ.get("PYTHONPATH", "")
if _tests_dir not in _pp.split(os.pathsep):
    os.environ["PYTHONPATH"] = (
        _tests_dir + (os.pathsep + _pp if _pp else ""))

import pytest  # noqa: E402

# Opt-in cluster-wide sanitizer run: RAY_TPU_LOCK_WITNESS=1 installs the
# lock-order witness (with hang watchdog) BEFORE any cluster fixture
# creates a lock, so every tier-1 test doubles as a race-detection pass.
# The session teardown below then fails the run on any recorded cycle.
WITNESS_ENABLED = os.environ.get("RAY_TPU_LOCK_WITNESS") == "1"
if WITNESS_ENABLED:
    from ray_tpu.util import lock_witness

    lock_witness.install(watchdog_s=float(
        os.environ.get("RAY_TPU_LOCK_WITNESS_WATCHDOG", "60")))


@pytest.fixture(scope="session", autouse=True)
def _lock_witness_session_gate():
    yield
    if WITNESS_ENABLED:
        from ray_tpu.util import lock_witness

        rep = lock_witness.report()
        assert rep.cycles == [], (
            "lock-order cycles recorded during the suite:\n"
            + "\n".join(rep.cycles))


@pytest.fixture(scope="session")
def multi_device_workers():
    """Multi-device CPU meshes in WORKER subprocesses.

    The XLA_FLAGS export above runs at conftest import — before any jax
    import and before any cluster exists — so every worker subprocess
    (cold execs inherit os.environ; forge forks inherit the template's
    env, and the template is spawned before XLA init) sees an 8-device
    CPU platform. Tests that build tp meshes inside replicas/rank actors
    take this fixture as their explicit dependency on that guarantee;
    it asserts the flag is still exported and returns the device count.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    marker = "xla_force_host_platform_device_count="
    assert marker in flags, (
        "XLA_FLAGS lost the forced device count — worker meshes would "
        f"be single-device: {flags!r}")
    count = flags.split(marker, 1)[1].split()[0]
    return int(count)


@pytest.fixture(scope="module")
def ray_start_shared():
    """Module-scoped cluster: fast, shared across a module's tests.

    Teardown only shuts down the cluster THIS fixture created: the runtime is
    a process-global, and a late-running finalizer from another module must
    not tear down its successor's cluster.
    """
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    created = ray_tpu._global_runtime
    yield
    if ray_tpu._global_runtime is created:
        ray_tpu.shutdown()


@pytest.fixture()
def ray_start_regular():
    """Function-scoped fresh cluster for tests that mutate cluster state."""
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    created = ray_tpu._global_runtime
    yield
    if ray_tpu._global_runtime is created:
        ray_tpu.shutdown()


def assert_compiles_once(source, *counters, context=None):
    """The compile-once discipline, shared across the JAX test surface
    (the dynamic complement of raylint's RL020/RL024 static checks).

    Two forms:

    - ``assert_compiles_once(jitted_fn)`` — the callable's trace cache
      holds exactly ONE compiled program (``_cache_size()``);
    - ``assert_compiles_once(stats, "prefill_compiles", ...)`` — each
      named counter in a stats/metrics dict is exactly 1.

    `context` is included in the failure message (engine name, arm
    label) so parametrized sweeps stay diagnosable.
    """
    if not isinstance(source, dict):
        n = source._cache_size()
        assert n == 1, (context, "trace cache holds", n, "programs")
        return
    assert counters, "name the counters to check on a stats dict"
    for key in counters:
        assert source.get(key) == 1, (context, key, source)


def pids_with_mark(mark: str):
    """Pids whose /proc cmdline carries `mark`. The job leak tests put
    the mark INSIDE the `python -c` source so it lands in the
    grandchild's argv — a shell-comment mark dies with the sh wrapper
    and the scan would pass vacuously. (A zombie has an empty cmdline,
    so a killed-but-unreaped process cannot false-positive.)"""
    pids = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmdline = f.read()
        except OSError:
            continue  # exited while scanning
        if mark.encode() in cmdline:
            pids.append(pid)
    return pids


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: tests over ~10 s (multi-node recovery, long compiles), "
        "excluded from the tier-1 budget; `pytest tests/` with no -m, as "
        "scripts/gate.sh calls it, runs them")


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a DESCRIBED v5e host, for compile rehearsals: the TPU's
    compiler is installed here and compiles for a chip that is not
    attached. Described inside the fixture, never at import (only one
    process at a time may load the TPU's library); the test skips where it
    cannot be. (The files under tests/benchmarks keep copies of their own:
    only a `benchmark` PR may edit them.)"""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# A test under tests/benchmarks that asserts where the PROGRAM stood when it
# was written, and that the program has outgrown: it is the benchmark's
# file, which only a `benchmark` PR may edit, so the PR that outgrows it
# enters it here and holds every assertion of it that still stands in a
# twin outside tests/benchmarks. Strict: the day a `benchmark` PR rewords
# it, it passes and its entry must go (as PR 59's rewording took the two
# entries of PR 50 with it).
OUTGROWN = {
    "tests/benchmarks/test_bench_sdar.py::"
    "test_the_cells_rehearsal_runs_end_to_end":
        "pins the block step's paged call at (4, 4, 8, 128), one block a "
        "row; since PR 63 a row is two blocks wide, (4, 8, 8, 128): "
        "tests/test_sdar_rehearsal.py holds every other assertion of it",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        reason = OUTGROWN.get(item.nodeid)
        if reason is not None:
            item.add_marker(pytest.mark.xfail(reason=reason, strict=True))
