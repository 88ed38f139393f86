"""Chaos plane: deterministic schedules, RPC fault hook, bounded recovery.

The contract under test (docs/FAULT_TOLERANCE.md): same seed => same
injected event log; the RPC fault filter is provably inert when absent;
every fault class recovers within the deadline with a measured MTTR; and
nothing — neither a parked future nor a state-machine transition — is
allowed to wedge silently.
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu.chaos import (
    ChaosRunner,
    ChaosSchedule,
    HangWatchdog,
    NodeKillInjector,
    RpcFaultInjector,
    TransitionWatch,
    WorkerKillInjector,
)
from ray_tpu.cluster_utils import Cluster
from ray_tpu.core import rpc as rpc_mod
from ray_tpu.core.rpc import (
    ConnectionLost,
    RpcClient,
    RpcServer,
    clear_chaos_filter,
    install_chaos_filter,
)


# ------------------------------------------------------------ determinism


def test_schedule_same_seed_same_event_log():
    kinds = {"node_kill": 3.0, "gcs_restart": 1.0, "rpc_faults": 1.0}
    a = ChaosSchedule(seed=1234, kinds=kinds, period_s=2.0, count=20)
    b = ChaosSchedule(seed=1234, kinds=kinds, period_s=2.0, count=20)
    c = ChaosSchedule(seed=1235, kinds=kinds, period_s=2.0, count=20)
    assert a.signatures() == b.signatures()
    assert a.signatures() != c.signatures()
    # Times are ordered-ish (one per slot) and kinds come from the set.
    assert all(e.kind in kinds for e in a.events)
    assert [e.seq for e in a.events] == list(range(20))


def test_runner_executes_exactly_the_scheduled_log():
    """The runner's executed log IS the schedule — injectors see events
    in order with the scheduled draws (proven without a cluster)."""

    class NullInjector:
        kind = "noop"

        def __init__(self):
            self.seen = []

        def inject(self, event):
            self.seen.append(event.signature())
            return {"ok": True}

        def recovered(self):
            return True

    sched = ChaosSchedule(seed=7, kinds=("noop",), period_s=0.05, count=5)
    inj = NullInjector()
    runner = ChaosRunner(cluster=None, schedule=sched,
                         injectors={"noop": inj}, recovery_deadline_s=5)
    with runner:
        assert runner.wait(timeout=10)
    assert runner.executed_signatures == sched.signatures()
    assert inj.seen == sched.signatures()
    assert runner.faults_injected == 5
    runner.assert_recovered()
    mttr = runner.mttr_by_kind()["noop"]
    assert mttr["count"] == 5 and mttr["max_ms"] < 1000


# ------------------------------------------------------------ rpc faults


@pytest.fixture()
def rpc_pair():
    server = RpcServer(name="chaos-test")
    server.register("echo", lambda conn, data: data)
    server.start()
    client = RpcClient(server.address, name="chaos-test-client")
    yield server, client
    clear_chaos_filter()
    client.close()
    server.stop()


def test_rpc_filter_error_and_clear(rpc_pair):
    _, client = rpc_pair
    assert client.call("echo", 1) == 1
    install_chaos_filter(lambda name, addr, method: "error")
    with pytest.raises(ConnectionLost):
        client.call("echo", 2)
    clear_chaos_filter()
    # Inert again: the connection itself was never closed.
    assert client.call("echo", 3) == 3


def test_rpc_filter_drop_hits_callers_own_timeout(rpc_pair):
    _, client = rpc_pair
    install_chaos_filter(lambda name, addr, method: "drop")
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        client.call("echo", 1, timeout=0.4)
    assert 0.3 < time.monotonic() - t0 < 5.0
    clear_chaos_filter()
    assert client.call("echo", 2) == 2


def test_rpc_filter_delay_and_selectivity(rpc_pair):
    _, client = rpc_pair

    def only_echo_delay(name, addr, method):
        return ("delay", 0.3) if method == "echo" else None

    install_chaos_filter(only_echo_delay)
    t0 = time.monotonic()
    assert client.call("echo", 1) == 1
    assert time.monotonic() - t0 >= 0.3
    clear_chaos_filter()


def test_rpc_filter_disabled_path_is_single_guard():
    """Inertness proof at the code level: with no filter installed the
    send path consults ONE module global and nothing else (the bench's
    A-B-A overhead check covers the runtime side)."""
    assert rpc_mod._CHAOS_FILTER is None


def test_rpc_fault_injector_window():
    inj = RpcFaultInjector(fraction=1.0, action="error", window_s=0.2)
    sched = ChaosSchedule(seed=3, kinds=("rpc_faults",), period_s=0.01,
                          count=1)
    inj.inject(sched.events[0])
    assert rpc_mod._CHAOS_FILTER is not None
    assert not inj.recovered()  # window still open
    time.sleep(0.25)
    assert inj.recovered()
    assert rpc_mod._CHAOS_FILTER is None  # filter removed with the window


# ------------------------------------------------------------- watchdog


def test_hang_watchdog_attributes_parked_ops():
    wd = HangWatchdog(limit_s=0.3, poll_s=0.05)
    release = threading.Event()

    def parked():
        with wd.track("test-op"):
            release.wait(5.0)

    t = threading.Thread(target=parked, daemon=True)
    with wd:
        t.start()
        time.sleep(0.8)
    release.set()
    t.join()
    assert wd.hang_count >= 1
    assert "test-op" in wd.hangs[0]
    with pytest.raises(AssertionError):
        wd.assert_no_hangs()


def test_hang_watchdog_quiet_on_bounded_ops():
    wd = HangWatchdog(limit_s=0.5, poll_s=0.05)
    with wd:
        for _ in range(5):
            with wd.track("quick"):
                time.sleep(0.02)
    wd.assert_no_hangs()


# ------------------------------------------------------- transition watch


def test_transition_watch_attribution_and_progress():
    watch = TransitionWatch("test", deadline_s=0.2)
    watch.enter("replica-1", "STARTING")
    watch.enter("replica-1", "STARTING")  # same state: clock keeps running
    assert watch.stuck() == []
    time.sleep(0.3)
    stuck = watch.stuck()
    assert len(stuck) == 1 and stuck[0][0] == "replica-1" \
        and stuck[0][1] == "STARTING"
    # Progress (a NEW state) resets the clock; completion clears it.
    watch.enter("replica-1", "RECOVERING")
    assert watch.stuck() == []
    watch.clear("replica-1")
    time.sleep(0.3)
    assert watch.stuck() == []
    # fail_stuck counts and clears.
    watch.enter("replica-2", "STARTING")
    time.sleep(0.3)
    assert [k for k, _s, _e in watch.fail_stuck()] == ["replica-2"]
    assert watch.stuck_total == 1


def test_transition_watch_disabled_at_zero_deadline():
    watch = TransitionWatch("test", deadline_s=0.0)
    watch.enter("x", "STARTING")
    time.sleep(0.1)
    assert watch.stuck() == []


# ---------------------------------------------------------- chaos e2e


def test_worker_kill_under_actor_load():
    """Worker-kill injector: a restartable actor's worker is SIGKILLed;
    the fault recovers (actor ALIVE again) within the deadline and
    callers never hang."""
    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    try:
        cluster.wait_for_nodes()
        cluster.connect()

        @ray_tpu.remote(max_restarts=4)
        class Bumper:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        b = Bumper.remote()
        assert ray_tpu.get(b.bump.remote(), timeout=30) == 1

        sched = ChaosSchedule(seed=11, kinds=("worker_kill",),
                              period_s=0.5, count=1, jitter=0.0)
        runner = ChaosRunner(
            cluster, sched,
            {"worker_kill": WorkerKillInjector(cluster, actors_only=True)},
            recovery_deadline_s=30)
        with HangWatchdog(limit_s=45) as wd:
            with runner:
                assert runner.wait(timeout=60)
                deadline = time.time() + 30
                ok = False
                while time.time() < deadline:
                    try:
                        ray_tpu.get(b.bump.remote(), timeout=5)
                        ok = True
                        break
                    except Exception:
                        time.sleep(0.2)
                assert ok, "actor never served again after worker kill"
        runner.assert_recovered()
        wd.assert_no_hangs()
        assert runner.faults_injected == 1
        assert runner.mttr_by_kind()["worker_kill"]["count"] == 1
    finally:
        cluster.shutdown()


@pytest.mark.slow
def test_node_kill_chaos_with_task_load():
    """Seeded node-kill chaos under retried task load: all results
    correct, every fault recovered with bounded MTTR, executed log
    matches the schedule, zero hangs."""
    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    try:
        for _ in range(2):
            cluster.add_node(num_cpus=2, resources={"churn": 2})
        cluster.wait_for_nodes()
        cluster.connect()

        @ray_tpu.remote
        def slow_square(x):
            time.sleep(0.2)
            return x * x

        sched = ChaosSchedule(seed=42, kinds=("node_kill",), period_s=1.5,
                              count=2, jitter=0.2)
        runner = ChaosRunner(
            cluster, sched,
            {"node_kill": NodeKillInjector(
                cluster, replace=True,
                node_args={"num_cpus": 2, "resources": {"churn": 2}})},
            recovery_deadline_s=45)
        opts = {"resources": {"churn": 1}, "max_retries": 8}
        with HangWatchdog(limit_s=90) as wd:
            with runner:
                results = ray_tpu.get(
                    [slow_square.options(**opts).remote(i)
                     for i in range(16)], timeout=120)
                assert runner.wait(timeout=90)
        assert results == [i * i for i in range(16)]
        runner.assert_recovered()
        wd.assert_no_hangs()
        assert runner.executed_signatures == sched.signatures()
        mttr = runner.mttr_by_kind().get("node_kill")
        assert mttr and mttr["count"] >= 1
    finally:
        cluster.shutdown()


@pytest.mark.slow  # multi-node cluster + recovery: >10s under load
def test_node_kill_chaos_with_serve_load():
    """One seeded node kill while a two-replica deployment takes a
    steady trickle of requests through its handle: the fault recovers
    within the deadline, the executed log is the schedule, no request's
    result parks forever, and most requests are answered."""
    from ray_tpu import serve

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 3})
    node_args = {"num_cpus": 2, "resources": {"churn": 2}}
    try:
        for _ in range(2):
            cluster.add_node(**node_args)
        cluster.wait_for_nodes()
        cluster.connect()

        @serve.deployment(num_replicas=2, max_concurrent_queries=32)
        class Echo:
            def __call__(self, payload):
                return payload

        handle = serve.run(Echo.bind())
        assert ray_tpu.get([handle.remote(i) for i in range(8)],
                           timeout=60) == list(range(8))

        sched = ChaosSchedule(seed=20260804, kinds=("node_kill",),
                              period_s=1.5, count=1, jitter=0.25)
        runner = ChaosRunner(
            cluster, sched,
            {"node_kill": NodeKillInjector(cluster, replace=True,
                                           node_args=node_args)},
            recovery_deadline_s=45)
        ok = err = 0
        with HangWatchdog(limit_s=60) as wd:
            with runner:
                refs = []
                for i in range(50):           # ~3.5 s: spans the kill
                    time.sleep(0.07)
                    try:
                        refs.append(handle.remote(i))
                    except Exception:  # noqa: BLE001 — routed into a
                        err += 1       # replica that died mid-churn
                for ref in refs:
                    try:
                        with wd.track("serve-result"):
                            ray_tpu.get(ref, timeout=30)
                        ok += 1
                    except Exception:  # noqa: BLE001 — replica died mid-call
                        err += 1
                assert runner.wait(timeout=90)
        runner.assert_recovered()
        wd.assert_no_hangs()
        assert runner.executed_signatures == sched.signatures()
        assert runner.faults_injected == 1
        assert ok + err == 50 and err < 25, (ok, err)
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001 — the controller may have died
            pass
        cluster.shutdown()


def test_train_gang_elastic_restart_resumes_from_checkpoint():
    """Kill a train worker mid-run: the gang aborts and restarts as a
    unit on a fresh placement group, and the loop RESUMES from the last
    reported checkpoint (step continuity, no lost progress beyond the
    checkpoint lag)."""
    from ray_tpu.train import session
    from ray_tpu.train.checkpoint import Checkpoint
    from ray_tpu.train.config import (
        FailureConfig,
        RunConfig,
        ScalingConfig,
    )
    from ray_tpu.train.trainer import DataParallelTrainer

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    try:
        def loop(config):
            ckpt = session.get_checkpoint()
            start = ckpt.to_dict()["step"] + 1 if ckpt is not None else 0
            for step in range(start, 10):
                time.sleep(0.2)
                session.report(
                    {"step": step, "start": start,
                     "world": session.get_world_size()},
                    checkpoint=Checkpoint.from_dict({"step": step})
                    if session.get_world_rank() == 0 else None)

        trainer = DataParallelTrainer(
            loop, scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(
                name="chaos_resume_test",
                failure_config=FailureConfig(max_failures=3)))

        def killer():
            time.sleep(1.6)
            rt = ray_tpu._global_runtime
            rt.raylet.call("chaos_kill_worker",
                           {"draw": 1, "actors_only": True})

        threading.Thread(target=killer, daemon=True).start()
        result = trainer.fit()
        assert result.error is None, result.error
        steps = [m["step"] for m in result.metrics_history]
        starts = sorted({m["start"] for m in result.metrics_history})
        assert steps[-1] == 9, steps
        # The run restarted at least once AND resumed from a checkpoint
        # (a non-zero start step), not from scratch.
        assert len(starts) >= 2 and starts[-1] > 0, starts
    finally:
        ray_tpu.shutdown()


@pytest.mark.slow
def test_serve_stuck_transition_fails_loudly():
    """A replica wedged in STARTING past chaos_recovery_deadline_s is
    failed LOUDLY (attributed critical + forced replacement + counter in
    status()) instead of silently spinning."""
    from ray_tpu import serve

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4,
                 _system_config={"chaos_recovery_deadline_s": 1.5})
    try:
        @serve.deployment
        class Wedged:
            def __init__(self):
                time.sleep(120)  # never finishes starting

            def __call__(self, x):
                return x

        try:
            serve.run(Wedged.bind(), timeout_s=6)
        except Exception:  # noqa: BLE001 — never becomes ready, expected
            pass
        st = serve.status()
        assert st["Wedged"]["stuck_transitions"] >= 1, st
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001
            pass
        ray_tpu.shutdown()


@pytest.mark.slow
def test_multiplexed_replica_kill_reloads_adapters_no_leaks():
    """ISSUE 11 satellite + ISSUE 16 warm-radix-tree extension: the
    adapter-multiplexed replica joins the chaos victim set WITH a warm
    radix prefix cache. The three adapters share one prompt, so the
    baselines cross-share cached prefix blocks on one arena (KV is
    adapter-invariant under q/o LoRA targeting). Kill the replica; the
    controller respawns it, requests reload each adapter ON DEMAND and
    rebuild the radix tree from scratch (same seeds => token-identical
    outputs, warm or cold), the rebuilt arena holds zero leaked blocks
    beyond the cache's own donations, and recovery stays under the
    deadline."""
    from ray_tpu import serve
    from ray_tpu.inference import LLMServer

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    try:
        adapters = {"m-a": {"seed": 11}, "m-b": {"seed": 22},
                    "m-c": {"seed": 33}}
        # block_size 4: the 9-token shared prompt caches 2 full blocks,
        # so adapters b/c hit adapter a's donated prefix.
        handle = serve.run(LLMServer.options(
            name="mux", num_replicas=1,
            max_concurrent_queries=16).bind(
                "tiny", 256, 8, {"block_size": 4, "max_blocks_per_seq": 16},
                adapters))

        def gen(mid, timeout=120):
            return ray_tpu.get(handle.generate.remote(
                {"ids": [1, 2, 3, 4, 5, 6, 7, 8, 9], "max_new_tokens": 6,
                 "model_id": mid}), timeout=timeout)

        baseline = {mid: gen(mid) for mid in adapters}
        pre = ray_tpu.get(handle.metrics.remote(None), timeout=30)
        assert sorted(pre["adapters"]["resident"]) == sorted(adapters)
        # Cross-adapter prefix sharing: the 2nd and 3rd adapters' shared
        # prompt hit the 1st's cached blocks.
        assert pre["prefix_cache"]["hits"] >= 2, pre["prefix_cache"]
        # Drained: the only arena references are the cache's donations.
        assert (pre["kv"]["blocks_in_use"]
                == pre["prefix_cache"]["cached_blocks"] > 0), pre

        # SIGKILL-equivalent: the replica actor dies with 3 resident
        # adapters; the controller's health check replaces it.
        victim = ray_tpu.get_actor("SERVE_REPLICA::mux#0",
                                   namespace="serve")
        ray_tpu.kill(victim)
        t0 = time.perf_counter()
        recovered = None
        with HangWatchdog(limit_s=90) as wd:
            deadline = time.time() + 60
            while time.time() < deadline:
                try:
                    recovered = gen("m-b", timeout=10)
                    break
                except Exception:  # noqa: BLE001 — replica mid-respawn
                    time.sleep(0.25)
        mttr_s = time.perf_counter() - t0
        assert recovered is not None, "replica never served again"
        assert mttr_s < 60.0, f"MTTR {mttr_s:.1f}s exceeds the deadline"
        wd.assert_no_hangs()

        # On-demand reload, token-identical to the pre-crash replica —
        # the first post-crash gen ran against a COLD tree, proving
        # cached and uncached paths emit the same bytes.
        assert recovered == baseline["m-b"]
        for mid in ("m-a", "m-c"):
            assert gen(mid) == baseline[mid], mid
        # Second pass: now the rebuilt tree is warm again — every
        # adapter's generation must hit it and stay bit-identical.
        for mid in adapters:
            assert gen(mid) == baseline[mid], mid
        post = ray_tpu.get(handle.metrics.remote(None), timeout=30)
        # The fresh replica loaded exactly the adapters requested since
        # the crash (on demand — not a bulk restore at spawn).
        assert sorted(post["adapters"]["resident"]) == sorted(adapters)
        assert post["adapters"]["loads"] == 3
        assert post["prefix_cache"]["hits"] >= 3, post["prefix_cache"]
        # Zero leaked arena blocks across the kill/respawn/reload/rewarm
        # cycle: in-use equals exactly the warm tree's donations.
        assert (post["kv"]["blocks_in_use"]
                == post["prefix_cache"]["cached_blocks"] > 0), post["kv"]
        assert post["prefill_compiles"] == 1 and \
            post["decode_compiles"] == 1, post
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001
            pass
        ray_tpu.shutdown()


# ---------------------------------------- task fast path in the victim set


def _assert_marked_dups_are_retries(mark_file, n_tasks):
    """Every one of the `n_tasks` tasks named `marked` left its
    side-channel execution mark, and duplicate executions are
    owner-accounted retries, never a stale-lease double push."""
    counts: dict = {}
    with open(mark_file) as f:
        for line in f:
            if line.strip():
                counts[int(line)] = counts.get(int(line), 0) + 1
    assert all(i in counts for i in range(n_tasks)), "a task never executed"
    rt = ray_tpu._require_runtime()
    retries = sum(rec.attempts for rec in rt._tasks.values()
                  if rec.spec is not None
                  and rec.spec.name.endswith("marked"))
    dup = sum(c - 1 for c in counts.values() if c > 1)
    assert dup <= retries, (
        f"{dup} duplicate executions but only {retries} owner "
        "retries: a stale lease double-pushed")


@pytest.mark.slow
def test_node_kill_invalidates_lease_cache():
    """Node death mid-push: every lease cached against the dead node's
    workers is invalidated (the RL012 death hook), in-flight tasks
    re-route to fresh leases within their retry budget, and the
    side-channel execution marks prove no task was lost and no stale
    lease double-pushed one (dup executions <= owner-recorded retries)."""
    import os
    import tempfile

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    mark_file = os.path.join(tempfile.mkdtemp(), "lease_marks")
    try:
        for _ in range(2):
            cluster.add_node(num_cpus=2, resources={"churn": 2})
        cluster.wait_for_nodes()
        cluster.connect()

        @ray_tpu.remote
        def marked(path, idx):
            time.sleep(0.05)
            with open(path, "a") as f:
                f.write(f"{idx}\n")
            return idx

        opts = {"resources": {"churn": 1}, "max_retries": 8}
        # Warm leases on the churn nodes, then keep the pipeline deep so
        # the kill lands while pushes are in flight.
        ray_tpu.get([marked.options(**opts).remote(mark_file, -1 - i)
                     for i in range(4)], timeout=60)
        d = ray_tpu._require_runtime()._direct
        lost_before = d.stats["leases_lost"] + d.stats["leases_swept"]

        refs = [marked.options(**opts).remote(mark_file, i)
                for i in range(60)]
        time.sleep(0.4)  # mid-stream...
        victim = next(r for r in cluster.raylets if not r.is_head)
        cluster.crash_node(victim)
        cluster.add_node(num_cpus=2, resources={"churn": 2})

        with HangWatchdog(limit_s=120) as wd:
            results = ray_tpu.get(refs, timeout=120)
        wd.assert_no_hangs()
        assert results == list(range(60)), "task lost under node kill"
        # The death hook fired for the victim's leases.
        assert d.stats["leases_lost"] + d.stats["leases_swept"] \
            > lost_before, "no cached lease was invalidated by the kill"
        with d._lock:
            for leases in d._leases.values():
                for lease in leases:
                    assert not lease.closed
        _assert_marked_dups_are_retries(mark_file, 60)
    finally:
        cluster.shutdown()


@pytest.mark.slow  # multi-node cluster + autoscaler relaunch: >10s under load
def test_node_kill_replaced_by_autoscaler_floor():
    """A node of the autoscaler's managed fleet is crashed under task
    load and NOTHING in the test adds a node back: the fault counts as
    recovered only once the autoscaler's `min_workers` floor has
    relaunched one. Every task resolves to its own result, and a task
    that ran twice is covered by an owner-side retry."""
    import os
    import tempfile

    from ray_tpu.autoscaler import (
        AutoscalerConfig,
        LocalNodeProvider,
        StandardAutoscaler,
    )

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    mark_file = os.path.join(tempfile.mkdtemp(), "floor_marks")
    provider = LocalNodeProvider(cluster)
    autoscaler = StandardAutoscaler(
        cluster.gcs_address, provider,
        AutoscalerConfig(min_workers=2, max_workers=2,
                         node_resources={"CPU": 2},
                         idle_timeout_s=3600.0, update_period_s=0.5))
    try:
        autoscaler.update()    # fill the floor now; the loop keeps it
        autoscaler.start()
        cluster.wait_for_nodes(timeout=60)
        cluster.connect()
        launches = autoscaler.num_launches
        assert launches == 2

        @ray_tpu.remote
        def marked(path, idx):
            time.sleep(0.05)
            with open(path, "a") as f:
                f.write(f"{idx}\n")
            return idx

        # Plain CPU tasks: the head and the surviving node can take what
        # the victim drops while the floor is being refilled (a task that
        # only the managed fleet could run has nowhere to go until then).
        opts = {"max_retries": 8}
        ray_tpu.get([marked.options(**opts).remote(mark_file, -1 - i)
                     for i in range(8)], timeout=60)    # warm leases
        sched = ChaosSchedule(seed=12, kinds=("node_kill",), period_s=1.0,
                              count=1, jitter=0.2)
        runner = ChaosRunner(
            cluster, sched,
            {"node_kill": NodeKillInjector(cluster, provider=provider)},
            recovery_deadline_s=45)
        with HangWatchdog(limit_s=120) as wd:
            with runner:
                refs = [marked.options(**opts).remote(mark_file, i)
                        for i in range(80)]
                results = ray_tpu.get(refs, timeout=120)
                assert runner.wait(timeout=90)
        runner.assert_recovered()
        wd.assert_no_hangs()
        assert results == list(range(80)), "task lost under node kill"
        assert runner.faults_injected == 1
        assert autoscaler.num_launches == launches + 1
        assert len(provider.non_terminated_nodes()) == 2
        _assert_marked_dups_are_retries(mark_file, 80)
    finally:
        autoscaler.stop()
        cluster.shutdown()


def test_pubsub_delta_batch_monotonic_across_gcs_failover():
    """Delta-batched pubsub frames carry a strictly-increasing seq per
    connection; resource churn before, during, and after a GCS failover
    never reorders or replays a batch, and the subscriber's merged view
    converges to the restarted GCS's live resource view."""
    import os
    import tempfile

    ray_tpu.shutdown()
    path = os.path.join(tempfile.mkdtemp(), "gcs_tables.bin")
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 1},
                      gcs_storage_path=path)
    subs = []
    try:
        cluster.wait_for_nodes()

        frames: list = []   # (client_epoch, seq, events)

        def make_subscriber(epoch):
            def on_push(method, data):
                if method == "pubsub_batch":
                    frames.append((epoch, data["seq"], data["events"]))
                elif method == "pubsub":
                    frames.append((epoch, None, [data]))
            cli = RpcClient(cluster.gcs.address,
                            name=f"delta-sub-{epoch}",
                            push_handler=on_push)
            cli.call("subscribe", {"channel": "RESOURCES", "key": b"*"},
                     timeout=10)
            subs.append(cli)
            return cli

        make_subscriber(0)
        # Resource churn: node joins force full-view broadcasts; task
        # load drives per-node deltas.
        added = [cluster.add_node(num_cpus=1, resources={"c": 1})
                 for _ in range(3)]
        cluster.wait_for_nodes()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not any(
                e for _, s, e in frames if s is not None):
            time.sleep(0.1)

        cluster.kill_gcs()
        cluster.restart_gcs()
        # The old connection died with the GCS; a reconnected subscriber
        # is a NEW connection epoch with its own seq stream.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                make_subscriber(1)
                break
            except Exception:  # noqa: BLE001 — GCS still restarting
                time.sleep(0.2)
        cluster.add_node(num_cpus=1, resources={"c": 1})
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not any(
                ep == 1 and s is not None for ep, s, _ in frames):
            time.sleep(0.1)

        # Monotonicity: per (epoch), batch seqs strictly increase —
        # never reordered, never replayed, across the failover.
        by_epoch: dict = {}
        for ep, seq, _events in frames:
            if seq is None:
                continue
            assert seq > by_epoch.get(ep, 0), (
                f"batch seq regressed in epoch {ep}: {seq} after "
                f"{by_epoch.get(ep)}")
            by_epoch[ep] = seq
        assert by_epoch.get(1), "no delta batch arrived after failover"

        # Convergence: fold every RESOURCES event in arrival order; the
        # merged view must match the restarted GCS's live view.
        view: dict = {}
        for _ep, _seq, events in frames:
            for ev in events:
                msg = ev["message"]
                if "delta" in msg:
                    view.update(msg["delta"])
                else:
                    view = dict(msg)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            live = cluster.gcs.handle_get_resource_view(None) \
                if hasattr(cluster.gcs, "handle_get_resource_view") \
                else cluster.gcs._resource_view()
            if set(view) >= {k for k, e in live.items() if e.get("alive")}:
                break
            time.sleep(0.2)
        alive = {k for k, e in live.items() if e.get("alive")}
        assert set(view) >= alive, (
            f"subscriber view missing alive nodes: {alive - set(view)}")
    finally:
        for cli in subs:
            try:
                cli.close()
            except Exception:  # noqa: BLE001
                pass
        cluster.shutdown()


# ------------------------------------------------------- job driver kill


def test_driver_kill_detached_survives_next_job_unaffected():
    """Driver-kill schedule for the job tier (docs/JOBS.md cleanup
    contract): SIGKILL a submitted job's driver mid-run; its detached
    actor survives with state, its non-detached actor is reclaimed, and
    a second job submitted DURING the first's cleanup runs its first
    task normally (cleanup never wedges dispatch)."""
    import os
    import signal
    import sys

    from ray_tpu.job_submission import JobStatus, JobSubmissionClient

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    client = JobSubmissionClient(ray_tpu._global_runtime.gcs.address)
    try:
        sid = client.submit_job(entrypoint=(
            f"{sys.executable} -c \""
            "import os, time, ray_tpu; ray_tpu.init()\n"
            "@ray_tpu.remote\n"
            "class Keeper:\n"
            "    def __init__(self):\n"
            "        self.n = 0\n"
            "    def bump(self):\n"
            "        self.n += 1\n"
            "        return self.n\n"
            "d = Keeper.options(name='chaos-keeper', "
            "lifetime='detached').remote()\n"
            "e = Keeper.options(name='chaos-eph').remote()\n"
            "ray_tpu.get([d.bump.remote(), e.bump.remote()])\n"
            "print('READY pid=%d' % os.getpid(), flush=True)\n"
            "time.sleep(120)\""))
        # Wait for the driver to report itself, then SIGKILL it — no
        # SIGTERM grace, no atexit: the hardest driver death.
        deadline = time.monotonic() + 60
        pid = None
        while time.monotonic() < deadline and pid is None:
            for line in client.get_job_logs(sid).splitlines():
                if line.startswith("READY pid="):
                    pid = int(line.split("=", 1)[1])
            time.sleep(0.2)
        assert pid is not None, client.get_job_logs(sid)[-500:]
        os.kill(pid, signal.SIGKILL)
        # Second job races the first's cleanup: submit-to-first-task must
        # complete normally while workers/actors of job 1 are torn down.
        sid2 = client.submit_job(entrypoint=(
            f"{sys.executable} -c \""
            "import ray_tpu; ray_tpu.init()\n"
            "@ray_tpu.remote\n"
            "def first():\n"
            "    return 'second-job-task-ran'\n"
            "print(ray_tpu.get(first.remote()))\n"
            "ray_tpu.shutdown()\""))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and \
                client.get_job_status(sid2) not in JobStatus.TERMINAL:
            time.sleep(0.25)
        assert client.get_job_status(sid2) == JobStatus.SUCCEEDED, \
            client.get_job_logs(sid2)[-500:]
        assert "second-job-task-ran" in client.get_job_logs(sid2)
        # Job 1 lands FAILED (killed, not stopped by the platform).
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and \
                client.get_job_status(sid) not in JobStatus.TERMINAL:
            time.sleep(0.25)
        assert client.get_job_status(sid) == JobStatus.FAILED
        # Detached actor survives the driver kill with its state...
        handle = ray_tpu.get_actor("chaos-keeper")
        assert ray_tpu.get(handle.bump.remote(), timeout=30) == 2
        # ...the non-detached one is reclaimed with the job.
        deadline = time.monotonic() + 30
        gone = False
        while time.monotonic() < deadline and not gone:
            try:
                ray_tpu.get_actor("chaos-eph")
                time.sleep(0.25)
            except ValueError:
                gone = True
        assert gone, "non-detached actor outlived its killed driver"
        ray_tpu.kill(handle)
    finally:
        client.close()
        ray_tpu.shutdown()
