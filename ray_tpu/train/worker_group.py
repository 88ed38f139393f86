"""WorkerGroup: N training-worker actors, placement-grouped.

Equivalent of the reference's `python/ray/train/_internal/worker_group.py:100`.
Workers are generic function-executor actors; the JaxBackend and the training
loop both run through `execute*`. TPU workers are placed one per host via a
STRICT_SPREAD placement group (ScalingConfig.topology).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train.session import (
    TrainContext,
    _TrainSession,
    init_session,
    shutdown_session,
)

logger = logging.getLogger(__name__)


class TrainWorker:
    """Actor hosting one training process (one JAX process per TPU host)."""

    def __init__(self, rank: int, world_size: int, env: Optional[Dict[str, str]] = None):
        self.rank = rank
        self.world_size = world_size
        if env:
            os.environ.update(env)
        self._session: Optional[_TrainSession] = None
        self._thread: Optional[threading.Thread] = None

    def execute(self, fn: Callable, *args, **kwargs):
        return fn(*args, **kwargs)

    def ping(self) -> Dict[str, Any]:
        """Liveness probe for the group's death monitor."""
        return {"ok": True, "rank": self.rank}

    def node_info(self):
        import socket

        return {"hostname": socket.gethostname(), "pid": os.getpid(),
                "rank": self.rank}

    # -- training lifecycle --------------------------------------------------

    def start_training(self, train_fn: Callable, config: Dict[str, Any],
                       checkpoint=None, mesh_builder: Optional[Callable] = None,
                       datasets: Optional[Dict[str, Any]] = None,
                       experiment_name: str = "", run_nonce: str = ""):
        assert self._thread is None or not self._thread.is_alive(), \
            "training already running"
        mesh = mesh_builder() if mesh_builder is not None else None
        context = TrainContext(world_rank=self.rank, world_size=self.world_size,
                               experiment_name=experiment_name)
        collective_factory = None
        if self.world_size > 1:
            rank, world = self.rank, self.world_size
            # Run-unique name (nonce from the executor): concurrent runs
            # with the same experiment name can never share a group.
            group_name = (f"train:{experiment_name or 'run'}"
                          f":{run_nonce or 'default'}")

            def collective_factory():
                import ray_tpu
                from ray_tpu import collective as _collective
                from ray_tpu.exceptions import CollectiveError

                try:
                    return _collective.init_collective_group(
                        world, rank, group_name=group_name)
                except CollectiveError:
                    # A crashed previous run left the name broken (its
                    # members died, the record stayed). Clear it — only
                    # if still broken, so a peer's fresh incarnation
                    # survives the race — and join the new epoch.
                    ray_tpu._require_runtime().gcs.call(
                        "collective_destroy",
                        {"name": group_name, "if_broken": True}, timeout=10)
                    return _collective.init_collective_group(
                        world, rank, group_name=group_name)

        session = _TrainSession(context, datasets=datasets, checkpoint=checkpoint,
                                mesh=mesh, collective_factory=collective_factory)
        self._session = session
        init_session(session)

        def run():
            try:
                import inspect

                if config and len(inspect.signature(train_fn).parameters) > 0:
                    session.final_return = train_fn(config)
                elif len(inspect.signature(train_fn).parameters) > 0:
                    session.final_return = train_fn({})
                else:
                    session.final_return = train_fn()
            except BaseException as e:  # noqa: BLE001
                session.error = e
                logger.exception("train loop failed on rank %d", self.rank)
            finally:
                session.finished.set()

        self._thread = threading.Thread(target=run, name="train-loop", daemon=True)
        # This rank's end of the start-up (`train.startup` ends at the
        # latest rank's mark), and the write-out of this process's
        # lifecycle file. Down here, and imported here, so that no line
        # of run() above moves: its frames are on the call stack the
        # train step is traced under, and a Pallas program's cache key
        # holds them (PERF.md section 6, PR 23).
        from ray_tpu.observability import tracing as _tracing

        _tracing.get_tracer().lifecycle_mark(
            "train.loop.enter", attrs={"rank": self.rank})
        self._thread.start()
        return True

    def next_result(self, timeout: float = 3600.0):
        """Block until the next session.report() or loop completion."""
        import queue as _q

        session = self._session
        assert session is not None, "training not started"
        while True:
            try:
                item = session.result_queue.get(timeout=0.1)
                return {"done": False, **item}
            except _q.Empty:
                if session.finished.is_set() and session.result_queue.empty():
                    if session.error is not None:
                        from ray_tpu.core import serialization

                        return {"done": True,
                                "error": serialization.serialize_exception(
                                    session.error, "train_loop_per_worker")}
                    return {"done": True, "final": session.final_return}
                timeout -= 0.1
                if timeout <= 0:
                    return {"done": False, "timeout": True}

    def finish(self):
        if self._session is not None:
            self._session.teardown_collective()
        shutdown_session()
        self._session = None
        return True


class WorkerGroup:
    def __init__(self, num_workers: int,
                 resources_per_worker: Optional[Dict[str, float]] = None,
                 placement_strategy: str = "PACK",
                 use_placement_group: bool = True):
        self.num_workers = num_workers
        self._resources_per_worker = dict(
            resources_per_worker or {"CPU": 1.0})
        self._placement_strategy = placement_strategy
        self._use_placement_group = use_placement_group
        # Bumped on every successful (re)creation: consumers key run-scoped
        # names (collective groups) off it so a restarted gang can never
        # collide with its previous incarnation.
        self.incarnation = 0
        self.workers: List[Any] = []
        self._pg = None
        self._dead_rank: Optional[int] = None
        self._monitor = None
        self._create(num_workers)

    @property
    def dead_rank(self) -> Optional[int]:
        return self._dead_rank

    def _create(self, num_workers: int, pg_timeout_s: float = 120.0):
        resources = dict(self._resources_per_worker)
        self.num_workers = num_workers
        self._pg = None
        actor_cls = ray_tpu.remote(TrainWorker)
        options: Dict[str, Any] = {}
        placement_strategy = self._placement_strategy
        use_placement_group = self._use_placement_group
        num_cpus = resources.pop("CPU", 1.0)
        num_tpus = resources.pop("TPU", 0)
        # CPU is a *logical* resource: scale the per-worker request down so
        # the group always fits the cluster (a 2-worker default must work on
        # a 1-CPU bench host). TPU chips are physical and never scaled.
        try:
            total_cpu = ray_tpu.cluster_resources().get("CPU", 0.0)
        except Exception:
            total_cpu = 0.0
        if total_cpu and num_cpus * num_workers > total_cpu:
            fitted = max(0.01, int(total_cpu * 100 / num_workers) / 100)
            logger.warning(
                "ScalingConfig requests %s CPUs x %d workers but the cluster "
                "has %s; scaling the per-worker CPU request to %s.",
                num_cpus, num_workers, total_cpu, fitted)
            num_cpus = fitted
        if use_placement_group and num_workers > 1:
            from ray_tpu.util.placement_group import placement_group
            from ray_tpu.util.scheduling_strategies import (
                PlacementGroupSchedulingStrategy,
            )

            bundle = {"CPU": num_cpus}
            if num_tpus:
                bundle["TPU"] = num_tpus
            bundle.update(resources)
            self._pg = placement_group([dict(bundle)] * num_workers,
                                       strategy=placement_strategy)
            self._pg.ready(timeout=pg_timeout_s)
        self.workers = []
        self._dead_rank = None
        self._monitor = None
        try:
            for rank in range(num_workers):
                opts = dict(options)
                opts["num_cpus"] = num_cpus
                if num_tpus:
                    opts["num_tpus"] = num_tpus
                if resources:
                    opts["resources"] = dict(resources)
                if self._pg is not None:
                    from ray_tpu.util.scheduling_strategies import (
                        PlacementGroupSchedulingStrategy,
                    )

                    opts["scheduling_strategy"] = \
                        PlacementGroupSchedulingStrategy(
                            self._pg, placement_group_bundle_index=rank)
                try:
                    handle = actor_cls.options(**opts).remote(
                        rank, num_workers)
                except Exception as e:
                    raise RuntimeError(
                        f"creating train worker rank {rank}/{num_workers} "
                        f"failed: {type(e).__name__}: {e}") from e
                self.workers.append(handle)
        except Exception:
            # All-or-nothing (raylint RL009): a mid-gang failure releases
            # every already-created worker AND the placement group's
            # bundles — no leaked reservations, no half-alive gangs.
            self._abort_gang()
            raise
        if num_workers > 1:
            # Group death hook: a dead worker fails the next execute()
            # fast with a rank-attributed error instead of a generic
            # actor error minutes later (gradient sync would otherwise
            # discover it at the collective timeout).
            from ray_tpu.shardgroup import GangMonitor, ReplicaGroup, ShardSpec

            grp = ReplicaGroup(
                f"train-wg-{id(self):x}", ShardSpec(world_size=num_workers),
                None, self.workers,
                [f"rank{r}" for r in range(num_workers)])
            self._monitor = GangMonitor(grp, self._on_worker_death)
        self.incarnation += 1

    def restart(self, num_workers: Optional[int] = None,
                deadline_s: Optional[float] = None) -> int:
        """Gang-native elastic restart: abort the whole gang (every rank
        AND the placement group), then re-create it on a FRESH placement
        group under the recovery deadline — shrinking the world when the
        surviving topology cannot place the full gang (a killed node
        whose replacement never came). Returns the new world size; raises
        a loudly-attributed RuntimeError when no gang of ANY size could
        be formed within the deadline (never hangs in pg.ready)."""
        import time as _time

        from ray_tpu.core.config import GLOBAL_CONFIG

        if deadline_s is None:
            deadline_s = GLOBAL_CONFIG.chaos_recovery_deadline_s or 300.0
        deadline = _time.monotonic() + deadline_s
        self.shutdown()
        n = num_workers if num_workers is not None else self.num_workers
        last_err: Optional[BaseException] = None
        while True:
            remaining = deadline - _time.monotonic()
            if remaining <= 0:
                raise RuntimeError(
                    f"train gang restart stuck: no {n}-worker gang could "
                    f"be formed within the {deadline_s:.0f}s recovery "
                    f"deadline (last error: {last_err})") from last_err
            try:
                self._create(n, pg_timeout_s=min(30.0, remaining))
                logger.info("train gang restarted: world=%d incarnation=%d",
                            n, self.incarnation)
                return n
            except Exception as e:  # noqa: BLE001 — retried under deadline
                last_err = e
                self._abort_gang()
                if n > 1:
                    # Elastic shrink: the full gang no longer places —
                    # try a smaller world (checkpoint restore reshards).
                    logger.warning(
                        "train gang restart at world=%d failed (%s); "
                        "shrinking to %d", n, e, n - 1)
                    n -= 1
                _time.sleep(min(0.5, max(0.0,
                                         deadline - _time.monotonic())))

    def _abort_gang(self):
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:  # noqa: BLE001 — never created / dead
                pass
        self.workers = []
        if self._pg is not None:
            from ray_tpu.util.placement_group import remove_placement_group

            try:
                remove_placement_group(self._pg)
            except Exception:  # noqa: BLE001 — already removed
                pass
            self._pg = None

    def _on_worker_death(self, group, rank: int):
        self._dead_rank = rank

    def _check_group_alive(self):
        if self._dead_rank is not None:
            raise RuntimeError(
                f"train worker group lost rank {self._dead_rank}/"
                f"{self.num_workers} — the group must be shut down and "
                "recreated (workers restart as a unit)")

    def __len__(self):
        return self.num_workers

    def execute(self, fn: Callable, *args, **kwargs) -> List[Any]:
        self._check_group_alive()
        return ray_tpu.get([w.execute.remote(fn, *args, **kwargs)
                            for w in self.workers])

    def execute_async(self, fn: Callable, *args, **kwargs):
        self._check_group_alive()
        return [w.execute.remote(fn, *args, **kwargs) for w in self.workers]

    def execute_single(self, rank: int, fn: Callable, *args, **kwargs) -> Any:
        self._check_group_alive()
        return ray_tpu.get(self.workers[rank].execute.remote(fn, *args, **kwargs))

    def shutdown(self):
        if self._monitor is not None:
            self._monitor.stop()
            self._monitor.group._dead = True
            self._monitor = None
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        if self._pg is not None:
            from ray_tpu.util.placement_group import remove_placement_group

            try:
                remove_placement_group(self._pg)
            except Exception:
                pass
        self.workers = []
