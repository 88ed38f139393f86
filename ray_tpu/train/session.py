"""Per-worker training session: report(), ranks, dataset shards, the mesh.

Equivalent of the reference's `session.report`/`get_dataset_shard`
(`python/ray/air/session.py:43,359`) + `_TrainSession`
(`python/ray/train/_internal/session.py:63`). TPU addition: `get_mesh()`
hands the worker its slice-wide `jax.sharding.Mesh` built by the JaxBackend.
"""

from __future__ import annotations

import queue
import sys
import threading
from typing import Any, Dict, Optional

from ray_tpu.train.checkpoint import Checkpoint


class TrainContext:
    def __init__(self, world_rank: int, world_size: int, local_rank: int = 0,
                 local_world_size: int = 1, node_rank: int = 0,
                 trial_name: str = "", experiment_name: str = ""):
        self.world_rank = world_rank
        self.world_size = world_size
        self.local_rank = local_rank
        self.local_world_size = local_world_size
        self.node_rank = node_rank
        self.trial_name = trial_name
        self.experiment_name = experiment_name


class _TrainSession:
    """Lives inside each training worker while the user loop runs."""

    def __init__(self, context: TrainContext,
                 datasets: Optional[Dict[str, Any]] = None,
                 checkpoint: Optional[Checkpoint] = None,
                 mesh=None, collective_factory=None):
        self.context = context
        self.datasets = datasets or {}
        self.loaded_checkpoint = checkpoint
        self.mesh = mesh
        self.result_queue: "queue.Queue" = queue.Queue()
        self.finished = threading.Event()
        self.error: Optional[BaseException] = None
        self.final_return: Any = None
        # Host collective plane (cross-host DDP outside XLA): lazily
        # joined on first use so single-host loops never pay for it.
        self._collective_factory = collective_factory
        self._collective = None
        self._collective_lock = threading.Lock()
        self._reported = False

    def collective(self):
        """This worker's handle on the run-wide host collective group
        (ray_tpu.collective), joined on first use. None when the session
        runs outside a WorkerGroup (no factory)."""
        with self._collective_lock:
            if self._collective is None and self._collective_factory is not None:
                self._collective = self._collective_factory()
            return self._collective

    def teardown_collective(self):
        with self._collective_lock:
            group, self._collective = self._collective, None
            self._collective_factory = None  # no join can land after this
        if group is not None:
            try:
                group.leave()
            except Exception:  # noqa: BLE001 — teardown is best-effort
                pass

    def report(self, metrics: Dict[str, Any],
               checkpoint: Optional[Checkpoint] = None):
        metrics = dict(metrics)
        if not self._reported and "jax" in sys.modules:
            # The first report of a jax loop says which device produced
            # its numbers (platform, device_kind, n_devices); the loop's
            # own keys win.
            from ray_tpu._jax_env import device_info

            metrics = {**device_info(), **metrics}
        self._reported = True
        self.result_queue.put({"metrics": metrics, "checkpoint": checkpoint})

    def get_dataset_shard(self, name: str = "train"):
        ds = self.datasets.get(name)
        if ds is None:
            return None
        # ray_tpu.data shards are pre-split by the trainer (prefetching
        # ShardIterators); plain iterables pass through.
        return ds

    def ingest_stats(self) -> Dict[str, Any]:
        """Per-dataset step-stall accounting from every shard that keeps
        it (ShardIterator): did input ever stall the step?"""
        out: Dict[str, Any] = {}
        for name, ds in self.datasets.items():
            stats_fn = getattr(ds, "ingest_stats", None)
            if stats_fn is not None:
                out[name] = stats_fn()
        return out


_session: Optional[_TrainSession] = None
_session_lock = threading.Lock()


def init_session(session: _TrainSession):
    global _session
    with _session_lock:
        _session = session


def shutdown_session():
    global _session
    with _session_lock:
        _session = None


def get_session() -> _TrainSession:
    if _session is None:
        raise RuntimeError(
            "No training session active: session APIs are only usable inside "
            "a train_loop_per_worker launched by a Trainer.")
    return _session


# Public functional API ------------------------------------------------------


def report(metrics: Dict[str, Any], checkpoint: Optional[Checkpoint] = None):
    get_session().report(metrics, checkpoint)


def get_checkpoint() -> Optional[Checkpoint]:
    return get_session().loaded_checkpoint


def get_dataset_shard(name: str = "train"):
    return get_session().get_dataset_shard(name)


def get_ingest_stats() -> Dict[str, Any]:
    """Step-stall accounting of this worker's dataset shards (per
    dataset: steps, stall_ms_total, stall_frac — see
    ray_tpu/data/streaming/ingest.py). Empty when shards don't track
    ingest (plain iterables)."""
    return get_session().ingest_stats()


def get_context() -> TrainContext:
    return get_session().context


def get_world_rank() -> int:
    return get_session().context.world_rank


def get_world_size() -> int:
    return get_session().context.world_size


def get_local_rank() -> int:
    return get_session().context.local_rank


def get_mesh():
    """The slice-wide jax.sharding.Mesh assembled by the backend (None when
    the trainer was configured without one)."""
    return get_session().mesh


def get_collective():
    """The run-wide host collective group (`ray_tpu.collective`): ring
    allreduce / tree broadcast between the training workers, outside
    compiled programs. Raises when the session has no worker group."""
    group = get_session().collective()
    if group is None:
        raise RuntimeError(
            "No host collective available: this session is not running "
            "under a WorkerGroup (single-process loops have no peers).")
    return group


def sync_gradients(grads, op: str = "mean"):
    """Cross-host data-parallel gradient sync: allreduce a pytree of
    numpy/jax gradients across all training workers over the host
    collective plane (ring reduce-scatter + all-gather through the object
    transfer plane — see docs/COLLECTIVE.md). The DDP seam for loops whose
    collectives are NOT compiled into XLA (separate JAX processes without
    jax.distributed, torch-free CPU loops, DCN-spanning worker groups)."""
    session = get_session()
    if session.context.world_size <= 1:
        return grads
    return get_collective().allreduce(grads, op=op)


def broadcast_params(params, src_rank: int = 0):
    """Broadcast a pytree (initial weights, updated params) from one
    training worker to all others via the transfer plane's tree
    broadcast."""
    session = get_session()
    if session.context.world_size <= 1:
        return params
    return get_collective().broadcast(params, src_rank=src_rank)
