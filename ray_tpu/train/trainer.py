"""Trainers: BaseTrainer -> DataParallelTrainer -> JaxTrainer.

Equivalent of the reference's `BaseTrainer.fit` (`python/ray/train/
base_trainer.py:555`) and `DataParallelTrainer` (`data_parallel_trainer.py:56`),
with the Torch/NCCL path replaced by the JaxBackend (SPMD over a mesh). A
Trainer is convertible to a Tune trainable (`as_trainable`) so experiments run
through the Tuner exactly as in the reference.
"""

from __future__ import annotations

import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ray_tpu.observability import tracing as _tracing
from ray_tpu.train.backend import (Backend, BackendConfig, JaxConfig,
                                   TorchConfig)
from ray_tpu.train.backend_executor import BackendExecutor, TrainingFailedError
from ray_tpu.train.checkpoint import Checkpoint, CheckpointManager
from ray_tpu.train.config import RunConfig, ScalingConfig

logger = logging.getLogger(__name__)


@dataclass
class Result:
    metrics: Dict[str, Any] = field(default_factory=dict)
    checkpoint: Optional[Checkpoint] = None
    best_checkpoint: Optional[Checkpoint] = None
    error: Optional[BaseException] = None
    metrics_history: List[Dict[str, Any]] = field(default_factory=list)
    path: Optional[str] = None


class BaseTrainer:
    def __init__(self, *, scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None,
                 datasets: Optional[Dict[str, Any]] = None):
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.resume_from_checkpoint = resume_from_checkpoint
        self.datasets = datasets or {}

    def training_loop(self) -> Result:
        raise NotImplementedError

    def fit(self) -> Result:
        import ray_tpu

        if not ray_tpu.is_initialized():
            ray_tpu.init()
        return self.training_loop()

    def as_trainable(self):
        """Wrap as a Tune trainable function (reference: base_trainer.py
        constructs a Tuner internally; we expose the seam directly)."""
        trainer = self

        def trainable(config: Dict[str, Any]):
            merged = trainer._with_config_overrides(config)
            result = merged.training_loop()
            if result.error:
                raise result.error
            return result.metrics

        trainable.__name__ = type(self).__name__
        return trainable

    def _with_config_overrides(self, config: Dict[str, Any]) -> "BaseTrainer":
        if config and hasattr(self, "train_loop_config"):
            merged = dict(self.train_loop_config or {})
            merged.update(config)
            self.train_loop_config = merged
        return self


class DataParallelTrainer(BaseTrainer):
    """Runs `train_loop_per_worker` on N workers with a backend-made process
    group; results stream through session.report (reference
    data_parallel_trainer.py:385 training_loop)."""

    _default_backend_config: BackendConfig = BackendConfig()

    def __init__(self, train_loop_per_worker: Callable, *,
                 train_loop_config: Optional[Dict[str, Any]] = None,
                 backend_config: Optional[BackendConfig] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        super().__init__(scaling_config=scaling_config, run_config=run_config,
                         resume_from_checkpoint=resume_from_checkpoint,
                         datasets=datasets)
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config or {}
        self.backend_config = backend_config or self._default_backend_config

    def _split_datasets(self) -> Optional[List[Dict[str, Any]]]:
        """Per-worker dataset shards: ray_tpu.data Datasets are
        streaming_split and wrapped in prefetching ShardIterators (the
        worker's prefetch thread double-buffers blocks onto its host over
        the transfer plane, with step-stall accounting — see
        ray_tpu/data/streaming/ingest.py); plain lists are round-robin
        sharded; other values are passed through whole."""
        if not self.datasets:
            return None
        n = self.scaling_config.num_workers
        per_worker: List[Dict[str, Any]] = [dict() for _ in range(n)]
        for name, ds in self.datasets.items():
            splits = None
            if hasattr(ds, "streaming_split"):
                from ray_tpu.data.streaming.ingest import ShardIterator

                splits = [ShardIterator(s) for s in ds.streaming_split(n)]
            elif isinstance(ds, (list, tuple)):
                splits = [list(ds[i::n]) for i in range(n)]
            if splits is None:
                for i in range(n):
                    per_worker[i][name] = ds
            else:
                for i in range(n):
                    per_worker[i][name] = splits[i]
        return per_worker

    def training_loop(self) -> Result:
        # The start-up's root: from here until every rank's wrapper is
        # about to call the train function (the executor closes it; the
        # reader takes the latest rank's own `train.loop.enter` mark).
        sc = self.scaling_config
        startup = _tracing.get_tracer().lifecycle_span(
            "train.startup", root=True,
            attrs={"workers": sc.num_workers,
                   "chips": sc.num_workers * int(
                       sc.worker_resources().get("TPU", 0))})
        run_config = self.run_config
        storage = run_config.resolved_storage_path()
        name = run_config.name or f"{type(self).__name__}_{int(time.time())}"
        exp_dir = os.path.join(storage, name)
        ckpt_conf = run_config.checkpoint_config
        manager = CheckpointManager(
            os.path.join(exp_dir, "checkpoints"),
            num_to_keep=ckpt_conf.num_to_keep,
            score_attribute=ckpt_conf.checkpoint_score_attribute,
            score_order=ckpt_conf.checkpoint_score_order,
        )
        executor = BackendExecutor(
            self.backend_config, self.scaling_config,
            max_failures=run_config.failure_config.max_failures)
        executor.startup_span = startup
        try:
            executor.start()
        except BaseException as e:  # noqa: BLE001 — recorded, re-raised
            executor.startup_span.end(error=f"{type(e).__name__}: {e}")
            raise
        history: List[Dict[str, Any]] = []
        last_metrics: Dict[str, Any] = {}
        last_ckpt: Optional[Checkpoint] = None
        error: Optional[BaseException] = None
        try:
            for round_results in executor.run(
                    self.train_loop_per_worker, self.train_loop_config,
                    checkpoint=self.resume_from_checkpoint,
                    datasets_per_worker=self._split_datasets(),
                    experiment_name=name):
                rank0 = next((r for r in round_results if r["rank"] == 0),
                             round_results[0])
                last_metrics = rank0["metrics"]
                history.append(last_metrics)
                ckpt = rank0.get("checkpoint")
                if ckpt is not None:
                    path = manager.register(ckpt, last_metrics)
                    last_ckpt = Checkpoint.from_directory(path)
                for cb in run_config.callbacks or []:
                    try:
                        cb(last_metrics)
                    except Exception:
                        logger.exception("callback failed")
                if run_config.stop and all(
                        last_metrics.get(k, float("-inf")) >= v
                        for k, v in run_config.stop.items()):
                    logger.info("stop condition met: %s", run_config.stop)
                    break
        except (TrainingFailedError, Exception) as e:  # noqa: BLE001
            error = e
        finally:
            executor.startup_span.end(
                error=None if error is None
                else f"{type(error).__name__}: {error}")
            executor.shutdown()
        return Result(metrics=last_metrics, checkpoint=last_ckpt,
                      best_checkpoint=manager.best_checkpoint(),
                      error=error, metrics_history=history, path=exp_dir)

    @classmethod
    def restore(cls, path: str, train_loop_per_worker: Callable, **kwargs):
        """Resume from the latest checkpoint under an experiment dir."""
        ckpt_dir = os.path.join(path, "checkpoints")
        latest = None
        if os.path.isdir(ckpt_dir):
            entries = sorted(os.listdir(ckpt_dir))
            if entries:
                latest = Checkpoint.from_directory(
                    os.path.join(ckpt_dir, entries[-1]))
        return cls(train_loop_per_worker,
                   resume_from_checkpoint=latest, **kwargs)


class JaxTrainer(DataParallelTrainer):
    """The TPU-native TorchTrainer equivalent: one JAX process per host,
    collectives compiled by XLA over ICI (JaxBackend), mesh handed to the
    loop via `session.get_mesh()`. This is the north-star path
    (BASELINE.json: "JaxTrainer ... data-parallel allreduce")."""

    _default_backend_config = JaxConfig()

    def __init__(self, train_loop_per_worker: Callable, *,
                 jax_config: Optional[JaxConfig] = None, **kwargs):
        backend_config = kwargs.pop("backend_config", None) or jax_config \
            or JaxConfig(mesh=(kwargs.get("scaling_config") or ScalingConfig()).mesh)
        super().__init__(train_loop_per_worker,
                         backend_config=backend_config, **kwargs)


class TorchTrainer(DataParallelTrainer):
    """Data-parallel torch training (reference `TorchTrainer`,
    `torch/torch_trainer.py:15`): the worker group forms a
    torch.distributed process group (gloo on CPU hosts) and the user loop
    wraps its model in DistributedDataParallel. On this framework the
    TPU-native path is JaxTrainer; TorchTrainer exists for drop-in
    migration of torch training scripts."""

    _default_backend_config = TorchConfig()

    def __init__(self, train_loop_per_worker, *, train_loop_config=None,
                 torch_config: Optional[TorchConfig] = None,
                 scaling_config=None, run_config=None, datasets=None,
                 resume_from_checkpoint=None):
        super().__init__(
            train_loop_per_worker,
            train_loop_config=train_loop_config,
            backend_config=torch_config or TorchConfig(),
            scaling_config=scaling_config, run_config=run_config,
            datasets=datasets, resume_from_checkpoint=resume_from_checkpoint)
