"""Training backends: the multi-host process-group seam.

Equivalent of the reference's `Backend.on_start` (`python/ray/train/backend.py:53`)
whose Torch implementation runs `dist.init_process_group` over NCCL
(`torch/config.py:69-113`). The TPU-native JaxBackend instead does
coordinator election + `jax.distributed.initialize` + mesh construction —
after which collectives live inside XLA programs (SURVEY.md §3.4 step 3).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from ray_tpu._jax_env import claim_devices
from ray_tpu.parallel.mesh import MeshSpec

logger = logging.getLogger(__name__)


@dataclass
class BackendConfig:
    backend_name: str = "none"

    def backend_cls(self):
        return Backend


class Backend:
    """No-op backend: workers run independently (pure data-parallel via
    host-level collectives, or single-worker)."""

    def on_start(self, worker_group, backend_config: "BackendConfig"):
        pass

    def on_training_start(self, worker_group, backend_config: "BackendConfig"):
        pass

    def on_shutdown(self, worker_group, backend_config: "BackendConfig"):
        pass


@dataclass
class JaxConfig(BackendConfig):
    """Configuration for the JAX/TPU backend.

    mesh: logical mesh laid over the job's global device set.
    force_platform: override jax platform inside workers ("cpu" for tests).
    coordinator_port: fixed port for jax.distributed (0 = auto).
    """

    backend_name: str = "jax"
    mesh: Optional[MeshSpec] = None
    force_platform: Optional[str] = None
    coordinator_port: int = 0
    distributed: Optional[bool] = None  # None = auto (world_size > 1)

    def backend_cls(self):
        return JaxBackend


def _set_platform(platform: str):
    import jax

    jax.config.update("jax_platforms", platform)
    return True


def _init_jax_distributed(coordinator: str, world: int, rank: int):
    from ray_tpu.observability import tracing
    from ray_tpu.parallel.distributed import initialize_distributed

    with tracing.get_tracer().lifecycle_span(
            "jax.distributed", attrs={"rank": rank, "world": world}):
        initialize_distributed(coordinator, world, rank)
    return True


def _claim_devices_on(rank: int):
    """`claim_devices` on one rank, as that rank's part of the backend's
    start (the lifecycle span says which rank was the slow one)."""
    from ray_tpu.observability import tracing

    with tracing.get_tracer().lifecycle_span(
            "train.backend.on_start", attrs={"rank": rank}):
        return claim_devices()


def _mesh_builder_for(spec: Optional[MeshSpec]):
    if spec is None:
        return None

    def build():
        from ray_tpu.parallel.mesh import build_mesh

        return build_mesh(spec)

    return build


class JaxBackend(Backend):
    def on_start(self, worker_group, backend_config: JaxConfig):
        world = len(worker_group)
        if backend_config.force_platform:
            worker_group.execute(_set_platform, backend_config.force_platform)
        distributed = backend_config.distributed
        if distributed is None:
            distributed = world > 1
        if distributed and world > 1:
            from ray_tpu.parallel.distributed import get_address_and_port

            host, port = worker_group.execute_single(0, get_address_and_port)
            if backend_config.coordinator_port:
                port = backend_config.coordinator_port
            coordinator = f"{host}:{port}"
            logger.info("forming JAX process group: %d procs via %s",
                        world, coordinator)
            # All ranks must call initialize concurrently (rank 0 hosts the
            # coordination service).
            import ray_tpu

            refs = [w.execute.remote(_init_jax_distributed, coordinator, world, rank)
                    for rank, w in enumerate(worker_group.workers)]
            ray_tpu.get(refs)
        # Every train worker: persistent compile cache on (repeated fits,
        # tune trials and restarts skip cold compiles), and a worker that
        # holds a TPU grant fails here unless jax shows it exactly the
        # granted chips. After the process group forms: this starts the
        # backend, which jax.distributed must precede.
        import ray_tpu

        devices = ray_tpu.get([
            w.execute.remote(_claim_devices_on, rank)
            for rank, w in enumerate(worker_group.workers)])
        logger.info("train workers on %s", devices)

    def mesh_builder(self, backend_config: JaxConfig):
        return _mesh_builder_for(backend_config.mesh)

    def on_shutdown(self, worker_group, backend_config: JaxConfig):
        from ray_tpu.parallel.distributed import shutdown_distributed

        try:
            worker_group.execute(shutdown_distributed)
        except Exception:
            pass


# --------------------------------------------------------------------------- #
# Torch backend (reference `train/torch/config.py`)
# --------------------------------------------------------------------------- #


@dataclass
class TorchConfig(BackendConfig):
    """torch.distributed process group over the worker group.

    CPU hosts use gloo (this environment has no CUDA); the seam matches
    the reference's `_TorchBackend.on_start` -> `_setup_torch_process_group`
    (`python/ray/train/torch/config.py:69-113`).
    """

    backend_name: str = "torch"
    backend: str = "gloo"
    init_timeout_s: int = 120

    def backend_cls(self):
        return TorchBackend


def _setup_torch_process_group(backend: str, addr: str, port: int,
                               rank: int, world: int, timeout_s: int):
    import datetime
    import os

    import torch.distributed as dist

    os.environ["MASTER_ADDR"] = addr
    os.environ["MASTER_PORT"] = str(port)
    dist.init_process_group(
        backend, init_method="env://", rank=rank, world_size=world,
        timeout=datetime.timedelta(seconds=timeout_s))
    return True


def _teardown_torch_process_group():
    import torch.distributed as dist

    if dist.is_initialized():
        dist.destroy_process_group()
    return True


class TorchBackend(Backend):
    def on_start(self, worker_group, backend_config: "TorchConfig"):
        import ray_tpu

        world = len(worker_group)
        if world <= 1:
            return
        from ray_tpu.parallel.distributed import get_address_and_port

        host, port = worker_group.execute_single(0, get_address_and_port)
        logger.info("forming torch %s process group: %d procs via %s:%d",
                    backend_config.backend, world, host, port)
        refs = [w.execute.remote(_setup_torch_process_group,
                                 backend_config.backend, host, port,
                                 rank, world, backend_config.init_timeout_s)
                for rank, w in enumerate(worker_group.workers)]
        ray_tpu.get(refs)

    def on_shutdown(self, worker_group, backend_config: "TorchConfig"):
        try:
            worker_group.execute(_teardown_torch_process_group)
        except Exception:  # noqa: BLE001 — workers may already be gone
            pass
