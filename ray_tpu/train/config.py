"""Training configuration dataclasses.

Equivalent of the reference's AIR configs (`python/ray/air/config.py`:
RunConfig/ScalingConfig/FailureConfig/CheckpointConfig) with TPU-first
extensions: ScalingConfig speaks pod slices and mesh axes, not GPU counts.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ray_tpu.parallel.mesh import MeshSpec


@dataclass
class ScalingConfig:
    """How many workers, with what resources, over what mesh.

    - `num_workers`: training worker processes (one JAX process per TPU host).
    - `use_tpu` + `tpus_per_worker`: grants TPU chips. A worker is granted
      one chip (it sees exactly that chip, via `TPU_VISIBLE_CHIPS`) or its
      whole host; other partial sets are refused. The worker fails at
      start unless jax shows it exactly the granted chips.
    - `topology`: pod slice name ("v4-32", "v5e-16") — when set, overrides
      num_workers/tpus_per_worker from the slice's host layout.
    - `mesh`: logical mesh spec laid over all granted chips.
    """

    num_workers: int = 1
    use_tpu: bool = False
    tpus_per_worker: int = 0
    resources_per_worker: Optional[Dict[str, float]] = None
    cpus_per_worker: float = 1.0
    topology: Optional[str] = None
    mesh: Optional[MeshSpec] = None
    placement_strategy: str = "PACK"

    def __post_init__(self):
        if self.topology:
            from ray_tpu.util.accelerators import slice_host_count, slice_chip_count

            self.num_workers = slice_host_count(self.topology)
            self.tpus_per_worker = slice_chip_count(self.topology) // self.num_workers
            self.use_tpu = True
            self.placement_strategy = "STRICT_SPREAD"

    def worker_resources(self) -> Dict[str, float]:
        out = {"CPU": float(self.cpus_per_worker)}
        if self.use_tpu and self.tpus_per_worker:
            out["TPU"] = float(self.tpus_per_worker)
        if self.resources_per_worker:
            out.update(self.resources_per_worker)
        return out

    def as_placement_group_bundles(self) -> List[Dict[str, float]]:
        return [self.worker_resources() for _ in range(self.num_workers)]

    @property
    def total_workers(self) -> int:
        return self.num_workers


@dataclass
class FailureConfig:
    """Retries for the whole worker group (the reference's Train-era
    semantics: restart the group, not partial-elastic — SURVEY.md §5.3)."""

    max_failures: int = 0


@dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"
    checkpoint_frequency: int = 0


@dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(default_factory=CheckpointConfig)
    stop: Optional[Dict[str, Any]] = None
    verbose: int = 1
    callbacks: Optional[List[Any]] = None

    def resolved_storage_path(self) -> str:
        base = self.storage_path or os.path.join(
            os.path.expanduser("~"), "ray_tpu_results")
        return os.path.join(base, self.name) if self.name else base
