"""Worker-side shard context: coordinated mesh bring-up for one rank.

Every rank actor of a gang calls :func:`activate` BEFORE any user code
(the serve `Replica` does it ahead of the deployment ctor, exactly like
`train.JaxBackend.on_start` runs `initialize_distributed` before the
train loop — XLA backends freeze on first use, so distributed init must
win that race).  Protocol:

1. rank 0 picks a free port and publishes ``host:port`` under the
   group's GCS KV key (`shardgroup:<group>:coordinator:<epoch>`);
2. every rank polls that key, then — on backends that support
   multi-process XLA — joins `jax.distributed` via
   `parallel.distributed.initialize_distributed`;
3. every rank builds the SAME `jax.sharding.Mesh` with a single "tp"
   axis over the first `tp` global devices.

On the CPU test backend jax has no multi-process runtime
("Multiprocess computations aren't implemented on the CPU backend"), so
step 2 is skipped and each rank builds a local mesh over its own forced
host devices (`--xla_force_host_platform_device_count`) — rank 0 drives
the real SPMD math, the other ranks keep the gang-lifecycle contract.
The deployment reads its mesh through :func:`current_mesh`.
"""

from __future__ import annotations

import logging
import os
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional

logger = logging.getLogger(__name__)

_KV_PREFIX = "shardgroup:"


@dataclass(frozen=True)
class ShardContext:
    """One rank's view of its gang (delivered by the gang scheduler)."""

    group_id: str          # unique per gang INCARNATION (restart = new id)
    rank: int
    world_size: int
    tp: int
    spmd: bool             # cross-process XLA active (jax.distributed)
    pp: int = 1            # pipeline stages (ranks, not mesh columns)
    sp: int = 1            # sequence-parallel mesh axis width

    @property
    def is_coordinator(self) -> bool:
        return self.rank == 0

    @property
    def stage(self) -> int:
        """This rank's pipeline stage (ranks are laid out stage-major:
        rank // ranks_per_stage)."""
        per = max(1, self.world_size // max(1, self.pp))
        return self.rank // per

    def as_dict(self) -> Dict[str, Any]:
        return {"group_id": self.group_id, "rank": self.rank,
                "world_size": self.world_size, "tp": self.tp,
                "pp": self.pp, "sp": self.sp, "spmd": self.spmd}


_current: Optional[ShardContext] = None
_mesh = None


def _platform_is_cpu() -> bool:
    """Decide WITHOUT touching jax backends (probing them would
    initialize XLA before `jax.distributed` gets its chance). Unset env
    counts as NOT-cpu — on a TPU pod nothing pins the platform and the
    SPMD path must not silently degrade; a bare-CPU process with no env
    hits the initialize_distributed fallback below instead."""
    # The first platform listed is jax's default backend: a TPU-granted
    # worker runs with "tpu,cpu".
    plat = os.environ.get("JAX_PLATFORMS") or ""
    return plat.split(",")[0].strip().lower() == "cpu"


def _kv():
    import ray_tpu

    return ray_tpu._require_runtime().gcs


def _coord_key(group_id: str) -> bytes:
    return (_KV_PREFIX + group_id + ":coordinator").encode()


def publish_coordinator(group_id: str, address: str) -> None:
    _kv().call("kv_put", {"key": _coord_key(group_id),
                          "value": address.encode()})


def wait_coordinator(group_id: str, timeout_s: float = 30.0) -> str:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        value = _kv().call("kv_get", {"key": _coord_key(group_id)})["value"]
        if value:
            return bytes(value).decode()
        time.sleep(0.02)
    raise TimeoutError(
        f"shard group {group_id}: coordinator address not published "
        f"within {timeout_s}s (rank 0 never came up?)")


def clear_rendezvous(group_id: str) -> None:
    """Drop the group's KV keys (gang teardown); a restarted gang has a
    fresh group_id, so this is hygiene, not correctness."""
    try:
        _kv().call("kv_del", {"key": _coord_key(group_id)})
    except Exception:  # noqa: BLE001 — best effort, GCS may be going down
        pass


def activate(ctx: Any, rendezvous_timeout_s: float = 30.0) -> ShardContext:
    """Join the gang: rendezvous, (maybe) jax.distributed, build the tp
    mesh. Idempotent for the same group_id; a different one raises —
    one process hosts one rank of one gang, ever (XLA state is global).
    """
    global _current, _mesh
    if isinstance(ctx, dict):
        ctx = ShardContext(**ctx)
    if _current is not None:
        if _current.group_id == ctx.group_id and _current.rank == ctx.rank:
            return _current
        raise RuntimeError(
            f"shard context already active for group {_current.group_id} "
            f"rank {_current.rank}; cannot re-activate as "
            f"{ctx.group_id} rank {ctx.rank}")

    spmd = bool(ctx.spmd) and ctx.world_size > 1 and not _platform_is_cpu()
    if ctx.world_size > 1 and spmd:
        from ray_tpu.parallel import distributed

        if ctx.is_coordinator:
            host, port = distributed.get_address_and_port()
            address = f"{host}:{port}"
            publish_coordinator(ctx.group_id, address)
        else:
            address = wait_coordinator(ctx.group_id, rendezvous_timeout_s)
        try:
            distributed.initialize_distributed(
                coordinator_address=address,
                num_processes=ctx.world_size,
                process_id=ctx.rank)
        except RuntimeError as e:
            # Backends without multi-process XLA (CPU with no platform
            # env pinned) degrade to per-process meshes rather than
            # killing the rank — the gang lifecycle still holds, rank 0
            # still drives the real math.
            logger.warning(
                "shardgroup %s rank %d: jax.distributed unavailable "
                "(%s) — degrading to per-process mesh", ctx.group_id,
                ctx.rank, e)
            spmd = False
    elif ctx.world_size > 1 and ctx.is_coordinator:
        # CPU degraded mode: still publish so laggard ranks (and tests)
        # can observe that rank 0 reached bring-up.
        publish_coordinator(ctx.group_id, "local")

    ctx = ShardContext(group_id=ctx.group_id, rank=ctx.rank,
                       world_size=ctx.world_size, tp=ctx.tp, spmd=spmd,
                       pp=ctx.pp, sp=ctx.sp)
    _mesh = _build_stage_mesh(ctx)
    _current = ctx
    logger.info("shardgroup: rank %d/%d of %s active (tp=%d, pp=%d, "
                "sp=%d, spmd=%s)", ctx.rank, ctx.world_size, ctx.group_id,
                ctx.tp, ctx.pp, ctx.sp, spmd)
    return ctx


def _build_stage_mesh(ctx: ShardContext):
    """The gang's per-stage device mesh: ("sp", "tp") axes over the
    first `sp*tp` (global) devices — "pp" is realized as stage PROCESSES
    exchanging activations over the collective plane, never as an
    in-program mesh axis. Every rank of an SPMD gang computes the
    identical mesh — `jax.devices()` is globally ordered after
    `jax.distributed` init. Size-1 axes are dropped (a tp-only gang gets
    the same single-axis mesh as before)."""
    axes = {name: size for name, size in (("sp", ctx.sp), ("tp", ctx.tp))
            if size > 1}
    if not axes:
        return None
    import jax

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    devices = jax.devices()
    need = 1
    for size in axes.values():
        need *= size
    if not (ctx.spmd or ctx.world_size == 1) and len(devices) < need:
        # CPU degraded mode: shrink the tp axis to what this process can
        # see (sp must fit — ring attention cannot run on a partial ring).
        tp_fit = max(1, len(devices) // max(1, ctx.sp))
        axes = {name: (min(size, tp_fit) if name == "tp" else size)
                for name, size in axes.items()}
        axes = {name: size for name, size in axes.items() if size > 1}
        if not axes:
            return None
        need = 1
        for size in axes.values():
            need *= size
    if len(devices) < need:
        raise RuntimeError(
            f"shard group {ctx.group_id}: mesh axes {axes} need {need} "
            f"devices, only {len(devices)} visible (set "
            "--xla_force_host_platform_device_count on CPU)")
    return build_mesh(MeshSpec(axes), devices=devices[:need])


def current() -> Optional[ShardContext]:
    return _current


def current_mesh():
    """The active gang's tp mesh (None outside a gang or at tp=1) —
    deployments/engines read this to decide the sharded path."""
    return _mesh


def deactivate() -> None:
    """Test hook: forget the context (does NOT undo jax.distributed)."""
    global _current, _mesh
    _current = None
    _mesh = None
