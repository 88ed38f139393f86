"""Learner / LearnerGroup: the gradient-update half of the new stack.

Equivalent of the reference's `Learner.{compute_loss,update}`
(`rllib/core/learner/learner.py:111,645,805`) and `LearnerGroup`
(`learner_group.py:61`) — TPU-first: the update is one jitted function
(loss + grad + optimizer apply fused by XLA onto the chip); a distributed
LearnerGroup shards the batch over a dp mesh axis inside jit instead of
DDP-allreducing torch gradients.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, List, Optional

import numpy as np

logger = logging.getLogger(__name__)


def host_local_numpy(arr) -> np.ndarray:
    """Materialize this process's rows of a (possibly multi-host sharded)
    jax array: np.asarray on a non-fully-addressable array raises, so
    concatenate the addressable shards in index order instead."""
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    shards = sorted(arr.addressable_shards,
                    key=lambda s: tuple(sl.start or 0 for sl in s.index))
    return np.concatenate([np.asarray(s.data) for s in shards])


class Learner:
    """Owns params + optimizer state; `update` is the jitted hot path.

    num_devices > 1 turns the learner into a data-parallel SPMD program:
    the update is jitted over a `Mesh` with a "dp" axis, the batch sharded
    along its leading axis and params/opt-state replicated — XLA's
    partitioner inserts the gradient all-reduce (psum over dp) that the
    reference obtains from torch DDP hooks
    (`rllib/core/learner/torch/torch_learner.py`). One jitted program, N
    chips, no per-gradient host traffic.
    """

    # Which batch axis data-parallelism shards: 0 for flat [B, ...]
    # batches (PPO/DQN); time-major learners ([T, n_envs, ...], IMPALA)
    # override to 1 so the V-trace time scan stays device-local.
    dp_axis: int = 0
    # Methods whose first argument is a batch to dp-split across learner
    # processes (subclasses with extra update entry points extend this —
    # DQN adds "update_dqn").
    batch_update_methods: tuple = ("update", "update_many")

    def __init__(self, module, config, seed: int = 0,
                 num_devices: int = 1, devices: Optional[List] = None):
        import jax
        import optax

        self.module = module
        self.config = config
        self.num_devices = max(1, int(num_devices))
        self.params = module.init_params(jax.random.PRNGKey(seed))
        lr = getattr(config, "lr", 3e-4)
        clip = getattr(config, "grad_clip", 0.5)
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(clip), optax.adam(lr))
        self.opt_state = self.optimizer.init(self.params)
        if self.num_devices > 1:
            self._init_sharded(devices)
        else:
            self._rep_sharding = None
            self._batch_sharding = None
            self._stacked_sharding = None
            self._update = jax.jit(self._update_impl)
            self._update_many = jax.jit(self._update_many_impl)

    def _init_sharded(self, devices: Optional[List] = None):
        import jax
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        devs = list(devices) if devices is not None else list(jax.devices())
        if len(devs) < self.num_devices:
            raise ValueError(
                f"num_learners={self.num_devices} but only {len(devs)} "
                f"devices visible ({jax.default_backend()})")
        self.mesh = Mesh(np.asarray(devs[: self.num_devices]), ("dp",))
        rep = NamedSharding(self.mesh, P())
        self._rep_sharding = rep
        self._batch_sharding = NamedSharding(
            self.mesh, P(*([None] * self.dp_axis), "dp"))
        self._stacked_sharding = NamedSharding(
            self.mesh, P(*([None] * (self.dp_axis + 1)), "dp"))
        self.params = jax.device_put(self.params, rep)
        self.opt_state = jax.device_put(self.opt_state, rep)
        self._update = jax.jit(
            self._update_impl,
            in_shardings=(rep, rep, self._batch_sharding),
            out_shardings=(rep, rep, rep))
        self._update_many = jax.jit(
            self._update_many_impl,
            in_shardings=(rep, rep, self._stacked_sharding),
            out_shardings=(rep, rep, rep))

    def _prepare_batch(self, batch: Dict[str, Any], axis: int
                       ) -> Optional[Dict[str, Any]]:
        """dp-shard a host batch: trim the batch axis to a multiple of dp
        (DDP drop-last semantics) and, under multi-host SPMD, assemble
        global arrays from this process's local rows. Returns None when
        trimming leaves nothing to train on."""
        if self.num_devices <= 1:
            return batch
        import jax

        world = jax.process_count()
        # Multi-host: this process holds 1/world of the global batch; its
        # rows need only cover the local device share of the dp axis.
        n = self.num_devices // world if world > 1 else self.num_devices
        n = max(1, n)

        def trim(x):
            x = np.asarray(x)
            keep = (x.shape[axis] // n) * n
            if keep == x.shape[axis]:
                return x
            sl = [slice(None)] * x.ndim
            sl[axis] = slice(0, keep)
            return x[tuple(sl)]

        out = {k: trim(v) for k, v in batch.items()}
        if any(v.shape[axis] == 0 for v in out.values()):
            return None
        if world > 1:
            sh = self._batch_sharding if axis == 0 else self._stacked_sharding
            out = {k: jax.make_array_from_process_local_data(sh, v)
                   for k, v in out.items()}
        return out

    # -- override point -------------------------------------------------------

    def compute_loss(self, params, batch: Dict[str, Any]):
        """Return (loss, metrics). Overridden per algorithm (PPO below)."""
        raise NotImplementedError

    # -- update ---------------------------------------------------------------

    def _update_impl(self, params, opt_state, batch):
        import jax
        import optax

        (loss, metrics), grads = jax.value_and_grad(
            self.compute_loss, has_aux=True)(params, batch)
        updates, opt_state = self.optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        metrics["total_loss"] = loss
        metrics["grad_norm"] = optax.global_norm(grads)
        return params, opt_state, metrics

    def _update_many_impl(self, params, opt_state, stacked):
        """One SGD epoch as a single XLA program: lax.scan over the
        leading minibatch axis. TPU-first — a per-minibatch Python loop
        pays one host->device dispatch per step; the scan pays one for
        the whole epoch."""
        import jax

        def step(carry, mb):
            p, o = carry
            p, o, metrics = self._update_impl(p, o, mb)
            return (p, o), metrics

        (params, opt_state), metrics = jax.lax.scan(
            step, (params, opt_state), stacked)
        # Epoch means for reporting — except KL, where the guard needs the
        # END-of-epoch divergence (the mean is diluted by the first
        # minibatch's near-zero KL and would fire the early stop too late).
        out = jax.tree_util.tree_map(lambda m: m.mean(), metrics)
        if "kl" in metrics:
            out["kl"] = metrics["kl"][-1]
        return params, opt_state, out

    def update(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        batch = self._prepare_batch(batch, axis=self.dp_axis)
        if batch is None:
            return {}
        self.params, self.opt_state, metrics = self._update(
            self.params, self.opt_state, batch)
        return {k: float(v) for k, v in metrics.items()}

    def update_many(self, stacked: Dict[str, np.ndarray]) -> Dict[str, float]:
        """Run one update per row of the leading minibatch axis."""
        stacked = self._prepare_batch(stacked, axis=self.dp_axis + 1)
        if stacked is None:
            return {}
        self.params, self.opt_state, metrics = self._update_many(
            self.params, self.opt_state, stacked)
        return {k: float(v) for k, v in metrics.items()}

    def get_weights(self) -> Any:
        import jax

        return jax.device_get(self.params)

    def set_weights(self, weights: Any):
        self.params = weights

    def get_state(self) -> Dict[str, Any]:
        import jax

        return {"params": jax.device_get(self.params),
                "opt_state": jax.device_get(self.opt_state)}

    def set_state(self, state: Dict[str, Any]):
        self.params = state["params"]
        self.opt_state = state["opt_state"]


class LearnerGroup:
    """Local or remote learner execution (reference `learner_group.py:61`).

    mode="local": the learner lives in the calling process (drives the
    local chip directly — the default for 1-host training).
    mode="remote": the learner runs in a dedicated actor (optionally with
    TPU resources) so rollout workers and the driver stay off the chip.

    num_learners > 1 scales the update the TPU way (reference
    `learner_group.py:114-126` scales via N DDP torch workers):
      * local — one SPMD program dp-sharded over num_learners local chips
        (the single-host multi-chip case; see `Learner._init_sharded`).
      * remote — num_learners actors form a `jax.distributed` process
        group (multi-host); every actor runs the same dp-sharded update
        over the global mesh on its local slice of the batch.
    """

    def __init__(self, learner_factory: Callable[..., Learner],
                 mode: str = "local",
                 resources: Optional[Dict[str, float]] = None,
                 num_learners: int = 1):
        self.mode = mode
        self.num_learners = max(1, int(num_learners))
        self._sharded_group = None
        if mode != "local" and self.num_learners > 1:
            self._learner = None
            self._actor = None
            self._sharded_group = _ShardedLearnerGroup(
                learner_factory, self.num_learners, resources)
        elif mode == "local":
            self._learner = (learner_factory(num_devices=self.num_learners)
                             if self.num_learners > 1 else learner_factory())
            self._actor = None
        else:
            import ray_tpu

            opts: Dict[str, Any] = {}
            if resources:
                res = dict(resources)
                if "CPU" in res:
                    opts["num_cpus"] = res.pop("CPU")
                if "TPU" in res:
                    opts["num_tpus"] = res.pop("TPU")
                if res:
                    opts["resources"] = res
            actor_cls = ray_tpu.remote(_LearnerActor)
            self._actor = (actor_cls.options(**opts) if opts else actor_cls
                           ).remote(learner_factory)
            self._learner = None
            ray_tpu.get(self._actor.ping.remote())

    def update(self, batch) -> Dict[str, float]:
        if self._learner is not None:
            return self._learner.update(batch)
        if self._sharded_group is not None:
            return self._sharded_group.update("update", batch)
        import ray_tpu

        return ray_tpu.get(self._actor.update.remote(batch))

    def update_many(self, stacked) -> Dict[str, float]:
        if self._learner is not None:
            return self._learner.update_many(stacked)
        if self._sharded_group is not None:
            return self._sharded_group.update("update_many", stacked)
        import ray_tpu

        return ray_tpu.get(self._actor.update_many.remote(stacked))

    def call(self, method: str, *args, **kwargs):
        """Dispatch an algorithm-specific learner method (DQN's update_dqn,
        sync_target, ...) through whichever mode this group runs in."""
        if self._learner is not None:
            return getattr(self._learner, method)(*args, **kwargs)
        if self._sharded_group is not None:
            if (method in self._sharded_group.batch_methods
                    and len(args) == 1 and not kwargs):
                # Batch-consuming updates split across the learner
                # processes like update()/update_many() — broadcasting
                # the full batch would duplicate work N times.
                return self._sharded_group.update(method, args[0])
            return self._sharded_group.call_all(method, *args, **kwargs)[0]
        import ray_tpu

        return ray_tpu.get(self._actor.call.remote(method, *args, **kwargs))

    def get_weights(self):
        if self._learner is not None:
            return self._learner.get_weights()
        if self._sharded_group is not None:
            return self._sharded_group.call_rank0("get_weights")
        import ray_tpu

        return ray_tpu.get(self._actor.get_weights.remote())

    def get_state(self):
        if self._learner is not None:
            return self._learner.get_state()
        if self._sharded_group is not None:
            return self._sharded_group.call_rank0("get_state")
        import ray_tpu

        return ray_tpu.get(self._actor.get_state.remote())

    def set_state(self, state):
        if self._learner is not None:
            self._learner.set_state(state)
        elif self._sharded_group is not None:
            self._sharded_group.call_all("set_state", state)
        else:
            import ray_tpu

            ray_tpu.get(self._actor.set_state.remote(state))

    def shutdown(self):
        if self._sharded_group is not None:
            self._sharded_group.shutdown()
        if self._actor is not None:
            import ray_tpu

            try:
                ray_tpu.kill(self._actor)
            except Exception:
                pass


class _ShardedLearnerGroup:
    """num_learners actors forming one SPMD update (multi-host path).

    Mirrors the reference LearnerGroup's N-worker scaling
    (`rllib/core/learner/learner_group.py:114-126`) with the TPU recipe:
    the actors form a `jax.distributed` process group, each builds the
    SAME dp-sharded jitted update over the global mesh, and every
    training round each actor receives only its slice of the batch —
    gradients meet in XLA's psum over ICI/DCN, never on the host.

    Requires a runtime whose process group yields a global device view
    (real multi-host TPU); raises a clear error otherwise — this jax
    build has no multi-process CPU collectives, so tests exercise the
    single-process sharded path and this class's slicing helpers.
    """

    def __init__(self, learner_factory, num_learners: int,
                 resources: Optional[Dict[str, float]] = None):
        import ray_tpu

        self.n = num_learners
        opts: Dict[str, Any] = {}
        if resources:
            res = dict(resources)
            if "CPU" in res:
                opts["num_cpus"] = res.pop("CPU")
            if "TPU" in res:
                opts["num_tpus"] = res.pop("TPU")
            if res:
                opts["resources"] = res
        actor_cls = ray_tpu.remote(_ShardedLearnerWorker)
        if opts:
            actor_cls = actor_cls.options(**opts)
        self.workers = [actor_cls.remote(learner_factory)
                        for _ in range(num_learners)]
        try:
            ray_tpu.get([w.ping.remote() for w in self.workers])
            host, port = ray_tpu.get(
                self.workers[0].get_free_address.remote())
            coordinator = f"{host}:{port}"
            logger.info("forming learner process group: %d procs via %s",
                        num_learners, coordinator)
            ray_tpu.get([w.setup_group.remote(coordinator, num_learners, rank)
                         for rank, w in enumerate(self.workers)])
            counts = ray_tpu.get([w.build.remote(num_learners)
                                  for w in self.workers])
            self.global_devices = counts[0]
            self.dp_axis, self.batch_methods = ray_tpu.get(
                self.workers[0].get_split_spec.remote())
        except Exception:
            # Formation failed (e.g. no global device view): don't leak
            # the spawned actors or their resource reservations.
            self.shutdown()
            raise

    @staticmethod
    def _split(batch: Dict[str, np.ndarray], n: int, axis: int
               ) -> List[Dict[str, np.ndarray]]:
        """Trim the batch axis to a multiple of n processes and cut it
        into n equal contiguous slices (one per learner process)."""
        out: List[Dict[str, np.ndarray]] = [dict() for _ in range(n)]
        for k, v in batch.items():
            v = np.asarray(v)
            per = v.shape[axis] // n
            for i in range(n):
                sl = [slice(None)] * v.ndim
                sl[axis] = slice(i * per, (i + 1) * per)
                out[i][k] = v[tuple(sl)]
        return out

    def update(self, method: str, batch) -> Dict[str, float]:
        import ray_tpu

        axis = self.dp_axis + (1 if method == "update_many" else 0)
        orig_rows = min(np.asarray(v).shape[axis] for v in batch.values())
        slices = self._split(batch, self.n, axis)
        if any(v.shape[axis] == 0 for v in slices[0].values()):
            if method in ("update", "update_many"):
                return {}  # clean no-op, like the local drop-last path
            # Methods returning (metrics, per-row aux) cannot no-op
            # without breaking their callers' unpacking — misconfig.
            raise ValueError(
                f"batch of {orig_rows} rows is too small to split across "
                f"{self.n} learners for {method}; raise train_batch_size "
                f"or lower num_learners")
        refs = [w.update_slice.remote(method, s)
                for w, s in zip(self.workers, slices)]
        results = ray_tpu.get(refs)
        if isinstance(results[0], tuple):
            # (metrics, per-row aux) shape — e.g. DQN's |TD| priorities:
            # metrics are replicated, the aux rows concatenate back in
            # rank order (slices were contiguous). Drop-last trimming may
            # have shed tail rows; re-pad so callers indexing with the
            # ORIGINAL batch's indices (replay priority updates) line up.
            metrics = results[0][0]
            aux = np.concatenate([np.asarray(r[1]) for r in results])
            if len(aux) < orig_rows:
                fill = float(aux.mean()) if len(aux) else 1.0
                aux = np.concatenate(
                    [aux, np.full(orig_rows - len(aux), fill, aux.dtype)])
            return metrics, aux
        return results[0]

    def call_all(self, name: str, *args, **kwargs) -> List[Any]:
        import ray_tpu

        return ray_tpu.get([w.call.remote(name, *args, **kwargs)
                            for w in self.workers])

    def call_rank0(self, name: str, *args, **kwargs):
        import ray_tpu

        return ray_tpu.get(self.workers[0].call.remote(name, *args, **kwargs))

    def shutdown(self):
        import ray_tpu

        try:
            ray_tpu.get([w.teardown.remote() for w in self.workers],
                        timeout=10)
        except Exception:
            pass
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass


class _ShardedLearnerWorker:
    """One process of a multi-host sharded learner (runs inside an actor)."""

    def __init__(self, learner_factory):
        self._factory = learner_factory
        self._learner: Optional[Learner] = None

    def ping(self):
        return True

    def get_free_address(self):
        from ray_tpu.parallel.distributed import get_address_and_port

        return get_address_and_port()

    def setup_group(self, coordinator: str, world: int, rank: int):
        from ray_tpu.parallel.distributed import initialize_distributed

        initialize_distributed(coordinator, world, rank)
        return True

    def build(self, num_learners: int) -> int:
        import jax

        n_global = jax.device_count()
        procs = {d.process_index for d in jax.devices()}
        if n_global < num_learners or len(procs) < num_learners:
            raise RuntimeError(
                f"sharded LearnerGroup needs a global device view spanning "
                f"its {num_learners} processes, but this process sees "
                f"{n_global} device(s) from {len(procs)} process(es) after "
                f"jax.distributed init — multi-process collectives are "
                f"unavailable on this platform; use mode='local' with "
                f"num_learners instead")
        self._learner = self._factory(num_devices=n_global)
        return n_global

    def get_split_spec(self):
        return self._learner.dp_axis, tuple(self._learner.batch_update_methods)

    def update_slice(self, method: str, local_batch):
        return getattr(self._learner, method)(local_batch)

    def call(self, name: str, *args, **kwargs):
        return getattr(self._learner, name)(*args, **kwargs)

    def teardown(self):
        from ray_tpu.parallel.distributed import shutdown_distributed

        shutdown_distributed()
        return True


class _LearnerActor:
    def __init__(self, learner_factory):
        self._learner = learner_factory()

    def ping(self):
        return True

    def call(self, method: str, *args, **kwargs):
        """Algorithm-specific learner methods (e.g. DQN's update_dqn /
        sync_target) without a dedicated RPC per method."""
        return getattr(self._learner, method)(*args, **kwargs)

    def update(self, batch):
        return self._learner.update(batch)

    def update_many(self, stacked):
        return self._learner.update_many(stacked)

    def get_weights(self):
        return self._learner.get_weights()

    def get_state(self):
        return self._learner.get_state()

    def set_state(self, state):
        self._learner.set_state(state)
