"""RolloutWorker / WorkerSet: the sampling half of the algorithm.

Equivalent of the reference's `RolloutWorker.sample`
(`rllib/evaluation/rollout_worker.py:166,879`) + `WorkerSet`
(`worker_set.py:79`, `sync_weights` :384): each worker steps a vectorized
env with the exploration policy, records [T, n_envs] trajectories, computes
per-step next-state values for GAE bootstrapping, and returns a flat
SampleBatch. Workers run as actors; sampling fans out with one task each.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.env import make_env
from ray_tpu.rllib.rl_module import build_module_from_env_spec

logger = logging.getLogger(__name__)


class RolloutWorker:
    """Stateful sampler: keeps env state between sample() calls so rollout
    fragments stitch into full episodes across iterations."""

    def __init__(self, env: Any, n_envs: int = 8, seed: int = 0,
                 hidden=(64, 64), module: Optional[Any] = None,
                 jax_platform: Optional[str] = None, connectors: Any = None):
        import jax

        if jax_platform:
            # Samplers are tiny MLP forwards: pin them to host CPU so the
            # chip belongs to the learner (one JAX process per chip —
            # SURVEY.md §7 TPU process model).
            jax.config.update("jax_platforms", jax_platform)

        self.env = make_env(env, n_envs=n_envs, seed=seed,
                            connectors=connectors)
        self.module = module or build_module_from_env_spec(
            self.env_spec(), hidden=hidden)
        self.params = self.module.init_params(jax.random.PRNGKey(seed))
        self._rng = jax.random.PRNGKey(seed + 1000)
        self._obs = self.env.reset()
        # Episode-return tracking (for episode_reward_mean).
        self._ep_returns = np.zeros(self.env.n_envs, dtype=np.float64)
        self._ep_lens = np.zeros(self.env.n_envs, dtype=np.int64)
        self._completed: List[float] = []
        self._completed_lens: List[int] = []

    def set_weights(self, weights: Any):
        self.params = weights

    def env_spec(self) -> Dict[str, Any]:
        return {"obs_dim": self.env.obs_dim, "n_actions": self.env.n_actions,
                "n_envs": self.env.n_envs,
                "obs_shape": tuple(self.env.obs_shape)}

    def sample(self, num_steps: int) -> Dict[str, np.ndarray]:
        """Collect `num_steps` env steps (x n_envs transitions), flattened."""
        import jax

        n = self.env.n_envs
        obs_buf = np.empty((num_steps, n) + tuple(self.env.obs_shape),
                           dtype=self.env.obs_dtype)
        act_buf = np.empty((num_steps, n), dtype=np.int64)
        rew_buf = np.empty((num_steps, n), dtype=np.float32)
        done_buf = np.empty((num_steps, n), dtype=bool)
        trunc_buf = np.empty((num_steps, n), dtype=bool)
        logp_buf = np.empty((num_steps, n), dtype=np.float32)
        vf_buf = np.empty((num_steps, n), dtype=np.float32)
        next_vf_buf = np.empty((num_steps, n), dtype=np.float32)

        obs = self._obs
        final_obs_fixups: List = []  # (t, rows, final_obs[rows])
        for t in range(num_steps):
            self._rng, key = jax.random.split(self._rng)
            out = self.module.forward_exploration(self.params, obs, key)
            # The env needs host actions every step — this sync IS the
            # rollout contract; one device_get moves the whole step
            # output in a single transfer instead of three round-trips.
            host = jax.device_get(out)  # raylint: disable=RL021 — per-step sync is the env-step contract
            actions = host["actions"]
            next_obs, rewards, dones, infos = self.env.step(actions)
            obs_buf[t] = obs
            act_buf[t] = actions
            rew_buf[t] = rewards
            done_buf[t] = dones
            trunc_buf[t] = infos.get("truncated", np.zeros(n, dtype=bool))
            logp_buf[t] = host["logp"]
            vf_buf[t] = host["vf"]
            self._ep_returns += rewards
            self._ep_lens += 1
            done_rows = np.nonzero(dones)[0]
            if done_rows.size:
                # Auto-reset replaces the episode's true final obs with the
                # new episode's first obs; keep the real one so truncation
                # bootstraps V(final), not V(reset) (reference stores the
                # final obs the same way).
                fo = infos.get("final_obs")
                if fo is not None:
                    final_obs_fixups.append(
                        (t, done_rows, np.asarray(fo)[done_rows]))
                for i in done_rows:
                    self._completed.append(float(self._ep_returns[i]))
                    self._completed_lens.append(int(self._ep_lens[i]))
                    self._ep_returns[i] = 0.0
                    self._ep_lens[i] = 0
            obs = next_obs
        self._obs = obs

        # One batched value pass for all next-state values: V(s_{t+1}) is
        # V(s_t) shifted, with the tail row evaluated on the final obs.
        next_vf_buf[:-1] = vf_buf[1:]
        tail = self.module.forward_inference(self.params, obs)
        next_vf_buf[-1] = np.asarray(tail["vf"])
        # Patch done rows with V(true final obs): one padded batched
        # forward over every done row in the fragment (padding to a power
        # of two bounds the number of distinct jit shapes).
        if final_obs_fixups:
            all_fo = np.concatenate([f[2] for f in final_obs_fixups])
            k = len(all_fo)
            padded_k = 1
            while padded_k < k:
                padded_k *= 2
            padded = np.zeros((padded_k,) + all_fo.shape[1:], all_fo.dtype)
            padded[:k] = all_fo
            vals = np.asarray(self.module.forward_inference(
                self.params, padded)["vf"])[:k]
            pos = 0
            for t, rows, _ in final_obs_fixups:
                next_vf_buf[t, rows] = vals[pos: pos + rows.size]
                pos += rows.size

        batch = {
            sb.OBS: obs_buf.reshape(
                (num_steps * n,) + tuple(self.env.obs_shape)),
            # Tail observation: lets an off-policy learner (IMPALA) compute
            # its own bootstrap V(x_{T}) with current params.
            "_last_obs": np.asarray(obs, dtype=self.env.obs_dtype),
            sb.ACTIONS: act_buf.reshape(-1),
            sb.REWARDS: rew_buf.reshape(-1),
            sb.DONES: done_buf.reshape(-1),
            sb.TRUNCATEDS: trunc_buf.reshape(-1),
            sb.LOGP: logp_buf.reshape(-1),
            sb.VF_PREDS: vf_buf.reshape(-1),
            "_next_vf": next_vf_buf.reshape(-1),
            "_shape": np.array([num_steps, n]),
        }
        if final_obs_fixups:
            # True final observations for done rows (flat [T*n] indices):
            # off-policy learners bootstrap truncated episodes from the
            # real final state instead of the auto-reset observation.
            batch["_final_obs_at"] = np.concatenate(
                [t * n + rows for t, rows, _ in final_obs_fixups])
            batch["_final_obs"] = np.concatenate(
                [fo for _, _, fo in final_obs_fixups])
        return batch

    def episode_stats(self, clear: bool = True) -> Dict[str, Any]:
        stats = {
            "episodes": len(self._completed),
            "episode_reward_mean": float(np.mean(self._completed))
            if self._completed else None,
            "episode_len_mean": float(np.mean(self._completed_lens))
            if self._completed_lens else None,
        }
        if clear:
            self._completed = self._completed[-100:]
            self._completed_lens = self._completed_lens[-100:]
        return stats


class WorkerSet:
    """N rollout-worker actors + weight broadcast (reference worker_set.py)."""

    def __init__(self, env: Any, num_workers: int = 2, n_envs: int = 8,
                 hidden=(64, 64), seed: int = 0,
                 num_cpus_per_worker: float = 0.5,
                 jax_platform: Optional[str] = None,
                 connectors: Any = None, module: Optional[Any] = None):
        import ray_tpu

        self._ctor = dict(env=env, n_envs=n_envs, hidden=tuple(hidden),
                          jax_platform=jax_platform, seed=seed,
                          num_cpus=num_cpus_per_worker,
                          connectors=connectors, module=module)
        actor_cls = ray_tpu.remote(RolloutWorker)
        self.workers = [
            actor_cls.options(num_cpus=num_cpus_per_worker).remote(
                env, n_envs=n_envs, seed=seed + i, hidden=tuple(hidden),
                jax_platform=jax_platform, connectors=connectors,
                module=module)
            for i in range(num_workers)]
        self.num_workers = num_workers
        self._last_weights_ref = None  # re-sync replacements (see sample)

    def restart_worker(self, idx: int):
        """Replace a dead worker actor in place (fault tolerance —
        reference `FaultTolerantActorManager`)."""
        import ray_tpu

        c = self._ctor
        try:
            ray_tpu.kill(self.workers[idx])
        except Exception:  # noqa: BLE001 — already gone
            pass
        actor_cls = ray_tpu.remote(RolloutWorker)
        self.workers[idx] = actor_cls.options(
            num_cpus=c["num_cpus"]).remote(
            c["env"], n_envs=c["n_envs"], seed=c["seed"] + idx,
            hidden=c["hidden"], jax_platform=c["jax_platform"],
            connectors=c["connectors"], module=c["module"])
        return self.workers[idx]

    def sync_weights(self, weights: Any):
        import ray_tpu

        ref = ray_tpu.put(weights)
        self._last_weights_ref = ref
        refs = [w.set_weights.remote(ref) for w in self.workers]  # fan out
        for r in refs:
            try:
                ray_tpu.get(r)
            except Exception:  # noqa: BLE001 — dead worker: the next
                # sample() replaces it and the following broadcast re-syncs
                # its weights; don't die mid-broadcast.
                logger.warning("sync_weights: a rollout worker is dead")

    def sample(self, steps_per_worker: int) -> List[Dict[str, np.ndarray]]:
        """Fan out one sample task per worker. A dead worker is replaced in
        place and its fragment re-collected from the replacement (reference
        FaultTolerantActorManager) — PPO/DQN iterations survive worker loss
        without their own fault logic."""
        import ray_tpu

        refs = [w.sample.remote(steps_per_worker) for w in self.workers]
        out = []
        for i, r in enumerate(refs):
            try:
                out.append(ray_tpu.get(r))
            except Exception:  # noqa: BLE001 — dead worker
                logger.warning("sample: restarting dead rollout worker %d", i)
                w = self.restart_worker(i)
                if self._last_weights_ref is not None:
                    # The replacement initialized random weights; re-sync
                    # the last broadcast before sampling so its fragment
                    # is on-policy.
                    ray_tpu.get(w.set_weights.remote(self._last_weights_ref))
                out.append(ray_tpu.get(w.sample.remote(steps_per_worker)))
        return out

    def episode_stats(self) -> List[Dict[str, Any]]:
        import ray_tpu

        refs = [w.episode_stats.remote() for w in self.workers]  # fan out
        out = []
        for r in refs:
            try:
                out.append(ray_tpu.get(r))
            except Exception:  # noqa: BLE001 — dead worker: stats are
                # advisory; its replacement reports next iteration.
                pass
        return out

    def env_spec(self) -> Dict[str, int]:
        import ray_tpu

        return ray_tpu.get(self.workers[0].env_spec.remote())

    def shutdown(self):
        import ray_tpu

        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
