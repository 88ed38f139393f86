"""Benchmark harness. Prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}

Headline: GPT-2-small training tokens/sec/chip, run through the framework
(JaxTrainer -> worker actor -> jitted train step on the local chip). The
baseline (70k tok/s) is a round-1 reviewer's unoptimized probe, taken on a
chip attachment that no longer exists. Extra metrics mirror the reference's
microbenchmark suite (`python/ray/_private/ray_perf.py:93-173`): tasks/s,
actor calls/s, object put/get throughput.

Usage: python bench.py [--quick] [--skip-<plane> ...]
Every plane is individually skippable: core, train, ppo, serve,
inference, sharded, zoo, envelope, pull, collective, tracing, chaos.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BASELINE_TOKENS_PER_SEC = 70_000.0


# --------------------------------------------------------------------------- #
# GPT-2 training throughput (inside a TrainWorker subprocess owning the chip)
# --------------------------------------------------------------------------- #


def _gpt2_train_loop(config):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.gpt2 import (
        GPT2,
        GPT2Config,
        count_params,
        flops_per_token,
        make_train_step,
    )
    from ray_tpu.train import session

    import dataclasses

    use_flash = config.get("use_flash", True)
    if config.get("quick"):
        cfg = dataclasses.replace(
            GPT2Config.tiny(seq=config.get("seq_len", 256)),
            use_flash=use_flash, remat=config.get("remat", False))
    else:
        cfg = GPT2Config(use_flash=use_flash,
                         n_positions=config.get("seq_len", 1024),
                         remat=config.get("remat", False))
    bs = config.get("batch_size", 16)
    seq = config.get("seq_len", cfg.n_positions)
    steps = config.get("steps", 10)

    model = GPT2(cfg)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (bs, seq), 0, cfg.vocab_size, dtype=jnp.int32)
    params = jax.jit(lambda: model.init(rng, ids))()
    n_params = count_params(params)
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = jax.jit(opt.init)(params)
    step = make_train_step(model, opt, donate=True)
    batch = {"input_ids": ids, "labels": ids}

    # Warmup (compile) then timed steps.
    t_compile = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, batch)
    loss.block_until_ready()
    compile_s = time.perf_counter() - t_compile
    params, opt_state, loss = step(params, opt_state, batch)
    loss.block_until_ready()

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, batch)
    loss.block_until_ready()
    dt = time.perf_counter() - t0

    tokens_per_sec = bs * seq * steps / dt
    ms_per_step = dt / steps * 1e3
    device = jax.devices()[0]
    flops = flops_per_token(cfg, seq) * tokens_per_sec
    # A utilization is a device number: only a chip run has one.
    mfu = flops / _peak_flops(device.device_kind) \
        if device.platform == "tpu" else 0.0

    # Long-context kernel bench: flash vs XLA attention fwd+bwd at S=4096
    # — same worker so the chip is already claimed.
    attn = {}
    if not config.get("quick") and not config.get("skip_attn_bench") \
            and device.platform == "tpu" and use_flash:
        from ray_tpu.ops.attention import (
            flash_attention,
            mha_reference,
            pallas_status,
        )

        kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
        S = 4096
        aq = jax.random.normal(kq, (1, 8, S, 64), jnp.bfloat16)
        ak = jax.random.normal(kk, (1, 8, S, 64), jnp.bfloat16)
        av = jax.random.normal(kv, (1, 8, S, 64), jnp.bfloat16)

        def time_grad(attn_fn):
            def loss_fn(q, k, v):
                return jnp.sum(attn_fn(q, k, v).astype(jnp.float32) ** 2)

            g = jax.jit(jax.grad(loss_fn, argnums=(0, 1, 2)))
            jax.block_until_ready(g(aq, ak, av))
            t = time.perf_counter()
            for _ in range(10):
                r = g(aq, ak, av)
            jax.block_until_ready(r)
            return (time.perf_counter() - t) / 10 * 1e3

        attn["flash_grad_ms_s4096"] = time_grad(
            lambda q, k, v: flash_attention(q, k, v, True))
        attn["xla_attn_grad_ms_s4096"] = time_grad(
            lambda q, k, v: mha_reference(q, k, v, causal=True))

        # On-chip numerics: the Pallas kernels must agree with the XLA
        # reference on the hardware itself, not just in interpret mode.
        nq, nk2, nv = (jax.random.normal(kx, (2, 4, 512, 64), jnp.float32)
                       for kx in jax.random.split(jax.random.PRNGKey(2), 3))
        err = jnp.max(jnp.abs(flash_attention(nq, nk2, nv, True)
                              - mha_reference(nq, nk2, nv, causal=True)))
        gf = jax.grad(lambda a, b, c: jnp.mean(
            flash_attention(a, b, c, True) ** 2), argnums=(0, 1, 2))(
                nq, nk2, nv)
        gr = jax.grad(lambda a, b, c: jnp.mean(
            mha_reference(a, b, c, causal=True) ** 2), argnums=(0, 1, 2))(
                nq, nk2, nv)
        gerr = max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(gf, gr))
        attn["flash_fwd_maxerr"] = float(err)
        attn["flash_grad_maxerr"] = gerr
        # The comparison above is only meaningful if the Pallas path really
        # engaged — a silently-disabled kernel would compare XLA to itself
        # and publish fake agreement (and fake "flash" timings).
        calls = pallas_status()
        engaged = bool(calls) and all(c["path"] == "pallas" for c in calls)
        attn["pallas_engaged"] = engaged
        assert engaged, f"attention calls off the Pallas path: {calls}"
        assert float(err) < 2e-2 and gerr < 2e-2, \
            f"flash kernels diverge from XLA on-chip: {float(err)}, {gerr}"

    session.report({
        "tokens_per_sec": tokens_per_sec,
        "ms_per_step": ms_per_step,
        "mfu": mfu,
        "compile_s": compile_s,
        "n_params": n_params,
        "loss": float(loss),
        "device_kind": getattr(device, "device_kind", "unknown"),
        "platform": device.platform,
        **attn,
    })


def _has_tpu() -> bool:
    """Does the connected cluster advertise TPU chips? (Workers only see
    a chip through an explicit TPU grant — see raylet.py spawn_worker.)"""
    import ray_tpu

    try:
        return any(n["Resources"].get("TPU", 0) > 0 for n in ray_tpu.nodes())
    except Exception:  # noqa: BLE001 — not connected yet
        from ray_tpu.core.node import detect_tpu_chips

        return detect_tpu_chips() > 0


def _peak_flops(device_kind: str) -> float:
    kind = device_kind.lower()
    table = [
        ("v6", 918e12), ("v5p", 459e12), ("v5 lite", 197e12),
        ("v5e", 197e12), ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
    ]
    for key, val in table:
        if key in kind:
            return val
    raise ValueError(f"no peak FLOP/s on record for device_kind "
                     f"{device_kind!r}: add it to the table with its source "
                     "before publishing a utilization for it")


def bench_gpt2_train(quick: bool, use_flash: bool = True) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train.backend import JaxConfig

    has_tpu = _has_tpu()
    trainer = JaxTrainer(
        _gpt2_train_loop,
        train_loop_config={"quick": quick,
                           "use_flash": use_flash,
                           # bs=24 is this chip's sweet spot (bs=16: 102k,
                           # bs=24: 109k, bs=32: 102k tok/s on v5e)
                           "batch_size": 4 if quick else 24,
                           "seq_len": 256 if quick else 1024,
                           "steps": 5 if quick else 10},
        jax_config=JaxConfig(distributed=False),
        # The chip must be REQUESTED: workers without a TPU grant are
        # pinned to CPU jax (chip isolation, raylet.py spawn_worker).
        scaling_config=ScalingConfig(num_workers=1, use_tpu=has_tpu,
                                     tpus_per_worker=1 if has_tpu else 0),
        run_config=RunConfig(name=f"bench_{int(time.time())}"),
    )
    result = trainer.fit()
    if result.error is not None:
        raise result.error
    return result.metrics


def bench_gpt2_long(quick: bool, steps: int = 6,
                    cached_probe_bs: int = 0) -> dict:
    """Long-context on-chip training: GPT-2-small at seq=8192 with flash +
    per-block remat (SURVEY §5.7's net-new axis needs an on-chip number).
    With `cached_probe_bs`, a second fresh worker re-runs 2 steps at the
    same batch size so its compile time measures the persistent
    compilation cache (each fit spawns a new process — its in-memory jit
    cache is cold, only the on-disk cache is warm)."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train.backend import JaxConfig

    cached_probe = bool(cached_probe_bs)
    has_tpu = _has_tpu()
    out: dict = {}
    for bs in ((cached_probe_bs,) if cached_probe
               else (2,) if quick else (4, 2, 1)):
        trainer = JaxTrainer(
            _gpt2_train_loop,
            train_loop_config={"quick": quick,
                               "use_flash": True,
                               "remat": True,
                               "batch_size": bs,
                               "seq_len": 512 if quick else 8192,
                               "steps": 2 if (quick or cached_probe)
                               else steps,
                               "skip_attn_bench": True},
            jax_config=JaxConfig(distributed=False),
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=has_tpu,
                tpus_per_worker=1 if has_tpu else 0),
            run_config=RunConfig(name=f"bench_long_{int(time.time())}"),
        )
        result = trainer.fit()
        if result.error is None:
            m = result.metrics
            seq = 512 if quick else 8192  # suffix names the REAL seq len
            suffix = f"_s{seq}" + ("_cached" if cached_probe else "")
            out[f"tokens_per_sec{suffix}"] = m["tokens_per_sec"]
            out[f"mfu{suffix}"] = m["mfu"]
            out[f"compile_s{suffix}"] = m["compile_s"]
            if not cached_probe:
                out[f"batch_size_s{seq}"] = bs
                out[f"loss_s{seq}"] = m["loss"]
            return out
        err = result.error
    raise err


# --------------------------------------------------------------------------- #
# Core microbenchmarks (reference ray_perf.py equivalents)
# --------------------------------------------------------------------------- #


def bench_core(quick: bool) -> dict:
    """Reference-parity microbenchmarks (`ray_perf.py:93-173`): single- and
    multi-client task/actor throughput, many-args, wait, put/get."""
    import threading

    import numpy as np

    import ray_tpu

    out = {}
    n_tasks = 200 if quick else 2000

    @ray_tpu.remote
    def noop():
        return None

    @ray_tpu.remote
    def many_args(a, b, c, d, e):
        return None

    # Warm the worker pool + lease cache.
    ray_tpu.get([noop.remote() for _ in range(32)])

    def timed_tasks(fn, n, *args):
        """(submit_per_s, total_per_s) for one burst — the submit rate is
        the owner-side cost alone (.remote() returns pre-dispatch), the
        total folds in dispatch + execution + result delivery."""
        t0 = time.perf_counter()
        refs = [fn.remote(*args) for _ in range(n)]
        submit_s = time.perf_counter() - t0
        ray_tpu.get(refs)
        total_s = time.perf_counter() - t0
        return n / submit_s, n / total_s

    # Best-of-2: the 2-core sandbox shares cores with the whole fake
    # cluster, and one descheduled flush tick can halve a single run.
    plain = max((timed_tasks(noop, n_tasks) for _ in range(2)),
                key=lambda r: r[1])
    out["tasks_submit_per_s"] = plain[0]
    out["tasks_per_s"] = plain[1]
    # Dispatch-side rate: completions per second during the drain phase
    # alone (post-submit). Derived from the same burst so the two sides
    # decompose the same number.
    total_s = n_tasks / plain[1]
    submit_s = n_tasks / plain[0]
    out["tasks_dispatch_per_s"] = n_tasks / max(total_s - submit_s, 1e-9)

    many = max((timed_tasks(many_args, n_tasks // 2,
                            1, 2.0, "x", b"y", None) for _ in range(2)),
               key=lambda r: r[1])
    out["tasks_many_args_per_s"] = many[1]
    ratio = many[1] / max(plain[1], 1e-9)
    out["tasks_many_args_ratio"] = round(ratio, 3)
    # The arg-dedupe cache removed the per-spec arg re-serialization that
    # made many-arg tasks lag plain ones by ~20% (r05: 1303 vs 1613);
    # hold the line at within-10% (best-of-2 damps sandbox noise).
    assert ratio >= 0.9, (
        f"tasks_many_args_per_s lags plain tasks by "
        f"{(1 - ratio) * 100:.0f}% (> 10%): arg dedupe regressed")

    # A-B-A inertness: the flush-tick path disabled must be exactly the
    # pre-batching behavior (fresh cluster so WORKERS inherit the flag
    # too — result coalescing is worker-side). The off rate doubles as
    # the same-run anchor for the soft regression flag: if batching-on
    # isn't clearly faster than its own off-path, the optimization
    # regressed (host-speed-normalized by construction — same run, same
    # machine, same load).
    ray_tpu.shutdown()
    os.environ["RAY_TPU_DIRECT_FLUSH_TICK_MS"] = "0"
    try:
        ray_tpu.init(num_cpus=4)

        @ray_tpu.remote
        def noop_off():
            return None

        ray_tpu.get([noop_off.remote() for _ in range(32)])
        off = max((timed_tasks(noop_off, n_tasks) for _ in range(2)),
                  key=lambda r: r[1])
        out["tasks_per_s_batching_off"] = off[1]
        d = ray_tpu._require_runtime()._direct
        # Inertness evidence: the flusher machinery never engaged (multi-
        # spec frames from backlog pumping are PRE-existing PR-7 behavior
        # and legal on either path).
        assert d._flusher is None, \
            "flush-tick disabled but the flusher thread engaged"
    finally:
        os.environ.pop("RAY_TPU_DIRECT_FLUSH_TICK_MS", None)
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    out["tasks_per_s_vs_offpath"] = round(
        plain[1] / max(off[1], 1e-9), 3)
    out["tasks_per_s_regressed"] = bool(plain[1] < 1.5 * off[1])
    if out["tasks_per_s_regressed"]:
        print("WARNING: tasks_per_s only "
              f"{out['tasks_per_s_vs_offpath']}x its same-run off-path "
              "anchor (soft flag)", file=sys.stderr)

    ray_tpu.get([noop.remote() for _ in range(32)])  # re-warm new cluster

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.x = 0

        def inc(self):
            self.x += 1
            return self.x

    c = Counter.remote()
    ray_tpu.get(c.inc.remote())
    n_calls = 200 if quick else 2000
    t0 = time.perf_counter()
    ray_tpu.get([c.inc.remote() for _ in range(n_calls)])
    out["actor_calls_per_s"] = n_calls / (time.perf_counter() - t0)

    # Multi-client: 4 driver threads, one actor each (ray_perf
    # "n:n actor calls").
    n_clients = 2 if quick else 4
    actors = [Counter.remote() for _ in range(n_clients)]
    ray_tpu.get([a.inc.remote() for a in actors])
    per_client = n_calls // n_clients

    def drive(actor):
        ray_tpu.get([actor.inc.remote() for _ in range(per_client)])

    t0 = time.perf_counter()
    threads = [threading.Thread(target=drive, args=(a,)) for a in actors]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    out["actor_calls_multi_client_per_s"] = (
        per_client * n_clients) / (time.perf_counter() - t0)

    # The actor fleets above hold CPU grants for life; release them so
    # the sections below measure the object/wait paths, not task
    # starvation behind parked actors (ray_perf isolates each bench).
    for a in [c] + actors:
        try:
            ray_tpu.kill(a)
        except Exception:  # noqa: BLE001
            pass
    time.sleep(0.5)
    # Re-warm task workers: actor creation consumed the pooled idle
    # workers (idle reuse) and the kills destroyed them, so the next
    # section would otherwise measure interpreter cold-start, not the
    # wait/completion plumbing it targets.
    ray_tpu.get([noop.remote() for _ in range(32)])

    # wait() on 1k in-flight refs (ray_perf "wait on 1k refs").
    n_wait = 100 if quick else 1000
    refs = [noop.remote() for _ in range(n_wait)]
    t0 = time.perf_counter()
    ready, _ = ray_tpu.wait(refs, num_returns=n_wait, timeout=120)
    out["wait_1k_refs_s"] = time.perf_counter() - t0
    assert len(ready) == n_wait

    # Object store throughput: 64 MiB numpy round-trip (best of 3 after a
    # warmup put that absorbs the one-time native-lib build).
    mb = 8 if quick else 64
    arr = np.random.default_rng(0).random(mb * 1024 * 1024 // 8)
    ray_tpu.put(np.ones(1024 * 1024))
    put_s = get_s = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        ref = ray_tpu.put(arr)
        put_s = min(put_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        back = ray_tpu.get(ref)
        get_s = min(get_s, time.perf_counter() - t0)
        assert back.nbytes == arr.nbytes
        del back, ref
        # Steady state, not the free-to-put race: the freed segment's
        # reclaim (rename + background pre-fault) needs a beat before
        # the next put can reuse it warm — as any real training loop's
        # compute provides.
        time.sleep(0.2)
    out["put_gbps"] = arr.nbytes / put_s / 1e9
    out["get_gbps"] = arr.nbytes / get_s / 1e9
    # Diagnostic: put bandwidth is memcpy/page-fault-bound; the MT native
    # copy only engages when a C compiler was available to build fastcopy.
    from ray_tpu._native import get_lib

    native = get_lib() is not None
    out["fastcopy_native"] = native
    from ray_tpu._native import _copy_threads

    # Both the native MT copy and the ctypes-memmove fallback use this
    # thread count; without either, the numpy path is single-threaded.
    out["put_copy_threads"] = _copy_threads(arr.nbytes)
    return out


# --------------------------------------------------------------------------- #
# PPO: env throughput + learner SPS (BASELINE.json north-star #2)
# --------------------------------------------------------------------------- #


def bench_ppo(quick: bool) -> dict:
    from ray_tpu.rllib import PPO, PPOConfig

    minibatch = 256
    algo = PPO(PPOConfig(
        env="CartPole-v1",
        num_rollout_workers=1 if quick else 2,
        num_envs_per_worker=8 if quick else 16,
        rollout_fragment_length=64 if quick else 128,
        num_sgd_iter=4 if quick else 8,
        sgd_minibatch_size=minibatch,
        rollout_platform="cpu",
    ))
    try:
        algo.train()  # warm compile
        iters = 2 if quick else 4
        t0 = time.perf_counter()
        timesteps0 = algo._timesteps
        sgd_total = 0
        learn_s = 0.0
        for _ in range(iters):
            m = algo.train()
            sgd_total += m.get("sgd_steps", 0)
            learn_s += m.get("learn_s", 0.0)
        dt = time.perf_counter() - t0
        steps = algo._timesteps - timesteps0
        return {
            "ppo_env_steps_per_s": steps / dt,
            "ppo_learner_sgd_per_s": sgd_total / learn_s if learn_s else 0.0,
            "ppo_learner_steps_per_s":
                sgd_total * minibatch / learn_s if learn_s else 0.0,
        }
    finally:
        algo.stop()


def bench_impala(quick: bool) -> dict:
    from ray_tpu.rllib import IMPALA, IMPALAConfig

    algo = IMPALA(IMPALAConfig(
        env="CartPole-v1",
        num_rollout_workers=1 if quick else 2,
        num_envs_per_worker=8 if quick else 16,
        rollout_fragment_length=32 if quick else 64,
        fragments_per_batch=2,
        replay_fragments=2,
        updates_per_iteration=4 if quick else 8,
        rollout_platform="cpu",
    ))
    try:
        algo.train()  # warm compile
        iters = 1 if quick else 3
        t0 = time.perf_counter()
        frames0 = algo._timesteps
        learner_sps = 0.0
        for _ in range(iters):
            m = algo.train()
            learner_sps = m.get("learner_sps", 0.0)
        dt = time.perf_counter() - t0
        return {
            "impala_env_steps_per_s": (algo._timesteps - frames0) / dt,
            "impala_learner_sps": learner_sps,
        }
    finally:
        algo.stop()


def bench_learner_dp(quick: bool) -> dict:
    """PPO learner SPS single-device vs dp=2 sharded (LearnerGroup
    num_learners). Only one real chip is attached, so both run in a
    subprocess on a 2-virtual-device CPU mesh — the comparison measures
    the sharded-update machinery, not chip FLOPs."""
    import json as _json
    import os
    import subprocess
    import sys

    script = r"""
import json, time
import numpy as np
from ray_tpu.rllib import sample_batch as sb
from ray_tpu.rllib.ppo import PPOConfig, PPOLearner
from ray_tpu.rllib.rl_module import DiscretePolicyModule, SpecDict

rows, iters = %d, %d
rng = np.random.default_rng(0)
batch = {
    sb.OBS: rng.standard_normal((rows, 8)).astype(np.float32),
    sb.ACTIONS: rng.integers(0, 4, rows).astype(np.int32),
    sb.LOGP: np.log(np.full(rows, 0.25, np.float32)),
    sb.ADVANTAGES: rng.standard_normal(rows).astype(np.float32),
    sb.VF_PREDS: rng.standard_normal(rows).astype(np.float32),
    sb.VALUE_TARGETS: rng.standard_normal(rows).astype(np.float32),
}
out = {}
for nd in (1, 2):
    module = DiscretePolicyModule(SpecDict(8, 4), hidden=(64, 64))
    learner = PPOLearner(module, PPOConfig(), seed=0, num_devices=nd)
    learner.update(batch)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        learner.update(batch)
    out[f"rllib_learner_sps_dp{nd}"] = rows * iters / (time.perf_counter() - t0)
print(json.dumps(out))
""" % ((4096, 20) if quick else (16384, 50))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2").strip()
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-500:])
    return _json.loads(proc.stdout.strip().splitlines()[-1])


# --------------------------------------------------------------------------- #
# Scalability envelope (reference release/benchmarks/README.md:9-31)
# --------------------------------------------------------------------------- #


def _envelope_main(n_tasks: int, n_actors: int, n_pgs: int, n_refs: int,
                   broadcast_mb: int) -> dict:
    """Runs inside a fresh subprocess: a 4-raylet fake cluster exercising
    the reference's scalability-envelope shapes (many queued tasks, many
    actors, many placement groups, many-ref get, large-object broadcast
    across nodes). Scaled by the caller; returns the metrics dict."""
    import time as _time

    import numpy as _np

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.placement_group import (
        placement_group,
        remove_placement_group,
    )

    out: dict = {}
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 4})
    for _ in range(3):
        cluster.add_node(num_cpus=4)
    cluster.wait_for_nodes()
    cluster.connect()
    try:
        @ray_tpu.remote
        def noop(i):
            return i

        ray_tpu.get([noop.remote(i) for i in range(20)])  # warm workers

        # Many queued tasks: submit far beyond capacity, then drain.
        # Best-of-2 (mirrors bench_core): the first burst pays the lease
        # and worker-pool ramp across 4 nodes — cold fork storms steal
        # the submitting thread's GIL — so it measures bring-up, not the
        # steady-state fast path this metric tracks.
        best_submit = best_total = 0.0
        for _ in range(2):
            t0 = _time.perf_counter()
            refs = [noop.remote(i) for i in range(n_tasks)]
            submit_s = _time.perf_counter() - t0
            ray_tpu.get(refs)
            total_s = _time.perf_counter() - t0
            if n_tasks / total_s > best_total:
                best_total = n_tasks / total_s
                best_submit = n_tasks / submit_s
            del refs
        out["envelope_tasks"] = n_tasks
        out["envelope_task_submit_per_s"] = best_submit
        out["envelope_task_throughput_per_s"] = best_total

        # Many-ref get (reference ray.get on 10k refs).
        refs = [noop.remote(i) for i in range(n_refs)]
        ray_tpu.wait(refs, num_returns=n_refs, timeout=600)
        t0 = _time.perf_counter()
        vals = ray_tpu.get(refs)
        out["envelope_get_many_refs_s"] = _time.perf_counter() - t0
        assert len(vals) == n_refs
        del refs, vals

        # Many actors: create, one call each, kill.
        @ray_tpu.remote
        class A:
            def ping(self):
                return 1

        # Let the direct transport return its idle leases first so actor
        # creations can REUSE pooled workers instead of cold-spawning
        # past the pool (a cold spawn storm on a small host outruns the
        # 30s registration window).
        _time.sleep(3.0)
        t0 = _time.perf_counter()
        actors = []
        # Waves: an unbounded spawn storm can outrun worker registration
        # on small hosts; with the worker forge, spawns are ~10-20ms
        # forks, so wider waves (16, up from 8) measure pipelining rather
        # than convoying — cold-fallback hosts still fit registration in
        # the raised lease window.
        wave = 16
        for start in range(0, n_actors, wave):
            batch = [A.options(num_cpus=0.01).remote()
                     for _ in range(min(wave, n_actors - start))]
            ray_tpu.get([a.ping.remote() for a in batch])
            actors.extend(batch)
        out["envelope_actors"] = n_actors
        out["envelope_actor_create_call_per_s"] = (
            n_actors / (_time.perf_counter() - t0))
        for a in actors:
            ray_tpu.kill(a)
        del actors

        # Many placement groups (1 tiny bundle each): create+ready+remove.
        t0 = _time.perf_counter()
        pgs = [placement_group([{"CPU": 0.01}]) for _ in range(n_pgs)]
        for pg in pgs:
            pg.ready()  # blocking (2PC commit across the fake nodes)
        for pg in pgs:
            remove_placement_group(pg)
        out["envelope_pgs"] = n_pgs
        out["envelope_pg_cycle_per_s"] = n_pgs / (_time.perf_counter() - t0)

        # Broadcast: one large object read by one task per node.
        arr = _np.random.default_rng(0).random(
            broadcast_mb * 1024 * 1024 // 8)
        big = ray_tpu.put(arr)

        @ray_tpu.remote
        def checksum(x):
            return float(x[::4096].sum())

        expect = float(arr[::4096].sum())
        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy,
        )

        nodes = [n["NodeID"] for n in ray_tpu.nodes() if n["Alive"]]
        # Warm one worker per node first: the broadcast number should
        # measure the object read path, not cold interpreter spawns.
        ray_tpu.get([noop.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=nid, soft=True)).remote(0) for nid in nodes],
            timeout=600)
        t0 = _time.perf_counter()
        reads = {checksum.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=nid, soft=True)).remote(big): nid for nid in nodes}
        # Per-node completion breakdown: with the multi-source transfer
        # plane the stragglers should finish close behind the first
        # completion (they drain from earlier pullers), not at N x its
        # time (everyone convoying on the seed node).
        pending = list(reads)
        node_done_s = {}
        read_deadline = _time.perf_counter() + 600
        while pending:
            done, pending = ray_tpu.wait(pending, num_returns=1, timeout=30)
            now = _time.perf_counter() - t0
            for ref in done:
                node_done_s[reads[ref][:12]] = round(now, 4)
            # wait() returns ([], pending) on timeout rather than raising:
            # bound the loop so a wedged broadcast records an error instead
            # of hanging the whole bench.
            if not done and _time.perf_counter() > read_deadline:
                raise TimeoutError(
                    f"broadcast reads stuck; completed {node_done_s}")
        sums = ray_tpu.get(list(reads), timeout=600)
        dt = _time.perf_counter() - t0
        assert all(abs(s - expect) < 1e-6 * max(1.0, abs(expect))
                   for s in sums)
        out["envelope_broadcast_mb"] = broadcast_mb
        out["envelope_broadcast_nodes"] = len(nodes)
        out["envelope_broadcast_node_s"] = node_done_s
        out["envelope_broadcast_gb_s"] = (
            arr.nbytes * len(nodes) / dt / 1e9)

        # Worker-spawn microbench: forge fork vs cold exec, timed from
        # the spawn call to worker registration (the moment the worker
        # can take work). Runs LAST, after a settle pause — measuring it
        # mid-envelope folds the cluster's own churn into the number.
        del arr
        _time.sleep(2.0)
        head = cluster.raylets[0]

        def timed_spawn(kind: str) -> float:
            t0 = _time.perf_counter()
            h = head.pool.spawn_worker(env_extra={}, kind=kind)
            ok = h.registered.wait(120)
            dt = (_time.perf_counter() - t0) * 1e3
            assert ok and h.conn is not None, f"{kind} spawn never registered"
            head.pool.mark_dead(h.worker_id)  # keep the pool unchanged
            h.proc.terminate()
            return dt

        if head.forge is not None and head.forge.wait_ready(30):
            forge_ms = sorted(timed_spawn("forge") for _ in range(3))
            out["worker_spawn_forge_ms"] = round(forge_ms[1], 1)
        out["worker_spawn_cold_ms"] = round(timed_spawn("cold"), 1)
    finally:
        cluster.shutdown()
    return out


def bench_envelope(quick: bool) -> dict:
    """Subprocess-isolated envelope run (its fake cluster must not touch
    the bench's own runtime)."""
    import json as _json
    import subprocess
    import sys

    sizes = ((3000, 30, 20, 2000, 128) if quick
             else (20000, 200, 100, 10000, 1024))
    code = ("import bench, json; "
            f"print('ENV_RESULT ' + json.dumps(bench._envelope_main"
            f"{sizes!r}))")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # Concurrent cold spawns share this host's cores with the whole fake
    # cluster; the default 30s registration window is sized for a real
    # node running one raylet.
    env["RAY_TPU_WORKER_LEASE_TIMEOUT_MS"] = "180000"
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=1800,
                          cwd=os.path.dirname(os.path.abspath(__file__)),
                          env=env)
    for line in (proc.stdout or "").splitlines():
        if line.startswith("ENV_RESULT "):
            return _json.loads(line[len("ENV_RESULT "):])
    raise RuntimeError(
        f"envelope run failed (rc={proc.returncode}): "
        f"{(proc.stderr or '')[-500:]}")


# --------------------------------------------------------------------------- #
# 100-node envelope: the width the 4-node envelope never exercises
# --------------------------------------------------------------------------- #


def _envelope100_main(n_nodes: int, managed: int, kills: int,
                      broadcast_mb: int, link_mb_s: float,
                      smoke: bool) -> dict:
    """Runs inside a fresh subprocess: a `n_nodes`-raylet fake cluster
    (head + thin control-plane nodes + an autoscaler-managed worker
    fleet) measuring what only exists at width — placement latency over
    a 100-entry view, task submission against a wide lease cache,
    broadcast through the link-modeled transfer tree, collective
    width at the GCS mailbox — then runs the PR-10 chaos schedule AT
    that width with AUTOSCALER-driven node replacement (not the bench's
    immediate add_node), asserting lease-cache invalidation: every task
    resolves, and any task that executed twice is accounted for by an
    owner-side retry (a kill), never by a stale-lease double push."""
    import tempfile as _tempfile
    import threading as _threading
    import time as _time

    import numpy as _np

    import ray_tpu
    from ray_tpu.autoscaler.autoscaler import (
        AutoscalerConfig,
        LocalNodeProvider,
        StandardAutoscaler,
    )
    from ray_tpu.chaos.injectors import NodeKillInjector
    from ray_tpu.chaos.runner import ChaosRunner
    from ray_tpu.chaos.schedule import ChaosSchedule
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.util.placement_group import (
        placement_group,
        remove_placement_group,
    )

    out: dict = {"envelope100_nodes": n_nodes}
    t_start = _time.perf_counter()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    thin = n_nodes - 1 - managed
    for _ in range(thin):
        cluster.add_node(num_cpus=0, resources={"slot": 1})
    provider = LocalNodeProvider(cluster)
    autoscaler = StandardAutoscaler(
        cluster.gcs.address, provider,
        AutoscalerConfig(min_workers=managed, max_workers=managed + 2,
                         node_resources={"CPU": 2, "slot": 1},
                         idle_timeout_s=3600.0, launch_grace_s=20.0,
                         update_period_s=0.5))
    autoscaler.update()  # synchronous floor fill, then the loop maintains
    autoscaler.start()
    try:
        cluster.wait_for_nodes(timeout=120)
        cluster.connect()
        out["envelope100_bringup_s"] = round(
            _time.perf_counter() - t_start, 2)

        # --- placement latency at width: SPREAD placement groups whose
        # 2PC must pick + reserve bundles across a 100-entry view.
        widths = (8,) if smoke else (8, 32)
        for w in widths:
            reps = []
            for _ in range(2 if smoke else 3):
                t0 = _time.perf_counter()
                pg = placement_group([{"slot": 1}] * w, strategy="SPREAD")
                pg.ready(timeout=120)
                reps.append((_time.perf_counter() - t0) * 1e3)
                remove_placement_group(pg)
            out[f"envelope100_pg{w}_ready_ms"] = round(sorted(reps)[len(reps) // 2], 1)

        # --- task plane at width: the fast path submitting against a
        # 100-node view (leases on the head + managed CPU nodes).
        mark_dir = _tempfile.mkdtemp(prefix="e100marks")
        mark_file = os.path.join(mark_dir, "execs")

        @ray_tpu.remote
        def marked(path, idx):
            with open(path, "a") as f:
                f.write(f"{idx}\n")
            return idx

        @ray_tpu.remote
        def noop(i):
            return i

        ray_tpu.get([noop.remote(i) for i in range(32)])  # warm leases
        n_tasks = 400 if smoke else 2000
        best_submit = best_total = 0.0
        for _ in range(2):  # best-of-2: first burst pays the lease ramp
            t0 = _time.perf_counter()
            refs = [noop.remote(i) for i in range(n_tasks)]
            submit_s = _time.perf_counter() - t0
            assert ray_tpu.get(refs, timeout=300) == list(range(n_tasks))
            total_s = _time.perf_counter() - t0
            if n_tasks / total_s > best_total:
                best_total = n_tasks / total_s
                best_submit = n_tasks / submit_s
            del refs
        out["envelope100_task_submit_per_s"] = round(best_submit, 1)
        out["envelope100_tasks_per_s"] = round(best_total, 1)

        if not smoke:
            # --- broadcast at width through the link-modeled transfer
            # tree: every thin raylet pulls the object; the partial-
            # location redirect tree must fan out, not convoy on the
            # seed's modeled NIC.
            head = cluster.raylets[0]
            size = broadcast_mb << 20
            oid = ObjectID.from_random()
            payload = _np.random.default_rng(0).integers(
                0, 255, size=size, dtype=_np.uint8).tobytes()
            head.store.put_serialized(oid, [payload])
            head.gcs.call("object_location_add",
                          {"object_id": oid, "node_id": head.node_id,
                           "size": head.store.local_size(oid)}, timeout=10)
            pullers = [r for r in cluster.raylets
                       if r is not head and not r.resources.total.get("CPU")]
            for r in cluster.raylets:
                r._chunk_serve_bw_bps = link_mb_s * 1e6
            done_at: dict = {}
            errs: list = []

            def pull_one(raylet):
                try:
                    entry = raylet.gcs.call("object_locations_get",
                                            {"object_id": oid}, timeout=30)
                    if not raylet._pull_object_pipelined(oid, entry):
                        errs.append(raylet.node_id.hex()[:8])
                    done_at[raylet.node_id.hex()[:8]] = \
                        _time.perf_counter() - t0
                except Exception as e:  # noqa: BLE001 — recorded, asserted
                    errs.append(f"{raylet.node_id.hex()[:8]}:{e}")

            t0 = _time.perf_counter()
            threads = [_threading.Thread(target=pull_one, args=(r,),
                                         daemon=True) for r in pullers]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            dt = _time.perf_counter() - t0
            for r in cluster.raylets:
                r._chunk_serve_bw_bps = 0.0
            assert not errs, f"broadcast pulls failed: {errs[:5]}"
            out["envelope100_broadcast_mb"] = broadcast_mb
            out["envelope100_broadcast_nodes"] = len(pullers)
            out["envelope100_broadcast_link_mb_s"] = link_mb_s
            out["envelope100_broadcast_gb_s"] = round(
                size * len(pullers) / dt / 1e9, 3)
            out["envelope100_broadcast_p50_s"] = round(
                sorted(done_at.values())[len(done_at) // 2], 2)
            head.store.delete(oid)

            # --- collective width: one barrier + inline fan-in across
            # n_nodes distinct GCS connections (the mailbox's width
            # limit, independent of payload bandwidth).
            from ray_tpu.core.rpc import RpcClient as _Rpc

            world = n_nodes
            members = [_Rpc(cluster.gcs.address, name=f"e100-r{i}")
                       for i in range(world)]
            try:
                epoch = None
                for i, cli in enumerate(members):
                    resp = cli.call("collective_join",
                                    {"name": "e100", "world_size": world,
                                     "rank": i}, timeout=30)
                    assert resp["status"] == "ok", resp
                    epoch = resp["epoch"]
                barrier_ms = []
                for seq in range(3):
                    t0 = _time.perf_counter()
                    ths = [_threading.Thread(
                        target=lambda c=c, i=i: c.call(
                            "collective_barrier",
                            {"name": "e100", "epoch": epoch, "seq": seq,
                             "rank": i}, timeout=60), daemon=True)
                        for i, c in enumerate(members)]
                    for t in ths:
                        t.start()
                    for t in ths:
                        t.join(timeout=90)
                    barrier_ms.append((_time.perf_counter() - t0) * 1e3)
                out["envelope100_collective_width"] = world
                out["envelope100_barrier_ms"] = round(
                    sorted(barrier_ms)[1], 1)
            finally:
                for cli in members:
                    cli.close()

        # --- query exchange AT width: a distributed sort whose scatter/
        # reduce state lives ONLY on the managed workers (tasks need
        # CPU + slot: thin nodes have no CPU, the head no slot), with the
        # busiest worker killed mid-exchange. The epoch must finish
        # sorted and complete, with recompute bounded by the victim's
        # resident blocks + n_parts and replacement driven by the
        # autoscaler floor — the same invariant the tier-1 slow test
        # checks at 3 nodes, here gated at 100.
        from ray_tpu import data as _rd
        from ray_tpu.chaos import HangWatchdog as _Watchdog
        from ray_tpu.data.context import DataContext as _DataContext
        from ray_tpu.data.streaming.lineage import (
            core_reconstructions as _core_recon,
        )

        q_rows, q_parts = (8_000, 4) if smoke else (16_000, 8)

        def _keyed(batch):
            return {"k": (batch["data"][:, 0].astype(_np.int64)) % 50,
                    "data": batch["data"]}

        _ctx = _DataContext.get_current()
        _old_inflight = _ctx.max_tasks_in_flight_per_op
        # Throttled launch keeps the exchange mid-flight at kill time, so
        # the victim's death destroys state the sort still needs.
        _ctx.max_tasks_in_flight_per_op = 2
        try:
            qds = _rd.range_tensor(q_rows, shape=(64,),
                                   parallelism=q_parts) \
                .with_resources(resources={"slot": 0.05}) \
                .map_batches(_keyed).sort(key="k")
            q_base = _core_recon()
            q_rows_seen, q_last, q_killed = 0, None, {}
            t_kill = 0.0
            with _Watchdog(limit_s=90.0) as wd:
                for i, batch in enumerate(qds.iter_batches(batch_size=512)):
                    q_rows_seen += len(batch["k"])
                    ks = _np.asarray(batch["k"])
                    assert (_np.diff(ks) >= 0).all()
                    if q_last is not None:
                        assert ks[0] >= q_last
                    q_last = int(ks[-1])
                    if i == 1 and not q_killed:
                        victim = max(
                            (r for r in cluster.raylets if not r.is_head
                             and r.resources.total.get("CPU")),
                            key=lambda r: r.store.stats()["num_objects"])
                        q_killed["resident"] = \
                            victim.store.stats()["num_objects"]
                        t_kill = _time.perf_counter()
                        cluster.crash_node(victim)
            wd.assert_no_hangs()
            assert q_rows_seen == q_rows, \
                f"query leg lost rows: {q_rows_seen}/{q_rows}"
            q_recomputed = (_core_recon() - q_base) \
                + (qds._lineage.recomputed_blocks if qds._lineage else 0)
            assert q_recomputed >= 1, \
                "the kill destroyed nothing the sort used"
            q_bound = max(q_killed.get("resident", 0), 1) + q_parts
            assert q_recomputed <= q_bound, (q_recomputed, q_killed)
            out["envelope100_query_rows"] = q_rows_seen
            out["envelope100_query_recomputed_blocks"] = q_recomputed
            out["envelope100_query_kill_recovered_s"] = round(
                _time.perf_counter() - t_kill, 2)
            out["envelope100_query_zero_hangs"] = wd.hang_count == 0
        finally:
            _ctx.max_tasks_in_flight_per_op = _old_inflight
        # The autoscaler refills the floor before the chaos phase leans
        # on the same fleet.
        cluster.wait_for_nodes(timeout=120)

        # --- chaos AT width: the PR-10 schedule with autoscaler-driven
        # replacement, under continuous direct-path task load. The
        # side-channel exec marks prove lease-cache invalidation: a task
        # may execute twice ONLY if its owner recorded a retry (kill),
        # never because a stale lease double-pushed it.
        sched = ChaosSchedule(seed=12, kinds=("node_kill",),
                              period_s=3.0 if smoke else 6.0, count=kills,
                              jitter=0.2, start_delay_s=1.0)
        out["envelope100_chaos_schedule"] = sched.describe()["events"]
        injector = NodeKillInjector(cluster, provider=provider)
        stop_load = _threading.Event()
        load_refs: list = []
        load_errs: list = []

        def load_loop():
            i = 0
            while not stop_load.is_set():
                try:
                    batch = [marked.remote(mark_file, i + k)
                             for k in range(20)]
                    i += 20
                    load_refs.extend(batch)
                    ray_tpu.wait(batch, num_returns=len(batch), timeout=120)
                except Exception as e:  # noqa: BLE001 — recorded, asserted
                    load_errs.append(repr(e))
                _time.sleep(0.05)

        loader = _threading.Thread(target=load_loop, daemon=True)
        loader.start()
        runner = ChaosRunner(cluster, sched, {"node_kill": injector},
                             recovery_deadline_s=45.0 if smoke else 90.0)
        with runner:
            finished = runner.wait(timeout=300.0)
        stop_load.set()
        loader.join(timeout=120)
        assert finished, "chaos schedule did not finish in time"
        runner.assert_recovered()
        assert not load_errs, f"task load errored under chaos: {load_errs[:3]}"
        out["envelope100_chaos_kills"] = runner.faults_injected
        out["envelope100_chaos_mttr_ms"] = runner.mttr_by_kind().get(
            "node_kill", {})
        out["envelope100_autoscaler_launches"] = autoscaler.num_launches

        # Drain every in-flight ref: zero hangs, zero losses.
        results = ray_tpu.get(load_refs, timeout=180)
        assert results == list(range(len(load_refs))), \
            "task results lost or misordered under chaos"
        # Lease-invalidation accounting: double executions must be
        # covered by owner-recorded retries (worker died mid-task), and
        # there must be no spurious duplicates from a stale lease.
        counts: dict = {}
        with open(mark_file) as f:
            for line in f:
                if line.strip():
                    counts[int(line)] = counts.get(int(line), 0) + 1
        dup_execs = sum(c - 1 for c in counts.values() if c > 1)
        rt = ray_tpu._require_runtime()
        retries = sum(
            rec.attempts for rec in rt._tasks.values()
            if rec.spec is not None and rec.spec.name.endswith("marked"))
        missing = len(load_refs) - len(counts)
        assert missing == 0, f"{missing} tasks never executed"
        assert dup_execs <= retries, (
            f"{dup_execs} duplicate executions but only {retries} "
            "owner-side retries: a stale lease double-pushed a task")
        out["envelope100_dup_execs"] = dup_execs
        out["envelope100_task_retries"] = retries
        d = rt._direct
        out["envelope100_leases_lost"] = d.stats["leases_lost"]
        out["envelope100_lease_steals"] = d.stats["lease_steals"]
        out["envelope100_total_s"] = round(_time.perf_counter() - t_start, 1)
    finally:
        autoscaler.stop()
        cluster.shutdown()
    return out


def bench_envelope100(quick: bool, smoke: bool = False) -> dict:
    """Subprocess-isolated 100-node envelope (its fake cluster must not
    touch the bench's own runtime). The smoke variant (gate step) runs
    placement + task plane + ONE seeded kill with autoscaler replacement,
    bounded; the full variant adds the link-modeled broadcast and the
    collective-width barrier."""
    import json as _json
    import subprocess
    import sys

    n_nodes = 100
    managed, kills, bmb, link = ((3, 1, 0, 0.0) if smoke
                                 else (6, 3, 16, 100.0)
                                 if quick else (6, 5, 32, 100.0))
    code = ("import bench, json; "
            f"print('E100_RESULT ' + json.dumps(bench._envelope100_main"
            f"({n_nodes}, {managed}, {kills}, {bmb}, {link}, {smoke})))")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # 100 forge clients add nothing at width-0 CPU nodes; cold spawns on
    # the few worker nodes amortize over the run.
    env["RAY_TPU_WORKER_FORGE_ENABLED"] = "0"
    # Tight-ish death detection so replacement MTTR measures the control
    # loop, not a detection window sized for real WAN heartbeats — but
    # wide enough that 100 GIL-sharing heartbeat threads under task load
    # can't miss the window (a false node death at width poisons the
    # alive-count recovery probe).
    env["RAY_TPU_HEALTH_CHECK_PERIOD_MS"] = "1500"
    env["RAY_TPU_HEALTH_CHECK_FAILURE_THRESHOLD"] = "5"
    env["RAY_TPU_WORKER_LEASE_TIMEOUT_MS"] = "180000"
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True,
                          timeout=300 if smoke else 1200,
                          cwd=os.path.dirname(os.path.abspath(__file__)),
                          env=env)
    for line in (proc.stdout or "").splitlines():
        if line.startswith("E100_RESULT "):
            return _json.loads(line[len("E100_RESULT "):])
    raise RuntimeError(
        f"envelope100 run failed (rc={proc.returncode}): "
        f"{(proc.stderr or '')[-800:]}")


# --------------------------------------------------------------------------- #
# Serve: batched GPT-2 sampler behind HTTP under concurrent load
# --------------------------------------------------------------------------- #


def _pull_micro_main(obj_mb: int, delay_ms: float) -> dict:
    """Raylet-level pull-pipelining microbench (runs in a subprocess):
    one seeded object pulled node-to-node at window=1 (stop-and-wait) vs
    the configured window, with an injected per-chunk-RPC latency, plus a
    no-delay pull measuring raw transfer bandwidth."""
    import time as _time

    import numpy as _np

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.core.ids import ObjectID

    chunk = 1 << 20
    GLOBAL_CONFIG._overrides["object_transfer_chunk_bytes"] = chunk
    # The window/latency arms measure the SOCKET path; on this one-host
    # bench every raylet is same-host, so the sealed-segment attach fast
    # path would silently replace the link under test. Off for the
    # legacy arms, re-enabled for the attach arm below.
    GLOBAL_CONFIG._overrides["object_transfer_same_host_attach"] = False
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    cluster.add_node(num_cpus=1)
    cluster.add_node(num_cpus=1)
    cluster.wait_for_nodes()
    out: dict = {}
    session_suffix = cluster.raylets[0].session_suffix
    try:
        seed, p1, p2 = cluster.raylets
        size = obj_mb << 20

        def seed_obj(tag: int) -> ObjectID:
            oid = ObjectID.from_random()
            payload = _np.random.default_rng(tag).integers(
                0, 255, size=size, dtype=_np.uint8).tobytes()
            seed.store.put_serialized(oid, [payload])
            seed.gcs.call("object_location_add",
                          {"object_id": oid, "node_id": seed.node_id,
                           "size": seed.store.local_size(oid)}, timeout=10)
            return oid

        def pull(raylet, oid, window):
            GLOBAL_CONFIG._overrides["object_transfer_window"] = window
            entry = raylet.gcs.call("object_locations_get",
                                    {"object_id": oid}, timeout=10)
            t0 = _time.perf_counter()
            assert raylet._pull_object_pipelined(oid, entry)
            return _time.perf_counter() - t0

        p1._chunk_fetch_delay_s = delay_ms / 1000.0
        w1 = pull(p1, seed_obj(1), window=1)
        p2._chunk_fetch_delay_s = delay_ms / 1000.0
        w4 = pull(p2, seed_obj(2), window=4)
        p1._chunk_fetch_delay_s = 0.0
        raw = pull(p1, seed_obj(3), window=4)
        out["pull_obj_mb"] = obj_mb
        out["pull_rpc_delay_ms"] = delay_ms
        out["pull_window1_s"] = round(w1, 4)
        out["pull_window4_s"] = round(w4, 4)
        out["pull_pipeline_speedup"] = round(w1 / w4, 3)
        out["pull_raw_gb_s"] = round(size / raw / 1e9, 3)

        # --- same-host sealed-segment attach: the zero-socket handoff.
        # No link model armed on either side, knob on: the pull must
        # adopt the holder's segment (tmpfs hardlink — zero bytes
        # moved), serve zero chunk bytes, leave zero unsealed buffers,
        # and clear 2.0 GB/s.
        GLOBAL_CONFIG._overrides.pop("object_transfer_same_host_attach",
                                     None)
        p2._chunk_fetch_delay_s = 0.0
        served_before = seed._chunk_bytes_served
        attach_s = pull(p2, seed_obj(4), window=4)
        assert p2._attach_hits >= 1, \
            "same-host pull took the socket path, not the attach path"
        assert seed._chunk_bytes_served == served_before, \
            "attach arm served chunk bytes over the socket"
        for r in cluster.raylets:
            assert r.store.stats()["num_unsealed"] == 0
        out["pull_attach_gb_s"] = round(size / attach_s / 1e9, 3)
        out["pull_attach_bytes"] = p2._attach_bytes
        assert out["pull_attach_gb_s"] >= 2.0, \
            f"same-host attach {out['pull_attach_gb_s']} GB/s < 2.0 GB/s"
    finally:
        cluster.shutdown()
    # Zero leaked segments: after shutdown every shm segment of this
    # session (sealed objects AND attach staging) must be unlinked.
    leaked = [n for n in os.listdir("/dev/shm") if session_suffix in n]
    assert not leaked, f"leaked shm segments: {leaked[:5]}"
    out["pull_attach_leaked_segments"] = 0
    return out


def bench_pull_pipelining(quick: bool) -> dict:
    """Subprocess-isolated pull microbench (its fake cluster must not
    touch the bench's own runtime)."""
    import json as _json
    import subprocess
    import sys

    obj_mb, delay_ms = (32, 5.0) if quick else (128, 5.0)
    code = ("import bench, json; "
            f"print('PULL_RESULT ' + json.dumps(bench._pull_micro_main"
            f"({obj_mb}, {delay_ms})))")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.abspath(__file__)),
                          env=env)
    for line in (proc.stdout or "").splitlines():
        if line.startswith("PULL_RESULT "):
            return _json.loads(line[len("PULL_RESULT "):])
    raise RuntimeError(
        f"pull microbench failed (rc={proc.returncode}): "
        f"{(proc.stderr or '')[-500:]}")


def _collective_micro_main(payload_mb: int, world: int,
                           link_mb_s: float) -> dict:
    """Host-collective allreduce bandwidth microbench (runs in a
    subprocess): rank actors pinned one per simulated node, star
    (rendezvous actor, the legacy path) vs ring (`ray_tpu.collective`
    over the transfer plane), under a modeled per-host link bandwidth
    (`raylet._chunk_serve_bw_bps` serializes each node's chunk egress —
    sleeps, not spins, so the modeled network dominates, the regime the
    ring plane targets). The star funnels O(world x bytes) through the
    hub's link; the ring moves 2(W-1)/W x bytes per link."""
    import time as _time

    import numpy as _np

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core.config import GLOBAL_CONFIG

    GLOBAL_CONFIG._overrides.update({
        "object_transfer_chunk_bytes": 2 << 20,
        "object_transfer_refetch_location_chunks": 2,
        "collective_stall_timeout_s": 180.0,
        "rpc_connect_timeout_s": 2.0,
    })
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    for _ in range(world - 1):
        cluster.add_node(num_cpus=1)
    cluster.wait_for_nodes()
    cluster.connect()

    class Rank:
        def __init__(self, rank, world_size, group_name, backend):
            from ray_tpu.util.collective import init_collective_group

            self.group = init_collective_group(
                world_size, rank, group_name=group_name, backend=backend)

        def allreduce_size(self, n_bytes):
            # Payloads are created rank-locally, like real gradients.
            x = _np.full(max(1, n_bytes // 4), float(self.group.rank + 1),
                         dtype=_np.float32)
            t0 = _time.perf_counter()
            self.group.allreduce(x)
            return _time.perf_counter() - t0

    actor_cls = ray_tpu.remote(Rank)
    out: dict = {"collective_payload_mb": payload_mb,
                 "collective_world": world,
                 "collective_link_mb_s": link_mb_s}
    try:
        for backend in ("star", "ring"):
            ranks = [actor_cls.options(num_cpus=1).remote(
                r, world, f"bench_{backend}", backend) for r in range(world)]
            ray_tpu.get([a.allreduce_size.remote(1024) for a in ranks],
                        timeout=120)  # spawn + join outside the timed window
            for raylet in cluster.raylets:
                raylet._chunk_serve_bw_bps = link_mb_s * 1e6
            try:
                t0 = _time.perf_counter()
                ray_tpu.get(
                    [a.allreduce_size.remote(payload_mb << 20)
                     for a in ranks], timeout=600)
                dt = _time.perf_counter() - t0
            finally:
                for raylet in cluster.raylets:
                    raylet._chunk_serve_bw_bps = 0.0
                for a in ranks:
                    ray_tpu.kill(a)
            out[f"collective_{backend}_s"] = round(dt, 3)
            out[f"collective_{backend}_gb_s"] = round(
                (payload_mb << 20) / dt / 1e9, 4)
    finally:
        cluster.shutdown()
    out["collective_ring_speedup"] = round(
        out["collective_star_s"] / out["collective_ring_s"], 3)
    return out


def bench_collective(quick: bool) -> dict:
    """Subprocess-isolated star-vs-ring allreduce bench (its fake cluster
    must not touch the bench's own runtime). Full mode adds a second
    payload/world point."""
    import json as _json
    import subprocess
    import sys

    points = [(64, 4)] if quick else [(64, 4), (8, 2)]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out: dict = {}
    for payload_mb, world in points:
        code = ("import bench, json; "
                f"print('COLL_RESULT ' + json.dumps(bench._collective_micro_main"
                f"({payload_mb}, {world}, 25.0)))")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=900,
                              cwd=os.path.dirname(os.path.abspath(__file__)),
                              env=env)
        point = None
        for line in (proc.stdout or "").splitlines():
            if line.startswith("COLL_RESULT "):
                point = _json.loads(line[len("COLL_RESULT "):])
        if point is None:
            raise RuntimeError(
                f"collective microbench failed (rc={proc.returncode}): "
                f"{(proc.stderr or '')[-500:]}")
        suffix = "" if (payload_mb, world) == points[0] \
            else f"_{payload_mb}mb_w{world}"
        out.update({k + suffix: v for k, v in point.items()})
    return out


async def _read_http_response(reader) -> int:
    """Minimal keep-alive response read (headers + content-length body)
    shared by every lean bench client — one copy of the parsing.
    Returns the status code (the zoo client tells 429 quota rejections
    from served requests; the other clients ignore it)."""
    hdr = await reader.readuntil(b"\r\n\r\n")
    status = int(hdr.split(b" ", 2)[1])
    clen = 0
    for line in hdr.split(b"\r\n"):
        if line[:15].lower() == b"content-length:":
            clen = int(line[15:])
    if clen:
        await reader.readexactly(clen)
    return status


def _lean_http_load(port: int, path: str, n: int, conns: int,
                    body: bytes = b"7") -> float:
    """Closed-loop HTTP load from a lean raw-socket keep-alive client
    (one in-flight request per connection, minimal response parsing).
    Returns requests/s. Deliberately not aiohttp: the client must cost
    less than the server or the bench measures the client."""
    import asyncio as _asyncio

    req = ((f"POST {path} HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body)

    async def run():
        async def worker(count):
            reader, writer = await _asyncio.open_connection("127.0.0.1",
                                                            port)
            try:
                for _ in range(count):
                    writer.write(req)
                    await writer.drain()
                    await _read_http_response(reader)
            finally:
                writer.close()
        t0 = time.perf_counter()
        await _asyncio.gather(*(worker(n // conns) for _ in range(conns)))
        return (n // conns) * conns / (time.perf_counter() - t0)

    return _asyncio.run(run())


def _poisson_http_load(port: int, path: str, rate: float, duration_s: float,
                       conns: int = 32, body: bytes = b"7") -> dict:
    """Open-loop Poisson arrivals at `rate` req/s for `duration_s`:
    arrivals do NOT wait for completions (the millions-of-users shape —
    a slow server accumulates in-flight work instead of throttling the
    offered load). Returns p50/p99 latency and the achieved rate."""
    import asyncio as _asyncio
    import random as _random

    req = ((f"POST {path} HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body)

    async def run():
        pool: _asyncio.Queue = _asyncio.Queue()
        for _ in range(conns):
            pool.put_nowait(await _asyncio.open_connection("127.0.0.1",
                                                           port))
        lats, errors = [], 0

        async def one():
            # The pool slot ALWAYS goes back (a None marks a dead slot
            # re-dialed lazily) — a reconnect failure escaping here would
            # shrink the pool and crash the gather.
            nonlocal errors
            t0 = time.perf_counter()  # latency includes conn-pool wait
            rw = await pool.get()
            if rw is None:
                try:
                    rw = await _asyncio.open_connection("127.0.0.1", port)
                except Exception:  # noqa: BLE001 — server still down
                    errors += 1
                    pool.put_nowait(None)
                    return
            reader, writer = rw
            try:
                writer.write(req)
                await writer.drain()
                await _read_http_response(reader)
                lats.append(time.perf_counter() - t0)
            except Exception:  # noqa: BLE001 — count and replace the conn
                errors += 1
                try:
                    writer.close()
                except Exception:  # noqa: BLE001
                    pass
                try:
                    reader, writer = await _asyncio.open_connection(
                        "127.0.0.1", port)
                except Exception:  # noqa: BLE001 — re-dial next use
                    pool.put_nowait(None)
                    return
            pool.put_nowait((reader, writer))

        # Arrival times drawn up front, launched in due batches: a
        # per-arrival asyncio.sleep() cannot tick faster than ~1k/s under
        # load, which would silently throttle the offered rate.
        rng = _random.Random(0)
        arrivals, t = [], 0.0
        while True:
            t += rng.expovariate(rate)
            if t >= duration_s:
                break
            arrivals.append(t)
        tasks = []
        t0 = time.perf_counter()
        i = 0
        while i < len(arrivals):
            now = time.perf_counter() - t0
            while i < len(arrivals) and arrivals[i] <= now:
                tasks.append(_asyncio.create_task(one()))
                i += 1
            if i < len(arrivals):
                await _asyncio.sleep(
                    max(0.0, arrivals[i] - (time.perf_counter() - t0)))
        await _asyncio.gather(*tasks)
        while not pool.empty():
            _, writer = pool.get_nowait()
            writer.close()
        lats.sort()

        def pct(p):
            return lats[min(len(lats) - 1, int(p * len(lats)))] * 1e3 \
                if lats else None

        return {"p50_ms": pct(0.50), "p99_ms": pct(0.99),
                "achieved_rps": len(lats) / duration_s, "errors": errors}

    return _asyncio.run(run())


def _zoo_poisson_load(port: int, streams: list, duration_s: float,
                      seed: int = 0, conns: int = 8) -> dict:
    """Multi-tenant open-loop load for bench_zoo: every stream draws its
    own Poisson arrivals (diurnally modulated by thinning against the
    peak rate) over a zipf-weighted path set, all merged onto one clock.
    Per-stream connection pools keep client-side queueing of one tenant
    from polluting another's latencies. Returns per-tag {n, p50_ms,
    p99_ms, errors, rejected_429, achieved_rps}."""
    import asyncio as _asyncio
    import math as _math
    import random as _random

    def build_req(path: str) -> bytes:
        body = b"7"
        return ((f"POST {path} HTTP/1.1\r\nHost: bench\r\n"
                 f"Content-Type: application/json\r\n"
                 f"Content-Length: {len(body)}\r\n\r\n").encode() + body)

    rng = _random.Random(seed)
    arrivals = []
    for s in streams:
        rate, diurnal = s["rate"], s.get("diurnal", 0.0)
        period = s.get("period", duration_s)
        phase = s.get("phase", 0.0)
        peak = rate * (1.0 + diurnal)
        reqs = [build_req(p) for p in s["paths"]]
        weights = s["weights"]
        t = 0.0
        while True:
            t += rng.expovariate(peak)
            if t >= duration_s:
                break
            if diurnal:
                cur = rate * (1.0 + diurnal * _math.sin(
                    2 * _math.pi * t / period + phase))
                if rng.random() * peak > max(cur, 0.0):
                    continue  # thinned away: the diurnal trough
            i = rng.choices(range(len(reqs)), weights=weights)[0]
            arrivals.append((t, s["tag"], reqs[i]))
    arrivals.sort(key=lambda a: a[0])
    stats = {s["tag"]: {"lats": [], "errors": 0, "rejected_429": 0, "n": 0}
             for s in streams}

    async def run():
        pools = {}
        for s in streams:
            pool: _asyncio.Queue = _asyncio.Queue()
            for _ in range(conns):
                pool.put_nowait(await _asyncio.open_connection(
                    "127.0.0.1", port))
            pools[s["tag"]] = pool

        async def one(tag: str, req: bytes):
            st = stats[tag]
            st["n"] += 1
            pool = pools[tag]
            t0 = time.perf_counter()  # includes conn-pool wait
            rw = await pool.get()
            if rw is None:
                try:
                    rw = await _asyncio.open_connection("127.0.0.1", port)
                except Exception:  # noqa: BLE001 — server still down
                    st["errors"] += 1
                    pool.put_nowait(None)
                    return
            reader, writer = rw
            try:
                writer.write(req)
                await writer.drain()
                status = await _read_http_response(reader)
                if status == 429:
                    st["rejected_429"] += 1
                elif status >= 400:
                    st["errors"] += 1
                else:
                    st["lats"].append(time.perf_counter() - t0)
            except Exception:  # noqa: BLE001 — count, replace the conn
                st["errors"] += 1
                try:
                    writer.close()
                except Exception:  # noqa: BLE001
                    pass
                try:
                    reader, writer = await _asyncio.open_connection(
                        "127.0.0.1", port)
                except Exception:  # noqa: BLE001 — re-dial next use
                    pool.put_nowait(None)
                    return
            pool.put_nowait((reader, writer))

        tasks = []
        t0 = time.perf_counter()
        i = 0
        while i < len(arrivals):
            now = time.perf_counter() - t0
            while i < len(arrivals) and arrivals[i][0] <= now:
                tasks.append(_asyncio.create_task(
                    one(arrivals[i][1], arrivals[i][2])))
                i += 1
            if i < len(arrivals):
                await _asyncio.sleep(max(
                    0.0, arrivals[i][0] - (time.perf_counter() - t0)))
        await _asyncio.gather(*tasks)
        for pool in pools.values():
            while not pool.empty():
                rw = pool.get_nowait()
                if rw is not None:
                    rw[1].close()

    _asyncio.run(run())
    out = {}
    for tag, st in stats.items():
        lats = sorted(st["lats"])

        def pct(p, lats=lats):
            return round(lats[min(len(lats) - 1, int(p * len(lats)))]
                         * 1e3, 2) if lats else None

        out[tag] = {"n": st["n"], "p50_ms": pct(0.50), "p99_ms": pct(0.99),
                    "errors": st["errors"],
                    "rejected_429": st["rejected_429"],
                    "achieved_rps": round(len(lats) / duration_s, 1)}
    return out


def bench_zoo(quick: bool) -> dict:
    """Model-zoo multi-tenancy acceptance (ISSUE 11 / ROADMAP 3): a
    mostly-parked zoo of deployments under per-tenant QoS — zipf
    popularity, Poisson diurnal arrivals per tenant, per-tier p99
    budgets, an isolation A/B proving a quota-saturating tenant cannot
    move a victim tenant's p99 past budget, controller reconcile cost
    sublinear in parked deployments, and the multiplexed-LLM compile
    proof (zero new XLA programs)."""
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.controller import CONTROLLER_NAME, SERVE_NAMESPACE

    out: dict = {}
    n_dep = 60 if quick else 200
    duration = 8.0 if quick else 16.0
    tiers = ("gold", "silver", "bronze")
    serve.register_tenant("gold", tier="gold")
    serve.register_tenant("silver", tier="silver")
    serve.register_tenant("bronze", tier="bronze")
    # The attacker: a quota'd bronze tenant that will offer many times
    # its allowance. Its over-quota excess must die as cheap 429s.
    serve.register_tenant("attacker", tier="bronze", rps_limit=20,
                          burst=20, max_inflight=8)

    @serve.deployment
    class ZooEcho:
        def __call__(self, payload):
            return payload

    def _reconcile_stats():
        c = ray_tpu.get_actor(CONTROLLER_NAME, namespace=SERVE_NAMESPACE)
        return ray_tpu.get(c.reconcile_stats.remote(), timeout=10)

    def _median_tick_ms(samples=8):
        vals = []
        for _ in range(samples):
            vals.append(_reconcile_stats()["last_tick_ms"])
            time.sleep(0.12)
        return sorted(vals)[len(vals) // 2]

    try:
        # Reconciler cost before the zoo exists (near-empty controller).
        serve.run(ZooEcho.options(name="zoo_warm").bind())
        tick_small = _median_tick_ms()

        t0 = time.perf_counter()
        for i in range(n_dep):
            serve.run(ZooEcho.options(
                name=f"zoo{i:03d}", tenant=tiers[i % 3],
                max_concurrent_queries=32,
                autoscaling_config=serve.AutoscalingConfig(
                    min_replicas=0, max_replicas=1, upscale_delay_s=0.2,
                    downscale_delay_s=5.0)).bind())
        out["zoo_deployments"] = n_dep
        out["zoo_deploy_s"] = round(time.perf_counter() - t0, 2)
        serve.run(ZooEcho.options(
            name="zoo_attacked", tenant="attacker",
            max_concurrent_queries=32,
            autoscaling_config=serve.AutoscalingConfig(
                min_replicas=0, max_replicas=1,
                downscale_delay_s=30.0)).bind())

        # Reconciler cost with the zoo parked: the sublinearity proof.
        time.sleep(1.0)
        tick_parked = _median_tick_ms()
        st = _reconcile_stats()
        out["zoo_reconcile_tick_ms_small"] = tick_small
        out["zoo_reconcile_tick_ms_parked"] = tick_parked
        out["zoo_reconcile_last_scanned"] = st["last_scanned"]
        out["zoo_reconcile_parked_skipped"] = st["last_parked_skipped"]
        # Sublinear: the zoo multiplied deployments ~100x (2 -> 200);
        # the tick may not grow anywhere near that (10x is the soft
        # ceiling — the sandbox's ambient noise dwarfs both numbers).
        out["zoo_reconcile_sublinear"] = \
            tick_parked <= max(10 * max(tick_small, 0.05), 5.0)

        port = serve.http_port()

        # Zipf popularity over each tier's deployments: the head stays
        # warm, the tail stays parked and pays a cold start when the
        # diurnal peak reaches it.
        def tier_paths(tier_idx, top=8):
            names = [f"/zoo{i:03d}" for i in range(n_dep)
                     if i % 3 == tier_idx]
            names = names[:top]
            weights = [1.0 / (k + 1) ** 1.1 for k in range(len(names))]
            return names, weights

        def tier_stream(tag, tier_idx, rate, phase):
            paths, weights = tier_paths(tier_idx)
            return {"tag": tag, "paths": paths, "weights": weights,
                    "rate": rate, "diurnal": 0.6, "period": duration,
                    "phase": phase}

        base_streams = [
            tier_stream("gold", 0, 25.0, 0.0),
            tier_stream("silver", 1, 15.0, 2.1),
            tier_stream("bronze", 2, 8.0, 4.2),
        ]
        # Warm each tier's most popular deployment so the A/B compares
        # steady traffic, not three simultaneous first-ever cold starts.
        for s in base_streams:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}{s['paths'][0]}", data=b"7",
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=60).read()

        # Phase A: the three tiers alone.
        res_a = _zoo_poisson_load(port, base_streams, duration, seed=1)
        # Phase B: same tiers + the attacker offering 8x its 20 rps
        # quota against its own deployment.
        attacker = {"tag": "attacker", "paths": ["/zoo_attacked"],
                    "weights": [1.0], "rate": 160.0}
        res_b = _zoo_poisson_load(port, base_streams + [attacker],
                                  duration, seed=2)

        for tier in ("gold", "silver", "bronze"):
            out[f"zoo_{tier}_p50_ms"] = res_b[tier]["p50_ms"]
            out[f"zoo_{tier}_p99_ms"] = res_b[tier]["p99_ms"]
            out[f"zoo_{tier}_errors"] = res_b[tier]["errors"]
        out["zoo_attacker_offered"] = res_b["attacker"]["n"]
        out["zoo_attacker_429"] = res_b["attacker"]["rejected_429"]
        out["zoo_attacker_429_rate"] = round(
            res_b["attacker"]["rejected_429"]
            / max(1, res_b["attacker"]["n"]), 3)

        # Per-tier p99 budgets (sandbox-calibrated: 2 CPU-throttled
        # cores, cold starts in the tail) — soft flags, like
        # serve_scaleup_regressed.
        budgets = {"gold": 750.0, "silver": 1250.0, "bronze": 2500.0}
        held = all(res_b[t]["p99_ms"] is not None
                   and res_b[t]["p99_ms"] <= budgets[t] for t in budgets)
        out["zoo_tier_budgets_held"] = held
        if not held:
            print(f"WARNING: zoo tier p99 budgets missed: "
                  f"{ {t: res_b[t]['p99_ms'] for t in budgets} }",
                  file=sys.stderr)

        # Isolation A/B: the victim (gold) tier's p99 with the attacker
        # saturating its quota vs without. Acceptance: shift < 20%.
        a99, b99 = res_a["gold"]["p99_ms"], res_b["gold"]["p99_ms"]
        if a99 and b99:
            shift = (b99 - a99) / a99 * 100.0
            out["zoo_isolation_victim_p99_a_ms"] = a99
            out["zoo_isolation_victim_p99_b_ms"] = b99
            out["zoo_isolation_p99_shift_pct"] = round(shift, 1)
            out["zoo_isolation_regressed"] = shift >= 20.0
            if shift >= 20.0:
                print(f"WARNING: attacker moved the victim's p99 by "
                      f"{shift:.0f}% (budget < 20%)", file=sys.stderr)

        # Cold-start sample off a far-tail parked deployment.
        t0 = time.perf_counter()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/zoo{n_dep - 1:03d}", data=b"7",
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=60).read()
        out["zoo_coldstart_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)

        # Multiplexed-LLM compile proof: several adapters on one
        # replica, one paged arena, and EXACTLY the PR-3 program count.
        from ray_tpu.inference import LLMServer

        adapters = {f"m{k}": {"seed": 100 + k, "rank": 8}
                    for k in range(4)}
        llm = serve.run(LLMServer.options(
            name="zoo_llm", num_replicas=1, tenant="gold",
            max_concurrent_queries=16).bind("tiny", 256, 8, None,
                                            adapters))
        for k in range(4):
            ray_tpu.get(llm.generate.remote(
                {"ids": [1, 2, 3], "max_new_tokens": 4,
                 "model_id": f"m{k}"}), timeout=120)
        m = ray_tpu.get(llm.metrics.remote(None), timeout=30)
        out["zoo_mux_adapters_resident"] = len(
            m["adapters"]["resident"])
        out["zoo_mux_adapter_loads"] = m["adapters"]["loads"]
        out["zoo_mux_prefill_compiles"] = m["prefill_compiles"]
        out["zoo_mux_decode_compiles"] = m["decode_compiles"]
        out["zoo_mux_zero_new_programs"] = (
            m["prefill_compiles"] == 1 and m["decode_compiles"] == 1)
        out["zoo_mux_leaked_blocks"] = m["kv"]["blocks_in_use"]
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001 — teardown is best effort
            pass
    return out


def bench_serve_fastpath(quick: bool) -> dict:
    """Serve fast data plane (ISSUE 8): closed-loop proxy capacity,
    Poisson open-loop latency, the zero-pickle/zero-leak proofs, and the
    scale-to-zero cold-start round trip."""
    import json as _json
    import urllib.request

    import ray_tpu
    from ray_tpu import serve

    out: dict = {}

    # Normalization anchor: same-run trivial-task throughput (the sandbox
    # is CPU-shares-throttled with high ambient variance — serve numbers
    # are only comparable across rounds relative to this).
    @ray_tpu.remote
    def _noop():
        return None

    n_norm = 150 if quick else 400
    ray_tpu.get([_noop.remote() for _ in range(32)])
    t0 = time.perf_counter()
    ray_tpu.get([_noop.remote() for _ in range(n_norm)])
    out["serve_fastpath_tasks_per_s"] = round(
        n_norm / (time.perf_counter() - t0), 1)

    @serve.deployment(num_replicas=2, max_concurrent_queries=64)
    class Echo:
        def __call__(self, payload):
            return payload

    serve.run(Echo.bind())
    try:
        port = serve.http_port()
        proxy = ray_tpu.get_actor("SERVE_PROXY", namespace="serve")
        c0 = ray_tpu.get(proxy.counters.remote())
        _lean_http_load(port, "/Echo", 256, 16)  # warm
        n = 1500 if quick else 6400
        out["serve_proxy_rps"] = round(
            _lean_http_load(port, "/Echo", n, 64), 1)
        c1 = ray_tpu.get(proxy.counters.remote())
        raw = c1["raw_requests"] - c0["raw_requests"]
        frames = c1["raw_frames"] - c0["raw_frames"]
        # Zero-copy proof: every request since c0 rode raw frames; none
        # fell back to the pickle lanes.
        out["serve_fastpath_pickle_free"] = bool(
            raw >= n and c1["fallback_requests"] == c0["fallback_requests"])
        out["serve_fastpath_reqs_per_frame"] = round(raw / max(frames, 1), 2)

        # Open-loop Poisson at ~60% of measured capacity: the latency
        # distribution under sustained arrivals.
        rate = max(100.0, 0.6 * out["serve_proxy_rps"])
        res = _poisson_http_load(port, "/Echo", rate,
                                 4.0 if quick else 10.0)
        out["serve_poisson_offered_rps"] = round(rate, 1)
        out["serve_poisson_achieved_rps"] = round(res["achieved_rps"], 1)
        out["serve_poisson_p50_ms"] = round(res["p50_ms"], 2) \
            if res["p50_ms"] is not None else None
        out["serve_poisson_p99_ms"] = round(res["p99_ms"], 2) \
            if res["p99_ms"] is not None else None
        out["serve_poisson_errors"] = res["errors"]
    finally:
        serve.delete("Echo")

    # Scale-to-zero: deploys parked (0 replicas); the first request wakes
    # the controller, cold-starts a replica through the forge, and is
    # served from the proxy's park buffer.
    @serve.deployment(
        max_concurrent_queries=16,
        autoscaling_config=serve.AutoscalingConfig(
            min_replicas=0, max_replicas=1, upscale_delay_s=0.1,
            downscale_delay_s=1.0))
    class ColdEcho:
        def __call__(self, payload):
            return payload

    serve.run(ColdEcho.bind())
    try:
        port = serve.http_port()
        st = serve.status().get("ColdEcho", {})
        assert st.get("target") == 0 and not st.get("replicas"), \
            f"scale-to-zero deployment did not park: {st}"
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/ColdEcho",
            data=_json.dumps({"cold": 1}).encode(),
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
            resp.read()
        out["serve_coldstart_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        st = serve.status().get("ColdEcho", {})
        out["serve_coldstart_controller_ms"] = st.get("cold_start_ms")
        # Soft regression flag (same convention as serve_scaleup_regressed;
        # ROADMAP item-3 leftover): the tier-1 acceptance bound is 500ms
        # against a 60-90ms steady state — flag, don't fail, the sandbox's
        # ambient variance is high.
        out["serve_coldstart_regressed"] = \
            out["serve_coldstart_ms"] > 500.0
        if out["serve_coldstart_regressed"]:
            print(f"WARNING: serve_coldstart_ms "
                  f"{out['serve_coldstart_ms']} exceeds the 500ms soft "
                  "budget", file=sys.stderr)
    finally:
        serve.delete("ColdEcho")
        serve.shutdown()

    # Zero leaked raw buffers: the raw frame lane never touches the
    # store, and nothing else on the serve path may leak unsealed
    # segments either.
    try:
        out["serve_store_unsealed_after"] = \
            ray_tpu._global_node.raylet.store.stats()["num_unsealed"]
    except Exception:  # noqa: BLE001 — store introspection is best effort
        pass
    return out


def bench_serve(quick: bool) -> dict:
    import concurrent.futures
    import json as _json
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.examples import GPT2Sampler

    out = {}
    # Framework overhead first: a trivial echo deployment measures the
    # router/proxy path itself (the GPT-2 numbers below measure the model).
    @serve.deployment(num_replicas=2, max_concurrent_queries=64)
    class Echo:
        def __call__(self, payload):
            return payload

    echo = serve.run(Echo.bind())
    try:
        n_echo = 200 if quick else 2000
        ray_tpu.get([echo.remote(i) for i in range(16)])
        t0 = time.perf_counter()
        ray_tpu.get([echo.remote(i) for i in range(n_echo)])
        out["serve_echo_rps"] = n_echo / (time.perf_counter() - t0)

        port = serve.http_port()

        n_http_echo = 500 if quick else 4000
        # Lean keep-alive client (raw sockets, minimal parsing): measures
        # the serving stack's capacity, not the client library's own CPU
        # — an aiohttp client saturates its half of the sandbox's two
        # cores around ~3.7k rps and would cap the number.
        _lean_http_load(port, "/Echo", 128, 16)  # warm route + conns
        out["serve_echo_http_rps"] = round(
            _lean_http_load(port, "/Echo", n_http_echo, 64), 1)

        # Replica scale-up latency: redeploy at +N replicas and time until
        # every new replica is RUNNING. Each replica is an actor, so this
        # is the serving-facing view of worker spawn latency — replica
        # cold-start regressions (forge loss, import creep) surface here.
        scale_n = 2 if quick else 6
        t0 = time.perf_counter()
        serve.run(Echo.options(num_replicas=2 + scale_n).bind())
        out["serve_scaleup_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        out["serve_scaleup_replicas"] = scale_n
        # Soft regression flag vs the PR-5 forge numbers (~90-170ms spawn
        # + promotion per replica): flag, don't fail — the sandbox's
        # ambient variance is high.
        out["serve_scaleup_regressed"] = \
            out["serve_scaleup_ms"] / max(scale_n, 1) > 800.0
    finally:
        serve.delete("Echo")

    n_requests = 32 if quick else 128
    # The sampler replica runs its jitted decode on the chip when one is
    # advertised (replicas without a TPU grant are pinned to CPU jax).
    sampler_opts = {"num_replicas": 1, "max_concurrent_queries": 64}
    if _has_tpu():
        sampler_opts["ray_actor_options"] = {"num_tpus": 1}
    handle = serve.run(GPT2Sampler.options(**sampler_opts).bind("tiny", 128, 8))
    try:
        # Warm the jit cache.
        ray_tpu.get(handle.remote({"ids": [1, 2, 3], "max_new_tokens": 2}))

        t0 = time.perf_counter()
        refs = [handle.remote({"ids": [1, 2, 3 + (i % 50)],
                               "max_new_tokens": 8})
                for i in range(n_requests)]
        ray_tpu.get(refs)
        handle_dt = time.perf_counter() - t0

        port = serve.http_port()
        url = f"http://127.0.0.1:{port}/GPT2Sampler"

        def one(i: int):
            req = urllib.request.Request(
                url, data=_json.dumps(
                    {"ids": [1, 2, 3 + (i % 50)],
                     "max_new_tokens": 8}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                return _json.loads(resp.read())

        n_http = min(n_requests, 64)
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(16) as pool:
            list(pool.map(one, range(n_http)))
        http_dt = time.perf_counter() - t0

        metrics = ray_tpu.get(handle.metrics.remote(None))
        out.update({
            "serve_handle_rps": n_requests / handle_dt,
            "serve_http_rps": n_http / http_dt,
            "serve_mean_batch_size": metrics["mean_batch_size"],
        })
        return out
    finally:
        serve.shutdown()


def _inference_poisson_run(quick: bool, model=None, params=None,
                           seed: int = 0) -> dict:
    """One Poisson-arrival serving run through the continuous-batching
    engine."""
    import random as _random
    import threading as _threading

    from ray_tpu.inference import EngineConfig, EngineLoop, InferenceEngine

    rng = _random.Random(seed)
    n = 16 if quick else 48
    rate = 100.0 if quick else 60.0          # arrivals per second
    budgets_menu = [4, 8, 16, 32]
    arrivals, t = [], 0.0
    for _ in range(n):
        t += rng.expovariate(rate)
        arrivals.append(t)
    prompts = [[rng.randrange(1, 500)
                for _ in range(rng.randrange(4, 24))] for _ in range(n)]
    budgets = [rng.choice(budgets_menu) for _ in range(n)]

    cfg = EngineConfig(batch_slots=4, block_size=16, num_blocks=48,
                       max_blocks_per_seq=8, prefill_chunk=16)
    engine = InferenceEngine(cfg, model=model, params=params)
    # Warm both step programs (one XLA compile each) off the clock.
    engine.add_request([1, 2, 3], 2, request_id="warmup")
    engine.run_until_idle()
    loop = EngineLoop(engine)
    done = _threading.Event()
    remaining = [n]
    lock = _threading.Lock()

    def on_finish(_req):
        with lock:
            remaining[0] -= 1
            if remaining[0] == 0:
                done.set()

    reqs = []
    t0 = time.monotonic()
    try:
        for i in range(n):
            delay = (t0 + arrivals[i]) - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            reqs.append(loop.submit(prompts[i], budgets[i],
                                    on_finish=on_finish,
                                    request_id=f"q{i}"))
        if not done.wait(timeout=600):
            raise TimeoutError(
                f"{remaining[0]} requests unfinished "
                f"({scheduling} scheduling)")
    finally:
        loop.stop()

    ttft = sorted((r.first_token_at - r.submitted_at) for r in reqs)
    tpot = sorted((r.finished_at - r.first_token_at)
                  / max(1, len(r.generated) - 1) for r in reqs)
    makespan = max(r.finished_at for r in reqs) - t0
    total_tokens = sum(len(r.generated) for r in reqs)

    def pct(sorted_vals, p):
        return sorted_vals[min(len(sorted_vals) - 1,
                               int(p * len(sorted_vals)))]

    stats = engine.stats()
    engine.check_no_leaks()
    return {
        "requests": n,
        "tokens_per_sec": total_tokens / makespan,
        "ttft_p50_ms": pct(ttft, 0.50) * 1e3,
        "ttft_p99_ms": pct(ttft, 0.99) * 1e3,
        "tpot_p50_ms": pct(tpot, 0.50) * 1e3,
        "tpot_p99_ms": pct(tpot, 0.99) * 1e3,
        "preemptions": stats["preemptions"],
        "leaked_blocks": stats["kv"]["blocks_in_use"],
        "peak_blocks": stats["kv"]["peak_blocks_in_use"],
        "decode_recompiles": max(0, stats["decode_compiles"] - 1),
        "prefill_recompiles": max(0, stats["prefill_compiles"] - 1),
    }


def _inference_multitenant_run(prefix_cache: bool, quick: bool, model=None,
                               params=None, seed: int = 0) -> dict:
    """Shared-prefix multi-tenant Poisson trace: three tenants, each
    with a 24-token system prefix shared by every one of its requests,
    mixed interactive/batch SLO classes (one reserved interactive
    slot). Run twice — prefix cache off, then on — over the SAME seeded
    trace: the delta is pure radix-cache effect (hit rate, tokens/s,
    per-class TTFT), with the compile-once and zero-leak invariants
    checked on both sides."""
    import random as _random
    import threading as _threading

    from ray_tpu.inference import EngineConfig, EngineLoop, InferenceEngine

    rng = _random.Random(seed)
    n = 18 if quick else 48
    # Arrivals outpace prefill on purpose: a 96-token tenant prefix is
    # 6 prefill chunks of work per request, so the uncached arm is
    # prefill-bound and a queue builds — that is where both the cache
    # (skip 6 chunks on a hit) and the SLO classes (admission order
    # under backlog) become visible in end-to-end numbers.
    rate = 300.0
    prefixes = [[rng.randrange(1, 500) for _ in range(96)]
                for _ in range(3)]
    reqspec, t = [], 0.0
    for i in range(n):
        t += rng.expovariate(rate)
        suffix = [rng.randrange(1, 500)
                  for _ in range(rng.randrange(4, 13))]
        # Bulk batch-class traffic with an interactive sprinkle (the
        # first two requests force one of each so the percentiles are
        # always defined on a quick trace).
        slo = ("interactive" if i == 0
               else "batch" if i == 1
               else "interactive" if rng.random() < 0.3 else "batch")
        reqspec.append((t, prefixes[rng.randrange(3)] + suffix,
                        rng.choice([4, 8]), slo))

    cfg = EngineConfig(batch_slots=4, block_size=16, num_blocks=64,
                       max_blocks_per_seq=8, prefill_chunk=16,
                       prefix_cache_enabled=prefix_cache,
                       slo_interactive_reserved_slots=1)
    engine = InferenceEngine(cfg, model=model, params=params)
    # Warm both step programs off the clock; both arms start cache-cold.
    engine.add_request([1, 2, 3], 2, request_id="warmup")
    engine.run_until_idle()
    engine.drop_prefix_cache()
    loop = EngineLoop(engine)
    done = _threading.Event()
    remaining = [n]
    lock = _threading.Lock()

    def on_finish(_req):
        with lock:
            remaining[0] -= 1
            if remaining[0] == 0:
                done.set()

    reqs = []
    t0 = time.monotonic()
    try:
        for i, (at, prompt, budget, slo) in enumerate(reqspec):
            delay = (t0 + at) - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            reqs.append(loop.submit(prompt, budget, on_finish=on_finish,
                                    request_id=f"mt{i}", slo_class=slo))
        if not done.wait(timeout=600):
            raise TimeoutError(f"{remaining[0]} multi-tenant requests "
                               f"unfinished (prefix_cache={prefix_cache})")
    finally:
        loop.stop()

    def pct_ms(vals, p):
        vals = sorted(vals)
        if not vals:
            return 0.0
        return vals[min(len(vals) - 1, int(p * len(vals)))] * 1e3

    makespan = max(r.finished_at for r in reqs) - t0
    ttft = {cls: [r.first_token_at - r.submitted_at for r in reqs
                  if r.slo_class == cls]
            for cls in ("interactive", "batch")}
    stats = engine.stats()
    engine.check_no_leaks()
    engine.drop_prefix_cache()
    pc = stats["prefix_cache"]
    return {
        "requests": n,
        "tokens_per_sec": sum(len(r.generated) for r in reqs) / makespan,
        "ttft_interactive_p50_ms": pct_ms(ttft["interactive"], 0.50),
        "ttft_interactive_p99_ms": pct_ms(ttft["interactive"], 0.99),
        "ttft_batch_p50_ms": pct_ms(ttft["batch"], 0.50),
        "ttft_batch_p99_ms": pct_ms(ttft["batch"], 0.99),
        "prefix_hit_rate": round(pc.get("hit_rate", 0.0), 3),
        "prefix_hit_tokens": pc.get("hit_tokens", 0),
        "cached_tokens": sum(r.cached_tokens for r in reqs),
        "preemptions": stats["preemptions"],
        "leaked_blocks": engine.stats()["kv"]["blocks_in_use"],
        "decode_recompiles": max(0, stats["decode_compiles"] - 1),
        "prefill_recompiles": max(0, stats["prefill_compiles"] - 1),
    }


def _inference_spec_run(k: int, quick: bool, model=None, params=None,
                        target_as_draft: bool = False,
                        seed: int = 0) -> dict:
    """Speculative-decoding accounting run: a fixed seeded request set,
    reporting the accepted-draft-length distribution and verify-round
    economics. `target_as_draft=True` runs the target as its own draft —
    the acceptance UPPER BOUND (every proposal accepted, n tokens in
    ceil(n/(k+1)) target passes); the default is the built-in
    truncated-target draft, whose acceptance is honest for the current
    weights (near zero on random init, climbing with trained ones)."""
    import random as _random

    from ray_tpu.inference import EngineConfig, InferenceEngine

    rng = _random.Random(seed)
    cfg = EngineConfig(batch_slots=2, block_size=16, num_blocks=32,
                       max_blocks_per_seq=8, prefill_chunk=16,
                       spec_decode_draft_len=k)
    kwargs = ({"draft_model": model, "draft_params": params}
              if target_as_draft else {})
    engine = InferenceEngine(cfg, model=model, params=params, **kwargs)
    n = 4 if quick else 8
    for i in range(n):
        prompt = [rng.randrange(1, 500)
                  for _ in range(rng.randrange(4, 12))]
        engine.add_request(prompt, 16, request_id=f"sp{i}")
    engine.run_until_idle()
    stats = engine.stats()
    sd = stats["spec_decode"]
    engine.check_no_leaks()
    engine.drop_prefix_cache()
    return {
        "draft_len": k,
        "rounds": sd["rounds"],
        "accept_rate": round(sd["accept_rate"], 3),
        "mean_accepted": round(sd["mean_accepted"], 3),
        "accepted_hist": sd["accepted_hist"],
        "tokens_emitted": stats["tokens_emitted"],
        "leaked_blocks": engine.stats()["kv"]["blocks_in_use"],
        "draft_prefill_recompiles": max(
            0, sd["draft_prefill_compiles"] - 1),
        "propose_recompiles": max(0, sd["propose_compiles"] - 1),
        "verify_recompiles": max(0, sd["verify_compiles"] - 1),
    }


def bench_inference(quick: bool, smoke: bool = False) -> dict:
    """Inference engine bench, round 3. Legs: (1) continuous batching
    under Poisson arrivals; (2) radix
    prefix cache A/B over the same shared-prefix multi-tenant trace with
    per-SLO-class TTFT; (3) speculative-decoding accepted-draft-length
    distributions (honest truncated draft + target-as-draft upper
    bound); plus a same-run trivial-task throughput anchor so tokens/s
    is comparable across rounds on this CPU-shares-throttled sandbox.
    smoke=True runs only legs 2+3 quick and HARD-asserts the invariants
    (zero recompiles anywhere, zero leaked blocks, a real hit rate)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama, LlamaConfig

    mcfg = LlamaConfig.tiny(seq=256)
    model = Llama(mcfg)
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))()

    out = {}
    if not smoke:
        cont = _inference_poisson_run(quick, model=model, params=params)
        out.update({f"inference_cont_{k}": v for k, v in cont.items()})

    # ---- radix prefix cache A/B on the same shared-prefix trace
    cold = _inference_multitenant_run(False, quick or smoke, model=model,
                                      params=params)
    warm = _inference_multitenant_run(True, quick or smoke, model=model,
                                      params=params)
    out.update({f"inference_uncached_{k}": v for k, v in cold.items()})
    out.update({f"inference_cached_{k}": v for k, v in warm.items()})
    out["inference_cache_hit_rate"] = warm["prefix_hit_rate"]
    out["inference_cache_tokens_per_s_speedup"] = round(
        warm["tokens_per_sec"] / max(cold["tokens_per_sec"], 1e-9), 3)
    # Acceptance: interactive TTFT holds under batch-class bulk load.
    out["inference_slo_interactive_p99_holds"] = bool(
        warm["ttft_interactive_p99_ms"] <= warm["ttft_batch_p99_ms"])
    # Soft regression flag (mirrors tasks_per_s_regressed): the cached
    # arm must beat the uncached arm on its own trace — same run, same
    # seed, so ambient sandbox noise largely cancels.
    out["inference_tokens_per_s_regressed"] = bool(
        warm["tokens_per_sec"] <= cold["tokens_per_sec"])
    if out["inference_tokens_per_s_regressed"]:
        print("WARNING: cached-path tokens/s "
              f"{warm['tokens_per_sec']:.1f} <= uncached "
              f"{cold['tokens_per_sec']:.1f} on the same trace "
              "(soft flag)", file=sys.stderr)

    # ---- speculative decoding: accepted-draft-length distribution
    spec = _inference_spec_run(4, quick or smoke, model=model,
                               params=params)
    spec_ub = _inference_spec_run(4, quick or smoke, model=model,
                                  params=params, target_as_draft=True)
    out.update({f"inference_spec_{k}": v for k, v in spec.items()})
    out.update({f"inference_spec_ub_{k}": v for k, v in spec_ub.items()})

    # ---- same-run task-throughput anchor (bench normalization)
    import ray_tpu

    started = False
    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=4)
        started = True

    @ray_tpu.remote
    def _noop():
        return None

    n_norm = 150 if (quick or smoke) else 400
    ray_tpu.get([_noop.remote() for _ in range(32)])
    t0 = time.perf_counter()
    ray_tpu.get([_noop.remote() for _ in range(n_norm)])
    out["inference_tasks_per_s_anchor"] = round(
        n_norm / (time.perf_counter() - t0), 1)
    out["inference_tokens_per_tasknorm"] = round(
        warm["tokens_per_sec"]
        / max(out["inference_tasks_per_s_anchor"], 1e-9), 4)
    if started and smoke:
        ray_tpu.shutdown()

    if smoke:
        for label, run in (("uncached", cold), ("cached", warm)):
            assert run["decode_recompiles"] == 0, (label, run)
            assert run["prefill_recompiles"] == 0, (label, run)
            assert run["leaked_blocks"] == 0, (label, run)
        assert warm["prefix_hit_rate"] > 0.0, warm
        for label, run in (("spec", spec), ("spec_ub", spec_ub)):
            assert run["leaked_blocks"] == 0, (label, run)
            assert run["draft_prefill_recompiles"] == 0, (label, run)
            assert run["propose_recompiles"] == 0, (label, run)
            assert run["verify_recompiles"] == 0, (label, run)
        assert spec_ub["accept_rate"] == 1.0, spec_ub
        out["inference_smoke_ok"] = True
    return out


def bench_tracing(quick: bool) -> dict:
    """Tracing-plane overhead: tier-1-class task throughput and serve
    echo RPS with tracing OFF vs ON (sampling 1.0). `tracing_overhead_pct`
    is the regression gate for span additions on the hot path — the
    disabled path must stay guard-check-only (off-vs-off run-to-run noise
    bounds what "unmeasurable" means on this sandbox), and the enabled
    path cheap enough to leave on in benches. A-B-A ordering (off, on,
    off) so ambient drift shows up as disagreement between the two
    baselines instead of being billed to tracing."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.observability import tracing as _tracing

    n_tasks = 300 if quick else 2000
    n_echo = 100 if quick else 1000

    def _clear_overrides():
        GLOBAL_CONFIG._overrides.pop("tracing_enabled", None)
        GLOBAL_CONFIG._overrides.pop("trace_sample_rate", None)
        _tracing.refresh_from_config()

    def run_once(enabled: bool) -> dict:
        ray_tpu.shutdown()
        _clear_overrides()
        sc = {"tracing_enabled": True, "trace_sample_rate": 1.0} \
            if enabled else None
        ray_tpu.init(num_cpus=4, _system_config=sc)

        @ray_tpu.remote
        def noop():
            return None

        ray_tpu.get([noop.remote() for _ in range(32)])  # warm pool/leases
        t0 = time.perf_counter()
        ray_tpu.get([noop.remote() for _ in range(n_tasks)])
        tps = n_tasks / (time.perf_counter() - t0)

        @serve.deployment(num_replicas=1, max_concurrent_queries=64)
        class TraceEcho:
            def __call__(self, payload):
                return payload

        handle = serve.run(TraceEcho.bind())
        ray_tpu.get([handle.remote(i) for i in range(16)])
        t0 = time.perf_counter()
        ray_tpu.get([handle.remote(i) for i in range(n_echo)])
        rps = n_echo / (time.perf_counter() - t0)
        # Full serve teardown (not delete): the process-global router must
        # not survive into the next off/on cluster of this A-B-A run.
        serve.shutdown()
        ray_tpu.shutdown()
        _clear_overrides()
        return {"tasks": tps, "rps": rps}

    off_a = run_once(False)
    on = run_once(True)
    off_b = run_once(False)
    base_tasks = max(off_a["tasks"], off_b["tasks"])
    base_rps = max(off_a["rps"], off_b["rps"])
    out = {
        "tasks_per_s_tracing_off": round(base_tasks, 1),
        "tasks_per_s_tracing_on": round(on["tasks"], 1),
        "serve_echo_rps_tracing_off": round(base_rps, 1),
        "serve_echo_rps_tracing_on": round(on["rps"], 1),
        "tracing_off_noise_pct": round(
            abs(off_a["tasks"] - off_b["tasks"])
            / max(off_a["tasks"], off_b["tasks"]) * 100.0, 2),
        "tracing_off_noise_serve_pct": round(
            abs(off_a["rps"] - off_b["rps"])
            / max(off_a["rps"], off_b["rps"]) * 100.0, 2),
        "tracing_overhead_pct": round(max(0.0, (base_tasks - on["tasks"])
                                          / base_tasks * 100.0), 2),
        "tracing_overhead_serve_pct": round(
            max(0.0, (base_rps - on["rps"]) / base_rps * 100.0), 2),
    }
    if out["tracing_overhead_pct"] > max(20.0,
                                         3 * out["tracing_off_noise_pct"]):
        # Well past both the budget and the ambient noise: flag it so the
        # bench trajectory (and reviewers) can't miss a hot-path tax.
        out["tracing_overhead_regression"] = True
        print(f"WARNING: tracing overhead {out['tracing_overhead_pct']}% "
              f"exceeds the regression budget", file=sys.stderr)
    return out


def _sharded_decode_main(quick: bool) -> dict:
    """Runs inside a fresh subprocess whose env forces a multi-device
    CPU platform (the bench's own process may have initialized jax with
    one device long before this section runs): tp=2 vs single-device
    decode tokens/s at equal parameter count."""
    import jax

    from ray_tpu.inference.api import preset_model
    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    new_tokens = 32 if quick else 96
    n_reqs = 4
    model, params = preset_model("tiny", 256)

    def run_engine(mesh) -> float:
        cfg = EngineConfig(batch_slots=4, num_blocks=64,
                           max_blocks_per_seq=16)
        engine = InferenceEngine(cfg, model=model, params=params,
                                 mesh=mesh)
        # Warm both programs out of the measurement window.
        engine.add_request([1, 2, 3], max_new_tokens=2)
        engine.run_until_idle()
        reqs = [engine.add_request([10 + i, 11 + i], new_tokens)
                for i in range(n_reqs)]
        t0 = time.perf_counter()
        engine.run_until_idle()
        dt = time.perf_counter() - t0
        total = sum(len(r.generated) for r in reqs)
        engine.check_no_leaks()
        return total / dt

    single = run_engine(None)
    mesh = build_mesh(MeshSpec({"tp": 2}), devices=jax.devices()[:2])
    sharded = run_engine(mesh)
    return {
        "single_decode_tokens_per_s": round(single, 1),
        "sharded_decode_tokens_per_s": round(sharded, 1),
        "sharded_decode_speedup": round(sharded / single, 3),
    }


def _sharded_pipeline_legs(quick: bool, smoke: bool) -> dict:
    """Pipeline-parallel training legs (ISSUE 20).

    Three measurements plus (smoke) two hard acceptance checks:

    - 1F1B vs sequential schedule A/B on the SAME LocalPipelineTrainer
      shapes: identical arithmetic (losses assert bitwise-equal), so the
      makespan ratio isolates the overlap. `sharded_regressed` soft-flags
      1F1B failing to beat the serialized baseline; smoke hard-asserts it.
    - pp=2 vs pp=1 parity: step-for-step bitwise losses + merged weights,
      with every stage program's trace cache holding exactly one entry
      (zero per-step recompiles).
    - ingest-fed steps: streaming shuffle -> iter_shards prefetch ->
      pipeline steps, reporting the shard's steady-state `stall_frac`
      (the "input never stalls the step" number) next to a same-run
      task-throughput anchor.
    - (smoke) seeded kill-a-stage: a pp=2 gang over worker processes is
      killed mid-run after its first merged checkpoint, elastically
      shrinks to pp=1, and must finish with weights BITWISE equal to an
      unkilled run at the same step count, under a recovery deadline.
    """
    import shutil
    import tempfile

    import numpy as np

    from ray_tpu.train.pipeline import (
        LocalPipelineTrainer,
        analytic_bubble,
        seeded_batch,
        tiny_pipeline_config,
    )

    out: dict = {}
    # Beefed-up toy shapes: per-microbatch compute must dominate the
    # transport/thread overhead or the schedule A/B measures scheduling
    # noise instead of overlap (at n_embd=32/seq=16 a microbatch is
    # sub-ms and the comparison is meaningless on a 2-core box).
    cfg = tiny_pipeline_config(n_embd=64, intermediate=128)
    fast = quick or smoke
    m = 4 if fast else 8
    steps = 4 if fast else 8
    batch, seq = 2 * m, 64

    # --- schedule A/B: same arithmetic, different overlap --------------
    runs = {}
    for sched in ("1f1b", "sequential"):
        tr = LocalPipelineTrainer(cfg, pp=2, num_microbatches=m, seed=0,
                                  schedule=sched, batch=batch, seq=seq)
        per = []
        for step in range(steps):
            ids, tg = seeded_batch(0, step, batch, seq, cfg.vocab_size)
            per.append(tr.train_step(ids, tg))
        runs[sched] = (tr, per)
    for x, y in zip(runs["1f1b"][1], runs["sequential"][1]):
        assert x["loss"] == y["loss"], \
            ("schedules diverged arithmetically", x, y)

    def _mean(vals):
        return sum(vals) / max(len(vals), 1)

    for sched, (_, per) in runs.items():
        steady = per[1:]            # step 0 pays the stage compiles
        out[f"sharded_pp2_makespan_ms_{sched}"] = round(
            _mean([p["makespan_s"] for p in steady]) * 1e3, 2)
        out[f"sharded_pp2_bubble_frac_{sched}"] = round(
            _mean([p["bubble_frac"] for p in steady]), 4)
    out["sharded_pp2_analytic_bubble_frac"] = round(analytic_bubble(2, m), 4)
    speedup = (out["sharded_pp2_makespan_ms_sequential"]
               / max(out["sharded_pp2_makespan_ms_1f1b"], 1e-9))
    out["sharded_pp2_1f1b_speedup"] = round(speedup, 3)
    # Soft regression flag (tasks_per_s_regressed convention): the
    # overlapped schedule must beat the serialized A/B on its own
    # arithmetic — same run, same shapes, so sandbox noise cancels.
    out["sharded_regressed"] = bool(speedup <= 1.0)
    if out["sharded_regressed"]:
        print("WARNING: 1F1B makespan "
              f"{out['sharded_pp2_makespan_ms_1f1b']}ms >= sequential "
              f"{out['sharded_pp2_makespan_ms_sequential']}ms "
              "(soft flag)", file=sys.stderr)

    # --- pp=2 vs pp=1 parity + compile-once ----------------------------
    ref = LocalPipelineTrainer(cfg, pp=1, num_microbatches=m, seed=0,
                               batch=batch, seq=seq)
    for step in range(steps):
        ids, tg = seeded_batch(0, step, batch, seq, cfg.vocab_size)
        met = ref.train_step(ids, tg)
        assert met["loss"] == runs["1f1b"][1][step]["loss"], \
            ("pp=2 diverged from pp=1", step, met)
    import jax

    pipe = runs["1f1b"][0]
    assert bool(jax.tree.all(jax.tree.map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
        ref.merged_params(), pipe.merged_params()))), \
        "pp=2 merged weights != pp=1 weights"
    recompiled = {name: fn._cache_size()
                  for tr in (ref, pipe)
                  for name, fn in tr.compile_counters().items()
                  if fn._cache_size() != 1}
    assert not recompiled, f"per-step recompiles: {recompiled}"
    out["sharded_pp2_parity_bitwise"] = True
    out["sharded_pp2_recompiles"] = 0

    # --- ingest-fed pipeline steps + task anchor -----------------------
    import ray_tpu
    import ray_tpu.data as rdata
    from ray_tpu.data.streaming.ingest import iter_shards

    started = False
    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=4)
        started = True
    try:
        rng = np.random.default_rng(7)
        n_rows = batch * (steps + 2)
        items = [{"ids": rng.integers(0, cfg.vocab_size, seq,
                                      dtype=np.int64).astype("int32"),
                  "targets": rng.integers(0, cfg.vocab_size, seq,
                                          dtype=np.int64).astype("int32")}
                 for _ in range(n_rows)]
        ds = rdata.from_items(items, parallelism=4).random_shuffle(seed=7)
        shard = iter_shards(ds, 1, prefetch=2)[0]
        tr = pipe        # keep training the already-compiled pp=2 stages
        fed = 0
        for bt in shard.iter_batches(batch_size=batch, drop_last=True):
            tr.train_step(np.ascontiguousarray(bt["ids"]),
                          np.ascontiguousarray(bt["targets"]))
            fed += 1
        stats = shard.ingest_stats()
        out["sharded_ingest_steps"] = fed
        out["sharded_ingest_stall_frac"] = stats["stall_frac"]
        out["sharded_ingest_stall_ms_per_step"] = stats["stall_ms_per_step"]
        out["sharded_ingest_first_batch_ms"] = stats["first_batch_ms"]

        @ray_tpu.remote
        def _noop():
            return None

        n_norm = 150 if fast else 400
        ray_tpu.get([_noop.remote() for _ in range(32)])
        t0 = time.perf_counter()
        ray_tpu.get([_noop.remote() for _ in range(n_norm)])
        out["sharded_tasks_per_s_anchor"] = round(
            n_norm / (time.perf_counter() - t0), 1)
        step_ms = out["sharded_pp2_makespan_ms_1f1b"]
        out["sharded_steps_per_tasknorm"] = round(
            (1e3 / max(step_ms, 1e-9))
            / max(out["sharded_tasks_per_s_anchor"], 1e-9), 5)
    finally:
        if started:
            ray_tpu.shutdown()

    if not smoke:
        return out

    # --- smoke hard asserts + seeded kill-a-stage elastic resume -------
    # The overlap assert is on BUBBLE, not makespan: on a 2-core sandbox
    # XLA's intra-op threading hands the sequential schedule both cores
    # per op, so wall-clock speedup is noise-bound (soft-flagged above)
    # while the idle fraction separates by >2x run after run.
    assert (out["sharded_pp2_bubble_frac_1f1b"]
            < out["sharded_pp2_bubble_frac_sequential"]), (
        "1F1B bubble did not beat the sequential A/B", out)
    assert fed >= steps, (fed, steps)
    assert out["sharded_ingest_stall_frac"] <= 0.2, stats

    import threading

    import optax

    from ray_tpu.train.backend import BackendConfig
    from ray_tpu.train.backend_executor import BackendExecutor
    from ray_tpu.train.config import ScalingConfig
    from ray_tpu.train.pipeline import (
        make_pipeline_train_fn,
        restore_pipeline_stage,
    )

    kill_steps = 6
    ckpt_dir = tempfile.mkdtemp(prefix="sharded_smoke_")
    train_fn = make_pipeline_train_fn(steps=kill_steps, microbatches=2,
                                      batch=4, seq=16, lr=1e-2, seed=0,
                                      ckpt_dir=ckpt_dir)
    os.environ["RAY_TPU_COLLECTIVE_STALL_TIMEOUT_S"] = "10"
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    deadline = time.monotonic() + 120.0
    try:
        ex = BackendExecutor(BackendConfig(), ScalingConfig(num_workers=2),
                             max_failures=2,
                             elastic_world_fn=lambda fail, world: 1)
        ex.start()

        def _killer():
            # Checkpoint-gated: the kill lands only after a merged pp=2
            # manifest exists, so the resume is a genuine RESHARD.
            while True:
                ck = ex.latest_checkpoint
                if ck is not None and ck.to_dict().get("step", -1) >= 1:
                    break
                if time.monotonic() > deadline:
                    return
                time.sleep(0.1)
            ray_tpu._global_runtime.raylet.call(
                "chaos_kill_worker", {"draw": 1, "actors_only": True})

        threading.Thread(target=_killer, daemon=True).start()
        t0 = time.perf_counter()
        for _ in ex.run(train_fn, {}, experiment_name="sharded_smoke"):
            pass
        out["sharded_kill_recover_s"] = round(time.perf_counter() - t0, 2)
        final = ex.latest_checkpoint.to_dict()
        restarts = list(ex.restarts)
        ex.shutdown()
    finally:
        ray_tpu.shutdown()
        os.environ.pop("RAY_TPU_COLLECTIVE_STALL_TIMEOUT_S", None)

    try:
        assert time.monotonic() < deadline, \
            "kill-a-stage recovery blew the 120s deadline"
        assert restarts and restarts[0]["world_size"] == 1, restarts
        assert final["step"] == kill_steps - 1, final
        # The gang ran the DEFAULT tiny config (make_pipeline_train_fn
        # with no overrides) — the unkilled reference must match it.
        kcfg = tiny_pipeline_config()
        ref = LocalPipelineTrainer(kcfg, pp=1, num_microbatches=2, seed=0)
        for step in range(kill_steps):
            ids, tg = seeded_batch(0, step, 4, 16, kcfg.vocab_size)
            ref.train_step(ids, tg)
        sample = seeded_batch(0, 0, 2, 16, kcfg.vocab_size)[0]
        st = restore_pipeline_stage(final["path"], kcfg, 0, 1,
                                    optax.adam(1e-2), sample)
        assert bool(jax.tree.all(jax.tree.map(
            lambda a, b: bool(np.array_equal(np.asarray(a),
                                             np.asarray(b))),
            st["params"], ref.merged_params()))), \
            "killed+shrunk run's weights != unkilled run's weights"
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    out["sharded_kill_restarted_world"] = restarts[0]["world_size"]
    out["sharded_kill_resume_bitwise"] = True
    out["sharded_smoke_ok"] = True
    return out


def bench_sharded(quick: bool, smoke: bool = False) -> dict:
    """Sharded replica groups (ISSUE 9) + pipeline training (ISSUE 20):
    tensor-parallel decode throughput vs single-device at EQUAL parameter
    count, gang cold-start latency (forge-spawned rank actors), and the
    pipeline-parallel training legs (1F1B schedule A/B, ingest-fed steps,
    elastic kill-a-stage in smoke).

    On this 2-core CPU sandbox tp=2 shards compute over forced host
    devices that share the same physical cores, so `sharded_decode_
    speedup` measures partitioning OVERHEAD (expect <= 1.0 here; on a
    real multi-chip host the same program is the scale-up path) — the
    number to watch is that overhead staying bounded and the parity
    tests staying green.

    `smoke=True` runs ONLY the pipeline legs with hard asserts (pp=2
    parity bitwise, zero recompiles, 1F1B beats sequential, seeded
    kill-a-stage resumes bit-exact) — the <60s gate.sh leg."""
    import json as _json
    import subprocess
    import sys

    import ray_tpu
    from ray_tpu import shardgroup

    if smoke:
        return _sharded_pipeline_legs(quick=True, smoke=True)

    code = ("import bench, json; "
            f"print('SHARD_RESULT ' + json.dumps("
            f"bench._sharded_decode_main({quick!r})))")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=1200,
                          cwd=os.path.dirname(os.path.abspath(__file__)),
                          env=env)
    out: dict = {}
    for line in (proc.stdout or "").splitlines():
        if line.startswith("SHARD_RESULT "):
            out = _json.loads(line[len("SHARD_RESULT "):])
    if not out:
        raise RuntimeError(
            f"sharded decode run failed (rc={proc.returncode}): "
            f"{(proc.stderr or '')[-500:]}")

    # Gang cold start: placement group 2PC + two forge-spawned rank
    # actors + bring-up, measured to the all-ranks-alive ping (tp=1:
    # no mesh needed, so this half runs fine in the bench process).
    class _Rank:
        def __call__(self, payload):
            return payload

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    coldstarts = []
    for _ in range(2 if quick else 4):
        t0 = time.perf_counter()
        group = shardgroup.create_replica_group(
            _Rank, shardgroup.ShardSpec(tp=1, world_size=2),
            deployment_name="bench", ready_timeout_s=60)
        coldstarts.append((time.perf_counter() - t0) * 1e3)
        group.kill()
    ray_tpu.shutdown()

    out["sharded_group_coldstart_ms"] = round(min(coldstarts), 1)
    out["sharded_group_coldstart_worst_ms"] = round(max(coldstarts), 1)
    out.update(_sharded_pipeline_legs(quick, smoke=False))
    return out


def _chaos_rpc_hook_aba(cluster, n_calls: int) -> dict:
    """A-B-A inertness check for the RPC chaos hook: kv round-trip rate
    with the filter ABSENT, with a pass-all filter INSTALLED, then absent
    again — the disabled path is one module-global None check, and the
    off-vs-off disagreement is the ambient noise floor that bounds what
    "unmeasurable" means on this box."""
    import ray_tpu
    from ray_tpu.core.rpc import clear_chaos_filter, install_chaos_filter

    runtime = ray_tpu._require_runtime()
    runtime.gcs.call("kv_put", {"key": b"chaos:aba", "value": b"x"})

    def rate() -> float:
        t0 = time.perf_counter()
        for _ in range(n_calls):
            runtime.gcs.call("kv_get", {"key": b"chaos:aba"})
        return n_calls / (time.perf_counter() - t0)

    off_a = rate()
    install_chaos_filter(lambda name, addr, method: None)
    try:
        on = rate()
    finally:
        clear_chaos_filter()
    off_b = rate()
    base = max(off_a, off_b)
    return {
        "chaos_rpc_hook_off_calls_per_s": round(base, 1),
        "chaos_rpc_hook_on_calls_per_s": round(on, 1),
        "chaos_rpc_hook_off_noise_pct": round(
            abs(off_a - off_b) / base * 100.0, 2),
        "chaos_rpc_hook_overhead_pct": round(
            max(0.0, (base - on) / base * 100.0), 2),
    }


def bench_chaos(quick: bool, smoke: bool = False,
                seed: int = 20260804) -> dict:
    """Chaos-plane acceptance bench (ISSUE 10 / ROADMAP 4): a seeded
    ChaosSchedule kills a node every ~N seconds — plus worker/forge kills
    and (full runs) a GCS restart — while Poisson serve traffic AND a
    checkpointing training loop run against the same cluster. Reported:
    per-fault-class detect->recovered MTTR (`chaos_mttr_ms`), request
    error rate, steps lost per fault, and HARD asserts: zero hangs
    (watchdog over every parked future), every fault recovered within the
    deadline, and the training loop provably resumed from its checkpoint
    after each gang restart (step continuity). The event log in the
    output IS the reproduction recipe: same seed => same log.

    `smoke=True` is the gate's short variant: one node kill under light
    serve load, deterministic seed, well under 60s, no training loop."""
    import random as _random
    import tempfile
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.chaos import (
        ChaosRunner,
        ChaosSchedule,
        ForgeKillInjector,
        GcsRestartInjector,
        HangWatchdog,
        NodeKillInjector,
        WorkerKillInjector,
    )
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    gcs_path = os.path.join(tempfile.mkdtemp(), "gcs_tables.bin")
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 3},
                      gcs_storage_path=gcs_path)
    node_args = {"num_cpus": 2, "resources": {"churn": 2}}
    n_nodes = 2 if (smoke or quick) else 3
    for _ in range(n_nodes):
        cluster.add_node(**node_args)
    cluster.wait_for_nodes()
    cluster.connect()
    out: dict = {"chaos_seed": seed}
    try:
        if not smoke:
            out.update(_chaos_rpc_hook_aba(cluster,
                                           300 if quick else 1500))

        # --- schedule + injectors -------------------------------------
        if smoke:
            kinds = {"node_kill": 1.0}
            count, period = 1, 1.5
        elif quick:
            kinds = {"node_kill": 2.0, "worker_kill": 1.0,
                     "forge_kill": 1.0}
            count, period = 4, 2.5
        else:
            kinds = {"node_kill": 3.0, "worker_kill": 2.0,
                     "forge_kill": 1.0, "gcs_restart": 1.0}
            count, period = 8, 3.0
        sched = ChaosSchedule(seed=seed, kinds=kinds, period_s=period,
                              count=count, jitter=0.25)
        injectors = {
            "node_kill": NodeKillInjector(cluster, replace=True,
                                          node_args=node_args),
            "worker_kill": WorkerKillInjector(cluster),
            "forge_kill": ForgeKillInjector(cluster),
            "gcs_restart": GcsRestartInjector(cluster),
        }
        runner = ChaosRunner(cluster, sched, injectors,
                             recovery_deadline_s=45.0)

        # --- Poisson serve load ---------------------------------------
        @serve.deployment(num_replicas=2, max_concurrent_queries=32)
        class ChaosEcho:
            def __call__(self, payload):
                return payload

        handle = serve.run(ChaosEcho.bind())
        _get = ray_tpu.get
        _get([handle.remote(i) for i in range(8)])  # warm

        rate_hz = 15.0 if (smoke or quick) else 30.0
        duration_s = (period * count) + (2.0 if smoke else 6.0)
        arrivals_rng = _random.Random(seed + 1)
        arrivals, t = [], 0.0
        while t < duration_s:
            t += arrivals_rng.expovariate(rate_hz)
            arrivals.append(t)
        serve_stats = {"sent": 0, "ok": 0, "err": 0}

        def serve_load(wd):
            t0 = time.perf_counter()
            refs = []
            for i, at in enumerate(arrivals):
                delay = t0 + at - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                try:
                    refs.append(handle.remote(i))
                    serve_stats["sent"] += 1
                except Exception:  # noqa: BLE001 — routed into a dead
                    serve_stats["err"] += 1  # replica mid-churn
            for ref in refs:
                try:
                    with wd.track("serve-result"):
                        _get(ref, timeout=30)
                    serve_stats["ok"] += 1
                except Exception:  # noqa: BLE001 — replica died mid-call
                    serve_stats["err"] += 1

        # --- checkpointing training loop ------------------------------
        train_result = {}

        def train_load():
            from ray_tpu.train import session as _session
            from ray_tpu.train.checkpoint import Checkpoint
            from ray_tpu.train.config import (
                FailureConfig,
                RunConfig,
                ScalingConfig,
            )
            from ray_tpu.train.trainer import DataParallelTrainer

            n_steps = max(10, int(duration_s / 0.25) + 4)

            def loop(config):
                ckpt = _session.get_checkpoint()
                start = ckpt.to_dict()["step"] + 1 \
                    if ckpt is not None else 0
                for step in range(start, n_steps):
                    time.sleep(0.25)
                    _session.report(
                        {"step": step, "start": start},
                        checkpoint=Checkpoint.from_dict({"step": step})
                        if _session.get_world_rank() == 0 else None)

            trainer = DataParallelTrainer(
                loop,
                # Pin the train workers to the KILLABLE nodes (the head
                # is never a chaos victim): node kills must actually hit
                # the gang so the resume-from-checkpoint assert means
                # something.
                scaling_config=ScalingConfig(
                    num_workers=2,
                    resources_per_worker={"churn": 0.5}),
                run_config=RunConfig(
                    name=f"bench_chaos_{seed}",
                    failure_config=FailureConfig(max_failures=count + 2)))
            res = trainer.fit()
            train_result["steps"] = [m["step"]
                                     for m in res.metrics_history]
            train_result["starts"] = [m["start"]
                                      for m in res.metrics_history]
            train_result["error"] = res.error
            train_result["n_steps"] = n_steps

        # --- run everything under the watchdog ------------------------
        with HangWatchdog(limit_s=60.0) as wd:
            threads = [threading.Thread(target=serve_load, args=(wd,),
                                        name="chaos-serve-load",
                                        daemon=True)]
            if not smoke:
                threads.append(threading.Thread(target=train_load,
                                                name="chaos-train-load",
                                                daemon=True))
            with runner:
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=300)
                    assert not t.is_alive(), f"{t.name} never finished"
                assert runner.wait(timeout=120), "chaos schedule stalled"

        # --- hard asserts ---------------------------------------------
        runner.assert_recovered()           # bounded recovery, per fault
        wd.assert_no_hangs()                # zero parked-forever futures
        assert runner.executed_signatures == sched.signatures(), \
            "executed event log diverged from the seeded schedule"

        out["chaos_event_log"] = [list(s) for s in sched.signatures()]
        out["chaos_faults_injected"] = runner.faults_injected
        out["chaos_mttr_ms"] = runner.mttr_by_kind()
        all_mttrs = [r.mttr_ms for r in runner.records
                     if r.mttr_ms is not None]
        out["chaos_mttr_max_ms"] = round(max(all_mttrs), 1) \
            if all_mttrs else None
        out["chaos_zero_hangs"] = wd.hang_count == 0
        total = serve_stats["ok"] + serve_stats["err"]
        out["chaos_requests_total"] = total
        out["chaos_request_error_rate"] = round(
            serve_stats["err"] / total, 4) if total else None

        if not smoke:
            assert train_result.get("error") is None, train_result["error"]
            steps = train_result["steps"]
            starts = sorted(set(train_result["starts"]))
            assert steps and steps[-1] == train_result["n_steps"] - 1, \
                "training loop did not run to completion"
            # Step continuity: the union of executed steps covers the
            # whole range — each gang restart resumed AT its checkpoint,
            # not from scratch and not past a gap.
            assert set(steps) == set(range(train_result["n_steps"])), \
                f"step gap after restart: {steps}"
            restarts = len(starts) - 1
            out["chaos_train_restarts"] = restarts
            out["chaos_train_resumed_from_checkpoint"] = \
                restarts == 0 or starts[-1] > 0
            # Re-executed steps (reported more than once) per fault:
            # bounded checkpoint lag, NOT restart-from-zero.
            dup_steps = len(steps) - len(set(steps))
            out["chaos_steps_lost_per_fault"] = round(
                dup_steps / max(1, runner.faults_injected), 2)
        if smoke:
            assert out["chaos_request_error_rate"] is not None and \
                out["chaos_request_error_rate"] < 0.5, \
                f"smoke error rate too high: {out}"

        # Soft regression flag (same convention as serve_scaleup_regressed):
        # recovery is the metric this subsystem exists to bound.
        if out["chaos_mttr_max_ms"] is not None and \
                out["chaos_mttr_max_ms"] > 20000:
            out["chaos_mttr_regressed"] = True
            print(f"WARNING: chaos_mttr_max_ms {out['chaos_mttr_max_ms']} "
                  "exceeds the 20s soft budget", file=sys.stderr)
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001 — controller may have died
            pass
        try:
            cluster.shutdown()
        except Exception:  # noqa: BLE001 — nodes already churned away
            pass
    return out


def bench_ingest(quick: bool, smoke: bool = False,
                 seed: int = 20260804) -> dict:
    """Streaming ingest plane acceptance bench (ISSUE 14 / ROADMAP 5):
    a shuffle-then-train pipeline at sustained load.

    Reported: `ingest_gb_s` for a full windowed-shuffle epoch, per-step
    `step_stall_ms` A/B (double-buffered prefetch on vs off — stall must
    be <10% of step time with prefetch on), window/backpressure
    accounting, and HARD asserts: `num_unsealed == 0` and zero leaked
    store objects after the epoch, and a seeded chaos node kill
    MID-SHUFFLE that recovers with recomputed blocks bounded by the dead
    node's resident block count (never a pipeline restart), watchdog-
    clean.

    `smoke=True` is the gate's bounded variant: only the seeded
    node-kill recovery phase, <60s."""
    import threading

    import ray_tpu
    from ray_tpu import data as rd
    from ray_tpu.chaos import HangWatchdog, NodeKillInjector
    from ray_tpu.chaos.schedule import single_event_schedule
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.data.streaming.ingest import ShardIterator
    from ray_tpu.data.streaming.lineage import core_reconstructions

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 3})
    # Chaos-phase pipeline tasks pin to the KILLABLE nodes via the churn
    # resource (the head is never a victim): the node kill must actually
    # hit blocks the pipeline still needs for the recompute bound to
    # mean something.
    node_args = {"num_cpus": 2, "resources": {"churn": 2}}
    for _ in range(2):
        cluster.add_node(**node_args)
    cluster.wait_for_nodes()
    cluster.connect()
    out: dict = {"ingest_seed": seed}

    def _store_stats():
        return [r.store.stats() for r in cluster.raylets]

    def _assert_store_clean(tag: str):
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            stats = _store_stats()
            if all(s["num_unsealed"] == 0 for s in stats):
                break
            time.sleep(0.2)
        stats = _store_stats()
        assert all(s["num_unsealed"] == 0 for s in stats), \
            f"{tag}: unsealed buffers leaked: {stats}"
        return stats

    try:
        if not smoke:
            # --- Phase A: full shuffle epoch throughput + zero leaks ---
            rows, shape = (40_000, (32,)) if quick else (120_000, (64,))
            parallelism = 8
            baseline_objs = [s["num_objects"] for s in _store_stats()]
            ds = rd.range_tensor(rows, shape=shape,
                                 parallelism=parallelism) \
                .random_shuffle(seed=seed)
            t0 = time.perf_counter()
            nbytes = 0
            for batch in ds.iter_batches(batch_size=2048):
                nbytes += batch["data"].nbytes
            wall = time.perf_counter() - t0
            out["ingest_gb_s"] = round(nbytes / 1e9 / wall, 4)
            out["ingest_epoch_bytes"] = nbytes
            out["ingest_windows"] = ds.last_shuffle_stats.get("windows")
            st = ds.stats()
            bp = (st.backpressure or {}) if st else {}
            out["ingest_bound_op"] = bp.get("bound_op")
            _assert_store_clean("epoch")
            # Zero store leaks: dropping the pipeline returns every node
            # to (at most) its pre-epoch object count. Frees are batched
            # on a 1s timer — poll with a deadline.
            del ds
            import gc as _gc

            _gc.collect()
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                now_objs = [s["num_objects"] for s in _store_stats()]
                if all(n <= b for n, b in zip(now_objs, baseline_objs)):
                    break
                time.sleep(0.2)
            now_objs = [s["num_objects"] for s in _store_stats()]
            assert all(n <= b for n, b in zip(now_objs, baseline_objs)), \
                f"store leak after epoch: {baseline_objs} -> {now_objs}"

            # --- Phase B: train-shard step-stall A/B (prefetch on/off) ---
            # The epoch is shuffled once and MATERIALIZED (epoch N trains
            # while epoch N+1 shuffles — the pipeline overlap shape), so
            # the A/B isolates what prefetch exists to hide: the per-host
            # pull latency of each shard block, not shuffle compute.
            ab_rows = 8_000 if quick else 24_000
            step_s = 0.02
            ds_ab = rd.range_tensor(ab_rows, shape=(32,), parallelism=8) \
                .random_shuffle(seed=seed + 1).materialize()

            def consume_shards(prefetch):
                shards = [ShardIterator(s, prefetch) for s in
                          ds_ab.streaming_split(2)]
                stats = [None, None]

                def run(i):
                    for _ in shards[i].iter_batches(batch_size=256):
                        time.sleep(step_s)  # the simulated train step
                    stats[i] = shards[i].ingest_stats()

                threads = [threading.Thread(target=run, args=(i,),
                                            daemon=True) for i in (0, 1)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=300)
                    assert not t.is_alive(), "ingest consumer wedged"
                steps = sum(s["steps"] for s in stats)
                stall = sum(s["stall_ms_total"] for s in stats)
                step_ms = sum(s["step_ms_total"] for s in stats)
                return {"steps": steps,
                        "step_stall_ms": round(stall / max(1, steps), 3),
                        "stall_frac": round(stall / max(1e-9,
                                                        stall + step_ms), 4)}

            off = consume_shards(prefetch=0)
            on = consume_shards(prefetch=2)
            out["step_stall_ms_prefetch_off"] = off["step_stall_ms"]
            out["step_stall_ms_prefetch_on"] = on["step_stall_ms"]
            out["step_stall_frac_prefetch_off"] = off["stall_frac"]
            out["step_stall_frac_prefetch_on"] = on["stall_frac"]
            assert on["stall_frac"] < 0.10, \
                f"prefetch-on stall {on['stall_frac']} >= 10% of step time"
            assert on["step_stall_ms"] <= off["step_stall_ms"], (on, off)

        # --- Phase C: seeded node kill MID-SHUFFLE, bounded recompute ---
        # Few fat partitions: every block (inputs ~1 MiB, buckets ~T/p²,
        # reduce outputs ~T/p) must clear the 100 KiB inline threshold or
        # the intermediates live in the GCS instead of node stores and a
        # node death loses nothing. Reduce in-flight is capped at 2 so
        # the kill lands while most partitions still NEED their buckets —
        # otherwise the fast exchange finishes before the fault bites and
        # the "recovery" proves nothing.
        from ray_tpu.data.context import DataContext

        c_rows, n_parts = (16_000, 8) if (smoke or quick) else (32_000, 8)
        ctx = DataContext.get_current()
        old_in_flight = ctx.max_tasks_in_flight_per_op
        ctx.max_tasks_in_flight_per_op = 2
        try:
            ds_chaos = rd.range_tensor(c_rows, shape=(64,),
                                       parallelism=n_parts) \
                .with_resources(resources={"churn": 0.25}) \
                .random_shuffle(seed=seed + 2)
            sched = single_event_schedule(seed, "node_kill")
            injector = NodeKillInjector(cluster, replace=True,
                                        node_args=node_args)
            base_recon = core_reconstructions()
            killed: dict = {}
            rows_seen = 0
            with HangWatchdog(limit_s=90.0) as wd:
                for i, batch in enumerate(
                        ds_chaos.iter_batches(batch_size=512)):
                    rows_seen += len(batch["data"])
                    if not killed:
                        # Kill the node holding the MOST pipeline blocks
                        # (steer the seeded event's draw onto it): a
                        # victim the scheduler happened to leave idle
                        # would prove nothing. Its resident count BEFORE
                        # the kill bounds the permissible recompute work.
                        import dataclasses as _dc

                        victims = sorted(
                            (r for r in cluster.raylets if not r.is_head),
                            key=lambda r: r.node_id.hex())
                        resident = [r.store.stats()["num_objects"]
                                    for r in victims]
                        idx = max(range(len(victims)),
                                  key=lambda k: resident[k])
                        event = _dc.replace(sched.events[0], draw=idx)
                        killed["resident"] = resident[idx]
                        detail = injector.inject(event)
                        killed["node"] = detail.get("node")
            wd.assert_no_hangs()
        finally:
            ctx.max_tasks_in_flight_per_op = old_in_flight
        assert rows_seen == c_rows, \
            f"epoch lost rows after node kill: {rows_seen}/{c_rows}"
        assert killed, "node kill never fired"
        recomputed = core_reconstructions() - base_recon
        lineage = getattr(ds_chaos, "_lineage", None)
        dataplane_recomputed = lineage.recomputed_blocks \
            if lineage is not None else 0
        recomputed += dataplane_recomputed
        out["ingest_chaos_victim_resident_blocks"] = killed["resident"]
        out["ingest_chaos_recomputed_blocks"] = recomputed
        out["ingest_chaos_dataplane_recomputed"] = dataplane_recomputed
        # Recovery actually ran (the kill destroyed blocks the pipeline
        # still needed) AND stayed bounded: no more re-executions than
        # the dead node held blocks (its map buckets + reduce outputs)
        # plus one resubmission per output partition — never a restart
        # of the whole pipeline.
        assert recomputed >= 1, \
            "node kill destroyed nothing the pipeline needed — the " \
            "recovery path was not exercised"
        bound = max(killed["resident"], 1) + n_parts
        assert recomputed <= bound, \
            f"recompute unbounded: {recomputed} > {bound} ({killed})"
        out["ingest_chaos_recovery_bounded"] = True
        out["ingest_zero_hangs"] = wd.hang_count == 0
        _assert_store_clean("chaos")
    finally:
        try:
            cluster.shutdown()
        except Exception:  # noqa: BLE001 — nodes already churned away
            pass
    return out


def bench_query(quick: bool, smoke: bool = False,
                seed: int = 20260807) -> dict:
    """Distributed query tier acceptance bench (ISSUE 18): width-scale
    sort/groupby/join through the windowed shuffle, plus the locality-
    routing A/B.

    Phase A measures the exchange operators against a SAME-RUN anchor
    (one plain streaming pass over identical rows — normalizes the
    2-core sandbox out of the numbers) with row-identity verified inline
    and the driver's sort footprint asserted bounded by the key sample.
    `query_regressed` is a soft flag (printed, never fatal) when the
    sort exceeds 12x the anchor pass.

    Phase B A/Bs locality-routed split handout: two consumers pinned to
    the two block-holding nodes drain the same-shape dataset with
    routing off then on, and the cross-node byte meter (summed
    `_chunk_bytes_served` over all raylets; the same-host attach is
    disabled so every remote pull pays the socket) must drop. HARD
    asserts: row totals, routed arm strictly cheaper, zero unsealed
    buffers.

    `smoke=True` (gate step) runs both phases at bounded sizes, <60s."""
    import numpy as np

    import ray_tpu
    from ray_tpu import data as rd
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.data.context import DataContext

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 3})
    # Per-node pin resources make Phase B's consumer placement exact:
    # consumer i sits WITH (then, in the off arm, WITHOUT) its blocks.
    for i in range(2):
        cluster.add_node(num_cpus=2,
                         resources={"churn": 2, f"pin{i}": 1})
    cluster.wait_for_nodes()
    cluster.connect()
    out: dict = {"query_seed": seed}
    try:
        # --- Phase A: exchange operators vs same-run anchor ------------
        rows = 20_000 if (smoke or quick) else 60_000
        n_parts = 8

        def keyed(batch):
            return {"k": (batch["data"][:, 0].astype(np.int64)) % 97,
                    "data": batch["data"]}

        base = rd.range_tensor(rows, shape=(16,), parallelism=n_parts) \
            .map_batches(keyed)

        t0 = time.perf_counter()
        anchor_rows = sum(len(b["k"])
                          for b in base.iter_batches(batch_size=2048))
        anchor_s = time.perf_counter() - t0
        assert anchor_rows == rows

        ds_sort = base.sort(key="k")
        t0 = time.perf_counter()
        sorted_rows, nbytes, last = 0, 0, None
        for batch in ds_sort.iter_batches(batch_size=2048):
            ks = np.asarray(batch["k"])
            sorted_rows += len(ks)
            nbytes += batch["data"].nbytes
            assert (np.diff(ks) >= 0).all(), "sort output out of order"
            if last is not None:
                assert ks[0] >= last
            last = int(ks[-1])
        sort_s = time.perf_counter() - t0
        assert sorted_rows == rows, f"sort lost rows: {sorted_rows}/{rows}"
        sstats = ds_sort.last_sort_stats
        # The driver's whole per-row footprint is the boundary sample.
        assert sstats["driver_sample_bytes"] <= 64 * 1024, sstats
        out["query_sort_sample_rows"] = sstats["sample_rows"]
        out["query_sort_driver_sample_bytes"] = sstats["driver_sample_bytes"]
        out["query_sort_gb_s"] = round(nbytes / 1e9 / sort_s, 4)

        t0 = time.perf_counter()
        groups = base.groupby("k").count().take_all()
        groupby_s = time.perf_counter() - t0
        assert sum(g["count()"] for g in groups) == rows
        assert len(groups) == 97

        left = rd.from_items(
            [{"id": i % 512, "lv": i} for i in range(rows // 4)],
            parallelism=n_parts)
        right = rd.from_items(
            [{"id": i, "rv": i * 3} for i in range(512)], parallelism=2)
        ctx = DataContext.get_current()
        old_bj = ctx.broadcast_join_bytes
        try:
            ctx.broadcast_join_bytes = 0  # force the hash exchange
            ds_join = left.join(right, on="id")
            t0 = time.perf_counter()
            join_rows = sum(1 for _ in ds_join.iter_rows())
            join_s = time.perf_counter() - t0
        finally:
            ctx.broadcast_join_bytes = old_bj
        assert join_rows == rows // 4, f"join lost rows: {join_rows}"
        assert ds_join.last_join_stats["strategy"] == "hash"

        out["query_anchor_pass_s"] = round(anchor_s, 3)
        out["query_sort_s"] = round(sort_s, 3)
        out["query_groupby_s"] = round(groupby_s, 3)
        out["query_join_s"] = round(join_s, 3)
        # Soft regression flag (chaos_mttr_regressed convention): the
        # exchange adds sample+scatter+reduce over a plain pass; 12x the
        # same-run anchor flags a pathological slowdown, not noise.
        if sort_s > 12 * max(anchor_s, 0.05):
            out["query_regressed"] = True
            print(f"WARNING: query sort {sort_s:.2f}s exceeds 12x the "
                  f"same-run anchor pass {anchor_s:.2f}s", file=sys.stderr)

        # --- Phase B: locality-routed handout A/B ----------------------
        # Socket path only: the same-host attach would hide exactly the
        # bytes this A/B exists to measure.
        GLOBAL_CONFIG._overrides["object_transfer_same_host_attach"] = False

        @ray_tpu.remote(num_cpus=1)
        class ShardConsumer:
            def consume(self, shard, routing: bool) -> dict:
                from ray_tpu.data.context import DataContext as _DC

                # The knob is resolved consumer-side (this process).
                _DC.get_current().locality_routing = bool(routing)
                n = 0
                for b in shard.iter_batches(batch_size=512):
                    n += len(b["data"])
                st = shard.ingest_stats()
                return {"rows": n,
                        "locality_hits": st["locality_hits"],
                        "locality_misses": st["locality_misses"]}

        # Deterministic placement: 8 blocks pinned to EACH worker (the
        # pin resources), interleaved so the coordinator's lookahead
        # always holds a block local to either consumer. Blocks are
        # 512 KiB — real store residency with directory entries (inline
        # blocks live nowhere and can't be routed to).
        @ray_tpu.remote(num_cpus=1)
        def make_block(tag: int):
            import numpy as _inp
            return {"data": _inp.full((2000, 32), float(tag))}

        n_per_node = 8
        ref_grid = [[make_block.options(
            resources={f"pin{i}": 0.01}).remote(i * n_per_node + j)
            for j in range(n_per_node)] for i in range(2)]
        refs = [ref_grid[i][j] for j in range(n_per_node)
                for i in range(2)]
        ray_tpu.wait(refs, num_returns=len(refs), timeout=120)
        ab_rows = 2000 * len(refs)

        from ray_tpu.data.dataset import Dataset as _DSet

        def run_arm(routing: bool) -> dict:
            ds = _DSet([(None, (r,)) for r in refs])
            shards = rd.DataIterator(ds).iter_shards(2, prefetch=0)
            served0 = sum(r._chunk_bytes_served for r in cluster.raylets)
            actors = [ShardConsumer.options(
                resources={f"pin{i}": 1}).remote() for i in range(2)]
            try:
                results = ray_tpu.get(
                    [a.consume.remote(s, routing)
                     for a, s in zip(actors, shards)], timeout=300)
            finally:
                for a in actors:
                    ray_tpu.kill(a)
            served = sum(r._chunk_bytes_served
                         for r in cluster.raylets) - served0
            assert sum(r["rows"] for r in results) == ab_rows
            return {"cross_node_bytes": served,
                    "hits": sum(r["locality_hits"] for r in results),
                    "misses": sum(r["locality_misses"] for r in results)}

        off = run_arm(routing=False)
        on = run_arm(routing=True)
        GLOBAL_CONFIG._overrides.pop("object_transfer_same_host_attach",
                                     None)
        out["query_locality_bytes_off"] = off["cross_node_bytes"]
        out["query_locality_bytes_on"] = on["cross_node_bytes"]
        out["query_locality_hits_on"] = on["hits"]
        assert off["hits"] == 0, off  # routing off advertises no node
        assert on["hits"] >= 1, \
            f"locality routing never landed a local block: {on}"
        assert on["cross_node_bytes"] < off["cross_node_bytes"], (
            "locality routing did not reduce cross-node bytes: "
            f"on={on} off={off}")
        for r in cluster.raylets:
            assert r.store.stats()["num_unsealed"] == 0
    finally:
        try:
            cluster.shutdown()
        except Exception:  # noqa: BLE001 — nodes already churned away
            pass
    return out


# --------------------------------------------------------------------------- #
# Job tier: submission plane, runtime-env forge templates, jobs-as-tenants
# --------------------------------------------------------------------------- #


def _cold_worker_pids() -> set:
    """Pids running `python -m ray_tpu.core.worker` (cold-spawned workers),
    matched as an exact argv element so lingering forge templates
    (`ray_tpu.core.worker_forge`, which self-exit on idle by design) are
    not counted. Forge-forked workers inherit the template's argv, so
    they are covered by the in-raylet reclaim poll instead."""
    pids = set()
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = f.read().split(b"\0")
        except OSError:
            continue  # exited while scanning
        if b"ray_tpu.core.worker" in argv:
            pids.add(pid)
    return pids


def _pids_with_mark(mark: str):
    """Pids whose /proc cmdline carries `mark`. The mark is placed INSIDE
    each job's `python -c` source so it lands in the driver's argv and
    survives the sh wrapper (tests/test_cluster_services.py idiom); a
    zombie has an empty cmdline and cannot false-positive."""
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


def bench_jobs(quick: bool, smoke: bool = False) -> dict:
    """Job-tier acceptance bench (ISSUE 17 / docs/JOBS.md): submit->
    first-task latency cold (per-env forge template still paying its
    preimport bill -> worker cold-spawns) vs warm (template fork path),
    N=3 concurrent jobs as distinct tenants sharing one cluster with a
    per-job throughput breakdown, and a same-run interactive task-latency
    anchor so the job numbers have an in-run yardstick.

    `smoke=True` is the gate's bounded variant, with HARD asserts: warm
    submit->first-task >=2x faster than cold, every job SUCCEEDED with
    its own env (isolation), zero orphan job processes via /proc scan
    (driver mark in argv + cold-worker argv diff), and `num_unsealed`
    0 after the jobs drain."""
    import uuid

    import ray_tpu
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient

    ray_tpu.shutdown()
    workers_before = _cold_worker_pids()
    ray_tpu.init(num_cpus=4)
    client = JobSubmissionClient(ray_tpu._global_runtime.gcs.address)
    mark = f"jobsbench-{uuid.uuid4().hex[:12]}"
    renv = {"preimports": ["jax"]}
    out: dict = {}
    job_hexes = []

    def first_task_entry():
        return (
            f"{sys.executable} -c \""
            f"_MARK = '{mark}'\n"
            "import time, ray_tpu; ray_tpu.init()\n"
            "t0 = time.time()\n"
            "@ray_tpu.remote\n"
            "def probe():\n"
            "    return 1\n"
            "ray_tpu.get(probe.remote())\n"
            "print('FIRST_TASK_MS=%.1f' % ((time.time() - t0) * 1e3))\n"
            "ray_tpu.shutdown()\"")

    def wait_terminal(sid, timeout=120.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if client.get_job_status(sid) in JobStatus.TERMINAL:
                break
            time.sleep(0.2)
        return client.get_job_status(sid)

    def first_task_ms(sid):
        status = wait_terminal(sid)
        logs = client.get_job_logs(sid)
        assert status == JobStatus.SUCCEEDED, \
            f"job {sid} status={status} logs={logs[-800:]}"
        for line in logs.splitlines():
            if line.startswith("FIRST_TASK_MS="):
                return float(line.split("=", 1)[1])
        raise AssertionError(f"no FIRST_TASK_MS in logs: {logs[-800:]}")

    try:
        # --- cold vs warm: the per-env forge template is the product ---
        t0 = time.monotonic()
        sid_cold = client.submit_job(entrypoint=first_task_entry(),
                                     runtime_env=dict(renv))
        cold_ms = first_task_ms(sid_cold)
        out["jobs_cold_submit_to_done_s"] = round(time.monotonic() - t0, 2)
        out["jobs_cold_first_task_ms"] = round(cold_ms, 1)
        job_hexes.append(client.get_job_info(sid_cold).driver_job_id)

        # The warm number measures the template, not a race against its
        # warmup: wait until the env forge reports fork-ready (the
        # lingering shared template reattaches in milliseconds) before
        # submitting the second job.
        raylet = ray_tpu._global_node.raylet  # in-process head node
        env_extra = {"RAY_TPU_RUNTIME_ENV": json.dumps(renv)}
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline \
                and not raylet.pool.forge_available(env_extra):
            time.sleep(0.2)
        out["jobs_template_ready"] = raylet.pool.forge_available(env_extra)

        t0 = time.monotonic()
        sid_warm = client.submit_job(entrypoint=first_task_entry(),
                                     runtime_env=dict(renv))
        warm_ms = first_task_ms(sid_warm)
        out["jobs_warm_submit_to_done_s"] = round(time.monotonic() - t0, 2)
        out["jobs_warm_first_task_ms"] = round(warm_ms, 1)
        out["jobs_forge_speedup_x"] = round(cold_ms / max(warm_ms, 1e-3), 2)
        job_hexes.append(client.get_job_info(sid_warm).driver_job_id)
        if smoke:
            assert warm_ms * 2.0 <= cold_ms, \
                f"forge-template submit not >=2x faster: cold {cold_ms:.0f}ms " \
                f"vs warm {warm_ms:.0f}ms ({out})"
        elif out["jobs_forge_speedup_x"] < 2.0:
            out["jobs_forge_regressed"] = True
            print(f"WARNING: jobs_forge_speedup_x "
                  f"{out['jobs_forge_speedup_x']} below the 2x budget",
                  file=sys.stderr)

        # --- N=3 concurrent jobs as tenants, per-job throughput --------
        n_tasks = 12 if (smoke or quick) else 48
        tiers = ["gold", "silver", "bronze"]
        sids = []
        for i, tier in enumerate(tiers):
            entry = (
                f"{sys.executable} -c \""
                f"_MARK = '{mark}'\n"
                "import os, time, ray_tpu; ray_tpu.init()\n"
                "@ray_tpu.remote\n"
                "def work(i):\n"
                "    return os.environ.get('JOB_COLOR', '?')\n"
                "ray_tpu.get([work.remote(i) for i in range(2)])\n"
                "t0 = time.time()\n"
                "got = ray_tpu.get("
                f"[work.remote(i) for i in range({n_tasks})])\n"
                "dt = max(time.time() - t0, 1e-6)\n"
                f"print('JOB_TPS=%.1f' % ({n_tasks} / dt))\n"
                "print('COLORS=' + ','.join(sorted(set(got))))\n"
                "ray_tpu.shutdown()\"")
            sids.append(client.submit_job(
                entrypoint=entry,
                runtime_env={"env_vars": {"JOB_COLOR": f"color-{i}"}},
                tenant={"name": f"jobsbench-{tier}", "tier": tier}))
        per_job = {}
        for i, sid in enumerate(sids):
            status = wait_terminal(sid)
            logs = client.get_job_logs(sid)
            assert status == JobStatus.SUCCEEDED, \
                f"concurrent job {i} status={status} logs={logs[-800:]}"
            assert f"COLORS=color-{i}" in logs, \
                f"env isolation breached for job {i}: {logs[-400:]}"
            tps = next(float(ln.split("=", 1)[1])
                       for ln in logs.splitlines()
                       if ln.startswith("JOB_TPS="))
            per_job[tiers[i]] = round(tps, 1)
            job_hexes.append(client.get_job_info(sid).driver_job_id)
        out["jobs_concurrent_n"] = len(sids)
        out["jobs_tasks_per_s_by_tenant"] = per_job

        # --- same-run anchor: interactive driver task latency ----------
        @ray_tpu.remote
        def _anchor():
            return 1

        ray_tpu.get(_anchor.remote())  # warm a worker for this driver
        lat = []
        for _ in range(10 if (smoke or quick) else 50):
            t1 = time.perf_counter()
            ray_tpu.get(_anchor.remote())
            lat.append((time.perf_counter() - t1) * 1e3)
        lat.sort()
        out["jobs_task_anchor_ms"] = round(lat[len(lat) // 2], 2)

        # --- cleanup invariants ----------------------------------------
        # 1. Every finished job's workers reclaimed from the pool (forge
        #    forks share the template's argv, so the pool — which knows
        #    every worker it leased — is the authority here).
        hexes = {h for h in job_hexes if h}
        deadline = time.monotonic() + 30
        leftovers = None
        while time.monotonic() < deadline:
            with raylet.pool._lock:
                leftovers = [h for h in raylet.pool._workers.values()
                             if h.state not in ("dead",)
                             and h.granted_env.get("RAY_TPU_JOB_ID")
                             in hexes]
            if not leftovers:
                break
            time.sleep(0.5)
        assert not leftovers, \
            f"{len(leftovers)} workers survived their job's finish"
        # 2. No driver process (or descendant carrying the mark) outlived
        #    its job — /proc cmdline scan.
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and _pids_with_mark(mark):
            time.sleep(0.2)
        orphans = _pids_with_mark(mark)
        assert orphans == [], f"orphan job processes: {orphans}"
        # 3. Zero leaked unsealed store buffers once the jobs drain.
        deadline = time.monotonic() + 20
        unsealed = None
        while time.monotonic() < deadline:
            unsealed = raylet.store.stats()["num_unsealed"]
            if unsealed == 0:
                break
            time.sleep(0.2)
        assert unsealed == 0, f"unsealed buffers leaked: {unsealed}"
        out["jobs_store_unsealed_after"] = unsealed
        out["jobs_orphan_workers"] = 0
    finally:
        try:
            client.close()
        except Exception:  # noqa: BLE001 — client may have died with GCS
            pass
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001 — teardown is best effort
            pass
    # 4. Cold-spawned worker processes died with the cluster: the /proc
    #    argv diff against the pre-init snapshot must drain to empty.
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline \
            and (_cold_worker_pids() - workers_before):
        time.sleep(0.2)
    leaked = _cold_worker_pids() - workers_before
    assert not leaked, f"cold-spawned workers outlived the cluster: {leaked}"
    return out


def main(out=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-core", action="store_true")
    ap.add_argument("--skip-train", action="store_true")
    ap.add_argument("--skip-ppo", action="store_true")
    ap.add_argument("--skip-serve", action="store_true")
    ap.add_argument("--skip-inference", action="store_true")
    ap.add_argument("--skip-sharded", action="store_true")
    ap.add_argument("--skip-envelope", action="store_true")
    ap.add_argument("--skip-envelope100", action="store_true",
                    help="skip the 100-node wide envelope (placement/"
                         "broadcast/collective width + chaos-at-width)")
    ap.add_argument("--envelope100-smoke", action="store_true",
                    help="run ONLY the bounded 100-node smoke (gate "
                         "step: placement + one seeded node kill with "
                         "autoscaler replacement) and exit nonzero on "
                         "any hang/loss/double-execution")
    ap.add_argument("--sharded-smoke", action="store_true",
                    help="run ONLY the bounded pipeline-training smoke "
                         "(gate step: pp=2 parity bitwise with zero "
                         "recompiles, 1F1B beats the sequential A/B, "
                         "seeded kill-a-stage resharded resume, <60s) "
                         "and exit nonzero on any breach")
    ap.add_argument("--skip-collective", action="store_true")
    ap.add_argument("--skip-pull", action="store_true")
    ap.add_argument("--skip-tracing", action="store_true")
    ap.add_argument("--skip-chaos", action="store_true")
    ap.add_argument("--skip-zoo", action="store_true")
    ap.add_argument("--chaos-smoke", action="store_true",
                    help="run ONLY the seeded chaos smoke (gate step: one "
                         "node kill under light serve load, <60s) and "
                         "exit nonzero on any hang/recovery failure")
    ap.add_argument("--skip-ingest", action="store_true",
                    help="skip the streaming ingest bench (windowed "
                         "shuffle epoch + train-shard stall A/B + "
                         "mid-shuffle node kill)")
    ap.add_argument("--ingest-smoke", action="store_true",
                    help="run ONLY the bounded ingest smoke (gate step: "
                         "one seeded node kill mid-shuffle, hard asserts "
                         "on bounded recompute, <60s) and exit nonzero "
                         "on any hang/unbounded-recovery failure")
    ap.add_argument("--inference-smoke", action="store_true",
                    help="run ONLY the bounded inference smoke (gate "
                         "step: prefix-cache A/B + spec-decode quick "
                         "runs, hard asserts on zero recompiles and "
                         "zero leaked blocks) and exit nonzero on any "
                         "invariant breach")
    ap.add_argument("--skip-query", action="store_true",
                    help="skip the distributed query bench (sort/"
                         "groupby/join through the windowed shuffle + "
                         "locality-routing A/B)")
    ap.add_argument("--query-smoke", action="store_true",
                    help="run ONLY the bounded query smoke (gate step: "
                         "sort/groupby/join row-identity with bounded "
                         "driver sample + locality A/B cross-node byte "
                         "drop, <60s) and exit nonzero on any invariant "
                         "breach")
    ap.add_argument("--skip-jobs", action="store_true",
                    help="skip the job-tier bench (submission plane, "
                         "runtime-env forge, jobs-as-tenants)")
    ap.add_argument("--jobs-smoke", action="store_true",
                    help="run ONLY the bounded job-tier smoke (gate "
                         "step: cold vs forge-template submit latency "
                         ">=2x, 3 concurrent tenant jobs, zero orphan "
                         "processes via /proc scan, num_unsealed 0) and "
                         "exit nonzero on any invariant breach")
    args = ap.parse_args()

    # This process never opens the chip. Its own jax (the engine legs run
    # here on LlamaConfig.tiny) is pinned to CPU before the first import;
    # the legs that need the chip (bench_gpt2_train, bench_gpt2_long,
    # GPT2Sampler) run in TPU-granted workers, which name their platform
    # themselves. A parent holding the chip would make those children
    # fail or hang, and until now only section order kept them apart.
    os.environ["JAX_PLATFORMS"] = "cpu"

    import ray_tpu

    if args.envelope100_smoke:
        stream = out or sys.stdout
        try:
            smoke = bench_envelope100(quick=True, smoke=True)
        except Exception as e:  # noqa: BLE001 — the gate needs the reason
            print(json.dumps({"envelope100_smoke_error":
                              f"{type(e).__name__}: {e}"}), file=stream)
            sys.exit(1)
        print(json.dumps({"envelope100_smoke": smoke}), file=stream)
        stream.flush()
        sys.exit(0)

    if args.ingest_smoke:
        stream = out or sys.stdout
        try:
            smoke = bench_ingest(quick=True, smoke=True)
        except Exception as e:  # noqa: BLE001 — the gate needs the reason
            print(json.dumps({"ingest_smoke_error":
                              f"{type(e).__name__}: {e}"}), file=stream)
            sys.exit(1)
        print(json.dumps({"ingest_smoke": smoke}), file=stream)
        stream.flush()
        sys.exit(0)

    if args.inference_smoke:
        stream = out or sys.stdout
        try:
            smoke = bench_inference(quick=True, smoke=True)
        except Exception as e:  # noqa: BLE001 — the gate needs the reason
            print(json.dumps({"inference_smoke_error":
                              f"{type(e).__name__}: {e}"}), file=stream)
            sys.exit(1)
        print(json.dumps({"inference_smoke": smoke}), file=stream)
        stream.flush()
        sys.exit(0)

    if args.query_smoke:
        stream = out or sys.stdout
        try:
            smoke = bench_query(quick=True, smoke=True)
        except Exception as e:  # noqa: BLE001 — the gate needs the reason
            print(json.dumps({"query_smoke_error":
                              f"{type(e).__name__}: {e}"}), file=stream)
            sys.exit(1)
        print(json.dumps({"query_smoke": smoke}), file=stream)
        stream.flush()
        sys.exit(0)

    if args.jobs_smoke:
        stream = out or sys.stdout
        try:
            smoke = bench_jobs(quick=True, smoke=True)
        except Exception as e:  # noqa: BLE001 — the gate needs the reason
            print(json.dumps({"jobs_smoke_error":
                              f"{type(e).__name__}: {e}"}), file=stream)
            sys.exit(1)
        print(json.dumps({"jobs_smoke": smoke}), file=stream)
        stream.flush()
        sys.exit(0)

    if args.sharded_smoke:
        stream = out or sys.stdout
        try:
            smoke = bench_sharded(quick=True, smoke=True)
        except Exception as e:  # noqa: BLE001 — the gate needs the reason
            print(json.dumps({"sharded_smoke_error":
                              f"{type(e).__name__}: {e}"}), file=stream)
            sys.exit(1)
        print(json.dumps({"sharded_smoke": smoke}), file=stream)
        stream.flush()
        sys.exit(0)

    if args.chaos_smoke:
        stream = out or sys.stdout
        try:
            smoke = bench_chaos(quick=True, smoke=True)
        except Exception as e:  # noqa: BLE001 — the gate needs the reason
            print(json.dumps({"chaos_smoke_error":
                              f"{type(e).__name__}: {e}"}), file=stream)
            sys.exit(1)
        print(json.dumps({"chaos_smoke": smoke}), file=stream)
        stream.flush()
        sys.exit(0)

    extra: dict = {}
    value = 0.0
    try:
        if not ray_tpu.is_initialized():
            ray_tpu.init(num_cpus=4)
    except Exception as e:  # noqa: BLE001
        extra["init_error"] = f"{type(e).__name__}: {e}"

    # Every section is blast-isolated: one failure can never zero the others
    # (round-2 postmortem — a kernel bug erased the whole round's numbers).
    if not args.skip_train:
        # No retry without flash: the headline is the flash step, and the
        # XLA reference's number must never appear under its name.
        try:
            train_metrics = bench_gpt2_train(args.quick)
        except Exception as e:  # noqa: BLE001
            extra["train_error"] = f"{type(e).__name__}: {e}"
            train_metrics = {}
        extra.update(train_metrics)
        value = float(train_metrics.get("tokens_per_sec", 0.0))
        # Long-context: seq=8192 with flash + remat, then a fresh-process
        # probe at the same shapes for the persistent-compile-cache number.
        try:
            long_metrics = bench_gpt2_long(args.quick)
            extra.update(long_metrics)
            if not args.quick and long_metrics.get("batch_size_s8192"):
                extra.update(bench_gpt2_long(
                    args.quick,
                    cached_probe_bs=long_metrics["batch_size_s8192"]))
        except Exception as e:  # noqa: BLE001
            extra["long_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_core:
        try:
            extra.update(bench_core(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["core_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_ppo:
        try:
            from ray_tpu.rllib.tuned_examples import atari_available

            extra["atari_unavailable"] = not atari_available()
        except Exception:  # noqa: BLE001
            extra["atari_unavailable"] = True
        try:
            extra.update(bench_ppo(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["ppo_error"] = f"{type(e).__name__}: {e}"
        try:
            extra.update(bench_impala(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["impala_error"] = f"{type(e).__name__}: {e}"
        try:
            extra.update(bench_learner_dp(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["learner_dp_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_serve:
        try:
            extra.update(bench_serve(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["serve_error"] = f"{type(e).__name__}: {e}"
        try:
            extra.update(bench_serve_fastpath(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["serve_fastpath_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_inference:
        try:
            extra.update(bench_inference(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["inference_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_sharded:
        try:
            extra.update(bench_sharded(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["sharded_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_zoo:
        try:
            extra.update(bench_zoo(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["zoo_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_envelope:
        try:
            extra.update(bench_envelope(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["envelope_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_envelope100:
        try:
            extra.update(bench_envelope100(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["envelope100_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_pull:
        try:
            extra.update(bench_pull_pipelining(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["pull_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_collective:
        try:
            extra.update(bench_collective(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["collective_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_tracing:
        try:
            extra.update(bench_tracing(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["tracing_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_chaos:
        try:
            extra.update(bench_chaos(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["chaos_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_ingest:
        try:
            extra.update(bench_ingest(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["ingest_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_query:
        try:
            extra.update(bench_query(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["query_error"] = f"{type(e).__name__}: {e}"
    if not args.skip_jobs:
        try:
            extra.update(bench_jobs(args.quick))
        except Exception as e:  # noqa: BLE001
            extra["jobs_error"] = f"{type(e).__name__}: {e}"
    try:
        ray_tpu.shutdown()
    except Exception:
        pass

    line = {
        "metric": "gpt2_small_train_tokens_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "tokens/s",
        "vs_baseline": round(value / BASELINE_TOKENS_PER_SEC, 3),
        "extra": {k: (round(v, 4) if isinstance(v, float) else v)
                  for k, v in extra.items()},
    }
    stream = out or sys.stdout
    print(json.dumps(line), file=stream)
    stream.flush()
    # Nonzero exit when the headline path degraded or failed, so CI (and
    # scripts/gate.sh) can catch it — blast isolation keeps the other
    # numbers recorded either way.
    if not args.skip_train and ("train_error" in extra
                                or "train_flash_error" in extra
                                or "init_error" in extra):
        sys.exit(1)


if __name__ == "__main__":
    # Keep stdout clean for the single JSON line: everything the framework
    # prints during the run (teardown notices etc.) goes to stderr.
    import contextlib

    real_stdout = sys.stdout
    with contextlib.redirect_stdout(sys.stderr):
        main(out=real_stdout)
