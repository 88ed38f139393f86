"""Builder `gpt2_train`: a GPT-2 configuration trained through
`JaxTrainer` -> the TPU-granted worker -> `make_train_step`, exactly as
`chip_smoke.py` legs A and C drive it.

`run(ctx)` executes in the benchmark's parent process (never touches
jax); `train_loop(config)` executes in the granted worker, which holds
the chip, and does everything that needs it: weights from the seed under
one jit, the on-chip check against the plain reference, compile, warm-up,
the measured window, and (in a traced run) the profiler and the trace's
reduction.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict

# Step-0 loss of the system (bf16 activations over f32 parameters, Pallas
# or XLA attention) against the float32 `highest` reference on the same
# two sequences. bf16 rounds each logit to ~2^-9 relative (~1e-3 absolute
# at init, where logits have std ~0.6); over 2 x 1023 positions and a
# 50k-wide softmax that averages out: 1.1e-4 on the chip (11.001091 vs
# 11.000978, PR 23). An 8-bit float anywhere in the forward pass would be
# ~16x that and fail.
LOSS_TOLERANCE = 1e-3
WARMUP_STEPS = 2
TRACED_SECONDS = 3.0


def model_config(cfg: Dict[str, Any], seq: int):
    from ray_tpu.models.gpt2 import GPT2Config

    return GPT2Config(vocab_size=int(cfg["vocab_size"]), n_positions=seq,
                      n_embd=int(cfg["n_embd"]), n_layer=int(cfg["n_layer"]),
                      n_head=int(cfg["n_head"]))


def reference_weights(params, n_layer: int) -> Dict[str, Any]:
    """The program's flax pytree under the published parameter names."""
    p = params["params"] if "params" in params else params
    import flax.linen as nn

    p = nn.unbox(p)
    out = {"wte": p["wte"], "wpe": p["wpe"],
           "ln_f.g": p["ln_f"]["scale"], "ln_f.b": p["ln_f"]["bias"]}
    names = {"ln_1": "ln_1", "ln_2": "ln_2", "c_attn": "attn.c_attn",
             "c_proj": "attn.c_proj", "c_fc": "mlp.c_fc",
             "mlp_proj": "mlp.c_proj"}
    for i in range(n_layer):
        block = p[f"h_{i}"]
        for ours, theirs in names.items():
            leaf = block[ours]
            if "kernel" in leaf:
                out[f"h.{i}.{theirs}.w"] = leaf["kernel"]
                out[f"h.{i}.{theirs}.b"] = leaf["bias"]
            else:
                out[f"h.{i}.{theirs}.g"] = leaf["scale"]
                out[f"h.{i}.{theirs}.b"] = leaf["bias"]
    return out


def make_batch(rng, batch: int, seq: int, vocab: int):
    """Seeded token ids with a skewed unigram distribution (u^3 over the
    vocabulary), so that the loss has something to learn and falls."""
    import numpy as np

    return (vocab * rng.random((batch, seq)) ** 3).astype(np.int32)


def train_loop(config: Dict[str, Any]) -> None:
    t_first_line = time.monotonic()
    import collections
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu._jax_env import compilation_cache_dir
    from ray_tpu.models.gpt2 import (GPT2, make_eval_step, make_train_step,
                                     mesh_shardings_for)
    from ray_tpu.ops import attention
    from ray_tpu.parallel.sharding import named_sharding
    from ray_tpu.train import session

    from benchmarks import jaxwatch, xplane
    from benchmarks.reference import gpt2_plain

    seen = jaxwatch.watch()

    cfg, seq = config["model"], int(config["seq"])
    seed = int(config["seed"])
    key_seed = seed % (2 ** 31 - 1)
    mesh = session.get_mesh()
    devices = jax.local_devices()
    batch_size = int(config["per_chip_batch"]) * len(devices)
    mc = model_config(cfg, seq)
    model = GPT2(mc)
    spans = {"worker_first_line": t_first_line}

    # Weights on the device, in one jitted call, from the seed. The key is
    # an ARGUMENT, so that every seed shares one compiled program
    # (`init_sharded` bakes its seed into the program: PERF.md, Open
    # questions; this is its body with the key passed in).
    t0 = time.monotonic()
    key = jax.random.PRNGKey(key_seed)
    if mesh is None:
        params = jax.jit(lambda k: model.init(
            k, jnp.zeros((1, seq), jnp.int32)))(key)
        put = jax.device_put
    else:
        shardings = mesh_shardings_for(model, mesh, (batch_size, seq))
        params = jax.jit(
            lambda k: model.init(k, jnp.zeros((batch_size, seq), jnp.int32)),
            out_shardings=shardings)(key)
        sharding = named_sharding(mesh, "batch", None)
        put = lambda a: jax.device_put(a, sharding)
    jax.block_until_ready(params)
    spans["init_s"] = time.monotonic() - t0

    rng = np.random.default_rng(seed)
    first = make_batch(rng, batch_size, seq, mc.vocab_size)

    # The on-chip check against the plain reference: two seeded sequences,
    # forward and loss, before the optimizer state takes its memory.
    t0 = time.monotonic()
    sample = jnp.asarray(first[:2])
    one = devices[0]
    p1 = params if mesh is None else jax.device_put(jax.tree.map(
        lambda a: a.addressable_shards[0].data, params), one)
    sample1 = jax.device_put(sample, one)
    ev = make_eval_step(model)
    system_loss = float(ev(p1, {"input_ids": sample1, "labels": sample1}))
    ref_logits = jax.jit(
        lambda w, ids: gpt2_plain.forward(
            w, ids, mc.n_layer, mc.n_head,
            eps=float(cfg.get("layer_norm_epsilon", 1e-5))))(
        reference_weights(p1, mc.n_layer), sample1)
    reference_loss = float(gpt2_plain.next_token_loss(ref_logits, sample1))
    first_loss_one_chip = None
    if mesh is not None:
        # The whole first batch on ONE chip, forward only, in per-chip
        # slices: what the four-chip step's first loss must equal.
        ids1 = jax.device_put(jnp.asarray(first), one)
        n = len(devices)
        first_loss_one_chip = sum(
            float(ev(p1, {"input_ids": c, "labels": c}))
            for c in jnp.split(ids1, n)) / n
        del ids1
    del ref_logits, p1, sample1
    spans["reference_check_s"] = time.monotonic() - t0

    opt = optax.adamw(float(config["lr"]),
                      weight_decay=float(config["weight_decay"]))
    opt_state = jax.jit(opt.init)(params)
    batch0 = put(first)
    batch = {"input_ids": batch0, "labels": batch0}
    step = make_train_step(model, opt, mesh=mesh, donate=True)
    attention.reset_pallas_status()
    t0 = time.monotonic()
    misses0, hits0 = seen["misses"], seen["hits"]
    compiled = step.lower(params, opt_state, batch).compile()
    params, opt_state, loss = compiled(params, opt_state, batch)
    first_loss = float(loss)
    spans["compile_s"] = time.monotonic() - t0
    spans["compile_cache_misses"] = seen["misses"] - misses0
    spans["compile_cache_hits"] = seen["hits"] - hits0
    attention_calls = attention.pallas_status()

    def next_batch():
        with jax.profiler.TraceAnnotation("bench.batch_fetch"):
            ids = put(make_batch(rng, batch_size, seq, mc.vocab_size))
        return {"input_ids": ids, "labels": ids}

    # The marker brackets a traced window on the device's own timeline.
    marker = jax.jit(lambda x: x + 1)
    mark = jax.device_put(jnp.zeros((), jnp.int32), devices[0])
    marker(mark).block_until_ready()

    nxt = next_batch()
    for _ in range(WARMUP_STEPS):
        cur, nxt = nxt, next_batch()
        params, opt_state, loss = compiled(params, opt_state, cur)
    loss.block_until_ready()

    trace = bool(config["trace"])
    seconds = float(config["seconds"])
    timed_seconds = max(1.0, seconds - TRACED_SECONDS) if trace else seconds
    compiles_before = seen["compiles"]
    in_flight = collections.deque()

    def run_steps(until_s, annotate):
        """Steps back to back until `until_s` of host time have passed:
        the next batch is made and put while the device runs this one,
        and the host stays at most two steps ahead of the device."""
        nonlocal params, opt_state, nxt, loss
        t_start = time.monotonic()
        steps = 0
        while True:
            cur = nxt
            if annotate:
                with jax.profiler.TraceAnnotation("bench.step_dispatch"):
                    params, opt_state, loss = compiled(params, opt_state, cur)
            else:
                params, opt_state, loss = compiled(params, opt_state, cur)
            steps += 1
            in_flight.append(loss)
            nxt = next_batch()
            if len(in_flight) > 2:
                if annotate:
                    with jax.profiler.TraceAnnotation("bench.wait_device"):
                        in_flight.popleft().block_until_ready()
                else:
                    in_flight.popleft().block_until_ready()
            if time.monotonic() - t_start >= until_s:
                break
        loss.block_until_ready()
        in_flight.clear()
        return steps, time.monotonic() - t_start

    spans["first_timed_step"] = time.monotonic()
    steps, elapsed = run_steps(timed_seconds, False)
    tokens = steps * batch_size * seq
    digest = None
    traced = {}
    if trace:
        trace_dir = os.path.join(config["out_dir"], "trace")
        jax.profiler.start_trace(trace_dir)
        marker(mark).block_until_ready()
        t_steps, t_elapsed = run_steps(TRACED_SECONDS, True)
        marker(mark).block_until_ready()
        jax.profiler.stop_trace()
        traced = {"steps": t_steps, "elapsed_s": t_elapsed}
        digest = xplane.reduce_dir(
            trace_dir, config["out_dir"] if config.get("keep_trace_sample")
            else None, span_ns=int(0.2e9), max_events=12000)
    last_loss = float(loss)
    stats = [d.memory_stats() or {} for d in devices]
    session.report({
        "spans": spans,
        "steps": steps, "tokens": tokens, "elapsed_s": elapsed,
        "batch": [batch_size, seq], "traced": traced,
        "first_loss": first_loss, "last_loss": last_loss,
        "finite": math.isfinite(first_loss) and math.isfinite(last_loss),
        "first_loss_one_chip": first_loss_one_chip,
        "system_loss_2seq": system_loss,
        "reference_loss_2seq": reference_loss,
        "attention": attention_calls,
        "compiles_in_window": seen["compiles"] - compiles_before,
        "cache_hits": seen["hits"], "cache_misses": seen["misses"],
        "cache_dir": compilation_cache_dir(),
        "memory_peak_bytes": max(
            (s.get("peak_bytes_in_use", 0) for s in stats), default=0),
        "trace": digest,
    })


def run(ctx) -> Dict[str, Any]:
    """Parent side: grant, fit, verdict. Returns the facts of the run."""
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train.backend import JaxConfig

    cfg, traffic, chips = ctx.config, ctx.traffic, ctx.cell["chips"]
    train = cfg["train"]
    mesh = MeshSpec(dict(traffic["mesh"])) if traffic.get("mesh") else None
    if ctx.rehearsal:
        scaling = ScalingConfig(num_workers=1, mesh=mesh)
    else:
        scaling = ScalingConfig(num_workers=1, use_tpu=True,
                                tpus_per_worker=chips, mesh=mesh)
    spans = {"fit_called": time.monotonic()}
    result = JaxTrainer(
        train_loop,
        train_loop_config={
            "model": {k: cfg[k] for k in ("vocab_size", "n_embd", "n_layer",
                                          "n_head", "layer_norm_epsilon")
                      if k in cfg},
            "seq": traffic["seq"], "per_chip_batch": train["per_chip_batch"],
            "lr": train["lr"], "weight_decay": train["weight_decay"],
            "seed": ctx.seed, "seconds": ctx.seconds, "trace": ctx.trace,
            "out_dir": ctx.out_dir,
            "keep_trace_sample": ctx.keep_trace_sample},
        jax_config=JaxConfig(distributed=False, mesh=mesh),
        scaling_config=scaling,
        run_config=RunConfig(name="bench_fit", storage_path=os.path.join(
            ctx.out_dir, "results")),
    ).fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    spans.update(m["spans"])
    n_dev = m["n_devices"]
    problems = []
    calls = m["attention"]
    if not calls or any(c["path"] != "pallas" for c in calls):
        problems.append(f"attention calls off the Pallas path: {calls}")
    if m["compiles_in_window"]:
        problems.append(f"{m['compiles_in_window']} compilations inside "
                        f"the window")
    gap = abs(m["system_loss_2seq"] - m["reference_loss_2seq"])
    if not gap <= LOSS_TOLERANCE:
        problems.append(
            f"step-0 loss {m['system_loss_2seq']} vs plain reference "
            f"{m['reference_loss_2seq']}: gap {gap} > {LOSS_TOLERANCE}")
    if not (m["finite"] and m["last_loss"] < m["first_loss"]):
        problems.append(f"loss did not fall: {m['first_loss']} -> "
                        f"{m['last_loss']}")
    if chips > 1 and not abs(m["first_loss"] - m["first_loss_one_chip"]) \
            < 5e-2:
        problems.append(f"first loss {m['first_loss']} on {chips} chips vs "
                        f"{m['first_loss_one_chip']} on one")
    rate = m["tokens"] / m["elapsed_s"] / n_dev
    ctx.emit(builder="gpt2_train", steps=m["steps"], tokens=m["tokens"],
             elapsed_s=m["elapsed_s"], batch=m["batch"], traced=m["traced"],
             first_loss=m["first_loss"], last_loss=m["last_loss"],
             system_loss_2seq=m["system_loss_2seq"],
             reference_loss_2seq=m["reference_loss_2seq"],
             first_loss_one_chip=m["first_loss_one_chip"],
             attention=calls, cache_hits=m["cache_hits"],
             cache_misses=m["cache_misses"], cache_dir=m["cache_dir"],
             compiles_in_window=m["compiles_in_window"],
             spans=spans)
    return {
        "device": {"platform": m["platform"], "kind": m["device_kind"],
                   "count": n_dev,
                   "memory_peak_bytes": m["memory_peak_bytes"]},
        "attempted": m["steps"], "failed": 0,
        "problems": problems,
        "setup_end": spans["first_timed_step"],
        "spans": spans,
        "counters": {"steps": m["steps"], "tokens": m["tokens"],
                     "cache_hits": m["cache_hits"],
                     "cache_misses": m["cache_misses"]},
        "end_to_end": {"train_tok_s_chip": rate},
        "trace": m["trace"],
    }
