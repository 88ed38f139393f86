"""Builder `qwen3_next_train`: a Qwen3-Next configuration (one chip's
share of an expert-parallel group) trained through `JaxTrainer` -> the
TPU-granted worker -> `make_train_step(model, opt, loss_fn=...)`.

`run(ctx)` executes in the benchmark's parent process (never touches jax);
`train_loop(config)` executes in the granted worker, which holds the chip:
weights from the seed under one jit, the on-chip check against the plain
reference at the timed sizes, compile, warm-up, the measured window, and
(in a traced run) the profiler and the trace's reduction.

What this repeats of `gpt2_train.py` (the batch generator, `run_steps`, the
traced window, the verdict's frame) is a note for a later `benchmark` PR:
a `model_config` PR adds files and edits none.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict

# The comparison that decides `correct`. The system computes in bf16
# activations over f32 parameters with an f32 router, an f32 carried
# recurrent state and f32 gate sums; the reference in f32 at `highest`.
#
# A randomly initialised 512-way router turns ANY rounding upstream of it
# into a different top-10 set on a tenth to a third of a layer's tokens
# (ranks 10 and 11 lie 0.04 apart in the logits on average), and a flipped
# token moves whole rows of logits and gradients. So nothing here compares
# across a flip. The reference is routed with the SYSTEM's picks (its gates
# from its own probabilities), and then loss, logits and gradients read
# rounding; and the three places whose precision the configuration states
# are each run ALONE on the reference's own operands (`taps`): the router on
# what the reference's router read, the recurrence on the reference's q, k,
# v, g, beta, the held experts on the reference's input, gates and picks.
# Last, the timed step's own first update is held against AdamW worked from
# the reference's gradient.
#
# Every limit stands between two readings (PERF.md section 6, PR 34, my chip
# runs; the table there has every number): the largest the system gave over
# its seeds, and what the control that limit is there for gave through this
# same `check_problems` (`benchmarks/qwen3next_controls.py`): the reference
# with a bf16 router, with its state carried in bf16, with its parameters
# held in bf16, a state left as it was, and four planted faults (the held
# experts, the embedding's rows or the router's columns one place along;
# one layer's mixer left out). Each control is refused by at least one
# limit; no limit is above both its readings.
#
# Mean next-token loss of a sequence, and of the timed step's first batch:
# the system 4.8e-6 to 4.3e-4 over fifteen seeds. The loss of a random
# initialisation is the number a fault moves least: the embedding's rows
# one place along read 1.9e-3 to 3.8e-2 by the seed, a mixer left out 1.4e-4
# to 2.6e-2, and where one slips under this limit another refuses it.
LOSS_TOLERANCE = 2e-3
# Logits at LOGIT_POSITIONS evenly spread positions, the largest difference
# over the largest reference logit: the system 0.028 to 0.040; the faults
# 0.18 to 0.43 (the two that misroute) and 1.0 to 1.5.
LOGIT_TOLERANCE = 0.1
LOGIT_POSITIONS = 64
# Gradients of the named leaves, |g - g_ref|_2 / |g_ref|_2, the reference
# routed with the system's picks: the system 0.02 to 0.05 on most leaves,
# up to 0.115 on the last layer's router and on A_log and dt_bias (32
# numbers each); the faults 0.67 to 2.1 on the leaves they reach.
GRAD_TOLERANCE = 0.25
# The router alone on the reference's input: the largest difference of a
# probability over the largest probability (the system 0.0 to the last
# digit; bf16 operands 2.4e-3 to 3.4e-3), and the tokens whose top-k SET
# differs although the reference's ranks k and k+1 lie more than
# ROUTER_MARGIN apart in the logits. Those must be none: the system none of
# 31,900 on every seed; bf16 operands 139 to 169 a layer; the router's
# columns one place along all of them.
ROUTER_TOLERANCE = 1e-4
ROUTER_MARGIN = 1e-3
# The same count in the assembled model, where the router's input has passed
# bf16 layers (which move a logit by ~0.02 by the last layer): of the tokens
# whose margin is over MODEL_MARGIN (~550 a layer), the share that got
# another set. The system at most 1 token of a layer (0.2%); the faults 30%
# to 100% of the layers they reach.
MODEL_MARGIN = 0.1
MODEL_FLIP_SHARE = 0.05
# The recurrence alone on the reference's operands rounded to bf16 (the
# stated precision of its inputs), against the position-by-position scan of
# the same: |o - o_ref|_2 / |o_ref|_2 of the worst head. Twice: with the
# layer's own gates, and with every head's log decay at PROBE_LOG_DECAY a
# position: a head that remembers across chunks, as trained heads do and
# most heads of a random initialisation (A_log from log U(0.001, 16)) do
# not. Only there does the precision the state is CARRIED in show, because
# the kernels hand the state to the MXU as bf16 in every chunk either way:
# the system 0.0042 to 0.0043 and 0.0053; the reference with its state
# carried in bf16 0.0017 to 0.0058 (on two seeds of three BELOW the system:
# no limit could refuse it there) and 0.0203 to 0.0207 on the probe.
RECURRENCE_TOLERANCE = 0.01
PROBE_LOG_DECAY = -1.0 / 1024
# The held experts alone on the reference's input, gates and picks: the
# system 0.0054 in every layer and seed (bf16 operands of three products);
# the held experts one place along 1.40 to 1.41.
EXPERT_TOLERANCE = 0.03
# The timed step's first update of the named leaves against AdamW worked
# from the reference's gradient, over the elements whose reference gradient
# is at least half its root mean square (Adam's first step is the rate
# times the gradient's SIGN: where the gradient is within rounding of zero
# the sign is noise). The system 0.0003 to 0.025, and 0.053 to 0.091 on the
# last layer's router (whose gradient is the noisiest, above); parameters
# held in bf16 0.99 to 1.0 (an update of 1.5e-6 is under half a bf16 ulp of
# 0.02); 1 is what a state left unchanged reads.
UPDATE_TOLERANCE = 0.3
# The displayed loss has to FALL over the window by at least this: the seeds
# fell by 1.14 to 1.22 (10.26 -> 9.04..9.12); fresh batches at unchanged
# parameters read 10.235 to 10.278, a "fall" of 0.043 at most.
LOSS_FALL = 0.3
WARMUP_STEPS = 2
TRACED_SECONDS = 3.0
NAMED_GRADIENTS = (
    "embed_tokens", "layers.0.mlp.gate", "layers.3.mlp.gate",
    "layers.0.linear_attn.A_log", "layers.0.linear_attn.dt_bias",
    "layers.0.linear_attn.conv1d", "layers.1.linear_attn.in_proj_qkvz",
    "layers.0.mlp.experts.gate_proj", "layers.0.mlp.experts.up_proj",
    "layers.0.mlp.experts.down_proj")
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "full_attention_interval", "num_attention_heads", "num_key_value_heads",
    "head_dim", "partial_rotary_factor", "rope_theta",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "rms_norm_eps")
ADAM_EPS = 1e-8


def model_config(cfg: Dict[str, Any]):
    """The program's configuration from the file's keys: `num_experts` is
    the count HELD here, `deployment.experts_routed` the router's width."""
    from ray_tpu.models.qwen3_next import Qwen3NextConfig

    share = cfg["deployment"]
    return Qwen3NextConfig(
        **{k: cfg[k] for k in MODEL_KEYS},
        num_experts=int(share["experts_routed"]),
        held_experts=(int(share["first_expert_held"]),
                      int(cfg["num_experts"])),
        router_aux_loss_coef=float(cfg["train"]["router_aux_loss_coef"]),
        remat=bool(cfg["train"]["remat"]))


def reference_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {**{k: cfg[k] for k in MODEL_KEYS},
            "router_width": int(cfg["deployment"]["experts_routed"])}


def make_batch(rng, batch: int, seq: int, vocab: int):
    """Seeded token ids with a skewed unigram distribution (u^3 over the
    vocabulary slice), so that the loss has something to learn and falls."""
    import numpy as np

    return (vocab * rng.random((batch, seq)) ** 3).astype(np.int32)


def init_params(model, seed: int, seq: int):
    """Weights on the device, in one jitted call, from the seed; the key is
    an ARGUMENT, so that every seed shares one compiled program."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda k: model.init(
        k, jnp.zeros((1, min(seq, 128)), jnp.int32)))(
        jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def learning_rate(train: Dict[str, Any]):
    """Linear warm-up that STARTS at lr / warmup_steps, so that the first
    timed-path step, the one held against the reference, moves the
    parameters."""
    import optax

    lr, warmup = float(train["lr"]), int(train["warmup_steps"])
    return optax.linear_schedule(lr / warmup, lr, warmup - 1)


def relative_error(a, b) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30))


def named(tree):
    # one held expert's three matrices, not all of them
    return {k: (tree[k][0] if ".experts." in k else tree[k])
            for k in NAMED_GRADIENTS}


def decided_flips(index, probs, margin: float):
    """Per layer, the tokens whose top-k set `index` [layers, seq, k]
    differs from the top-k of `probs` [layers, seq, experts] although ranks
    k and k+1 of `probs` lie more than `margin` apart in the logits; and the
    tokens that were counted at all."""
    import jax
    import jax.numpy as jnp

    k = index.shape[-1]
    top, want = jax.lax.top_k(probs, k + 1)
    decided = jnp.log(top[..., k - 1]) - jnp.log(top[..., k]) > margin
    same = jnp.all(jnp.sort(index, -1) == jnp.sort(want[..., :k], -1), -1)
    return ([int(n) for n in jnp.sum(decided & ~same, -1)],
            [int(n) for n in jnp.sum(decided, -1)])


def worst_head_error(o, want) -> float:
    """o, want [seq, heads, d]: the largest relative error of a head."""
    import jax.numpy as jnp

    o, want = o.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.linalg.norm(o - want, axis=(0, 2))
                         / jnp.linalg.norm(want, axis=(0, 2))))


def probe(operands, log_decay):
    """The recurrence's operands with every head's log decay set to
    `log_decay` a position (None: as they are)."""
    import jax.numpy as jnp

    q, k, v, g, beta = operands
    return (q, k, v, g if log_decay is None else jnp.full_like(g, log_decay),
            beta)


def check_programs(model, mc, cfg, seq: int) -> Dict[str, Any]:
    """The jitted programs of the comparison: the system's and the
    reference's value-and-grad on one sequence, and the system's router,
    recurrence and held experts each alone."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import next_token_loss
    from ray_tpu.models.qwen3_next import published_weights
    from ray_tpu.ops.gated_delta import gated_delta_rule
    from ray_tpu.ops.held_experts import held_expert_mlp, route

    from benchmarks.reference import qwen3_next_plain as plain

    at = jnp.linspace(0, seq - 1, min(LOGIT_POSITIONS, seq)).astype(jnp.int32)
    coef = mc.router_aux_loss_coef
    rc = reference_config(cfg)
    rep = mc.linear_num_value_heads // mc.linear_num_key_heads

    def system(p, ids):
        def objective(p):
            logits, aux = model.apply(p, ids[None], return_aux=True)
            ce = next_token_loss(logits, ids[None])
            return ce + coef * jnp.mean(aux["load_balance"]), (
                ce, logits[0, at], aux["index"].reshape(
                    mc.num_hidden_layers, seq, -1),
                jnp.sum(aux["assigned"]), jnp.sum(aux["placed"]))
        (_, out), grads = jax.value_and_grad(objective, has_aux=True)(p)
        return out, named(published_weights(grads, mc))

    def reference(w, ids, picks):
        chosen = {k: w[k] for k in NAMED_GRADIENTS}

        def objective(chosen):
            logits, free, balance, taps = plain.forward(
                {**w, **chosen}, ids, rc, mc.held_experts, picks=picks,
                taps=True)
            ce = plain.next_token_loss(logits, ids)
            return ce + coef * jnp.mean(balance), (ce, logits[at], taps)
        (_, out), grads = jax.value_and_grad(objective, has_aux=True)(chosen)
        return out, named(grads)

    def rounded(t):
        return t.astype(jnp.bfloat16)

    def recurrence(q, k, v, g, beta):
        return gated_delta_rule(rounded(q)[None], rounded(k)[None],
                                rounded(v)[None], g[None], beta[None])[0]

    def recurrence_reference(q, k, v, g, beta, carry=jnp.float32):
        q, k, v = (rounded(t).astype(jnp.float32) for t in (q, k, v))
        return plain.delta_rule(jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1),
                                v, g, beta, carry)

    def experts(x, gates, index, w_gate_up, w_down):
        return held_expert_mlp(rounded(x), gates, index, w_gate_up, w_down,
                               mc.held_experts, mc.num_experts)[0]

    return {
        "system": jax.jit(system), "reference": jax.jit(reference),
        "weights": jax.jit(lambda p: published_weights(p, mc)),
        "named_weights": jax.jit(lambda p: named(published_weights(p, mc))),
        "router": jax.jit(lambda x, w: route(x, w, mc.num_experts_per_tok)),
        "recurrence": jax.jit(recurrence),
        "recurrence_reference": jax.jit(recurrence_reference,
                                        static_argnames="carry"),
        "experts": jax.jit(experts)}


def check_against_reference(programs, mc, params, first, weights=None):
    """The system against `qwen3_next_plain` on the first timed batch, one
    sequence at a time (the comment above the limits says what is compared
    and why). Returns (the readings, what the update's comparison and the
    controls need of the last sequence: the reference's named gradients,
    its taps, its weights)."""
    import flax.linen as nn
    import jax.numpy as jnp

    if weights is None:
        weights = programs["weights"](params)
    layers = [nn.unbox(params["params"])[f"layers_{i}"]["mlp"]
              for i in range(mc.num_hidden_layers)]
    out = {"loss": [], "reference_loss": [], "logit_gap": [], "flips": [],
           "model_flips": [], "model_decided": [], "router_gap": [],
           "router_flips": [], "router_decided": [], "recurrence_gap": [],
           "recurrence_probe_gap": [],
           "expert_gap": [], "assigned": 0, "placed": 0, "grad_gaps": {}}
    for ids in jnp.asarray(first):
        (ce, logits, picks, assigned, placed), grads = programs["system"](
            params, ids)
        (ref_ce, ref_logits, taps), ref_grads = programs["reference"](
            weights, ids, picks)
        out["loss"].append(float(ce))
        out["reference_loss"].append(float(ref_ce))
        out["logit_gap"].append(float(
            jnp.max(jnp.abs(logits.astype(jnp.float32) - ref_logits))
            / jnp.max(jnp.abs(ref_logits))))
        out["flips"].append(decided_flips(picks, taps["probs"], -1.0)[0])
        for key, got in zip(("model_flips", "model_decided"), decided_flips(
                picks, taps["probs"], MODEL_MARGIN)):
            out[key].append(got)
        out["assigned"] += int(assigned)
        out["placed"] += int(placed)
        for k in NAMED_GRADIENTS:
            out["grad_gaps"][k] = max(out["grad_gaps"].get(k, 0.0),
                                      relative_error(grads[k], ref_grads[k]))
        # each stated precision alone, on the reference's operands
        routed = [programs["router"](x, layer["router"])
                  for x, layer in zip(taps["router_in"], layers)]
        probs = jnp.stack([r[0] for r in routed])
        out["router_gap"].append(float(
            jnp.max(jnp.abs(probs - taps["probs"]))
            / jnp.max(taps["probs"])))
        for key, got in zip(("router_flips", "router_decided"), decided_flips(
                jnp.stack([r[2] for r in routed]), taps["probs"],
                ROUTER_MARGIN)):
            out[key].append(got)
        for key, gates in (("recurrence_gap", None),
                           ("recurrence_probe_gap", PROBE_LOG_DECAY)):
            out[key].append([
                worst_head_error(
                    programs["recurrence"](*probe(operands, gates)),
                    programs["recurrence_reference"](*probe(operands, gates)))
                for operands in taps["recurrence"]])
        out["expert_gap"].append([
            relative_error(programs["experts"](
                x, gates, index, layer["experts_gate_up"],
                layer["experts_down"]), want)
            for x, gates, index, layer, want in zip(
                taps["router_in"], taps["gates"], picks, layers,
                taps["routed"])])
    return out, {"grads": ref_grads, "taps": taps, "picks": picks,
                 "weights": weights}


def update_gaps(before, after, grads, rate: float, decay: float):
    """The named leaves' change over one step (numpy arrays, on the host:
    the step needs the device's memory) against AdamW's FIRST step worked
    from the reference's gradient (both moments from zero, so the
    step is -rate * (g / (|g| + eps) + decay * p)), over the elements whose
    reference gradient is decided (UPDATE_TOLERANCE's comment)."""
    import numpy as np

    gaps = {}
    for k, g in grads.items():
        want = -rate * (g / (np.abs(g) + ADAM_EPS) + decay * before[k])
        decided = np.abs(g) >= 0.5 * np.sqrt(np.mean(g * g))
        gaps[k] = float(
            np.linalg.norm((after[k] - before[k] - want)[decided])
            / np.linalg.norm(want[decided]))
    return gaps


def train_loop(config: Dict[str, Any]) -> None:
    t_first_line = time.monotonic()
    import collections
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu._jax_env import compilation_cache_dir
    from ray_tpu.models.gpt2 import make_train_step
    from ray_tpu.models.qwen3_next import Qwen3Next, make_loss_fn
    from ray_tpu.ops import attention, gated_delta, held_experts

    from benchmarks import jaxwatch, xplane

    seen = jaxwatch.watch()

    cfg, seq = config["model"], int(config["seq"])
    seed = int(config["seed"])
    devices = jax.local_devices()
    batch_size = int(config["per_chip_batch"]) * len(devices)
    mc = model_config(cfg)
    model = Qwen3Next(mc)
    spans = {"worker_first_line": t_first_line}

    t0 = time.monotonic()
    params = init_params(model, seed, seq)
    jax.block_until_ready(params)
    spans["init_s"] = time.monotonic() - t0

    rng = np.random.default_rng(seed)
    first = make_batch(rng, batch_size, seq, mc.vocab_size)

    # Before the optimizer state takes its memory.
    t0 = time.monotonic()
    programs = check_programs(model, mc, cfg, seq)
    check, kept = check_against_reference(programs, mc, params, first)
    # to the host: the step needs the device's memory
    before, reference_grads = jax.device_get(
        (programs["named_weights"](params), kept["grads"]))
    del kept
    spans["reference_check_s"] = time.monotonic() - t0

    # Linear warm-up, as every pretraining run has one: from a random
    # router a constant 3e-4 moved two seeds of six onto a few experts
    # within a hundred steps (PERF.md section 6, PR 34).
    train = cfg["train"]
    rate = learning_rate(train)
    opt = optax.adamw(rate, weight_decay=float(train["weight_decay"]))
    opt_state = jax.jit(opt.init)(params)
    batch0 = jax.device_put(first)
    batch = {"input_ids": batch0, "labels": batch0}
    step = make_train_step(model, opt, donate=True,
                           loss_fn=make_loss_fn(model))
    for reset in (attention.reset_pallas_status,
                  gated_delta.reset_gated_delta_status,
                  held_experts.reset_held_experts_status):
        reset()
    t0 = time.monotonic()
    misses0, hits0 = seen["misses"], seen["hits"]
    compiled = step.lower(params, opt_state, batch).compile()
    memory = compiled.memory_analysis()
    params, opt_state, shown = compiled(params, opt_state, batch)
    first_loss = float(shown["loss"])
    spans["compile_s"] = time.monotonic() - t0
    spans["compile_cache_misses"] = seen["misses"] - misses0
    spans["compile_cache_hits"] = seen["hits"] - hits0
    calls = {"attention": attention.pallas_status(),
             "gated_delta": gated_delta.gated_delta_status(),
             "held_experts": held_experts.held_experts_status()}
    # The timed path's own first update against the reference's AdamW (one
    # sequence a batch: the reference's gradient is that sequence's).
    if batch_size == 1:
        check["update_gaps"] = update_gaps(
            before, jax.device_get(programs["named_weights"](params)),
            reference_grads, float(rate(0)), float(train["weight_decay"]))
    del before, reference_grads, programs

    def next_batch():
        with jax.profiler.TraceAnnotation("bench.batch_fetch"):
            ids = jax.device_put(make_batch(rng, batch_size, seq,
                                            mc.vocab_size))
        return {"input_ids": ids, "labels": ids}

    # The marker brackets a traced window on the device's own timeline.
    marker = jax.jit(lambda x: x + 1)
    mark = jax.device_put(jnp.zeros((), jnp.int32), devices[0])
    marker(mark).block_until_ready()

    nxt = next_batch()
    for _ in range(WARMUP_STEPS):
        cur, nxt = nxt, next_batch()
        params, opt_state, shown = compiled(params, opt_state, cur)
    shown["loss"].block_until_ready()

    trace = bool(config["trace"])
    seconds = float(config["seconds"])
    timed_seconds = max(1.0, seconds - TRACED_SECONDS) if trace else seconds
    compiles_before = seen["compiles"]
    in_flight = collections.deque()

    def run_steps(until_s, annotate):
        """Steps back to back until `until_s` of host time have passed:
        the next batch is made and put while the device runs this one,
        and the host stays at most two steps ahead of the device. The
        routed counts are outputs of the step, kept for every step and
        read after the window: nothing is read here."""
        nonlocal params, opt_state, nxt, shown
        t_start = time.monotonic()
        routed = []
        while True:
            cur = nxt
            if annotate:
                with jax.profiler.TraceAnnotation("bench.step_dispatch"):
                    params, opt_state, shown = compiled(params, opt_state,
                                                        cur)
            else:
                params, opt_state, shown = compiled(params, opt_state, cur)
            routed.append(shown["moe"])
            in_flight.append(shown["loss"])
            nxt = next_batch()
            if len(in_flight) > 2:
                if annotate:
                    with jax.profiler.TraceAnnotation("bench.wait_device"):
                        in_flight.popleft().block_until_ready()
                else:
                    in_flight.popleft().block_until_ready()
            if time.monotonic() - t_start >= until_s:
                break
        shown["loss"].block_until_ready()
        in_flight.clear()
        return routed, time.monotonic() - t_start

    def as_lists(routed):
        # {"load": [steps, layers, held], "assigned", "placed": [steps,
        # layers]}
        return jax.tree.map(lambda *a: np.stack(a).tolist(), *routed)

    spans["first_timed_step"] = time.monotonic()
    routed, elapsed = run_steps(timed_seconds, False)
    steps = len(routed)
    tokens = steps * batch_size * seq
    digest = None
    traced = {}
    if trace:
        trace_dir = os.path.join(config["out_dir"], "trace")
        jax.profiler.start_trace(trace_dir)
        marker(mark).block_until_ready()
        t_routed, t_elapsed = run_steps(TRACED_SECONDS, True)
        marker(mark).block_until_ready()
        jax.profiler.stop_trace()
        traced = {"steps": len(t_routed), "elapsed_s": t_elapsed,
                  "moe": as_lists(t_routed)}
        digest = xplane.reduce_dir(
            trace_dir, config["out_dir"] if config.get("keep_trace_sample")
            else None, span_ns=int(0.2e9), max_events=12000)
    last_loss = float(shown["loss"])
    stats = [d.memory_stats() or {} for d in devices]
    session_report = {
        "spans": spans,
        "steps": steps, "tokens": tokens, "elapsed_s": elapsed,
        "batch": [batch_size, seq], "traced": traced,
        "first_loss": first_loss, "last_loss": last_loss,
        "finite": math.isfinite(first_loss) and math.isfinite(last_loss),
        "check": check, "calls": calls, "moe": as_lists(routed),
        "load_balance": np.asarray(shown["load_balance"]).tolist(),
        "compiles_in_window": seen["compiles"] - compiles_before,
        "cache_hits": seen["hits"], "cache_misses": seen["misses"],
        "cache_dir": compilation_cache_dir(),
        # what the compiler gave the step, beside what the allocator saw
        "step_memory": {
            "arguments": memory.argument_size_in_bytes,
            "outputs": memory.output_size_in_bytes,
            "aliased": memory.alias_size_in_bytes,
            "temporaries": memory.temp_size_in_bytes} if memory else None,
        "memory_peak_bytes": max(
            (s.get("peak_bytes_in_use", 0) for s in stats), default=0),
        "memory_limit_bytes": max(
            (s.get("bytes_limit", 0) for s in stats), default=0),
        "trace": digest,
    }
    from ray_tpu.train import session

    session.report(session_report)


def moe_counters(moe: Dict[str, Any], tokens_per_step: int) -> Dict[str, Any]:
    """The expert layers' counters over a window, from every step's own
    outputs: `moe` = {"load": [steps, layers, held], "assigned", "placed":
    [steps, layers]}."""
    steps, layers = len(moe["assigned"]), len(moe["assigned"][0])
    assigned = sum(map(sum, moe["assigned"]))
    placed = sum(map(sum, moe["placed"]))
    ratios = []
    for step in moe["load"]:
        loads = [x for layer in step for x in layer]
        ratios.append(max(loads) * len(loads) / max(sum(loads), 1))
    return {
        "steps": steps, "assigned": assigned, "placed": placed,
        "dropped": assigned - placed,
        "assigned_per_step": assigned / steps,
        "held_assignments_per_token":
            assigned / (steps * layers * tokens_per_step),
        "load_max": max(x for step in moe["load"] for layer in step
                        for x in layer),
        "load_max_over_mean": sum(ratios) / steps,
    }


def check_problems(check: Dict[str, Any]) -> list:
    """What of the comparison against the reference is over its limit."""
    problems = []
    for ours, theirs in zip(check["loss"], check["reference_loss"]):
        if not abs(ours - theirs) <= LOSS_TOLERANCE:
            problems.append(f"sequence loss {ours} vs plain reference "
                            f"{theirs}: over {LOSS_TOLERANCE}")
    if not max(check["logit_gap"]) <= LOGIT_TOLERANCE:
        problems.append(f"logits off the reference by {check['logit_gap']} "
                        f"of the largest logit: over {LOGIT_TOLERANCE}")
    for name, gap in check["grad_gaps"].items():
        if not gap <= GRAD_TOLERANCE:
            problems.append(f"gradient of {name} off the reference by "
                            f"{gap}: over {GRAD_TOLERANCE}")
    if not max(check["router_gap"]) <= ROUTER_TOLERANCE:
        problems.append(f"the router alone, on the reference's input, is "
                        f"off by {check['router_gap']} of the largest "
                        f"probability: over {ROUTER_TOLERANCE}")
    for where, margin, share in (("router", ROUTER_MARGIN, 0.0),
                                 ("model", MODEL_MARGIN, MODEL_FLIP_SHARE)):
        flips = [n for row in check[where + "_flips"] for n in row]
        decided = [n for row in check[where + "_decided"] for n in row]
        if not all(d and f <= share * d for f, d in zip(flips, decided)):
            problems.append(
                f"{where}: {check[where + '_flips']} tokens of "
                f"{check[where + '_decided']} whose reference margin is "
                f"over {margin} got another top-k set: over {share} of them")
    for key in ("recurrence_gap", "recurrence_probe_gap"):
        if not max(x for row in check[key] for x in row) \
                <= RECURRENCE_TOLERANCE:
            problems.append(f"the recurrence alone, on the reference's "
                            f"operands, is off by {key} {check[key]} in its "
                            f"worst head: over {RECURRENCE_TOLERANCE}")
    worst = max(x for row in check["expert_gap"] for x in row)
    if not worst <= EXPERT_TOLERANCE:
        problems.append(f"the held experts alone, on the reference's input "
                        f"and picks, are off by {check['expert_gap']}: over "
                        f"{EXPERT_TOLERANCE}")
    if check["placed"] != check["assigned"]:
        problems.append(f"check: {check['assigned']} assignments, "
                        f"{check['placed']} rows computed")
    for name, gap in check.get("update_gaps", {"(not compared)": 1.0}).items():
        if not gap <= UPDATE_TOLERANCE:
            problems.append(f"the first step's update of {name} is off the "
                            f"reference's AdamW by {gap}: over "
                            f"{UPDATE_TOLERANCE}")
    return problems


def verdict(m: Dict[str, Any], counters: Dict[str, Any]) -> list:
    """Everything that makes the run not `correct`."""
    problems = []
    calls, check = m["calls"], m["check"]
    for what, name in (("attention", "attention"),
                       ("gated_delta", "recurrence"),
                       ("held_experts", "expert layer")):
        if not calls[what] or any(c["path"] != "pallas"
                                  for c in calls[what]):
            problems.append(f"{name} calls off the kernel path: "
                            f"{calls[what]}")
    if m["compiles_in_window"]:
        problems.append(f"{m['compiles_in_window']} compilations inside "
                        f"the window")
    problems += check_problems(check)
    mean_ref = sum(check["reference_loss"]) / len(check["reference_loss"])
    if not abs(m["first_loss"] - mean_ref) <= LOSS_TOLERANCE:
        problems.append(f"the timed step's first loss {m['first_loss']} vs "
                        f"plain reference {mean_ref}")
    if counters["dropped"]:
        problems.append(f"{counters['dropped']} assignments dropped")
    if not (m["finite"] and m["first_loss"] - m["last_loss"] >= LOSS_FALL):
        problems.append(f"loss fell by less than {LOSS_FALL}: "
                        f"{m['first_loss']} -> {m['last_loss']}")
    return problems


def run(ctx) -> Dict[str, Any]:
    """Parent side: grant, fit, verdict. Returns the facts of the run."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train.backend import JaxConfig

    cfg, traffic, chips = ctx.config, ctx.traffic, ctx.cell["chips"]
    if ctx.rehearsal:
        scaling = ScalingConfig(num_workers=1)
    else:
        scaling = ScalingConfig(num_workers=1, use_tpu=True,
                                tpus_per_worker=chips)
    spans = {"fit_called": time.monotonic()}
    result = JaxTrainer(
        train_loop,
        train_loop_config={
            "model": cfg, "seq": traffic["seq"],
            "per_chip_batch": cfg["train"]["per_chip_batch"],
            "seed": ctx.seed, "seconds": ctx.seconds, "trace": ctx.trace,
            "out_dir": ctx.out_dir,
            "keep_trace_sample": ctx.keep_trace_sample},
        jax_config=JaxConfig(distributed=False),
        scaling_config=scaling,
        run_config=RunConfig(name="bench_fit", storage_path=os.path.join(
            ctx.out_dir, "results")),
    ).fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    spans.update(m["spans"])
    n_dev = m["n_devices"]
    per_step = m["batch"][0] * m["batch"][1]
    counters = moe_counters(m["moe"], per_step)
    traced = dict(m["traced"] or {})
    traced_counters = (moe_counters(traced.pop("moe"), per_step)
                       if traced else None)
    problems = verdict(m, counters)
    rate = m["tokens"] / m["elapsed_s"] / n_dev
    ctx.emit(builder="qwen3_next_train", steps=m["steps"],
             tokens=m["tokens"], elapsed_s=m["elapsed_s"], batch=m["batch"],
             traced=traced, first_loss=m["first_loss"],
             last_loss=m["last_loss"], check=m["check"],
             attention=m["calls"]["attention"],
             gated_delta=m["calls"]["gated_delta"],
             held_experts=[{**c, **counters}
                           for c in m["calls"]["held_experts"]],
             load_balance=m["load_balance"], cache_hits=m["cache_hits"],
             cache_misses=m["cache_misses"], cache_dir=m["cache_dir"],
             step_memory=m["step_memory"],
             memory_limit_bytes=m["memory_limit_bytes"],
             compiles_in_window=m["compiles_in_window"], spans=spans)
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
                     "cache_misses": m["cache_misses"], "moe": counters,
                     "moe_traced": traced_counters,
                     "traced_steps": traced.get("steps")},
        "end_to_end": {"train_tok_s_chip": rate},
        "trace": m["trace"],
    }
