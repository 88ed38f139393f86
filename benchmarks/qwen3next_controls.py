#!/usr/bin/env python3
"""The controls of the Qwen3-Next cell's comparison against its reference.

    python benchmarks/qwen3next_controls.py --seed <n> [--rehearsal]

`benchmarks/builders/qwen3_next_train.py` holds the system to limits; this
shows what each limit is there to refuse. In ONE process that holds the
chip (no cluster, no window) it makes the cell's weights and first batch
from the seed, runs the builder's own comparison on the system (which must
come out clean), and then puts each control through the builder's own
`check_problems`, the system's readings with the control's in their place:

- the reference's router with bf16 operands, on the reference's own input;
- the reference's recurrence with its state carried in bf16;
- the reference's AdamW step with the parameters held in bf16, and a state
  left as it was;
- four planted faults, each a change to the WEIGHTS the reference is
  handed (the system then disagrees with it as a faulty system would): the
  held experts one place along, one layer's mixer left out, the embedding's
  rows one place along, the router's columns one place along.

Every line printed is one JSON object: `who`, its `readings`, and the
`problems` `check_problems` found. The last line lists the controls that
came out clean, which is the failure: exit code 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT] + [p for p in sys.path
                        if os.path.abspath(p or ".") != os.path.dirname(
                            os.path.abspath(__file__))]

CELL = "train_qwen3next_8k_ep16share"


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="the tiny sizes of the cell's rehearsal, on the CPU")
    args = p.parse_args(argv)
    if args.rehearsal:
        os.environ.update(JAX_PLATFORMS="cpu", RAY_TPU_PALLAS_INTERPRET="1")

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks import manifest as mf
    from benchmarks.builders import qwen3_next_train as b
    from benchmarks.reference import qwen3_next_plain as plain
    from ray_tpu.models.gpt2 import make_train_step
    from ray_tpu.models.qwen3_next import Qwen3Next, make_loss_fn

    manifest = mf.load(ROOT)
    cell = mf.cell_of(manifest, CELL)
    cfg, traffic = mf.config_of(manifest, cell, ROOT), mf.traffic_of(cell)
    if args.rehearsal:
        cfg, traffic = mf.apply_rehearsal(cfg), mf.apply_rehearsal(traffic)
    seq, train = int(traffic["seq"]), cfg["train"]
    mc = b.model_config(cfg)
    model = Qwen3Next(mc)
    params = b.init_params(model, args.seed, seq)
    rng = np.random.default_rng(args.seed)
    first = b.make_batch(rng, 1, seq, mc.vocab_size)
    programs = b.check_programs(model, mc, cfg, seq)
    check, kept = b.check_against_reference(programs, mc, params, first)
    taps, weights = kept["taps"], kept["weights"]
    clean = []

    def control(who: str, readings: dict) -> None:
        problems = b.check_problems({**check, **readings})
        if not problems:
            clean.append(who)
        emit(who=who, readings=readings, problems=problems)

    # The reference's router with bf16 operands (the product the MXU makes
    # of them in one pass, accumulated in f32), on the reference's input.
    def rounded(t):
        return t.astype(jnp.bfloat16).astype(jnp.float32)

    low_router = jax.jit(lambda x, w: plain.router(
        rounded(x), rounded(w), mc.num_experts_per_tok))
    routed = [low_router(x, weights[f"layers.{i}.mlp.gate"])
              for i, x in enumerate(taps["router_in"])]
    probs = jnp.stack([r[0] for r in routed])
    flips, decided = b.decided_flips(jnp.stack([r[1] for r in routed]),
                                     taps["probs"], b.ROUTER_MARGIN)
    router_control = {
        "router_gap": [float(jnp.max(jnp.abs(probs - taps["probs"]))
                             / jnp.max(taps["probs"]))],
        "router_flips": [flips], "router_decided": [decided]}

    # The reference's recurrence with its state carried in bf16.
    carry_control = {key: [[
        b.worst_head_error(
            programs["recurrence_reference"](*b.probe(operands, gates),
                                             carry=jnp.bfloat16),
            programs["recurrence_reference"](*b.probe(operands, gates)))
        for operands in taps["recurrence"]]]
        for key, gates in (("recurrence_gap", None),
                           ("recurrence_probe_gap", b.PROBE_LOG_DECAY))}

    # What a state left as it was shows of a "fall": the loss of fresh
    # batches at the first step's parameters.
    fresh = [float(programs["system"](params, jnp.asarray(ids))[0][0])
             for ids in b.make_batch(rng, 6, seq, mc.vocab_size)]

    # Planted faults: the reference is handed changed weights.
    def along(t, axis):
        return jnp.roll(t, 1, axis=axis)

    faults = {
        "fault: the held experts one place along": {
            k: along(v, 0) for k, v in weights.items() if ".experts." in k},
        "fault: one layer's mixer left out": {
            "layers.1.linear_attn.out_proj": jnp.zeros_like(
                weights["layers.1.linear_attn.out_proj"])},
        "fault: the embedding's rows one place along": {
            "embed_tokens": along(weights["embed_tokens"], 0)},
        "fault: the router's columns one place along": {
            k: along(v, 1) for k, v in weights.items()
            if k.endswith(".mlp.gate")}}
    before, grads = jax.device_get((programs["named_weights"](params),
                                    kept["grads"]))
    del kept, taps
    faulty = {who: b.check_against_reference(
        programs, mc, params, first, weights={**weights, **changed})[0]
        for who, changed in faults.items()}
    del weights, faults

    # The timed path's own first step, as the builder takes it, and the
    # reference's AdamW with the parameters held in bf16.
    rate = b.learning_rate(train)
    rate0, decay = float(rate(0)), float(train["weight_decay"])
    opt = optax.adamw(rate, weight_decay=decay)
    step = make_train_step(model, opt, donate=True,
                           loss_fn=make_loss_fn(model))
    ids = jax.device_put(first)
    params, _, _ = step(params, jax.jit(opt.init)(params),
                        {"input_ids": ids, "labels": ids})
    after = jax.device_get(programs["named_weights"](params))
    check["update_gaps"] = b.update_gaps(before, after, grads, rate0, decay)

    def held_in_bf16(t):
        return np.asarray(jnp.asarray(t).astype(jnp.bfloat16).astype(
            jnp.float32))

    low = {k: held_in_bf16(v) for k, v in before.items()}
    low_after = {k: held_in_bf16(
        low[k] - rate0 * (g / (np.abs(g) + b.ADAM_EPS) + decay * low[k]))
        for k, g in grads.items()}

    problems = b.check_problems(check)
    emit(who="the system", seed=args.seed, readings=check,
         problems=problems, fresh_batch_losses=fresh)
    control("control: the reference's router with bf16 operands",
            router_control)
    control("control: the reference's state carried in bf16", carry_control)
    control("control: the reference's parameters held in bf16",
            {"update_gaps": b.update_gaps(low, low_after, grads, rate0,
                                          decay)})
    control("control: a state left as it was",
            {"update_gaps": b.update_gaps(before, before, grads, rate0,
                                          decay)})
    for who, readings in faulty.items():
        control(who, {k: v for k, v in readings.items()})
    emit(system_clean=not problems, controls_that_came_out_clean=clean)
    return 1 if problems or clean else 0


if __name__ == "__main__":
    sys.exit(main())
