#!/usr/bin/env python3
"""The controls of the Kanana-2 cell's check against its reference.

    python benchmarks/kanana2_controls.py --seed <n> [--rehearsal]

`benchmarks/builders/kanana2_serve.py` holds the system to six limits;
this shows what they are there to refuse. In ONE process that holds the
chip (no cluster, no HTTP, no window) it makes the cell's weights once, as
the builder makes them (`seeded_params`: the seed's, with the
configuration's router), and for the system as it is and for each control builds the
cell's engine, caches document 0 through it, drives the builder's five
check requests (`adopter` adopts the document, `leaver` leaves, `reuser` is
admitted when it has), and puts what came out through the builder's own
`reference_check` and `check_problems` against the TRUE weights (the routing
is read from the record the engine's own two programs left in the cache,
so a fault is caught where it runs):

- `cache_8bit`: the latent cache's rows rounded to 8 bits (4 of exponent,
  3 of mantissa) before they are stored;
- `unnormed_latent`: the latent goes into the cache without
  `kv_a_layernorm`;
- `unrotated_key`: the rope key goes into the cache without the rotary;
- `bf16_router`: the router's logits, scores, bias sum and gates in
  bfloat16;
- `bf16_router_decode`: the same, in decode steps ALONE (a fault of one of
  the two compiled programs: the chunks route in float32);
- `bias_in_gates`: the gates are made of score + bias, not of the score;
- `no_scaling`: the gates are not multiplied by `routed_scaling_factor`.

Every line printed is one JSON object: `who`, its `readings` and the
`problems` found. The system must come out clean and every control must
not; the last line lists what did otherwise, and the exit code is 1 if
anything did.
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

CELL = "serve_kanana2_docqa_8k"


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def drive(engine, check, doc):
    """The document once with one new token, then the check's five
    requests through `engine.step()`: (request -> its tokens)."""
    engine.add_request(list(doc), 1)
    engine.run_until_idle(max_steps=100000)

    def add(who):
        return engine.add_request(check[who]["ids"],
                                  check[who]["max_new_tokens"])

    reqs = {who: add(who) for who in ("short", "leaver", "long", "adopter")}
    while engine.has_work() or "reuser" not in reqs:
        if "reuser" not in reqs and reqs["leaver"].done:
            reqs["reuser"] = add("reuser")
        engine.step()
    return reqs


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--only", default=None,
                   help="comma-separated subset of the runs")
    p.add_argument("--rehearsal", action="store_true",
                   help="the tiny sizes of the cell's rehearsal, on the CPU")
    args = p.parse_args(argv)
    if args.rehearsal:
        os.environ.update(JAX_PLATFORMS="cpu", RAY_TPU_PALLAS_INTERPRET="1")

    import jax
    import jax.numpy as jnp

    from benchmarks import manifest as mf
    from benchmarks.builders import kanana2_serve as b
    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.models import deepseek_v3 as dsv3
    from ray_tpu.ops import held_experts as moe

    manifest = mf.load(ROOT)
    cell = mf.cell_of(manifest, CELL)
    cfg, traffic = mf.config_of(manifest, cell, ROOT), mf.traffic_of(cell)
    if args.rehearsal:
        cfg, traffic = mf.apply_rehearsal(cfg), mf.apply_rehearsal(traffic)
    model_cfg = {k: cfg[k] for k in b.MODEL_KEYS}
    mc = b.model_config(cfg)
    model = dsv3.DeepseekV3(mc)
    params = b.seeded_params(model, args.seed, int(cfg["router_seed"]))
    doc = b.documents(traffic, args.seed, int(cfg["vocab_size"]))[0]
    check = b.check_requests(cfg, args.seed, doc)
    true_rows, true_route = dsv3.latent_rows, moe.route_sigmoid

    def cache_8bit(cfg_, lp, h, positions):
        rows = true_rows(cfg_, lp, h, positions)
        return jax.lax.reduce_precision(rows, 4, 3)

    def unnormed_latent(cfg_, lp, h, positions):
        return true_rows(cfg_, {**lp, "kv_norm": None}, h, positions)

    def unrotated_key(cfg_, lp, h, positions):
        return true_rows(cfg_, lp, h, jnp.zeros_like(positions))

    def bf16_router(x, w, bias, top_k, scaling):
        bf = jnp.bfloat16
        scores = jax.nn.sigmoid(jnp.dot(x.astype(bf), w.astype(bf)))
        _, index = jax.lax.top_k(scores + bias.astype(bf), top_k)
        chosen = jnp.take_along_axis(scores, index, axis=-1)
        gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                          + jnp.asarray(1e-20, bf)) * jnp.asarray(scaling, bf)
        return scores.astype(jnp.float32), gates.astype(jnp.float32), \
            index.astype(jnp.int32)

    def bf16_router_decode(x, w, bias, top_k, scaling):
        # a decode step routes one row a slot, a chunk `prefill_chunk` rows
        rule = bf16_router if x.shape[0] == cfg["engine"]["batch_slots"] \
            else true_route
        return rule(x, w, bias, top_k, scaling)

    def bias_in_gates(x, w, bias, top_k, scaling):
        scores, _, index = true_route(x, w, bias, top_k, scaling)
        chosen = jnp.take_along_axis(scores + bias, index, axis=-1)
        return scores, scaling * chosen / (
            jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20), index

    def no_scaling(x, w, bias, top_k, scaling):
        return true_route(x, w, bias, top_k, 1.0)

    # An un-normed latent is `_rms_norm` left out: the model's helper takes
    # the weight None as "no norm" only here.
    true_norm = dsv3._rms_norm

    def norm_or_not(x, weight, eps, groups: int = 1):
        if weight is None:
            return x.astype(jnp.float32)
        return true_norm(x, weight, eps, groups)

    runs = {"system": {}, "cache_8bit": {"rows": cache_8bit},
            "unnormed_latent": {"rows": unnormed_latent},
            "unrotated_key": {"rows": unrotated_key},
            "bf16_router": {"route": bf16_router},
            "bf16_router_decode": {"route": bf16_router_decode},
            "bias_in_gates": {"route": bias_in_gates},
            "no_scaling": {"route": no_scaling}}
    only = args.only.split(",") if args.only else list(runs)
    wrong = []
    dsv3._rms_norm = norm_or_not
    try:
        for who in only:
            dsv3.latent_rows = runs[who].get("rows", true_rows)
            moe.route_sigmoid = runs[who].get("route", true_route)
            engine = InferenceEngine(EngineConfig(**cfg["engine"]),
                                     model=model, params=params)
            reqs = drive(engine, check, doc)
            reference = b.reference_check(
                engine, model_cfg,
                [{"who": r, "prompt": check[r]["ids"],
                  "generated": list(reqs[r].generated)} for r in check])
            problems = b.check_problems(reference)
            if reqs["adopter"].cached_tokens != len(doc):
                problems.append(f"the adopter adopted "
                                f"{reqs['adopter'].cached_tokens} tokens")
            emit(who=who, readings=reference,
                 routing=b.routing_readings(reference), problems=problems)
            if bool(problems) == (who == "system"):
                wrong.append(who)
            del engine, reqs
            jax.clear_caches()
    finally:
        dsv3.latent_rows, moe.route_sigmoid = true_rows, true_route
        dsv3._rms_norm = true_norm
    emit(came_out_wrong=wrong,
         limits={"LOGIT_MARGIN": b.LOGIT_MARGIN,
                 "LATENT_LIMIT": b.LATENT_LIMIT,
                 "LOGIT_MEAN_MARGIN": b.LOGIT_MEAN_MARGIN,
                 "ROUTE_MISMATCH_LIMIT": b.ROUTE_MISMATCH_LIMIT,
                 "DECODE_MISMATCH_LIMIT": b.DECODE_MISMATCH_LIMIT,
                 "GATE_LIMIT": b.GATE_LIMIT})
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
