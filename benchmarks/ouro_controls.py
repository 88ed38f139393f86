#!/usr/bin/env python3
"""The controls of the Ouro cell's check against its reference.

    python benchmarks/ouro_controls.py --seed <n> [--rehearsal]

`benchmarks/builders/ouro_serve.py` holds the system to five limits; this
shows what they are there to refuse. In ONE process that holds the chip (no
cluster, no HTTP, no window) it makes the cell's weights once, as the
builder makes them, and for the system as it is and for each control
builds the cell's engine, drives the builder's nine check requests (eight
in flight, `reuser` admitted when `leaver` has left), and puts what came
out through the builder's own `reference_check` and `check_problems`
against the TRUE weights and the TRUE number of passes:

- `three_passes`: a pass dropped (the same weights run three times);
- `shared_kv`: passes 2-4 read and write pass 1's pages (one set of keys
  and values a token, the last pass's);
- `last_pass_kv`: decode steps read the LAST pass's pages in every pass
  (the paper's approximation of the cache; writes go where they belong);
- `no_pass_norm`: the model's norm only after the last pass;
- `pre_norm_only`: the sandwich norms N_2 and N_4 dropped;
- `cache_8bit`: keys and values rounded to 8 bits (4 of exponent, 3 of
  mantissa) before they are stored;
- `bf16_norms`: every RMSNorm computed in bfloat16;
- `bf16_residual`: the residual stream in bfloat16 (`stream_dtype`): the
  published activations' type, a precision below the one that is served.

(In the rehearsal the parameters are float32, and `bf16_norms` and
`bf16_residual` read under limits that were set for bf16 operands: they are
listed as having come out clean there, and only the chip's run says what
they read.)

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

CELL = "serve_ouro2p6b_batchgen"


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def drive(engine, check):
    """The check's nine requests through `engine.step()`: (request -> the
    engine's record of it)."""
    def add(who):
        return engine.add_request(check[who]["ids"],
                                  check[who]["max_new_tokens"])

    reqs = {who: add(who) for who in check if who != "reuser"}
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

    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks import manifest as mf
    from benchmarks.builders import ouro_serve as b
    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.models import ouro
    from ray_tpu.ops.paged_attention import paged_attention

    manifest = mf.load(ROOT)
    cell = mf.cell_of(manifest, CELL)
    cfg = mf.config_of(manifest, cell, ROOT)
    if args.rehearsal:
        cfg = mf.apply_rehearsal(cfg)
    model_cfg = {k: cfg[k] for k in b.MODEL_KEYS}
    mc = b.model_config(cfg)
    passes, num_blocks = mc.total_ut_steps, int(cfg["engine"]["num_blocks"])
    params = ouro.Ouro(mc).init(
        jax.random.PRNGKey(int(args.seed) % (2 ** 31 - 1)))
    jax.block_until_ready(params)
    check = b.check_requests(cfg, args.seed)
    true = {name: getattr(ouro, name) for name in (
        "_rms_norm", "_pass_tables", "paged_write_and_attend")}

    def shared_tables(block_tables, u, per_pass):
        return block_tables

    def read_last_pass(q, k, v, k_arena, v_arena, tables, positions,
                       write_mask):
        attn, k_arena, v_arena = true["paged_write_and_attend"](
            q, k, v, k_arena, v_arena, tables, positions, write_mask)
        if q.shape[2] > 1:                      # a prefill chunk: as it is
            return attn, k_arena, v_arena
        last = tables % num_blocks + (passes - 1) * num_blocks
        with jax.named_scope("paged_attn"):
            attn = paged_attention(
                q.transpose(0, 2, 1, 3), k_arena, v_arena, last, positions,
                write_mask).transpose(0, 2, 1, 3)
        return attn, k_arena, v_arena

    def store_8bit(q, k, v, *rest):
        return true["paged_write_and_attend"](
            q, jax.lax.reduce_precision(k, 4, 3),
            jax.lax.reduce_precision(v, 4, 3), *rest)

    # The model has no word for "which norm is this": a pass asks for its
    # tables once, then for a layer's four norms a layer, then for N_f.
    at = {}

    def tables_noted(block_tables, u, per_pass):
        at.update(u=u, norms=0)
        return true["_pass_tables"](block_tables, u, per_pass)

    def norm_but_between_passes(x, weight, eps):
        at["norms"] += 1
        normed = true["_rms_norm"](x, weight, eps)
        if at["norms"] <= 4 * mc.num_hidden_layers:
            return normed
        return jnp.where(at["u"] == passes - 1, normed,
                         x.astype(jnp.float32))

    # A sandwich norm dropped is `_rms_norm` left out: the model's helper
    # takes the weight None as "no norm" only here.
    def norm_or_not(x, weight, eps):
        if weight is None:
            return x.astype(jnp.float32)
        return true["_rms_norm"](x, weight, eps)

    def bf16_norm(x, weight, eps):
        bf = jnp.bfloat16
        x = x.astype(bf)
        return (x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            + jnp.asarray(eps, bf)) * weight.astype(bf)).astype(jnp.float32)

    no_sandwich = {**params, "layers": [
        {**lp, "attn_post_norm": None, "mlp_post_norm": None}
        for lp in params["layers"]]}
    runs = {
        "system": {},
        "three_passes": {"config": dataclasses.replace(
            mc, total_ut_steps=passes - 1)},
        "shared_kv": {"_pass_tables": shared_tables},
        "last_pass_kv": {"paged_write_and_attend": read_last_pass},
        "no_pass_norm": {"_pass_tables": tables_noted,
                         "_rms_norm": norm_but_between_passes},
        "pre_norm_only": {"_rms_norm": norm_or_not, "params": no_sandwich},
        "cache_8bit": {"paged_write_and_attend": store_8bit},
        "bf16_norms": {"_rms_norm": bf16_norm},
        "bf16_residual": {"config": dataclasses.replace(
            mc, stream_dtype=jnp.bfloat16)},
    }
    only = args.only.split(",") if args.only else list(runs)
    wrong = []
    try:
        for who in only:
            run = runs[who]
            for name, fn in true.items():
                setattr(ouro, name, run.get(name, fn))
            engine = InferenceEngine(
                EngineConfig(**cfg["engine"]),
                model=ouro.Ouro(run.get("config", mc)),
                params=run.get("params", params))
            reqs = drive(engine, check)
            # held to the TRUE weights and passes, whatever ran
            engine._params = params
            reference = b.reference_check(
                engine, model_cfg,
                [{"who": r, "prompt": check[r]["ids"],
                  "generated": list(reqs[r].generated)} for r in b.COMPARED])
            problems = b.check_problems(reference)
            emit(who=who, readings=reference, problems=problems)
            if bool(problems) == (who == "system"):
                wrong.append(who)
            del engine, reqs
            jax.clear_caches()
    finally:
        for name, fn in true.items():
            setattr(ouro, name, fn)
    emit(came_out_wrong=wrong,
         limits={"LOGIT_MARGIN": b.LOGIT_MARGIN,
                 "LOGIT_MEAN_MARGIN": b.LOGIT_MEAN_MARGIN,
                 "KV_LIMIT_FIRST": b.KV_LIMIT_FIRST,
                 "KV_LIMIT_SECOND_PASS": b.KV_LIMIT_SECOND_PASS,
                 "KV_LIMIT": b.KV_LIMIT})
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
