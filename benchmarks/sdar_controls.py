#!/usr/bin/env python3
"""The controls of the SDAR cell's check against its reference.

    python benchmarks/sdar_controls.py --seed <n> [--rehearsal] [--only a,b]

`benchmarks/builders/sdar_serve.py` holds the system to eight limits; this
shows what they are there to refuse. In ONE process that holds the chip (no
cluster, no HTTP, no window) it makes the cell's weights once, as the
builder makes them, and for the system as it is and for each control
builds the cell's engine, drives the builder's check requests (every slot
live, `reuser` admitted when `leaver` has left, every pass's buffer kept),
and puts what came out through the builder's own `reference_check` and
`check_problems` against the TRUE weights and the TRUE model:

- `causal_in_block`: plain causal attention inside a block (a query sees
  its own position and those before it, not its block's later ones);
- `no_commit_pass`: a block's commit pass writes nothing: its keys and
  values stay as the last denoise pass left them, made from a buffer that
  still held a `[MASK]`;
- `shifted_logits`: the selection reads the logits shifted by one, as a
  next-token model would (position p commits the argmax AT p - 1);
- `bf16_router`: the router's logits, softmax and gates in bfloat16;
- `top7`: the eighth choice of every token routed to no expert, the gates
  renormalised over seven;
- `cache_8bit`: keys and values rounded to 8 bits (4 of exponent, 3 of
  mantissa) before they are stored;
- `bf16_norms`: every RMSNorm computed in bfloat16: the nearest precision
  below the stated one.

(In the rehearsal the parameters are float32, and `bf16_norms` and
`bf16_router` may read under limits that were set for bf16 operands: only
the chip's run says what they read.)

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

CELL = "serve_sdar30b_blockgen"


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def drive(engine, check):
    """The check's requests through `engine.step()`: (request -> the
    engine's record of it)."""
    def add(who):
        return engine.add_request(check[who]["ids"],
                                  check[who]["max_new_tokens"],
                                  record_passes=True)

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

    import jax
    import jax.numpy as jnp

    from benchmarks import manifest as mf
    from benchmarks.builders import sdar_serve as b
    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.models import sdar

    manifest = mf.load(ROOT)
    cell = mf.cell_of(manifest, CELL)
    cfg = mf.config_of(manifest, cell, ROOT)
    if args.rehearsal:
        cfg = mf.apply_rehearsal(cfg)
    model_cfg = {k: cfg[k] for k in b.MODEL_KEYS}
    mc = b.model_config(cfg)
    model = sdar.SDAR(mc)
    params = b.seeded_params(model, args.seed, int(cfg["router_seed"]))
    check = b.check_requests(cfg, args.seed)
    moe = sdar.moe
    true = {"_rms_norm": sdar._rms_norm,
            "paged_write_and_attend": sdar.paged_write_and_attend,
            "block_select": sdar.block_select, "route": moe.route}

    def causal(q, k, v, k_arena, v_arena, tables, positions, write_mask,
               sees=None):
        return true["paged_write_and_attend"](
            q, k, v, k_arena, v_arena, tables, positions, write_mask)

    def store_8bit(q, k, v, *rest):
        return true["paged_write_and_attend"](
            q, jax.lax.reduce_precision(k, 4, 3),
            jax.lax.reduce_precision(v, 4, 3), *rest)

    def shifted_select(logits, masked, n, threshold=None):
        return true["block_select"](jnp.roll(logits, 1, axis=1), masked, n,
                                    threshold)

    def bf16_route(x, w_router, top_k):
        bf = jnp.bfloat16
        probs = jax.nn.softmax(jnp.dot(x.astype(bf), w_router.astype(bf)),
                               axis=-1)
        top_p, index = jax.lax.top_k(probs, top_k)
        gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        return (probs.astype(jnp.float32), gates.astype(jnp.float32),
                index.astype(jnp.int32))

    def top7_route(x, w_router, top_k):
        probs, gates, index = true["route"](x, w_router, top_k)
        kept = jnp.arange(top_k) < top_k - 1
        gates = jnp.where(kept, gates, 0.0)
        return (probs, gates / jnp.sum(gates, axis=-1, keepdims=True),
                jnp.where(kept, index, w_router.shape[1]))

    def bf16_norm(x, weight, eps, groups=1):
        bf = jnp.bfloat16
        x = x.astype(bf)
        return (x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            + jnp.asarray(eps, bf)) * weight.astype(bf)).astype(jnp.float32)

    def commit_writes_nothing(engine):
        """The engine's block program with the commit rows (n = 0) masked
        out of the model's step: their keys and values go to the trash
        block and the pages keep the last denoise pass's."""
        raw = engine._decode_fn

        def decode_fn(params, arenas, adapters, tokens, bt, pos, wmask,
                      fresh, start, n):
            out, arenas = raw(params, arenas, adapters, tokens, bt, pos,
                              wmask & (n > 0)[:, None], fresh, start, n)
            return jnp.where(wmask, out, tokens), arenas

        engine._decode_fn = jax.jit(decode_fn, donate_argnums=(1,))
        engine._prefill_fn = jax.jit(engine._prefill_fn, donate_argnums=(1,))

    runs = {
        "system": {},
        "causal_in_block": {"paged_write_and_attend": causal},
        "no_commit_pass": {"engine": commit_writes_nothing},
        "shifted_logits": {"block_select": shifted_select},
        "bf16_router": {"route": bf16_route},
        "top7": {"route": top7_route},
        "cache_8bit": {"paged_write_and_attend": store_8bit},
        "bf16_norms": {"_rms_norm": bf16_norm},
    }
    only = args.only.split(",") if args.only else list(runs)
    wrong = []
    try:
        for who in only:
            run = runs[who]
            for name, fn in true.items():
                setattr(moe if name == "route" else sdar, name,
                        run.get(name, fn))
            patch = run.get("engine")
            engine = InferenceEngine(
                EngineConfig(**cfg["engine"], use_jit=patch is None),
                model=model, params=params)
            if patch is not None:
                patch(engine)
            reqs = drive(engine, check)
            reference = b.reference_check(
                engine, model_cfg,
                [{"who": r, "prompt": check[r]["ids"],
                  "generated": list(reqs[r].generated),
                  "pass_log": list(reqs[r].pass_log)} for r in b.COMPARED])
            problems = b.check_problems(reference)
            emit(who=who, readings=reference,
                 routing=b.routing_readings(reference), problems=problems)
            if bool(problems) == (who == "system"):
                wrong.append(who)
            del engine, reqs
            jax.clear_caches()
    finally:
        for name, fn in true.items():
            setattr(moe if name == "route" else sdar, name, fn)
    emit(came_out_wrong=wrong,
         limits={name: getattr(b, name) for name in (
             "LOGIT_MARGIN", "LOGIT_MEAN_MARGIN", "SELECT_MARGIN",
             "SELECT_MEAN_MARGIN", "KV_LIMIT_FIRST", "KV_LIMIT_LAST",
             "ROUTE_MISMATCH_LIMIT", "GATE_LIMIT")})
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
