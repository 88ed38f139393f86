#!/usr/bin/env python3
"""The controls of the dots3 cell's check against its reference.

    python benchmarks/dots3_controls.py --seed <n> [--rehearsal] [--only a,b]

`benchmarks/builders/dots3_serve.py` holds the system to its limits; this
shows what they are there to refuse. In ONE process that holds the chip (no
cluster, no HTTP, no window) it makes the cell's weights once, as the
builder makes them, runs the plain reference over document 0 ONCE, and for
the system as it is and for each control builds the cell's engine, caches
document 0 through it, drives the builder's held requests (`leaver` leaves,
`reuser` is admitted when it has) and puts what came out through the
builder's own `reference_check`, `document_readings`, `check_problems` and
`window_book_problems` against the TRUE weights:

- `recent_2048`: the selection replaced by the most recent `index_topk`;
- `top_2047`: one position fewer chosen;
- `no_key_layernorm`: the indexer's keys cached without their LayerNorm;
- `bf16_index_scores`: the index scores accumulated in bfloat16;
- `index_cache_8bit`: the index keys rounded to 8 bits before they are
  stored;
- `window_512`, `window_514`: the window one key short, one key long;
- `rope_bases_swapped`: the two layer kinds' rotary bases exchanged;
- `no_gate`: no headwise output gate;
- `no_rescale`: the latents not rescaled;
- `bf16_router`: the router's logits, scores, bias sum and gates bfloat16;
- `top7_of_8`: one expert fewer a token;
- `absent_expert_computed`: an assignment to an expert held elsewhere is
  computed by a held one;
- `window_page_released_early`: a live sequence gives back a window page a
  block before its oldest query stops reading it.

`FAULTS` is what each control replaces, by name, of `ray_tpu.models.dots3`
(a function of the module, or fields of the configuration) or of the
engine's window pool; `tests/test_dots3.py` plants the same faults at tiny
sizes. Every line printed is one JSON object; the system must come out
clean and every control must not; the last line lists what did otherwise,
and the exit code is 1 if anything did.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT] + [p for p in sys.path
                        if os.path.abspath(p or ".") != os.path.dirname(
                            os.path.abspath(__file__))]

CELL = "serve_dots3_docqa_32k"


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def faults():
    """name -> {"module": {function name: replacement}, "config": lambda
    config -> config, "pool": {method name: replacement}}."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.inference import kv_cache
    from ray_tpu.models import dots3 as d3
    from ray_tpu.ops.sparse_latent_attention import select_topk

    true_keys, true_scores, true_route = d3.index_keys, \
        d3.index_accumulate, d3.route
    true_release = kv_cache.WindowBlockManager.release_below
    bf = jnp.bfloat16

    def recent(scores, positions, topk):
        count = jnp.minimum(positions + 1, topk).astype(jnp.int32)
        chosen = (positions - count + 1)[..., None] + jnp.arange(topk)
        return jnp.where(jnp.arange(topk) < count[..., None], chosen,
                         0).astype(jnp.int32), count

    def one_fewer(scores, positions, topk):
        chosen, count = select_topk(scores, topk - 1)
        return jnp.pad(chosen, ((0, 0), (0, 0), (0, 1))), count

    def no_key_layernorm(cfg, lp, h, positions):
        was, d3._layer_norm = d3._layer_norm, \
            lambda x, w, b, eps: x.astype(jnp.float32)
        try:
            return true_keys(cfg, lp, h, positions)
        finally:
            d3._layer_norm = was

    def bf16_scores(q_idx, w, arena, block_tables, positions, write_mask):
        """The indexer's products and its sum over heads ACCUMULATED in
        bfloat16 (a definition of its own: the kernel accumulates in
        float32), 32 queries at a time."""
        _, bsz, d = arena.shape
        b, s = positions.shape
        ctx = block_tables.shape[1] * bsz
        keys = arena[block_tables].reshape(b, ctx, d)
        pad = -s % 32
        q = jnp.pad(q_idx, ((0, 0), (0, pad), (0, 0), (0, 0)))
        wp = jnp.pad(w, ((0, 0), (0, pad), (0, 0))).astype(bf)

        def block(args):
            qb, wb = args                       # [b, 32, n, d], [b, 32, n]
            dots = jnp.einsum("bsnd,bkd->bsnk", qb, keys,
                              preferred_element_type=bf)
            return jnp.sum(jax.nn.relu(dots) * wb[..., None], axis=2,
                           dtype=bf)

        blocks = (s + pad) // 32
        scores = jax.lax.map(block, (
            q.reshape(b, blocks, 32, *q.shape[2:]).swapaxes(0, 1),
            wp.reshape(b, blocks, 32, -1).swapaxes(0, 1)))
        scores = scores.swapaxes(0, 1).reshape(b, s + pad, ctx)[:, :s]
        seen = (jnp.arange(ctx)[None, None, :] <= positions[:, :, None]) \
            & write_mask[:, :, None]
        return jnp.where(seen, scores.astype(jnp.float32), -jnp.inf)

    def keys_8bit(cfg, lp, h, positions):
        return jax.lax.reduce_precision(true_keys(cfg, lp, h, positions), 4, 3)

    def bf16_router(cfg, lp, n):
        scores = jax.nn.sigmoid(jnp.dot(n.astype(bf),
                                        lp["router"].astype(bf)))
        _, index = jax.lax.top_k(scores + lp["router_bias"].astype(bf),
                                 cfg.num_experts_per_tok)
        chosen = jnp.take_along_axis(scores, index, axis=-1)
        gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                          + jnp.asarray(1e-20, bf)) \
            * jnp.asarray(cfg.routed_scaling_factor, bf)
        return gates.astype(jnp.float32), index.astype(jnp.int32)

    def top7(cfg, lp, n):
        fewer = dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)
        gates, index = true_route(fewer, lp, n)
        return jnp.pad(gates, ((0, 0), (0, 1))), jnp.pad(
            index, ((0, 0), (0, 1)), constant_values=cfg.experts_routed)

    def absent_computed(cfg, lp, n):
        gates, index = true_route(cfg, lp, n)
        first, held = cfg.held
        return gates, first + (index - first) % held

    def released_early(self, seq_id, first_kept):
        # the rule keeps one block more than a boundary's queries read
        return true_release(self, seq_id, first_kept + 2)

    def window(w):
        return lambda c: dataclasses.replace(c, sliding_window_size=w)

    return {
        "recent_2048": {"module": {"select": recent}},
        "top_2047": {"module": {"select": one_fewer}},
        "no_key_layernorm": {"module": {"index_keys": no_key_layernorm}},
        "bf16_index_scores": {"module": {"index_accumulate": bf16_scores}},
        "index_cache_8bit": {"module": {"index_keys": keys_8bit}},
        "window_512": {"config": lambda c: window(
            c.sliding_window_size - 1)(c)},
        "window_514": {"config": lambda c: window(
            c.sliding_window_size + 1)(c)},
        "rope_bases_swapped": {"config": lambda c: dataclasses.replace(
            c, rope_theta=c.swa_rope_theta, swa_rope_theta=c.rope_theta)},
        "no_gate": {"module": {"attention_gate": lambda cfg, lp, h: 1.0}},
        "no_rescale": {"module": {"rescale": lambda cfg, rank: 1.0}},
        "bf16_router": {"module": {"route": bf16_router}},
        "top7_of_8": {"module": {"route": top7}},
        "absent_expert_computed": {"module": {"route": absent_computed}},
        "window_page_released_early": {"pool": {
            "release_below": released_early}},
    }


@contextlib.contextmanager
def planted(fault):
    """The fault's replacements in place, and taken out again."""
    from ray_tpu.inference import kv_cache
    from ray_tpu.models import dots3 as d3

    places = [(d3, name, fn) for name, fn in fault.get("module", {}).items()] \
        + [(kv_cache.WindowBlockManager, name, fn)
           for name, fn in fault.get("pool", {}).items()]
    was = [(where, name, getattr(where, name)) for where, name, _ in places]
    for where, name, fn in places:
        setattr(where, name, fn)
    try:
        yield
    finally:
        for where, name, fn in was:
            setattr(where, name, fn)


def drive(engine, check, doc, held):
    """The document once with one new token, then the held requests through
    `engine.step()`: (request -> the engine's record of it)."""
    engine.add_request(list(doc), 1)
    engine.run_until_idle(max_steps=100000)

    def add(who):
        return engine.add_request(check[who]["ids"],
                                  check[who]["max_new_tokens"])

    reqs = {who: add(who) for who in held if who != "reuser"}
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

    from benchmarks import manifest as mf
    from benchmarks.builders import dots3_serve as b
    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.models.dots3 import Dots3

    manifest = mf.load(ROOT)
    cell = mf.cell_of(manifest, CELL)
    cfg, traffic = mf.config_of(manifest, cell, ROOT), mf.traffic_of(cell)
    if args.rehearsal:
        cfg, traffic = mf.apply_rehearsal(cfg), mf.apply_rehearsal(traffic)
    cfg = {**cfg, "check": {**(cfg.get("check") or {}), "fillers": 0}}
    model_cfg = {k: cfg[k] for k in b.MODEL_KEYS}
    true_config = b.model_config(cfg)
    params = b.seeded_params(Dots3(true_config), args.seed,
                             int(cfg["router_seed"]))
    docs = b.k2.documents(traffic, args.seed, int(cfg["vocab_size"]))
    check = b.check_requests(cfg, args.seed, docs)
    runs = {"system": {}, **faults()}
    only = args.only.split(",") if args.only else list(runs)
    wrong, reference = [], None
    for who in only:
        fault = runs[who]
        config = fault.get("config", lambda c: c)(true_config)
        with planted(fault):
            engine = InferenceEngine(EngineConfig(**cfg["engine"]),
                                     model=Dots3(config), params=params)
            reqs = drive(engine, check, docs[0], b.HELD)
            if reference is None:       # the TRUE weights: once for all
                reference = b.Reference(engine, model_cfg, docs[0])
            readings = [b.document_readings(engine, reference)] \
                + b.reference_check(
                    engine, reference,
                    [{"who": r, "prompt": check[r]["ids"],
                      "generated": list(reqs[r].generated)}
                     for r in b.HELD])
            stats = {**engine.stats(), "has_work": engine.has_work()}
        problems = b.check_problems(readings) \
            + b.window_book_problems(stats, cfg)
        for r in b.HELD:
            want = 0 if r == "nodoc" else len(docs[0])
            if reqs[r].cached_tokens != want:
                problems.append(f"{r} adopted {reqs[r].cached_tokens} "
                                f"tokens, want {want}")
        emit(who=who, readings=readings,
             routing=b.routing_readings(readings), problems=problems,
             kv_kinds=stats.get("kv_kinds"))
        if bool(problems) == (who == "system"):
            wrong.append(who)
        del engine, reqs
    emit(came_out_wrong=wrong, limits={
        name: getattr(b, name) for name in dir(b) if name.endswith("_LIMIT")})
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
