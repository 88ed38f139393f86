"""The engine's step programs ALONE at a serve cell's shapes, on the chip:
ms an execution of `decode_fn` (all slots but one live), of `prefill_fn`
(one chunk of 256 positions of which 160 are live) and of the fused program
that runs both as one (a decode step with the chunk aboard, the model's
`paged_step_with_chunk`), each program's first call (`compile_s`: what a
start-up pays for it) and, where the model counts them, the experts a layer
each draws. ROADMAP caveat 9: time a program alone before predicting what
an engine gains from it.

    chiprun -- python3 scripts/time_serve_steps.py [--config <name>]

The configuration's `model_type` chooses the model and the shapes (`CELLS`):
`kanana-2-30b-a3b-l8-serve` (the default; 31 of 32 slots at contexts of
8,300-8,800, the chunk behind 8,192 cached tokens) or
`falcon-h1-34b-l6-serve` (63 of 64 slots at contexts of ~290, the chunk
FRESH, into a slot whose state and tail hold another request's leavings) or
`mistral-7b-v0.3-l16-serve` (`models/llama.py`; 15 of 16 slots at contexts
of 64-640, the cell's prompts and outputs, the chunk a fresh prompt of 160
ids in its 512 rows).
One JSON line on stdout, the same in `chiprun_out/serve_steps.json`. The
model is the cell's configuration (`benchmarks/configs/<--config>.json`)
with seeded weights and a cache of seeded rows; the programs are the
engine's own jitted objects, called as `_decode_step` calls them.
`--rehearsal` under RAY_TPU_PALLAS_INTERPRET=1 runs the model's tiny
configuration on the CPU (times of the interpreter: no device number).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from importlib import import_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.inference.engine import (EngineConfig,  # noqa: E402
                                      InferenceEngine)

# model_type -> the model's module under `ray_tpu.models` and its class,
# the tokens cached before the chunk, the decode rows' contexts beyond them
# (from, to) and the parts of its cache that hold rows (None: all of it).
# The flax family takes its configuration from the cell's builder and its
# parameters from an `init` that is shown ids.
CELLS = {
    "deepseek_v3": dict(module="deepseek_v3", cls="DeepseekV3", prefix=8192,
                        contexts=(108, 609), rows=("latent",)),
    "falcon_h1": dict(module="falcon_h1", cls="FalconH1", prefix=0,
                      contexts=(80, 500), rows=("kv", "ssm", "conv")),
    "mistral": dict(module="llama", cls="Llama", prefix=0,
                    contexts=(64, 640), rows=None, flax=True),
}


def build(args):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    cell = CELLS[cfg["model_type"]]
    module = import_module("ray_tpu.models." + cell["module"])
    model_cls = getattr(module, cell["cls"])
    config_cls = getattr(module, cell["cls"] + "Config")
    if args.rehearsal:
        model = model_cls(config_cls.tiny())
        engine_cfg = dict(batch_slots=3, block_size=16, num_blocks=40,
                          max_blocks_per_seq=12, prefill_chunk=16)
        cell = {**cell, "prefix": min(cell["prefix"], 128)}
        live = 10
    else:
        if cell.get("flax"):
            from benchmarks import manifest

            model = model_cls(manifest.builder_of(cfg).llama_config(cfg))
        else:
            model = model_cls(config_cls.from_published(
                cfg, dtype=jnp.bfloat16))
        engine_cfg, live = cfg["engine"], args.live
    key = jax.random.PRNGKey(args.seed % (2 ** 31))
    if cell.get("flax"):
        params = jax.jit(lambda k: model.init(
            k, jnp.zeros((1, 8), jnp.int32)))(key)
    else:
        params = model.init(key)
    engine = InferenceEngine(
        EngineConfig(prefix_cache_enabled=False, **engine_cfg), model=model,
        params=params)
    return model, engine, cell, live


def arguments(engine, cell, live: int, seed: int):
    """(decode's, the chunk's) arguments: every slot its own shuffled
    blocks, the last slot the chunk's (dead among the decode rows)."""
    cfg = engine.config
    rng = np.random.default_rng(seed)
    slots, width, bsz = cfg.batch_slots, cfg.max_blocks_per_seq, \
        cfg.block_size
    reach = width * bsz
    tables = 1 + rng.permutation(cfg.num_blocks - 1)[:slots * width].reshape(
        slots, width).astype(np.int32)
    prefix, (near, far) = cell["prefix"], cell["contexts"]
    pos = rng.integers(min(prefix + near, reach - 2),
                       min(prefix + far, reach), slots).astype(np.int32)
    wmask = np.ones((slots, 1), bool)
    wmask[-1] = False
    chunk = cfg.prefill_chunk
    vocab = engine._model.config.vocab_size
    ids = rng.integers(1, vocab, (1, chunk)).astype(np.int32)
    chunk_args = (ids, tables[-1:], np.asarray([prefix], np.int32),
                  np.arange(chunk)[None] < live,
                  np.asarray([live - 1], np.int32),
                  np.asarray([slots - 1], np.int32))
    return (tables, pos, wmask), chunk_args


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="kanana-2-30b-a3b-l8-serve")
    parser.add_argument("--seed", type=int, default=2718281829)
    parser.add_argument("--live", type=int, default=160,
                        help="the chunk's live positions")
    parser.add_argument("--calls", type=int, default=30)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args()
    model, engine, cell, live = build(args)
    # Seeded rows in place of the zeros, an array at a time into its own
    # buffer: weights and cache fill the chip.
    fill = jax.jit(lambda a, k: (0.5 * jax.random.normal(
        k, a.shape, jnp.float32)).astype(a.dtype), donate_argnums=0)

    def filled(rows):
        leaves, tree = jax.tree.flatten(rows)
        keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
        return jax.tree.unflatten(
            tree, [fill(a, k) for k, a in zip(keys, leaves)])

    if cell["rows"] is None:
        engine._arenas = filled(engine._arenas)
    for part in cell["rows"] or ():
        engine._arenas[part] = filled(engine._arenas[part])
    decode_args, chunk_args = arguments(engine, cell, live, args.seed)
    vocab = model.config.vocab_size
    engine._tokens = jnp.asarray(np.random.default_rng(args.seed).integers(
        1, vocab, engine.config.batch_slots), jnp.int32)
    head = lambda: (engine._params, engine._arenas)  # noqa: E731
    programs = {
        "decode": lambda: engine._decode_fn(
            *head(), None, engine._tokens, *decode_args),
        "prefill": lambda: engine._prefill_fn(
            *head(), None, engine._tokens, *chunk_args),
        "decode_with_chunk": lambda: engine._decode_with_chunk_fn(
            *head(), engine._tokens, *decode_args, *chunk_args)}
    if args.rehearsal:
        args.calls = 2

    counted = model.cache_counters is not None

    def counters():
        return model.counter_stats(jax.device_get(
            model.cache_counters(engine._arenas)))["moe"]

    device = jax.devices()[0]
    line = {"config": args.config, "seed": args.seed, "live": live,
            "calls": args.calls, "platform": device.platform,
            "device_kind": device.device_kind, "ms": {}, "compile_s": {}}
    if counted:
        line["experts_drawn_a_layer"] = {}
    for name, run in programs.items():
        start = time.perf_counter()
        engine._tokens, engine._arenas = run()
        jax.block_until_ready(engine._tokens)
        line["compile_s"][name] = time.perf_counter() - start
        before, best = counters() if counted else None, float("inf")
        for _ in range(3):
            start = time.perf_counter()
            for _ in range(args.calls):
                # the tokens are not fed back: every call the same rows
                _, engine._arenas = run()
            jax.block_until_ready(engine._arenas)
            best = min(best, (time.perf_counter() - start) / args.calls)
        line["ms"][name] = best * 1e3
        if not counted:
            continue
        after = counters()
        drew = sum(after[k]["drew"] - before[k]["drew"]
                   for k in ("decode", "prefill"))
        line["experts_drawn_a_layer"][name] = drew / (
            3 * args.calls * after["layers"])
    ms = line["ms"]
    line["saved_ms"] = ms["decode"] + ms["prefill"] - ms["decode_with_chunk"]
    print(json.dumps(line), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "serve_steps.json"), "w") as f:
        json.dump(line, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
