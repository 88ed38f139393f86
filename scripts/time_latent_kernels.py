"""The two latent attention kernels ALONE at the Kanana-2 cell's shapes, on
the chip: ms a call of `latent_decode` (32 slots x 32 heads at contexts of
8,300-8,800 over a `[2560, 128, 640]` bf16 arena) and of `latent_prefill`
(one row of 256 positions of which 64 / 160 / 256 are live behind 8,192
cached tokens, and one full chunk behind 4,096 as set-up runs it), of this
tree and of every checkout named beside it, and how far each tree's outputs
on LIVE queries lie from the first tree's. ROADMAP caveat 9: time a kernel
alone before predicting the step.

    chiprun -- python3 scripts/time_latent_kernels.py \
        --against parent=.scratch/parent

One JSON line a tree on stdout (the checkouts first, this tree last), all of
them in `chiprun_out/latent_kernels.json`. A call is the op as a model makes
it (`latent_attention`: the pad of q and the wrapper's scalars ride along).
`--slots 3 --prefix 256 --blocks 40 --calls 1` under
RAY_TPU_PALLAS_INTERPRET=1 rehearses it on the CPU (times of the
interpreter: no device number).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

HEADS, LATENT, ROPE, WIDTH, BLOCK, CHUNK = 32, 512, 64, 640, 128, 256
SCALE = (128 + ROPE) ** -0.5
TOLERANCE = 2e-2          # bf16 outputs of order one


def load(name: str, tree: str):
    """`ops/latent_attention.py` of the checkout at `tree`, as its own
    module."""
    path = os.path.join(tree, "ray_tpu", "ops", "latent_attention.py")
    spec = importlib.util.spec_from_file_location(f"latent_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cases(seed: int, slots: int, prefix: int, blocks: int) -> dict:
    """name -> (q, arena, block_tables, positions, write_mask): every row
    its own shuffled physical blocks (block 0 the trash block)."""
    rng = np.random.default_rng(seed)
    per_row = -(-(prefix + 608 + 1) // BLOCK) + 2
    if 1 + slots * per_row > blocks:
        raise SystemExit(f"{slots} rows of {per_row} blocks do not fit "
                         f"{blocks}")
    tables = 1 + rng.permutation(blocks - 1)[:slots * per_row].reshape(
        slots, per_row).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 3)
    arena = jax.random.normal(keys[0], (blocks, BLOCK, WIDTH), jnp.bfloat16)
    arena = arena.at[..., LATENT + ROPE:].set(0)

    def queries(key, b, s):
        return jax.random.normal(key, (b, s, HEADS, LATENT + ROPE),
                                 jnp.bfloat16)

    context = rng.integers(prefix + 108, prefix + 609, slots)
    out = {"decode": (queries(keys[1], slots, 1), arena, jnp.asarray(tables),
                      jnp.asarray(context[:, None] - 1, jnp.int32),
                      jnp.ones((slots, 1), bool))}
    q = queries(keys[2], 1, CHUNK)
    at = np.arange(CHUNK)[None]
    for name, start, live in (("prefill_q64", prefix, 64),
                              ("prefill_q160", prefix, 160),
                              ("prefill_q256", prefix, 256),
                              ("prefill_setup", prefix // 2, 256)):
        out[name] = (q, arena, jnp.asarray(tables[:1]),
                     jnp.asarray(start + at, jnp.int32),
                     jnp.asarray(at < live))
    return out


def per_call_ms(fn, calls: int, repeats: int = 5) -> float:
    """The least of `repeats` timings of `calls` queued calls."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(repeats):
        start = time.perf_counter()
        for _ in range(calls):
            out = fn()
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - start) / calls)
    return best * 1e3


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--against", action="append", default=[],
                        metavar="[NAME=]CHECKOUT")
    parser.add_argument("--seed", type=int, default=2718281829)
    parser.add_argument("--slots", type=int, default=32)
    parser.add_argument("--prefix", type=int, default=8192)
    parser.add_argument("--blocks", type=int, default=2560)
    parser.add_argument("--calls", type=int, default=200)
    args = parser.parse_args()
    # NAME=CHECKOUT, or a bare checkout named after its directory
    trees = [t.split("=", 1) if "=" in t
             else [os.path.basename(os.path.normpath(t)), t]
             for t in args.against] + [["this", ROOT]]
    shapes = cases(args.seed, args.slots, args.prefix, args.blocks)
    device = jax.devices()[0]
    first, lines = None, []
    for name, tree in trees:
        op = load(name, tree)
        line = {"tree": name, "seed": args.seed, "slots": args.slots,
                "prefix": args.prefix, "platform": device.platform,
                "device_kind": device.device_kind, "ms": {}, "walk": {},
                "max_abs_diff": {}, "bit_equal": {}}
        got = {}
        for case, (q, arena, tables, positions, mask) in shapes.items():
            call = jax.jit(lambda *a, op=op: op.latent_attention(
                *a, latent=LATENT, scale=SCALE))
            run = lambda: call(q, arena, tables, positions, mask)  # noqa: E731
            live = np.asarray(mask)
            got[case] = np.asarray(run().astype(jnp.float32))[live]
            line["ms"][case] = per_call_ms(run, args.calls)
            if hasattr(op, "tile_walk"):
                counts = op.tile_walk(
                    positions, mask, heads=HEADS, block_size=BLOCK,
                    max_ctx=tables.shape[1] * BLOCK, dtype=q.dtype)[2]
                line["walk"][case] = {k: int(v) for k, v in counts.items()}
        first = first or got
        for case in shapes:
            line["max_abs_diff"][case] = float(
                np.max(np.abs(got[case] - first[case])))
            line["bit_equal"][case] = bool(
                np.array_equal(got[case], first[case]))
        line["paths"] = sorted({(r["pass"], r["path"], r["block_q"],
                                 r["block_k"])
                                for r in op.latent_attention_status()})
        lines.append(line)
        print(json.dumps(line), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "latent_kernels.json"), "w") as f:
        json.dump(lines, f, indent=1)
    worst = max(max(line["max_abs_diff"].values()) for line in lines)
    return 0 if np.isfinite(worst) and worst <= TOLERANCE else 1


if __name__ == "__main__":
    sys.exit(main())
