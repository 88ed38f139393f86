"""A served expert layer ALONE at SDAR's widths, on the chip: ms a call of
`held_expert_forward` (the sorts, the rows' gather, both `moe_gmm` products
and the sum back) over 128 experts top-8, hidden 2048 and 768 a projection,
at the block step's shapes and at both row tiles `serve_tile` can give it.
ROADMAP caveat 9: time a layer alone before predicting the step.

    chiprun -- python3 scripts/time_expert_tiles.py

The cases (rows, of which live; the others are routed to no expert, as the
step routes its idle rows):

  one_block     128 rows, 126 live: the block step of one block a row
                (`live_tokens` None: 16-row tiles by the shape)
  two_blocks    256 rows, 160 live: a block step two blocks wide with a
                quarter of the rows' second halves live, at 16-row tiles
                (`live_tokens` 160) and at 128 (None: the shape's rule)
  all_aboard    256 rows, 252 live: every commit aboard at once, both tiles

A case is ONE program that makes `--calls` calls in a scan, each on seeded
rows of its own, timed on the host's clock around `block_until_ready`: the
least of five. The router is `route` on seeded weights, so the experts
drawn are a first layer's (nearly all of them; the cell's deeper layers
draw ~116 of 128). One JSON line on stdout, the same in
`chiprun_out/expert_tiles.json`. `--rehearsal` runs tiny shapes on the CPU
through the interpreter (no device number is printed).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import held_experts as moe  # noqa: E402

# name -> (rows, live rows, live_tokens handed to the layer)
CASES = {
    "one_block.tile16": (128, 126, None),
    "two_blocks.tile16": (256, 160, 160),
    "two_blocks.tile128": (256, 160, None),
    "all_aboard.tile16": (256, 252, 160),
    "all_aboard.tile128": (256, 252, None),
}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=2718281829)
    parser.add_argument("--calls", type=int, default=64)
    parser.add_argument("--rehearsal", action="store_true")
    parser.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = parser.parse_args(argv)
    hidden, width, experts, top_k = (64, 32, 128, 8) if args.rehearsal \
        else (2048, 768, 128, 8)
    calls = 2 if args.rehearsal else args.calls
    device = jax.devices()[0]
    keys = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 4)
    dt = jnp.bfloat16
    router = (jax.random.normal(keys[0], (hidden, experts)) * 0.02).astype(dt)
    w_gate_up = (jax.random.normal(keys[1], (experts, hidden, 2 * width))
                 * 0.02).astype(dt)
    w_down = (jax.random.normal(keys[2], (experts, width, hidden))
              * 0.02).astype(dt)
    line = {"seed": args.seed, "calls": calls, "platform": device.platform,
            "device_kind": device.device_kind, "ms": {}, "tile": {},
            "experts_drawn": {}, "tiles_run": {}}
    for name, (rows, live, live_tokens) in CASES.items():
        # every call its own seeded rows: a call fed the one before it
        # routes its tokens alike and draws a fifth of the experts
        xs = jax.random.normal(keys[3], (calls, rows, hidden)).astype(dt)
        mask = jnp.arange(rows) < live

        def chain(xs, router, w_gate_up, w_down, live_tokens=live_tokens,
                  mask=mask):
            def body(total, x):
                _, gates, index = moe.route(x, router, top_k)
                index = jnp.where(mask[:, None], index,
                                  experts).astype(jnp.int32)
                y, counts = moe.held_expert_forward(
                    x, gates, index, w_gate_up, w_down, (0, experts),
                    experts, live_tokens)
                return total + jnp.sum(y), (jnp.sum(counts["load"] > 0),
                                            counts["tiles"])
            return jax.lax.scan(body, jnp.float32(0), xs)

        moe.reset_held_experts_status()
        program = jax.jit(chain)
        run = lambda: program(xs, router, w_gate_up, w_down)  # noqa: E731
        _, (drew, tiles) = jax.block_until_ready(run())
        best = float("inf")
        for _ in range(5):
            start = time.perf_counter()
            jax.block_until_ready(run())
            best = min(best, (time.perf_counter() - start) / calls)
        line["ms"][name] = None if device.platform != "tpu" else 1e3 * best
        line["tile"][name] = moe.held_experts_status()[0]["tile"]
        line["experts_drawn"][name] = float(jnp.mean(drew))
        line["tiles_run"][name] = float(jnp.mean(tiles))
    moe.reset_held_experts_status()
    print(json.dumps(line), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "expert_tiles.json"), "w") as f:
        json.dump(line, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
