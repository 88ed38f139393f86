"""The paged attention kernel ALONE at the serve cells' shapes, on the chip:
us a `paged_attention` call and the share of its bytes' roofline, of this
tree and of every checkout named beside it, and how far each tree's outputs
lie from the dense reference's. ROADMAP caveat 9: time a kernel alone before
predicting the step.

    chiprun -- python3 scripts/time_paged_kernels.py \
        --tree parent=.scratch/parent

The decode shapes (one query token a row; block tables of shuffled pages of
16 tokens, bf16):

  ouro.*      8 rows x 16 heads over 16 KV heads, a table of 36 pages:
              contexts drawn U(64,576) (`ouro.u64_576`) and all equal at
              128 / 284 / 512 / 576, the ninth cell's decode call;
  mistral.*   16 rows x 32 heads over 8 KV heads, a table of 256: contexts
              near 350 and near 900, the two Mistral cells';
  falconh1.*  64 rows x 20 heads over 4 KV heads, a table of 64: near 290;

the block step of a model that decodes by blocks (`models/sdar.py`; 32 rows
x 32 heads over 4 KV heads, a table of 32, ~420 tokens a row, each query
seeing to its block's end): `sdar.block64`, two blocks of four positions a
row (64 query rows a KV head: the step since PR 63) beside `sdar.block32`,
one block a row (32: the step before it);

a speculative round's verify call at Mistral's widths (`mistral.spec5`: five
query tokens a row, 20 query rows a KV head) and one prefill chunk each (`*.chunk`: 256 / 512 / 256 positions behind 256
cached tokens), which the decode tile's changes must not move.

A case is timed as ONE program that makes `--calls` calls in a scan (each
call's output is the next one's query), so the host's dispatch is not in the
number. `us` is the device time of the `paged_attention` custom call in a
profiler trace of that program (what the ledger's breakdown reads), `us_op`
the program's wall clock a call (the wrapper's pad and transposes ride
along), `roofline_pct` the live keys and values, q and o over 819 GB/s over
`us`. One JSON line a tree on stdout (the checkouts first, this tree last),
all of them in `chiprun_out/paged_kernels.json`. `--rehearsal` under
RAY_TPU_PALLAS_INTERPRET=1 runs small shapes on the CPU (times of the
interpreter: no device number is printed).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

HD, BLOCK = 128, 16
HBM_BYTES_PER_S = 819e9   # one v5e chip (benchmarks/peaks.py)
TOLERANCE = 2e-2          # bf16 outputs of order one

# name -> (rows, heads, kv_heads, table width, query tokens a row,
#          (lowest, highest) context after the call[, the positions of a
#          diffusion block: a query sees to its block's end])
SHAPES = {
    "ouro.u64_576": (8, 16, 16, 36, 1, (64, 576)),
    "ouro.128": (8, 16, 16, 36, 1, (128, 128)),
    "ouro.284": (8, 16, 16, 36, 1, (284, 284)),
    "ouro.512": (8, 16, 16, 36, 1, (512, 512)),
    "ouro.576": (8, 16, 16, 36, 1, (576, 576)),
    "mistral.350": (16, 32, 8, 256, 1, (250, 450)),
    "mistral.900": (16, 32, 8, 256, 1, (800, 1000)),
    "falconh1.290": (64, 20, 4, 64, 1, (200, 380)),
    "mistral.spec5": (16, 32, 8, 256, 5, (250, 450)),
    "sdar.block32": (32, 32, 4, 32, 4, (332, 508), 4),
    "sdar.block64": (32, 32, 4, 32, 8, (332, 508), 4),
    "ouro.chunk": (1, 16, 16, 36, 256, (512, 512)),
    "mistral.chunk": (1, 32, 8, 256, 512, (768, 768)),
    "falconh1.chunk": (1, 20, 4, 64, 256, (512, 512)),
}
REHEARSAL = {
    "ouro.u64_576": (3, 16, 16, 6, 1, (1, 96)),
    "mistral.350": (2, 8, 2, 40, 1, (500, 640)),
    "falconh1.290": (3, 10, 2, 6, 1, (20, 90)),
    "sdar.block64": (3, 8, 2, 6, 8, (40, 92), 4),
    "ouro.chunk": (1, 16, 16, 6, 32, (64, 64)),
}


def load(name: str, tree: str):
    """`ops/paged_attention.py` of the checkout at `tree`, as its own
    module."""
    path = os.path.join(tree, "ray_tpu", "ops", "paged_attention.py")
    spec = importlib.util.spec_from_file_location(f"paged_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def case(shape, seed: int):
    """(q, k_arena, v_arena, block_tables, positions, write_mask, live
    tokens): every row its own shuffled physical pages (page 0 the trash
    block the tables' tails point at)."""
    b, heads, kvh, width, s, (lo, hi) = shape[:6]
    length = shape[6] if len(shape) > 6 else 1
    rng = np.random.default_rng(seed)
    # (whole diffusion blocks, where the model has them)
    context = rng.integers(lo, hi + 1, b) // length * length
    tables = np.zeros((b, width), np.int32)
    held = -(-context // BLOCK)
    pages = 1 + rng.permutation(int(held.sum()))
    for i, n in enumerate(held):
        tables[i, :n], pages = pages[:n], pages[n:]
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 3)
    arena = (1 + int(held.sum()), BLOCK, kvh, HD)
    k, v = (jax.random.normal(key, arena, jnp.bfloat16) for key in keys[:2])
    q = jax.random.normal(keys[2], (b, s, heads, HD), jnp.bfloat16)
    positions = (context[:, None] - s + np.arange(s)[None]).astype(np.int32)
    positions = (positions // length + 1) * length - 1
    return (q, k, v, jnp.asarray(tables), jnp.asarray(positions),
            jnp.ones((b, s), bool), int(context.sum()))


def wall_us(fn, calls: int, repeats: int = 5) -> float:
    """The least of `repeats` timings of one program of `calls` calls."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(repeats):
        start = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, (time.perf_counter() - start) / calls)
    return best * 1e6


def traced_us(fn, executions: int = 3):
    """Device us a `paged_attention` custom call in a profiler trace of
    `executions` runs of the program, or None where the trace has no device
    plane (the CPU)."""
    from benchmarks import xplane
    from benchmarks.layer_metrics._common import kernel_label

    out = tempfile.mkdtemp(prefix="paged_trace_")
    try:
        with jax.profiler.trace(out):
            for _ in range(executions):
                jax.block_until_ready(fn())
        digest = xplane.reduce_dir(out)
    finally:
        shutil.rmtree(out, ignore_errors=True)
    if not digest:
        return None
    calls, seconds = xplane.ops_matching(digest,
                                         kernel_label("paged_attention"))
    return 1e6 * seconds / calls if calls else None


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tree", action="append", default=[],
                        metavar="[NAME=]CHECKOUT")
    parser.add_argument("--seed", type=int, default=2718281829)
    parser.add_argument("--calls", type=int, default=48)
    parser.add_argument("--only", default="",
                        help="comma-separated prefixes of the cases to run")
    parser.add_argument("--rehearsal", action="store_true")
    parser.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = parser.parse_args(argv)
    # NAME=CHECKOUT, or a bare checkout named after its directory
    trees = [t.split("=", 1) if "=" in t
             else [os.path.basename(os.path.normpath(t)), t]
             for t in args.tree] + [["this", ROOT]]
    shapes = REHEARSAL if args.rehearsal else SHAPES
    if args.only:
        shapes = {k: v for k, v in shapes.items()
                  if k.startswith(tuple(args.only.split(",")))}
    calls = 2 if args.rehearsal else args.calls
    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    cases = {name: case(shape, args.seed + i)
             for i, (name, shape) in enumerate(shapes.items())}
    lines = []
    for name, tree in trees:
        op = load(name, tree)
        line = {"tree": name, "seed": args.seed, "calls": calls,
                "platform": device.platform,
                "device_kind": device.device_kind, "us": {}, "us_op": {},
                "roofline_pct": {}, "max_abs_diff": {}}
        for label, (q, k, v, tables, positions, mask, live) in cases.items():
            def chain(q, k, v, tables, positions, mask, op=op):
                def body(x, _):
                    return op.paged_attention(x, k, v, tables, positions,
                                              mask), None
                return jax.lax.scan(body, q, None, length=calls)[0]

            one = jax.jit(op.paged_attention)(q, k, v, tables, positions,
                                              mask)
            ref = op.paged_attention_reference(q, k, v, tables, positions)
            line["max_abs_diff"][label] = float(jnp.max(jnp.abs(
                one.astype(jnp.float32) - ref.astype(jnp.float32))))
            program = jax.jit(chain)
            run = lambda: program(q, k, v, tables, positions,  # noqa: E731
                                  mask)
            line["us_op"][label] = wall_us(run, calls) if on_chip else None
            us = traced_us(run) if on_chip else None
            line["us"][label] = us
            need = 2 * (2 * k.shape[2] * HD * live + 2 * q.size)
            line["roofline_pct"][label] = \
                100.0 * need / HBM_BYTES_PER_S / (us * 1e-6) if us else None
        line["paths"] = sorted(
            {tuple(str(r.get(key)) for key in
                   ("pass", "path", "block_q", "block_k", "tile"))
             for r in op._attn.pallas_status()
             if r["pass"].startswith("paged_")})
        lines.append(line)
        print(json.dumps(line), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "paged_kernels.json"), "w") as f:
        json.dump(lines, f, indent=1)
    worst = max(max(line["max_abs_diff"].values()) for line in lines)
    return 0 if np.isfinite(worst) and worst <= TOLERANCE else 1


if __name__ == "__main__":
    sys.exit(main())
