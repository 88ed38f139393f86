"""The two recurrence kernels ALONE at the Qwen3-Next cell's shapes, on the
chip: ms a call of `gdn_chunk_fwd` and `gdn_chunk_bwd`, of this tree and of
every `ops/gated_delta.py` named beside it, and whether the seven arrays
they write (o, the chunk-start states, dq, dk, dv, dG, dbeta) are bit-equal
to the first tree's. ROADMAP caveat 9: time a kernel alone before the cell.

    chiprun -- python3 scripts/time_gdn_kernels.py \
        --tree parent=.scratch/parent --tree change=.

One JSON line a tree on stdout, all of them in `chiprun_out/gdn_kernels.json`.
`--seq 256 --calls 1` under RAY_TPU_PALLAS_INTERPRET=1 rehearses it on the
CPU (times of the interpreter: no device number).
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

from ray_tpu.ops.attention import _interpret  # noqa: E402

NAMES = ("o", "states", "dq", "dk", "dv", "dG", "dbeta")


def load(name: str, tree: str):
    """`ops/gated_delta.py` of the checkout at `tree`, as its own module."""
    path = os.path.join(tree, "ray_tpu", "ops", "gated_delta.py")
    spec = importlib.util.spec_from_file_location(f"gated_delta_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def inputs(seed: int, seq: int, key_heads: int, value_heads: int, d: int):
    """q, k, v, g, beta as `models/qwen3_next.py` hands them, and do."""
    ks = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 6)
    q = jax.random.normal(ks[0], (1, seq, key_heads, d))
    k = jax.random.normal(ks[1], (1, seq, key_heads, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (1, seq, value_heads, d))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3],
                                                 (1, seq, value_heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, seq, value_heads)))
    do = jax.random.normal(ks[5], (1, seq, value_heads * d))
    bf16 = jnp.bfloat16
    return (q.astype(bf16), k.astype(bf16), v.astype(bf16), g, beta), \
        do.astype(bf16)


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
    parser.add_argument("--tree", action="append", default=[],
                        metavar="NAME=PATH")
    parser.add_argument("--seed", type=int, default=2718281829)
    parser.add_argument("--seq", type=int, default=8192)
    parser.add_argument("--calls", type=int, default=40)
    args = parser.parse_args()
    trees = [t.split("=", 1) for t in args.tree] or [["change", ROOT]]
    operands, do = inputs(args.seed, args.seq, 16, 32, 128)
    device = jax.devices()[0]
    first, lines = None, []
    for name, tree in trees:
        gd = load(name, tree)
        ops, steps = gd._kernel_operands(*operands)
        kw = dict(chunk=gd.CHUNK, steps=steps, interpret=_interpret())
        pad = ops[0].shape[1] - args.seq
        do_w = jnp.pad(do, ((0, 0), (0, pad), (0, 0)))
        o, states = gd._gdn_forward(*ops, save=True, **kw)
        got = [o, states, *gd._gdn_backward(*ops, states, do_w, **kw)]
        got = [np.asarray(t.astype(jnp.float32)) for t in got]
        first = first or got
        line = {
            "tree": name, "seq": args.seq, "seed": args.seed,
            "chunks_abreast": steps,
            "platform": device.platform, "device_kind": device.device_kind,
            "fwd_ms": per_call_ms(
                lambda: gd._gdn_forward(*ops, save=True, **kw), args.calls),
            "bwd_ms": per_call_ms(
                lambda: gd._gdn_backward(*ops, states, do_w, **kw),
                args.calls),
            "bit_equal": {n: bool(np.array_equal(a, b))
                          for n, a, b in zip(NAMES, got, first)},
            "max_rel_diff": {
                n: float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))
                for n, a, b in zip(NAMES, got, first)},
        }
        lines.append(line)
        print(json.dumps(line), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "gdn_kernels.json"), "w") as f:
        json.dump(lines, f, indent=1)
    return 0 if all(all(line["bit_equal"].values()) for line in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
