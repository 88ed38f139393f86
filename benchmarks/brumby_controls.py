#!/usr/bin/env python3
"""The controls of the Brumby cell's check against its reference.

    python benchmarks/brumby_controls.py --seed <n> [--rehearsal]

`benchmarks/builders/brumby_serve.py` holds the system to three limits;
this shows what they are there to refuse. In ONE process that holds the
chip (no cluster, no HTTP, no window) it makes the cell's weights from the
seed once, and for the system as it is and for each control builds the
cell's engine, drives the builder's four check requests through it
(`leaver` leaves a slot, `reuser` is admitted when it has), and puts what
came out through the builder's own `reference_check` and `check_problems`
against the TRUE weights:

- `bf16_state`: the state and the key sum carried in bfloat16 (rounded
  after every step; the kernels' arithmetic stays float32);
- `no_reset`: a row that starts at position 0 inherits its slot's state;
- `advance_masked`: masked positions advance the state (a row waiting
  between two of its prefill chunks, a chunk's padding).

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

CELL = "serve_brumby14b_batchgen"


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


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
    from benchmarks.builders import brumby_serve as b
    from benchmarks.falconh1_controls import drive
    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.models.brumby import Brumby
    from ray_tpu.ops.power_retention import (reset_retention_status,
                                             retention_status)

    manifest = mf.load(ROOT)
    cfg = mf.config_of(manifest, mf.cell_of(manifest, CELL), ROOT)
    if args.rehearsal:
        cfg = mf.apply_rehearsal(cfg)
    model_cfg = {k: cfg[k] for k in b.MODEL_KEYS}
    mc = b.model_config(cfg)
    params = b.init_params(Brumby(mc), args.seed)
    check = b.check_requests(cfg, args.seed)

    class Bf16State(Brumby):
        def paged_step(self, *args, **kwargs):
            logits, cache = super().paged_step(*args, **kwargs)
            # (not a cast there and back: the compiler may keep the excess
            # precision of one)
            return logits, jax.tree.map(
                lambda a: jax.lax.reduce_precision(a, 8, 7), cache)

    class NoReset(Brumby):
        def state_rows(self, row_pos, write_mask):
            return jnp.zeros(row_pos.shape, bool), write_mask

    class AdvanceMasked(Brumby):
        def state_rows(self, row_pos, write_mask):
            fresh, _ = super().state_rows(row_pos, write_mask)
            return fresh, jnp.ones_like(write_mask)

    runs = {"system": Brumby, "bf16_state": Bf16State, "no_reset": NoReset,
            "advance_masked": AdvanceMasked}
    only = args.only.split(",") if args.only else list(runs)
    wrong = []
    for who in only:
        reset_retention_status()
        engine = InferenceEngine(EngineConfig(**cfg["engine"]),
                                 model=runs[who](mc), params=params)
        tokens = drive(engine, check)
        reference = b.reference_check(
            params, engine._arenas, model_cfg,
            [{"who": r, "prompt": check[r]["ids"], "generated": tokens[r]}
             for r in check], args.seed)
        stats = {**engine.stats(), "retention": retention_status()}
        problems = b.check_problems(reference) + b.path_problems(stats) \
            + b.cache_problems(stats, cfg)
        emit(who=who, readings=reference, problems=problems)
        if bool(problems) == (who == "system"):
            wrong.append(who)
        del engine
        jax.clear_caches()
    emit(came_out_wrong=wrong,
         limits={"LOGIT_MARGIN": b.LOGIT_MARGIN,
                 "STATE_LIMIT_FIRST": b.STATE_LIMIT_FIRST,
                 "STATE_LIMIT": b.STATE_LIMIT})
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
