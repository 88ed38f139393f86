#!/usr/bin/env python3
"""One run of one benchmark cell.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that starts a cluster, drives the cell's configuration
through the system's normal entry points (`JaxTrainer.fit`, `serve.run`),
measures for `--seconds`, prints its lines, stops everything it started
and exits. This process never starts a jax backend: the train worker or
the serve replica holds the chip. No chip of the cell's count, no run.

Every line printed is one JSON object. The LAST line is the result the
driver reads (`correct`, `attempted`, `failed`, `metrics`, `device`, and
`breakdown` in a traced run); sample counts, generator lateness, the
engine's `stats()` and the spans go on earlier lines and into
`<out>/run.json`. With `--trace 0` the metrics are the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics.

`--rehearsal` is a labelled CPU dress rehearsal at the tiny sizes each
file's `rehearsal` block gives: it proves the harness, prints no metric
value, and says nothing about the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

T_PROCESS_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# This file's own directory must not shadow top-level modules; the
# checkout's root makes `benchmarks` and `ray_tpu` importable, here and
# (through PYTHONPATH) in every worker.
sys.path[:] = [ROOT] + [p for p in sys.path
                        if os.path.abspath(p or ".") != os.path.dirname(
                            os.path.abspath(__file__))]

STAMP: dict = {}


def emit(**fields) -> None:
    print(json.dumps({**fields, **STAMP}), flush=True)


class Context:
    """What a builder's `run(ctx)` is handed."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.emit = emit


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU dress rehearsal at tiny sizes; no metric value "
                        "is printed")
    p.add_argument("--rate", type=float, default=None,
                   help="override an open-loop mix's rate (the knee sweep "
                        "only; a cell's rate is the one in its file)")
    p.add_argument("--out", default=None,
                   help="output directory (default: a new directory under "
                        "the temporary directory)")
    p.add_argument("--keep-trace-sample", action="store_true",
                   help="also write a cut of the trace in the plain form "
                        "the tests read")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from benchmarks import manifest as mf

    manifest = mf.load(ROOT)
    cell = mf.cell_of(manifest, args.workload)
    config = mf.config_of(manifest, cell, ROOT)
    traffic = mf.traffic_of(cell)
    seconds = float(manifest["run_seconds"] if args.seconds is None
                    else args.seconds)
    chips = int(cell["chips"])
    if args.rehearsal:
        STAMP["rehearsal"] = True
        config, traffic = mf.apply_rehearsal(config), \
            mf.apply_rehearsal(traffic)
        os.environ.update(
            JAX_PLATFORMS="cpu", RAY_TPU_PALLAS_INTERPRET="1",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    try:
        from ray_tpu.core.node import detect_tpu_chips
    except ImportError as e:
        sys.exit(f"benchmark: the system under test is not in this "
                 f"checkout ({e}); nothing to measure")
    found = detect_tpu_chips()
    pinned = os.environ.get("JAX_PLATFORMS", "")
    if not args.rehearsal:
        if found < chips:
            sys.exit(f"benchmark: workload {cell['name']} needs {chips} TPU "
                     f"chip(s), this host has {found}. It never runs on "
                     f"the CPU: see --rehearsal.")
        if pinned and "tpu" not in pinned.split(","):
            sys.exit(f"benchmark: JAX_PLATFORMS={pinned!r} keeps this run "
                     f"off the chip; unset it.")
    # Everything the system writes goes under the run's own temporary
    # directory or the checkout (the compile cache, `.jax_cache/`).
    out_dir = args.out or tempfile.mkdtemp(prefix="bench_")
    os.makedirs(out_dir, exist_ok=True)
    os.environ.setdefault("RAY_TPU_TMPDIR",
                          os.path.join(tempfile.gettempdir(), "ray_tpu"))

    from benchmarks import procs

    procs.tag_this_tree()
    builder = mf.builder_of(config)
    ctx = Context(cell=cell, config=config, traffic=traffic, seed=args.seed,
                  seconds=seconds, trace=bool(args.trace),
                  rehearsal=args.rehearsal, rate=args.rate, out_dir=out_dir,
                  keep_trace_sample=args.keep_trace_sample,
                  t_process_start=T_PROCESS_START)
    facts, error = None, None
    try:
        import ray_tpu

        ray_tpu.init()
        if not args.rehearsal:
            advertised = int(ray_tpu.cluster_resources().get("TPU", 0))
            if advertised < chips:
                raise RuntimeError(f"node advertises {advertised} chips, "
                                   f"the cell needs {chips}")
        emit(workload=cell["name"], config=cell["config"],
             traffic=cell["traffic"], chips=chips, seed=args.seed,
             seconds=seconds, trace=args.trace, chips_on_host=found,
             out_dir=out_dir)
        facts = builder.run(ctx)
    except BaseException as e:  # noqa: BLE001 — reported, then re-raised
        error = e               # as the exit code, after the clean-up
    finally:
        try:
            from ray_tpu import serve

            if ray_tpu.is_initialized():
                serve.shutdown()
        except Exception:  # noqa: BLE001 — nothing was served
            pass
        left = procs.stop_everything()
    if args.out is None:
        import shutil

        # Hundreds of MB a traced run: reduced already, not kept.
        shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
    if error is not None:
        import traceback

        traceback.print_exception(error, file=sys.stderr)
        sys.exit(f"benchmark: run failed: {type(error).__name__}: {error}")
    if left:
        sys.exit(f"benchmark: processes outlived the shutdown: {left}")
    return report(manifest, cell, config, traffic, args, seconds, facts,
                  out_dir)


def report(manifest, cell, config, traffic, args, seconds, facts, out_dir):
    from benchmarks import manifest as mf

    device = dict(facts["device"])
    if not args.rehearsal and (device["platform"] != "tpu"
                               or device["count"] != cell["chips"]):
        sys.exit(f"benchmark: ran on {device}, the cell asks for "
                 f"{cell['chips']} TPU chip(s)")
    facts["cell"], facts["config"], facts["traffic"] = cell, config, traffic
    facts["seconds"] = seconds
    setup_s = facts["setup_end"] - T_PROCESS_START
    measured = {"setup_s": setup_s, **facts["end_to_end"]}
    metrics, missing = {}, []
    if args.trace:
        kind = "per_layer"
        for m in mf.metrics_of(manifest, cell["name"], kind):
            value = mf.reader_of(m["name"])(facts)
            if value is None:
                missing.append(m["name"])
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        kind = "end_to_end"
        for m in mf.metrics_of(manifest, cell["name"], kind):
            if m["name"] in measured:
                metrics[m["name"]] = {"value": measured[m["name"]],
                                      "unit": m["unit"]}
            else:
                missing.append(m["name"])
    problems = list(facts["problems"])
    trace = facts.get("trace")
    if args.trace and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    emit(kind=kind, problems=problems, metrics_left_out=missing,
         setup_s=setup_s)
    result = {"correct": not problems, "attempted": facts["attempted"],
              "failed": facts["failed"], "metrics": metrics,
              "device": device}
    if args.trace and trace:
        result["breakdown"] = trace["breakdown"]
    with open(os.path.join(out_dir, "run.json"), "w") as f:
        json.dump({"result": result, "problems": problems,
                   "spans": facts.get("spans"),
                   "counters": facts.get("counters"),
                   "client": facts.get("client"),
                   "trace": trace}, f)
    if args.rehearsal:
        # A CPU run names no device metric: which metrics the harness
        # produced, never what they read.
        result = {"rehearsal": True, "correct": result["correct"],
                  "attempted": result["attempted"],
                  "failed": result["failed"],
                  "metrics_reported": sorted(metrics),
                  "metrics_left_out": missing, "problems": problems,
                  "device": {"platform": device["platform"],
                             "count": device["count"]}}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
