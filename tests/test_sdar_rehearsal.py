"""The SDAR cell's labelled CPU rehearsal end to end, as
`tests/benchmarks/test_bench_sdar.py::test_the_cells_rehearsal_runs_end_to_end`
holds it, with the block step's kernel call at the shape it has since PR 63
(two blocks a row: `(4, 8, 8, 128)`; that test pins the one-block
`(4, 4, 8, 128)` and is entered in `conftest.OUTGROWN`, being a file only a
`benchmark` PR may edit). Every other assertion of it is here, and what the
commits aboard add to the engine's books."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "serve_sdar30b_blockgen"


def test_the_cells_rehearsal_runs_end_to_end():
    env = {**os.environ, "PYTHONPATH": ROOT}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "4",
         "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True, lines[-2:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert {"setup.compile_s", "setup.deploy_s.serve", "startup.backend_s",
            "compile.cold_s", "diffusion.passes_per_token",
            "diffusion.commit_share_pct",
            "moe.experts_drawn_per_step.sdar"} <= set(
        last["metrics_reported"])
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    run = next(x for x in lines if x.get("builder") == "sdar_serve")
    stats = run["engine_stats"]
    # heads of 128: the kernel's interpreter path on both tiles, one call
    # a layer a program; the block program's rows are two blocks wide
    assert stats["paged_attn"] == {"decode": "pallas", "prefill": "pallas"}
    assert stats["paged_attn_tile"]["decode"].startswith("few rows")
    assert stats["paged_attn_tile"]["prefill"].startswith("many rows")
    assert sorted((tuple(c["shape"]), c["path"], c["calls"])
                  for c in stats["pallas"]) == [
        ((1, 48, 8, 128), "pallas", 2), ((4, 8, 8, 128), "pallas", 2)]
    # ... and its expert layers run 16-row tiles, like the chunk's here
    assert sorted((h["tokens"], h["tile"], h["calls"])
                  for h in stats["held_experts"]) == [(32, 16, 2),
                                                      (48, 16, 2)]
    book = stats["diffusion"]
    assert book["rule"] == "static" and book["schedule"] == [1, 1, 1, 1]
    assert book["committed_hist"][1] == book["denoise_passes"] > 0
    assert book["commit_passes"] == book["blocks_committed"] > 0
    # three blocks a request (12 new tokens): two of three commits carry
    # the next block's first denoise pass, less the streams cut short
    assert 0.5 * book["commit_passes"] < book["commits_aboard"] \
        < book["commit_passes"]
    assert stats["prefill_compiles"] == stats["decode_compiles"] == 1
    # dispatch-ahead is kept in block steps
    assert stats["steps"]["decode_ahead"] > 0.9 * stats["steps"]["decode"]
    assert stats["kv"]["bytes"] == 25 * 16 * (2 * 2 * 2 * 128 * 4 + 16) \
        + 18 * 4
    assert stats["state"]["slots"] == 0
    assert {r["who"] for r in run["reference"]} == {
        "short", "leaver", "mid", "long", "reuser"}
    assert all(r["cached_tokens"] >= 16 and r["tokens"] > 0
               for r in run["reference"])
    assert run["routing"]["tokens"] > 0
    assert run["routing"]["mismatch_share"] == 0.0
    # the books count row-passes: five a block whatever rode with what,
    # in fewer executions than row-passes a live row
    window = run["window"]["diffusion"]
    assert 1.25 <= (window["denoise_passes"] + window["commit_passes"]) \
        / window["tokens_committed"] < 1.4
    steps = run["window"]["steps"]
    assert steps["decode_rows"] == window["denoise_passes"] \
        + window["commit_passes"]
    assert run["window"]["moe"]["decode"]["steps"] == steps["decode"]
