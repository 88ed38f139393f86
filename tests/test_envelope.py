"""Scalability-envelope smoke (reference release/benchmarks/README.md).

The real numbers come from `python bench.py` (bench_envelope); this
keeps the envelope harness itself from rotting, at toy sizes. Runs in a
subprocess for the same reason bench_envelope does: the fake cluster
would otherwise collide with the pytest process's shared global runtime.
"""

import json
import os
import subprocess
import sys


def test_envelope_smoke():
    code = ("import bench, json; "
            "print('ENV_RESULT ' + json.dumps("
            "bench._envelope_main(60, 4, 3, 40, 8)))")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_WORKER_LEASE_TIMEOUT_MS"] = "180000"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                          capture_output=True, text=True, timeout=600)
    out = None
    for line in (proc.stdout or "").splitlines():
        if line.startswith("ENV_RESULT "):
            out = json.loads(line[len("ENV_RESULT "):])
    assert out is not None, (proc.stderr or "")[-800:]
    assert out["envelope_tasks"] == 60
    assert out["envelope_task_throughput_per_s"] > 0
    assert out["envelope_get_many_refs_s"] >= 0
    assert out["envelope_actors"] == 4
    assert out["envelope_pgs"] == 3
    assert out["envelope_broadcast_nodes"] >= 1
    assert out["envelope_broadcast_gb_s"] > 0
