"""The harness is driven by data: a new configuration, traffic mix and
per-layer metric are each a file plus an entry, and the new cell runs with
no other file touched. Doubles as the end-to-end `--rehearsal` of one
train and one serve cell on the CPU at tiny sizes (~15 s each: cluster
start, a worker, jax start-up and the tiny compiles)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import _paths

READER = '''"""A metric of its own: steps or requests per second of set-up."""


def read(facts):
    return facts["attempted"] / (facts["setup_end"]
                                 - facts["spans"]["%s"])
'''


def checkout(tmp_path):
    """BENCHMARK.json and benchmarks/ alone, beside the system under test."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(_paths.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(_paths.ROOT, "benchmarks"),
                    root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(_paths.ROOT, "ray_tpu"), root / "ray_tpu")
    return root


def run_cell(root, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", *args], cwd=root, env=env,
        capture_output=True, text=True, timeout=240)


CASES = {
    "train": dict(
        base_cell="train_gpt2m_1chip", base_traffic="pretrain_packed_1k",
        config_edit=lambda c: c["rehearsal"].update(n_layer=1),
        traffic_edit=lambda t: t["rehearsal"].update(seq=256),
        moves="train_tok_s_chip", first_span="fit_called",
        e2e=["setup_s", "train_tok_s_chip"]),
    "serve": dict(
        base_cell="serve_mistral7b_chat", base_traffic="chat",
        config_edit=lambda c: c["rehearsal"]["engine"].update(batch_slots=2),
        traffic_edit=lambda t: t["rehearsal"].update(rate_rps=6.0),
        moves="itl_p90_ms", first_span="serve_run_called",
        e2e=["itl_p90_ms", "setup_s"]),
}


@pytest.mark.parametrize("kind", sorted(CASES))
def test_a_new_cell_is_files_and_entries(tmp_path, kind):
    case = CASES[kind]
    root = checkout(tmp_path)
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _, files in os.walk(root / "benchmarks")
              for p in files}
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    base = next(w for w in manifest["workloads"]
                if w["name"] == case["base_cell"])
    base_cfg = next(c for c in manifest["configs"]
                    if c["name"] == base["config"])
    # one new file each: configuration, traffic mix, per-layer metric
    config = json.loads((root / base_cfg["file"]).read_text())
    config["name"] = "dropped-in-config"
    case["config_edit"](config)
    (root / "benchmarks/configs/dropped-in-config.json").write_text(
        json.dumps(config))
    traffic = json.loads(
        (root / f"benchmarks/traffic/{case['base_traffic']}.json").read_text())
    case["traffic_edit"](traffic)
    (root / "benchmarks/traffic/dropped_in_mix.json").write_text(
        json.dumps(traffic))
    (root / "benchmarks/layer_metrics/dropped.in_rate.py").write_text(
        READER % case["first_span"])
    # ... and one entry each
    manifest["configs"].append({**base_cfg, "name": "dropped-in-config",
                                "file": "benchmarks/configs/"
                                        "dropped-in-config.json"})
    manifest["workloads"].append({**base, "name": "dropped_in_cell",
                                  "config": "dropped-in-config",
                                  "traffic": "dropped_in_mix"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if case["base_cell"] in m.get("workloads", []):
            m["workloads"].append("dropped_in_cell")
    manifest["per_layer"].append({
        "name": "dropped.in_rate", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "model and step",
        "moves": case["moves"], "workloads": ["dropped_in_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    sys.path.insert(0, _paths.ROOT)
    from benchmarks import manifest as mf

    assert mf.validate(manifest, str(root)) == []

    for trace, want in (("0", case["e2e"]), ("1", ["dropped.in_rate"])):
        done = run_cell(root, "--workload", "dropped_in_cell", "--seed",
                        "3000000019", "--seconds", "2", "--trace", trace,
                        "--rehearsal")
        assert done.returncode == 0, done.stderr[-3000:]
        lines = [json.loads(x) for x in done.stdout.splitlines()
                 if x.startswith("{")]
        assert all(x.get("rehearsal") is True for x in lines)
        last = lines[-1]
        assert last["correct"] is True and last["failed"] == 0, lines[-2:]
        assert last["attempted"] > 0
        assert set(want) <= set(last["metrics_reported"])
        # a CPU run prints which metrics it produced, never a value
        assert "metrics" not in last and last["device"]["platform"] == "cpu"
    after = {p: os.path.getmtime(os.path.join(dp, p))
             for dp, _, files in os.walk(root / "benchmarks")
             for p in files if p in before}
    assert after == before, "an existing benchmark file was touched"


def test_no_chip_no_run(tmp_path):
    """Without the cell's chips the run fails and prints no result."""
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "train_gpt2m_1chip", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=_paths.ROOT, capture_output=True, text=True, timeout=60,
        env={**os.environ, "RAY_TPU_NUM_TPUS": ""})
    assert done.returncode != 0
    assert "never runs on the CPU" in done.stderr
    assert not [x for x in done.stdout.splitlines() if x.startswith("{")]


def test_nothing_to_measure_without_the_system(tmp_path):
    """BENCHMARK.json and the benchmark's own directories alone: non-zero
    exit, no result."""
    root = tmp_path / "bare"
    root.mkdir()
    shutil.copy(os.path.join(_paths.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(_paths.ROOT, "benchmarks"),
                    root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "train_gpt2m_1chip", "--seed", "1", "--seconds", "1", "--trace",
         "0", "--rehearsal"], cwd=root, env=env, capture_output=True,
        text=True, timeout=60)
    assert done.returncode != 0
    assert not [x for x in done.stdout.splitlines() if x.startswith("{")]
