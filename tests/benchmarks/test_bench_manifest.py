"""`BENCHMARK.json` against the contract, and the files it names."""

import copy
import json
import os

import pytest

import _paths
from benchmarks import manifest as mf


@pytest.fixture(scope="module")
def manifest():
    return mf.load(_paths.ROOT)


def test_benchmark_json_meets_the_contract(manifest):
    assert mf.validate(manifest, _paths.ROOT) == []


def test_a_full_check_of_24_cells_fits_the_budget(manifest):
    assert mf.check_budget(manifest, cells=24) is None
    assert mf.check_budget({**manifest, "run_seconds": 52}) is not None


def test_paths_hold_the_benchmark_and_its_tests(manifest):
    assert manifest["paths"] == ["benchmarks", "tests/benchmarks"]
    assert manifest["command"] == ["python3", "benchmarks/run.py"]


def break_(manifest, fn):
    m = copy.deepcopy(manifest)
    fn(m)
    return mf.validate(m, _paths.ROOT)


def metric(m, name):
    return next(x for x in m["end_to_end"] + m["per_layer"]
                if x["name"] == name)


def one_beyond_the_quarter(m):
    """Four chips for as many one-chip cells as make one more than a
    quarter of the cells, rounded down (one always may)."""
    allowed = max(1, len(m["workloads"]) // 4)
    have = sum(w["chips"] == 4 for w in m["workloads"])
    for w in [w for w in m["workloads"] if w["chips"] == 1][
            :allowed + 1 - have]:
        w["chips"] = 4


@pytest.mark.parametrize("what,fn", [
    ("a unit over 16 characters",
     lambda m: metric(m, "train_tok_s_chip").update(unit="tokens per second")),
    ("a unit with a space",
     lambda m: metric(m, "itl_p90_ms").update(unit="m s")),
    ("a name with a slash",
     lambda m: m["workloads"][0].update(name="train/one")),
    ("a bound over 0.1",
     lambda m: metric(m, "itl_p90_ms").update(bound=0.2)),
    ("a bound under 1%",
     lambda m: metric(m, "train_tok_s_chip").update(bound=0.001)),
    ("moves a metric the cell does not report",
     lambda m: metric(m, "engine.queue_ms").update(moves="train_tok_s_chip")),
    ("moves an unknown metric",
     lambda m: metric(m, "engine.queue_ms").update(moves="nothing")),
    ("a why on a metric",
     lambda m: metric(m, "setup_s").update(why="because")),
    ("one four-chip cell beyond the quarter", one_beyond_the_quarter),
    ("three chips", lambda m: m["workloads"][0].update(chips=3)),
    ("a pair twice",
     lambda m: m["workloads"][3].update(traffic="pretrain_packed_1k")),
    ("a width in reduced",
     lambda m: m["configs"][1]["reduced"].append("hidden_size")),
    ("a head size in reduced",
     lambda m: m["configs"][1]["reduced"].append("head_dim")),
    ("a config file outside paths",
     lambda m: m["configs"][0].update(file="ray_tpu/models/gpt2.py")),
    ("a command outside paths",
     lambda m: m.update(command=["python3", "bench.py"])),
    ("an absolute command",
     lambda m: m.update(command=["python3", "/root/repo/benchmarks/run.py"])),
    ("run_seconds over the limit", lambda m: m.update(run_seconds=52)),
    ("no setup_s",
     lambda m: m["end_to_end"].remove(metric(m, "setup_s"))),
    ("a program-sourced end-to-end metric",
     lambda m: metric(m, "itl_p90_ms").update(source="program_span")),
    ("a per-layer metric without a reader",
     lambda m: metric(m, "engine.queue_ms").update(name="engine.nothing")),
    ("an unknown workload on a metric",
     lambda m: metric(m, "itl_p90_ms").update(workloads=["nowhere"])),
    ("an extra top-level key", lambda m: m.update(notes="x")),
    ("a missing traffic file",
     lambda m: m["workloads"][0].update(traffic="absent")),
    ("a cell left with setup_s alone",
     lambda m: metric(m, "serve_out_tok_s").update(
         workloads=["serve_mistral7b_chat"])),
    ("a roofline share not in %",
     lambda m: metric(m, "flash_fwd_roofline").update(unit="ratio")),
])
def test_validate_refuses(manifest, what, fn):
    assert break_(manifest, fn), what


@pytest.mark.parametrize("key,width", [
    ("hidden_size", True), ("intermediate_size", True), ("head_dim", True),
    ("kv_lora_rank", True), ("moe_intermediate_size", True),
    ("num_experts_per_tok", True), ("state_size", True),
    ("num_hidden_layers", False), ("vocab_size", False),
    ("attn_pdrop", False), ("n_layer", False),
])
def test_what_counts_as_a_width(key, width):
    assert mf.names_a_width(key) is width


def test_every_cell_finds_its_files(manifest):
    for cell in manifest["workloads"]:
        config = mf.config_of(manifest, cell, _paths.ROOT)
        traffic = mf.traffic_of(cell)
        assert config["name"] == cell["config"]
        assert traffic["kind"] in ("train", "serve")
        for name in (config["builder"],):
            assert os.path.isfile(os.path.join(
                _paths.ROOT, "benchmarks", "builders", f"{name}.py"))
        assert os.path.isfile(os.path.join(
            _paths.ROOT, "benchmarks", "reference",
            f"{config['reference']}.py"))


def test_config_files_list_what_they_changed(manifest):
    for entry in manifest["configs"]:
        with open(os.path.join(_paths.ROOT, entry["file"])) as f:
            config = json.load(f)
        assert config["source"] == entry["source"]
        assert sorted(config["changed"]) == sorted(entry["reduced"])
        assert config["assumed"] and config["deployment"]


def test_mistral_widths_are_the_published_ones(manifest):
    cfg = mf.config_of(manifest, mf.cell_of(
        manifest, "serve_mistral7b_chat"), _paths.ROOT)
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["vocab_size"]) == \
        (4096, 14336, 32, 8, 128, 32768)
    assert cfg["rope_theta"] == 1e6 and cfg["rms_norm_eps"] == 1e-5


def test_rehearsal_blocks_lay_over_one_level(manifest):
    cfg = mf.config_of(manifest, mf.cell_of(
        manifest, "serve_mistral7b_chat"), _paths.ROOT)
    tiny = mf.apply_rehearsal(cfg)
    assert tiny["hidden_size"] == 128 and cfg["hidden_size"] == 4096
    assert tiny["engine"]["batch_slots"] == 4
    assert tiny["rope_theta"] == cfg["rope_theta"]


def test_every_reader_returns_nothing_when_there_is_nothing(manifest):
    facts = {"spans": {}, "counters": {}, "client": {}, "trace": None,
             "end_to_end": {}, "device": {"platform": "cpu", "kind": "cpu"}}
    for m in manifest["per_layer"]:
        assert mf.reader_of(m["name"])(facts) is None, m["name"]


def test_readers_read_spans_counters_and_the_trace(manifest):
    facts = {
        "spans": {"fit_called": 10.0, "worker_first_line": 14.5,
                  "serve_run_called": 1.0, "serve_run_returned": 31.0,
                  "compile_s": 6.25},
        "counters": {"tokens_emitted_in_trace": 330,
                     "first_tokens_in_trace": 10, "batch_slots": 16},
        "client": {"queue_ms": 1.5, "overhead_ms": 4.0, "ttft_p50_ms": 590.0,
                   "ttft_p90_ms": 1450.0, "itl_p99_ms": 425.0},
        "end_to_end": {"train_tok_s_chip": 43_354.0},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "config": {"n_layer": 24, "n_embd": 1024, "n_head": 16,
                   "vocab_size": 50304, "train": {"per_chip_batch": 8}},
        "traffic": {"seq": 1024},
        "trace": {"busy_s": 3.0, "window_s": 4.0,
                  "exposed_collective_s": 0.02,
                  "modules": {"jit_step_with_rules": [10, 2.0],
                              "jit_decode_fn": [25, 2.0],
                              "jit_prefill_fn": [5, 0.25]},
                  "ops": {"flash_fwd.1 | a/flash_fwd": [240, 0.24],
                          "x.2 | a/flash_bwd_dq/b": [240, 0.3],
                          "y.3 | a/flash_bwd_dkv/b": [240, 0.36]}}}
    read = lambda name: mf.reader_of(name)(facts)
    assert read("setup.to_worker_s.train") == 4.5
    assert read("setup.deploy_s.serve") == 30.0
    assert read("setup.compile_s") == 6.25
    assert read("engine.queue_ms") == 1.5 and read("serve.overhead_ms") == 4.0
    assert (read("client.ttft_p50_ms"), read("client.ttft_p90_ms"),
            read("client.itl_p99_ms")) == (590.0, 1450.0, 425.0)
    assert read("device.idle_pct.train") == pytest.approx(25.0)
    assert read("engine.decode_step_ms.batch") == pytest.approx(80.0)
    assert read("engine.prefill_step_ms.chat") == pytest.approx(50.0)
    assert read("engine.slot_fill_pct") == pytest.approx(80.0)
    assert read("collective.exposed_pct") == pytest.approx(1.0)
    assert read("kernel.flash_share_pct") == pytest.approx(45.0)
    # 43,354 tok/s x 2.272 GFLOP / 197 TFLOP/s
    assert read("train.mfu_pct") == pytest.approx(50.0, abs=0.01)
    # fwd at [8,16,1024,64]: 2 x 8.59 GFLOP / 197 TFLOP/s = 87.2 us a call
    assert read("flash_fwd_roofline") == pytest.approx(8.72, abs=0.01)
    assert read("flash_bwd_dq_roofline") == pytest.approx(10.46, abs=0.01)
    assert read("flash_bwd_dkv_roofline") == pytest.approx(11.63, abs=0.01)
