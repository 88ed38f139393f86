"""The dots3 configuration and its cell: the file against the catalog's
config, the required-work arithmetic hand-worked, the readers on synthetic
facts (and silent on another configuration's and on a program without the
counters), the traffic's shapes, the cell's labelled CPU rehearsal end to
end (it SELECTS and RELEASES). (`benchmarks/dots3_controls.py --rehearsal`
is run by hand: fifteen engine builds; `tests/test_dots3.py` plants the
same faults in the model alone. The programs' compile for a described v5e
is in `tests/test_dots3_compile.py`.)"""

import json
import os
import subprocess
import sys

import pytest

import _paths
from benchmarks import manifest as mf
from benchmarks import peaks_dots3 as pd

CELL = "serve_dots3_docqa_32k"
CONFIG = "dots3-note-l5-e32-serve"
NEW_METRICS = [
    "serve.mfu_pct.dots3", "serve.membw_pct.dots3", "dsa.share_pct",
    "dsa_index_roofline", "dsa_select.share_pct", "dsa_sparse_attn_roofline",
    "dsa.selected_pct", "window_attn.share_pct", "window_latent_roofline",
    "kv.window_blocks_released_per_s", "moe.expert_share_pct.dots3",
    "dots3_moe_gmm_roofline", "moe.experts_drawn_per_step.dots3",
    "prefix.hit_pct.dots3"]
SHARED_METRICS = {
    "serve_out_tok_s", "setup_s", "setup.deploy_s.serve", "setup.compile_s",
    "startup.lease_s", "startup.spawn_s", "startup.backend_s",
    "startup.ready_lag_s", "startup.uncovered_s", "compile.trace_s",
    "compile.lower_s", "compile.load_s", "compile.cold_s",
    "engine.decode_step_ms.batch", "engine.slot_fill_pct",
    "device.idle_pct.batch"}


@pytest.fixture(scope="module")
def files():
    manifest = mf.load(_paths.ROOT)
    cell = mf.cell_of(manifest, CELL)
    return manifest, cell, mf.config_of(manifest, cell, _paths.ROOT), \
        mf.traffic_of(cell)


def catalog_config():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "dots3-note-prev")


def test_the_manifest_holds_the_cell_and_only_appends(files):
    manifest, cell, _, _ = files
    assert mf.validate(manifest, _paths.ROOT) == []
    assert mf.check_budget(manifest, len(manifest["workloads"])) is None
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "docqa_32k", 1)
    for word in ("128 clients on 64 slots", "32,768", "one chunk",
                 "384-640", "8x its deployed share"):
        assert word in cell["why"], word
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == "https://huggingface.co/dots-studio/" \
                              "dots3-note-prev/blob/main/config.json"
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == NEW_METRICS
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[n]["moves"] == "serve_out_tok_s" for n in mine)
    assert all(by_name[n]["unit"] == "%" for n in mine
               if n.endswith("_roofline"))
    reported = {m["name"] for kind in ("end_to_end", "per_layer")
                for m in mf.metrics_of(manifest, CELL, kind)}
    # (`engine.prefill_step_ms.batch` is NOT among them: ISSUE 66, as
    # PR 60 and PR 62 found: `test_bench_kanana2.py` holds its list.)
    assert reported == set(mine) | SHARED_METRICS
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] \
        == ["train_gpt2m_dp4"]
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == CONFIG


def test_every_published_key_stands_and_three_are_reduced(files):
    _, _, config, _ = files
    published = catalog_config()["config"]
    want = {**published, "num_hidden_layers": 5, "n_routed_experts": 32,
            "vocab_size": 19008}
    assert {k: config.get(k, "absent") for k in published} == want
    assert config["source"] == catalog_config()["source_url"]
    assert list(config["changed"]) == ["num_hidden_layers",
                                       "n_routed_experts", "vocab_size"]
    for said in ("144,060,160", "90,845,184", "923,938,816", "870,723,840",
                 "4,087,154,176"):
        assert said in config["changed"]["num_hidden_layers"], said
    assert {"mla_rescale", "indexer", "attention_gate", "window_edge",
            "rope_layout", "selection_bias", "router_seed", "param_dtype",
            "decoding", "weights", "positions", "left_out"} \
        <= set(config["assumed"])
    dep = config["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["experts_routed"],
            dep["first_expert_held"], dep["vocab_slices"]) == (8, 256, 0, 8)
    assert "an eighth of its load" in dep["text"]
    assert config["router_seed"] == 20261006
    assert (config["builder"], config["reference"]) == ("dots3_serve",
                                                        "dots3_plain")
    eng = config["engine"]
    assert (eng["batch_slots"], eng["block_size"], eng["max_blocks_per_seq"],
            eng["prefill_chunk"]) == (64, 128, 264, 256)
    # the pools hold what the traffic needs: the documents, 64 live tails,
    # and of the window kind a tail a document and a live sequence's rule
    assert eng["num_blocks"] > 16 * 256 + 64 * 8
    assert eng["window_blocks"] > 16 * 5 + 64 * 7
    # the rehearsal's selection and window are SMALLER than its documents
    tiny = mf.apply_rehearsal(config)
    doc = mf.apply_rehearsal(mf.traffic_of({"traffic": "docqa_32k"}))
    assert tiny["index_topk"] < doc["document_len"] \
        and tiny["sliding_window_size"] < doc["document_len"]
    assert tiny["deployment"]["experts_routed"] == 2 * tiny["n_routed_experts"]


def test_the_model_counts_the_parameters_the_file_says(files):
    """The program's own shapes hold ISSUE 66's count to the parameter."""
    import jax

    from benchmarks.builders.dots3_serve import model_config
    from ray_tpu.models.dots3 import Dots3

    _, _, config, _ = files
    model = Dots3(model_config(config))
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(shapes)
    assert sum(a.size for a in leaves) == 4_087_154_176
    cache = jax.eval_shape(lambda: model.paged_cache(
        config["engine"]["num_blocks"], 128, None, 64,
        kinds={"window": config["engine"]["window_blocks"]}))
    assert [a.shape for a in cache["latent"]] == [
        (4900, 128, 640)] * 2 + [(640, 128, 1152)] * 3
    assert [a.shape for a in cache["index"]] == [(4900, 128, 128)] * 2


def test_the_traffic_is_the_issues(files):
    from benchmarks import loadgen

    _, _, config, traffic = files
    assert (traffic["loop"], traffic["clients"], traffic["documents"],
            traffic["document_len"], traffic["shared_prefix"]) == (
        "closed", 128, 16, 32768, 32768)
    assert (traffic["prompt"], traffic["output"]) == (
        {"dist": "uniform", "min": 64, "max": 256},
        {"dist": "uniform", "min": 384, "max": 640})
    assert (traffic["pool"], traffic["shape_seed"], traffic["order"],
            traffic["lead_s"]) == (2048, 20261006, "rotated", 10.0)
    for word in ("BYPASSED", "set-up", "ADOPTS 32,768"):
        assert word in traffic["why"], word
    shapes = [[(r["prompt_len"], r["max_new_tokens"])
               for r in loadgen.closed_pool(traffic, seed, 19008)]
              for seed in (1, 2)]
    # the shape_seed's pool, rotated by the run's seed
    assert shapes[0] != shapes[1] and sorted(shapes[0]) == sorted(shapes[1])
    longest = 32768 + 256 + 640
    assert longest <= config["engine"]["max_blocks_per_seq"] * 128


def test_required_work_by_hand(files):
    _, _, cfg, _ = files
    assert pd.attention_params(cfg, "full_attention") == 144_060_160 - 12_032
    assert pd.attention_params(cfg, "sliding_attention") \
        == 90_845_184 - 12_288
    assert pd.expert_params(cfg) == 23_592_960
    assert pd.held_share(cfg) == 0.125
    # the weights every step reads: 1.94 GB (ISSUE 66's count, the norms
    # left out)
    fixed = pd.fixed_weight_bytes(cfg)
    assert fixed == 2 * (
        2 * (144_060_160 - 12_032) + 3 * (90_845_184 - 12_288)
        + 212_336_640 + 4 * (5120 * 256 + 23_592_960) + 5120 * 19008)
    assert 1.93e9 < fixed < 1.95e9
    # a decode step of 64 rows at 33,200 positions with 27.8 experts drawn
    step = pd.step_bytes(cfg, 64, 64, 33200.0, 27.8)
    experts = 4 * 27.8 * 23_592_960 * 2
    index = 2 * 64 * 33200 * 128 * 2
    chosen = 2 * 64 * 2048 * 640 * 2
    window = 3 * 64 * 513 * 1152 * 2
    own = 64 * (2 * (640 + 128) + 3 * 1152) * 2
    assert step == pytest.approx(fixed + experts + index + chosen + window
                                 + own)
    assert 8.8e9 < step < 9.1e9               # ISSUE 66: 8.9 GB
    # a chunk's queries never read more rows than are visible
    few = pd.step_bytes(cfg, 1, 256, 1000.0, 32.0)
    assert few < pd.step_bytes(cfg, 1, 256, 33000.0, 32.0)
    need = pd.index_required(cfg, 64, 64, 33200.0)
    assert need["flops"] == 2.0 * 64 * 64 * 128 * 33200
    assert need["bytes"] == 64 * 33200 * 256 + 64 * 64 * 260 \
        + 64 * 33200 * 4
    need = pd.sparse_attn_required(cfg, 64, 2048)
    assert need["bytes"] == 64 * 2048 * 1280 + 64 * 128 * (576 + 512) * 2
    assert need["flops"] == 2.0 * 64 * 128 * 2048 * (1024 + 64)
    need = pd.window_latent_required(cfg, 1, 256, 33000.0)
    assert need["bytes"] == (513 + 255) * 2304 + 256 * 64 * (2048 + 64) * 2
    need = pd.moe_gmm_required(cfg, 64.0, 27.8)
    assert need["flops"] == 2.0 * 64 * 3 * 5120 * 1536
    # a token at 33k positions: 4.77 GFLOP: products 2.13, the two full
    # layers' attention over 2,048 keys 1.14, the three windows 0.42, and
    # the indexer over every visible key 1.09, a good fifth
    flops = pd.serve_flops_per_token(cfg, 33200.0)
    index = 2 * 2.0 * 64 * 128 * 33200
    assert 0.2 < index / flops < 0.25 and 4.7e9 < flops < 4.85e9
    assert flops - pd.serve_flops_per_token(cfg, 2048.0) == pytest.approx(
        index * (1 - 2048 / 33200))


def synthetic_facts(config, traffic, model_type=None):
    """A traced run's facts as the builder makes them: 100 decode
    executions of 18 ms and 10 chunks of 40 ms."""
    cfg = dict(config)
    if model_type:
        cfg["model_type"] = model_type
    dsa = {"full_layers": 2,
           "decode": {"queries": 64000, "keys_visible": 2 * 64000 * 33000,
                      "keys_chosen": 2 * 64000 * 2048},
           "prefill": {"queries": 1600, "keys_visible": 2 * 1600 * 32900,
                       "keys_chosen": 2 * 1600 * 2048}}
    moe = {"layers": 4, "experts": 256,
           "decode": {"steps": 1000, "assignments_per_step": 64.0,
                      "experts_drawn_per_step": 27.8},
           "prefill": {"steps": 10, "assignments_per_step": 160.0,
                       "experts_drawn_per_step": 31.9}}
    return {
        "config": cfg, "traffic": traffic,
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "client": {"out_tok_s": 3000.0, "prefill_tok_s": 900.0,
                   "requests_s": 6.0},
        "counters": {"window_dsa": dsa, "window_moe": moe,
                     "window_steps": {"decode": 1000, "prefill": 10},
                     "window_kv": {"window_blocks_released": 900,
                                   "seconds": 45.0},
                     "window_prefix": {"hit_tokens": 32768 * 10,
                                       "prompt_tokens": 32928 * 10}},
        "trace": {
            "modules": {"jit_decode_fn": [100, 1.8],
                        "jit_prefill_fn": [10, 0.4]},
            "ops": {"dsa_index.1 | f32[64,1,33792] custom-call": [220, 0.25],
                    "moe_gmm.3 | bf16[2304,3072] custom-call": [880, 0.9]},
            "scopes": {"dsa_index": [300, 0.27], "dsa_select": [900, 0.1],
                       "dsa_gather": [220, 0.7], "dsa_attend": [220, 0.08],
                       "window_attn": [330, 0.07]}}}


def test_the_readers_read_what_the_builder_hands_them(files):
    _, _, config, traffic = files
    facts = synthetic_facts(config, traffic)
    got = {name: mf.reader_of(name)(facts) for name in NEW_METRICS}
    assert all(v is not None for v in got.values()), got
    assert got["dsa.share_pct"] == pytest.approx(100 * 1.15 / 2.2)
    assert got["dsa_select.share_pct"] == pytest.approx(100 * 0.1 / 2.2)
    assert got["window_attn.share_pct"] == pytest.approx(100 * 0.07 / 2.2)
    assert got["moe.expert_share_pct.dots3"] == pytest.approx(100 * 0.9 / 2.2)
    assert got["dsa.selected_pct"] == pytest.approx(100 * 2048 / 33000)
    assert got["kv.window_blocks_released_per_s"] == 20.0
    assert got["moe.experts_drawn_per_step.dots3"] == 27.8
    assert got["prefix.hit_pct.dots3"] == pytest.approx(100 * 32768 / 32928)
    # the index kernel: both layers' calls of 100 steps of 64 rows and of
    # 10 chunks of 160 queries, by their bytes, over its 0.25 s
    dec = pd.index_required(config, 64, 64, 33000.0)
    pre = pd.index_required(config, 1, 160, 32900.0)
    floor = 2 * (100 * dec["bytes"] + 10 * pre["bytes"]) / 819e9
    assert got["dsa_index_roofline"] == pytest.approx(100 * floor / 0.25)
    for name in NEW_METRICS:
        if name.endswith("_roofline") or "mfu" in name or "membw" in name:
            assert 0 < got[name] < 100, (name, got[name])
    # the whole window's share of the peak: decode and chunk tokens
    flops = 3000 * pd.serve_flops_per_token(config, 33000.0) \
        + 900 * pd.serve_flops_per_token(config, 32900.0)
    assert got["serve.mfu_pct.dots3"] == pytest.approx(100 * flops / 197e12)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_is_silent_elsewhere(files, name):
    """None on another configuration's facts, on the parent's (no scopes,
    no counters), off the chip for a share of its peak, and with no
    trace."""
    _, _, config, traffic = files
    read = mf.reader_of(name)
    assert read(synthetic_facts(config, traffic, "deepseek_v3")) is None
    parent = synthetic_facts(config, traffic)
    parent["counters"] = {}
    parent["trace"] = {**parent["trace"], "scopes": {}, "ops": {}}
    assert read(parent) is None
    bare = synthetic_facts(config, traffic)
    bare["trace"], bare["counters"] = None, {}
    assert read(bare) is None


def test_the_cells_rehearsal_selects_and_releases(tmp_path):
    """`run.py --rehearsal` of the cell on the CPU, end to end: `correct`,
    every held request inside every limit, the window pool releasing and
    every windowed request adopting its document."""
    out = subprocess.run(
        [sys.executable, os.path.join(_paths.ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "3",
         "--trace", "1", "--rehearsal", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=900, cwd=_paths.ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    result = lines[-1]
    assert result["rehearsal"] and result["correct"], result
    assert result["failed"] == 0 and result["attempted"] > 10
    for name in ("dsa.selected_pct", "kv.window_blocks_released_per_s",
                 "moe.experts_drawn_per_step.dots3", "prefix.hit_pct.dots3"):
        assert name in result["metrics_reported"], name
    facts = next(line for line in lines if line.get("builder"))
    held = {r["who"]: r for r in facts["reference"]}
    assert set(held) == {"document", "short", "mid", "long", "leaver",
                         "reuser", "nodoc"}
    for who, r in held.items():
        if who == "document":
            continue
        assert r["logit_rel_max"] < 1e-5 and r["served_exact"] == r["tokens"]
        # the replay at the engine's shapes wrote what the timed steps had
        assert r["replay_rows_moved"] == 0.0 and r["record_is_replays"]
        assert r["replay_agrees"] == r["tokens"]
        for pick in r["selection"].values():
            assert pick["overlap_min"] == 1.0 and not pick["miscounted"]
            # the rehearsal SELECTS: more is visible than is chosen
            assert pick["visible_max"] > pick["chosen_min"] == 32
    kinds = facts["kinds"]
    assert kinds["kv"]["window_blocks_released"] > 10
    assert kinds["dsa"]["decode"]["keys_chosen"] \
        < 0.5 * kinds["dsa"]["decode"]["keys_visible"]
    assert kinds["moe_more"]["decode"]["absent"] > 0   # half are held
    prefix = facts["window"]["prefix"]
    assert prefix["hit_tokens"] == 96 * prefix["requests"]
    stats = facts["engine_stats"]
    assert stats["kv_kinds"]["window"]["in_use"] \
        == stats["kv_kinds"]["window"]["cached"]
    assert {r["path"] for r in stats["sparse_attn"] + stats["latent_attn"]} \
        == {"pallas"}


def _held_reading(**faults):
    """One held request's readings as `reference_check` makes them, inside
    every limit."""
    pick = {"score_err": 0.004, "overlap_min": 0.993, "overlap_mean": 0.997,
            "margin_max": 0.016, "margin_mean": 0.004, "miscounted": 0,
            "chosen_min": 2048, "visible_max": 33000}
    routed = {"tokens": 8, "mismatched": 0, "gates": 64, "gate_sq": 1e-9}
    return {"who": "mid", "tokens": 16, "logit_rel_max": 0.09,
            "logit_rel_mean": 0.05, "served_gap_max": 0.2,
            "replay_rows_moved": 0.0, "replay_agrees": 16,
            "record_is_replays": True, "selection": {0: pick, 1: dict(
                pick, overlap_min=0.92, margin_max=2.0)},
            "latent_err.0": 0.0024, "latent_err.1": 0.09,
            "window_latent_err.4": 0.14, "pad_lanes_max": 0.0,
            "window_pages": 5, "routing": {"prefill": routed,
                                           "decode": routed}, **faults}


@pytest.mark.parametrize("fault, named", [
    ({}, None),
    ({"replay_rows_moved": 0.02}, "REPLAY_ROWS_LIMIT"),
    ({"replay_agrees": 12}, "REPLAY_TOKEN_SHARE"),
    ({"record_is_replays": False}, "routing record"),
    ({"served_gap_max": 0.8}, "SERVED_GAP_LIMIT"),
    ({"window_latent_err.4": 0.25}, "DEEP_ROWS_LIMIT")])
def test_the_check_holds_the_replay_to_the_timed_programs(fault, named):
    """A replay that does not write back what the timed programs left, whose
    argmax is not the served token or whose routing is not the record's
    shows nothing about them: each is a problem, as a served token far
    under the reference's best and a deep layer's rows far from it are."""
    from benchmarks.builders import dots3_serve as b

    problems = b.check_problems([_held_reading(**fault)])
    if named is None:
        assert problems == []
    else:
        assert len(problems) == 1 and named in problems[0], problems
