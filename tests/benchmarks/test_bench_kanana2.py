"""The Kanana-2 configuration and its cell: the manifest with an eighth
cell, the file against the catalog's config, the traffic's documents and
pool, the router that `--seed` does not move, the required-work arithmetic
hand-worked, the readers on synthetic facts (a window of plain steps, and
one in which chunks ride in decode steps), the cell's labelled CPU
rehearsal end to end.
(`benchmarks/kanana2_controls.py --rehearsal` is run by hand: six more
engine builds beside the rehearsal's would make this the heaviest file of
the suite.)"""

import copy
import json
import os
import subprocess
import sys

import pytest

import _paths
from benchmarks import manifest as mf
from benchmarks import peaks_kanana2 as pk

CELL = "serve_kanana2_docqa_8k"
# https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601
# config.json, the keys that say something about the model's shape
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256}
MINE = ["mla.share_pct", "latent_decode_roofline", "latent_prefill_roofline",
        "moe.expert_share_pct.serve", "serve_moe_gmm_roofline",
        "moe.load_max_over_mean.serve", "prefix.hit_pct",
        "serve.mfu_pct.kanana2", "serve.membw_pct.kanana2",
        "engine.chunk_aboard_pct"]
BATCH_SERVED = {
    "serve_out_tok_s", "setup_s", "setup.deploy_s.serve", "setup.compile_s",
    "engine.decode_step_ms.batch", "engine.prefill_step_ms.batch",
    "engine.slot_fill_pct", "device.idle_pct.batch", "startup.lease_s",
    "startup.spawn_s", "startup.backend_s", "startup.ready_lag_s",
    "startup.uncovered_s", "compile.trace_s", "compile.lower_s",
    "compile.load_s", "compile.cold_s"}


@pytest.fixture(scope="module")
def files():
    manifest = mf.load(_paths.ROOT)
    cell = mf.cell_of(manifest, CELL)
    return (manifest, cell, mf.config_of(manifest, cell, _paths.ROOT),
            mf.traffic_of(cell))


def reported(manifest, cell_name):
    return {m["name"] for kind in ("end_to_end", "per_layer")
            for m in mf.metrics_of(manifest, cell_name, kind)}


def metric_of(manifest, name):
    return next(m for m in manifest["per_layer"] if m["name"] == name)


def test_the_manifest_is_clean_and_gained_what_the_issue_names(files):
    manifest, cell, _, _ = files
    assert mf.validate(manifest, _paths.ROOT) == []
    assert mf.check_budget(manifest, len(manifest["workloads"])) is None
    assert len(manifest["workloads"]) >= 8
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] \
        == ["train_gpt2m_dp4"]
    assert cell["chips"] == 1
    assert cell["config"] in [c["name"] for c in manifest["configs"]]
    assert len(cell["why"]) <= 200
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == MINE
    assert all(m["moves"] == "serve_out_tok_s"
               for m in manifest["per_layer"] if m["name"] in MINE)
    # every chunk of this cell's window rides in a decode step (PR 58):
    # no `jit_prefill_fn` runs there, and the metric is not listed for it
    assert reported(manifest, CELL) == set(MINE) | BATCH_SERVED - {
        "engine.prefill_step_ms.batch"}
    assert metric_of(manifest, "engine.prefill_step_ms.batch")[
        "workloads"] == ["serve_falconh1_batchgen",
                         "serve_brumby14b_batchgen"]
    assert metric_of(manifest, "engine.chunk_aboard_pct") == {
        "name": "engine.chunk_aboard_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine scheduler",
        "moves": "serve_out_tok_s", "workloads": [CELL]}


def test_what_two_outgrown_tests_still_hold(files):
    """What two tests of the benchmark at PR 43 held before PR 59 reworded
    them (`test_bench_brumby.py`, `test_bench_manifest.py`), on this
    cell's side."""
    manifest = files[0]
    assert reported(manifest, "serve_brumby14b_batchgen") == {
        "retention.share_pct", "retention_step_roofline",
        "retention_chunk_fwd_roofline", "serve.mfu_pct.brumby"} | BATCH_SERVED
    # of eight cells two may ask for four chips, a third may not
    for extra, refused in ((1, False), (2, True)):
        m = copy.deepcopy(manifest)
        for w in m["workloads"][:extra]:
            w["chips"] = 4
        assert any("four-chip" in bad for bad in mf.validate(
            m, _paths.ROOT)) is refused


def test_every_published_key_stands_or_is_listed_as_reduced(files):
    manifest, cell, config, _ = files
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    differs = sorted(k for k, v in PUBLISHED.items() if config.get(k) != v)
    assert differs == sorted(entry["reduced"]) == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8   # one dense + 7 >= 4 following
    assert entry["source"] == config["source"] \
        == "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601" \
           "/blob/main/config.json"
    assert {"param_dtype", "cache_dtype", "selection_bias", "rope_layout",
            "decoding", "weights", "form"} <= set(config["assumed"])
    assert (config["builder"], config["reference"]) == (
        "kanana2_serve", "deepseek_v3_plain")
    assert "six pipeline stages" in config["deployment"]
    assert "640" in config["engine_notes"]
    # the router's seed is the configuration's, fixed by ISSUE 59
    assert config["router_seed"] == 20261003
    assert "router_seed" in config["assumed"] \
        and "router_seed" in config["engine_notes"]


def test_the_traffic_is_the_issues(files):
    from benchmarks.builders import kanana2_serve as b

    _, cell, config, traffic = files
    engine = config["engine"]
    assert engine == {"batch_slots": 32, "block_size": 128,
                      "num_blocks": 2560, "max_blocks_per_seq": 72,
                      "prefill_chunk": 256}
    assert cell["traffic"] == "docqa_8k"
    assert traffic["loop"] == "closed" and traffic["stream"] is True
    assert traffic["clients"] == 64 == 2 * engine["batch_slots"]
    assert (traffic["documents"], traffic["document_len"]) == (32, 8192)
    assert traffic["shared_prefix"] == traffic["document_len"]
    assert (traffic["prompt"], traffic["output"]) == (
        {"dist": "uniform", "min": 64, "max": 256},
        {"dist": "uniform", "min": 192, "max": 320})
    assert traffic["pool"] == 2048 and traffic["lead_s"] == 10
    assert traffic["order"] == "rotated"
    for word in ("set-up", "queueing", "evict"):
        assert word in traffic["why"]
    # a document is whole blocks; every request fits the context; the
    # arena holds every document, the live tails and the trash block
    per_doc, odd = divmod(traffic["document_len"], engine["block_size"])
    assert not odd and traffic["prompt"]["max"] <= engine["prefill_chunk"]
    longest = traffic["document_len"] + traffic["prompt"]["max"] \
        + traffic["output"]["max"]
    assert longest <= engine["max_blocks_per_seq"] * engine["block_size"]
    tail = -(-(longest - traffic["document_len"]) // engine["block_size"])
    assert 1 + 32 * per_doc + engine["batch_slots"] * tail \
        <= engine["num_blocks"]
    assert b.filler_count(config, traffic) == 8         # 511 spare blocks
    # >= 3 GB of arena: 2,560 x 128 tokens x 640 lanes x 2 B x 8 layers
    assert engine["num_blocks"] * engine["block_size"] * 640 * 2 * 8 \
        == 3_355_443_200


def test_every_requests_first_ids_are_its_documents(files):
    from benchmarks.builders import kanana2_serve as b

    _, _, config, traffic = files
    vocab = config["vocab_size"]
    few = {**traffic, "pool": 96}

    def made(seed):
        docs = b.documents(few, seed, vocab, extra=2)
        return docs, b.docqa_pool(few, seed, vocab, docs[:32])

    docs, pool = made(3000000019)
    assert len(docs) == 34 and {len(d) for d in docs} == {8192}
    assert len({tuple(d[:64]) for d in docs}) == 34
    assert max(max(d) for d in docs) < vocab and min(min(d) for d in docs) > 0
    for j, req in enumerate(pool):
        assert req["ids"][:8192] == docs[j % 32]
        assert len(req["ids"]) == req["prompt_len"] \
            == 8192 + req["question_len"]
        assert 64 <= req["question_len"] <= 256
        assert 192 <= req["max_new_tokens"] <= 320
    # the seed draws the documents and the questions; the same seed the same
    other_docs, other_pool = made(3)
    assert other_docs[0] != docs[0]
    assert made(3000000019)[1][5]["ids"] == pool[5]["ids"]
    check = b.check_requests(config, 7, docs[0])
    assert {w: (len(r["ids"]), r["max_new_tokens"])
            for w, r in check.items()} == {
        "short": (114, 16), "leaver": (126, 4), "long": (626, 16),
        "adopter": (8192 + 242, 16), "reuser": (114, 16)}
    assert check["adopter"]["ids"][:8192] == docs[0]
    # every prompt ends where all but the last of the rows its decode steps
    # write lie in a whole block, which is donated and read by the check
    block = config["engine"]["block_size"]
    for who, r in check.items():
        cached = len(r["ids"]) + r["max_new_tokens"] - 1
        assert cached % block == 1 and len(r["ids"]) // block \
            == cached // block - 1, who


def _leaves(params):
    import jax

    return {jax.tree_util.keystr(path): leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(params)}


def test_the_seed_moves_every_weight_but_the_routers(files):
    """`--seed` draws the other weights; `router_seed` the expert layers'
    `router` and `router_bias`, at `DeepseekV3.init`'s shapes, dtypes and
    spreads."""
    import numpy as np

    from benchmarks.builders import kanana2_serve as b
    from ray_tpu.models.deepseek_v3 import DeepseekV3

    config = mf.apply_rehearsal(files[2])
    model = DeepseekV3(b.model_config(config))
    plain = _leaves(b.init_params(model, 3000000019))
    one = _leaves(b.seeded_params(model, 3000000019, config["router_seed"]))
    two = _leaves(b.seeded_params(model, 7, config["router_seed"]))
    other = _leaves(b.seeded_params(model, 7, config["router_seed"] + 1))
    routers = sorted(k for k in one if "router" in k)
    assert len(routers) == 2 * (config["num_hidden_layers"]
                                - config["first_k_dense_replace"])
    assert one.keys() == two.keys() == plain.keys()
    for key, leaf in one.items():
        assert (leaf.shape, leaf.dtype, leaf.sharding, leaf.committed) == (
            plain[key].shape, plain[key].dtype, plain[key].sharding,
            plain[key].committed), key
        same = np.array_equal(np.asarray(leaf), np.asarray(two[key]))
        if key in routers:
            assert same, key                 # bit-equal across --seed
            assert not np.array_equal(np.asarray(leaf),
                                      np.asarray(plain[key])), key
            assert not np.array_equal(np.asarray(leaf),
                                      np.asarray(other[key])), key
        elif "norm" not in key:              # norms are one on every seed
            assert not same, key
            assert np.array_equal(np.asarray(leaf),
                                  np.asarray(plain[key])), key
    # no two layers share a router
    first, second = [np.asarray(one[k]) for k in routers
                     if k.endswith("['router']")][:2]
    assert not np.array_equal(first, second)


def test_the_pinned_router_has_inits_spread():
    """At the cell's own shapes: normal of std 0.02 in bfloat16 and of
    std `BIAS_STD` in float32, as `DeepseekV3.init` draws them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.builders import kanana2_serve as b
    from ray_tpu.models.deepseek_v3 import BIAS_STD
    from ray_tpu.models.falcon_h1 import _normal

    layer = {"router": jnp.zeros((2048, 128), jnp.bfloat16),
             "router_bias": jnp.zeros((128,), jnp.float32),
             "wq": jnp.ones((4, 4), jnp.bfloat16)}
    placed = {**layer, "router": jax.device_put(layer["router"],
                                                 jax.devices()[0])}
    params = {"embed": jnp.ones((8, 4)), "layers": [
        {"wq": jnp.ones((4, 4), jnp.bfloat16)}, dict(layer), placed]}
    pinned = b.pin_router(params, 20261003)
    # a leaf keeps its placement, and one left to the default device stays
    # uncommitted (a committed input would commit the engine's arenas and
    # compile its programs a second time)
    assert [lp["router"].committed for lp in pinned["layers"][1:]] == [
        False, True]
    assert not pinned["layers"][2]["router_bias"].committed
    assert pinned["embed"] is params["embed"]
    assert pinned["layers"][0] is params["layers"][0]
    assert pinned["layers"][1]["wq"] is layer["wq"]
    inits = np.asarray(_normal(jax.random.PRNGKey(1), (2048, 128),
                               jnp.bfloat16), np.float32)
    for lp in pinned["layers"][1:]:
        w = np.asarray(lp["router"], np.float32)
        bias = np.asarray(lp["router_bias"])
        assert (lp["router"].dtype, lp["router_bias"].dtype) == (
            jnp.bfloat16, jnp.float32)
        assert (w.shape, bias.shape) == ((2048, 128), (128,))
        assert w.std() == pytest.approx(0.02, rel=0.01)
        assert w.std() == pytest.approx(inits.std(), rel=0.01)
        assert abs(w.mean()) < 2e-4 and abs(np.abs(w).max() - 0.09) < 0.03
        assert bias.std() == pytest.approx(BIAS_STD, rel=0.25)
    assert not np.array_equal(np.asarray(pinned["layers"][1]["router"]),
                              np.asarray(pinned["layers"][2]["router"]))
    again = b.pin_router(params, 20261003)
    assert np.array_equal(np.asarray(again["layers"][2]["router_bias"]),
                          np.asarray(pinned["layers"][2]["router_bias"]))


def test_the_controls_make_their_weights_as_the_builder_does(monkeypatch):
    """`kanana2_controls.py` goes through the builder's `seeded_params`
    with the configuration's `router_seed`: its faults route by the
    router the cell times."""
    from benchmarks import kanana2_controls
    from benchmarks.builders import kanana2_serve as b

    class Reached(Exception):
        pass

    def seeded_params(model, seed, router_seed):
        raise Reached(seed, router_seed)

    for key in ("JAX_PLATFORMS", "RAY_TPU_PALLAS_INTERPRET"):
        monkeypatch.setenv(key, os.environ.get(key, ""))   # main() sets them
    monkeypatch.setattr(b, "seeded_params", seeded_params)
    with pytest.raises(Reached) as reached:
        kanana2_controls.main(["--seed", "2718281829", "--rehearsal"])
    assert reached.value.args == (2718281829, 20261003)


def test_required_work_hand_worked(files):
    _, _, config, _ = files
    assert pk.attention_params(config) == 12_582_912 + 1_179_648 \
        + 4_194_304 + 8_388_608 == 26_345_472
    assert pk.dense_mlp_params(config) == 37_748_736
    assert pk.expert_params(config) == 4_718_592
    assert pk.shared_params(config) == 9_437_184
    assert pk.router_params(config) == 262_144
    assert pk.model_params(config) == 5_069_642_624
    # a (query token, cached token) pair: 32 heads x (576 + 512) x 2
    assert pk.pair_flops(config) == 2 * 32 * 1088 == 69_632
    assert pk.token_row_bytes(config) == 1_152
    decoded = pk.serve_flops_per_token(config, True, 8500.0)
    per_layer = 2 * 26_345_472 + 69_632 * 8500
    assert decoded == 8 * per_layer + 2 * 37_748_736 + 7 * 2 * (
        262_144 + 6 * 4_718_592 + 9_437_184) + 2 * 128256 * 2048
    assert decoded == pytest.approx(6.29e9, rel=2e-3)
    assert decoded - pk.serve_flops_per_token(config, False, 8500.0) \
        == 2 * 128256 * 2048


def test_kernel_requirements_hand_worked(files):
    _, _, config, _ = files
    dec = pk.latent_decode_required(config, 64, 8500.0)
    assert dec["flops"] == 64 * 8500 * 69_632
    # each live page once a slot, q [64, 32, 576] and o [64, 32, 512] once
    assert dec["bytes"] == 64 * 8500 * 1152 + 2 * 64 * 32 * 1088
    pre = pk.latent_prefill_required(config, 160, 8192)
    assert pre["flops"] == (160 * 8192 + 160 * 161 / 2) * 69_632
    assert pre["bytes"] == (8192 + 160) * 1152 + 2 * 160 * 32 * 1088
    gmm = pk.moe_gmm_required(config, 384, 120)
    assert gmm["flops"] == 2 * 384 * 4_718_592
    assert gmm["bytes"] == 2 * 120 * 4_718_592 + 2 * 384 * (
        2 * 2048 + 3 * 768)
    # a decode step of 64 rows at 8,500 tokens with every expert drawing
    # a row: the issue's 14.6 GB
    step = pk.step_bytes(config, 64, 64 * 8500, 128, 64)
    assert step == 2 * (8 * 26_345_472 + 37_748_736 + 7 * (
        9_437_184 + 262_144 + 128 * 4_718_592) + 64 * 2048
        + 128256 * 2048) + 8 * 64 * 8500 * 1152
    assert step == pytest.approx(14.6e9, rel=0.01)


def test_readers_on_synthetic_facts(files):
    _, _, config, traffic = files
    moe = {"layers": 7, "experts": 128,
           "decode": {"steps": 1000, "assignments_per_step": 372.0,
                      "experts_drawn_per_step": 121.0,
                      "load_max_over_mean": 3.1},
           "prefill": {"steps": 250, "assignments_per_step": 960.0,
                       "experts_drawn_per_step": 128.0,
                       "load_max_over_mean": 1.9}}
    facts = {
        "end_to_end": {"serve_out_tok_s": 2000.0},
        "client": {"out_tok_s": 2000.0, "prefill_tok_s": 1300.0},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "config": config, "traffic": traffic,
        "counters": {"batch_slots": 64, "tokens_emitted_in_trace": 8_000,
                     "first_tokens_in_trace": 30, "mean_context": 8480.0,
                     "window_moe": moe,
                     "window_prefix": {"hit_tokens": 2_621_440,
                                       "lookups": 320, "hits": 320,
                                       "requests": 320,
                                       "prompt_tokens": 2_672_640}},
        "trace": {"busy_s": 3.9, "window_s": 4.0,
                  "modules": {"jit_decode_fn": [128, 3.4],
                              "jit_prefill_fn": [30, 0.45]},
                  "ops": {"latent_decode.1 | bf16[64,32,512] custom-call":
                          [1024, 1.1],
                          "latent_prefill.2 | bf16[1,8192,512] custom-call":
                          [240, 0.16],
                          "moe_gmm.3 | bf16[16768,1536] custom-call":
                          [2212, 2.0],
                          "fusion.3 | x": [1320, 0.5]}}}
    read = lambda name: mf.reader_of(name)(facts)
    assert read("mla.share_pct") == pytest.approx(100 * 1.26 / 3.85)
    assert read("moe.expert_share_pct.serve") == pytest.approx(
        100 * 2.0 / 3.85)
    rows = (8000 - 30) / 128
    need = pk.latent_decode_required(config, rows, 8480.0)
    assert read("latent_decode_roofline") == pytest.approx(
        100 * 1024 * max(need["flops"] / 197e12, need["bytes"] / 819e9) / 1.1)
    need = pk.latent_prefill_required(config, 160.0, 8192.0)
    assert read("latent_prefill_roofline") == pytest.approx(
        100 * 240 * need["flops"] / 197e12 / 0.16)
    nbytes = 7 * sum(runs * pk.moe_gmm_required(
        config, moe[k]["assignments_per_step"],
        moe[k]["experts_drawn_per_step"])["bytes"]
        for runs, k in ((128, "decode"), (30, "prefill")))
    assert read("serve_moe_gmm_roofline") == pytest.approx(
        100 * nbytes / 819e9 / 2.0)
    assert read("moe.load_max_over_mean.serve") == 3.1
    assert read("prefix.hit_pct") == pytest.approx(98.08, abs=0.01)
    assert read("serve.mfu_pct.kanana2") == pytest.approx(100 * (
        2000 * pk.serve_flops_per_token(config, True, 8480.0)
        + 1300 * pk.serve_flops_per_token(config, False, 8192.0)) / 197e12)
    rows = 372.0 / 6
    assert read("serve.membw_pct.kanana2") == pytest.approx(100 * (
        2000 / rows * pk.step_bytes(config, rows, rows * 8480.0, 121.0, rows)
        + 1300 / 160 * pk.step_bytes(config, 160.0, 8352.0, 128.0, 1.0))
        / 819e9)
    for name in ("engine.decode_step_ms.batch", "engine.prefill_step_ms.batch",
                 "engine.slot_fill_pct", "device.idle_pct.batch"):
        assert read(name) is not None
    # every share of a roofline or of a peak under 100 on these facts
    for name in MINE:
        if name.endswith("_roofline") or "mfu" in name or "membw" in name:
            assert 0 < read(name) < 100, name
    # another configuration's facts, or the parent's program (no such
    # kernel in its trace, no such counter): silent, and no raise
    other = {**facts, "config": {"model_type": "brumby"}}
    bare = {**facts, "trace": {**facts["trace"], "ops": {}},
            "counters": {"batch_slots": 64}}
    for name in MINE:
        assert mf.reader_of(name)(bare) is None, name
        assert mf.reader_of(name)({**facts, "trace": None,
                                   "client": None, "counters": None}) is None
        if name not in ("moe.load_max_over_mean.serve",):
            assert mf.reader_of(name)(other) is None, name
    assert mf.reader_of("serve.mfu_pct.kanana2")(
        {**facts, "device": {"platform": "cpu", "kind": "cpu"}}) is None


def test_readers_count_a_fused_execution_once(files):
    """A window in which chunks ride in decode steps (PR 58): the model
    books such an execution whole under `prefill`, the engine's ledger
    counts it in `decode` and in `chunks_aboard`, and the trace calls it
    `jit_decode_fn`."""
    _, _, config, traffic = files
    moe = {"layers": 7, "experts": 128,
           # a plain decode step: 31 live rows
           "decode": {"steps": 2600, "assignments_per_step": 186.0,
                      "experts_drawn_per_step": 64.0,
                      "load_max_over_mean": 3.1},
           # the 360 fused executions of 31 + 160 rows beside the 40 chunks
           # of 160 that ran alone: 160 + 0.9 x 31 rows a `prefill` step
           "prefill": {"steps": 400, "assignments_per_step": 6 * 187.9,
                       "experts_drawn_per_step": 90.0,
                       "load_max_over_mean": 1.9}}
    steps = {"n": 3040, "decode": 2960, "prefill": 40, "chunks_aboard": 360,
             "wall_s": 39.0, "wait_work_s": 0.5}
    facts = {
        "client": {"out_tok_s": 2300.0, "prefill_tok_s": 1440.0},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "config": config, "traffic": traffic,
        "counters": {"batch_slots": 32, "tokens_emitted_in_trace": 9_000,
                     "first_tokens_in_trace": 36, "mean_context": 8480.0,
                     "window_moe": moe, "window_steps": steps},
        "trace": {"busy_s": 3.9, "window_s": 4.0,
                  "modules": {"jit_decode_fn": [296, 3.8],
                              "jit_prefill_fn": [4, 0.07]},
                  "ops": {"moe_gmm.3 | bf16[2304,1536] custom-call":
                          [4200, 1.8]}}}
    read = lambda name, f=facts: mf.reader_of(name)(f)
    assert read("engine.chunk_aboard_pct") == pytest.approx(90.0)
    # 296 traced decode executions, 360 / 2960 of them fused: 36 go to the
    # `prefill` kind beside the 4 chunks alone, 260 stay plain
    need = {k: pk.moe_gmm_required(config, moe[k]["assignments_per_step"],
                                   moe[k]["experts_drawn_per_step"])
            for k in ("decode", "prefill")}
    once = 7 * (260 * need["decode"]["bytes"] + 40 * need["prefill"]["bytes"])
    assert read("serve_moe_gmm_roofline") == pytest.approx(
        100 * once / 819e9 / 1.8)
    # as read before PR 59: 296 plain steps and the 4 chunks, the fused
    # steps' extra rows and experts uncounted, so LOWER
    before = 7 * (296 * need["decode"]["bytes"] + 4 * need["prefill"]["bytes"])
    assert before < once
    # the bytes: 9 chunk-holding executions a second, each ONE step over
    # 160 + 27.9 rows, and the decode rows aboard them taken out of the
    # plain steps' count
    chunks_s, aboard = 1440 / 160, 27.9
    per_s = (2300 - chunks_s * aboard) / 31 * pk.step_bytes(
        config, 31, 31 * 8480.0, 64.0, 31) + chunks_s * pk.step_bytes(
        config, 187.9, 8352.0 + aboard * 8480.0, 90.0, 28.9)
    assert read("serve.membw_pct.kanana2") == pytest.approx(
        100 * per_s / 819e9)
    # as read before PR 59: every request a chunk with weights of its own
    # beside decode steps that emit every token, so HIGHER
    charged_twice = 2300 / 31 * pk.step_bytes(
        config, 31, 31 * 8480.0, 64.0, 31) + chunks_s * pk.step_bytes(
        config, 160.0, 8352.0, 90.0, 1.0)
    assert per_s < charged_twice
    for name in ("serve_moe_gmm_roofline", "serve.membw_pct.kanana2"):
        assert 0 < read(name) < 100, name
    # no chunk rode: the chunk share is 0 of what ran, and a program
    # without the fused step (no such key in its ledger) reports nothing
    alone = {**steps, "decode": 2600, "prefill": 400, "chunks_aboard": 0}
    counters = facts["counters"]
    assert read("engine.chunk_aboard_pct", {**facts, "counters": {
        **counters, "window_steps": alone}}) == 0.0
    del alone["chunks_aboard"]
    for window in (alone, {}, {**steps, "prefill": 0, "chunks_aboard": 0}):
        assert read("engine.chunk_aboard_pct", {**facts, "counters": {
            **counters, "window_steps": window}}) is None
    # ... and both repaired readers read such a window as before PR 58
    plain = {**facts, "counters": {**counters, "window_steps": alone,
                                   "window_moe": {**moe, "prefill": {
                                       **moe["prefill"],
                                       "assignments_per_step": 960.0}}}}
    assert read("serve_moe_gmm_roofline", plain) == pytest.approx(
        100 * 7 * (296 * need["decode"]["bytes"] + 4 * pk.moe_gmm_required(
            config, 960.0, 90.0)["bytes"]) / 819e9 / 1.8)
    assert read("serve.membw_pct.kanana2", plain) == pytest.approx(
        100 * charged_twice / 819e9)


def test_the_cells_rehearsal_runs_end_to_end():
    env = {**os.environ, "PYTHONPATH": _paths.ROOT}
    done = subprocess.run(
        [sys.executable, os.path.join(_paths.ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "4",
         "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, cwd=_paths.ROOT, env=env,
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True, lines[-2:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert {"setup.compile_s", "setup.deploy_s.serve", "startup.backend_s",
            "compile.cold_s", "prefix.hit_pct", "engine.chunk_aboard_pct",
            "moe.load_max_over_mean.serve"} <= set(last["metrics_reported"])
    listed = set(last["metrics_reported"]) | set(last["metrics_left_out"])
    assert "engine.prefill_step_ms.batch" not in listed
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    run = next(x for x in lines if x.get("builder") == "kanana2_serve")
    stats = run["engine_stats"]
    assert {c["pass"] for c in stats["latent_attn"]} == {
        "paged_latent_decode", "paged_latent_prefill"}
    assert all(c["path"] == "pallas" for c in stats["latent_attn"])
    assert stats["paged_attn"] == {"decode": "pallas", "prefill": "pallas"}
    assert stats["held_experts"] and all(
        c["held"] == [0, 8] and c["experts"] == 8
        for c in stats["held_experts"])
    assert stats["state"]["slots"] == 0 and stats["kv"]["bytes"] > 0
    assert stats["prefix_cache"]["enabled"] is True
    assert stats["prefix_cache"]["cached_blocks"] >= 4 * 8
    assert run["fillers"] == 8
    # the window's chunks rode in decode steps (all but the few admitted
    # to an idle engine), and the ledger's `decode` counts those
    # executions too
    steps = run["window_steps"]
    assert steps["decode"] >= steps["chunks_aboard"] > 10 * steps["prefill"]
    window = run["window"]
    assert window["prefix"]["hits"] == window["prefix"]["lookups"] > 0
    assert window["prefix"]["lookup_hit_tokens"] \
        >= 128 * window["prefix"]["hits"]
    # (the rehearsal's pool of 64 wraps, so a repeated prompt also adopts
    # its twin's donated question blocks: at least the document's)
    assert window["prefix"]["hit_tokens"] \
        >= 128 * window["prefix"]["requests"] > 0
    for kind in ("decode", "prefill"):
        assert window["moe"][kind]["steps"] > 0
        assert window["moe"][kind]["placed"] == window["moe"][kind]["assigned"]
    assert {r["who"] for r in run["reference"]} == {
        "short", "leaver", "long", "adopter", "reuser"}
    by_who = {r["who"]: r for r in run["reference"]}
    assert by_who["adopter"]["latent_rows"] == 160     # 10 blocks of 16
    assert by_who["long"]["latent_rows"] == 80
    # the routing the two timed programs left in the cache, each read
    assert {k: v["tokens"] for k, v in run["routing"].items()} == {
        "prefill": 12 * 2 + 14 + 76 + 156, "decode": 4 * 4 + 2}
    assert all(v["mismatch"] == 0 and v["gate_err"] < 1e-5
               for v in run["routing"].values())
