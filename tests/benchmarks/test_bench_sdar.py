"""The SDAR configuration and its cell: the file against the public config,
the required-work arithmetic hand-worked, the readers on a synthetic trace
(and silent on another configuration's facts and on a program without the
counters), the engine's two programs compiled for a described v5e chip at
the cell's sizes, the traffic's shapes across seeds, the cell's labelled CPU
rehearsal end to end. (`benchmarks/sdar_controls.py --rehearsal` is run by
hand: seven more engine builds beside the rehearsal's would make this the
heaviest file of the suite.)"""

import json
import os
import subprocess
import sys

import pytest

import _paths
from benchmarks import manifest as mf
from benchmarks import peaks_sdar as ps

CELL = "serve_sdar30b_blockgen"
CONFIG = "sdar-30b-a3b-l8-serve"
# https://huggingface.co/JetLM/SDAR-30B-A3B-Chat config.json, every key of
# the catalog's `config`
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
NEW_METRICS = ["serve.mfu_pct.sdar", "serve.membw_pct.sdar",
               "diffusion.passes_per_token", "diffusion.commit_share_pct",
               "paged_attn.share_pct.sdar", "sdar_paged_block_roofline",
               "moe.expert_share_pct.sdar", "sdar_moe_gmm_roofline",
               "moe.experts_drawn_per_step.sdar"]
SHARED_METRICS = {
    "serve_out_tok_s", "setup_s", "setup.deploy_s.serve", "setup.compile_s",
    "engine.decode_step_ms.batch", "engine.slot_fill_pct",
    "device.idle_pct.batch", "startup.lease_s",
    "startup.spawn_s", "startup.backend_s", "startup.ready_lag_s",
    "startup.uncovered_s", "compile.trace_s", "compile.lower_s",
    "compile.load_s", "compile.cold_s"}


@pytest.fixture(scope="module")
def files():
    manifest = mf.load(_paths.ROOT)
    cell = mf.cell_of(manifest, CELL)
    return (manifest, cell, mf.config_of(manifest, cell, _paths.ROOT),
            mf.traffic_of(cell))


def test_the_manifest_holds_the_tenth_cell_by_name(files):
    """Asserted by NAME, never by position or count of what later PRs
    append: the cell, its configuration, its nine metrics side by side, and
    the accepted metrics it reports."""
    manifest, cell, _, _ = files
    assert mf.validate(manifest, _paths.ROOT) == []
    assert mf.check_budget(manifest, len(manifest["workloads"])) is None
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "blockgen_c64", 1)
    for word in ("64 clients on 32 slots", "one chunk", "fixed 256",
                 "block step", "128 experts"):
        assert word in cell["why"], word
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == "https://huggingface.co/JetLM/" \
                              "SDAR-30B-A3B-Chat/blob/main/config.json"
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == NEW_METRICS
    at = [i for i, m in enumerate(manifest["per_layer"])
          if m["name"] in mine]
    assert at == list(range(at[0], at[0] + len(mine)))     # side by side
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[n]["moves"] == "serve_out_tok_s" for n in mine)
    assert {n: (by_name[n]["unit"], by_name[n]["source"],
                by_name[n]["layer"]) for n in mine} == {
        "serve.mfu_pct.sdar": ("%", "host_clock", "engine step"),
        "serve.membw_pct.sdar": ("%", "host_clock", "engine step"),
        "diffusion.passes_per_token": ("passes", "program_counter",
                                       "engine scheduler"),
        "diffusion.commit_share_pct": ("%", "program_counter",
                                       "engine scheduler"),
        "paged_attn.share_pct.sdar": ("%", "device_trace",
                                      "attention kernels"),
        "sdar_paged_block_roofline": ("%", "device_trace",
                                      "attention kernels"),
        "moe.expert_share_pct.sdar": ("%", "device_trace", "expert layer"),
        "sdar_moe_gmm_roofline": ("%", "device_trace", "expert layer"),
        "moe.experts_drawn_per_step.sdar": ("experts", "program_counter",
                                            "expert layer")}
    reported = {m["name"] for kind in ("end_to_end", "per_layer")
                for m in mf.metrics_of(manifest, CELL, kind)}
    # (`engine.prefill_step_ms.batch` is NOT among them, though ISSUE 62
    # asked, as ISSUE 60 did: `test_bench_kanana2.py` holds that metric's
    # list to two cells by equality, and a model_config PR may not edit
    # that file. PERF.md section 7.)
    assert reported == set(mine) | SHARED_METRICS
    # one cell on four chips, as before
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] \
        == ["train_gpt2m_dp4"]


def test_every_published_key_stands_and_only_depth_is_reduced(files):
    _, _, config, _ = files
    want = {**PUBLISHED, "num_hidden_layers": 8}
    assert {k: config.get(k, "absent") for k in PUBLISHED} == want
    assert list(config["changed"]) == ["num_hidden_layers"]
    for said in ("5,607,297,024", "623,120,640", "622,329,856",
                 "does not apply"):
        assert said in config["changed"]["num_hidden_layers"], said
    # what the published config does not hold is listed with its origin
    assert {"block_length", "denoising_steps", "remasking_strategy",
            "confidence_threshold", "mask_token_id", "qk_norm", "no_shift",
            "commit_pass", "param_dtype", "decoding", "weights",
            "router_seed", "context"} <= set(config["assumed"])
    assert (config["block_length"], config["denoising_steps"],
            config["remasking_strategy"], config["confidence_threshold"],
            config["mask_token_id"], config["router_seed"]) == (
        4, 4, "low_confidence_static", 0.9, 151669, 20261005)
    assert "six pipeline stages" in config["deployment"]
    assert (config["builder"], config["reference"]) == ("sdar_serve",
                                                        "sdar_plain")
    # the rehearsal keeps heads of 128 (the kernel's interpreter path is
    # the one rehearsed), two layers, top-2 of 8 experts and blocks of 4
    tiny = mf.apply_rehearsal(config)
    assert (tiny["head_dim"], tiny["num_hidden_layers"], tiny["num_experts"],
            tiny["num_experts_per_tok"], tiny["block_length"]) == (
        128, 2, 8, 2, 4)


def test_the_traffic_is_the_issues_and_its_shapes_do_not_follow_the_seed(
        files):
    from benchmarks import loadgen

    _, _, config, traffic = files
    engine = config["engine"]
    assert engine == {"batch_slots": 32, "block_size": 16,
                      "max_blocks_per_seq": 32, "num_blocks": 1025,
                      "prefill_chunk": 256}
    assert engine["num_blocks"] == engine["batch_slots"] \
        * engine["max_blocks_per_seq"] + 1
    assert traffic["loop"] == "closed" and traffic["stream"] is True
    assert traffic["clients"] == 64 == 2 * engine["batch_slots"]
    assert (traffic["prompt"], traffic["output"]) == (
        {"dist": "uniform", "min": 64, "max": 256},
        {"dist": "fixed", "value": 256, "min": 256, "max": 256})
    assert (traffic["block_length"], traffic["denoising_steps"],
            traffic["remasking_strategy"]) == (4, 4, "low_confidence_static")
    assert traffic["shared_prefix"] == 0 and traffic["order"] == "rotated"
    assert traffic["pool"] == 2048 and traffic["lead_s"] == 10
    assert traffic["shape_seed"] == 20261005
    # every prompt is one chunk, every request fits the served context,
    # and chunks and pages are whole diffusion blocks
    assert traffic["prompt"]["max"] <= engine["prefill_chunk"]
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= engine["max_blocks_per_seq"] * engine["block_size"] == 512
    assert engine["prefill_chunk"] % 4 == engine["block_size"] % 4 == 0

    def shapes(seed):
        pool = loadgen.closed_pool(traffic, seed, config["vocab_size"])
        return [(r["prompt_len"], r["max_new_tokens"]) for r in pool], pool

    (a, pool), (b, _) = shapes(3), shapes(3000000019)
    assert len(a) == 2048 and sorted(a) == sorted(b) and a != b
    assert {k for _, k in a} == {256}
    # ids from the whole vocabulary: a prompt may hold the mask id
    assert max(max(r["ids"]) for r in pool[:200]) > 151_000


def test_the_builder_hands_the_program_the_published_sizes(files):
    import jax.numpy as jnp

    from benchmarks.builders.sdar_serve import (check_requests, kept_layers,
                                                model_config)

    _, _, config, _ = files
    mc = model_config(config)
    assert (mc.num_hidden_layers, mc.hidden_size, mc.moe_intermediate_size,
            mc.vocab_size, mc.num_experts, mc.num_experts_per_tok) == (
        8, 2048, 768, 151936, 128, 8)
    assert (mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim) \
        == (32, 4, 128)
    assert (mc.rope_theta, mc.rms_norm_eps, mc.norm_topk_prob) \
        == (1e6, 1e-6, True)
    assert (mc.block_length, mc.schedule, mc.mask_token_id,
            mc.remasking_strategy) == (4, (1, 1, 1, 1), 151669,
                                       "low_confidence_static")
    assert jnp.dtype(mc.dtype) == jnp.bfloat16
    assert kept_layers(config) == [0, 7]
    reqs = check_requests(config, 7)
    shapes = {w: (r["prompt_len"], r["max_new_tokens"])
              for w, r in reqs.items()}
    assert {w: shapes[w] for w in ("short", "leaver", "mid", "long",
                                   "reuser")} == {
        "short": (64, 16), "leaver": (84, 8), "mid": (157, 16),
        "long": (254, 16), "reuser": (67, 16)}
    # tails of 0, 0, 1, 2 and 3; every slot of the block program live (32
    # in flight), then one
    assert [shapes[w][0] % 4 for w in ("short", "leaver", "mid", "long",
                                       "reuser")] == [0, 0, 1, 2, 3]
    assert len(reqs) == 33 and list(reqs)[-1] == "reuser"
    assert all(n <= config["engine"]["prefill_chunk"]
               for n, _ in shapes.values())
    assert check_requests(config, 7) == reqs != check_requests(config, 8)


def test_required_work_hand_worked(files):
    _, _, config, _ = files
    # a layer: q and o 2048 x 4096 each, k and v 2048 x 512 each
    assert ps.attention_params(config) == 2 * 8_388_608 + 2 * 1_048_576 \
        == 18_874_368
    assert ps.expert_params(config) == 3 * 2048 * 768 == 4_718_592
    assert ps.router_params(config) == 262_144
    assert ps.layer_params(config) == 18_874_368 + 262_144 \
        + 128 * 4_718_592 + 4_352 == 623_120_640
    # the issue's count: 11.21 GB in bf16
    assert ps.model_params(config) == 8 * 623_120_640 + 622_329_856 + 2_048 \
        == 5_607_297_024
    assert ps.kv_bytes_per_token(config) == 8 * 2 * 4 * 128 * 2 == 16_384
    # five row-passes a block of four masks, fewer where a tail is given
    assert [ps.passes_per_block(config, m) for m in (4, 3, 2, 1)] \
        == [5, 4, 3, 2]
    # a position through a block execution: 8 layers of attention, router
    # and 8 experts, the head, attention over what it sees
    per_layer = 2 * (18_874_368 + 262_144 + 8 * 4_718_592)
    assert ps.flops_per_row_pass(config, 300) == 8 * (
        per_layer + 4 * 4096 * 300) + 2 * 151936 * 2048
    # a prompt token: no head, and no expert layer in the last layer
    assert ps.flops_per_row_pass(config, 80, head=False) == 8 * (
        2 * 18_874_368 + 4 * 4096 * 80) + 7 * 2 * (262_144 + 8 * 4_718_592)
    # a block execution of 32 rows that see 300 tokens each reads 10.6 GB
    # of weights and 0.16 GB of pages
    block = ps.execution_bytes(config, 128, 32 * 300, 128, True)
    assert block == 2 * (8 * (18_874_368 + 262_144 + 128 * 4_718_592)
                         + 128 * 2048 + 151936 * 2048) \
        + (32 * 300 + 128) * 16_384
    assert block == pytest.approx(10.75e9, rel=2e-3)
    chunk = ps.execution_bytes(config, 160, 160, 128, False)
    assert chunk == 2 * (8 * 18_874_368 + 7 * (262_144 + 128 * 4_718_592)
                         + 160 * 2048) + 320 * 16_384
    # one paged call of a block step: 32 rows x 4 queries over 302.5
    # visible tokens a row, K and V of 4 heads of 128 in bf16 once a row
    call = ps.paged_required(config, 128, 32 * 302.5, 128 * 302.5)
    assert call == {"flops": 4.0 * 4096 * 128 * 302.5,
                    "bytes": 2.0 * (2 * 512 * 32 * 302.5 + 2 * 4096 * 128)}
    # a layer's grouped products: 1,024 assignments over 128 experts
    gmm = ps.moe_gmm_required(config, 1024, 128)
    assert gmm == {"flops": 2.0 * 1024 * 4_718_592,
                   "bytes": 2.0 * (128 * 4_718_592
                                   + 1024 * (2 * 2048 + 3 * 768))}


def test_readers_on_a_synthetic_trace(files):
    _, _, config, traffic = files
    kind = {"steps": 1000, "assignments_per_step": 1024.0,
            "experts_drawn_per_step": 127.9, "max_load_per_step": 17.0}
    facts = {
        "end_to_end": {"serve_out_tok_s": 1600.0},
        "client": {"out_tok_s": 1600.0, "prefill_tok_s": 1000.0,
                   "requests_s": 6.25, "mean_context": 290.0,
                   "mean_prompt": 160.0,
                   # the traced interval: 6,400 tokens, 290 before each
                   "traced_decoded": 6_400, "traced_context_sum": 1_856_000},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "config": config, "traffic": traffic,
        "counters": {"batch_slots": 32, "tokens_emitted_in_trace": 6_400,
                     "first_tokens_in_trace": 0,
                     "rows_per_decode_step": 32.0,
                     "diffusion": {"blocks_committed": 16_000,
                                   "denoise_passes": 64_000,
                                   "commit_passes": 16_000,
                                   "tokens_committed": 64_000,
                                   "given_tokens": 0, "truncated_tokens": 0},
                     "moe": {"layers": 8, "decode": kind,
                             "prefill": {**kind, "steps": 25,
                                         "assignments_per_step": 1280.0,
                                         "experts_drawn_per_step": 128.0}}},
        "trace": {"busy_s": 3.9, "window_s": 4.0,
                  "modules": {"jit_decode_fn": [250, 3.5],
                              "jit_prefill_fn": [25, 0.3]},
                  "ops": {"paged_attention.1 | f32[32,4,32,128] "
                          "custom-call": [2_000, 0.19],
                          "paged_attention.2 | bf16[1,4,2048,128] "
                          "custom-call": [200, 0.02],
                          "moe_gmm.3 | x": [4_350, 2.66],
                          "fusion.3 | x": [1320, 0.5]}}}
    read = lambda name: mf.reader_of(name)(facts)
    assert read("diffusion.passes_per_token") == 1.25
    assert read("diffusion.commit_share_pct") == 20.0
    assert read("moe.experts_drawn_per_step.sdar") == 127.9
    assert read("paged_attn.share_pct.sdar") == pytest.approx(100 * .21 / 3.8)
    assert read("moe.expert_share_pct.sdar") == pytest.approx(70.0)
    # a block step's call: 2 x (2 x 512 x 32 x 292.5 + 2 x 4096 x 128) B
    # = 21.27 MB over 819 GB/s = 25.97 us against 95 us
    assert read("sdar_paged_block_roofline") == pytest.approx(27.3, abs=0.1)
    # 250 executions x 8 layers x (128 x 127.9/128 experts ...) and 25 x 7
    gmm = 2.0 * (127.9 * 4_718_592 + 1024 * 6400) * 250 * 8 \
        + 2.0 * (128 * 4_718_592 + 1280 * 6400) * 25 * 7
    assert read("sdar_moe_gmm_roofline") == pytest.approx(
        100 * gmm / 819e9 / 2.66, rel=1e-6)
    assert read("engine.decode_step_ms.batch") == pytest.approx(14.0)
    # tokens emitted an execution a slot: 4/5 where every slot is in a block
    assert read("engine.slot_fill_pct") == pytest.approx(80.0)
    assert read("device.idle_pct.batch") == pytest.approx(2.5)
    # 1,600 tokens/s x 5 position-passes of 1.568 GFLOP + 1,000 prompt
    # tokens/s of 0.75 GFLOP, over 197 TFLOP/s
    flops = 1600 * 5 * ps.flops_per_row_pass(config, 292.5) \
        + 1000 * ps.flops_per_row_pass(config, 80.0, False)
    assert read("serve.mfu_pct.sdar") == pytest.approx(
        100 * flops / 197e12) == pytest.approx(6.81, abs=0.05)
    # 62.5 block executions a second of 10.75 GB and 6.25 chunks of 8.8 GB
    nbytes = 62.5 * ps.execution_bytes(config, 128, 32 * 292.5, 127.9,
                                       True) \
        + 6.25 * ps.execution_bytes(config, 160, 160, 128.0, False)
    assert read("serve.membw_pct.sdar") == pytest.approx(
        100 * nbytes / 819e9) == pytest.approx(88.7, abs=0.3)
    # another configuration's facts, or a program without the kernels or
    # the counters (the parent's): these readers stay silent, none raises
    other = {**facts, "config": {"model_type": "deepseek_v3"}}
    bare = {**facts, "counters": {"batch_slots": 32},
            "trace": {**facts["trace"], "ops": {}}}
    for name in NEW_METRICS:
        assert mf.reader_of(name)(other) is None, name
        assert mf.reader_of(name)(bare) is None, name
    for name in ("paged_attn.share_pct.sdar", "sdar_paged_block_roofline",
                 "moe.expert_share_pct.sdar", "sdar_moe_gmm_roofline"):
        assert mf.reader_of(name)({**facts, "trace": None}) is None
    for name in ("serve.mfu_pct.sdar", "serve.membw_pct.sdar"):
        assert mf.reader_of(name)(
            {**facts, "device": {"platform": "cpu", "kind": "cpu"}}) is None
    # the older serve cells' expert and paged readers say nothing here
    for name in ("moe.expert_share_pct.serve", "serve_moe_gmm_roofline",
                 "paged_attn.share_pct.ouro", "ouro_paged_decode_roofline"):
        assert mf.reader_of(name)(facts) is None, name


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_block_and_prefill_compile_for_one_v5e_chip_and_fit(
        one_chip, files, monkeypatch):
    """The engine's two programs over `SDAR.paged_step` at the cell's
    sizes, spelled as `InferenceEngine._build_block_programs` spells them:
    the kernels (not the interpreter) on both new shapes, the cache updated
    in place, arguments + temporaries the memory table's 11.5 GB; and the
    prefill, which reads no logits, drops the head and the last layer's
    expert products (22 kernel calls to the block program's 24)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.builders.sdar_serve import model_config
    from ray_tpu.models.sdar import SDAR
    from ray_tpu.ops import attention, grouped_matmul, held_experts

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    monkeypatch.setattr(grouped_matmul, "_platform", lambda: "tpu")
    with attention._CALLS_LOCK:
        before = dict(attention._CALLS)
        attention._CALLS.clear()
    held_before = held_experts.held_experts_status()
    held_experts.reset_held_experts_status()
    _, _, config, _ = files
    eng = config["engine"]
    model = SDAR(model_config(config))
    block = model.decode_block

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = shaped(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))))
    cache = shaped(jax.eval_shape(lambda: model.paged_cache(
        eng["num_blocks"], eng["block_size"], None, eng["batch_slots"])))

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    assert nbytes(params) == 2 * 5_607_297_024
    # pages, the routing record (16 floats a token) and 66 counters
    assert nbytes(cache) == 1025 * 16 * (16_384 + 64) + 66 * 4 == 269_747_464
    slots, chunk, width, length = (eng["batch_slots"], eng["prefill_chunk"],
                                   eng["max_blocks_per_seq"], block.length)

    def decode_fn(params, arenas, tokens, bt, pos, wmask, fresh, start, n):
        buf = jnp.where(fresh[:, None], start, tokens)
        masked = buf < 0
        logits, arenas = model.paged_step(
            params, jnp.where(masked, block.mask_id, buf), arenas, bt, pos,
            wmask, None)
        x0, chosen = block.select(logits, masked & wmask, n)
        buf = jnp.where(chosen, x0, buf)
        return jnp.where(wmask, buf, tokens), arenas

    def prefill_fn(params, arenas, tokens, ids, bt, pos, wmask, last_idx,
                   slot):
        _, arenas = model.paged_step(params, ids, arenas, bt, pos, wmask,
                                     None, slot, last_idx)
        return tokens, arenas

    i32, flag = jnp.int32, jnp.bool_
    programs = {
        "decode": (decode_fn, 24, (
            spec((slots, length), i32), spec((slots, width), i32),
            spec((slots,), i32), spec((slots, length), flag),
            spec((slots,), flag), spec((slots, length), i32),
            spec((slots,), i32))),
        "prefill": (prefill_fn, 22, (
            spec((slots, length), i32), spec((1, chunk), i32),
            spec((1, width), i32), spec((1,), i32), spec((1, chunk), flag),
            spec((1,), i32), spec((1,), i32)))}
    try:
        for name, (fn, kernels, args) in programs.items():
            compiled = jax.jit(fn, donate_argnums=(1,)).lower(
                params, cache, *args).compile()
            assert compiled.as_text().count("tpu_custom_call") == kernels, \
                name
            mem = compiled.memory_analysis()
            assert mem.alias_size_in_bytes >= nbytes(cache) - 8, name
            assert mem.temp_size_in_bytes < 0.2e9, (name, mem)
            need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
            if name == "decode":
                assert 0.25 * 16e9 < 11.4e9 < need < 11.6e9, need
            else:           # the head and a layer's experts are not read
                assert 9.6e9 < need < 9.9e9, need
        calls = {(tuple(c["shape"]), c["path"], c["tile"][:9]): c["calls"]
                 for c in attention.pallas_status()}
        assert calls == {((32, 4, 32, 128), "pallas", "few rows:"): 8,
                         ((1, 256, 32, 128), "pallas", "many rows"): 8}
        held = {(h["tokens"], h["top_k"], h["tile"], h["path"],
                 h["forward_only"]): h["calls"]
                for h in held_experts.held_experts_status()}
        # ~8 rows an expert in 16-row tiles a block step; 128-row tiles a
        # chunk (serve_tile's rule at 256 x 8 = 16 x 128 assignments)
        assert held == {(128, 8, 16, "pallas", True): 8,
                        (256, 8, 128, "pallas", True): 8}
    finally:
        with attention._CALLS_LOCK:
            attention._CALLS.clear()
            attention._CALLS.update(before)
        with held_experts._CALLS_LOCK:
            held_experts._CALLS.clear()
        del held_before


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
            "compile.cold_s", "diffusion.passes_per_token",
            "diffusion.commit_share_pct",
            "moe.experts_drawn_per_step.sdar"} <= set(
        last["metrics_reported"])
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    run = next(x for x in lines if x.get("builder") == "sdar_serve")
    stats = run["engine_stats"]
    # heads of 128: the kernel's interpreter path on both tiles, one call
    # a layer a program
    assert stats["paged_attn"] == {"decode": "pallas", "prefill": "pallas"}
    assert stats["paged_attn_tile"]["decode"].startswith("few rows")
    assert stats["paged_attn_tile"]["prefill"].startswith("many rows")
    assert sorted((tuple(c["shape"]), c["path"], c["calls"])
                  for c in stats["pallas"]) == [
        ((1, 48, 8, 128), "pallas", 2), ((4, 4, 8, 128), "pallas", 2)]
    book = stats["diffusion"]
    assert book["rule"] == "static" and book["schedule"] == [1, 1, 1, 1]
    assert book["committed_hist"][1] == book["denoise_passes"] > 0
    assert book["commit_passes"] == book["blocks_committed"] > 0
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
    window = run["window"]["diffusion"]
    assert 1.25 <= (window["denoise_passes"] + window["commit_passes"]) \
        / window["tokens_committed"] < 1.4
