"""The Brumby configuration and its cell: the file against the public
config, the required-work arithmetic hand-worked, the readers on a
synthetic trace, the engine's two programs compiled for a described v5e
chip at the cell's sizes, the traffic's shapes across seeds, the cell's
labelled CPU rehearsal end to end. (`benchmarks/brumby_controls.py
--rehearsal` is run by hand: two more engine builds beside the rehearsal's
would make this the heaviest file of the suite.)"""

import json
import os
import subprocess
import sys

import pytest

import _paths
from benchmarks import manifest as mf
from benchmarks import peaks_brumby as pb

CELL = "serve_brumby14b_batchgen"
# https://huggingface.co/manifestai/Brumby-14B-Base config.json, the keys
# that say something about the model's shape
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def files():
    manifest = mf.load(_paths.ROOT)
    cell = mf.cell_of(manifest, CELL)
    return (manifest, cell, mf.config_of(manifest, cell, _paths.ROOT),
            mf.traffic_of(cell))


def test_the_manifest_is_clean_and_holds_what_the_issue_names_in_order(
        files):
    """The cell, its configuration and its four metrics are present and in
    the order the issue gave them (later cells are appended after them)."""
    manifest, cell, _, _ = files
    assert mf.validate(manifest, _paths.ROOT) == []
    assert mf.check_budget(manifest) is None
    assert cell in manifest["workloads"] and cell["chips"] == 1
    assert cell["config"] in [c["name"] for c in manifest["configs"]]
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["retention.share_pct", "retention_step_roofline",
                    "retention_chunk_fwd_roofline", "serve.mfu_pct.brumby"]
    at = [i for i, m in enumerate(manifest["per_layer"])
          if m["name"] in mine]
    assert at == list(range(at[0], at[0] + 4))       # side by side
    reported = {m["name"] for kind in ("end_to_end", "per_layer")
                for m in mf.metrics_of(manifest, CELL, kind)}
    assert reported == set(mine) | {
        "serve_out_tok_s", "setup_s", "setup.deploy_s.serve",
        "setup.compile_s", "engine.decode_step_ms.batch",
        "engine.prefill_step_ms.batch", "engine.slot_fill_pct",
        "device.idle_pct.batch", "startup.lease_s", "startup.spawn_s",
        "startup.backend_s", "startup.ready_lag_s", "startup.uncovered_s",
        "compile.trace_s", "compile.lower_s", "compile.load_s",
        "compile.cold_s"}


def test_every_published_key_stands_or_is_listed_as_reduced(files):
    manifest, cell, config, _ = files
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    differs = sorted(k for k, v in PUBLISHED.items() if config.get(k) != v)
    assert differs == sorted(entry["reduced"]) == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8          # the floor is four
    assert entry["source"] == config["source"] \
        == "https://huggingface.co/manifestai/Brumby-14B-Base/blob/" \
           "main/config.json"
    # what the published config does not hold is listed with its origin
    assert {"retention_degree", "gate", "qk_norm", "eps_r", "state_dtype",
            "weights", "form"} <= set(config["assumed"])
    assert (config["retention_degree"], config["eps_r"]) == (2, 1e-6)
    assert (config["builder"], config["reference"]) == ("brumby_serve",
                                                        "brumby_plain")


def test_the_traffic_is_the_issues_and_its_shapes_do_not_follow_the_seed(
        files):
    from benchmarks import loadgen

    _, cell, config, traffic = files
    engine = config["engine"]
    assert engine == {"batch_slots": 16, "block_size": 16,
                      "prefill_chunk": 256}
    assert cell["traffic"] == "batchgen_c32"
    assert traffic["loop"] == "closed" and traffic["stream"] is True
    assert traffic["clients"] == 32 == 2 * engine["batch_slots"]
    assert (traffic["prompt"], traffic["output"]) == (
        {"dist": "uniform", "min": 64, "max": 256},
        {"dist": "uniform", "min": 192, "max": 320})
    assert traffic["shared_prefix"] == 0 and traffic["order"] == "rotated"
    assert traffic["pool"] == 2048 and traffic["lead_s"] == 10
    # every prompt is one chunk, every request fits the context
    assert traffic["prompt"]["max"] <= engine["prefill_chunk"]
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= config["max_position_embeddings"]
    # its own shapes, not `batchgen`'s, and the same multiset whatever the
    # seed (the seed rotates the order and draws the token ids)
    other = mf.traffic_of({"traffic": "batchgen"})
    assert traffic["shape_seed"] != other["shape_seed"]
    assert {k: v for k, v in traffic.items()
            if k not in ("why", "clients", "shape_seed")} \
        == {k: v for k, v in other.items()
            if k not in ("why", "clients", "shape_seed")}

    def shapes(seed):
        pool = loadgen.closed_pool(traffic, seed, config["vocab_size"])
        return [(r["prompt_len"], r["max_new_tokens"]) for r in pool]

    a, b = shapes(3), shapes(3000000019)
    assert len(a) == 2048 and sorted(a) == sorted(b) and a != b
    assert shapes(3) == a


def test_the_builder_hands_the_program_the_published_widths(files):
    from benchmarks.builders.brumby_serve import check_requests, model_config
    from ray_tpu.models.brumby import Brumby

    _, _, config, _ = files
    mc = model_config(config)
    assert (mc.num_hidden_layers, mc.hidden_size, mc.intermediate_size,
            mc.vocab_size) == (8, 5120, 17408, 151936)
    assert (mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim) \
        == (40, 8, 128)
    import jax.numpy as jnp

    assert (jnp.dtype(mc.dtype), jnp.dtype(mc.state_dtype)) == (
        jnp.bfloat16, jnp.float32)
    # the memory table: 16 slots x 8 layers x (8 heads x 8,320 stored rows
    # x 129 float32): 4.40 GB held, of which the 8,256 live rows are the
    # issue's 4.36 GB
    model = Brumby(mc)
    slots = config["engine"]["batch_slots"]
    assert model.slot_state_bytes * slots == 4_396_154_880
    assert 8 * slots * pb.state_bytes(config) == 4_362_338_304
    assert model.pageless_context == 32768
    reqs = check_requests(config, 7)
    assert {w: (r["prompt_len"], r["max_new_tokens"])
            for w, r in reqs.items()} == {
        "short": (48, 16), "leaver": (60, 4), "long": (640, 16),
        "reuser": (48, 16)}
    assert check_requests(config, 7) == reqs != check_requests(config, 8)


def test_required_work_hand_worked(files):
    _, _, config, _ = files
    # a layer: q and o 26,214,400 each, k and v 5,242,880 each, the gate
    # 40,960 + 8, the two head norms 256, SwiGLU 267,386,880, norms 10,240
    assert pb.layer_params(config) == (
        2 * 26_214_400 + 2 * 5_242_880 + 40_968 + 256 + 267_386_880
        + 10_240) == 330_352_904
    assert pb.layer_matmul_params(config) == 330_352_904 - 8 - 256 - 10_240
    # the whole configuration: 8 layers, embedding and head, the last norm
    assert 8 * pb.layer_params(config) + 2 * 151936 * 5120 + 5120 \
        == 4_198_652_992
    # a slot's state a layer: 8 heads x 8,256 x 128 float32 and the key sum
    assert pb.state_elements(config) == 8 * 8256 * 128 == 8_454_144
    assert pb.state_bytes(config) == 33_816_576 + 264_192 == 34_080_768
    assert pb.state_flops_per_element(config) == 1 + 2 + 2 * 5
    decoded = pb.serve_flops_per_token(config, True)
    assert decoded == 8 * (2 * 330_342_400 + 13 * 8_454_144) \
        + 2 * 151936 * 5120
    assert decoded == pytest.approx(7.72e9, rel=1e-3)
    assert decoded - pb.serve_flops_per_token(config, False) \
        == 2 * 151936 * 5120


def test_kernel_requirements_hand_worked(files):
    _, _, config, _ = files
    step = pb.retention_step_required(config, 16)
    assert step["flops"] == 13 * 16 * 8_454_144
    # state and key sum in and out; q and y [16, 40, 128], k and v [16, 8,
    # 128], the gate [16, 8], float32
    assert step["bytes"] == 2 * 16 * 34_080_768 + 4 * (
        2 * 16 * 40 * 128 + 2 * 16 * 8 * 128 + 16 * 8) == 1_091_371_520
    chunk = pb.retention_chunk_fwd_required(config, 1, 256)
    # the update a KV head and the start state's readout a query head,
    # 2 C D d each; Q K^T and A V a query head, the causal half of 2 C^2 d
    assert chunk["flops"] == 2 * 256 * 8256 * 128 * (8 + 40) \
        + 40 * 2 * (2 * 256 * 256 * 128 // 2)
    assert chunk["bytes"] == (2 * 34_080_768 + 2 * 256 * 128 * (40 + 16)
                              + 4 * 256 * 8 + 4 * 256 * 40 * 128)


def test_readers_on_a_synthetic_trace(files):
    _, _, config, traffic = files
    facts = {
        "end_to_end": {"serve_out_tok_s": 650.0},
        "client": {"out_tok_s": 650.0, "prefill_tok_s": 420.0},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "config": config, "traffic": traffic,
        "counters": {"batch_slots": 16, "tokens_emitted_in_trace": 2_600,
                     "first_tokens_in_trace": 10},
        "trace": {"busy_s": 3.8, "window_s": 4.0,
                  "modules": {"jit_decode_fn": [165, 3.63],
                              "jit_prefill_fn": [10, 0.17]},
                  "ops": {"retention_step.1 | f32[16,8,8,128] custom-call":
                          [1320, 2.2],
                          "retention_chunk_fwd.2 | f32[1,256,5120] "
                          "custom-call": [80, 0.08],
                          "fusion.3 | x": [1320, 0.5]}}}
    read = lambda name: mf.reader_of(name)(facts)
    assert read("retention.share_pct") == pytest.approx(100 * 2.28 / 3.8)
    # 1,091.4 MB over 819 GB/s = 1,332.6 us a call against 1,666.7 us
    assert read("retention_step_roofline") == pytest.approx(79.95, abs=0.05)
    # 26.64 GFLOP over 197 TFLOP/s = 135.2 us a call against 1,000 us
    assert read("retention_chunk_fwd_roofline") == pytest.approx(13.52,
                                                                 abs=0.05)
    assert read("engine.decode_step_ms.batch") == pytest.approx(22.0)
    assert read("engine.prefill_step_ms.batch") == pytest.approx(17.0)
    assert read("engine.slot_fill_pct") == pytest.approx(
        100 * (2_600 - 10) / (165 * 16))
    assert read("device.idle_pct.batch") == pytest.approx(5.0)
    # 650 x 7.72 G + 420 x 6.16 G over 197 TFLOP/s
    assert read("serve.mfu_pct.brumby") == pytest.approx(3.86, abs=0.02)
    # another configuration's facts, or the parent's program (no such
    # kernel in its trace): these readers stay silent and do not raise
    other = {**facts, "config": {"model_type": "falcon_h1"}}
    bare = {**facts, "trace": {**facts["trace"], "ops": {}}}
    for name in ("retention.share_pct", "retention_step_roofline",
                 "retention_chunk_fwd_roofline"):
        assert mf.reader_of(name)(other) is None
        assert mf.reader_of(name)(bare) is None
        assert mf.reader_of(name)({**facts, "trace": None}) is None
    assert mf.reader_of("serve.mfu_pct.brumby")(other) is None
    assert mf.reader_of("serve.mfu_pct.brumby")(
        {**facts, "device": {"platform": "cpu", "kind": "cpu"}}) is None


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


def test_decode_and_prefill_compile_for_one_v5e_chip_and_fit(
        one_chip, files, monkeypatch):
    """The engine's two programs over `Brumby.paged_step` at the cell's
    sizes: the kernels are in them, the state is updated in place (the
    whole cache is aliased, no second copy among the temporaries), the
    cache has no arena leaf, and arguments + temporaries are the memory
    table's 12.9 GB."""
    import jax
    import jax.numpy as jnp

    from benchmarks.builders.brumby_serve import model_config
    from ray_tpu.models.brumby import Brumby
    from ray_tpu.ops import power_retention

    monkeypatch.setattr(power_retention, "_platform", lambda: "tpu")
    power_retention.reset_retention_status()
    _, _, config, _ = files
    eng = config["engine"]
    model = Brumby(model_config(config))

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = shaped(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))))
    cache = shaped(jax.eval_shape(lambda: model.paged_cache(
        0, eng["block_size"], None, eng["batch_slots"])))

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    assert nbytes(params) == 2 * 4_198_652_992 + 8 * 2 * 8   # b_g is f32
    assert {k: nbytes(v) for k, v in cache.items()} == {
        "state": 4_362_076_160, "sums": 34_078_720}
    slots, chunk = eng["batch_slots"], eng["prefill_chunk"]

    def decode_fn(params, cache, tokens, bt, pos, wmask):
        logits, cache = model.paged_step(params, tokens[:, None], cache, bt,
                                         pos, wmask, None)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

    def prefill_fn(params, cache, ids, bt, pos, wmask, last_idx, slot):
        logits, cache = model.paged_step(params, ids, cache, bt, pos, wmask,
                                         None, slot, last_idx)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    programs = {
        "retention_step": (decode_fn, (
            spec((slots,), jnp.int32), spec((slots, 0), jnp.int32),
            spec((slots,), jnp.int32), spec((slots, 1), jnp.bool_))),
        "retention_chunk_fwd": (prefill_fn, (
            spec((1, chunk), jnp.int32), spec((1, 0), jnp.int32),
            spec((1,), jnp.int32), spec((1, chunk), jnp.bool_),
            spec((1,), jnp.int32), spec((1,), jnp.int32)))}
    for kernel, (fn, args) in programs.items():
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, *args).compile()
        hlo = compiled.as_text()
        assert f'"{kernel}"' in hlo and "tpu_custom_call" in hlo
        assert "paged_attention" not in hlo
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == nbytes(cache)
        assert mem.temp_size_in_bytes < 0.2e9, (kernel, mem)
        need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert 0.25 * 16e9 < 12.7e9 < need < 13.0e9, (kernel, need)
    assert {(c["pass"], c["path"])
            for c in power_retention.retention_status()} == {
        ("step", "pallas"), ("chunk_fwd", "pallas")}


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
            "compile.cold_s"} <= set(last["metrics_reported"])
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    run = next(x for x in lines if x.get("builder") == "brumby_serve")
    stats = run["engine_stats"]
    assert {c["pass"] for c in stats["retention"]} == {"chunk_fwd", "step"}
    assert all(c["path"] == "pallas" for c in stats["retention"])
    state = stats["state"]
    assert state["slots"] == 4 and state["bytes"] == 4 * 2 * 65 * 128 * 129 * 4
    assert stats["kv"]["bytes"] == 0 == stats["kv"]["num_blocks"]
    assert state["resets"] == state["prefix_adoptions_refused"] \
        >= last["attempted"]
    assert stats["prefix_cache"]["enabled"] is False
    assert {r["who"] for r in run["reference"]} == {
        "short", "leaver", "long", "reuser"}
