"""The Falcon-H1 configuration and its cell: the file against the public
config, the required-work arithmetic hand-worked, the readers on a
synthetic trace, the engine's two programs compiled for a described v5e
chip at the cell's sizes, the cell's labelled CPU rehearsal end to end, and
the controls of its check."""

import json
import os
import subprocess
import sys

import pytest

import _paths
from benchmarks import manifest as mf
from benchmarks import peaks_falconh1 as pf

CELL = "serve_falconh1_batchgen"
# https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct config.json, the keys
# that say something about the model's shape
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}


@pytest.fixture(scope="module")
def files():
    manifest = mf.load(_paths.ROOT)
    cell = mf.cell_of(manifest, CELL)
    return (manifest, cell, mf.config_of(manifest, cell, _paths.ROOT),
            mf.traffic_of(cell))


def test_every_published_key_stands_or_is_listed_as_reduced(files):
    manifest, cell, config, _ = files
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    differs = sorted(k for k, v in PUBLISHED.items() if config.get(k) != v)
    assert differs == sorted(entry["reduced"]) == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 6          # the floor is four
    assert entry["source"] == config["source"] \
        == "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/" \
           "main/config.json"
    assert cell["chips"] == 1 and cell["traffic"] == "batchgen"


def test_the_traffic_is_the_issues(files):
    _, _, config, traffic = files
    engine = config["engine"]
    assert engine == {"batch_slots": 64, "block_size": 16,
                      "max_blocks_per_seq": 64, "num_blocks": 4097,
                      "prefill_chunk": 256}
    assert traffic["loop"] == "closed" and traffic["stream"] is True
    assert traffic["clients"] == 2 * engine["batch_slots"]
    assert (traffic["prompt"], traffic["output"]) == (
        {"dist": "uniform", "min": 64, "max": 256},
        {"dist": "uniform", "min": 192, "max": 320})
    assert traffic["shared_prefix"] == 0 and traffic["order"] == "rotated"
    assert traffic["pool"] == 2048 and traffic["lead_s"] == 10
    # every prompt is one chunk, every request fits the context
    assert traffic["prompt"]["max"] <= engine["prefill_chunk"]
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= engine["max_blocks_per_seq"] * engine["block_size"]


def test_the_builder_hands_the_program_the_published_widths(files):
    from benchmarks.builders.falcon_h1_serve import (CHECK, check_requests,
                                                     model_config)
    from ray_tpu.models.falcon_h1 import FalconH1

    _, _, config, _ = files
    mc = model_config(config)
    assert (mc.num_hidden_layers, mc.hidden_size, mc.intermediate_size,
            mc.vocab_size) == (6, 5120, 21504, 261120)
    assert (mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim) \
        == (20, 4, 128)
    assert (mc.mamba_n_heads, mc.mamba_d_head, mc.mamba_d_state,
            mc.mamba_n_groups, mc.in_proj_dim) == (32, 128, 256, 2, 9248)
    import jax.numpy as jnp

    assert (jnp.dtype(mc.dtype), jnp.dtype(mc.state_dtype)) == (
        jnp.bfloat16, jnp.float32)
    # the memory table: state 64 x 6 x 4.19 MB, its tail 12 MB
    slots = config["engine"]["batch_slots"]
    assert FalconH1(mc).slot_state_bytes * slots == 1_610_612_736 + 11_796_480
    reqs = check_requests(config, 7)
    assert {w: (r["prompt_len"], r["max_new_tokens"])
            for w, r in reqs.items()} == {
        "short": (48, 16), "leaver": (60, 4), "long": (640, 16),
        "reuser": (48, 16)}
    assert CHECK["long_prompt"] > 2 * config["engine"]["prefill_chunk"]
    assert check_requests(config, 7) == reqs != check_requests(config, 8)


def test_required_work_hand_worked(files):
    _, _, config, _ = files
    # a layer's products: q 5120 x 2560, k and v 5120 x 512, o 2560 x 5120,
    # in_proj 5120 x 9248, out_proj 4096 x 5120, SwiGLU 3 x 5120 x 21504
    assert pf.layer_matmul_params(config) == (
        13_107_200 + 2 * 2_621_440 + 13_107_200 + 47_349_760 + 20_971_520
        + 330_301_440) == 430_080_000
    state = 32 * 256 * 128
    decoded = pf.serve_flops_per_token(config, 400.0, True)
    # 6 x (2 x 430.08 M + 5 x 1.05 M state + 4 x 2560 x 400) + 2 x 1.337 G
    assert decoded == 6 * (2 * 430_080_000 + 5 * state + 4 * 2560 * 400) \
        + 2 * 261120 * 5120
    assert decoded == pytest.approx(7.891e9, rel=1e-3)
    assert decoded - pf.serve_flops_per_token(config, 400.0, False) \
        == 2 * 261120 * 5120


def test_kernel_requirements_hand_worked(files):
    _, _, config, _ = files
    step = pf.ssd_step_required(config, 64)
    state = 64 * 32 * 256 * 128                    # 67.1 M elements
    assert step["flops"] == 5 * state
    # state in and out, dt*x, decay and y [64, 32, 128], B and C [64, 2, 256]
    assert step["bytes"] == 4 * (2 * state + 3 * 64 * 32 * 128
                                 + 2 * 64 * 2 * 256) == 540_278_784
    chunk = pf.ssd_chunk_fwd_required(config, 1, 256)
    # two chunks: C B^T a group and M X a head (causal halves), C H and
    # B^T X a head
    assert chunk["flops"] == 2 * (2 * 128 * 128 * 256 + 32 * (
        128 * 128 * 128 + 4 * 128 * 256 * 128))
    assert chunk["bytes"] == (8 * 32 * 256 * 128 + 2 * 256 * (4096 + 1024)
                              + 8 * 32 * 256 + 4 * 256 * 4096)


def test_readers_on_a_synthetic_trace(files):
    _, _, config, traffic = files
    facts = {
        "end_to_end": {"serve_out_tok_s": 3000.0},
        "client": {"out_tok_s": 3000.0, "prefill_tok_s": 1900.0},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "config": config, "traffic": traffic,
        "counters": {"batch_slots": 64, "tokens_emitted_in_trace": 12_000,
                     "first_tokens_in_trace": 48},
        "trace": {"busy_s": 3.6, "window_s": 4.0,
                  "modules": {"jit_decode_fn": [190, 3.42],
                              "jit_prefill_fn": [12, 0.18]},
                  "ops": {"ssd_step.1 | f32[64,32,128] custom-call":
                          [1140, 0.9],
                          "ssd_chunk_fwd.2 | f32[1,256,4096] custom-call":
                          [72, 0.0072],
                          "paged_attention.3 | x": [1212, 0.1]}}}
    read = lambda name: mf.reader_of(name)(facts)
    assert read("ssm.share_pct") == pytest.approx(100 * 0.9072 / 3.6)
    # 540.3 MB over 819 GB/s = 659.7 us a call against 789.5 us
    assert read("ssd_step_roofline") == pytest.approx(83.56, abs=0.05)
    # 15.27 MB over 819 GB/s = 18.64 us a call against 100 us
    assert read("ssd_chunk_fwd_roofline") == pytest.approx(18.64, abs=0.05)
    assert read("engine.decode_step_ms.batch") == pytest.approx(18.0)
    assert read("engine.prefill_step_ms.batch") == pytest.approx(15.0)
    assert read("engine.slot_fill_pct") == pytest.approx(
        100 * (12_000 - 48) / (190 * 64))
    assert read("device.idle_pct.batch") == pytest.approx(10.0)
    # 3,000 x 7.89 G (context 160 + 128) + 1,900 x 5.17 G over 197 TFLOP/s
    assert read("serve.mfu_pct.falconh1") == pytest.approx(17.0, abs=0.2)
    # another configuration's facts, or the parent's program (no such
    # kernel in its trace): these readers stay silent and do not raise
    other = {**facts, "config": {"model_type": "mistral"}}
    assert mf.reader_of("ssd_step_roofline")(other) is None
    assert mf.reader_of("serve.mfu_pct.falconh1")(other) is None
    bare = {**facts, "trace": {**facts["trace"], "ops": {}}}
    for name in ("ssm.share_pct", "ssd_step_roofline",
                 "ssd_chunk_fwd_roofline"):
        assert mf.reader_of(name)(bare) is None


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
    """The engine's two programs over `FalconH1.paged_step` at the cell's
    sizes: the kernels are in them, the state is updated in place (no
    second copy among the temporaries), and arguments + temporaries are
    the memory table's 12.9 GB."""
    import jax
    import jax.numpy as jnp

    from benchmarks.builders.falcon_h1_serve import model_config
    from ray_tpu.models.falcon_h1 import FalconH1
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    _, _, config, _ = files
    eng = config["engine"]
    model = FalconH1(model_config(config))

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

    assert nbytes(params) == 2 * 5_254_594_688
    assert {k: nbytes(v) for k, v in cache.items()} == {
        "kv": 805_502_976, "ssm": 1_610_612_736, "conv": 11_796_480}
    slots, chunk = eng["batch_slots"], eng["prefill_chunk"]
    width = eng["max_blocks_per_seq"]

    def decode_fn(params, cache, tokens, bt, pos, wmask):
        logits, cache = model.paged_step(params, tokens[:, None], cache, bt,
                                         pos, wmask, None)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

    def prefill_fn(params, cache, ids, bt, pos, wmask, last_idx, slot):
        logits, cache = model.paged_step(params, ids, cache, bt, pos, wmask,
                                         None, slot, last_idx)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    programs = {
        "ssd_step": (decode_fn, (
            spec((slots,), jnp.int32), spec((slots, width), jnp.int32),
            spec((slots,), jnp.int32), spec((slots, 1), jnp.bool_))),
        "ssd_chunk_fwd": (prefill_fn, (
            spec((1, chunk), jnp.int32), spec((1, width), jnp.int32),
            spec((1,), jnp.int32), spec((1, chunk), jnp.bool_),
            spec((1,), jnp.int32), spec((1,), jnp.int32)))}
    for kernel, (fn, args) in programs.items():
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, *args).compile()
        hlo = compiled.as_text()
        assert kernel in hlo and "paged_attention" in hlo
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == nbytes(cache)
        assert mem.temp_size_in_bytes < 0.3e9, (kernel, mem)
        need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert 0.25 * 16e9 < 12.9e9 < need < 13.1e9, (kernel, need)


def test_the_cells_rehearsal_runs_end_to_end():
    env = {**os.environ, "PYTHONPATH": _paths.ROOT}
    done = subprocess.run(
        [sys.executable, os.path.join(_paths.ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "4",
         "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, cwd=_paths.ROOT, env=env,
        timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is True, lines[-2:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert {"setup.compile_s", "setup.deploy_s.serve", "startup.backend_s",
            "compile.cold_s"} <= set(last["metrics_reported"])
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    run = next(x for x in lines if x.get("builder") == "falcon_h1_serve")
    stats = run["engine_stats"]
    assert {c["pass"] for c in stats["ssd"]} == {"chunk_fwd", "step"}
    assert all(c["path"] == "pallas" for c in stats["ssd"])
    state = stats["state"]
    assert state["slots"] == 8 and state["bytes"] > 0
    assert state["resets"] == state["prefix_adoptions_refused"] \
        >= last["attempted"]
    assert stats["prefix_cache"]["enabled"] is False
    assert {r["who"] for r in run["reference"]} == {
        "short", "leaver", "long", "reuser"}


def test_the_controls_come_out_as_not_correct():
    """`falconh1_controls.py` at the rehearsal's sizes: the system is
    clean and the two planted faults of the state's bookkeeping are
    refused whatever the widths. What a bf16 carried state and a dropped
    `D x` read at the TIMED sizes is the chip's to say (PERF.md section 6):
    at widths of 64 the mixer's branch is too small a part of the logits."""
    done = subprocess.run(
        [sys.executable, os.path.join(_paths.ROOT, "benchmarks",
                                      "falconh1_controls.py"),
         "--seed", "3000000019", "--rehearsal",
         "--only", "system,no_reset,advance_masked"],
        capture_output=True, text=True, cwd=_paths.ROOT,
        env={**os.environ, "PYTHONPATH": _paths.ROOT}, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(y) for y in done.stdout.splitlines()
             if y.startswith("{")]
    by_who = {x["who"]: x for x in lines if "who" in x}
    assert by_who["system"]["problems"] == []
    for fault in ("no_reset", "advance_masked"):
        assert any("recurrent state" in p
                   for p in by_who[fault]["problems"]), fault
    assert lines[-1]["came_out_wrong"] == []
