"""The Qwen3-Next configuration and its cell: the file against the public
config, the required-work arithmetic hand-worked, the readers on a
synthetic trace, the kernels compiled for a described v5e chip at the
cell's widths, and the cell's labelled CPU rehearsal end to end."""

import json
import os
import subprocess
import sys

import pytest

import _paths
from benchmarks import manifest as mf
from benchmarks import peaks_qwen3next as pq

CELL = "train_qwen3next_8k_ep16share"
# https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct config.json, the
# keys that say something about the model's shape
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


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
    assert differs == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert not any(mf.names_a_width(k) for k in entry["reduced"])
    assert config["published"] == {k: PUBLISHED[k] for k in differs}
    # the router's width and the experts a token is sent to are as published
    assert config["deployment"]["experts_routed"] == 512
    assert config["num_experts_per_tok"] == 10
    # the floors: a whole period, >= 8 experts, >= an eighth of the rows
    assert config["num_hidden_layers"] % config["full_attention_interval"] \
        == 0 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_the_builder_hands_the_program_the_share(files):
    from benchmarks.builders.qwen3_next_train import model_config

    _, _, config, _ = files
    mc = model_config(config)
    assert (mc.num_experts, mc.held_experts) == (512, (0, 32))
    assert (mc.vocab_size, mc.num_hidden_layers, mc.remat) == (18992, 4, True)
    assert [mc.is_attention(i) for i in range(4)] == [False] * 3 + [True]


def test_required_work_hand_worked(files):
    _, _, config, traffic = files
    # 3 x (2048 x 12288 + 2048 x 64 + 4096 x 2048) delta-rule mixers
    # + (2048 x 8192 + 2 x 2048 x 512 + 4096 x 2048) attention mixer
    # + 4 x (2048 x 512 router + 3 x 2048 x 512 shared + 2048 its gate)
    # + 2048 x 18992 head
    assert pq.dense_matmul_params(config) == (
        3 * 33_685_504 + 27_262_976 + 4 * 4_196_352 + 38_895_616) \
        == 184_000_512
    assert pq.expert_params(config) == 3_145_728
    per_token = pq.train_flops_per_token(config, traffic["seq"], 0.625)
    # 6 x (184.0 M + 4 x 0.625 x 3.146 M) + 6 x 4096 x 8192 + 6 x 2 x 128^2
    # x 32 heads x 3 layers = 1.1512 + 0.2013 + 0.0189 GFLOP
    assert per_token == pytest.approx(1.3714e9, rel=1e-4)
    # no assignment held: the experts' term is gone, nothing else moves
    assert per_token - pq.train_flops_per_token(
        config, traffic["seq"], 0.0) == 6 * 4 * 0.625 * 3_145_728


def test_kernel_requirements_hand_worked():
    need = pq.gdn_required(1, 8192, 16, 32, 128)
    chunks = 32 * 128
    fwd = 5 * 64 * 64 * 128 + 6 * 64 * 128 * 128
    assert need["gdn_chunk_fwd"]["flops"] == chunks * fwd
    assert need["gdn_chunk_bwd"]["flops"] == chunks * (
        2 * fwd + 4 * 64 * 64 * 128 + 2 * 64 * 128 * 128)
    key, value, gates = 8192 * 2048 * 2, 8192 * 4096 * 2, 8192 * 32 * 4
    assert need["gdn_chunk_fwd"]["bytes"] == 2 * key + 2 * value + 2 * gates
    moe = pq.moe_gmm_required(1000.0, 4, 32, 2048, 512)
    assert moe["flops"] == 9 * 2 * 1000 * 2048 * 512
    assert moe["bytes"] == 3 * 4 * 32 * 2048 * 512 * 8 + 9 * 1000 * 2 * 2560


def test_readers_on_a_synthetic_trace(files):
    _, _, config, traffic = files
    facts = {
        "end_to_end": {"train_tok_s_chip": 20_000.0},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "config": config, "traffic": traffic,
        "counters": {"moe": {"held_assignments_per_token": 0.625,
                             "load_max_over_mean": 1.5},
                     "moe_traced": {"assigned_per_step": 20480.0}},
        "trace": {"busy_s": 4.0, "window_s": 4.0,
                  "modules": {"jit_step_with_rules": [10, 4.0]},
                  "ops": {"gdn_chunk_fwd.6 | bf16[1,8192,4096] custom-call":
                          [60, 0.6],
                          "gdn_chunk_bwd.3 | bf16[1,8192,4096] custom-call":
                          [30, 0.4],
                          "moe_gmm.1 | x": [80, 0.04],
                          "moe_gmm_dlhs.1 | x": [80, 0.04],
                          "moe_gmm_drhs.1 | x": [80, 0.04],
                          "flash_fwd.2 | x": [20, 0.26]}}}
    read = lambda name: mf.reader_of(name)(facts)
    assert read("gdn.share_pct") == pytest.approx(25.0)
    assert read("moe.expert_share_pct") == pytest.approx(3.0)
    # 20,000 tok/s x 1.3714 GFLOP / 197 TFLOP/s
    assert read("train.mfu_pct.qwen3next") == pytest.approx(13.92, abs=0.01)
    assert read("moe.held_assignments_per_token") == 0.625
    assert read("moe.load_max_over_mean") == 1.5
    # forward: 203.4 MB over 819 GB/s = 248.4 us a call against 10 ms
    assert read("gdn_chunk_fwd_roofline") == pytest.approx(2.484, abs=0.01)
    assert 0 < read("gdn_chunk_bwd_roofline") < 100
    assert 0 < read("moe_gmm_roofline") < 100
    # peaks.flash_required(1, 16, 8192, 256): two causal products of 274.9
    # GFLOP = 2.79 ms a call at the peak, against 13 ms
    assert read("d256_flash_fwd_roofline") == pytest.approx(21.46, abs=0.05)
    assert read("d256_flash_bwd_dq_roofline") is None     # not in the trace
    # another configuration's facts: these readers stay silent
    other = {**facts, "config": {"n_layer": 24}}
    assert mf.reader_of("gdn_chunk_fwd_roofline")(other) is None
    assert mf.reader_of("train.mfu_pct.qwen3next")(other) is None


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


def test_the_new_kernels_compile_for_one_v5e_chip(one_chip, files):
    """The recurrence's two kernels and the three grouped products at the
    cell's widths: what the interpreter cannot refuse (tiling, VMEM)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import gated_delta as gd
    from ray_tpu.ops import grouped_matmul as gm

    _, _, config, traffic = files
    seq, d = traffic["seq"], config["linear_key_head_dim"]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    key, value = (shape((1, seq, h * d), jnp.bfloat16) for h in (hk, hv))
    gate = shape((1, hv, seq // gd.CHUNK, gd.CHUNK), jnp.float32)
    states = shape((1, hv, seq // gd.CHUNK, d, d), jnp.float32)
    forward = jax.jit(lambda *a: gd._gdn_forward(
        *a, chunk=gd.CHUNK, steps=8, save=True)).lower(
        key, key, value, gate, gate).compile()
    assert "gdn_chunk_fwd" in forward.as_text()
    backward = jax.jit(lambda *a: gd._gdn_backward(
        *a, chunk=gd.CHUNK, steps=8)).lower(
        key, key, value, gate, gate, states, value).compile()
    assert "gdn_chunk_bwd" in backward.as_text()

    rows = 7680 + 32 * gm.TILE
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    tiles = shape((rows // gm.TILE,), jnp.int32)
    used = shape((1,), jnp.int32)
    x = shape((rows, hidden), jnp.bfloat16)
    h = shape((rows, 2 * width), jnp.bfloat16)
    w = shape((32, hidden, 2 * width), jnp.bfloat16)
    for name, fn, args in (
            ("moe_gmm", lambda *a: gm._gmm(*a), (x, w, tiles, used)),
            ("moe_gmm_dlhs", lambda *a: gm._gmm(*a, transpose_rhs=True),
             (h, w, tiles, used)),
            ("moe_gmm_drhs", lambda *a: gm._gmm_drhs(*a, groups=32),
             (x, h, tiles, used))):
        assert name in jax.jit(fn).lower(*args).compile().as_text()


def test_the_cells_rehearsal_runs_end_to_end():
    env = {**os.environ, "PYTHONPATH": _paths.ROOT}
    done = subprocess.run(
        [sys.executable, os.path.join(_paths.ROOT, "benchmarks", "run.py"),
         # 4 s, not 2: the verdict asks the loss to fall 0.3 inside the
         # window, ~20 steps, and a loaded host fit 14 and 15 in 2 s
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
    assert {"moe.held_assignments_per_token", "moe.load_max_over_mean",
            "setup.compile_s", "setup.to_worker_s.train"} <= set(
        last["metrics_reported"])
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    run = next(x for x in lines if x.get("builder") == "qwen3_next_train")
    assert all(c["path"] == "pallas" for c in run["gated_delta"])
    assert all(c["path"] == "pallas" for c in run["attention"])
    (experts,) = run["held_experts"]
    assert experts["dropped"] == 0 and experts["held"] == [4, 4]
    assert run["check"]["placed"] == run["check"]["assigned"] > 0


def test_update_gaps_reads_adamws_first_step():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks.builders import qwen3_next_train as b

    train = {"lr": 3e-4, "warmup_steps": 200, "weight_decay": 0.1}
    rate = b.learning_rate(train)
    assert float(rate(0)) == pytest.approx(1.5e-6, rel=1e-4)
    assert float(rate(199)) == float(rate(500)) == pytest.approx(
        3e-4, rel=1e-4)
    rng = np.random.default_rng(0)
    before = {"w": rng.normal(0, 0.02, (64, 32)).astype(np.float32)}
    grads = {"w": rng.normal(0, 1e-3, (64, 32)).astype(np.float32)}
    opt = optax.adamw(rate, weight_decay=0.1)
    updates, _ = opt.update(grads, opt.init(before), before)
    after = jax.device_get(optax.apply_updates(before, updates))
    gaps = b.update_gaps(before, after, grads, float(rate(0)), 0.1)
    assert gaps["w"] < 1e-3                     # f32 rounding of the sum
    assert b.update_gaps(before, before, grads, float(rate(0)), 0.1) == {
        "w": 1.0}
    # held in bf16, an update of 1.5e-6 is under half an ulp of 0.02
    low = {"w": np.asarray(jnp.asarray(before["w"]).astype(
        jnp.bfloat16).astype(jnp.float32))}
    moved = np.asarray(jnp.asarray(low["w"] + after["w"] - before["w"]
                                   ).astype(jnp.bfloat16).astype(jnp.float32))
    assert b.update_gaps(low, {"w": moved}, grads, float(rate(0)), 0.1)[
        "w"] > 0.9


def test_decided_flips_counts_only_tokens_the_reference_decided():
    import jax.numpy as jnp

    from benchmarks.builders.qwen3_next_train import decided_flips

    # one layer, three tokens, top-2 of four: margins log(.3/.2), log(.26 /
    # .25), log(.4/.1) between ranks 2 and 3
    probs = jnp.asarray([[[0.4, 0.3, 0.2, 0.1], [0.27, 0.26, 0.25, 0.22],
                          [0.4, 0.4, 0.1, 0.1]]])
    same = jnp.asarray([[[1, 0], [0, 1], [0, 1]]])
    other = jnp.asarray([[[0, 2], [0, 2], [0, 1]]])
    assert decided_flips(same, probs, 0.1) == ([0], [2])
    assert decided_flips(other, probs, 0.1) == ([1], [2])
    assert decided_flips(other, probs, -1.0) == ([2], [3])


def test_moe_counters_are_the_windows():
    from benchmarks.builders.qwen3_next_train import moe_counters

    # two steps, two layers, two held experts, 10 tokens a step
    moe = {"load": [[[3, 1], [2, 2]], [[4, 0], [1, 1]]],
           "assigned": [[4, 4], [4, 2]], "placed": [[4, 4], [4, 2]]}
    got = moe_counters(moe, 10)
    assert (got["steps"], got["assigned"], got["dropped"]) == (2, 14, 0)
    assert got["assigned_per_step"] == 7.0
    assert got["held_assignments_per_token"] == pytest.approx(14 / 40)
    assert got["load_max"] == 4
    # step 1: 3 / (8 / 4); step 2: 4 / (6 / 4)
    assert got["load_max_over_mean"] == pytest.approx((1.5 + 8 / 3) / 2)
    moe["placed"][1][1] = 1
    assert moe_counters(moe, 10)["dropped"] == 1


def test_the_controls_come_out_as_not_correct():
    """`qwen3next_controls.py` at the rehearsal's sizes: the system is
    clean, and what does not depend on the sizes is refused (held in bf16
    an update is lost whatever the widths; a planted fault is a fault).
    What a bf16 router and a bf16 carried state read at the TIMED sizes is
    the chip's to say (PERF.md section 6)."""
    done = subprocess.run(
        [sys.executable, os.path.join(_paths.ROOT, "benchmarks",
                                      "qwen3next_controls.py"),
         "--seed", "3000000019", "--rehearsal"],
        capture_output=True, text=True, cwd=_paths.ROOT,
        env={**os.environ, "PYTHONPATH": _paths.ROOT}, timeout=600)
    assert done.returncode in (0, 1), done.stderr[-3000:]
    lines = {x.get("who"): x for x in map(json.loads, (
        y for y in done.stdout.splitlines() if y.startswith("{")))}
    assert lines["the system"]["problems"] == []
    assert set(lines["the system"]["readings"]["update_gaps"]) == set(
        lines["the system"]["readings"]["grad_gaps"])
    refused = [who for who, x in lines.items() if who and x["problems"]]
    assert len([w for w in refused if w.startswith("fault:")]) == 4
    assert {"control: the reference's parameters held in bf16",
            "control: a state left as it was",
            "control: the reference's router with bf16 operands"} <= set(
        refused)
