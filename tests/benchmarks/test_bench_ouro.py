"""The Ouro configuration and its cell: the file against the public config,
the required-work arithmetic hand-worked, the readers on a synthetic trace,
the engine's two programs compiled for a described v5e chip at the cell's
sizes (one traced stack of layers, the arena in place), the traffic's
shapes across seeds, the builder's limits against a cache that shares its
pages across passes, the cell's labelled CPU rehearsal end to end.
(`benchmarks/ouro_controls.py --rehearsal` is run by hand: eight more
engine builds beside the rehearsal's would make this the heaviest file of
the suite.)"""

import json
import os
import subprocess
import sys

import pytest

import _paths
from benchmarks import manifest as mf
from benchmarks import peaks_ouro as po

CELL = "serve_ouro2p6b_batchgen"
CONFIG = "ouro-2.6b-serve"
# https://huggingface.co/ByteDance/Ouro-2.6B config.json, every key of the
# catalog's `config`
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
NEW_METRICS = ["serve.mfu_pct.ouro", "serve.membw_pct.ouro",
               "paged_attn.share_pct.ouro", "ouro_paged_decode_roofline",
               "loop.passes_per_token", "kv.arena_fill_pct.ouro"]
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


def test_the_manifest_holds_the_ninth_cell_by_name(files):
    """Asserted by NAME, never by position or count of what later PRs
    append: the cell, its configuration, its six metrics side by side, and
    the accepted metrics it reports."""
    manifest, cell, _, _ = files
    assert mf.validate(manifest, _paths.ROOT) == []
    assert mf.check_budget(manifest) is None
    assert cell == {k: cell[k] for k in ("name", "config", "traffic",
                                         "chips", "why")}
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "batchgen_c16", 1)
    for word in ("16 clients on 8 slots", "4 passes", "4.9 GB", "K/V",
                 "one chunk", "thousand-token"):
        assert word in cell["why"], word
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == []
    assert entry["file"] == "benchmarks/configs/ouro-2.6b-serve.json"
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == NEW_METRICS
    at = [i for i, m in enumerate(manifest["per_layer"])
          if m["name"] in mine]
    assert at == list(range(at[0], at[0] + 6))       # side by side
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[n]["moves"] == "serve_out_tok_s" for n in mine)
    assert {n: (by_name[n]["unit"], by_name[n]["better"],
                by_name[n]["source"], by_name[n]["layer"]) for n in mine} == {
        "serve.mfu_pct.ouro": ("%", "higher", "host_clock", "engine step"),
        "serve.membw_pct.ouro": ("%", "higher", "host_clock", "engine step"),
        "paged_attn.share_pct.ouro": ("%", "lower", "device_trace",
                                      "attention kernels"),
        "ouro_paged_decode_roofline": ("%", "higher", "device_trace",
                                       "attention kernels"),
        "loop.passes_per_token": ("passes", "lower", "program_counter",
                                  "model and step"),
        "kv.arena_fill_pct.ouro": ("%", "higher", "program_counter",
                                   "engine scheduler")}
    reported = {m["name"] for kind in ("end_to_end", "per_layer")
                for m in mf.metrics_of(manifest, CELL, kind)}
    # (`engine.prefill_step_ms.batch` is NOT among them, though ISSUE 60
    # asked: `test_bench_kanana2.py` holds that metric's list to two cells
    # by equality, and a model_config PR may not edit that file. PERF.md
    # section 7.)
    assert reported == set(mine) | SHARED_METRICS
    # one cell on four chips, as before
    assert [w["name"] for w in manifest["workloads"] if w["chips"] == 4] \
        == ["train_gpt2m_dp4"]


def test_every_published_key_stands_and_nothing_is_reduced(files):
    _, _, config, _ = files
    assert {k: config.get(k, "absent") for k in PUBLISHED} == PUBLISHED
    assert config["changed"] == {}
    assert config["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/" \
                               "blob/main/config.json"
    # what the published config does not hold is listed with its origin
    assert {"sandwich_norms", "norm_between_passes", "exit_gate",
            "early_exit_threshold", "cache", "context", "weights",
            "param_dtype", "decoding"} <= set(config["assumed"])
    assert (config["builder"], config["reference"]) == ("ouro_serve",
                                                        "ouro_plain")
    # the rehearsal keeps heads of 128 (the kernel's interpreter path is
    # the one rehearsed) and at least two layers and two passes
    tiny = mf.apply_rehearsal(config)
    assert tiny["head_dim"] == 128 and tiny["num_hidden_layers"] >= 2 \
        and tiny["total_ut_steps"] >= 2


def test_the_traffic_is_the_issues_and_its_shapes_do_not_follow_the_seed(
        files):
    from benchmarks import loadgen

    _, cell, config, traffic = files
    engine = config["engine"]
    assert engine == {"batch_slots": 8, "block_size": 16,
                      "max_blocks_per_seq": 36, "num_blocks": 289,
                      "prefill_chunk": 256}
    assert engine["num_blocks"] == engine["batch_slots"] \
        * engine["max_blocks_per_seq"] + 1
    assert traffic["loop"] == "closed" and traffic["stream"] is True
    assert traffic["clients"] == 16 == 2 * engine["batch_slots"]
    # ISSUE 60's bands as it gave them (PERF.md section 2 has the spread
    # over seeds they read)
    assert (traffic["prompt"], traffic["output"]) == (
        {"dist": "uniform", "min": 64, "max": 256},
        {"dist": "uniform", "min": 192, "max": 320})
    assert traffic["shared_prefix"] == 0 and traffic["order"] == "rotated"
    assert traffic["pool"] == 2048 and traffic["lead_s"] == 10
    assert traffic["shape_seed"] == 20261004
    # every prompt is one chunk, every request fits the served context
    assert traffic["prompt"]["max"] <= engine["prefill_chunk"]
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= engine["max_blocks_per_seq"] * engine["block_size"] == 576

    def shapes(seed):
        pool = loadgen.closed_pool(traffic, seed, config["vocab_size"])
        return [(r["prompt_len"], r["max_new_tokens"]) for r in pool], pool

    (a, pool), (b, _) = shapes(3), shapes(3000000019)
    assert len(a) == 2048 and sorted(a) == sorted(b) and a != b
    assert shapes(3)[0] == a
    # ids from the whole vocabulary
    assert max(max(r["ids"]) for r in pool[:200]) > 49_000


def test_the_builder_hands_the_program_the_published_sizes(files):
    import jax.numpy as jnp

    from benchmarks.builders.ouro_serve import (check_requests, kept_pairs,
                                                model_config)

    _, _, config, _ = files
    mc = model_config(config)
    assert (mc.num_hidden_layers, mc.total_ut_steps, mc.hidden_size,
            mc.intermediate_size, mc.vocab_size) == (48, 4, 2048, 5632, 49152)
    assert (mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim) \
        == (16, 16, 128)
    assert (mc.rope_theta, mc.rms_norm_eps, mc.early_exit_threshold) \
        == (1e6, 1e-6, 1)
    assert jnp.dtype(mc.dtype) == jnp.bfloat16
    assert kept_pairs(config) == [(0, 0), (1, 0), (3, 47)]
    reqs = check_requests(config, 7)
    shapes = {w: (r["prompt_len"], r["max_new_tokens"])
              for w, r in reqs.items()}
    assert {w: shapes[w] for w in ("short", "leaver", "mid", "long",
                                   "reuser")} == {
        "short": (40, 24), "leaver": (60, 4), "mid": (150, 24),
        "long": (250, 24), "reuser": (40, 24)}
    # eight in flight (every slot of the decode program live), then one
    assert len(reqs) == 9 and list(reqs)[-1] == "reuser"
    assert all(n <= config["engine"]["prefill_chunk"]
               for n, _ in shapes.values())
    assert check_requests(config, 7) == reqs != check_requests(config, 8)


def test_required_work_hand_worked(files):
    _, _, config, _ = files
    # a layer: q, k, v and o 2048^2 each, SwiGLU 3 x 2048 x 5632, 4 norms
    assert po.layer_matmul_params(config) == 4 * 4_194_304 + 34_603_008 \
        == 51_380_224
    assert po.layer_params(config) == 51_380_224 + 4 * 2048 == 51_388_416
    # the whole model, the issue's count
    assert 48 * po.layer_params(config) == 2_466_643_968
    assert po.model_params(config) == 2_466_643_968 + 2 * 100_663_296 \
        + 4_097 == 2_667_974_657
    assert po.layer_applications(config) == 192
    # a token's cache: 192 sets of K and V over 16 heads of 128, bf16
    assert po.kv_bytes_per_token(config) == 192 * 2 * 16 * 128 * 2 \
        == 1_572_864
    # 19.93 GFLOP a token outside attention: every pass counted
    outside = po.flops_per_token_outside_attention(config)
    assert outside == 2 * (192 * 51_380_224 + 100_663_296) \
        == 19_931_332_608
    assert outside - po.flops_per_token_outside_attention(
        config, head=False) == 2 * 49152 * 2048
    # attention over c cached tokens: 4 x 2048 x c a layer a pass
    assert po.attention_flops_per_token(config, 290) == 4 * 2048 * 290 * 192
    assert po.serve_flops_per_token(config, 290, True) == pytest.approx(
        20.39e9, rel=1e-3)
    # a step reads the layers' weights four times and the head once:
    # 19.9 GB, and the cache of whoever is live
    assert po.step_weight_bytes(config) == 2 * (4 * 2_466_643_968
                                                + 100_663_296)
    assert po.step_weight_bytes(config) == pytest.approx(19.93e9, rel=1e-3)
    assert po.step_bytes(config, 8, 8 * 290) \
        == po.step_weight_bytes(config) + (8 * 290 + 8) * 1_572_864
    # one paged decode call: 8 rows over 2,320 live tokens, K and V of 16
    # heads of 128 in bf16, q in and o out
    call = po.paged_decode_required(config, 8, 2320)
    assert call == {"flops": 4.0 * 2048 * 2320,
                    "bytes": 2.0 * (2 * 2048 * 2320 + 2 * 2048 * 8)}


def test_readers_on_a_synthetic_trace(files):
    _, _, config, traffic = files
    facts = {
        "end_to_end": {"serve_out_tok_s": 200.0},
        "client": {"out_tok_s": 200.0, "prefill_tok_s": 128.0,
                   "requests_s": 0.8, "mean_context": 290.0,
                   "mean_prompt": 160.0,
                   # the traced interval: 100 steps of 8 rows, 2,320
                   # cached tokens a step
                   "traced_decoded": 800, "traced_context_sum": 232_000},
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "config": config, "traffic": traffic,
        "counters": {"batch_slots": 8, "tokens_emitted_in_trace": 810,
                     "first_tokens_in_trace": 10,
                     "rows_per_decode_step": 8.0,
                     "loop": {"passes": 52_000, "tokens": 13_000,
                              "layer_passes": 2_496_000,
                              "decode_steps": 1_600,
                              "decode_blocks": 1_600 * 216},
                     "kv": {"num_blocks": 289, "peak_blocks_in_use": 288}},
        "trace": {"busy_s": 3.9, "window_s": 4.0,
                  "modules": {"jit_decode_fn": [100, 3.6],
                              "jit_prefill_fn": [4, 0.2]},
                  "ops": {"paged_attention.1 | bf16[8,16,16,128] "
                          "custom-call": [19_200, 0.96],
                          "paged_attention.2 | bf16[1,16,256,128] "
                          "custom-call": [768, 0.04],
                          "fusion.3 | x": [1320, 0.5]}}}
    read = lambda name: mf.reader_of(name)(facts)
    assert read("paged_attn.share_pct.ouro") == pytest.approx(100 / 3.8)
    # a decode call: 2 x (2 x 2048 x 2320 + 2 x 2048 x 8) B over 819 GB/s
    # = 23.29 us against 50 us
    assert read("ouro_paged_decode_roofline") == pytest.approx(46.57,
                                                               abs=0.05)
    assert read("engine.decode_step_ms.batch") == pytest.approx(36.0)
    assert read("engine.prefill_step_ms.batch") == pytest.approx(50.0)
    assert read("engine.slot_fill_pct") == pytest.approx(100.0)
    assert read("device.idle_pct.batch") == pytest.approx(2.5)
    # 200 x 20.39 G + 128 x (19.73 G + 80 x 1.57 M) over 197 TFLOP/s
    assert read("serve.mfu_pct.ouro") == pytest.approx(3.36, abs=0.02)
    # 24.9 decode steps a second of 19.93 + 3.66 GB, 0.8 chunks of 19.93 +
    # 0.50 GB, over 819 GB/s
    assert read("serve.membw_pct.ouro") == pytest.approx(73.7, abs=0.2)
    assert read("loop.passes_per_token") == 4.0
    assert read("kv.arena_fill_pct.ouro") == pytest.approx(75.0)
    # another configuration's facts, or a program without the kernel or
    # the counters: these readers stay silent and do not raise
    other = {**facts, "config": {"model_type": "mistral"}}
    bare = {**facts, "counters": {"batch_slots": 8},
            "trace": {**facts["trace"], "ops": {}}}
    for name in NEW_METRICS:
        assert mf.reader_of(name)(other) is None, name
    for name in ("paged_attn.share_pct.ouro", "ouro_paged_decode_roofline",
                 "loop.passes_per_token", "kv.arena_fill_pct.ouro",
                 "serve.membw_pct.ouro"):
        assert mf.reader_of(name)(bare) is None, name
    for name in ("paged_attn.share_pct.ouro", "ouro_paged_decode_roofline"):
        assert mf.reader_of(name)({**facts, "trace": None}) is None
    for name in ("serve.mfu_pct.ouro", "serve.membw_pct.ouro"):
        assert mf.reader_of(name)(
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
    """The engine's two programs over `Ouro.paged_step` at the cell's
    sizes: ONE loop over the passes around one stack of 48 layers (48
    kernel calls a program, not 192), the arena updated in place (the
    whole cache aliased, nothing of an arena's size among the temporaries
    and no copy of one in the program), and arguments + temporaries are
    the memory table's 12.6 GB."""
    import re

    import jax
    import jax.numpy as jnp

    from benchmarks.builders.ouro_serve import model_config
    from ray_tpu.models.ouro import Ouro
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    with attention._CALLS_LOCK:
        before = dict(attention._CALLS)
        attention._CALLS.clear()
    _, _, config, _ = files
    eng = config["engine"]
    model = Ouro(model_config(config))

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

    assert nbytes(params) == 2 * 2_667_974_657
    assert nbytes(cache) == 289 * 16 * 1_572_864 + 16 == 7_272_923_152
    slots, chunk, width = (eng["batch_slots"], eng["prefill_chunk"],
                           eng["max_blocks_per_seq"])

    def decode_fn(params, cache, tokens, bt, pos, wmask):
        logits, cache = model.paged_step(params, tokens[:, None], cache, bt,
                                         pos, wmask, None)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

    def prefill_fn(params, cache, ids, bt, pos, wmask, last_idx, slot):
        logits, cache = model.paged_step(params, ids, cache, bt, pos, wmask,
                                         None, slot, last_idx)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    programs = {
        "decode": (decode_fn, (
            spec((slots,), jnp.int32), spec((slots, width), jnp.int32),
            spec((slots,), jnp.int32), spec((slots, 1), jnp.bool_))),
        "prefill": (prefill_fn, (
            spec((1, chunk), jnp.int32), spec((1, width), jnp.int32),
            spec((1,), jnp.int32), spec((1, chunk), jnp.bool_),
            spec((1,), jnp.int32), spec((1,), jnp.int32)))}
    arena = re.escape(f"bf16[{4 * 289},16,16,128]")
    try:
        for name, (fn, args) in programs.items():
            compiled = jax.jit(fn, donate_argnums=(1,)).lower(
                params, cache, *args).compile()
            hlo = compiled.as_text()
            assert hlo.count("tpu_custom_call") == 48, name
            assert len(re.findall(r"\bwhile\(", hlo)) == 1, name
            assert not re.findall(rf"= {arena}\S* copy\(", hlo), name
            mem = compiled.memory_analysis()
            assert mem.alias_size_in_bytes >= nbytes(cache) - 8, name
            assert mem.temp_size_in_bytes < 0.1e9, (name, mem)
            need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
            assert 0.25 * 16e9 < 12.6e9 < need < 12.7e9, (name, need)
        calls = {(c["pass"], c["path"], tuple(c["shape"])): c["calls"]
                 for c in attention.pallas_status()}
        assert calls == {
            ("paged_decode", "pallas", (8, 1, 16, 128)): 48,
            ("paged_prefill", "pallas", (1, 256, 16, 128)): 48}
    finally:
        with attention._CALLS_LOCK:
            attention._CALLS.clear()
            attention._CALLS.update(before)


def _check_of(engine, model, shared: bool, monkeypatch):
    """The builder's check of a tiny engine's own requests: its problems."""
    from benchmarks.builders import ouro_serve as b
    from ray_tpu.models import ouro

    if shared:
        monkeypatch.setattr(ouro, "_pass_tables", lambda bt, u, n: bt)
    import numpy as np

    rng = np.random.default_rng(11)
    reqs = [engine.add_request([int(t) for t in rng.integers(1, 96, n)], 8)
            for n in (40, 21, 33)]
    engine.run_until_idle()
    cfg = model.config
    model_cfg = {k: getattr(cfg, k) for k in (
        "num_hidden_layers", "total_ut_steps", "num_attention_heads",
        "head_dim", "rms_norm_eps", "rope_theta")}
    reference = b.reference_check(engine, model_cfg, [
        {"who": f"r{i}", "prompt": list(r.prompt),
         "generated": list(r.generated)} for i, r in enumerate(reqs)])
    return reference, b.check_problems(reference)


@pytest.mark.parametrize("shared", [False, True],
                         ids=["a page a pass", "shared_kv"])
def test_the_builders_limits_refuse_a_cache_shared_across_passes(
        shared, monkeypatch):
    """The system as it is passes the builder's five limits; one whose
    passes 2-4 read and write pass 1's pages (`shared_kv` of
    `ouro_controls.py`) fails them, by the pages and by the logits."""
    import jax

    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.models.ouro import Ouro, OuroConfig

    model = Ouro(OuroConfig.tiny())
    params = model.init(jax.random.PRNGKey(3))
    engine = InferenceEngine(
        EngineConfig(batch_slots=3, block_size=16, num_blocks=20,
                     max_blocks_per_seq=6, prefill_chunk=64),
        model=model, params=params)
    reference, problems = _check_of(engine, model, shared, monkeypatch)
    assert all(r["cached_tokens"] >= 16 for r in reference)
    assert all(set(r["kv_err"]) == {"0,0", "1,0", "3,1"} for r in reference)
    if not shared:
        assert problems == []
        assert max(max(r["kv_err"].values()) for r in reference) < 1e-5
        return
    assert any("cached keys or values" in p for p in problems), problems
    # pass 2's pages were never written: they read 1.0 from the reference
    assert min(r["kv_err"]["1,0"] for r in reference) > 0.99
    assert max(r["max_gap"] for r in reference) > 1e-3


# the chip's readings (PERF.md section 6, my chip runs, PR 60): the system's
# largest over its seeds, then each precision control on seed 2654435761
@pytest.mark.parametrize("who, first, second, last, gap, mean, refused_by", [
    ("system", 0.002786, 0.01262, 0.209, 0.581, 0.080, []),
    ("bf16_residual", 0.00278, 0.02163, 0.344, 0.656, 0.129, ["the second"]),
    ("bf16_norms", 0.003159, 0.01471, 0.234, 0.704, 0.166, ["the first"]),
    ("cache_8bit", 0.0269, 0.0947, 1.04, 2.40, 1.18,
     ["a served token", "the served tokens", "the first", "the second",
      "a kept"]),
])
def test_each_limit_refuses_what_it_is_there_for(who, first, second, last,
                                                 gap, mean, refused_by):
    """A stream in bf16 is refused by the second pass's limit ALONE and
    norms in bf16 by the first layer's alone: neither is seen by the
    logits or by the last pair, where the seeded network's own tail is."""
    from benchmarks.builders import ouro_serve as b

    problems = b.check_problems([
        {"who": who, "max_gap": gap, "mean_gap": mean, "tokens": 24,
         "kv_err": {"0,0": first, "1,0": second, "3,47": last}}])
    assert len(problems) == len(refused_by), problems
    assert all(p.startswith(want) for p, want in zip(problems, refused_by))


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
            "compile.cold_s", "loop.passes_per_token",
            "kv.arena_fill_pct.ouro"} <= set(last["metrics_reported"])
    assert "metrics" not in last and last["device"]["platform"] == "cpu"
    run = next(x for x in lines if x.get("builder") == "ouro_serve")
    stats = run["engine_stats"]
    # heads of 128: the kernel's interpreter path, one call a layer (two
    # layers run twice)
    assert stats["paged_attn"] == {"decode": "pallas", "prefill": "pallas"}
    assert sorted((c["pass"], c["path"], c["calls"])
                  for c in stats["pallas"]) == [
        ("paged_decode", "pallas", 2), ("paged_prefill", "pallas", 2)]
    assert stats["loop"]["passes_per_token"] == 2.0
    assert stats["kv_layout"] == {"bytes_per_token": 2 * 2 * 2 * 2 * 128 * 4,
                                  "passes": 2, "layers": 2}
    assert stats["kv"]["bytes"] == 61 * 16 * 8192 + 16
    assert stats["state"]["slots"] == 0
    assert {r["who"] for r in run["reference"]} == {
        "short", "leaver", "mid", "long", "reuser"}
    assert all(r["cached_tokens"] >= 16 for r in run["reference"])
