"""Compile rehearsal for the paged-attention kernel: `decode_paged` at the
Mistral serve cell's sizes, with the dispatch rule steered to `tpu`,
lowers and compiles for one described v5e chip WITH the kernel in every
layer, without the dense path's context-wide tensors, inside the chip's
memory. The TPU's compiler is installed here and compiles for a chip that
is described, not attached; nothing runs, so this says nothing about
times. (`tests/benchmarks/test_bench_compile_rehearsal.py` compiles the
same programs as the CPU sees them, through the reference.)

The topology is described inside a fixture (`conftest.one_chip`; never at
import: only one process at a time may load the TPU's library) and the test
skips where it cannot be."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 16e9


def test_mistral_decode_and_prefill_compile_with_the_kernel(one_chip,
                                                            monkeypatch):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama
    from ray_tpu.ops import attention

    monkeypatch.syspath_prepend(ROOT)
    from benchmarks.builders.llama_serve import llama_config

    # The dispatch rule asks jax for the platform, which is the CPU here:
    # steer it in the test so that the programs compile WITH the kernel.
    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mistral-7b-v0.3-l16-serve.json")) as f:
        config = json.load(f)
    eng = config["engine"]
    cfg = llama_config(config)
    model = Llama(cfg)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))
    arena = spec((eng["num_blocks"], eng["block_size"], cfg.n_kv_head,
                  cfg.head_dim), cfg.dtype)
    arenas = [(arena, arena) for _ in range(cfg.n_layer)]
    assert (cfg.n_layer, eng["num_blocks"], eng["block_size"]) == (
        16, 4097, 16)

    # The engine's two programs (`InferenceEngine._build_programs`): one
    # paged forward, at [slots, 1] and at [1, chunk].
    def step_fn(params, arenas, toks, bt, pos, wmask):
        logits, arenas = model.apply(params, toks, arenas, bt, pos, wmask,
                                     method=Llama.decode_paged)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), arenas

    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    cache = 2 * cfg.n_layer * arena.size * arena.dtype.itemsize
    shapes = ((eng["batch_slots"], 1), (1, eng["prefill_chunk"]))
    assert shapes == ((16, 1), (1, 512))
    for b, s in shapes:
        compiled = jax.jit(step_fn, donate_argnums=(1,)).lower(
            params, arenas, spec((b, s), jnp.int32),
            spec((b, eng["max_blocks_per_seq"]), jnp.int32),
            spec((b,), jnp.int32), spec((b, s), jnp.bool_)).compile()
        hlo = compiled.as_text()
        kernels = [line for line in hlo.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line
                   and re.search(r"%paged_attention[.\d]* = ", line)]
        assert len(kernels) == cfg.n_layer, (b, s, len(kernels))
        # Nothing as wide as the block table is left: neither the GQA
        # repeat of every row's whole context ([16,4096,8,4,128] at
        # decode) nor its gather ([65536,8,128]).
        ctx = eng["max_blocks_per_seq"] * eng["block_size"]
        groups = cfg.n_head // cfg.n_kv_head
        for gone in (f"[{b},{ctx},{cfg.n_kv_head},{groups},{cfg.head_dim}]",
                     f"[{b * ctx},{cfg.n_kv_head},{cfg.head_dim}]",
                     f"[{b},{ctx},{cfg.n_kv_head},{cfg.head_dim}]"):
            assert gone not in hlo, (b, s, gone)
        # The arena is updated in place and read where it lies: weights,
        # cache and little else.
        mem = compiled.memory_analysis()
        need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert weights + cache < need < weights + cache + 0.5e9 < HBM, (
            b, s, need)
        assert mem.alias_size_in_bytes >= cache, (b, s)


def test_mistral_fused_step_compiles_with_the_kernel_twice_a_layer(
        one_chip, monkeypatch):
    """The engine's third program over `Llama.paged_step_with_chunk` at the
    Mistral cells' sizes (16 decode rows and a chunk of 512 as ONE
    execution): the kernel twice in every layer, once a row group at the
    two programs' own shapes, every product over the 528 rows laid end to
    end and the head over 17, the arenas updated in place, weights + cache
    + little else inside the chip's memory."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama
    from ray_tpu.ops import attention

    monkeypatch.syspath_prepend(ROOT)
    from benchmarks.builders.llama_serve import llama_config

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mistral-7b-v0.3-l16-serve.json")) as f:
        config = json.load(f)
    eng = config["engine"]
    cfg = llama_config(config)
    model = Llama(cfg)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))
    arena = spec((eng["num_blocks"], eng["block_size"], cfg.n_kv_head,
                  cfg.head_dim), cfg.dtype)
    arenas = [(arena, arena) for _ in range(cfg.n_layer)]
    b, c, width = (eng["batch_slots"], eng["prefill_chunk"],
                   eng["max_blocks_per_seq"])

    # `decode_with_chunk_fn` of `InferenceEngine._build_programs`.
    def step_fn(params, arenas, tokens, bt, pos, wmask, ids, chunk_bt,
                chunk_pos, chunk_wmask, last_idx, slot):
        logits, chunk_logits, arenas = model.paged_step_with_chunk(
            params, tokens[:, None], ids, arenas, bt, pos, wmask, chunk_bt,
            chunk_pos, chunk_wmask, slot, last_idx)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        first = jnp.argmax(chunk_logits, axis=-1).astype(jnp.int32)
        return jnp.where(wmask[:, 0], nxt, tokens).at[slot].set(
            first), arenas

    i32 = jnp.int32
    compiled = jax.jit(step_fn, donate_argnums=(1,)).lower(
        params, arenas, spec((b,), i32), spec((b, width), i32),
        spec((b,), i32), spec((b, 1), jnp.bool_), spec((1, c), i32),
        spec((1, width), i32), spec((1,), i32), spec((1, c), jnp.bool_),
        spec((1,), i32), spec((1,), i32)).compile()
    hlo = compiled.as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and re.search(r"%paged_attention[.\d]* = ", line)]
    assert len(kernels) == 2 * cfg.n_layer, len(kernels)
    # The products run once over all rows, the head over b + 1.
    assert f"[{b + c},{cfg.intermediate}]" in hlo
    assert f"[{b + 1},{cfg.vocab_size}]" in hlo
    assert f"[{b + c},{cfg.vocab_size}]" not in hlo
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    cache = 2 * cfg.n_layer * arena.size * arena.dtype.itemsize
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert weights + cache < need < weights + cache + 0.5e9 < HBM, need
    assert mem.alias_size_in_bytes >= cache


def test_kanana2_decode_and_prefill_compile_with_the_latent_kernels(
        one_chip, monkeypatch):
    """The engine's two programs over `DeepseekV3.paged_step` at the
    Kanana-2 cell's sizes: `latent_decode` / `latent_prefill` in every
    layer and `moe_gmm` twice in every expert layer, nothing as wide as a
    row's whole context left, the arenas and the routing record updated in
    place, weights + arena + little else inside the chip's memory (the cell's
    84%)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.deepseek_v3 import DeepseekV3, DeepseekV3Config
    from ray_tpu.ops import attention, grouped_matmul

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    monkeypatch.setattr(grouped_matmul, "_platform", lambda: "tpu")
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kanana-2-30b-a3b-l8-serve.json")) as f:
        config = json.load(f)
    eng = config["engine"]
    cfg = DeepseekV3Config.from_published(config)
    model = DeepseekV3(cfg)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def specs(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    params = specs(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = specs(jax.eval_shape(lambda: model.paged_cache(
        eng["num_blocks"], eng["block_size"], None, eng["batch_slots"])))
    assert cache["latent"][0].shape == (2560, 128, 640)
    assert cache["routing"].shape == (12, 2560 * 128)
    weights, arena = nbytes(params), nbytes(cache)
    # 3.355 GB of latent rows + 15.7 MB of routing record + the counters
    assert 10.13e9 < weights < 10.15e9 and 3.37e9 < arena < 3.38e9
    assert nbytes(cache["latent"]) == 3_355_443_200
    slots, width, chunk = (eng["batch_slots"], eng["max_blocks_per_seq"],
                           eng["prefill_chunk"])

    def decode_fn(params, cache, tokens, bt, pos, wmask):
        logits, cache = model.paged_step(params, tokens[:, None], cache, bt,
                                         pos, wmask)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

    def prefill_fn(params, cache, ids, bt, pos, wmask, last_idx, slot):
        logits, cache = model.paged_step(params, ids, cache, bt, pos, wmask,
                                         None, slot, last_idx)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    programs = {
        "latent_decode": (decode_fn, slots, (
            spec((slots,), jnp.int32), spec((slots, width), jnp.int32),
            spec((slots,), jnp.int32), spec((slots, 1), jnp.bool_))),
        "latent_prefill": (prefill_fn, 1, (
            spec((1, chunk), jnp.int32), spec((1, width), jnp.int32),
            spec((1,), jnp.int32), spec((1, chunk), jnp.bool_),
            spec((1,), jnp.int32), spec((1,), jnp.int32)))}
    ctx = width * eng["block_size"]
    for kernel, (fn, rows, args) in programs.items():
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, *args).compile()
        hlo = compiled.as_text()
        calls = [line for line in hlo.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert sum(bool(re.search(rf"%{kernel}[.\d]* = ", c))
                   for c in calls) == cfg.num_hidden_layers, kernel
        assert sum(bool(re.search(r"%moe_gmm[.\d]* = ", c))
                   for c in calls) == 2 * cfg.n_moe_layers, kernel
        assert f"[{rows},{ctx},{cfg.latent_page_width}]" not in hlo, kernel
        # the forward-only layout: a block's buffer of 16-row tiles for the
        # experts that can draw a row, not a 128-row tile for each of 128
        tokens = rows * args[0].shape[-1] if kernel == "latent_prefill" \
            else rows
        buffer, trained = {32: (2304, 16640), 256: (3584, 17920)}[tokens]
        assert all(f"bf16[{buffer}," in c for c in calls
                   if re.search(r"%moe_gmm[.\d]* = ", c)), kernel
        assert f"[{trained}" not in hlo, kernel
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= nbytes(cache["latent"]) \
            + nbytes(cache["routing"]), kernel
        need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert weights + arena < need < weights + arena + 0.3e9 < HBM, (
            kernel, need)
        assert need > 0.25 * HBM


def test_records_say_pallas_for_the_cells_shapes(monkeypatch):
    """The dispatch rule alone, no compiler: at the cell's shapes on
    platform `tpu` both passes go to the kernel."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention, paged_attention as pa

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    attention.reset_pallas_status()
    arena = jax.ShapeDtypeStruct((4097, 16, 8, 128), jnp.bfloat16)
    for b, s in ((16, 1), (1, 512)):
        q = jax.ShapeDtypeStruct((b, s, 32, 128), jnp.bfloat16)
        assert pa._dispatch(q, arena)
    assert pa.paged_calls() == {("paged_decode", "pallas"): 1,
                                ("paged_prefill", "pallas"): 1}


@pytest.mark.parametrize("shape, dtype, causal", [
    ((8, 16, 1024, 64), "bfloat16", True),     # the train cells' local shape
    ((8, 16, 1024, 64), "bfloat16", False),
    ((1, 12, 8192, 64), "bfloat16", True),     # chip_smoke's long leg
    ((2, 4, 512, 64), "float32", True),        # chip_smoke's on-chip parity
    ((1, 8, 4096, 128), "bfloat16", True),     # a Llama-shaped head
    ((1, 4, 8192, 128), "float32", True),      # the most VMEM the rule asks
    ((2, 4, 1024, 256), "bfloat16", True),
    ((2, 4, 64, 64), "bfloat16", True),        # a block under one lane tile
])
def test_flash_kernels_compile_at_the_rules_schedule(one_chip, monkeypatch,
                                                     shape, dtype, causal):
    """Forward and backward of `flash_attention` at the blocks and tiles
    `pick_block_sizes` gives lower and compile for a described v5e with
    the three kernels in the program. Interpret mode cannot refuse a slice
    off the tiling or a kernel over its VMEM; the chip's compiler can."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)

    def fwd_bwd(q, k, v, do):
        out, vjp = jax.vjp(
            lambda q, k, v: attention.flash_attention(q, k, v, causal),
            q, k, v)
        return out, vjp(do)

    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    attention.reset_pallas_status()
    hlo = jax.jit(fwd_bwd).lower(x, x, x, x).compile().as_text()
    # Found the way chip_smoke.py's check_kernels finds them.
    kernels = [re.search(r"flash_(fwd|bwd_dq|bwd_dkv)", line).group(0)
               for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(kernels) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    assert {(e["pass"], e["path"], e["causal"])
            for e in attention.pallas_status()} == {
        ("fwd", "pallas", causal), ("bwd", "pallas", causal)}


@pytest.mark.parametrize("shape, d, dtype, causal", [
    ((8, 1024, 1024), 64, "bfloat16", True),   # the train cells' local shape
    ((8, 1024, 1024), 64, "bfloat16", False),
    ((1, 8192, 768), 64, "bfloat16", True),    # GPT-2-small, chip_smoke's long leg
    ((2, 512, 256), 64, "float32", True),
    ((1, 4096, 1024), 128, "bfloat16", True),  # one head a column block
    ((2, 1024, 1024), 256, "bfloat16", True),  # a 256-lane column block
])
def test_flash_bse_kernels_compile_on_a_fused_projection(
        one_chip, monkeypatch, shape, d, dtype, causal):
    """`flash_attention_bse` between two projections, forward and backward,
    compiles for a described v5e: the three kernels read the fused
    [batch, seq, 3e] product as it is (three views of one array) and no
    `copy` of an activation stands between the products and the kernels."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    b, s, e = shape
    dt = jnp.dtype(dtype)

    def layer(x, w_in, w_out):
        qkv = x @ w_in
        return attention.flash_attention_bse(qkv, d, causal) @ w_out

    def fwd_bwd(x, w_in, w_out, do):
        out, vjp = jax.vjp(layer, x, w_in, w_out)
        return out, vjp(do)

    def spec(*dims):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    attention.reset_pallas_status()
    hlo = jax.jit(fwd_bwd).lower(spec(b, s, e), spec(e, 3 * e), spec(e, e),
                                 spec(b, s, e)).compile().as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(re.search(r"flash_(fwd|bwd_dq|bwd_dkv)", k).group(0)
                  for k in kernels) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    for k in kernels:
        # q, k and v are ONE operand, the product itself, three times
        first = re.search(r"custom-call\(([^)]*)\)", k).group(1).split(", ")[:3]
        assert len(set(first)) == 1, k
    short = {"bfloat16": "bf16", "float32": "f32"}[dt.name]
    assert not re.search(
        rf"= {short}\[{b},{s},(?:{e}|{3 * e})\][^ ]* copy\(", hlo)
    assert {(x["pass"], x["path"], x["layout"], x["heads_per_block"])
            for x in attention.pallas_status()} == {
        (p, "pallas", "bse", max(1, 128 // d)) for p in ("fwd", "bwd")}


def test_flash_bse_reads_a_kv_head_in_place_for_its_group(one_chip,
                                                          monkeypatch):
    """The Qwen3-Next cell's softmax layer, [1, 8192, 16 x 256] queries on
    2 KV heads, between its projections, forward and backward, at the
    blocks the rule gives (1024 x 1024: the most VMEM any flash call
    asks): ONE execution of each kernel (the per-layer readers multiply
    the required work by the executions they find), k and v go in at
    [1, 8192, 512] as the projections make them, dK / dV come out at that
    width, and no array of the query heads' width is copied, broadcast or
    summed to make or unmake a repeat."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    b, s, hidden, h, kv, d = 1, 8192, 2048, 16, 2, 256

    def layer(x, w_q, w_k, w_v, w_out):
        out = attention.flash_attention_bse(
            (x @ w_q, x @ w_k, x @ w_v), d, True)
        return out @ w_out

    def fwd_bwd(x, w_q, w_k, w_v, w_out, do):
        out, vjp = jax.vjp(layer, x, w_q, w_k, w_v, w_out)
        return out, vjp(do)

    def spec(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    attention.reset_pallas_status()
    hlo = jax.jit(fwd_bwd).lower(
        spec(b, s, hidden), spec(hidden, h * d), spec(hidden, kv * d),
        spec(hidden, kv * d), spec(h * d, hidden),
        spec(b, s, hidden)).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kernels = {re.search(r"flash_(fwd|bwd_dq|bwd_dkv)", line).group(0): line
               for line in calls}
    assert len(calls) == 3 and sorted(kernels) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    narrow, wide = f"bf16[{b},{s},{kv * d}]", f"bf16[{b},{s},{h * d}]"
    for name, line in kernels.items():
        # q, k, v as the kernel takes them: k and v at the KV heads' width
        took = re.findall(r"bf16\[[0-9,]*\]", line.split(
            "operand_layout_constraints=")[1])[:3]
        assert took == [wide, narrow, narrow], (name, took)
    # and dK, dV leave their kernel at that width, the group's sum inside
    assert re.findall(r"bf16\[[0-9,]*\]", kernels["flash_bwd_dkv"].split(
        " custom-call(")[0]) == [narrow, narrow]
    assert not re.search(
        rf"= {re.escape(wide)}[^ ]* (copy|broadcast|reduce)\(", hlo)
    assert {(x["pass"], x["path"], tuple(x["shape"]), x["kv_heads"],
             x["block_q"], x["block_k"], x["tiles_live"], x["tiles"])
            for x in attention.pallas_status()} == {
        (p, "pallas", (b, h, s, d), kv, 1024, 1024, 528, 1024)
        for p in ("fwd", "bwd")}


def _gpt2_medium_step(n_layer, spec):
    """(jitted train step, its abstract arguments): GPT-2-medium at full
    width, `n_layer` layers, batch 8 x 1024, AdamW, donation, as the train
    cells build it; `spec(shape, dtype)` makes an argument."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.gpt2 import GPT2, GPT2Config, make_train_step

    cfg = dataclasses.replace(GPT2Config.medium(), n_layer=n_layer)
    model = GPT2(cfg)
    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = jax.tree.map(lambda a: spec(a.shape, a.dtype),
                             jax.eval_shape(opt.init, params))
    ids = spec((8, 1024), jnp.int32)
    step = make_train_step(model, opt, donate=True)
    return step, (params, opt_state, {"input_ids": ids, "labels": ids})


ACTIVATION_COPY = re.compile(
    r"= bf16\[(8,1024,1024|8,16,1024,64|8,1024,16,64)\][^ ]* copy\(")


@pytest.mark.slow
def test_gpt2_medium_step_compiles_without_activation_copies(one_chip,
                                                             monkeypatch):
    """The train cells' step at 2 layers compiled for one described v5e:
    one `flash_fwd`, `flash_bwd_dq`, `flash_bwd_dkv` a layer and no `copy`
    of a whole activation around them (the `[batch, heads, seq, d]` kernels
    of before PR 33 had 14 a layer: q, k, v split and transposed, the
    output transposed back, and the same for the gradients). ~15 s;
    `test_gpt2_step_hands_attention_the_projections_own_arrays` is the
    twin that runs with the rest."""
    import jax

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    n_layer = 2
    step, args = _gpt2_medium_step(
        n_layer, lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=one_chip))
    hlo = step.lower(*args).compile().as_text()
    kernels = [re.search(r"flash_(fwd|bwd_dq|bwd_dkv)", line).group(0)
               for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(kernels) == sorted(
        ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"] * n_layer)
    assert not ACTIVATION_COPY.findall(hlo)


def test_gpt2_step_hands_attention_the_projections_own_arrays(monkeypatch):
    """The same step as traced, no compiler: every layer calls each kernel
    once, on `c_attn`'s [8, 1024, 3072] output itself (q, k and v are one
    variable) and nothing between `c_attn` and `c_proj` transposes or
    splits an activation."""
    import jax

    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    n_layer = 2
    step, args = _gpt2_medium_step(n_layer, jax.ShapeDtypeStruct)
    attention.reset_pallas_status()
    found = {"transposes": [], "kernels": [], "wrappers": []}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.params.get("name")
            if eqn.primitive.name == "pallas_call":
                found["kernels"].append((name, eqn.invars[0].aval.shape))
            elif name in ("_flash_forward", "_flash_backward"):
                # the jitted wrappers' q, k, v as the layer hands them over
                found["wrappers"].append(eqn.invars[:3])
            elif eqn.primitive.name in ("transpose", "split") and \
                    eqn.invars[0].aval.ndim >= 3:
                found["transposes"].append(
                    (eqn.primitive.name, eqn.invars[0].aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(lambda *a: step(*a))(*args).jaxpr)
    assert found["transposes"] == []
    assert sorted(found["kernels"]) == sorted(
        [(k, (8, 1024, 3072))
         for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")] * n_layer)
    assert len(found["wrappers"]) == 2 * n_layer
    for q, k, v in found["wrappers"]:
        assert q is k is v and q.aval.shape == (8, 1024, 3072)
    assert {(e["pass"], e["layout"], e["heads_per_block"], tuple(e["shape"]),
             e["calls"]) for e in attention.pallas_status()} == {
        ("fwd", "bse", 2, (8, 16, 1024, 64), n_layer),
        ("bwd", "bse", 2, (8, 16, 1024, 64), n_layer)}
