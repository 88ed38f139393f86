"""Compile rehearsal for the dots3 cell's two programs: `Dots3.paged_step`
at the cell's sizes, a table a kind, lowers and compiles for one described
v5e chip with its three kernels in every layer that has them, both pools'
arenas updated in place, inside the chip's memory (the pattern of
`tests/test_paged_attention_compile.py`; nothing runs, so this says nothing
about times). A file of its own: a compiled program's text lists the FILES
its operations were traced under, a function traced here is found again by
a later test's program at the same shapes (Brumby's hidden size is this
model's), and `tests/benchmarks/test_bench_brumby.py` holds that its
programs' text does not say `paged_attention`.

The topology is described inside a fixture (`conftest.one_chip`)."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 16e9


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_dots3_programs_compile_with_the_three_kernels_and_fit(
        one_chip, monkeypatch, program):
    """The engine's programs over `Dots3.paged_step` at the dots3 cell's
    sizes, a table a kind: `dsa_index` and the gathered `latent_decode` in
    both full layers, the windowed latent kernel in the three sliding
    ones, `moe_gmm` twice in each of the four expert layers; both pools'
    arenas updated in place; weights + arenas + the gather's private
    buffer inside the chip's memory, over 60% of it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.dots3 import Dots3
    from ray_tpu.ops import attention, grouped_matmul

    monkeypatch.syspath_prepend(ROOT)
    from benchmarks.builders.dots3_serve import model_config

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    monkeypatch.setattr(grouped_matmul, "_platform", lambda: "tpu")
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "dots3-note-l5-e32-serve.json")) as f:
        config = json.load(f)
    eng = config["engine"]
    model = Dots3(model_config(config))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def specs(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    params = specs(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = specs(jax.eval_shape(lambda: model.paged_cache(
        eng["num_blocks"], eng["block_size"], None, eng["batch_slots"],
        kinds={"window": eng["window_blocks"]})))
    weights, arenas = nbytes(params), nbytes(cache)
    assert 8.17e9 < weights < 8.18e9 and 2.5e9 < arenas < 2.6e9
    slots, width, chunk = (eng["batch_slots"], eng["max_blocks_per_seq"],
                           eng["prefill_chunk"])

    def tables(b):
        return {kind: spec((b, width), jnp.int32)
                for kind in ("full", "window")}

    def decode_fn(params, cache, tokens, bt, pos, wmask):
        logits, cache = model.paged_step(params, tokens[:, None], cache, bt,
                                         pos, wmask)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

    def prefill_fn(params, cache, ids, bt, pos, wmask, last_idx, slot):
        logits, cache = model.paged_step(params, ids, cache, bt, pos, wmask,
                                         None, slot, last_idx)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    fn, args = {
        "decode": (decode_fn, (
            spec((slots,), jnp.int32), tables(slots),
            spec((slots,), jnp.int32), spec((slots, 1), jnp.bool_))),
        "prefill": (prefill_fn, (
            spec((1, chunk), jnp.int32), tables(1), spec((1,), jnp.int32),
            spec((1, chunk), jnp.bool_), spec((1,), jnp.int32),
            spec((1,), jnp.int32)))}[program]
    attention.reset_pallas_status()
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]

    def count(kernel):
        return sum(bool(re.search(rf"%{kernel}[.\d]* = ", c)) for c in calls)

    # (an expert layer's two products, in its first block and once more
    # in the later blocks' loop, which runs where a step routes more to
    # the held share than three times its even load)
    assert count("dsa_index") == 2 and count("moe_gmm") == 16
    # the gathered attention is the decode kernel, a query token a row (a
    # chunk's queries 64 at a time, each group under a `cond`); a chunk's
    # window layers are the prefill kernel
    gathered = 2 if program == "decode" else 2 * chunk // 64
    assert count("latent_decode") == gathered + (3 if program == "decode"
                                                 else 0)
    assert count("latent_prefill") == (0 if program == "decode" else 3)
    queries = slots if program == "decode" else 64
    passes = {(r["pass"], tuple(r["shape"])): r["path"]
              for r in attention.pallas_status()}
    assert passes == {
        ("paged_dsa_index", (slots, 1, 64, 128) if program == "decode"
         else (1, chunk, 64, 128)): "pallas",
        ("paged_latent_decode", (queries, 1, 128, 640)): "pallas",
        ("paged_latent_decode", (slots, 1, 64, 1152))
        if program == "decode"
        else ("paged_latent_prefill", (1, chunk, 64, 1152)): "pallas"}
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes(cache["latent"]) \
        + nbytes(cache["index"]) + nbytes(cache["routing"])
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0.6 * HBM < weights + arenas < need < weights + arenas + 0.9e9 \
        < HBM, need
