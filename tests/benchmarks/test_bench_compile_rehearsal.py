"""Compile rehearsal: the two programs the cells stand on compile for one
v5e chip at their real sizes and fit its memory. The TPU's compiler is
installed here and compiles for a chip that is described, not attached;
nothing runs, so this says nothing about times. It guards that a later PR
has not made a cell unrunnable, at no chip time.

The topology is described inside a fixture (never at import: only one
process at a time may load the TPU's library), and the file is alone of
its kind so that one xdist worker owns the library."""

import os

import pytest

import _paths
from benchmarks import manifest as mf

HBM = 16e9


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
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def shaped(tree, sharding):
    import jax

    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def cell_files(name):
    manifest = mf.load(_paths.ROOT)
    cell = mf.cell_of(manifest, name)
    return mf.config_of(manifest, cell, _paths.ROOT), mf.traffic_of(cell)


def test_gpt2_medium_train_step_fits_one_chip(one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.gpt2 import GPT2, make_train_step
    from ray_tpu.ops import attention

    from benchmarks.builders.gpt2_train import model_config

    # The dispatch rule asks jax for the platform, which is the CPU here:
    # steer it in the test so that the step compiles WITH the kernels.
    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    config, traffic = cell_files("train_gpt2m_1chip")
    batch, seq = config["train"]["per_chip_batch"], traffic["seq"]
    model = GPT2(model_config(config, seq))
    opt = optax.adamw(config["train"]["lr"],
                      weight_decay=config["train"]["weight_decay"])
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)))
    opt_state = jax.eval_shape(opt.init, params)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    compiled = make_train_step(model, opt, mesh=None, donate=True).lower(
        shaped(params, one_chip), shaped(opt_state, one_chip),
        {"input_ids": ids, "labels": ids}).compile()
    hlo = compiled.as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert hlo.count(kernel) >= 24, kernel
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0.5 * HBM < need < HBM, need


def test_mistral_decode_and_prefill_fit_one_chip(one_chip):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama

    from benchmarks.builders.llama_serve import llama_config

    config, _ = cell_files("serve_mistral7b_decode_heavy")
    eng = config["engine"]
    cfg = llama_config(config)
    model = Llama(cfg)
    params = shaped(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))), one_chip)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    arena = spec((eng["num_blocks"], eng["block_size"], cfg.n_kv_head,
                  cfg.head_dim), cfg.dtype)
    arenas = [(arena, arena) for _ in range(cfg.n_layer)]

    # The engine's two programs (`InferenceEngine._build_programs`): one
    # paged forward, at [slots, 1] and at [1, chunk].
    def step_fn(params, arenas, toks, bt, pos, wmask):
        logits, arenas = model.apply(params, toks, arenas, bt, pos, wmask,
                                     method=Llama.decode_paged)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), arenas

    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    cache = 2 * cfg.n_layer * arena.size * arena.dtype.itemsize
    assert weights == pytest.approx(7.52e9, rel=0.01)
    assert cache == pytest.approx(4.30e9, rel=0.01)
    for b, s in ((eng["batch_slots"], 1), (1, eng["prefill_chunk"])):
        compiled = jax.jit(step_fn, donate_argnums=(1,)).lower(
            params, arenas, spec((b, s), jnp.int32),
            spec((b, eng["max_blocks_per_seq"]), jnp.int32),
            spec((b,), jnp.int32), spec((b, s), jnp.bool_)).compile()
        mem = compiled.memory_analysis()
        need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert weights + cache < need < HBM, (b, s, need)
