#!/usr/bin/env python3
"""python3 scripts/lowered_programs.py <out-dir>: the lowered text of every
program a cell times, on the CPU at its configuration's `rehearsal` sizes,
a file a program. StableHLO text carries no source locations, so a refactor
that changes no operation leaves every file byte for byte what it was: run
it on a copy of the parent and on the change and `diff -r` the two (~1 min
a tree). A train cell's program is its step; a served cell's are whatever
its engine calls while two requests overlap (prefill, decode, a decode step
with a chunk aboard, the block program), lowered at their first call."""

import os
import sys
from importlib import import_module

os.environ.update(JAX_PLATFORMS="cpu", RAY_TPU_PALLAS_INTERPRET="1",
                  XLA_FLAGS="--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from benchmarks import manifest as mf  # noqa: E402

# builder -> (the model's module, its class, the builder's config maker)
SERVED = {"llama_serve": ("llama", "Llama", "llama_config"),
          "falcon_h1_serve": ("falcon_h1", "FalconH1", "model_config"),
          "brumby_serve": ("brumby", "Brumby", "model_config"),
          "kanana2_serve": ("deepseek_v3", "DeepseekV3", "model_config"),
          "ouro_serve": ("ouro", "Ouro", "model_config"),
          "sdar_serve": ("sdar", "SDAR", "model_config"),
          "dots3_serve": ("dots3", "Dots3", "model_config")}


def served_programs(config):
    from ray_tpu.inference.engine import EngineConfig, InferenceEngine

    module, cls, maker = SERVED[config["builder"]]
    model = getattr(import_module(f"ray_tpu.models.{module}"), cls)(
        getattr(mf.builder_of(config), maker)(config))
    key = jax.random.PRNGKey(0)
    params = model.init(key, jnp.zeros((1, 8), jnp.int32)) \
        if cls == "Llama" else model.init(key)
    engine = InferenceEngine(EngineConfig(**config["engine"]), model=model,
                             params=params)
    texts, call = {}, engine._call

    def lowering_call(name, fn, *args):
        if name not in texts:
            texts[name] = fn.lower(*args).as_text()
        return call(name, fn, *args)

    engine._call = lowering_call
    engine.add_request(list(range(1, 9)), max_new_tokens=12)
    for _ in range(3):
        engine.step()
    # longer than a chunk: one rides with the first request's decode steps
    engine.add_request(list(range(1, engine.config.prefill_chunk + 6)),
                       max_new_tokens=4)
    engine.run_until_idle()
    return texts


def train_programs(config, traffic):
    from ray_tpu.models.gpt2 import GPT2, make_train_step
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    builder = mf.builder_of(config)
    seq, train, kw = int(traffic["seq"]), config["train"], {}
    mesh = build_mesh(MeshSpec(dict(traffic["mesh"]))) \
        if traffic.get("mesh") else None
    batch = int(train["per_chip_batch"]) * (
        1 if mesh is None else mesh.devices.size)
    if config["builder"] == "gpt2_train":
        model = GPT2(builder.model_config(config, seq))
        rate = float(train["lr"])
    else:
        from ray_tpu.models.qwen3_next import Qwen3Next, make_loss_fn

        model = Qwen3Next(builder.model_config(config))
        rate, kw["loss_fn"] = builder.learning_rate(train), make_loss_fn(model)
    opt = optax.adamw(rate, weight_decay=float(train["weight_decay"]))
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((batch, seq), jnp.int32)))
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    step = make_train_step(model, opt, mesh=mesh, donate=True, **kw)
    return {"step": step.lower(params, jax.eval_shape(opt.init, params),
                               {"input_ids": ids, "labels": ids}).as_text()}


def main() -> int:
    os.makedirs(sys.argv[1], exist_ok=True)
    manifest, seen = mf.load(ROOT), set()
    for cell in manifest["workloads"]:
        config = mf.apply_rehearsal(mf.config_of(manifest, cell, ROOT))
        traffic = mf.apply_rehearsal(mf.traffic_of(cell))
        if (config["name"], str(traffic.get("mesh"))) in seen:
            continue        # a second traffic mix over the same programs
        seen.add((config["name"], str(traffic.get("mesh"))))
        texts = served_programs(config) if config["builder"] in SERVED \
            else train_programs(config, traffic)
        for name, text in texts.items():
            with open(os.path.join(
                    sys.argv[1], f"{cell['name']}.{name}.txt"), "w") as f:
                f.write(text)
            print(f"{cell['name']}.{name}: {len(text)} bytes", flush=True)
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
