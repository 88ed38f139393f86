#!/usr/bin/env python3
"""Chip smoke: the train and serve main paths, once, on the TPU, through
the entry points a user calls.

    python chip_smoke.py              # needs a TPU; fails without one
    python chip_smoke.py --rehearsal  # CPU dress rehearsal at toy sizes

This process never starts a jax backend: it brings the cluster up with
`ray_tpu.init()` and every leg runs in a worker that holds a TPU grant.

  A   JaxTrainer.fit, one chip: GPT2Config.small() at batch 24 x seq 1024
      (bf16 activations, f32 params, flash attention, AdamW, donation);
      the Pallas kernels compiled at that shape, agree with the XLA
      reference on the chip, and also take one step at seq 8192 (remat).
  A'  the same fit at once in a fresh worker: the chip is handed over and
      the persistent compile cache hits across processes.
  B   serve.run(LLMServer(model_size="small")) on one chip behind HTTP:
      concurrent and streamed requests, checked from the replica's stats.
  C   the fit of A over four chips through session.get_mesh() (dp=4,
      global batch 96), when the host has four.

Every line printed is one JSON object; the last one is the verdict. Any
failed check exits non-zero at once. Passed or failed, the run ends with
the cluster shut down and no process it started alive (`stop_everything`;
one that the system's own shutdown left behind fails the run). No number
here is a benchmark: step and compile times are smoke observations.
`--rehearsal` stamps `"rehearsal": true` on every line and proves nothing
about the chip.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import platform
import signal
import sys
import threading
import time
import urllib.request

FULL = {
    "model": "small", "batch": 24, "seq": 1024, "timed_steps": 5,
    "long_batch": 4, "long_seq": 8192,
    "parity_shape": (2, 4, 512, 64),
    "serve_model": "small",
    "engine": {"batch_slots": 8, "block_size": 16, "max_blocks_per_seq": 64,
               "num_blocks": 8 * 64 + 1, "prefill_chunk": 128},
    "prompts": (100, 400, 250, 180, 320, 140), "new_tokens": 24,
    "stream_prompt": 200,
}
TOY = {
    "model": "toy", "batch": 4, "seq": 256, "timed_steps": 5,
    "long_batch": 2, "long_seq": 512,
    "parity_shape": (1, 2, 256, 64),
    "serve_model": "tiny",
    "engine": {"batch_slots": 8, "block_size": 8, "max_blocks_per_seq": 16,
               "num_blocks": 8 * 16 + 1, "prefill_chunk": 16},
    "prompts": (20, 60, 40, 30, 50, 25), "new_tokens": 6,
    "stream_prompt": 30,
}
STAMP: dict = {}


def emit(**fields):
    print(json.dumps({**fields, **STAMP}), flush=True)


def check(ok, what: str):
    if not ok:
        sys.exit(f"chip_smoke: FAILED: {what}")


# Every process the cluster starts inherits this variable, so what this run
# started can be found again whoever its parent is by then.
MARK = "CHIP_SMOKE_RUN"


def started_processes():
    """{pid: command} of the live processes this run started."""
    want = f"{MARK}={os.getpid()}".encode()
    found = {}
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == os.getpid():
            continue
        try:
            with open(f"/proc/{name}/environ", "rb") as f:
                if want not in f.read().split(b"\0"):
                    continue        # a zombie reads empty: it is not counted
            with open(f"/proc/{name}/cmdline", "rb") as f:
                found[int(name)] = f.read().replace(b"\0", b" ").decode()[:120]
        except OSError:
            continue                # gone while we looked
    return found


def stop_everything():
    """Cluster down, forge templates stopped, and nothing this run started
    still alive. Returns what had to be killed for that ({} when the
    system's own shutdown left nothing)."""
    import ray_tpu
    from ray_tpu.core import worker_forge

    ray_tpu.shutdown()
    worker_forge.kill_templates()
    deadline = time.monotonic() + 10
    while (left := started_processes()) and time.monotonic() < deadline:
        time.sleep(0.1)
    for pid in left:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    deadline = time.monotonic() + 5
    while left and started_processes() and time.monotonic() < deadline:
        time.sleep(0.1)
    return left


# --------------------------------------------------------------------------- #
# The train leg: runs inside the TPU-granted train worker
# --------------------------------------------------------------------------- #


def gpt2_leg(config):
    """(params, opt_state, batch) steps of GPT-2 through make_train_step,
    over session.get_mesh() when the trainer built one. Reports what it
    saw; the parent asserts."""
    import dataclasses
    import math
    import re

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu._jax_env import compilation_cache_dir
    from ray_tpu.models.gpt2 import (GPT2, GPT2Config, init_sharded,
                                     make_eval_step, make_train_step)
    from ray_tpu.ops import attention
    from ray_tpu.parallel.sharding import named_sharding
    from ray_tpu.train import session

    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("compilation_cache/cache_misses"):
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    mesh = session.get_mesh()
    devices = jax.local_devices()
    base = GPT2Config.small() if config["model"] == "small" else \
        dataclasses.replace(GPT2Config.tiny(), n_embd=256, n_head=4)
    out = {"cache_dir": compilation_cache_dir()}

    def kernel_calls(hlo: str):
        """name -> result shapes of the Mosaic custom calls in compiled HLO."""
        found = {}
        for line in hlo.splitlines():
            if 'custom_call_target="tpu_custom_call"' not in line:
                continue
            name = re.search(r"flash_(fwd|bwd_dq|bwd_dkv)", line)
            shape = re.search(r"= \(?(\w+\[[\d,]*\])", line)
            found.setdefault(name.group(0) if name else "unnamed", []).append(
                shape.group(1) if shape else "?")
        return found

    def run(tag, batch_size, seq, remat, timed_steps):
        cfg = dataclasses.replace(base, n_positions=seq, remat=remat)
        model = GPT2(cfg)
        ids = jax.random.randint(jax.random.PRNGKey(config["seed"]),
                                 (batch_size, seq), 0, cfg.vocab_size,
                                 dtype=jnp.int32)
        if mesh is None:
            params = jax.jit(lambda: model.init(jax.random.PRNGKey(0),
                                                ids[:1]))()
        else:
            params = init_sharded(model, mesh, (batch_size, seq))
            ids = jax.device_put(ids, named_sharding(mesh, "batch", None))
        opt = optax.adamw(3e-4, weight_decay=0.1)
        opt_state = jax.jit(opt.init)(params)
        batch = {"input_ids": ids, "labels": ids}
        first_loss_1dev = None
        if mesh is not None and tag == "main":
            # The same global batch and seed on ONE chip, before the first
            # update: forward-only in per-chip slices (the whole batch's
            # logits do not fit one chip), mean of equal-sized slices.
            one = devices[0]
            p1 = jax.device_put(jax.tree.map(
                lambda a: a.addressable_shards[0].data, params), one)
            ids1 = jax.device_put(jax.device_get(ids), one)
            ev = make_eval_step(model)
            n = len(devices)
            first_loss_1dev = sum(float(ev(p1, {"input_ids": c, "labels": c}))
                                  for c in jnp.split(ids1, n)) / n
            del p1, ids1
        step = make_train_step(model, opt, mesh=mesh, donate=True)
        attention.reset_pallas_status()
        misses0 = cache["misses"]
        t0 = time.perf_counter()
        lowered = step.lower(params, opt_state, batch)
        t1 = time.perf_counter()
        compiled = lowered.compile()    # the part the persistent cache holds
        t2 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, batch)
        first_loss = float(loss)
        hlo = compiled.as_text()
        rec = {"shape": [batch_size, seq], "remat": remat,
               "trace_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
               "first_step_s": round(time.perf_counter() - t2, 2),
               "first_loss": first_loss,
               "cache_misses": cache["misses"] - misses0,
               "attention": attention.pallas_status(),
               "kernels": kernel_calls(hlo)}
        if first_loss_1dev is not None:
            rec["first_loss_one_chip"] = first_loss_1dev
        if timed_steps:
            params, opt_state, loss = compiled(params, opt_state, batch)
            loss.block_until_ready()          # second warm-up step
            t0 = time.perf_counter()
            for _ in range(timed_steps):
                params, opt_state, loss = compiled(params, opt_state, batch)
            loss.block_until_ready()
            rec["ms_per_step"] = round(
                (time.perf_counter() - t0) / timed_steps * 1e3, 2)
            rec["loss"] = float(loss)
        if mesh is not None:
            want = set(devices)
            leaves = jax.tree.leaves((opt_state, batch))
            rec["leaves_on_all_devices"] = all(
                {s.device for s in leaf.addressable_shards} == want
                for leaf in leaves)
            rec["bytes_in_use"] = [          # the CPU backend keeps none
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in devices]
            rec["hlo_has_global_attention_operand"] = (
                f"[{batch_size},{seq},{3 * cfg.n_embd}]" in hlo)
        rec["finite"] = math.isfinite(first_loss) and math.isfinite(
            rec.get("loss", first_loss))
        out[tag] = rec

    run("main", config["batch"], config["seq"], False, config["timed_steps"])
    if config["extras"]:
        # On-chip numerics: kernels against the XLA reference, forward and
        # grads, on the device itself (not interpret mode).
        attention.reset_pallas_status()
        q, k, v = (jax.random.normal(key, config["parity_shape"],
                                     jnp.float32)
                   for key in jax.random.split(jax.random.PRNGKey(2), 3))

        def sq(fn):
            return lambda q, k, v: jnp.mean(fn(q, k, v) ** 2)

        flash = lambda q, k, v: attention.flash_attention(q, k, v, True)
        ref = lambda q, k, v: attention.mha_reference(q, k, v, causal=True)
        g_flash = jax.grad(sq(flash), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(sq(ref), argnums=(0, 1, 2))(q, k, v)
        out["parity"] = {
            "shape": list(config["parity_shape"]),
            "fwd_maxerr": float(jnp.max(jnp.abs(flash(q, k, v)
                                                - ref(q, k, v)))),
            "grad_maxerr": max(float(jnp.max(jnp.abs(a - b)))
                               for a, b in zip(g_flash, g_ref)),
            "attention": attention.pallas_status()}
        run("long", config["long_batch"], config["long_seq"], True, 0)
    out["cache_hits"] = cache["hits"]
    out["cache_misses"] = cache["misses"]
    session.report(out)


def check_attention(rec, n_layer, local_shape, what):
    """Every traced attention call of the step went through Pallas, at the
    per-device shape, once per layer and pass at least."""
    calls = rec["attention"]
    check(calls and all(c["path"] == "pallas" for c in calls),
          f"{what}: attention calls off the Pallas path: {calls}")
    check(all(c["shape"] == list(local_shape) for c in calls),
          f"{what}: kernels traced at {[c['shape'] for c in calls]}, "
          f"expected {list(local_shape)}")
    for pass_ in ("fwd", "bwd"):
        n = sum(c["calls"] for c in calls if c["pass"] == pass_)
        check(n >= n_layer, f"{what}: {n} {pass_} calls for {n_layer} layers")


def check_kernels(rec, n_layer, local_shape, what):
    """The compiled program holds the three Mosaic kernels, once per layer
    at least, with outputs of the local [b, s, h*d] shape."""
    b, h, s, d = local_shape
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        shapes = rec["kernels"].get(name, [])
        check(len(shapes) >= n_layer,
              f"{what}: {len(shapes)} compiled {name} calls for {n_layer} "
              f"layers (found {({k: len(v) for k, v in rec['kernels'].items()})})")
        check(all(x == f"bf16[{b},{s},{h * d}]" for x in shapes),
              f"{what}: {name} compiled at {sorted(set(shapes))}")


def fit(tag, sizes, extras, scaling, expect, session_dir, seed=0):
    from ray_tpu.train import JaxTrainer, RunConfig
    from ray_tpu.train.backend import JaxConfig

    t0 = time.perf_counter()
    result = JaxTrainer(
        gpt2_leg,
        train_loop_config={**sizes, "extras": extras, "seed": seed},
        jax_config=JaxConfig(distributed=False, mesh=scaling.mesh),
        scaling_config=scaling,
        run_config=RunConfig(name=f"chip_smoke_{tag}",
                             storage_path=os.path.join(session_dir,
                                                       "results")),
    ).fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    device = {k: m[k] for k in ("platform", "device_kind", "n_devices")}
    check(all(device[k] == v for k, v in expect.items()),
          f"leg {tag} ran on {device}, expected {expect}")
    check(m["main"]["finite"], f"leg {tag}: non-finite loss")
    emit(leg=tag, ok=True, **device, wall_s=round(time.perf_counter() - t0, 1),
         cache_dir=m["cache_dir"], cache_hits=m["cache_hits"],
         cache_misses=m["cache_misses"],
         **{k: {f: ({n: len(x) for n, x in v.items()} if f == "kernels"
                    else v)
                for f, v in m[k].items() if f != "attention"}
            for k in ("main", "parity", "long") if k in m})
    return m


# --------------------------------------------------------------------------- #
# The serve leg
# --------------------------------------------------------------------------- #


def post(port, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/LLMServer", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        return resp.read()


def serve_leg(sizes, actor_options, expect):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.inference import LLMServer

    t0 = time.perf_counter()
    handle = serve.run(
        LLMServer.options(ray_actor_options=actor_options).bind(
            model_size=sizes["serve_model"], default_new_tokens=16,
            engine_config=sizes["engine"]),
        timeout_s=600.0)
    deploy_s = time.perf_counter() - t0
    port = serve.http_port()
    new = sizes["new_tokens"]
    prompts = [[(7 * i + j) % 997 + 1 for j in range(n)]
               for i, n in enumerate(sizes["prompts"])]
    answers = {}

    def ask(i):
        body = json.loads(post(port, {"ids": prompts[i],
                                      "max_new_tokens": new}))
        answers[i] = body["result"]["ids"]

    t0 = time.perf_counter()
    ask(0)                      # compiles prefill + decode
    first_s = time.perf_counter() - t0
    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(1, len(prompts))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    stream_ids = [(3 * j) % 997 + 1 for j in range(sizes["stream_prompt"])]
    lines = [json.loads(line) for line in post(
        port, {"ids": stream_ids, "max_new_tokens": new,
               "stream": True}).splitlines() if line.strip()]
    for t in threads:
        t.join(timeout=300)
    burst_s = time.perf_counter() - t0
    for i, p in enumerate(prompts):
        check(answers.get(i, [])[:len(p)] == p
              and len(answers[i]) == len(p) + new,
              f"request {i}: wrong answer shape {len(answers.get(i, []))}")
        check(all(0 <= t < 32000 for t in answers[i]),
              f"request {i}: token out of vocabulary")
    tokens = [e["token"] for e in lines if "token" in e]
    check(len(tokens) == new and lines[-1].get("done")
          and lines[-1]["ids"] == stream_ids + tokens,
          f"streamed request: {len(tokens)} tokens, tail {lines[-1:]}")
    sent = len(prompts) + 1
    stats = ray_tpu.get(handle.metrics.remote(None), timeout=60)
    device = {k: stats[k] for k in ("platform", "device_kind", "n_devices")}
    check(all(device[k] == v for k, v in expect.items()),
          f"leg B answered from {device}, expected {expect}")
    for key, want in (("prefill_compiles", 1), ("decode_compiles", 1),
                      ("requests_finished", sent), ("requests_failed", 0)):
        check(stats[key] == want, f"leg B: {key}={stats[key]}, want {want}")
    check(stats["kv"]["blocks_in_use"]
          == stats["prefix_cache"]["cached_blocks"],
          f"leg B: blocks leaked at drain: {stats['kv']} vs "
          f"{stats['prefix_cache']}")
    serve.shutdown()
    emit(leg="B", ok=True, **device, deploy_s=round(deploy_s, 1),
         first_request_s=round(first_s, 2), burst_s=round(burst_s, 2),
         requests=sent, tokens_emitted=stats["tokens_emitted"],
         prefill_compiles=1, decode_compiles=1,
         blocks_in_use=stats["kv"]["blocks_in_use"],
         cached_blocks=stats["prefix_cache"]["cached_blocks"])
    return device


def wait_chips_back(total, what):
    """The TPU share of a worker returns when its pid has exited."""
    import ray_tpu

    raylet = ray_tpu._global_node.raylet
    deadline = time.monotonic() + 60
    while raylet.resources.snapshot()[1].get("TPU", 0.0) != total:
        check(time.monotonic() < deadline,
              f"{what}: its worker still holds the chip after 60 s")
        time.sleep(0.2)


# --------------------------------------------------------------------------- #


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearsal", action="store_true",
                        help="CPU dress rehearsal at toy sizes; proves "
                             "nothing about the chip")
    args = parser.parse_args()
    rehearsal = args.rehearsal
    sizes = TOY if rehearsal else FULL
    if rehearsal:
        STAMP["rehearsal"] = True
        os.environ.update(
            JAX_PLATFORMS="cpu", RAY_TPU_PALLAS_INTERPRET="1",
            XLA_FLAGS="--xla_force_host_platform_device_count=4")

    from ray_tpu.core.node import detect_tpu_chips

    chips = detect_tpu_chips()
    pinned = os.environ.get("JAX_PLATFORMS", "")
    if not rehearsal:
        if not chips:
            sys.exit("chip_smoke: no TPU chip on this host (no /dev/accel* "
                     "or Google vfio group; JAX_PLATFORMS="
                     f"{pinned!r}). It never runs on the CPU: see "
                     "--rehearsal.")
        if pinned and "tpu" not in pinned.split(","):
            sys.exit(f"chip_smoke: JAX_PLATFORMS={pinned!r} keeps this run "
                     f"off the {chips} TPU chip(s) of this host; unset it.")
    os.environ[MARK] = str(os.getpid())
    try:
        device, advertised, four_chip = legs(sizes, rehearsal, chips)
    finally:
        left = stop_everything()
    check(not left, f"processes outlived the cluster's shutdown: {left}")
    emit(summary={"A": "passed", "A'": "passed", "B": "passed"},
         four_chip=four_chip, processes_left=0, claim=None)
    emit(ok=True, device={"platform": device["platform"],
                          "kind": device["device_kind"],
                          "count": advertised or device["n_devices"]})


def legs(sizes, rehearsal, chips):
    import ray_tpu
    from ray_tpu import _jax_env, _native
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train import ScalingConfig

    ray_tpu.init()
    advertised = int(ray_tpu.cluster_resources().get("TPU", 0))
    session_dir = ray_tpu._global_node.session_dir
    emit(python=platform.python_version(),
         **{p: importlib.metadata.version(p)
            for p in ("jax", "jaxlib", "libtpu", "flax", "optax")},
         chips_on_host=chips, chips_advertised=advertised,
         compile_cache_dir=_jax_env.compilation_cache_dir(),
         compile_cache_from_env=bool(os.environ.get(_jax_env.CACHE_ENV)),
         fastcopy_native=_native.get_lib() is not None)
    check(advertised == chips, f"node advertises {advertised} chips, the "
                               f"host has {chips}")

    if rehearsal:
        expect, expect4 = {"platform": "cpu"}, {"platform": "cpu"}
        one_chip = ScalingConfig(num_workers=1)
        four = 4
        serve_opts = {}
    else:
        expect = {"platform": "tpu", "n_devices": 1}
        expect4 = {"platform": "tpu", "n_devices": 4}
        one_chip = ScalingConfig(num_workers=1, use_tpu=True,
                                 tpus_per_worker=1)
        four = 4 if chips >= 4 else 0
        serve_opts = {"num_tpus": 1}
    small = sizes["model"] == "small"
    n_layer, n_head = (12, 12) if small else (2, 4)
    train_shape = (sizes["batch"], n_head, sizes["seq"], 64)
    long_shape = (sizes["long_batch"], n_head, sizes["long_seq"], 64)

    a = fit("A", sizes, True, one_chip, expect, session_dir)
    check_attention(a["main"], n_layer, train_shape, "leg A")
    check_attention(a["long"], n_layer, long_shape, "leg A seq-8192")
    check(all(c["path"] == "pallas" for c in a["parity"]["attention"]),
          f"leg A parity ran off the kernels: {a['parity']['attention']}")
    check(max(a["parity"]["fwd_maxerr"], a["parity"]["grad_maxerr"]) < 2e-2,
          f"flash kernels diverge from the XLA reference: {a['parity']}")
    check(10.0 < a["main"]["first_loss"] < 12.0 or not small,
          f"first loss {a['main']['first_loss']} is not ~ln(50304)")
    if not rehearsal:   # interpret mode compiles no Mosaic kernel
        check_kernels(a["main"], n_layer, train_shape, "leg A")
        check_kernels(a["long"], n_layer, long_shape, "leg A seq-8192")
    wait_chips_back(advertised, "leg A")

    a2 = fit("A'", sizes, False, one_chip, expect, session_dir)
    check(a2["cache_dir"] == a["cache_dir"],
          f"compile cache moved: {a['cache_dir']} -> {a2['cache_dir']}")
    check(a2["main"]["cache_misses"] == 0 and a2["cache_hits"] > 0,
          f"second fit missed the compile cache: {a2['cache_hits']} hits, "
          f"{a2['cache_misses']} misses")
    cold, second = a["main"]["compile_s"], a2["main"]["compile_s"]
    if a["main"]["cache_misses"]:   # A really compiled: the times compare
        check(second < 0.5 * cold, f"second-fit compile {second}s is not a "
                                   f"small fraction of the cold {cold}s")
    emit(compile_cache="hit across processes", cold_compile_s=cold,
         second_fit_compile_s=second,
         first_fit_was_cold=bool(a["main"]["cache_misses"]))
    wait_chips_back(advertised, "leg A'")

    device = serve_leg(sizes, serve_opts, expect)
    wait_chips_back(advertised, "leg B")

    if four:
        c = fit("C", {**sizes, "batch": sizes["batch"] * four}, False,
                ScalingConfig(num_workers=1, use_tpu=not rehearsal,
                              tpus_per_worker=0 if rehearsal else four,
                              mesh=MeshSpec({"dp": four})),
                expect4, session_dir)["main"]
        check_attention(c, n_layer, train_shape, "leg C")
        check(c["leaves_on_all_devices"],
              "leg C: a batch or optimizer-state leaf misses a device")
        check(abs(c["first_loss"] - c["first_loss_one_chip"]) < 5e-2,
              f"leg C: first loss {c['first_loss']} vs one chip "
              f"{c['first_loss_one_chip']}")
        if not rehearsal:
            check(min(c["bytes_in_use"]) > 0.5 * max(c["bytes_in_use"]),
                  f"leg C: memory is not spread: {c['bytes_in_use']}")
            check_kernels(c, n_layer, train_shape, "leg C")
            check(not c["hlo_has_global_attention_operand"],
                  "leg C: an attention operand of the global shape is in "
                  "the compiled step (q/k/v gathered in front of the kernel)")
        four_chip = "passed"
        wait_chips_back(advertised, "leg C")
    else:
        four_chip = f"not run: {chips} chip"
    return device, advertised, four_chip


if __name__ == "__main__":
    main()
