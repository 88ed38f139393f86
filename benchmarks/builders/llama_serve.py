"""Builder `llama_serve`: a Llama-equations configuration (here Mistral-7B
widths) served through `serve.run` of a deployment that subclasses
`LLMServer`'s class and differs only in handing `InferenceEngine` the
configuration's `Llama(cfg)` and seeded parameters (`LLMServer` takes a
preset name, not a configuration: PERF.md, Open questions), plus methods
that read what the benchmark needs from the process that holds the chip.

Requests go over HTTP through the proxy, streamed, so that tokens are
counted and timed as they reach the client.
"""

from __future__ import annotations

import os
import statistics
import time
from typing import Any, Dict, List

from ray_tpu import serve
from ray_tpu.inference import LLMServer

# Each served greedy token must have a float32-reference logit within this
# margin of the reference's maximum at its position. Logits, not token
# equality: with seeded random weights (init std 0.02, logit std ~1.3
# over a 32k vocabulary) the top two logits are often a few 1e-2 apart and
# the argmax flips on rounding. The served path computes in bf16, whose
# error over 16 layers reaches ~0.1 on a logit: over 20 seeded requests of
# 16 tokens on the chip (PR 23) the largest gap was 0.144, the mean 0.006,
# and 9 tokens in 10 were the reference's own choice. 0.5 passes that with
# room for other seeds and fails a path that drops a layer, a head group
# or the rotary phase (those move logits by ~1), or computes in 8 bits
# (~8x the bf16 error).
LOGIT_MARGIN = 0.5
CHECK_PROMPT, CHECK_NEW = 48, 16
TRACED_SECONDS = 4.0


def llama_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=int(cfg["vocab_size"]),
        n_positions=int(cfg["max_position_embeddings"]),
        n_embd=int(cfg["hidden_size"]),
        n_layer=int(cfg["num_hidden_layers"]),
        n_head=int(cfg["num_attention_heads"]),
        n_kv_head=int(cfg["num_key_value_heads"]),
        intermediate=int(cfg["intermediate_size"]),
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        param_dtype=jnp.bfloat16, use_flash=False)


class _BenchLLM(LLMServer._target):
    """`LLMServer` with the model handed in. Everything a request touches
    (`__call__`, `generate`, `stream`, the engine loop) is inherited."""

    def __init__(self, model_cfg: Dict[str, Any],
                 engine_cfg: Dict[str, Any], seed: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.inference.engine import (EngineConfig, EngineLoop,
                                              InferenceEngine)
        from ray_tpu.models.llama import Llama

        from benchmarks import jaxwatch

        self._seen = jaxwatch.watch()
        self._spans = {"ctor_first_line": time.monotonic()}
        self._adapter_specs = {}
        self._default_new = 16
        self._config = EngineConfig(**engine_cfg)
        model = Llama(llama_config(model_cfg))
        t0 = time.monotonic()
        # Weights on the device, in one jitted call, from the seed, in
        # the type they are served in.
        # The key is an argument: every seed shares one compiled program.
        params = jax.jit(lambda key: model.init(
            key, jnp.zeros((1, 8), jnp.int32)))(
            jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
        jax.block_until_ready(params)
        self._spans["init_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        self._engine = InferenceEngine(self._config, model=model,
                                       params=params)
        self._spans["engine_ctor_s"] = time.monotonic() - t0
        self._loop = EngineLoop(self._engine)
        # Every request the engine takes is kept, for its phase stamps.
        self._requests: List[Any] = []
        submit = self._loop.submit

        def recording_submit(*args, **kwargs):
            req = submit(*args, **kwargs)
            self._requests.append(req)
            return req

        self._loop.submit = recording_submit
        self._marker = jax.jit(lambda x: x + 1)
        self._mark = jnp.zeros((), jnp.int32)
        self._marker(self._mark).block_until_ready()
        self._trace_dir = None
        self._trace_t0 = None

    # ------------------------------------------------------- benchmark reads

    def bench_stats(self, _=None) -> Dict[str, Any]:
        import jax

        stats = self._engine.stats()
        mem = [d.memory_stats() or {} for d in jax.local_devices()]
        stats["memory_peak_bytes"] = max(
            (m.get("peak_bytes_in_use", 0) for m in mem), default=0)
        stats["jax"] = dict(self._seen)
        stats["spans"] = dict(self._spans)
        stats["has_work"] = self._engine.has_work()
        return stats

    def bench_requests(self, _=None) -> List[Dict[str, Any]]:
        from benchmarks.loadgen import prompt_key

        return [{"key": prompt_key(r.prompt), "submitted_at": r.submitted_at,
                 "admitted_at": r.admitted_at,
                 "first_token_at": r.first_token_at,
                 "finished_at": r.finished_at, "error": r.error,
                 "preemptions": r.preemptions,
                 "cached_tokens": r.cached_tokens,
                 "n_generated": len(r.generated)}
                for r in list(self._requests)]

    def bench_reference(self, served: List[Dict[str, Any]]
                        ) -> List[Dict[str, float]]:
        """The served greedy tokens against the plain float32 forward of
        the same weights, on the chip, a layer upcast at a time."""
        import flax.linen as nn
        import jax
        import jax.numpy as jnp

        from benchmarks.reference import llama_plain

        p = nn.unbox(self._engine._params)
        p = p["params"] if "params" in p else p
        cfg = self._engine._model.config
        top = {"embed": p["embed"], "final_norm": p["final_norm"]["scale"],
               "lm_head": p["lm_head"]["kernel"]}

        def layer(i):
            blk = p[f"layer_{i}"]
            return {"attn_norm": blk["attn_norm"]["scale"],
                    "mlp_norm": blk["mlp_norm"]["scale"],
                    **{k: blk[k]["kernel"]
                       for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up",
                                 "w_down")}}

        out = []
        for item in served:
            prompt, generated = item["prompt"], item["generated"]
            ids = jnp.asarray([prompt + generated[:-1]], jnp.int32)
            logits = llama_plain.forward(
                top, layer, ids, cfg.n_layer, cfg.n_head, cfg.n_kv_head,
                cfg.rope_theta, cfg.rms_eps)[0]
            gaps = llama_plain.chosen_token_gaps(logits, len(prompt),
                                                 generated)
            out.append({"max_gap": float(jnp.max(gaps)),
                        "mean_gap": float(jnp.mean(gaps)),
                        "exact": int(jnp.sum(gaps == 0)),
                        "tokens": len(generated)})
            del logits
        return out

    def bench_trace_start(self, out_dir: str) -> float:
        import jax

        self._trace_dir = os.path.join(out_dir, "trace")
        jax.profiler.start_trace(self._trace_dir)   # takes seconds
        self._marker(self._mark).block_until_ready()
        self._trace_t0 = time.monotonic()
        self._trace_emitted0 = self._engine.stats()["tokens_emitted"]
        return self._trace_t0

    def bench_trace_stop(self, _=None) -> Dict[str, Any]:
        import jax

        self._marker(self._mark).block_until_ready()
        t1 = time.monotonic()
        emitted = self._engine.stats()["tokens_emitted"] \
            - self._trace_emitted0
        jax.profiler.stop_trace()                   # takes seconds
        return {"t0": self._trace_t0, "t1": t1, "tokens_emitted": emitted}

    def bench_trace_digest(self, keep_sample: bool = False):
        """The reduction of the trace just taken (after the window: it
        holds this process's event loop for seconds)."""
        from benchmarks import xplane

        return xplane.reduce_dir(
            self._trace_dir,
            os.path.dirname(self._trace_dir) if keep_sample else None)


def _deployment(rehearsal: bool):
    return serve.deployment(
        _BenchLLM, name="BenchLLM", max_concurrent_queries=64,
        route_prefix="/",
        ray_actor_options={} if rehearsal else {"num_tpus": 1})


def _call(handle, method: str, *args, timeout: float = 300.0):
    import ray_tpu

    return ray_tpu.get(getattr(handle, method).remote(*args), timeout=timeout)


def _wait_idle(handle, timeout_s: float = 30.0) -> Dict[str, Any]:
    deadline = time.monotonic() + timeout_s
    while True:
        stats = _call(handle, "bench_stats", None)
        if not stats["has_work"] or time.monotonic() > deadline:
            return stats
        time.sleep(0.2)


def run(ctx) -> Dict[str, Any]:
    """Parent side: deploy, warm up and check, offer the mix, verdict."""
    import numpy as np

    from benchmarks import loadgen

    cfg, traffic = ctx.config, ctx.traffic
    engine_cfg = dict(cfg["engine"])
    vocab = int(cfg["vocab_size"])
    model_cfg = {k: cfg[k] for k in (
        "vocab_size", "max_position_embeddings", "hidden_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "intermediate_size", "rope_theta", "rms_norm_eps")}
    spans = {"serve_run_called": time.monotonic()}
    handle = serve.run(_deployment(ctx.rehearsal).bind(
        model_cfg, engine_cfg, ctx.seed), timeout_s=900.0)
    spans["serve_run_returned"] = time.monotonic()
    url = f"http://127.0.0.1:{serve.http_port()}/"

    # Warm-up = the reference check: two seeded requests compile (or load)
    # prefill and decode, and their tokens are held to the plain forward.
    rng = np.random.default_rng(ctx.seed)
    check = [{"idx": i, "prompt_len": CHECK_PROMPT,
              "max_new_tokens": CHECK_NEW,
              "ids": [int(t) for t in rng.integers(1, vocab, CHECK_PROMPT)]}
             for i in range(2)]
    t0 = time.monotonic()
    warm = loadgen.run_open_loop(
        url, [{**r, "due_s": 0.0, "in_window": True} for r in check],
        time.monotonic(), 0.0, 900.0)
    spans["compile_s"] = time.monotonic() - t0
    problems = [f"warm-up request failed: {r['error']}"
                for r in warm if r["error"]]
    reference = []
    if not problems:
        t0 = time.monotonic()
        reference = _call(handle, "bench_reference", [
            {"prompt": c["ids"], "generated": r["tokens"]}
            for c, r in zip(check, warm)], timeout=600.0)
        spans["reference_check_s"] = time.monotonic() - t0
        worst = max(r["max_gap"] for r in reference)
        if not worst <= LOGIT_MARGIN:
            problems.append(f"a served token lies {worst} under the plain "
                            f"reference's best logit (> {LOGIT_MARGIN})")
    after_warm = _call(handle, "bench_stats", None)

    # The mix.
    lead_s = float(traffic.get("lead_s", 0.0))
    closed = traffic["loop"] == "closed"
    if closed:
        pool = loadgen.closed_pool(traffic, ctx.seed, vocab)
        prompts = None
    else:
        schedule = loadgen.open_schedule(traffic, ctx.seed, ctx.seconds,
                                         vocab, rate=ctx.rate)
        prompts = {r["idx"]: r["ids"] for r in schedule}
    t_zero = time.monotonic() + lead_s + 0.2
    spans["first_timed_request"] = t_zero
    tracer = None
    if ctx.trace:
        import threading

        # The profiler runs in the replica; it is started and stopped
        # from here, over the steady middle of the window.
        def trace_middle():
            start = t_zero + max(0.0, (ctx.seconds - TRACED_SECONDS) / 2)
            time.sleep(max(0.0, start - time.monotonic()))
            _call(handle, "bench_trace_start", ctx.out_dir)
            time.sleep(min(TRACED_SECONDS, ctx.seconds))
            tracer.result = _call(handle, "bench_trace_stop", None)

        tracer = threading.Thread(target=trace_middle, daemon=True)
        tracer.result = None
        tracer.start()
    if closed:
        records = loadgen.run_closed_loop(url, pool, int(traffic["clients"]),
                                          t_zero, ctx.seconds)
    else:
        records = loadgen.run_open_loop(url, schedule, t_zero, ctx.seconds,
                                        float(traffic["drain_s"]))
    stats = _wait_idle(handle)
    traced = None
    if tracer is not None:
        tracer.join(timeout=600.0)
        traced = tracer.result
        if traced is not None:
            traced["digest"] = _call(handle, "bench_trace_digest",
                                     ctx.keep_trace_sample, timeout=600.0)
    engine_reqs = _call(handle, "bench_requests", None)
    client = loadgen.reduce_records(records, t_zero, ctx.seconds)
    # Starting and stopping the profiler stalls the replica for seconds:
    # in a traced run the client-side layer metrics are taken over the
    # part of the window before it starts.
    quiet = client if not ctx.trace else loadgen.reduce_records(
        records, t_zero, max(1.0, (ctx.seconds - TRACED_SECONDS) / 2))

    # Verdict.
    if closed:
        prompts = {r["idx"]: pool[r["idx"] % len(pool)]["ids"]
                   for r in records}
        if len(records) > len(pool) and not ctx.rehearsal:
            problems.append(f"closed-loop pool of {len(pool)} wrapped "
                            f"({len(records)} requests): prompts repeated")
    problems += loadgen.wrong_answers(records, prompts)
    problems += [f"request {r['idx']} failed: {r['error']}"
                 for r in records if r["error"] and not r["cut"]][:5]
    for key in ("prefill_compiles", "decode_compiles"):
        if stats[key] != 1:
            problems.append(f"{key}={stats[key]}, want 1")
    compiles_in_window = stats["jax"]["compiles"] \
        - after_warm["jax"]["compiles"]
    if compiles_in_window:
        problems.append(f"{compiles_in_window} compilations after warm-up")
    if stats["has_work"]:
        problems.append("engine still has work 30 s after the last request")
    elif stats["kv"]["blocks_in_use"] != \
            stats["prefix_cache"]["cached_blocks"]:
        problems.append(f"blocks leaked at idle: {stats['kv']} vs "
                        f"{stats['prefix_cache']}")

    # Engine-side phases of the window's requests, matched by prompt.
    by_key = {r["key"]: r for r in engine_reqs}
    queue_s, overhead_ms = [], []
    for key, ttft_ms in quiet["ttft_from_send_ms"].items():
        e = by_key.get(key)
        if e is None or e["first_token_at"] is None:
            continue
        queue_s.append(e["admitted_at"] - e["submitted_at"])
        overhead_ms.append(
            ttft_ms - (e["first_token_at"] - e["submitted_at"]) * 1e3)
    first_tokens_in_trace = 0
    if traced:
        first_tokens_in_trace = sum(
            1 for e in engine_reqs if e["first_token_at"] is not None
            and traced["t0"] <= e["first_token_at"] <= traced["t1"])
    gaps, ttft = client["gaps_ms"], client["ttft_ms"]
    end_to_end = {"serve_out_tok_s": client["tokens_in_window"] / ctx.seconds}
    if gaps:
        end_to_end["itl_p90_ms"] = loadgen.percentile(gaps, 90)
    tails = {}
    if quiet["gaps_ms"]:
        tails["itl_p99_ms"] = loadgen.percentile(quiet["gaps_ms"], 99)
    if quiet["ttft_ms"]:
        tails["ttft_p50_ms"] = loadgen.percentile(quiet["ttft_ms"], 50)
        tails["ttft_p90_ms"] = loadgen.percentile(quiet["ttft_ms"], 90)
    late = client["late_ms"]
    ctx.emit(builder="llama_serve", loop=traffic["loop"],
             rate_rps=None if closed else (ctx.rate or traffic["rate_rps"]),
             attempted=client["attempted"], failed=client["failed"],
             cut_at_window_end=client["cut_at_window_end"],
             lead_in_requests=client["lead_in_requests"],
             open_at_window_end=client["open_at_window_end"],
             ttft_half_median_ms=client["ttft_half_median_ms"],
             tokens_in_window=client["tokens_in_window"],
             itl_samples=len(gaps), ttft_samples=len(ttft),
             itl_p50_ms=loadgen.percentile(gaps, 50) if gaps else None,
             itl_p99_ms=loadgen.percentile(gaps, 99) if gaps else None,
             ttft_p90_ms=loadgen.percentile(ttft, 90) if ttft else None,
             itl_top_ms=[round(g, 1) for g in sorted(gaps)[-40:]],
             itl_hist_25ms=loadgen.histogram(gaps, 25.0),
             ttft_sorted_ms=[round(t) for t in sorted(ttft)],
             ttft_p50_ms=loadgen.percentile(ttft, 50) if ttft else None,
             generator_late_ms_max=max(late) if late else None,
             generator_late_ms_median=statistics.median(late) if late
             else None,
             reference=reference, compiles_in_window=compiles_in_window,
             engine_stats={k: v for k, v in stats.items()
                           if k not in ("spans",)},
             spans={**spans, **stats["spans"]})
    return {
        "device": {"platform": stats["platform"],
                   "kind": stats["device_kind"],
                   "count": stats["n_devices"],
                   "memory_peak_bytes": stats["memory_peak_bytes"]},
        "attempted": client["attempted"], "failed": client["failed"],
        "problems": problems,
        "setup_end": t_zero,
        "spans": {**spans, **stats["spans"]},
        "counters": {
            "batch_slots": stats["batch_slots"],
            "tokens_emitted_in_trace": traced["tokens_emitted"]
            if traced else None,
            "first_tokens_in_trace": first_tokens_in_trace,
            "cache_hits": stats["jax"]["hits"],
            "cache_misses": stats["jax"]["misses"],
            "preemptions": stats["preemptions"],
        },
        "client": {"queue_ms": statistics.median(queue_s) * 1e3
                   if queue_s else None,
                   "overhead_ms": statistics.median(overhead_ms)
                   if overhead_ms else None, **tails},
        "end_to_end": end_to_end,
        "trace": traced["digest"] if traced else None,
    }
