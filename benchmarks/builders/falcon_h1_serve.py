"""Builder `falcon_h1_serve`: Falcon-H1 at its published widths served
through `serve.run` of a deployment that subclasses `LLMServer`'s class (by
way of `llama_serve`'s, whose benchmark reads it inherits) and differs only
in handing `InferenceEngine` a `FalconH1` and its seeded parameters.

Requests go over HTTP through the proxy, streamed. What `llama_serve.run`
does after the warm-up (the mix, the trace, the verdict on the window) is
repeated here because that function cannot be handed another deployment or
another check without an edit (PERF.md, Open questions).

THE CHECK (it is also the warm-up: it compiles prefill and decode) runs
through the timed programs at the timed sizes. Four seeded requests, three
of them in flight together:

- `short`: a prompt of 48, 16 new tokens; sent first, so that it decodes
  while `long`'s chunks run;
- `leaver`: a prompt of 60, 4 new tokens: it leaves its slot early;
- `long`: a prompt of 640, three prefill chunks with the recurrent state
  and the convolution's tail carried across them while other rows decode
  (a row between two of its chunks is a masked row of those decode steps),
  then 16 new tokens;
- `reuser`: a prompt of 48, 16 new tokens, sent when `leaver`'s answer has
  returned: it is admitted into the slot `leaver` left, whose state it must
  not inherit.

Three limits, each with its reason; a run is `correct` only inside all.
The readings behind them are the chip's (my chip runs, PR 41,
`benchmarks/falconh1_controls.py` and the cell's own runs: PERF.md §6).

LOGIT_MARGIN: each served greedy token's float32-reference logit lies within
this of the reference's maximum at its position. Logits, not token equality
(`llama_serve.LOGIT_MARGIN`'s argument: with seeded weights the top two are
close and the argmax flips on rounding). The family's `lm_head_multiplier`
(2^-7) puts the logits' scale at ~0.011 where Mistral's is ~1.3, so the
margin is stated in that scale: the largest gap of the system over 15
seeds is 0.00018 (9 tokens in 10 are the reference's own
choice); a dropped `D x` term reads 0.0012 and 0.0023 (two seeds).

After the check `long` and `reuser` still own the last state of a slot,
and so does `short` or `leaver` (`reuser` took the lowest free slot:
`leaver`'s, or `short`'s if that had finished too by the time it arrived).
For a layer, a request's STATE DISTANCE is ||S_served - S_ref|| / ||S_ref||
between the reference's recurrent state after the request's last processed
token (one token at a time in float32) and the NEAREST slot's state
(another slot's is ~1 away, so the nearest is the request's own); it is
read for `long`, for `reuser`, and for the better of `short` and `leaver`.

STATE_LIMIT_FIRST, on the FIRST layer's distance: the limit of precision.
The first layer's state is made of the embedding through one norm, one
product and the convolution, so the bf16 operands of the path put it at
0.00397 to 0.00419 whatever the seed (45 readings over 15 seeds: a norm
over a million elements), and a state CARRIED in bf16 at 0.00497 to
0.00547 (two seeds): the limit lies between, 10% above the one and 7%
below the other. Deeper layers inherit the error of the layers before
them (0.010 at the sixth, 0.0107 with a bf16 state) and would hide it.

STATE_LIMIT, on every layer's: what the state's bookkeeping may not do. A
row not reset at position 0 reads 0.06 to 0.21, a masked row advanced over
1.6, a dropped `D x` 0.05 to 0.11 from the second layer on; the system
at most 0.0104.
"""

from __future__ import annotations

import asyncio
import importlib.util
import statistics
import time
from typing import Any, Dict, List

from ray_tpu import serve

from benchmarks.builders.llama_serve import (TRACED_SECONDS, _BenchLLM, _call,
                                             _wait_idle)

# Asked here, in the parent process and before a cluster is started: a
# checkout whose program lacks the model (the commit before PR 41) fails
# at once, not in a replica's constructor after a deployment's timeout.
if importlib.util.find_spec("ray_tpu.models.falcon_h1") is None:
    raise ImportError("this checkout's program has no "
                      "ray_tpu.models.falcon_h1: nothing to measure")

LOGIT_MARGIN = 0.0008
STATE_LIMIT_FIRST = 0.0046
STATE_LIMIT = 0.03
CHECK = {"long_prompt": 640, "short_prompt": 48, "new_tokens": 16}
LEAVER_NEW = 4

MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "mamba_d_ssm",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
    "mamba_d_conv", "mamba_chunk_size", "rms_norm_eps", "rope_theta",
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
    "param_dtype")


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.falcon_h1 import FalconH1Config

    return FalconH1Config.from_published(
        cfg, dtype=jnp.dtype(cfg["param_dtype"]))


def init_params(model, seed: int):
    import jax

    params = model.init(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
    jax.block_until_ready(params)
    return params


def check_requests(cfg: Dict[str, Any], seed: int) -> Dict[str, Dict]:
    """The check's four requests, from the seed (module docstring)."""
    import numpy as np

    sizes = {**CHECK, **(cfg.get("check") or {})}
    rng = np.random.default_rng(seed)
    vocab = int(cfg["vocab_size"])
    short, new = sizes["short_prompt"], sizes["new_tokens"]
    shapes = {"short": (short, new),
              "leaver": (short + new - LEAVER_NEW, LEAVER_NEW),
              "long": (sizes["long_prompt"], new), "reuser": (short, new)}
    return {who: {"idx": i, "prompt_len": n, "max_new_tokens": k,
                  "ids": [int(t) for t in rng.integers(1, vocab, n)]}
            for i, (who, (n, k)) in enumerate(shapes.items())}


def reference_check(params, slot_states, model_cfg: Dict[str, Any],
                    served: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Each served request against the plain float32 forward of `params`,
    in the process that holds them, a tensor upcast at a time: the chosen
    tokens' logit gaps, and the distance of every layer's final recurrent
    state to the nearest slot's (`slot_states`: the cache's `ssm`)."""
    import jax.numpy as jnp

    from benchmarks.reference import falcon_h1_plain as plain
    from ray_tpu.models.falcon_h1 import published_weights

    top, layer = published_weights(params)
    out = []
    for item in served:
        prompt, generated = item["prompt"], item["generated"]
        ids = jnp.asarray([prompt + generated[:-1]], jnp.int32)
        at = range(len(prompt) - 1, len(prompt) - 1 + len(generated))
        logits, states = plain.forward(top, layer, ids, model_cfg,
                                       positions=list(at), with_states=True)
        gaps = plain.chosen_token_gaps(logits[0], generated)
        res = {"who": item["who"], "max_gap": float(jnp.max(gaps)),
               "mean_gap": float(jnp.mean(gaps)),
               "exact": int(jnp.sum(gaps == 0)), "tokens": len(generated)}
        errs = []
        for want, have in zip(states, slot_states):
            want = jnp.swapaxes(want[0], -1, -2)         # [heads, N, P]
            dist = jnp.sqrt(jnp.sum(jnp.square(
                have.astype(jnp.float32) - want), axis=(1, 2, 3)))
            errs.append(float(jnp.min(dist) / jnp.sqrt(
                jnp.sum(jnp.square(want)))))
        res["state_err"] = max(errs)
        res["state_err_by_layer"] = errs
        out.append(res)
        del logits, states
    return out


def check_problems(reference: List[Dict[str, Any]]) -> List[str]:
    problems = []
    worst = max(r["max_gap"] for r in reference)
    if not worst <= LOGIT_MARGIN:
        problems.append(f"a served token lies {worst} under the plain "
                        f"reference's best logit (> {LOGIT_MARGIN})")
    by_who = {r["who"]: r["state_err_by_layer"] for r in reference}
    survivor = min(("short", "leaver"), key=lambda who: max(by_who[who]))
    for limit, layers, what in ((STATE_LIMIT_FIRST, slice(0, 1), "first"),
                                (STATE_LIMIT, slice(None), "a")):
        state = max(max(by_who[who][layers])
                    for who in ("long", "reuser", survivor))
        if not state <= limit:
            problems.append(f"{what} layer's recurrent state lies {state} "
                            f"(relative) from the plain reference's "
                            f"(> {limit})")
    return problems


def path_problems(stats: Dict[str, Any], rehearsal: bool) -> List[str]:
    """A call off the kernel path is not `correct`."""
    problems = [f"ssd {r['pass']} ran the {r['path']}: {r['reason']}"
                for r in stats["ssd"] if r["path"] != "pallas"]
    if not {r["pass"] for r in stats["ssd"]} >= {"chunk_fwd", "step"}:
        problems.append(f"ssd kernels not both traced: {stats['ssd']}")
    if not rehearsal:
        problems += [f"paged attention of {prog}: {path}"
                     for prog, path in stats["paged_attn"].items()
                     if path != "pallas"]
    return problems


class _BenchFalconH1(_BenchLLM):
    """`LLMServer` with a `FalconH1` handed in. Everything a request
    touches is inherited from `LLMServer`'s class, and the benchmark's
    reads from `llama_serve._BenchLLM`."""

    def __init__(self, model_cfg: Dict[str, Any],
                 engine_cfg: Dict[str, Any], seed: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.inference.engine import (EngineConfig, EngineLoop,
                                              InferenceEngine)
        from ray_tpu.models.falcon_h1 import FalconH1

        from benchmarks import jaxwatch

        self._seen = jaxwatch.watch()
        self._spans = {"ctor_first_line": time.monotonic()}
        self._adapter_specs = {}
        self._default_new = 16
        self._config = EngineConfig(**engine_cfg)
        self._model_cfg = model_cfg
        model = FalconH1(model_config(model_cfg))
        t0 = time.monotonic()
        params = init_params(model, seed)
        self._spans["init_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        self._engine = InferenceEngine(self._config, model=model,
                                       params=params)
        self._spans["engine_ctor_s"] = time.monotonic() - t0
        self._loop = EngineLoop(self._engine)
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

    def bench_stats(self, _=None) -> Dict[str, Any]:
        from ray_tpu.ops.ssd import ssd_status

        stats = super().bench_stats()
        stats["ssd"] = ssd_status()
        return stats

    def bench_reference(self, served: List[Dict[str, Any]]
                        ) -> List[Dict[str, Any]]:
        return reference_check(self._engine._params,
                               self._engine._arenas["ssm"], self._model_cfg,
                               served)


def _deployment(rehearsal: bool):
    return serve.deployment(
        _BenchFalconH1, name="BenchFalconH1", max_concurrent_queries=256,
        route_prefix="/",
        ray_actor_options={} if rehearsal else {"num_tpus": 1})


async def _check_wave(url: str, reqs: Dict[str, Dict]) -> Dict[str, Dict]:
    """`short`, `leaver` and `long` together; `reuser` when `leaver`'s
    answer has returned."""
    import aiohttp

    from benchmarks import loadgen

    recs = {who: loadgen._new_record(r, None) for who, r in reqs.items()}
    timeout = aiohttp.ClientTimeout(total=None, sock_read=900.0)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        tasks = {}
        for who in ("short", "leaver", "long"):
            tasks[who] = asyncio.ensure_future(
                loadgen._stream_one(s, url, reqs[who], recs[who]))
            await asyncio.sleep(0.05)      # arrive in this order
        await tasks["leaver"]
        await loadgen._stream_one(s, url, reqs["reuser"], recs["reuser"])
        await asyncio.gather(*tasks.values())
    return recs


def run(ctx) -> Dict[str, Any]:
    """Parent side: deploy, warm up and check, offer the mix, verdict."""
    from benchmarks import loadgen

    cfg, traffic = ctx.config, ctx.traffic
    engine_cfg = dict(cfg["engine"])
    vocab = int(cfg["vocab_size"])
    model_cfg = {k: cfg[k] for k in MODEL_KEYS}
    spans = {"serve_run_called": time.monotonic()}
    handle = serve.run(_deployment(ctx.rehearsal).bind(
        model_cfg, engine_cfg, ctx.seed), timeout_s=900.0)
    spans["serve_run_returned"] = time.monotonic()
    url = f"http://127.0.0.1:{serve.http_port()}/"

    # Warm-up = the check (module docstring).
    check = check_requests(cfg, ctx.seed)
    t0 = time.monotonic()
    warm = asyncio.run(_check_wave(url, check))
    spans["compile_s"] = time.monotonic() - t0
    problems = [f"warm-up request {who} failed: {r['error']}"
                for who, r in warm.items() if r["error"]]
    reference = []
    if not problems:
        t0 = time.monotonic()
        reference = _call(handle, "bench_reference", [
            {"who": who, "prompt": check[who]["ids"],
             "generated": warm[who]["tokens"]}
            for who in check], timeout=900.0)
        spans["reference_check_s"] = time.monotonic() - t0
        problems += check_problems(reference)
    after_warm = _call(handle, "bench_stats", None)

    # The mix: closed loop (an open-loop mix for this model waits for the
    # `benchmark` PR of ROADMAP Speed 1).
    if traffic["loop"] != "closed":
        raise ValueError("falcon_h1_serve offers closed-loop mixes only")
    lead_s = float(traffic.get("lead_s", 0.0))
    pool = loadgen.closed_pool(traffic, ctx.seed, vocab)
    t_zero = time.monotonic() + lead_s + 0.2
    spans["first_timed_request"] = t_zero
    tracer = None
    if ctx.trace:
        import threading

        def trace_middle():
            start = t_zero + max(0.0, (ctx.seconds - TRACED_SECONDS) / 2)
            time.sleep(max(0.0, start - time.monotonic()))
            _call(handle, "bench_trace_start", ctx.out_dir)
            time.sleep(min(TRACED_SECONDS, ctx.seconds))
            tracer.result = _call(handle, "bench_trace_stop", None)

        tracer = threading.Thread(target=trace_middle, daemon=True)
        tracer.result = None
        tracer.start()
    records = loadgen.run_closed_loop(url, pool, int(traffic["clients"]),
                                      t_zero, ctx.seconds)
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
    # in a traced run the rates a utilisation is made of are taken over
    # the part of the window before it starts.
    quiet_s = ctx.seconds if not ctx.trace else max(
        1.0, (ctx.seconds - TRACED_SECONDS) / 2)
    quiet = client if not ctx.trace else loadgen.reduce_records(
        records, t_zero, quiet_s)
    prefilled = sum(r["prompt_len"] for r in records if r["token_times"]
                    and t_zero <= r["token_times"][0] < t_zero + quiet_s)

    # Verdict.
    prompts = {r["idx"]: pool[r["idx"] % len(pool)]["ids"] for r in records}
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
    elif stats["kv"]["blocks_in_use"]:
        problems.append(f"blocks leaked at idle: {stats['kv']}")
    problems += path_problems(stats, ctx.rehearsal)

    first_tokens_in_trace = 0
    if traced:
        first_tokens_in_trace = sum(
            1 for e in engine_reqs if e["first_token_at"] is not None
            and traced["t0"] <= e["first_token_at"] <= traced["t1"])
    gaps, ttft = client["gaps_ms"], client["ttft_ms"]
    ctx.emit(builder="falcon_h1_serve", loop=traffic["loop"],
             attempted=client["attempted"], failed=client["failed"],
             cut_at_window_end=client["cut_at_window_end"],
             open_at_window_end=client["open_at_window_end"],
             tokens_in_window=client["tokens_in_window"],
             itl_samples=len(gaps), ttft_samples=len(ttft),
             itl_p50_ms=loadgen.percentile(gaps, 50) if gaps else None,
             itl_p99_ms=loadgen.percentile(gaps, 99) if gaps else None,
             ttft_p50_ms=statistics.median(ttft) if ttft else None,
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
            "state": stats["state"],
            "prefill_steps": stats["steps"]["prefill"],
            "decode_steps": stats["steps"]["decode"],
        },
        "client": {"out_tok_s": quiet["tokens_in_window"] / quiet_s,
                   "prefill_tok_s": prefilled / quiet_s},
        "end_to_end": {
            "serve_out_tok_s": client["tokens_in_window"] / ctx.seconds},
        "trace": traced["digest"] if traced else None,
    }
