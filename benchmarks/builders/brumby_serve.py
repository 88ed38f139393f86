"""Builder `brumby_serve`: Brumby at its published widths served through
`serve.run` of a deployment that subclasses `LLMServer`'s class (by way of
`llama_serve`'s, whose benchmark reads it inherits) and differs only in
handing `InferenceEngine` a `Brumby` and its seeded parameters.

Requests go over HTTP through the proxy, streamed. What `falcon_h1_serve.run`
does after the warm-up (the mix, the trace, the verdict on the window) is
repeated here because that function cannot be handed another deployment or
another check without an edit (PERF.md, Open questions); the check's four
requests, their wave and the seeded parameters ARE that builder's.

THE CHECK (it is also the warm-up: it compiles prefill and decode) runs
through the timed programs at the timed sizes: `falcon_h1_serve`'s four
seeded requests, three of them in flight together. `short` (48 + 16) decodes
while `long`'s chunks run; `leaver` (60 + 4) leaves its slot early; `long`
(640 + 16) is three prefill chunks with the state carried across them while
other rows decode (a row between two of its chunks is a masked row of those
decode steps); `reuser` (48 + 16) is admitted into the slot `leaver` left,
whose state it must not inherit.

The reference (`benchmarks/reference/brumby_plain.py`) is the QUADRATIC
form and has no state. So that a served STATE can be held to it, it also
answers what PROBES extra query vectors (seeded, standard normal) at a
request's last position would read before the division, per layer and KV
head: sum_u exp(c_T - c_u) (p . k_u)^2 / d v_u, and the same without v.
The system's state answers the same question through its feature map
(`ops/power_retention.phi`, here, in float32 at the highest precision). For
a layer, a request's STATE DISTANCE is the larger of ||read_served -
read_ref|| / ||read_ref|| over the two readings, at the NEAREST slot
(another slot's reads ~1 away); it is taken for `long`, for `reuser`, and
for the better of `short` and `leaver`.

Three limits, each with its reason; a run is `correct` only inside all. The
readings behind them are the chip's (my chip runs, PR 43,
`benchmarks/brumby_controls.py` and the cell's own runs: PERF.md section 6).

LOGIT_MARGIN: each served greedy token's float32-reference logit lies
within this of the reference's maximum at its position. Logits, not token
equality (`llama_serve.LOGIT_MARGIN`'s argument and its value: the logits'
scale is the same ~1.4, and with seeded weights the top two are close and
the argmax flips on rounding). The system's largest gap over 19 seeds is
0.105 (the next 0.089, 0.080, 0.076; 14 tokens in 16 or more are the
reference's own choice); a row not reset reads 4.3, a masked row advanced
7.9 to 9.2.

STATE_LIMIT_FIRST, on the FIRST layer's distance: the limit of precision.
The first layer's state is made of the embedding through one norm, the
three products, the head norms and the rotary, so the bf16 operands of the
path (q, k and v enter the retention in bf16) put it at 0.00397 to 0.00419
whatever the seed or the request (a norm over 64 probes x 8 heads x 128),
and a state CARRIED in bf16 (rounded after every step, float32 arithmetic)
at 0.00596 to 0.00617: the limit lies between, 20% above the one and 16%
below the other. Deeper layers inherit the error of the layers before them
(0.025 to 0.028 at the eighth, 0.026 to 0.031 with a bf16 state) and would
hide it.

STATE_LIMIT, on every layer's: what the state's bookkeeping may not do. A
row not reset at position 0 reads 0.21 at the first layer and 0.73 to 1.0
below it, a masked row advanced 1.6 to 4.5. The system's deepest layer reads
0.023 to 0.034 on 18 seeds of 19 and 0.072 on one, every time (a 64-token request
whose error grew 0.004, 0.013, 0.029 .. 0.072 down the layers where the
others' grows 0.004, 0.010, 0.013 .. 0.027: the bf16 path's error
compounds through eight layers with a heavy tail, on short sequences most),
so the limit stands 3.5 times above that and 4 times below the faults'
smallest deepest-layer reading, 1.0.
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
# checkout whose program lacks the model (the commit before PR 43) fails
# at once, not in a replica's constructor after a deployment's timeout.
if importlib.util.find_spec("ray_tpu.models.brumby") is None:
    raise ImportError("this checkout's program has no "
                      "ray_tpu.models.brumby: nothing to measure")

from benchmarks.builders.falcon_h1_serve import (  # noqa: E402
    _check_wave, check_requests, init_params)

LOGIT_MARGIN = 0.5
STATE_LIMIT_FIRST = 0.005
STATE_LIMIT = 0.25
PROBES = 64

MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size",
    "max_position_embeddings", "rms_norm_eps", "rope_theta",
    "retention_degree", "eps_r", "param_dtype")


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.brumby import BrumbyConfig

    return BrumbyConfig.from_published(
        cfg, dtype=jnp.dtype(cfg["param_dtype"]))


def state_readings(cache, probes):
    """What `probes` [n, d] read of every slot's state, per layer: (with v
    [slots, kv_heads, n, d], without [slots, kv_heads, n])."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.power_retention import phi

    @jax.jit
    def read(state, sums):
        with jax.default_matmul_precision("highest"):
            pp = phi(probes)
            return (jnp.einsum("nta,sjtea->sjne", pp, state),
                    jnp.einsum("nta,sjta->sjn", pp, sums))

    return [read(s, z) for s, z in zip(cache["state"], cache["sums"])]


def reference_check(params, cache, model_cfg: Dict[str, Any],
                    served: List[Dict[str, Any]], seed: int
                    ) -> List[Dict[str, Any]]:
    """Each served request against the plain float32 forward of `params`,
    in the process that holds them, a tensor upcast at a time: the chosen
    tokens' logit gaps, and the distance of what the probes read of every
    layer's final state to the nearest slot's."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import brumby_plain as plain
    from ray_tpu.models.brumby import published_weights

    top, layer = published_weights(params)
    probes = jax.random.normal(
        jax.random.PRNGKey(int(seed) % (2 ** 31 - 1) ^ 0x5EED),
        (PROBES, int(model_cfg["head_dim"])), jnp.float32)
    have = state_readings(cache, probes)

    def distance(want, got):
        """[slots]: ||got[s] - want|| / ||want||."""
        axes = tuple(range(1, got.ndim))
        return jnp.sqrt(jnp.sum(jnp.square(got - want), axis=axes)
                        / jnp.sum(jnp.square(want)))

    out = []
    for item in served:
        prompt, generated = item["prompt"], item["generated"]
        ids = jnp.asarray([prompt + generated[:-1]], jnp.int32)
        at = range(len(prompt) - 1, len(prompt) - 1 + len(generated))
        logits, reads = plain.forward(top, layer, ids, model_cfg,
                                      positions=list(at), probes=probes)
        gaps = plain.chosen_token_gaps(logits[0], generated)
        res = {"who": item["who"], "max_gap": float(jnp.max(gaps)),
               "mean_gap": float(jnp.mean(gaps)),
               "exact": int(jnp.sum(gaps == 0)), "tokens": len(generated)}
        errs = []
        for (want_v, want_z), (have_v, have_z) in zip(reads, have):
            by_slot = distance(want_v, have_v)
            slot = jnp.argmin(by_slot)
            errs.append(float(jnp.maximum(
                by_slot[slot], distance(want_z, have_z)[slot])))
        res["state_err"] = max(errs)
        res["state_err_by_layer"] = errs
        out.append(res)
        del logits, reads
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
            problems.append(f"{what} layer's retention state reads {state} "
                            f"(relative) from the plain reference's "
                            f"(> {limit})")
    return problems


def path_problems(stats: Dict[str, Any]) -> List[str]:
    """A call off the kernel path is not `correct`."""
    calls = stats["retention"]
    problems = [f"retention {r['pass']} ran the {r['path']}: {r['reason']}"
                for r in calls if r["path"] != "pallas"]
    if not {r["pass"] for r in calls} >= {"chunk_fwd", "step"}:
        problems.append(f"retention kernels not both traced: {calls}")
    return problems


def cache_problems(stats: Dict[str, Any], cfg: Dict[str, Any]) -> List[str]:
    """The cache is per-slot state and nothing else."""
    problems = []
    if stats["kv"].get("bytes", 0) or stats["kv"]["num_blocks"]:
        problems.append(f"the cache has a paged part: {stats['kv']}")
    if stats["state"]["slots"] != int(cfg["engine"]["batch_slots"]):
        problems.append(f"state of {stats['state']['slots']} slots, want "
                        f"{cfg['engine']['batch_slots']}")
    return problems


class _BenchBrumby(_BenchLLM):
    """`LLMServer` with a `Brumby` handed in. Everything a request
    touches is inherited from `LLMServer`'s class, and the benchmark's
    reads from `llama_serve._BenchLLM`."""

    def __init__(self, model_cfg: Dict[str, Any],
                 engine_cfg: Dict[str, Any], seed: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.inference.engine import (EngineConfig, EngineLoop,
                                              InferenceEngine)
        from ray_tpu.models.brumby import Brumby

        from benchmarks import jaxwatch

        self._seen = jaxwatch.watch()
        self._spans = {"ctor_first_line": time.monotonic()}
        self._adapter_specs = {}
        self._default_new = 16
        self._config = EngineConfig(**engine_cfg)
        self._model_cfg = model_cfg
        self._seed = seed
        model = Brumby(model_config(model_cfg))
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
        from ray_tpu.ops.power_retention import retention_status

        stats = super().bench_stats()
        stats["retention"] = retention_status()
        return stats

    def bench_reference(self, served: List[Dict[str, Any]]
                        ) -> List[Dict[str, Any]]:
        return reference_check(self._engine._params, self._engine._arenas,
                               self._model_cfg, served, self._seed)


def _deployment(rehearsal: bool):
    return serve.deployment(
        _BenchBrumby, name="BenchBrumby", max_concurrent_queries=256,
        route_prefix="/",
        ray_actor_options={} if rehearsal else {"num_tpus": 1})


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
        raise ValueError("brumby_serve offers closed-loop mixes only")
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
    problems += path_problems(stats) + cache_problems(stats, cfg)

    first_tokens_in_trace = 0
    if traced:
        first_tokens_in_trace = sum(
            1 for e in engine_reqs if e["first_token_at"] is not None
            and traced["t0"] <= e["first_token_at"] <= traced["t1"])
    gaps, ttft = client["gaps_ms"], client["ttft_ms"]
    ctx.emit(builder="brumby_serve", loop=traffic["loop"],
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
