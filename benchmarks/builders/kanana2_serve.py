"""Builder `kanana2_serve`: a `deepseek_v3` configuration (here Kanana-2
30B-A3B's widths) served through `serve.run` of a deployment that
subclasses `LLMServer`'s class (by way of `llama_serve`'s, whose benchmark
reads it inherits) and differs only in handing `InferenceEngine` a
`DeepseekV3` and its seeded parameters.

WHAT `--seed` MOVES, AND WHAT IT DOES NOT (PR 59). `--seed` draws every
weight but the routers', the 32 documents, the questions' ids and the
check's five requests. It does NOT draw the expert layers' `router` and
`router_bias`: those come from the configuration's `router_seed` folded
with the layer's index (`pin_router`), as the requests' SHAPES come from the
traffic's `shape_seed`. Since PR 51 a served step reads only the experts
that drew a row, so its time follows the router: ~1% of `serve_out_tok_s`
for each expert more that a layer's decode step draws, 62.9-67.3 a layer a
step over some thirty seeds' routers, which spread six seeds by 1.4-3.1%
against a bound of 1% while a seed run again repeated to 0.04-0.10%
(PERF.md sections 2 and 7). The reference is handed the served parameters,
so it sees the same router, and the check compares what it compared.

Requests go over HTTP through the proxy, streamed. What `llama_serve.run`
does after the warm-up (the mix, the trace, the verdict on the window) is
repeated here because that function cannot be handed another deployment,
another check or a pool with documents without an edit (PERF.md, Open
questions).

THE TRAFFIC (`benchmarks/traffic/docqa_8k.json`, made here: the generator
that is there draws the questions, this file the documents). `documents`
documents of `document_len` ids are drawn from the run's seed; request j,
in the order clients take them, is document j mod `documents` followed by
the j-th question of `loadgen.closed_pool`. During SET-UP, before the
lead-in and inside `setup_s`, first `fillers` throwaway documents and then
every document are sent once through the same HTTP path with ONE new token,
so that their blocks are donated to the radix cache. The fillers are as
many as fill the arena's spare blocks: from then on no block is handed out
that another sequence has not left (the oldest filler, a cold leaf, is
evicted for it), in the check and in the window alike, and the window
starts in the state it ends in.

THE CHECK (it is also the warm-up: it compiles prefill and decode) runs
through the timed programs at the timed sizes, after the documents are
cached. Five seeded requests, four of them in flight together:

- `short`: a prompt of 114, 16 new tokens; sent first, so that it decodes
  while `long`'s and `adopter`'s chunks run;
- `leaver`: a prompt of 126, 4 new tokens: it leaves early;
- `long`: a prompt of 626 nobody has cached: three prefill chunks over a
  growing prefix of its own while other rows decode;
- `adopter`: document 0 and a question of 242: it ADOPTS 8,192 cached
  tokens (64 blocks), prefills only its question and decodes 16 tokens at
  positions 8,434..;
- `reuser`: a prompt of 114, sent when `leaver`'s answer has returned, into
  blocks others left (above).

Every prompt ends 14 rows short of a block's end (`leaver`'s two), so that
the rows its DECODE steps write, all but the last, lie in a whole block:
whole blocks are donated when a request finishes and found again through
the radix cache, which is how the check reads what the timed programs
left in the cache, prefill chunks' rows and decode steps' rows apart.

Each is held to `benchmarks/reference/deepseek_v3_plain.py` (the EXPANDED
form, no cache, float32 at the highest precision) by six limits in three
groups, each with its reason; a run is `correct` only inside all
(`benchmarks/kanana2_controls.py` makes the faults; PERF.md section 6).

The readings (my chip runs, PR 50). First session: the system over 49 runs
of the cell and two of the controls, 12 seeds, 5 requests each, at 64, 48
and 32 slots; each control twice (seeds 2860486313 at 64 slots and
1779033703). Second session, with the prompts' lengths above and the
routing read from the record: 26 runs of the cell and 12 of the controls'
`system`, 19 seeds, and the routing's controls once (seed 2971215073),
through these same functions: largest gap 0.38-1.83, mean 0.018-0.069,
rows 0.00235-0.00240.

LOGIT_MARGIN and LOGIT_MEAN_MARGIN: each served greedy token's
float32-reference logit lies within LOGIT_MARGIN of the reference's
maximum at its position, and the check's 68 tokens within
LOGIT_MEAN_MARGIN on average. Logits, not token equality
(`llama_serve.LOGIT_MARGIN`'s argument: with seeded weights the top two are
close and the argmax flips on rounding). TWO numbers where the dense cells
have one, because an expert layer is discontinuous: bf16 activations move
the sixth and seventh score + bias of 3.3-3.9% of the tokens past each
other in the FIRST expert layer (more below it), a token routed otherwise
than the reference routes it swaps an expert's output (a tenth of the
residual's size) for another's, and the served argmax over 128,256 rows
then sits up to 1.06 under the reference's best where a dense model's sat
0.14 (`llama_serve`): 11 to 16 tokens in 16 are still the reference's own
choice. The system's largest single gap is 0.31 to 1.06 a run on all seeds
but one, which read 1.83 (seed 668265263: one token of the adopter's 16, 14
of them the reference's own), and its mean over the 68 tokens 0.012 to
0.069 (one request of 4 tokens read 0.41 on its own: a mean of four is no
mean). The MEAN is what refuses a dropped or misplaced TERM: no
`routed_scaling_factor` reads 0.33-0.39, an un-normed latent 0.31-0.33, an
un-rotated rope key 1.14-1.15 (largest gaps 1.14-1.81, 1.19-1.24,
2.73-2.92); its limit lies 2.2 times above the system's largest and 2.1
times below the least of them. An 8-bit cache (0.68-1.24, 0.08-0.11) passes
both and is refused by LATENT_LIMIT. The LARGEST gap is heavy-tailed (a
routing flip deep in the stack under a near-tie of the top logits) and is
the backstop against ONE wrong token, which a mean of 68 would dilute: a
token chosen at random reads 3.92-4.18 under the reference's best (each
request's `arbitrary_gap`: the logits spread 0.9 and the best of 128,256
lies four of them up). Its limit, first placed at 2.0 on readings up to
1.06, is 3.0 since the 1.83: 1.64 times above the largest reading, 1.3
times below an arbitrary token's. (At 8k positions of seeded weights
attention averages thousands of values and is a few percent of the
residual: the logits see the expert layer and the dense products, which is
why the cache has a limit of its own.)

LATENT_LIMIT, on the FIRST layer's cached rows: every request's whole
blocks are found again through the radix cache it donated them to, and the
arena's rows `[c | k_r]` are compared with the reference's, the latent and
the rope key each as ||served - ref|| / ||ref|| over all of the request's
rows; the larger counts, and the padding lanes must be zero. The first
layer's rows are made of the embedding through one norm and one product, so
the bf16 path puts both at 0.00234 to 0.00240 whatever the seed or the
request; a cache kept in 8 bits reads 0.0269-0.0273, an un-normed latent
0.103, an un-rotated key 1.13: the limit lies 1.7 times above the one and
6.7 times below the least of the others.

ROUTE_MISMATCH_LIMIT, DECODE_MISMATCH_LIMIT and GATE_LIMIT, on the FIRST
expert layer's routing AS THE TIMED PROGRAMS MADE IT: every step writes
what it handed the experts (the chosen experts after the idle rows' mask,
and their gates) into the cache's routing record at the token's own cache
location (`models/deepseek_v3.py`), and the check reads the record of every
request's whole blocks beside their latent rows, each token's through the
program that routed it: the tokens of prompts (and of the document, routed
in set-up) by the one compiled `prefill_fn`, the fed-back tokens by the one
compiled `decode_fn`. For each program, pooled over the check's tokens: the
share whose chosen SET differs from the reference's, and the root mean
square of (gate_served - gate_ref) / gate_ref over the tokens whose sets
agree. Prefill routes ~9,400 of the check's tokens and decode 58, so the
share has two limits: the chunks' is placed between the readings below; the
decode steps' is there for a fault of the decode program alone (the mask
clobbering the indices, another row's routing), which reads 1.0, and its
PRECISION is held by the gates' limit, which 58 x 6 gates read to a few
percent of themselves. The readings through the record (second session):
the system 0.0338-0.0407 of the chunks' 9,414 tokens and 1 to 6 of the
decode steps' 58 (0.017-0.103), gates 0.00099-0.00115 and 0.00100-0.00132
(the float32 router's only error is its bf16 input); a router whose logits, scores, bias sum and
gates are bfloat16 0.078 and 0.103, gates 0.00325 and 0.00334; the same in
DECODE STEPS ALONE leaves the chunks' readings the system's and reads
0.00347 on the decode steps' gates, which alone refuses it; the bias added
to the gates 0.088 and 0.083 on the gates; no `routed_scaling_factor` 0.59
on both. The chunks' share and the gates' limit each lie 1.3 to 1.9 times
above the system's largest reading and 1.4 to 1.5 times below the bf16
router's. (The first session read the routing through a whole-sequence
program of the check's own, which held neither compiled program: 0.0327 to
0.0393 and 0.0011 to 0.00145 over 49 runs, a bf16 router 0.070-0.078 and
0.00335-0.00345: the same places.)
"""

from __future__ import annotations

import asyncio
import importlib.util
import statistics
import threading
import time
from typing import Any, Dict, List

from ray_tpu import serve

from benchmarks.builders.llama_serve import (TRACED_SECONDS, _BenchLLM, _call,
                                             _wait_idle)

# Asked here, in the parent process and before a cluster is started: a
# checkout whose program lacks the model (the commit before PR 50) fails
# at once, not in a replica's constructor after a deployment's timeout.
if importlib.util.find_spec("ray_tpu.models.deepseek_v3") is None:
    raise ImportError("this checkout's program has no "
                      "ray_tpu.models.deepseek_v3: nothing to measure")

from benchmarks.builders.falcon_h1_serve import init_params  # noqa: E402

LOGIT_MARGIN = 3.0
LOGIT_MEAN_MARGIN = 0.15
LATENT_LIMIT = 0.004
ROUTE_MISMATCH_LIMIT = 0.055
DECODE_MISMATCH_LIMIT = 0.2
GATE_LIMIT = 0.0022
CHECK = {"question": 242, "long_prompt": 626, "short_prompt": 114,
         "new_tokens": 16}
LEAVER_NEW = 4
SETUP_ABREAST = 4       # documents in flight at once during set-up
COUNTER_LEAD_S = 3.0    # the first read of the counters, before the window

MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "intermediate_size", "first_k_dense_replace",
    "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    "n_shared_experts", "routed_scaling_factor", "scoring_func",
    "norm_topk_prob", "n_group", "topk_group", "rope_scaling",
    "max_position_embeddings", "rms_norm_eps", "rope_theta", "param_dtype")


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.deepseek_v3 import DeepseekV3Config

    return DeepseekV3Config.from_published(
        cfg, dtype=jnp.dtype(cfg["param_dtype"]))


def pin_router(params, router_seed: int):
    """`params` with every expert layer's `router` and `router_bias` drawn
    anew from `router_seed` folded with the layer's index: what
    `DeepseekV3.init` gives them (normal of std 0.02 through float32 into
    the leaf's dtype; normal of std `BIAS_STD` in float32) at the leaf's
    shape, dtype and placement, in one jitted call on the device."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.deepseek_v3 import BIAS_STD

    held = {i: (lp["router"], lp["router_bias"])
            for i, lp in enumerate(params["layers"]) if "router" in lp}

    def draw():
        out = {}
        for i, (w, bias) in held.items():
            kw, kb = jax.random.split(jax.random.fold_in(
                jax.random.PRNGKey(int(router_seed)), i))
            out[i] = (
                (jax.random.normal(kw, w.shape, jnp.float32)
                 * 0.02).astype(w.dtype),
                jax.random.normal(kb, bias.shape, jnp.float32) * BIAS_STD)
        return out

    # A leaf that `init` placed keeps its placement; one it left to the
    # default device stays UNCOMMITTED like its neighbours (a committed
    # input commits a program's outputs, and the arenas that come back
    # committed are another program to `jax.jit`: a second compile).
    drawn = jax.block_until_ready(jax.tree.map(
        lambda new, leaf: jax.device_put(new, leaf.sharding)
        if leaf.committed else new, jax.jit(draw)(), held))
    return {**params, "layers": [
        {**lp, "router": drawn[i][0], "router_bias": drawn[i][1]}
        if i in drawn else lp for i, lp in enumerate(params["layers"])]}


def seeded_params(model, seed: int, router_seed: int):
    """The cell's weights: `--seed`'s, with the configuration's router
    (module docstring). The controls make theirs here too."""
    return pin_router(init_params(model, seed), router_seed)


# --------------------------------------------------------------------------- #
# the traffic
# --------------------------------------------------------------------------- #


def filler_count(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> int:
    """Throwaway documents that fill the arena's spare blocks (block 0 is
    the trash block)."""
    engine = cfg["engine"]
    per_doc = int(traffic["document_len"]) // int(engine["block_size"])
    spare = int(engine["num_blocks"]) - 1 \
        - int(traffic["documents"]) * per_doc
    if spare < 0:
        raise ValueError("the arena does not hold the documents")
    return -(-spare // per_doc)


def documents(traffic: Dict[str, Any], seed: int, vocab: int,
              extra: int = 0) -> List[List[int]]:
    """`documents` + `extra` documents of `document_len` ids from the seed
    (the extra ones last)."""
    import numpy as np

    rng = np.random.default_rng([int(seed), 0xD0C5])
    n, length = int(traffic["documents"]) + extra, \
        int(traffic["document_len"])
    return [[int(t) for t in rng.integers(1, vocab, length)]
            for _ in range(n)]


def docqa_pool(traffic: Dict[str, Any], seed: int, vocab: int,
               docs: List[List[int]]) -> List[Dict[str, Any]]:
    """The closed-loop pool: the generator's questions, each behind its
    document (request j asks document j mod len(docs))."""
    from benchmarks import loadgen

    pool = []
    for j, q in enumerate(loadgen.closed_pool(traffic, seed, vocab)):
        doc = docs[j % len(docs)]
        pool.append({**q, "question_len": q["prompt_len"],
                     "prompt_len": len(doc) + q["prompt_len"],
                     "ids": doc + q["ids"]})
    return pool


def check_requests(cfg: Dict[str, Any], seed: int, doc: List[int]
                   ) -> Dict[str, Dict]:
    """The check's five requests, from the seed (module docstring)."""
    import numpy as np

    sizes = {**CHECK, **(cfg.get("check") or {})}
    rng = np.random.default_rng(seed)
    vocab = int(cfg["vocab_size"])
    short, new = sizes["short_prompt"], sizes["new_tokens"]
    shapes = {"short": (short, new),
              "leaver": (short + new - LEAVER_NEW, LEAVER_NEW),
              "long": (sizes["long_prompt"], new),
              "adopter": (sizes["question"], new), "reuser": (short, new)}
    out = {}
    for i, (who, (n, k)) in enumerate(shapes.items()):
        ids = [int(t) for t in rng.integers(1, vocab, n)]
        if who == "adopter":
            ids = list(doc) + ids
        out[who] = {"idx": i, "prompt_len": len(ids), "max_new_tokens": k,
                    "ids": ids}
    return out


async def _send_documents(url: str, docs: List[List[int]]) -> List[Dict]:
    """Every document once, one new token, `SETUP_ABREAST` in flight, in
    order (the first sent is the first donated, hence the coldest)."""
    import aiohttp

    from benchmarks import loadgen

    reqs = [{"idx": i, "prompt_len": len(d), "max_new_tokens": 1, "ids": d}
            for i, d in enumerate(docs)]
    recs = [loadgen._new_record(r, None) for r in reqs]
    gate = asyncio.Semaphore(SETUP_ABREAST)
    timeout = aiohttp.ClientTimeout(total=None, sock_read=900.0)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        async def one(req, rec):
            async with gate:
                await loadgen._stream_one(s, url, req, rec)

        tasks = []
        for req, rec in zip(reqs, recs):
            tasks.append(asyncio.ensure_future(one(req, rec)))
            await asyncio.sleep(0.01)              # arrive in this order
        await asyncio.gather(*tasks)
    return recs


async def _check_wave(url: str, reqs: Dict[str, Dict]) -> Dict[str, Dict]:
    """`short`, `leaver`, `long` and `adopter` together; `reuser` when
    `leaver`'s answer has returned."""
    import aiohttp

    from benchmarks import loadgen

    recs = {who: loadgen._new_record(r, None) for who, r in reqs.items()}
    timeout = aiohttp.ClientTimeout(total=None, sock_read=900.0)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        tasks = {}
        for who in ("short", "leaver", "long", "adopter"):
            tasks[who] = asyncio.ensure_future(
                loadgen._stream_one(s, url, reqs[who], recs[who]))
            await asyncio.sleep(0.05)      # arrive in this order
        await tasks["leaver"]
        await loadgen._stream_one(s, url, reqs["reuser"], recs["reuser"])
        await asyncio.gather(*tasks.values())
    return recs


# --------------------------------------------------------------------------- #
# the check against the reference
# --------------------------------------------------------------------------- #


def _relative(have, want) -> float:
    import jax.numpy as jnp

    return float(jnp.sqrt(jnp.sum(jnp.square(have - want))
                          / jnp.sum(jnp.square(want))))


def cached_rows(engine, ids: List[int]):
    """What the cache holds of the whole blocks of `ids` that the radix
    cache finds: (the FIRST layer's arena rows float32 [n, width], the
    routing record's columns float32 [n, 2k]), n a multiple of the block;
    or None where it finds none."""
    import jax.numpy as jnp

    with engine._lock:
        blocks, _ = engine._prefix.match(list(ids))
        if not blocks:
            return None
        blocks = jnp.asarray(blocks, jnp.int32)
        arena = engine._arenas["latent"][0]
        record = engine._arenas["routing"]
        rows = arena[blocks].reshape(-1, arena.shape[-1])
        routing = record.reshape(record.shape[0], *arena.shape[:2])[
            :, blocks].reshape(record.shape[0], -1).T
    return rows.astype(jnp.float32), routing


def routing_errors(routing, experts, gates, k: int) -> Dict[str, Any]:
    """A group of tokens' routing record [n, 2k] against the reference's
    chosen experts and gates [n, k] (sorted by expert): how many tokens,
    how many chose another SET, and over the others' gates (how many) the
    sum of squares of the relative error."""
    import jax.numpy as jnp

    order = jnp.argsort(routing[:, :k], axis=-1)
    index = jnp.take_along_axis(routing[:, :k], order, -1)
    same = jnp.all(index == experts.astype(jnp.float32), axis=-1)
    rel = (jnp.take_along_axis(routing[:, k:], order, -1) - gates) / gates
    return {"tokens": int(routing.shape[0]),
            "mismatched": int(jnp.sum(~same)),
            "gates": int(jnp.sum(same)) * k,
            "gate_sq": float(jnp.sum(jnp.where(same[:, None],
                                               jnp.square(rel), 0.0)))}


def reference_check(engine, model_cfg: Dict[str, Any],
                    served: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Each served request against the plain float32 forward of the
    engine's parameters, in the process that holds them, a tensor upcast
    at a time: the chosen tokens' logit gaps, and what the timed programs
    left in the cache of the request's whole blocks: the first layer's
    rows and the first expert layer's routing, the prompt's tokens
    (`prefill_fn`) and the fed-back tokens (`decode_fn`) apart."""
    import jax.numpy as jnp

    from benchmarks.reference import deepseek_v3_plain as plain
    from ray_tpu.models.deepseek_v3 import published_weights

    mc = engine._model.config
    top, layer = published_weights(mc, engine._params)
    lat, row, k = mc.kv_lora_rank, mc.latent_row, mc.num_experts_per_tok
    out = []
    for item in served:
        prompt, generated = item["prompt"], item["generated"]
        stream = prompt + generated[:-1]
        ids = jnp.asarray([stream], jnp.int32)
        at = range(len(prompt) - 1, len(prompt) - 1 + len(generated))
        logits, taps = plain.forward(top, layer, ids, model_cfg,
                                     positions=list(at), with_taps=True)
        gaps = plain.chosen_token_gaps(logits[0], generated)
        res = {"who": item["who"], "max_gap": float(jnp.max(gaps)),
               "mean_gap": float(jnp.mean(gaps)),
               "exact": int(jnp.sum(gaps == 0)), "tokens": len(generated),
               # what a token chosen at random would read (no limit: the
               # scale LOGIT_MARGIN is placed against)
               "arbitrary_gap": float(jnp.mean(
                   jnp.max(logits[0], -1) - jnp.median(logits[0], -1))),
               "latent_rows": 0}
        held = cached_rows(engine, stream)
        if held is not None:
            have, routing = held
            n = int(have.shape[0])
            want = taps["latent_rows"][0, :n]
            res["latent_rows"] = n
            res["latent_err"] = _relative(have[:, :lat], want[:, :lat])
            res["rope_key_err"] = _relative(have[:, lat:row], want[:, lat:])
            res["pad_lanes_max"] = float(jnp.max(jnp.abs(have[:, row:]))) \
                if have.shape[1] > row else 0.0
            cut = min(len(prompt), n)
            res["routing"] = {
                program: routing_errors(
                    routing[rows], taps["experts"][0, rows],
                    taps["gates"][0, rows], k)
                for program, rows in (("prefill", slice(0, cut)),
                                      ("decode", slice(cut, n)))}
        out.append(res)
        del logits, taps, held
    return out


def routing_readings(reference: List[Dict[str, Any]]) -> Dict[str, Dict]:
    """The check's routing, pooled a program: tokens, the share that chose
    another set than the reference, the gates' relative error (rms)."""
    out = {}
    for program in ("prefill", "decode"):
        parts = [r["routing"][program] for r in reference if "routing" in r]
        tokens = sum(p["tokens"] for p in parts)
        gates = sum(p["gates"] for p in parts)
        out[program] = {
            "tokens": tokens,
            "mismatch": sum(p["mismatched"] for p in parts) / tokens
            if tokens else None,
            "gate_err": (sum(p["gate_sq"] for p in parts) / gates) ** 0.5
            if gates else None}
    return out


def check_problems(reference: List[Dict[str, Any]]) -> List[str]:
    problems = []
    worst = max(r["max_gap"] for r in reference)
    if not worst <= LOGIT_MARGIN:
        problems.append(f"a served token lies {worst} under the plain "
                        f"reference's best logit (> {LOGIT_MARGIN})")
    mean = sum(r["mean_gap"] * r["tokens"] for r in reference) \
        / sum(r["tokens"] for r in reference)
    if not mean <= LOGIT_MEAN_MARGIN:
        problems.append(f"the served tokens lie {mean} on average under "
                        f"the plain reference's best logit "
                        f"(> {LOGIT_MEAN_MARGIN})")
    by_who = {r["who"]: r for r in reference}
    for who in ("adopter", "long"):
        if not by_who[who]["latent_rows"]:
            problems.append(f"{who}'s blocks are not in the radix cache: "
                            f"no cached row was compared")
    rows = max(max(r.get("latent_err", 0.0), r.get("rope_key_err", 0.0))
               for r in reference)
    if not rows <= LATENT_LIMIT:
        problems.append(f"the first layer's cached rows lie {rows} "
                        f"(relative) from the plain reference's "
                        f"(> {LATENT_LIMIT})")
    if any(r.get("pad_lanes_max", 0.0) for r in reference):
        problems.append("the cached rows' padding lanes are not zero")
    limits = {"prefill": ROUTE_MISMATCH_LIMIT,
              "decode": DECODE_MISMATCH_LIMIT}
    for program, read in routing_readings(reference).items():
        if not read["tokens"] or read["gate_err"] is None:
            problems.append(f"no routing of a {program} step was compared: "
                            f"{read}")
            continue
        if not read["mismatch"] <= limits[program]:
            problems.append(
                f"{read['mismatch']} of the {read['tokens']} tokens that "
                f"{program} steps routed chose other experts than the plain "
                f"reference (> {limits[program]})")
        if not read["gate_err"] <= GATE_LIMIT:
            problems.append(
                f"the first expert layer's gates in {program} steps lie "
                f"{read['gate_err']} (rms, relative) from the plain "
                f"reference's (> {GATE_LIMIT})")
    return problems


def path_problems(stats: Dict[str, Any], rehearsal: bool) -> List[str]:
    """A call off the kernel path is not `correct`."""
    calls = stats["latent_attn"]
    problems = [f"latent attention {r['pass']} ran the {r['path']}: "
                f"{r['reason']}" for r in calls if r["path"] != "pallas"]
    if len({r["pass"] for r in calls}) < 2:
        problems.append(f"latent kernels not both traced: {calls}")
    paths = ("pallas", "interpret") if rehearsal else ("pallas",)
    problems += [f"held experts ran the {r['path']} path"
                 for r in stats["held_experts"] if r["path"] not in paths]
    if not stats["held_experts"]:
        problems.append("no expert layer was traced")
    problems += [f"paged attention of {prog}: {path}"
                 for prog, path in stats["paged_attn"].items()
                 if path != "pallas"]
    return problems


def cache_problems(stats: Dict[str, Any], cfg: Dict[str, Any],
                   traffic: Dict[str, Any]) -> List[str]:
    """Every document is still resident, and nothing is kept per slot."""
    problems = []
    per_doc = int(traffic["document_len"]) // int(cfg["engine"]["block_size"])
    held = stats["prefix_cache"]["cached_blocks"]
    if held < int(traffic["documents"]) * per_doc:
        problems.append(f"the radix cache holds {held} blocks, fewer than "
                        f"the documents'")
    if stats["state"]["slots"]:
        problems.append(f"state per slot: {stats['state']}")
    return problems


def device_counters(engine) -> Dict[str, Any]:
    """`stats()["moe"]` as of this instant: a copy of the model's device
    counters of the benchmark's own, waited for (`stats()` itself hands a
    serving caller the copy an earlier call left, and never waits)."""
    import jax
    import jax.numpy as jnp

    with engine._lock:
        copy = jax.tree.map(jnp.copy,
                            engine._model.cache_counters(engine._arenas))
    return engine._model.counter_stats(jax.device_get(copy))


class _BenchKanana2(_BenchLLM):
    """`LLMServer` with a `DeepseekV3` handed in. Everything a request
    touches is inherited from `LLMServer`'s class, and the benchmark's
    reads from `llama_serve._BenchLLM`."""

    def __init__(self, model_cfg: Dict[str, Any],
                 engine_cfg: Dict[str, Any], seed: int, router_seed: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.inference.engine import (EngineConfig, EngineLoop,
                                              InferenceEngine)
        from ray_tpu.models.deepseek_v3 import DeepseekV3

        from benchmarks import jaxwatch

        self._seen = jaxwatch.watch()
        self._spans = {"ctor_first_line": time.monotonic()}
        self._adapter_specs = {}
        self._default_new = 16
        self._config = EngineConfig(**engine_cfg)
        self._model_cfg = model_cfg
        model = DeepseekV3(model_config(model_cfg))
        t0 = time.monotonic()
        params = seeded_params(model, seed, router_seed)
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
        import jax

        from ray_tpu.ops.held_experts import held_experts_status
        from ray_tpu.ops.latent_attention import latent_attention_status

        # Asked BEFORE the snapshot: an engine that was idle then is idle
        # in it (a request that finishes between the snapshot and a later
        # question would read as blocks held by an idle engine).
        busy = self._engine.has_work()
        stats = {**self._engine.stats(), **device_counters(self._engine)}
        mem = [d.memory_stats() or {} for d in jax.local_devices()]
        stats["memory_peak_bytes"] = max(
            (m.get("peak_bytes_in_use", 0) for m in mem), default=0)
        stats["jax"] = dict(self._seen)
        stats["spans"] = dict(self._spans)
        stats["has_work"] = busy or self._engine.has_work()
        stats["latent_attn"] = latent_attention_status()
        stats["held_experts"] = held_experts_status()
        params = jax.tree.leaves(self._engine._params)
        stats["weight_bytes"] = sum(leaf.nbytes for leaf in params)
        return stats

    def bench_counters(self, _=None) -> Dict[str, Any]:
        """What the program counts, and nothing that asks the device's
        runtime a question (`bench_stats` reads its memory)."""
        stats = {**self._engine.stats(), **device_counters(self._engine)}
        return {k: stats.get(k) for k in ("moe", "prefix_cache", "steps")}

    def bench_forget_requests(self, _=None) -> int:
        """Drop the records of set-up's requests (8,192 ids each)."""
        n = len(self._requests)
        del self._requests[:]
        return n

    def bench_reference(self, served: List[Dict[str, Any]]
                        ) -> List[Dict[str, Any]]:
        return reference_check(self._engine, self._model_cfg, served)


def _deployment(rehearsal: bool):
    return serve.deployment(
        _BenchKanana2, name="BenchKanana2", max_concurrent_queries=256,
        route_prefix="/",
        ray_actor_options={} if rehearsal else {"num_tpus": 1})


def window_counters(before: Dict[str, Any], after: Dict[str, Any],
                    admitted: List[Dict[str, int]]) -> Dict[str, Any]:
    """What the program counted between two reads of `bench_stats`: the
    prefix cache's lookups and the expert layers' loads, and over the
    requests ADMITTED between them (`admitted`: each one's prompt length
    and the engine's count of its adopted tokens) the share adopted."""
    out: Dict[str, Any] = {}
    pa, pb = after.get("prefix_cache") or {}, before.get("prefix_cache") or {}
    if "hit_tokens" in pa and "lookups" in pa:
        out["prefix"] = {
            "lookups": pa["lookups"] - pb.get("lookups", 0),
            "hits": pa["hits"] - pb.get("hits", 0),
            "lookup_hit_tokens": pa["hit_tokens"] - pb.get("hit_tokens", 0),
            "requests": len(admitted),
            "hit_tokens": sum(r["cached_tokens"] for r in admitted),
            "prompt_tokens": sum(r["prompt_len"] for r in admitted)}
    ma, mb = after.get("moe"), before.get("moe")
    if ma and mb:
        moe = {"layers": ma["layers"], "experts": ma["experts"]}
        for kind in ("decode", "prefill"):
            d = {k: ma[kind][k] - mb[kind][k]
                 for k in ("steps", "assigned", "placed", "drew",
                           "max_over_mean")}
            calls = max(1, d["steps"] * ma["layers"])
            moe[kind] = {**d,
                         "assignments_per_step": d["assigned"] / calls,
                         "experts_drawn_per_step": d["drew"] / calls,
                         "load_max_over_mean": d["max_over_mean"] / calls}
        out["moe"] = moe
    return out


def stalls(records: List[Dict[str, Any]], t_zero: float, seconds: float,
           over_ms: float = 120.0, top: int = 12) -> List[List[float]]:
    """The moments of the window at which token gaps over `over_ms` ended,
    pooled over the streams in bins of 50 ms: [[seconds into the window,
    streams that waited, their median gap in ms], ...], the `top` bins
    with the most streams. A host pause shows as every slot's stream
    waiting at the same moment; a late delivery as a few."""
    bins: Dict[int, List[float]] = {}
    for rec in records:
        times = rec["token_times"]
        for a, b in zip(times, times[1:]):
            if (b - a) * 1e3 > over_ms and t_zero <= b < t_zero + seconds:
                bins.setdefault(int((b - t_zero) / 0.05), []).append(
                    (b - a) * 1e3)
    worst = sorted(bins.items(), key=lambda kv: -len(kv[1]))[:top]
    return [[round(k * 0.05, 2), len(v), round(statistics.median(v), 1)]
            for k, v in sorted(worst)]


def run(ctx) -> Dict[str, Any]:
    """Parent side: deploy, cache the documents, warm up and check, offer
    the mix, verdict."""
    from benchmarks import loadgen

    cfg, traffic = ctx.config, ctx.traffic
    engine_cfg = dict(cfg["engine"])
    vocab = int(cfg["vocab_size"])
    model_cfg = {k: cfg[k] for k in MODEL_KEYS}
    if traffic["loop"] != "closed":
        raise ValueError("kanana2_serve offers closed-loop mixes only")
    spans = {"serve_run_called": time.monotonic()}
    handle = serve.run(_deployment(ctx.rehearsal).bind(
        model_cfg, engine_cfg, ctx.seed, int(cfg["router_seed"])),
        timeout_s=900.0)
    spans["serve_run_returned"] = time.monotonic()
    url = f"http://127.0.0.1:{serve.http_port()}/"

    # Set-up: the fillers, then the documents, once each (module
    # docstring). The first of them compiles prefill.
    fillers = filler_count(cfg, traffic)
    docs = documents(traffic, ctx.seed, vocab, extra=fillers)
    n_docs = int(traffic["documents"])
    t0 = time.monotonic()
    sent = asyncio.run(_send_documents(url, docs[n_docs:] + docs[:n_docs]))
    spans["documents_s"] = time.monotonic() - t0
    problems = [f"document {r['idx']} failed: {r['error']}"
                for r in sent if r["error"]][:5]
    docs = docs[:n_docs]
    _call(handle, "bench_forget_requests", None)

    # Warm-up = the check (module docstring).
    check = check_requests(cfg, ctx.seed, docs[0])
    t0 = time.monotonic()
    warm = asyncio.run(_check_wave(url, check))
    spans["compile_s"] = spans["documents_s"] + time.monotonic() - t0
    problems += [f"warm-up request {who} failed: {r['error']}"
                 for who, r in warm.items() if r["error"]]
    reference = []
    if not problems:
        _wait_idle(handle)
        t0 = time.monotonic()
        reference = _call(handle, "bench_reference", [
            {"who": who, "prompt": check[who]["ids"],
             "generated": warm[who]["tokens"]}
            for who in check], timeout=1800.0)
        spans["reference_check_s"] = time.monotonic() - t0
        problems += check_problems(reference)
    after_warm = _call(handle, "bench_stats", None)
    adopted = {e["cached_tokens"] for e in _call(handle, "bench_requests",
                                                 None)
               if e["key"] == loadgen.prompt_key(check["adopter"]["ids"])}
    if adopted != {len(docs[0])}:
        problems.append(f"the adopter adopted {sorted(adopted)} cached "
                        f"tokens, want {len(docs[0])}")

    # The mix.
    lead_s = float(traffic.get("lead_s", 0.0))
    pool = docqa_pool(traffic, ctx.seed, vocab, docs)
    t_zero = time.monotonic() + lead_s + 0.2
    spans["first_timed_request"] = t_zero
    tracer = None
    if ctx.trace:
        def trace_middle():
            start = t_zero + max(0.0, (ctx.seconds - TRACED_SECONDS) / 2)
            time.sleep(max(0.0, start - time.monotonic()))
            _call(handle, "bench_trace_start", ctx.out_dir)
            time.sleep(min(TRACED_SECONDS, ctx.seconds))
            tracer.result = _call(handle, "bench_trace_stop", None)

        tracer = threading.Thread(target=trace_middle, daemon=True)
        tracer.result = None
        tracer.start()
    # The program's counters over the window: read COUNTER_LEAD_S before
    # it starts, from another thread, and again when it is over. Not at
    # its start: a call into the replica costs the engine 0.1 to 1 s of
    # its stepping (my chip runs, PR 50: every slot's stream waited 136 ms
    # at the first read in three runs and 995 ms in a fourth), which
    # inside the window is 0.3 to 2.5% of `serve_out_tok_s`.
    at_zero: Dict[str, Any] = {}

    def read_at_zero():
        time.sleep(max(0.0, t_zero - COUNTER_LEAD_S - time.monotonic()))
        at_zero.update(_call(handle, "bench_counters", None))

    reader = threading.Thread(target=read_at_zero, daemon=True)
    reader.start()
    records = loadgen.run_closed_loop(url, pool, int(traffic["clients"]),
                                      t_zero, ctx.seconds)
    at_end = _call(handle, "bench_counters", None)
    reader.join(timeout=60.0)
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
    # Prefill WORK is a request's question: its document is adopted.
    questions = {r["idx"]: pool[r["idx"] % len(pool)]["question_len"]
                 for r in records}
    prefilled = sum(questions[r["idx"]] for r in records if r["token_times"]
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
    elif stats["kv"]["blocks_in_use"] != \
            stats["prefix_cache"]["cached_blocks"]:
        problems.append(f"blocks leaked at idle: {stats['kv']} vs "
                        f"{stats['prefix_cache']}")
    problems += path_problems(stats, ctx.rehearsal) \
        + cache_problems(stats, cfg, traffic)

    # The window's admissions, by the engine's own record of them.
    window = {}
    if at_zero:
        sent = {r["key"]: r["prompt_len"] for r in records}
        window = window_counters(at_zero, at_end, [
            {"prompt_len": sent[e["key"]],
             "cached_tokens": e["cached_tokens"]} for e in engine_reqs
            if e["admitted_at"] is not None and e["key"] in sent
            and t_zero <= e["admitted_at"] < t_zero + ctx.seconds])
    first_tokens_in_trace = 0
    if traced:
        first_tokens_in_trace = sum(
            1 for e in engine_reqs if e["first_token_at"] is not None
            and traced["t0"] <= e["first_token_at"] <= traced["t1"])
    gaps, ttft = client["gaps_ms"], client["ttft_ms"]
    # Where a slow run lost its time: the engine's host phases over the
    # window, and the moments at which many streams waited long at once.
    window_steps = {}
    if at_zero:
        a, b = at_zero["steps"], at_end["steps"]
        window_steps = {
            # (a decode execution that carried a chunk counts in `decode`
            # AND in `chunks_aboard`)
            **{k: b[k] - a[k] for k in ("n", "decode", "prefill",
                                        "chunks_aboard", "wall_s",
                                        "wait_work_s")},
            "phase_s": {k: round(b["phase_s"][k] - a["phase_s"][k], 4)
                        for k in b["phase_s"]}}
    ctx.emit(builder="kanana2_serve", loop=traffic["loop"],
             window_steps=window_steps,
             stalls=stalls(records, t_zero, ctx.seconds),
             attempted=client["attempted"], failed=client["failed"],
             cut_at_window_end=client["cut_at_window_end"],
             open_at_window_end=client["open_at_window_end"],
             tokens_in_window=client["tokens_in_window"],
             itl_samples=len(gaps), ttft_samples=len(ttft),
             itl_p50_ms=loadgen.percentile(gaps, 50) if gaps else None,
             itl_p99_ms=loadgen.percentile(gaps, 99) if gaps else None,
             ttft_p50_ms=statistics.median(ttft) if ttft else None,
             reference=reference,
             routing=routing_readings(reference) if reference else None,
             compiles_in_window=compiles_in_window,
             fillers=fillers, window=window,
             weight_bytes=stats["weight_bytes"],
             arena_bytes=stats["kv"]["bytes"],
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
            "prefill_steps": stats["steps"]["prefill"],
            "decode_steps": stats["steps"]["decode"],
            "weight_bytes": stats["weight_bytes"],
            "arena_bytes": stats["kv"]["bytes"],
            "mean_context": statistics.mean(
                r["prompt_len"] + r["max_new_tokens"] / 2
                for r in records) if records else None,
            **{f"window_{k}": v for k, v in window.items()},
            "window_steps": window_steps,
        },
        "client": {"out_tok_s": quiet["tokens_in_window"] / quiet_s,
                   "prefill_tok_s": prefilled / quiet_s},
        "end_to_end": {
            "serve_out_tok_s": client["tokens_in_window"] / ctx.seconds},
        "trace": traced["digest"] if traced else None,
    }
