"""Builder `sdar_serve`: SDAR at its published widths, depth 8, served
through `serve.run` of a deployment that subclasses `LLMServer`'s class (by
way of `llama_serve`'s, whose benchmark reads it inherits) and differs only
in handing `InferenceEngine` an `SDAR` and its seeded parameters. The model
answers the contract's `decode_block`, so the engine decodes by BLOCKS of
four positions (docs/INFERENCE.md finding (i)); nothing here runs a pass.

Requests go over HTTP through the proxy, streamed. What `ouro_serve.run`
does after the warm-up (the mix, the trace, the verdict on the window) is
repeated here because that function cannot be handed another deployment or
another check without an edit (PERF.md, Open questions).

THE CHECK (it is also the warm-up: it compiles the prefill and the block
program) runs through the timed programs at the timed sizes with every slot
live. Five requests are held to the reference: `short` (a prompt of whole
blocks), `leaver` (it leaves its slot and pages after two blocks), `mid`
(a tail of 1), `long` (a tail of 2) and `reuser` (a tail of 3), admitted
when `leaver` has returned, into the slot and the pages it left; fillers
keep every other slot in a block.

Seeded weights make the confidences of a block's positions nearly equal, so
WHICH position a pass commits flips on rounding: the comparison is
TEACHER-FORCED. The engine keeps, for the five, every pass's buffer as it
entered and as it left (`add_request(record_passes=True)`); the reference
(`benchmarks/reference/sdar_plain.py`: a dense mask over the whole
sequence, a loop over the experts, float32 at `highest`, no cache) runs a
full forward pass over the sequence so far with the SAME buffer, every pass
a row of one batch. A run is `correct` only inside every limit:

The readings behind each limit are the chip's (my chip runs, PR 62: the
cell's own runs on 15 seeds, 78 committed tokens and 656 cached tokens a
check, and `benchmarks/sdar_controls.py` on seed 2971215073; PERF.md
section 6). The logits' scale: a token chosen at random reads 3.7-3.9 under
the reference's best.

(i) LOGIT_MARGIN, LOGIT_MEAN_MARGIN: each committed token's
float32-reference logit lies within a margin of the reference's maximum at
that position OF THAT BUFFER, and so does the mean of those gaps. 73 to 78
of a check's 78 tokens are the reference's own choice; the system's largest
gap reads 0.0000 to 0.0586 and its mean 0.00000 to 0.00165. Plain causal
attention inside a block reads 0.483 / 0.209, logits read shifted by one
0.271 / 0.0257 (a masked position's neighbour is as often a committed one,
whose logits are another token's), top-7 of 8 0.091 / 0.0044, an 8-bit
cache 0.070 / 0.0017, no commit pass 0.067 / 0.0033; norms in bf16 (0.0065 /
0.00012) and a bf16 router (0.0012 / 0.00001) read INSIDE both, as the
system does: other limits refuse them. The margins stand 3.1 and 3.6 times
above the system's largest readings (a gap is a near-tie of the top two of
151,936 logits flipped by the bf16 path, so its tail over seeds is long:
0.0203 was the largest of the first 8 seeds, 0.0586 of 15) and 1.5 and 4.3
times below the shifted logits'.
(ii) SELECT_MARGIN, SELECT_MEAN_MARGIN: each committed POSITION's reference
log-confidence lies within a margin of the largest among the positions
still masked on entry, and so does the mean: the selection rule is held,
not only the forward pass. The system reads 0.0075 to 0.0502 at the largest
and 0.00024 to 0.00201 in the mean (seeded weights make a block's
confidences nearly equal, and which position is best flips on rounding);
the shifted logits 0.176 / 0.0304, causal attention 0.160 / 0.0116, top-7
0.059 / 0.0033, no commit pass 0.057 / 0.0036, an 8-bit cache 0.048 /
0.0043, norms in bf16 0.035 / 0.0020. The limits stand 2.2 and 4.0 times
above the system's largest and 1.5 times below the smaller of the first two
controls'.
(iii) KV_LIMIT_FIRST: the KV distance (the larger of ||K_served - K_ref|| /
||K_ref|| and the same of V over a request's whole cached pages; the
largest of a check's five requests counts) at the FIRST layer, read out of
the arena through the blocks the radix cache holds. Keys and values of
layer 0 are made of the position's own token through one norm, one product,
the q/k norm and the rotary, so the bf16 operands put them at one distance
whatever the seed: 0.002359 to 0.002382 on 15 seeds. A page left over from a
denoise pass (made from `[MASK]`: the control `no_commit_pass`) reads 0.317,
an 8-bit cache 0.0273 (its smallest request 0.0266), norms in bf16, the
nearest precision below the stated one, 0.003507 (its smallest request
0.003418). The limit lies 22% above the one and 15% below the other, and
both repeat to 1%.
(iv) KV_LIMIT_LAST: the same at every kept layer; the last inherits
everything upstream. The system reads 0.0112 to 0.0206; top-7 of 8 0.0392,
an 8-bit cache 0.0532, no commit pass 0.317, causal attention 0.506 (a
bf16 router 0.0165 and norms in bf16 0.0159, inside). The limit stands 1.55
times above the system's largest and 1.2 times below the least of the
faults'.
(v) ROUTE_MISMATCH_LIMIT, GATE_LIMIT: the experts the timed programs routed
the FIRST layer's tokens to, from the routing record every step leaves in
the cache at the token's own location (a denoise pass's is overwritten by
the commit pass's, like its keys and values), against the reference's
router on the same input: the share of the check's 656 cached tokens whose
chosen SET differs, and the root mean square of (gate_served - gate_ref) /
gate_ref over the others. The system reads 0.0320 to 0.0518 and 0.003940 to
0.004110 (the float32 router's only error is its bf16 input, so the gates
repeat to 2%); top-7 of 8 1.0, causal attention 0.613 / 0.0676, an 8-bit
cache 0.282 / 0.0307, no commit pass 0.101 / 0.0102, norms in bf16 0.0579 /
0.005789, a router whose logits, softmax and gates are bfloat16 0.0518 /
0.005279: its sets differ no more often than the system's, and the gates'
limit alone refuses it. The share's limit stands 1.5 times above the
system's largest (4 standard deviations of a count of 656 at 4.5%) and 1.3
times below the least fault's; the gates' 12% above the system's largest
and 13% below the bf16 router's.
"""

from __future__ import annotations

import asyncio
import importlib.util
import statistics
import time
from typing import Any, Dict, List

from ray_tpu import serve

from benchmarks.builders.llama_serve import TRACED_SECONDS, _BenchLLM, _call

# Asked here, in the parent process and before a cluster is started: a
# checkout whose program lacks the model (the commit before PR 62) fails
# at once, not in a replica's constructor after a deployment's timeout.
if importlib.util.find_spec("ray_tpu.models.sdar") is None:
    raise ImportError("this checkout's program has no "
                      "ray_tpu.models.sdar: nothing to measure")

# The wave of the check (everyone but `reuser` together, `reuser` when
# `leaver`'s answer has returned) and the settled read of an idle engine's
# counters are the ninth cell's.
from benchmarks.builders.ouro_serve import (_check_wave,  # noqa: E402
                                            _settled_stats)

# Each limit between the system's largest reading over its seeds and the
# controls' smallest, with room on both sides (module docstring):
LOGIT_MARGIN = 0.18
LOGIT_MEAN_MARGIN = 0.006
SELECT_MARGIN = 0.11
SELECT_MEAN_MARGIN = 0.008
KV_LIMIT_FIRST = 0.0029
KV_LIMIT_LAST = 0.032
ROUTE_MISMATCH_LIMIT = 0.08
GATE_LIMIT = 0.0046

MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_intermediate_size", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "max_position_embeddings",
    "rms_norm_eps", "rope_theta", "block_length", "denoising_steps",
    "remasking_strategy", "confidence_threshold", "mask_token_id",
    "param_dtype")

COMPARED = ("short", "leaver", "mid", "long", "reuser")


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.sdar import SDARConfig

    return SDARConfig.from_published(
        cfg, dtype=jnp.dtype(cfg["param_dtype"]))


def pin_router(params, router_seed: int):
    """`params` with every layer's `router` drawn anew from `router_seed`
    folded with the layer's index: what `SDAR.init` gives it (normal of std
    0.02 through float32 into the leaf's dtype), in one jitted call."""
    import jax
    import jax.numpy as jnp

    held = [lp["router"] for lp in params["layers"]]

    def draw():
        return [(jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(int(router_seed)), i),
            w.shape, jnp.float32) * 0.02).astype(w.dtype)
            for i, w in enumerate(held)]

    # (a leaf `init` left uncommitted stays so: kanana2_serve.pin_router)
    drawn = jax.block_until_ready(jax.tree.map(
        lambda new, leaf: jax.device_put(new, leaf.sharding)
        if leaf.committed else new, jax.jit(draw)(), held))
    return {**params, "layers": [{**lp, "router": w} for lp, w in zip(
        params["layers"], drawn)]}


def seeded_params(model, seed: int, router_seed: int):
    """The cell's weights: `--seed`'s, with the configuration's router. The
    controls make theirs here too."""
    import jax

    params = model.init(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
    return jax.block_until_ready(pin_router(params, router_seed))


def check_requests(cfg: Dict[str, Any], seed: int) -> Dict[str, Dict]:
    """The check's requests, from the seed (module docstring): the four
    that start together, a filler for every other slot, then `reuser`."""
    import numpy as np

    sizes = {"prompt_min": 64, "prompt_max": 254, "new_tokens": 16,
             "filler_new": 24, "leaver_new": 8, **(cfg.get("check") or {})}
    lo, hi, new = (sizes[k] for k in ("prompt_min", "prompt_max",
                                      "new_tokens"))
    length = int(cfg["block_length"])
    lo, hi = lo // length * length, hi // length * length + 2
    rng = np.random.default_rng(seed)
    vocab = int(cfg["vocab_size"])
    fillers = int(cfg["engine"]["batch_slots"]) - 4
    shapes = {"short": (lo, new),                       # whole blocks
              "leaver": (lo + 5 * length, sizes["leaver_new"]),
              "mid": ((lo + hi) // 2 // length * length + 1, new),
              "long": (hi, new),                        # a tail of 2
              **{f"filler{i}": (int(rng.integers(lo, hi + 1)),
                                sizes["filler_new"])
                 for i in range(fillers)},
              "reuser": (lo + 3, new)}                  # a tail of 3
    return {who: {"idx": i, "prompt_len": n, "max_new_tokens": k,
                  "ids": [int(t) for t in rng.integers(1, vocab, n)]}
            for i, (who, (n, k)) in enumerate(shapes.items())}


# --------------------------------------------------------------------------- #
# the comparison
# --------------------------------------------------------------------------- #


def cached_pages(engine, ids: List[int], layers):
    """What the cache holds of the whole blocks of `ids` that the radix
    cache finds: ({layer: (keys, values) float32 [n, kv_heads, d]}, the
    routing record's columns float32 [n, 2k]), numpy, n a multiple of the
    block; or None where it finds none. The gathers run at ONE shape (a
    whole block table, padded with the trash block) whatever the request:
    an eager op of a new shape is a compilation."""
    import jax.numpy as jnp
    import numpy as np

    with engine._lock:
        blocks, _ = engine._prefix.match(list(ids))
        if not blocks:
            return None
        width = engine.config.max_blocks_per_seq
        table = jnp.asarray(list(blocks) + [0] * (width - len(blocks)),
                            jnp.int32)
        kv = engine._arenas["kv"]
        nb, bsz = kv[0][0].shape[:2]
        n = len(blocks) * bsz
        pages = {i: tuple(
            np.asarray(arena[table]).astype(np.float32).reshape(
                width * bsz, *arena.shape[2:])[:n] for arena in kv[i])
            for i in layers}
        record = engine._arenas["routing"]
        routing = np.asarray(record.reshape(record.shape[0], nb, bsz)[
            :, table]).reshape(record.shape[0], -1).T[:n]
    return pages, routing


def _relative(have, want) -> float:
    import numpy as np

    return float(np.sqrt(np.sum(np.square(have - want))
                         / np.sum(np.square(want))))


def routing_errors(routing, experts, gates, k: int) -> Dict[str, Any]:
    """A group of tokens' routing record [n, 2k] against the reference's
    chosen experts and gates [n, k] (sorted by expert): how many tokens,
    how many chose another SET, and over the others' gates (how many) the
    sum of squares of the relative error."""
    import numpy as np

    order = np.argsort(routing[:, :k], axis=-1)
    index = np.take_along_axis(routing[:, :k], order, -1)
    same = np.all(index == experts.astype(np.float32), axis=-1)
    rel = (np.take_along_axis(routing[:, k:], order, -1) - gates) / gates
    return {"tokens": int(routing.shape[0]),
            "mismatched": int(np.sum(~same)),
            "gates": int(np.sum(same)) * k,
            "gate_sq": float(np.sum(np.where(same[:, None],
                                             np.square(rel), 0.0)))}


def kept_layers(cfg: Dict[str, Any]):
    return sorted({0, int(cfg["num_hidden_layers"]) - 1})


def final_sequence(model_cfg: Dict[str, Any], prompt: List[int],
                   pass_log: List[Dict[str, Any]]) -> List[int]:
    """The sequence as the cache holds it when the request has ended, block
    by block: a block's last record is its commit pass (after a preemption
    the later record counts)."""
    length = model_cfg["block_length"]
    final = list(prompt[:len(prompt) // length * length])
    for rec in pass_log:
        if -1 not in rec["left"] and rec["start"] <= len(final):
            final[rec["start"]:] = rec["left"]
    return final


def teacher_forced(top, layer, model_cfg: Dict[str, Any], prompt: List[int],
                   pass_log: List[Dict[str, Any]], rows: int, width: int):
    """One request's passes through the reference, every pass a row of one
    batch (the tokens before its block as they ended, then the buffer it
    ENTERED with, the mask id where it was masked), and after them the
    sequence as the cache holds it; the batch is padded to `rows` x `width`
    (the check's largest: one shape, one compilation of the reference;
    padding lies in later blocks, which no position before it sees).
    Returns (the readings of the denoise passes' commitments, that
    sequence, the reference's extras of its row, numpy)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import sdar_plain as plain

    length, mask_id = model_cfg["block_length"], model_cfg["mask_token_id"]
    final = final_sequence(model_cfg, prompt, pass_log)
    batch = np.zeros((rows, width), np.int32)
    at = np.tile(np.arange(length), (rows, 1))
    for r, rec in enumerate(pass_log):
        fed = [mask_id if v < 0 else v for v in rec["entered"]]
        row = final[:rec["start"]] + fed
        batch[r, :len(row)] = row
        at[r] += rec["start"]
    last = len(pass_log)
    batch[last:, :len(final)] = final
    logits, extra = plain.forward(
        top, layer, jnp.asarray(batch), model_cfg, at=at,
        keep=kept_layers(model_cfg), taps=True)
    x0, conf = plain.confidences(logits)
    logits, x0, conf = (np.asarray(a) for a in (logits, x0, conf))
    gaps, select_gaps, exact, arbitrary = [], [], 0, []
    for r, rec in enumerate(pass_log):
        masked = [p for p in range(length) if rec["entered"][p] < 0]
        for p in masked:
            if rec["left"][p] < 0:
                continue
            token = rec["left"][p]
            gaps.append(float(logits[r, p].max() - logits[r, p, token]))
            exact += token == int(x0[r, p])
            select_gaps.append(float(max(conf[r, q] for q in masked)
                                     - conf[r, p]))
            arbitrary.append(float(logits[r, p].max()
                                   - np.median(logits[r, p])))
    readings = {
        "tokens": len(gaps), "max_gap": max(gaps, default=0.0),
        "mean_gap": statistics.fmean(gaps) if gaps else 0.0,
        "exact": int(exact), "max_select_gap": max(select_gaps, default=0.0),
        "mean_select_gap": statistics.fmean(select_gaps)
        if select_gaps else 0.0,
        # what a token chosen at random would read (no limit: the scale
        # LOGIT_MARGIN is placed against)
        "arbitrary_gap": statistics.fmean(arbitrary) if arbitrary else None,
        "passes": len(pass_log)}
    mine = {"kv": {i: tuple(np.asarray(t)[last] for t in pair)
                   for i, pair in extra["kv"].items()},
            "experts": np.asarray(extra["experts"])[last],
            "gates": np.asarray(extra["gates"])[last]}
    return readings, final, mine


def reference_check(engine, model_cfg: Dict[str, Any],
                    served: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Each served request against the plain float32 forward of the
    engine's parameters, in the process that holds them, a layer and an
    expert upcast at a time (module docstring). `served`: who, prompt,
    generated, pass_log."""
    from ray_tpu.models.sdar import published_weights

    top, layer = published_weights(engine._model.config, engine._params)
    k, length = (int(model_cfg["num_experts_per_tok"]),
                 int(model_cfg["block_length"]))
    rows = max(len(item["pass_log"]) for item in served) + 1
    width = -(-max(len(final_sequence(
        model_cfg, item["prompt"], item["pass_log"]))
        for item in served) // length) * length
    out = []
    for item in served:
        res, final, last = teacher_forced(
            top, layer, model_cfg, item["prompt"], item["pass_log"], rows,
            width)
        res.update(who=item["who"], cached_tokens=0, kv_err={}, routing=None)
        stream = item["prompt"] + item["generated"]
        if final[:len(stream)] != stream:
            res["stream_differs"] = True
        held = cached_pages(engine, stream, kept_layers(model_cfg))
        if held is not None:
            pages, routing = held
            n = int(routing.shape[0])
            res["cached_tokens"] = n
            for i, have in pages.items():
                res["kv_err"][str(i)] = max(
                    _relative(h, w[:n]) for h, w in zip(have, last["kv"][i]))
            res["routing"] = routing_errors(
                routing, last["experts"][:n], last["gates"][:n], k)
        out.append(res)
        del held, last
    return out


def routing_readings(reference: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The check's routing pooled: tokens, the share that chose another
    set, the gates' relative RMS over the others."""
    parts = [r["routing"] for r in reference if r.get("routing")]
    tokens = sum(p["tokens"] for p in parts)
    gates = sum(p["gates"] for p in parts)
    if not tokens:
        return {}
    return {"tokens": tokens,
            "mismatch_share": sum(p["mismatched"] for p in parts) / tokens,
            "gate_rel_rms": (sum(p["gate_sq"] for p in parts) / gates) ** 0.5
            if gates else None}


def check_problems(reference: List[Dict[str, Any]]) -> List[str]:
    problems = []
    tokens = sum(r["tokens"] for r in reference)
    if not tokens:
        return ["no committed token was compared"]
    worst = max(r["max_gap"] for r in reference)
    if not worst <= LOGIT_MARGIN:
        problems.append(f"a committed token lies {worst} under the plain "
                        f"reference's best logit (> {LOGIT_MARGIN})")
    mean = sum(r["mean_gap"] * r["tokens"] for r in reference) / tokens
    if not mean <= LOGIT_MEAN_MARGIN:
        problems.append(f"the committed tokens lie {mean} under the plain "
                        f"reference's best logit on average "
                        f"(> {LOGIT_MEAN_MARGIN})")
    select = max(r["max_select_gap"] for r in reference)
    if not select <= SELECT_MARGIN:
        problems.append(f"a committed position's reference log-confidence "
                        f"lies {select} under the best among the masked "
                        f"(> {SELECT_MARGIN})")
    select = sum(r["mean_select_gap"] * r["tokens"]
                 for r in reference) / tokens
    if not select <= SELECT_MEAN_MARGIN:
        problems.append(f"the committed positions' reference log-confidences "
                        f"lie {select} under the best among the masked on "
                        f"average (> {SELECT_MEAN_MARGIN})")
    if any(r.get("stream_differs") for r in reference):
        problems.append("a request's streamed tokens are not what its "
                        "commit passes left in the buffer")
    unread = [r["who"] for r in reference if not r["kv_err"]]
    if unread:
        problems.append(f"no cached block found for {unread}: their pages "
                        f"were not compared")
    first = [r["kv_err"]["0"] for r in reference if "0" in r["kv_err"]]
    if first and not max(first) <= KV_LIMIT_FIRST:
        problems.append(f"the first layer's cached keys or values read "
                        f"{max(first)} (relative) from the plain "
                        f"reference's (> {KV_LIMIT_FIRST})")
    last = [v for r in reference for v in r["kv_err"].values()]
    if last and not max(last) <= KV_LIMIT_LAST:
        problems.append(f"a kept layer's cached keys or values read "
                        f"{max(last)} (relative) from the plain "
                        f"reference's (> {KV_LIMIT_LAST})")
    routing = routing_readings(reference)
    if not routing:
        problems.append("no routing was compared")
    else:
        if not routing["mismatch_share"] <= ROUTE_MISMATCH_LIMIT:
            problems.append(
                f"{routing['mismatch_share']} of the cached tokens were "
                f"routed to another set of experts than the reference's "
                f"router gives (> {ROUTE_MISMATCH_LIMIT})")
        if routing["gate_rel_rms"] is None \
                or not routing["gate_rel_rms"] <= GATE_LIMIT:
            problems.append(
                f"the gates read {routing['gate_rel_rms']} (relative RMS) "
                f"from the reference's (> {GATE_LIMIT})")
    return problems


def path_problems(stats: Dict[str, Any], cfg: Dict[str, Any]) -> List[str]:
    """A call off the kernel path is not `correct`, nor one in the other
    tile than its shape's: a block step is 32 query rows a KV head (few
    rows), a chunk 2,048 (many rows)."""
    problems = [f"paged attention {r['pass']} ran the {r['path']}: "
                f"{r['reason']}" for r in stats["pallas"]
                if r["path"] != "pallas"]
    for program, tile in (("decode", "few rows"), ("prefill", "many rows")):
        if stats["paged_attn"][program] != "pallas":
            problems.append(f"the {program} program's paged attention took "
                            f"{stats['paged_attn'][program]!r}")
        elif not stats["paged_attn_tile"][program].startswith(tile):
            problems.append(f"the {program} program's paged attention ran "
                            f"{stats['paged_attn_tile'][program]!r}, want "
                            f"the {tile} tile")
    layers = int(cfg["num_hidden_layers"])
    calls = sum(r["calls"] for r in stats["pallas"])
    if calls != 2 * layers:
        problems.append(f"{calls} traced paged-attention calls, want "
                        f"{2 * layers} (one a layer a program)")
    return problems


def cache_problems(stats: Dict[str, Any], cfg: Dict[str, Any]) -> List[str]:
    """The cache holds one set of keys and values a token a layer, the
    routing record and the counters, and nothing a slot."""
    from benchmarks import peaks_sdar

    itemsize = 2 if cfg["param_dtype"] == "bfloat16" else 4
    engine = cfg["engine"]
    tokens = int(engine["num_blocks"]) * int(engine["block_size"])
    want = tokens * (peaks_sdar.kv_bytes_per_token(cfg, itemsize)
                     + 2 * int(cfg["num_experts_per_tok"]) * 4)
    problems = []
    if not want <= stats["kv"]["bytes"] <= want + 1024:
        problems.append(f"the cache holds {stats['kv']['bytes']} B, want "
                        f"{want} B of pages and record, and the counters")
    if stats["state"]["slots"]:
        problems.append(f"the cache has per-slot state: {stats['state']}")
    return problems


class _BenchSDAR(_BenchLLM):
    """`LLMServer` with an `SDAR` handed in. Everything a request touches
    is inherited from `LLMServer`'s class, and the benchmark's reads from
    `llama_serve._BenchLLM`."""

    def __init__(self, model_cfg: Dict[str, Any],
                 engine_cfg: Dict[str, Any], seed: int, router_seed: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.inference.engine import (EngineConfig, EngineLoop,
                                              InferenceEngine)
        from ray_tpu.models.sdar import SDAR

        from benchmarks import jaxwatch

        self._seen = jaxwatch.watch()
        self._spans = {"ctor_first_line": time.monotonic()}
        self._adapter_specs = {}
        self._default_new = 16
        self._config = EngineConfig(**engine_cfg)
        self._model_cfg = model_cfg
        model = SDAR(model_config(model_cfg))
        t0 = time.monotonic()
        params = seeded_params(model, seed, router_seed)
        self._spans["init_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        self._engine = InferenceEngine(self._config, model=model,
                                       params=params)
        self._spans["engine_ctor_s"] = time.monotonic() - t0
        self._loop = EngineLoop(self._engine)
        self._requests: List[Any] = []
        # While the check runs every request keeps its passes' buffers.
        self._record_passes = False
        submit = self._loop.submit

        def recording_submit(*args, **kwargs):
            req = submit(*args, record_passes=self._record_passes, **kwargs)
            self._requests.append(req)
            return req

        self._loop.submit = recording_submit
        self._marker = jax.jit(lambda x: x + 1)
        self._mark = jnp.zeros((), jnp.int32)
        self._marker(self._mark).block_until_ready()
        self._trace_dir = None
        self._trace_t0 = None

    def bench_record_passes(self, on: bool) -> bool:
        self._record_passes = bool(on)
        return self._record_passes

    def bench_stats(self, _=None) -> Dict[str, Any]:
        from ray_tpu.ops.attention import pallas_status
        from ray_tpu.ops.held_experts import held_experts_status

        stats = super().bench_stats()
        stats["pallas"] = [r for r in pallas_status()
                           if r["pass"].startswith("paged_")]
        stats["held_experts"] = held_experts_status()
        return stats

    def bench_reference(self, served: List[Dict[str, Any]]
                        ) -> List[Dict[str, Any]]:
        from benchmarks.loadgen import prompt_key

        logs = {prompt_key(r.prompt): r for r in self._requests
                if r.pass_log is not None}
        for item in served:
            req = logs[prompt_key(item["prompt"])]
            item["pass_log"] = list(req.pass_log)
        return reference_check(self._engine, self._model_cfg, served)


def _deployment(rehearsal: bool):
    return serve.deployment(
        _BenchSDAR, name="BenchSDAR", max_concurrent_queries=128,
        route_prefix="/",
        ray_actor_options={} if rehearsal else {"num_tpus": 1})


def _contexts(records, t0: float, t1: float) -> List[int]:
    """Of every token that arrived in [t0, t1), the tokens before it: its
    prompt and what was generated before it."""
    return [r["prompt_len"] + j for r in records
            for j, t in enumerate(r["token_times"]) if t0 <= t < t1]


def _window(now: Dict[str, Any], then: Dict[str, Any]) -> Dict[str, Any]:
    """The counters' differences between two `bench_stats`: the step
    ledger, `stats()["diffusion"]` and `stats()["moe"]` a kind."""
    steps = {k: now["steps"][k] - then["steps"][k]
             for k in ("decode", "prefill", "decode_rows", "decode_ahead",
                       "dropped_rows")}
    book, book0 = now.get("diffusion") or {}, then.get("diffusion") or {}
    diffusion = {k: book[k] - book0.get(k, 0) for k in (
        "blocks_committed", "denoise_passes", "commit_passes",
        "tokens_committed", "given_tokens", "truncated_tokens")
        if k in book}
    if "committed_hist" in book:
        diffusion["committed_hist"] = [
            a - b for a, b in zip(book["committed_hist"], book0.get(
                "committed_hist", [0] * len(book["committed_hist"])))]
    moe, moe0 = now.get("moe") or {}, then.get("moe") or {}
    kinds = {}
    for kind in ("decode", "prefill"):
        if kind not in moe:
            continue
        was = moe0.get(kind) or {}
        d = {k: moe[kind][k] - was.get(k, 0) for k in (
            "steps", "assigned", "tiles", "drew", "max_load")}
        calls = max(1, d["steps"] * moe["layers"])
        kinds[kind] = {**d, "assignments_per_step": d["assigned"] / calls,
                       "experts_drawn_per_step": d["drew"] / calls,
                       "max_load_per_step": d["max_load"] / calls}
    return {"steps": steps, "diffusion": diffusion,
            "moe": {"layers": moe.get("layers"), **kinds} if kinds else None}


def run(ctx) -> Dict[str, Any]:
    """Parent side: deploy, warm up and check, offer the mix, verdict."""
    from benchmarks import loadgen

    cfg, traffic = ctx.config, ctx.traffic
    engine_cfg = dict(cfg["engine"])
    vocab = int(cfg["vocab_size"])
    model_cfg = {k: cfg[k] for k in MODEL_KEYS}
    for key in ("block_length", "denoising_steps", "remasking_strategy"):
        if traffic[key] != cfg[key]:
            raise ValueError(f"the traffic's {key} {traffic[key]!r} is not "
                             f"the configuration's {cfg[key]!r}")
    spans = {"serve_run_called": time.monotonic()}
    handle = serve.run(_deployment(ctx.rehearsal).bind(
        model_cfg, engine_cfg, ctx.seed, int(cfg["router_seed"])),
        timeout_s=900.0)
    spans["serve_run_returned"] = time.monotonic()
    url = f"http://127.0.0.1:{serve.http_port()}/"

    # Warm-up = the check (module docstring).
    check = check_requests(cfg, ctx.seed)
    _call(handle, "bench_record_passes", True)
    t0 = time.monotonic()
    warm = asyncio.run(_check_wave(url, check))
    spans["compile_s"] = time.monotonic() - t0
    _call(handle, "bench_record_passes", False)
    problems = [f"warm-up request {who} failed: {r['error']}"
                for who, r in warm.items() if r["error"]]
    reference = []
    if not problems:
        t0 = time.monotonic()
        reference = _call(handle, "bench_reference", [
            {"who": who, "prompt": check[who]["ids"],
             "generated": warm[who]["tokens"]}
            for who in COMPARED], timeout=1800.0)
        spans["reference_check_s"] = time.monotonic() - t0
        problems += check_problems(reference)
    after_warm = _settled_stats(handle)

    # The mix: closed loop.
    if traffic["loop"] != "closed":
        raise ValueError("sdar_serve offers closed-loop mixes only")
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
    stats = _settled_stats(handle)
    traced = None
    if tracer is not None:
        tracer.join(timeout=600.0)
        traced = tracer.result
        if traced is not None:
            traced["digest"] = _call(handle, "bench_trace_digest",
                                     ctx.keep_trace_sample, timeout=600.0)
    client = loadgen.reduce_records(records, t_zero, ctx.seconds)
    # Starting and stopping the profiler stalls the replica for seconds:
    # in a traced run the rates a utilisation is made of are taken over
    # the part of the window before it starts.
    quiet_s = ctx.seconds if not ctx.trace else max(
        1.0, (ctx.seconds - TRACED_SECONDS) / 2)
    quiet = client if not ctx.trace else loadgen.reduce_records(
        records, t_zero, quiet_s)
    first = [r for r in records if r["token_times"]
             and t_zero <= r["token_times"][0] < t_zero + quiet_s]
    contexts = _contexts(records, t_zero, t_zero + quiet_s)
    in_trace = _contexts(records, traced["t0"], traced["t1"]) \
        if traced else []

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
    problems += path_problems(stats, cfg) + cache_problems(stats, cfg)
    if "diffusion" not in stats:
        problems.append("the engine did not decode by blocks")

    window = _window(stats, after_warm)
    steps = window["steps"]
    gaps, ttft = client["gaps_ms"], client["ttft_ms"]
    ctx.emit(builder="sdar_serve", loop=traffic["loop"],
             attempted=client["attempted"], failed=client["failed"],
             cut_at_window_end=client["cut_at_window_end"],
             open_at_window_end=client["open_at_window_end"],
             tokens_in_window=client["tokens_in_window"],
             itl_samples=len(gaps), ttft_samples=len(ttft),
             itl_p50_ms=loadgen.percentile(gaps, 50) if gaps else None,
             itl_p99_ms=loadgen.percentile(gaps, 99) if gaps else None,
             ttft_p50_ms=statistics.median(ttft) if ttft else None,
             reference=reference, routing=routing_readings(reference),
             window=window, compiles_in_window=compiles_in_window,
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
            # no token comes from a prefill chunk: every one from a block
            "first_tokens_in_trace": 0,
            "cache_hits": stats["jax"]["hits"],
            "cache_misses": stats["jax"]["misses"],
            "preemptions": stats["preemptions"],
            "prefill_steps": steps["prefill"],
            "decode_steps": steps["decode"],
            "rows_per_decode_step": steps["decode_rows"] / steps["decode"]
            if steps["decode"] else None,
            # since the warm-up: the lead-in, the window and the drain
            "diffusion": window["diffusion"], "moe": window["moe"],
            "kv": stats["kv"], "prefix_cache": stats["prefix_cache"],
        },
        "client": {"out_tok_s": quiet["tokens_in_window"] / quiet_s,
                   "prefill_tok_s": sum(r["prompt_len"] for r in first)
                   / quiet_s,
                   "requests_s": len(first) / quiet_s,
                   "mean_prompt": statistics.fmean(
                       r["prompt_len"] for r in first) if first else None,
                   "mean_context": statistics.fmean(contexts)
                   if contexts else None,
                   # the traced interval's own (the replica's clock and the
                   # client's are one machine's monotonic clock)
                   "traced_decoded": len(in_trace),
                   "traced_context_sum": sum(in_trace)},
        "end_to_end": {
            "serve_out_tok_s": client["tokens_in_window"] / ctx.seconds},
        "trace": traced["digest"] if traced else None,
    }
