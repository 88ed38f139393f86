"""Builder `dots3_serve`: a `dots3_note` configuration (dots3-note-prev's
widths, layers 0-4, 32 of 256 experts held) served through `serve.run` of a
deployment that subclasses `LLMServer`'s class (by way of `kanana2_serve`'s,
whose benchmark reads it inherits) and differs in handing `InferenceEngine`
a `Dots3` and its seeded parameters. The traffic, the set-up and the window
are `kanana2_serve`'s (`docqa_32k.json` has `docqa_8k.json`'s shape:
documents cached in set-up, every windowed request adopts one); what is
this file's own is THE CHECK, the reads of the second pool, and the trace's
reduction by scope.

`--seed` draws every weight but the routers' (`router_seed`: PR 59), the 16
documents, the questions' ids and the check's requests.

THE CHECK (it is also the warm-up: it compiles prefill and decode) runs
through the timed programs at the timed sizes, after the documents are
cached, with EVERY SLOT LIVE: `fillers` requests over the other documents
are sent first and decode all through it. Six HELD requests:

- `short`, `mid`, `long`: document 0 and a question of 113, 177, 241 ids;
  16, 80 and 16 new tokens: each ADOPTS 32,768 cached tokens with the
  window pages of the document's last blocks, prefills one chunk and
  decodes past 32.9k positions, where the selection leaves 94% out;
- `leaver`: document 0 and 125 ids, 4 new tokens: it leaves early;
- `reuser`: document 0 and 113 ids, sent when `leaver`'s answer has
  returned: it takes a slot and pages another sequence left;
- `nodoc`: 625 ids of its own, 16 new tokens: three chunks over a growing
  prefix, a context under the window, then under `index_topk` (everything
  visible is chosen; the window layers release their first page).

Every prompt ends so that the last position its decode steps write closes a
block: whole blocks are donated when a request finishes and found again
through the radix cache, which is how the check reads what the timed
programs left in BOTH pools.

What the timed programs hand the host is a token a step, so the system's
logits, index scores and selections at the held positions (a prompt's last
and every fed-back token's) are read by REPLAYING those positions through
`Dots3.paged_step_tapped`, which is `paged_step` with what it computed on
the way kept as outputs, AT THE TIMED SHAPES over the cache the timed
programs left: a prompt's last chunk as `prefill_fn` ran it ([1,
`prefill_chunk`] ids from the chunk's own start, the same live mask and
`last_idx`: the four query groups under `lax.cond`), the fed-back tokens
`batch_slots` rows at a time as `decode_fn` ran them ([`batch_slots`, 1],
every row live: the s == 1 branch). A replay writes what the timed step
wrote, so the rows of EVERY layer and the routing record are read before
it and compared after it: REPLAY_ROWS_LIMIT holds the replay to what the
timed programs left (layer 1's rows are made of layer 0's selection,
gather and attention; layer 4's of all before it), and the served token is
the replay's argmax. The plain reference
(`benchmarks/reference/dots3_plain.py`, float32 at `highest`) runs the
document ONCE, keeps every layer's input rows, and runs each held request
from the document's end on (a row never depends on a later one), GIVEN the
system's selections at the held positions in both full layers and its own
free selection everywhere else (a record of every cached token's 2,048
choices would be 10 GB). A run is `correct` only inside every limit below;
`benchmarks/dots3_controls.py` makes the faults, and PERF.md section 6 has
each limit's readings: the system's over its seeds and every control's.

(i) LOGIT_REL_LIMIT / LOGIT_REL_MEAN_LIMIT: ||system - reference|| /
    ||reference|| of the logits at a held position, the largest and the
    mean; SERVED_GAP_LIMIT: how far a SERVED token's reference logit lies
    under the reference's best (the timed programs' own output);
    REPLAY_ROWS_LIMIT / the replay's argmax: above.
(ii) INDEX_SCORE_LIMIT: the system's index scores against the reference's
    over a held query's visible keys (relative, both full layers);
    SELECT_OVERLAP_LIMIT: the share of a held query's chosen positions
    that the reference's own selection also holds; SELECT_MARGIN_LIMIT:
    how far under the reference's 2,048th score, in standard deviations of
    the query's visible scores, the reference's score of a position lies
    that the system chose and the reference did not.
(iii) ROWS_LIMIT: the cached rows read back out of the arenas, each as
    ||served - ref|| / ||ref||: `full` rows (latent and rope key) and
    index keys at both full layers, window rows at the last sliding layer
    for the pages still held; the padding lanes zero.
(iv) ROUTE_MISMATCH_LIMIT / GATE_LIMIT: the first expert layer's routing
    record, as the timed programs left it, against the reference's router
    on the same input (the layer's normed input at the held positions,
    which the replay hands out and REPLAY_ROWS_LIMIT ties to the timed
    programs'); kanana2_serve's readings.
(v) the window pool's books (`window_book_problems`): a cached node holds
    at most the rule's tail, an idle engine no page beyond the cached
    ones, the pool's peak at most what the live sequences' rule allows.
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
import importlib.util
import statistics
import threading
import time
from typing import Any, Dict, List

from ray_tpu import serve

from benchmarks.builders.llama_serve import TRACED_SECONDS, _call, _wait_idle

# Asked here, in the parent process and before a cluster is started: a
# checkout whose program lacks the model fails at once.
if importlib.util.find_spec("ray_tpu.models.dots3") is None:
    raise ImportError("this checkout's program has no ray_tpu.models.dots3: "
                      "nothing to measure")

from benchmarks.builders import kanana2_serve as k2  # noqa: E402
from benchmarks.builders.falcon_h1_serve import init_params  # noqa: E402

# Each between the system's largest reading and the least of the controls
# it is there to refuse (PERF.md section 6 has every reading).
LOGIT_REL_LIMIT = 0.15
LOGIT_REL_MEAN_LIMIT = 0.07
SERVED_GAP_LIMIT = 0.6
REPLAY_ROWS_LIMIT = 0.001         # rows after a replay against before it
REPLAY_TOKEN_SHARE = 0.95         # of the served tokens the replay's argmax
INDEX_SCORE_LIMIT = 0.0045        # the FIRST full layer's, pooled
SELECT_OVERLAP_LIMIT = 0.98       # the FIRST full layer's
DEEP_OVERLAP_LIMIT = 0.88         # every full layer's
SELECT_MARGIN_LIMIT = 0.023       # the FIRST full layer's
ROWS_LIMIT = 0.004                # the FIRST layer's rows and index keys
DEEP_ROWS_LIMIT = 0.19            # the later layers' rows
ROUTE_MISMATCH_LIMIT = 0.05
GATE_LIMIT = 0.0005               # a prompt's last token (6 of them)
DECODE_GATE_LIMIT = 0.0005        # the fed-back tokens (142)

# who -> (question or prompt ids, new tokens); `nodoc` has no document.
CHECK = {"short": (113, 16), "mid": (177, 80), "long": (241, 16),
         "leaver": (125, 4), "reuser": (113, 16), "nodoc": (625, 16),
         "fillers": 58, "filler_new": 400}
HELD = ("short", "mid", "long", "leaver", "reuser", "nodoc")
HELD_WIDTH = 80         # held positions a reference call takes (padded to)
SCOPES = ("dsa_index", "dsa_select", "dsa_gather", "dsa_attend",
          "window_attn", "moe_route", "moe_experts", "lm_head")
COUNTER_LEAD_S = k2.COUNTER_LEAD_S

MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
    "index_n_heads", "index_head_dim", "index_topk",
    "swa_num_attention_heads", "swa_q_lora_rank", "swa_kv_lora_rank",
    "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
    "swa_rope_theta", "sliding_window_size", "intermediate_size",
    "first_k_dense_replace", "n_routed_experts", "num_experts_per_tok",
    "moe_intermediate_size", "n_shared_experts", "routed_scaling_factor",
    "scoring_func", "norm_topk_prob", "rope_scaling",
    "attention_gate_type", "swa_attention_gate_type",
    "apply_mla_qkv_lora_rescale", "max_position_embeddings", "rms_norm_eps",
    "param_dtype", "deployment")


def model_config(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.dots3 import Dots3Config

    dep = cfg["deployment"]
    return Dots3Config.from_published(
        cfg, experts_routed=int(dep["experts_routed"]),
        first_expert_held=int(dep.get("first_expert_held", 0)),
        dtype=jnp.dtype(cfg["param_dtype"]))


def seeded_params(model, seed: int, router_seed: int):
    """The cell's weights: `--seed`'s, with the configuration's router
    and selection bias. The controls make theirs here too."""
    return k2.pin_router(init_params(model, seed), router_seed)


# --------------------------------------------------------------------------- #
# the check's traffic
# --------------------------------------------------------------------------- #


def check_requests(cfg: Dict[str, Any], seed: int, docs: List[List[int]]
                   ) -> Dict[str, Dict]:
    """The held requests and the fillers, from the seed (module
    docstring)."""
    import numpy as np

    sizes = {**CHECK, **(cfg.get("check") or {})}
    rng = np.random.default_rng([int(seed), 0xC4EC])
    vocab = int(cfg["vocab_size"])
    out = {}
    for i, who in enumerate(HELD):
        n, new = sizes[who]
        ids = [int(t) for t in rng.integers(1, vocab, n)]
        if who != "nodoc":
            ids = list(docs[0]) + ids
        out[who] = {"idx": i, "prompt_len": len(ids), "max_new_tokens": new,
                    "ids": ids}
    for j in range(int(sizes["fillers"])):
        doc = docs[1 + j % (len(docs) - 1)] if len(docs) > 1 else docs[0]
        ids = list(doc) + [int(t) for t in rng.integers(
            1, vocab, int(rng.integers(8, 1 + sizes["short"][0])))]
        out[f"filler{j}"] = {"idx": len(HELD) + j, "prompt_len": len(ids),
                             "max_new_tokens": int(sizes["filler_new"]),
                             "ids": ids}
    return out


@contextlib.contextmanager
def long_lines():
    """A stream's last line echoes the request's ids: 33,400 of them are
    ~200 KB, over the 128 KB a line of an `aiohttp.ClientSession` may hold
    by default (`ValueError: Chunk too big`, and the request reads as
    failed with every token received). `benchmarks/loadgen.py` makes its
    sessions itself and takes no buffer size (PERF.md section 7), so for
    the time of a call the class it constructs is one with room."""
    import aiohttp

    was = aiohttp.ClientSession
    aiohttp.ClientSession = functools.partial(was, read_bufsize=1 << 22)
    try:
        yield
    finally:
        aiohttp.ClientSession = was


async def _check_wave(url: str, reqs: Dict[str, Dict]) -> Dict[str, Dict]:
    """The fillers, then the held requests but `reuser`, then `reuser` when
    `leaver`'s answer has returned."""
    import aiohttp

    from benchmarks import loadgen

    recs = {who: loadgen._new_record(r, None) for who, r in reqs.items()}
    timeout = aiohttp.ClientTimeout(total=None, sock_read=900.0)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        tasks = {}
        for who in [w for w in reqs if w.startswith("filler")] \
                + [w for w in HELD if w != "reuser"]:
            tasks[who] = asyncio.ensure_future(
                loadgen._stream_one(s, url, reqs[who], recs[who]))
            await asyncio.sleep(0.02)      # arrive in this order
        await tasks["leaver"]
        await loadgen._stream_one(s, url, reqs["reuser"], recs["reuser"])
        await asyncio.gather(*tasks.values())
    return recs


# --------------------------------------------------------------------------- #
# the check against the reference (in the process that holds the chip)
# --------------------------------------------------------------------------- #


def _rel(have, want) -> float:
    import jax.numpy as jnp

    have, want = jnp.asarray(have, jnp.float32), jnp.asarray(want,
                                                             jnp.float32)
    return float(jnp.sqrt(jnp.sum(jnp.square(have - want))
                          / jnp.maximum(jnp.sum(jnp.square(want)), 1e-30)))


def cached_state(engine, ids: List[int]):
    """What both pools hold of the whole blocks of `ids` that the radix
    cache finds: the tables a sequence that adopts them is given, or None.
    {"tokens", "full": [blocks], "window": [a page a block, 0 = none]}. The
    window pages are those of EVERY tail on the path (the request's node's
    and, under it, the document's): a replayed chunk's first queries read
    back into the document's last blocks."""
    with engine._lock:
        blocks, node = engine._prefix.match(list(ids))
        if not blocks:
            return None
        window = [0] * len(blocks)
        while node is not None and node.depth:
            for at, page in enumerate(node.wtail or (),
                                      node.depth - len(node.wtail or ())):
                window[at] = window[at] or page
            node = node.parent
        return {"tokens": len(blocks) * engine.config.block_size,
                "full": list(blocks), "window": window}


def arena_rows(arena, blocks, first_block: int = 0):
    """float32 [n, width] of the pages `blocks[first_block:]`."""
    import jax.numpy as jnp

    got = arena[jnp.asarray(blocks[first_block:], jnp.int32)]
    return got.reshape(-1, arena.shape[-1]).astype(jnp.float32)


def own_rows(engine, state, first_row: int = 0) -> Dict[str, Any]:
    """What the arenas hold of a sequence's rows from `first_row` on (a
    multiple of the block): `latent.<layer>` [n, page width] of every
    layer (a sliding layer's: the pages still held, `window_blocks`) and
    `index.<layer>` of the full ones."""
    mc, bsz = engine._model.config, engine.config.block_size
    cache, fb = engine._arenas, first_row // bsz
    held = [b for b in range(fb, len(state["window"])) if state["window"][b]]
    out: Dict[str, Any] = {"window_blocks": held}
    full_at = 0
    for i, kind in enumerate(mc.kinds):
        if kind == "full_attention":
            out[f"latent.{i}"] = arena_rows(cache["latent"][i],
                                            state["full"], fb)
            out[f"index.{i}"] = arena_rows(cache["index"][full_at],
                                           state["full"], fb)
            full_at += 1
        elif held:
            out[f"latent.{i}"] = arena_rows(
                cache["latent"][i], [state["window"][b] for b in held])
    return out


def rows_readings(engine, rows: Dict[str, Any], taps, n: int,
                  first_row: int = 0) -> Dict[str, Any]:
    """(iii): `own_rows` of a sequence of `n` cached tokens against the
    reference's taps (`rows` {layer: [t, L + r]}, `index_keys` {layer: [t,
    d]}): both full layers' rows and index keys, the LAST sliding layer's
    rows for the pages still held; the padding lanes zero."""
    import jax.numpy as jnp
    import numpy as np

    mc, bsz = engine._model.config, engine.config.block_size
    kinds = mc.kinds
    last_sliding = max(i for i, kind in enumerate(kinds)
                       if kind != "full_attention")
    held = rows["window_blocks"]
    out: Dict[str, Any] = {"rows": n - first_row, "pad_lanes_max": 0.0,
                           "window_pages": len(held)}
    for i, kind in enumerate(kinds):
        have = rows.get(f"latent.{i}")
        if have is None or (kind != "full_attention" and i != last_sliding):
            continue
        g = mc.geometry(kind)
        if kind == "full_attention":
            want, name = taps["rows"][i][first_row:n], ""
            out[f"index_key_err.{i}"] = _rel(
                rows[f"index.{i}"], taps["index_keys"][i][first_row:n])
        else:
            at = np.concatenate([np.arange(b * bsz, (b + 1) * bsz)
                                 for b in held])
            want, name = taps["rows"][i][at], "window_"
        out[f"{name}latent_err.{i}"] = _rel(have[:, :g.kv_rank],
                                            want[:, :g.kv_rank])
        out[f"{name}rope_key_err.{i}"] = _rel(have[:, g.kv_rank:g.row],
                                              want[:, g.kv_rank:])
        out["pad_lanes_max"] = max(out["pad_lanes_max"], float(
            jnp.max(jnp.abs(have[:, g.row:]))))
    return out


def routing_of(engine, state, first_row: int, n: int):
    """The routing record's columns [n - first_row, 2k] of a sequence's
    rows."""
    import jax.numpy as jnp

    bsz = engine.config.block_size
    record = engine._arenas["routing"]
    blocks = jnp.asarray(state["full"][first_row // bsz:n // bsz], jnp.int32)
    return record.reshape(record.shape[0], -1, bsz)[:, blocks].reshape(
        record.shape[0], -1).T


def chosen_mask(chosen, count, t: int):
    """bool [m, t] of the `count` first of the `chosen` [m, k] positions."""
    import jax.numpy as jnp

    valid = jnp.arange(chosen.shape[1])[None, :] < count[:, None]
    hits = jnp.zeros((chosen.shape[0], t), jnp.int32).at[
        jnp.arange(chosen.shape[0])[:, None],
        jnp.where(valid, chosen, 0)].add(valid.astype(jnp.int32))
    return hits > 0


def selection_readings(sys_scores, sys_chosen, sys_count, ref_scores,
                       topk: int) -> Dict[str, float]:
    """(ii) for one full layer: scores [m, ctx] of both, the system's
    chosen [m, topk] / count [m]."""
    import jax.numpy as jnp

    from benchmarks.reference import dots3_plain as plain

    t = ref_scores.shape[1]
    seen = ref_scores > -jnp.inf
    sys_s = jnp.where(seen, sys_scores[:, :t], 0.0)
    ref_s = jnp.where(seen, ref_scores, 0.0)
    ref_mask = plain.selection_mask(ref_scores, topk)
    sys_mask = chosen_mask(sys_chosen, sys_count, t)
    both = jnp.sum(sys_mask & ref_mask, axis=-1)
    chosen = jnp.maximum(jnp.sum(sys_mask, axis=-1), 1)
    # the reference's k-th score and the spread of the visible ones
    kth = jnp.min(jnp.where(ref_mask, ref_scores, jnp.inf), axis=-1)
    n_seen = jnp.sum(seen, axis=-1)
    mean = jnp.sum(ref_s, axis=-1) / n_seen
    std = jnp.sqrt(jnp.sum(jnp.where(seen, jnp.square(
        ref_scores - mean[:, None]), 0.0), axis=-1) / n_seen)
    under = jnp.where(sys_mask & ~ref_mask, kth[:, None] - ref_scores, 0.0)
    return {"score_err": _rel(sys_s, ref_s),
            "overlap_min": float(jnp.min(both / chosen)),
            "overlap_mean": float(jnp.mean(both / chosen)),
            "margin_max": float(jnp.max(
                jnp.max(under, axis=-1) / jnp.maximum(std, 1e-30))),
            "margin_mean": float(jnp.mean(
                jnp.max(under, axis=-1) / jnp.maximum(std, 1e-30))),
            # an exact count: every visible key while there are at most
            # `topk`, `topk` distinct ones from there on
            "miscounted": int(jnp.sum(jnp.sum(sys_mask, axis=-1)
                                      != jnp.minimum(n_seen, topk))),
            "chosen_min": int(jnp.min(jnp.sum(sys_mask, axis=-1))),
            "visible_max": int(jnp.max(n_seen))}


def routing_readings(readings: List[Dict[str, Any]]) -> Dict[str, Dict]:
    """The check's routing, pooled a program (`kanana2_serve`'s, over the
    readings that hold that program's tokens)."""
    out = {}
    for program in ("prefill", "decode"):
        parts = [r["routing"][program] for r in readings
                 if program in r.get("routing", {})]
        tokens = sum(p["tokens"] for p in parts)
        gates = sum(p["gates"] for p in parts)
        out[program] = {
            "tokens": tokens,
            "mismatch": sum(p["mismatched"] for p in parts) / tokens
            if tokens else None,
            "gate_err": (sum(p["gate_sq"] for p in parts) / gates) ** 0.5
            if gates else None}
    return out


class Reference:
    """The plain reference over the engine's parameters, the document's
    pass kept (every layer's input rows, on the host) so that each held
    request is computed from the document's end on. EVERY call has one
    shape: the sequence padded to the engine's context, `HELD_WIDTH` held
    positions (the last repeated) and a given selection for each (the
    document's pass is given none: rows of -1, which nothing takes), so
    that the document, a request behind it and a request of its own run
    the same three compiled programs (a dense full layer, a full layer and
    a sliding layer with experts; a minute each to compile, cold)."""

    def __init__(self, engine, model_cfg: Dict[str, Any], doc: List[int]):
        from benchmarks.reference import dots3_plain as plain
        from ray_tpu.models.dots3 import published_weights

        self.plain, self.cfg, self.doc = plain, model_cfg, list(doc)
        mc = engine._model.config
        self.top, self.layer = published_weights(mc, engine._params)
        self.fulls = [i for i, kind in enumerate(mc.kinds)
                      if kind == "full_attention"]
        self.pad_to = engine._max_context
        t0 = time.monotonic()
        _, self.doc_taps = self._forward(self.doc, [len(doc) - 1], None,
                                         keep_inputs=True)
        self.doc_inputs = self.doc_taps.pop("inputs")
        self.doc_s = time.monotonic() - t0

    def _forward(self, stream, positions, given, **kw):
        import jax.numpy as jnp

        m = len(positions)
        assert m <= HELD_WIDTH, (m, HELD_WIDTH)
        pad = HELD_WIDTH - m
        rows = jnp.asarray(list(positions) + [positions[-1]] * pad,
                           jnp.int32)
        masks = {}
        for i in self.fulls:
            if given is None:
                masks[i] = (jnp.full((HELD_WIDTH,), -1, jnp.int32),
                            jnp.zeros((HELD_WIDTH, self.pad_to), bool))
            else:
                mask = jnp.pad(given[i], ((0, 0), (
                    0, self.pad_to - given[i].shape[1])))
                masks[i] = (rows, jnp.concatenate(
                    [mask, jnp.repeat(mask[-1:], pad, axis=0)]))
        logits, taps = self.plain.forward(
            self.top, self.layer, jnp.asarray([stream], jnp.int32), self.cfg,
            positions=rows, with_taps=True, given=masks,
            pad_to=self.pad_to, **kw)
        for name in ("scores", "chosen"):
            taps[name] = {i: v[:m] for i, v in taps[name].items()}
        return logits[0, :m], taps

    def held(self, stream: List[int], positions: List[int], given):
        """(logits [m, vocab], taps) of `stream` at `positions`, GIVEN the
        selections {full layer: bool [m, len(stream)]} there; a stream
        that begins with the document runs from its end on."""
        with_doc = stream[:len(self.doc)] == self.doc
        return self._forward(stream, positions, given,
                             prefix=self.doc_inputs if with_doc else None)


def replay_held(engine, state, stream: List[int], first: int, adopted: int):
    """The stream's positions from `first` (its prompt's last) on, replayed
    through `Dots3.paged_step_tapped` AT THE TIMED SHAPES over the cache
    the timed programs left (module docstring): (logits [m, vocab], for
    every full layer (scores [m, ctx], chosen [m, k], count [m]), and of
    the first expert layer (its normed input [m, hidden], what its router
    handed the experts [2k, m])). `adopted`: the cached tokens the request
    was admitted with, where its first chunk began."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = engine.config
    slots, chunk = cfg.batch_slots, cfg.prefill_chunk
    if getattr(engine, "_bench_tapped", None) is None:
        engine._bench_tapped = jax.jit(engine._model.paged_step_tapped,
                                       donate_argnums=(2,))

    def step(ids, pos, live, last_idx):
        tables = {}
        for kind in ("full", "window"):
            table = np.zeros((len(pos), engine._table_width), np.int32)
            table[:, :len(state[kind])] = state[kind]
            tables[kind] = jnp.asarray(table)
        with engine._lock:       # the arenas are donated, as a step's are
            logits, engine._arenas, chose, routed = engine._bench_tapped(
                engine._params, jnp.asarray(ids, jnp.int32), engine._arenas,
                tables, jnp.asarray(pos, jnp.int32), jnp.asarray(live),
                last_idx)
        return logits, chose, routed

    # the prompt's last chunk, as `prefill_fn` ran it. A query whose
    # window reaches behind the pages still held (none at the cell's
    # sizes: a question is one chunk behind the document's tail) is not
    # replayed: its rows stay as the timed chunk wrote them.
    start = adopted + (first - adopted) // chunk * chunk
    own = stream[start:first + 1]
    n, pad = len(own), chunk - len(own)
    bsz, reach = cfg.block_size, engine._model.config.sliding_window_size - 1
    oldest = first // bsz
    while oldest and state["window"][oldest - 1]:
        oldest -= 1
    live = [max(0, start + j - reach) >= oldest * bsz for j in range(n)]
    logits, chose, routed = step([own + [0] * pad], [start],
                                 [live + [False] * pad],
                                 jnp.asarray([n - 1], jnp.int32))
    parts = [(logits, [tuple(a[0, n - 1:n] for a in c) for c in chose],
              (routed[0][n - 1:n], routed[1][:, n - 1:n]))]
    # the fed-back tokens, as `decode_fn` ran them: every row live (the
    # last repeated, which writes its row again)
    rest = list(range(first + 1, len(stream)))
    for at in range(0, len(rest), slots):
        rows = rest[at:at + slots]
        r = len(rows)
        rows = rows + rows[-1:] * (slots - r)
        logits, chose, routed = step([[stream[p]] for p in rows], rows,
                                     [[True]] * slots, None)
        parts.append((logits[:r, 0],
                      [tuple(a[:r, 0] for a in c) for c in chose],
                      (routed[0][:r], routed[1][:, :r])))
    return (jnp.concatenate([p[0] for p in parts]),
            [tuple(jnp.concatenate([p[1][at][j] for p in parts])
                   for j in range(3)) for at in range(len(parts[0][1]))],
            (jnp.concatenate([p[2][0] for p in parts]),
             jnp.concatenate([p[2][1] for p in parts], axis=1)))


def reference_check(engine, reference: Reference,
                    served: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Each held request against the plain reference (module docstring)."""
    import jax.numpy as jnp

    mc = engine._model.config
    k = mc.num_experts_per_tok
    plain, out = reference.plain, []
    n_doc = len(reference.doc)
    for item in served:
        prompt, generated = item["prompt"], item["generated"]
        stream = prompt + generated[:-1]
        first = len(prompt) - 1
        positions = list(range(first, len(stream)))
        m = len(positions)
        res: Dict[str, Any] = {"who": item["who"], "tokens": len(generated),
                               "rows": 0}
        state = cached_state(engine, stream)
        if state is None or state["tokens"] < len(stream):
            res["problem"] = f"its blocks are not all in the radix cache: " \
                f"{state and state['tokens']} of {len(stream)} tokens"
            out.append(res)
            continue
        # what the TIMED programs left of the request's own rows (a
        # document's are read once), before anything is replayed
        with_doc = stream[:n_doc] == reference.doc
        own = n_doc if with_doc else 0
        left = own_rows(engine, state, own)
        routing = routing_of(engine, state, own, state["tokens"])
        sys_logits, chose, routed = replay_held(engine, state, stream, first,
                                                own)
        again = own_rows(engine, state, own)
        res["replay_rows_moved"] = max(
            _rel(again[name], rows) for name, rows in left.items()
            if name != "window_blocks")
        held_rows = routing[first - own:first - own + m]
        res["record_is_replays"] = bool(
            jnp.all(held_rows[:, :k] == routed[1][:k].T)
            and jnp.allclose(held_rows[:, k:], routed[1][k:].T, rtol=1e-4))
        given = {i: chosen_mask(c, n, len(stream))
                 for i, (_, c, n) in zip(reference.fulls, chose)}
        ref_logits, taps = reference.held(stream, positions, given)
        # (i)
        rel = jnp.sqrt(jnp.sum(jnp.square(sys_logits - ref_logits), -1)
                       / jnp.sum(jnp.square(ref_logits), -1))
        gaps = plain.chosen_token_gaps(ref_logits, generated)
        res.update(
            logit_rel_max=float(jnp.max(rel)),
            logit_rel_mean=float(jnp.mean(rel)),
            served_gap_max=float(jnp.max(gaps)),
            served_gap_mean=float(jnp.mean(gaps)),
            served_exact=int(jnp.sum(gaps == 0)),
            replay_agrees=int(jnp.sum(jnp.argmax(sys_logits, -1)
                                      == jnp.asarray(generated))),
            arbitrary_gap=float(jnp.mean(jnp.max(ref_logits, -1)
                                         - jnp.median(ref_logits, -1))))
        # (ii)
        res["selection"] = {
            i: selection_readings(scores, chosen, count,
                                  taps["scores"][i][:, :len(stream)],
                                  mc.index_topk)
            for i, (scores, chosen, count) in zip(reference.fulls, chose)}
        # (iii), (iv)
        res.update(rows_readings(engine, left, taps, state["tokens"], own))
        experts, gates = taps["experts"], taps["gates"]   # rows from `own`
        cut = len(prompt) - own
        # against the reference's whole forward pass (no limit: its router
        # sees the REFERENCE's layer input, which a long context's bf16
        # attention leaves 9% away, and half the sets flip)
        res["routing_whole"] = {
            program: k2.routing_errors(routing[rows], experts[rows],
                                       gates[rows], k)
            for program, rows in (
                ("prefill", slice(0, cut)),
                ("decode", slice(cut, state["tokens"] - own)))}
        # ... and the record the timed programs left against the
        # reference's router ON THE SAME INPUT: the held positions' own
        # normed input of the first expert layer
        want_idx, want_gates = plain.route(
            reference.cfg, reference.layer(int(mc.first_k_dense_replace)),
            jnp.asarray(routed[0], jnp.float32))
        res["routing"] = {
            "prefill": k2.routing_errors(held_rows[:1], want_idx[:1],
                                         want_gates[:1], k),
            "decode": k2.routing_errors(held_rows[1:], want_idx[1:],
                                        want_gates[1:], k)}
        out.append(res)
        del taps, sys_logits, ref_logits, chose, left, again
    return out


def document_readings(engine, reference: Reference) -> Dict[str, Any]:
    """(iii) and (iv) for the document's own 32,768 rows, which set-up's
    chunks wrote: the two full layers' rows and index keys, the window
    pages its node still holds, the routing record."""
    state = cached_state(engine, reference.doc)
    if state is None or state["tokens"] < len(reference.doc):
        return {"who": "document", "problem": "the document is not cached"}
    taps = reference.doc_taps
    res = {"who": "document", **rows_readings(
        engine, own_rows(engine, state), taps, state["tokens"])}
    k = engine._model.config.num_experts_per_tok
    res["routing_whole"] = {"prefill": k2.routing_errors(
        routing_of(engine, state, 0, state["tokens"]), taps["experts"],
        taps["gates"], k)}
    return res


def check_problems(readings: List[Dict[str, Any]]) -> List[str]:
    problems = [f"{r['who']}: {r['problem']}" for r in readings
                if "problem" in r]
    held = [r for r in readings if "logit_rel_max" in r]
    if not held:
        return problems + ["no held request was compared"]

    def over(name, value, limit, what):
        if not value <= limit:
            problems.append(f"{what}: {name} {value} > {limit}")

    over("LOGIT_REL_LIMIT", max(r["logit_rel_max"] for r in held),
         LOGIT_REL_LIMIT, "a held position's logits against the reference's")
    tokens = sum(r["tokens"] for r in held)
    over("LOGIT_REL_MEAN_LIMIT",
         sum(r["logit_rel_mean"] * r["tokens"] for r in held) / tokens,
         LOGIT_REL_MEAN_LIMIT, "the held positions' logits on average")
    over("SERVED_GAP_LIMIT", max(r["served_gap_max"] for r in held),
         SERVED_GAP_LIMIT, "a served token under the reference's best logit")
    # the replay IS the timed programs: it writes the rows they wrote, its
    # argmax is the token they served, its routing the record they left
    over("REPLAY_ROWS_LIMIT", max(r["replay_rows_moved"] for r in held),
         REPLAY_ROWS_LIMIT, "a layer's cached rows after the replay against "
         "what the timed programs had left")
    agrees = sum(r["replay_agrees"] for r in held)
    if agrees < REPLAY_TOKEN_SHARE * tokens:
        problems.append(f"the replay's argmax is the served token at "
                        f"{agrees} of {tokens} held positions "
                        f"(< REPLAY_TOKEN_SHARE {REPLAY_TOKEN_SHARE})")
    if not all(r["record_is_replays"] for r in held):
        problems.append("the routing record of a held position is not what "
                        "the replay routes there")
    picks = [s for r in held for s in r["selection"].values()]
    # the first full layer's input is the embedding through one norm: its
    # scores and its selection are held tightly; a later layer's follow
    # its input (9% away at 33k positions: module docstring)
    first = [r["selection"][min(r["selection"])] for r in held]
    # POOLED over the held queries behind the document (root mean square
    # by tokens): a mean over ~130 queries x 33,000 keys that seeds move
    # by 2%, where a request of four tokens reads 20% off on its own
    long = [(r["tokens"], r["selection"][min(r["selection"])]["score_err"])
            for r in held if r["who"] != "nodoc"] or [
        (r["tokens"], s["score_err"]) for r, s in zip(held, first)]
    over("INDEX_SCORE_LIMIT",
         (sum(t * e * e for t, e in long) / sum(t for t, _ in long)) ** 0.5,
         INDEX_SCORE_LIMIT,
         "the first indexer's scores against the reference's")
    for name, limit, group in (
            ("SELECT_OVERLAP_LIMIT", SELECT_OVERLAP_LIMIT, first),
            ("DEEP_OVERLAP_LIMIT", DEEP_OVERLAP_LIMIT, picks)):
        least = min(s["overlap_min"] for s in group)
        if not least >= limit:
            problems.append(
                f"a held query's selection shares {least} of its positions "
                f"with the reference's (< {name} {limit})")
    if any(s["miscounted"] for s in picks):
        problems.append(
            f"{sum(s['miscounted'] for s in picks)} held queries did not "
            f"choose min(visible, index_topk) distinct positions (the "
            f"fewest: {min(s['chosen_min'] for s in picks)})")
    over("SELECT_MARGIN_LIMIT", max(s["margin_max"] for s in first),
         SELECT_MARGIN_LIMIT,
         "a position chosen against the reference lies under its k-th score")
    errs = [(name, v) for r in readings for name, v in r.items()
            if name.split(".")[0].endswith("_err")]
    over("ROWS_LIMIT", max(v for name, v in errs if name.endswith(".0")),
         ROWS_LIMIT, "the first layer's cached rows and index keys")
    over("DEEP_ROWS_LIMIT", max(v for name, v in errs), DEEP_ROWS_LIMIT,
         "the later layers' cached rows")
    if any(r.get("pad_lanes_max", 0.0) for r in readings):
        problems.append("the cached rows' padding lanes are not zero")
    if not any(r.get("window_pages") for r in readings):
        problems.append("no window page was compared")
    routed = routing_readings(readings)
    # the share of other SETS is pooled over both programs: a prompt's last
    # token is one a request (6 in all), and one near-tie would read 0.17
    # alone
    tokens = sum(read["tokens"] for read in routed.values())
    if tokens:
        over("ROUTE_MISMATCH_LIMIT",
             sum(read["mismatch"] * read["tokens"]
                 for read in routed.values() if read["tokens"]) / tokens,
             ROUTE_MISMATCH_LIMIT,
             "the held tokens routed otherwise than the reference's router "
             "on the same input")
    for program, read in routed.items():
        if not read["tokens"] or read["gate_err"] is None:
            problems.append(f"no routing of a {program} step was compared")
            continue
        limit = GATE_LIMIT if program == "prefill" else DECODE_GATE_LIMIT
        over("GATE_LIMIT" if program == "prefill" else "DECODE_GATE_LIMIT",
             read["gate_err"], limit,
             f"the first expert layer's gates in {program} steps")
    return problems


def window_book_problems(stats: Dict[str, Any], cfg: Dict[str, Any]
                         ) -> List[str]:
    """(v): the window pool's books at idle, by the rule (module
    docstring)."""
    kinds = stats.get("kv_kinds")
    if not kinds:
        return ["the engine keeps no second pool: stats()['kv_kinds']"]
    problems = []
    win, pc = kinds["window"], stats["prefix_cache"]
    eng = cfg["engine"]
    tail = -(-(int(cfg["sliding_window_size"]) - 1)
             // int(eng["block_size"])) + 1
    if (win["window"], win["tail_blocks"]) != (
            int(cfg["sliding_window_size"]), tail):
        problems.append(
            f"the window pool keeps a window of {win['window']} and a tail "
            f"of {win['tail_blocks']} blocks; the configuration's rule is "
            f"{cfg['sliding_window_size']} and {tail}")
    if not stats["has_work"] and win["in_use"] != win["cached"]:
        problems.append(f"window pages leaked at idle: {win}")
    if win["cached"] > pc["nodes"] * win["tail_blocks"]:
        problems.append(f"the radix cache holds {win['cached']} window pages "
                        f"for {pc['nodes']} nodes of at most "
                        f"{win['tail_blocks']}")
    chunk = -(-int(eng["prefill_chunk"]) // int(eng["block_size"])) + 1
    rule = int(eng["batch_slots"]) * (win["tail_blocks"] + 1 + chunk) \
        + pc["nodes"] * win["tail_blocks"]
    if win["peak"] > min(rule, win["blocks"] - 1):
        problems.append(f"the window pool's peak {win['peak']} is over its "
                        f"rule's {rule}")
    if not win["window_blocks_released"]:
        problems.append("no window page was ever released")
    return problems


def path_problems(stats: Dict[str, Any], rehearsal: bool) -> List[str]:
    """A call off the kernel path is not `correct`."""
    calls = stats["latent_attn"] + stats["sparse_attn"]
    problems = [f"{r['pass']} ran the {r['path']}: {r['reason']}"
                for r in calls if r["path"] != "pallas"]
    if len({r["pass"] for r in calls}) < 3:
        problems.append(f"the three kernels were not all traced: "
                        f"{sorted({r['pass'] for r in calls})}")
    paths = ("pallas", "interpret") if rehearsal else ("pallas",)
    problems += [f"held experts ran the {r['path']} path"
                 for r in stats["held_experts"] if r["path"] not in paths]
    if not stats["held_experts"]:
        problems.append("no expert layer was traced")
    problems += [f"paged attention of {prog}: {path}"
                 for prog, path in stats["paged_attn"].items()
                 if path != "pallas"]
    return problems


# --------------------------------------------------------------------------- #
# the deployment
# --------------------------------------------------------------------------- #


def scope_seconds(trace_dir: str) -> Dict[str, Any]:
    """{scope: [device ops, their seconds]} of the trace under `trace_dir`,
    for the model's `jax.named_scope`s (`SCOPES`). A TPU trace keeps an
    op's `op_name`, which holds the scopes, as a stat of the device plane's
    EVENT METADATA, which `jax.profiler.ProfileData` does not show (PERF.md
    section 7): the XSpace proto is parsed, an event joined to its metadata
    and counted under the INNERMOST of the scopes its texts name. Empty
    where the trace's ops carry none; `{"error": ...}` where the proto
    cannot be read here."""
    from benchmarks import xplane

    path = xplane.find_xplane(trace_dir)
    if path is None:
        return {}
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2

        space = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
    except Exception as e:  # noqa: BLE001 — no parser here: no scopes
        return {"error": f"{type(e).__name__}: {e}"[:200]}
    out: Dict[str, List[float]] = {}
    for plane in space.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        names = {i: m.name for i, m in plane.stat_metadata.items()}
        scope_of = {}
        for i, meta in plane.event_metadata.items():
            texts = [meta.name, meta.display_name]
            for stat in meta.stats:
                which = stat.WhichOneof("value")
                if which == "str_value":
                    texts.append(stat.str_value)
                elif which == "ref_value":
                    texts.append(names.get(stat.ref_value, ""))
            text = " ".join(texts)
            at = {s: text.rfind(s) for s in SCOPES if s in text}
            if at:
                scope_of[i] = max(at, key=at.get)
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for ev in line.events:
                scope = scope_of.get(ev.metadata_id)
                if scope is not None:
                    rec = out.setdefault(scope, [0, 0.0])
                    rec[0] += 1
                    rec[1] += ev.duration_ps / 1e12
    return out


class GcPauses:
    """The garbage collector's pauses in this process, by its own callback:
    `since(t)` gives how many collections of the oldest generation ended
    after monotonic `t`, their seconds in all and the longest. A closed
    loop's parent holds 2,048 prompts of 33,000 ids and a replica every
    request's: a full collection walks them all (PERF.md section 5)."""

    def __init__(self):
        import gc

        self._began, self.pauses = None, []
        gc.callbacks.append(self._note)

    def _note(self, phase, info):
        if info["generation"] < 2:
            return
        if phase == "start":
            self._began = time.monotonic()
        elif self._began is not None:
            now = time.monotonic()
            self.pauses.append((now, now - self._began))
            self._began = None

    def since(self, t: float) -> Dict[str, Any]:
        took = [d for at, d in self.pauses if at >= t]
        return {"full_collections": len(took), "seconds": sum(took),
                "longest_s": max(took, default=0.0)}


class _BenchDots3(k2._BenchKanana2):
    """`LLMServer` with a `Dots3` handed in. Everything a request touches
    is inherited from `LLMServer`'s class, and the benchmark's reads from
    `llama_serve._BenchLLM` and `kanana2_serve._BenchKanana2`."""

    def __init__(self, model_cfg: Dict[str, Any],
                 engine_cfg: Dict[str, Any], seed: int, router_seed: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.inference.engine import (EngineConfig, EngineLoop,
                                              InferenceEngine)
        from ray_tpu.models.dots3 import Dots3

        from benchmarks import jaxwatch

        self._seen = jaxwatch.watch()
        self._spans = {"ctor_first_line": time.monotonic()}
        self._gc = GcPauses()
        self._adapter_specs = {}
        self._default_new = 16
        self._config = EngineConfig(**engine_cfg)
        self._model_cfg = model_cfg
        model = Dots3(model_config(model_cfg))
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
        from ray_tpu.ops.sparse_latent_attention import sparse_status

        return {**super().bench_stats(), "sparse_attn": sparse_status()}

    def bench_counters(self, _=None) -> Dict[str, Any]:
        stats = {**self._engine.stats(), **k2.device_counters(self._engine)}
        return {**{name: stats.get(name)
                   for name in ("moe", "dsa", "kv_kinds", "prefix_cache",
                                "steps")},
                "gc_pauses": [list(p) for p in self._gc.pauses]}

    def bench_settle(self, _=None) -> Dict[str, Any]:
        """What the warm-up left (the documents' radix nodes, the programs,
        the weights' handles) is set aside from the garbage collector, as
        a server does once it is warm: a full collection in the window then
        walks what the window made."""
        import gc

        gc.collect()
        gc.freeze()
        return {"frozen": gc.get_freeze_count()}

    def bench_reference(self, asked: Dict[str, Any]) -> Dict[str, Any]:
        reference = Reference(self._engine, self._model_cfg, asked["doc"])
        readings = [document_readings(self._engine, reference)] \
            + reference_check(self._engine, reference, asked["served"])
        return {"readings": readings, "document_s": reference.doc_s}

    def bench_trace_digest(self, keep_sample: bool = False):
        digest = super().bench_trace_digest(keep_sample)
        if digest is not None:
            digest["scopes"] = scope_seconds(self._trace_dir)
        return digest


def _deployment(rehearsal: bool):
    return serve.deployment(
        _BenchDots3, name="BenchDots3", max_concurrent_queries=512,
        route_prefix="/",
        ray_actor_options={} if rehearsal else {"num_tpus": 1})


def window_kinds(before: Dict[str, Any], after: Dict[str, Any],
                 seconds: float) -> Dict[str, Any]:
    """What the second pool and the selection counted between two reads
    of `bench_counters`."""
    out: Dict[str, Any] = {}
    ka, kb = after.get("kv_kinds"), before.get("kv_kinds")
    if ka and kb:
        out["kv"] = {
            "window_blocks_released": ka["window"]["window_blocks_released"]
            - kb["window"]["window_blocks_released"],
            "adoptions_refused": ka["window"]["adoptions_refused"]
            - kb["window"]["adoptions_refused"],
            "seconds": seconds, "window_peak": ka["window"]["peak"],
            "full_peak": ka["full"]["peak"]}
    da, db = after.get("dsa"), before.get("dsa")
    if da and db:
        out["dsa"] = {kind: {name: da[kind][name] - db[kind][name]
                             for name in da[kind]}
                      for kind in ("decode", "prefill")}
        out["dsa"]["full_layers"] = da["full_layers"]
    ma, mb = after.get("moe"), before.get("moe")
    if ma and mb:
        out["moe_more"] = {kind: {name: ma[kind][name] - mb[kind][name]
                                  for name in ("absent", "max_load")}
                           for kind in ("decode", "prefill")}
    return out


def run(ctx) -> Dict[str, Any]:
    """Parent side: deploy, cache the documents, warm up and check, offer
    the mix, verdict."""
    from benchmarks import loadgen

    cfg, traffic = ctx.config, ctx.traffic
    engine_cfg = dict(cfg["engine"])
    vocab = int(cfg["vocab_size"])
    model_cfg = {name: cfg[name] for name in MODEL_KEYS}
    if traffic["loop"] != "closed":
        raise ValueError("dots3_serve offers closed-loop mixes only")
    spans = {"serve_run_called": time.monotonic()}
    handle = serve.run(_deployment(ctx.rehearsal).bind(
        model_cfg, engine_cfg, ctx.seed, int(cfg["router_seed"])),
        timeout_s=900.0)
    spans["serve_run_returned"] = time.monotonic()
    url = f"http://127.0.0.1:{serve.http_port()}/"

    # Set-up: every document once (the first compiles prefill).
    docs = k2.documents(traffic, ctx.seed, vocab)
    t0 = time.monotonic()
    with long_lines():
        sent = asyncio.run(k2._send_documents(url, docs))
    spans["documents_s"] = time.monotonic() - t0
    problems = [f"document {r['idx']} failed: {r['error']}"
                for r in sent if r["error"]][:5]
    _call(handle, "bench_forget_requests", None)

    # Warm-up = the check (module docstring).
    check = check_requests(cfg, ctx.seed, docs)
    t0 = time.monotonic()
    with long_lines():
        warm = asyncio.run(_check_wave(url, check))
    spans["check_wave_s"] = time.monotonic() - t0
    spans["compile_s"] = spans["documents_s"] + spans["check_wave_s"]
    problems += [f"warm-up request {who} failed: {r['error']}"
                 for who, r in warm.items() if r["error"]][:5]
    readings: List[Dict[str, Any]] = []
    if not problems:
        _wait_idle(handle)
        t0 = time.monotonic()
        got = _call(handle, "bench_reference", {
            "doc": docs[0], "served": [
                {"who": who, "prompt": check[who]["ids"],
                 "generated": warm[who]["tokens"]} for who in HELD]},
            timeout=3600.0)
        readings = got["readings"]
        spans["reference_check_s"] = time.monotonic() - t0
        spans["reference_document_s"] = got["document_s"]
        problems += check_problems(readings)
    after_warm = _call(handle, "bench_stats", None)
    problems += window_book_problems(after_warm, cfg)
    by_key = {}
    for e in _call(handle, "bench_requests", None):
        by_key.setdefault(e["key"], set()).add(e["cached_tokens"])
    for who in HELD:
        want = {0 if who == "nodoc" else len(docs[0])}
        if by_key.get(loadgen.prompt_key(check[who]["ids"])) != want:
            problems.append(
                f"{who} adopted "
                f"{sorted(by_key.get(loadgen.prompt_key(check[who]['ids']), []))}"
                f" cached tokens, want {sorted(want)}")

    # The mix. The pool is 2,048 prompts of a document and a question:
    # 67 million ids this process keeps all through the window, which a
    # full collection would walk (0.4 s of every stream's silence, twice a
    # window: PERF.md section 5). They are set aside from the collector,
    # as a load generator's fixtures are.
    import gc

    lead_s = float(traffic.get("lead_s", 0.0))
    pool = k2.docqa_pool(traffic, ctx.seed, vocab, docs)
    pauses = GcPauses()
    gc.collect()
    gc.freeze()
    frozen = {"parent": gc.get_freeze_count(),
              "replica": _call(handle, "bench_settle", None)["frozen"]}
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
    at_zero: Dict[str, Any] = {}

    def read_at_zero():
        time.sleep(max(0.0, t_zero - COUNTER_LEAD_S - time.monotonic()))
        at_zero.update(_call(handle, "bench_counters", None))
        at_zero["read_at"] = time.monotonic()

    reader = threading.Thread(target=read_at_zero, daemon=True)
    reader.start()
    with long_lines():
        records = loadgen.run_closed_loop(url, pool, int(traffic["clients"]),
                                          t_zero, ctx.seconds)
    at_end = _call(handle, "bench_counters", None)
    read_end = time.monotonic()
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
    quiet_s = ctx.seconds if not ctx.trace else max(
        1.0, (ctx.seconds - TRACED_SECONDS) / 2)
    quiet = client if not ctx.trace else loadgen.reduce_records(
        records, t_zero, quiet_s)
    questions = {r["idx"]: pool[r["idx"] % len(pool)]["question_len"]
                 for r in records}
    prefilled = sum(questions[r["idx"]] for r in records if r["token_times"]
                    and t_zero <= r["token_times"][0] < t_zero + quiet_s)
    first_tokens = sum(1 for r in records if r["token_times"]
                       and t_zero <= r["token_times"][0] < t_zero + quiet_s)

    # Verdict.
    prompts = {r["idx"]: pool[r["idx"] % len(pool)]["ids"] for r in records}
    if len(records) > len(pool) and not ctx.rehearsal:
        problems.append(f"closed-loop pool of {len(pool)} wrapped "
                        f"({len(records)} requests): prompts repeated")
    problems += loadgen.wrong_answers(records, prompts)
    problems += [f"request {r['idx']} failed: {r['error']}"
                 for r in records if r["error"] and not r["cut"]][:5]
    for name in ("prefill_compiles", "decode_compiles"):
        if stats[name] != 1:
            problems.append(f"{name}={stats[name]}, want 1")
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
        + k2.cache_problems(stats, cfg, traffic) \
        + window_book_problems(stats, cfg)

    window, kinds = {}, {}
    if at_zero:
        sent_len = {r["key"]: r["prompt_len"] for r in records}
        window = k2.window_counters(at_zero, at_end, [
            {"prompt_len": sent_len[e["key"]],
             "cached_tokens": e["cached_tokens"]} for e in engine_reqs
            if e["admitted_at"] is not None and e["key"] in sent_len
            and t_zero <= e["admitted_at"] < t_zero + ctx.seconds])
        kinds = window_kinds(at_zero, at_end, read_end - at_zero["read_at"])
    first_tokens_in_trace = 0
    if traced:
        first_tokens_in_trace = sum(
            1 for e in engine_reqs if e["first_token_at"] is not None
            and traced["t0"] <= e["first_token_at"] <= traced["t1"])
    gaps, ttft = client["gaps_ms"], client["ttft_ms"]
    window_steps = {}
    if at_zero:
        a, b = at_zero["steps"], at_end["steps"]
        window_steps = {
            **{name: b[name] - a[name]
               for name in ("n", "decode", "prefill", "chunks_aboard",
                            "decode_rows", "wall_s", "wait_work_s")},
            "phase_s": {name: round(b["phase_s"][name] - a["phase_s"][name],
                                    4) for name in b["phase_s"]}}
    replica_gc = [d for at, d in at_end.get("gc_pauses", ())
                  if at_zero and at >= at_zero["read_at"]]
    ctx.emit(builder="dots3_serve", loop=traffic["loop"],
             window_steps=window_steps,
             gc={"frozen_objects": frozen, "parent": pauses.since(t_zero),
                 "replica": {"full_collections": len(replica_gc),
                             "seconds": sum(replica_gc),
                             "longest_s": max(replica_gc, default=0.0)}},
             stalls=k2.stalls(records, t_zero, ctx.seconds),
             attempted=client["attempted"], failed=client["failed"],
             cut_at_window_end=client["cut_at_window_end"],
             open_at_window_end=client["open_at_window_end"],
             tokens_in_window=client["tokens_in_window"],
             itl_samples=len(gaps), ttft_samples=len(ttft),
             itl_p50_ms=loadgen.percentile(gaps, 50) if gaps else None,
             itl_p99_ms=loadgen.percentile(gaps, 99) if gaps else None,
             ttft_p50_ms=statistics.median(ttft) if ttft else None,
             reference=readings,
             routing=routing_readings(readings) if readings else None,
             compiles_in_window=compiles_in_window,
             window=window, kinds=kinds,
             weight_bytes=stats["weight_bytes"],
             arena_bytes=stats["kv"]["bytes"],
             scopes=(traced or {}).get("digest", {}).get("scopes")
             if traced and traced.get("digest") else None,
             engine_stats={name: v for name, v in stats.items()
                           if name not in ("spans",)},
             spans={**spans, **stats["spans"]})
    mean_context = statistics.mean(
        r["prompt_len"] + r["max_new_tokens"] / 2 for r in records) \
        if records else None
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
            "mean_context": mean_context,
            **{f"window_{name}": v for name, v in window.items()},
            **{f"window_{name}": v for name, v in kinds.items()},
            "window_steps": window_steps,
        },
        "client": {"out_tok_s": quiet["tokens_in_window"] / quiet_s,
                   "prefill_tok_s": prefilled / quiet_s,
                   "requests_s": first_tokens / quiet_s},
        "end_to_end": {
            "serve_out_tok_s": client["tokens_in_window"] / ctx.seconds},
        "trace": traced["digest"] if traced else None,
    }
