"""`dots3_note` decoder forward (latent attention of two geometries, a
learned indexer that lets a full layer read its `index_topk` best cached
tokens, window layers, a routed expert layer of which a share is held), in
plain `jax.numpy` float32 under `jax.default_matmul_precision("highest")`:
the EXPANDED attention form with dense [s, s] masks (causal; the window;
the selection as a mask built from a full SORT of the index scores), a loop
over the held experts, no cache, no kernel, no absorbed product: independent
of the form the system runs and of `ray_tpu.models` / `ray_tpu.ops`.

Per block, `cfg` the configuration as a dict (the published keys;
`deployment.experts_routed` the router's width, `n_routed_experts` the
experts HELD, from `deployment.first_expert_held`); N an RMSNorm with a
learned weight, eps `rms_norm_eps`:

    h = N_1(x)
    a FULL layer (H = num_attention_heads, Q = q_lora_rank, L =
    kv_lora_rank, n / r / v = qk_nope / qk_rope / v head dims):
      c_q = sqrt(hidden / Q) N(q_a_proj h)
      [q_n | q_r]^g = q_b_proj c_q;   [c_kv | k_r] = kv_a_proj_with_mqa h
      c = sqrt(hidden / L) N(c_kv);   q_r, k_r <- rotary (de-interleave,
      rotate halves; theta rope_theta), k_r ONE head for all
      [k_n | v]^g(j) = kv_b_proj^g c(j)
      qI^i = indexer.wq_b c_q; kI = LayerNorm(indexer.wk h) (weight, bias,
      eps 1e-6); the first r dims of each rotated as halves (no de-
      interleave); w = (indexer.weights_proj h) n_heads^-1/2 head_dim^-1/2
      I(p, j) = sum_i w_i(p) relu(qI^i(p) . kI(j)),  j <= p
      S(p) = the index_topk positions of largest I(p, .), ties to the
      lower position (a stable sort), all of them while p + 1 <= index_topk;
      or the positions GIVEN for p
      a^g(p) = sum_{S(p)} softmax_{S(p)}((q_n.k_n + q_r.k_r)/sqrt(n + r)) v
      x = x + o_proj concat_g(sigmoid(g_proj h)_g a^g)
    a SLIDING layer: the same with the swa_* sizes, no indexer, S(p) =
      {j : p - sliding_window_size < j <= p}
    m = N_2(x)
    layer < first_k_dense_replace: x = x + down(silu(gate m) * up m)
    else: s = sigmoid(m W_r^T); E = top-k of (s + bias); g_e = scaling s_e /
          (sum_E s + 1e-20); x = x + shared(m) + sum_{e in E, held} g_e
          expert_e(m)
    logits = lm_head N_f(x)

Weights under the published names, products [out, in]; the held experts
arrive STACKED (`mlp.experts.gate_up` [held, in, 2F], `mlp.experts.down`
[held, F, out]) and are upcast and applied one at a time, every token
through every held expert with its weight (zero where not chosen). They
come a layer at a time through `layer(i)`. Token-wise products run in
blocks of `ROW_BLOCK` rows, attention in blocks of `Q_BLOCK` queries and
`HEAD_GROUP` heads (a sliding layer's block against the keys its window
reaches, a full layer's against every key under the mask), the selection in
blocks of `IDX_BLOCK` queries and kept a BIT a key, a layer `Q_SLAB` query
rows a call against every row's keys, so that 33,000 positions fit in the
3 GB a served model leaves; and a sequence that begins with one already
computed is computed from there on (`forward`'s `prefix`).

`forward(..., with_taps=True)` also returns, for the check: `rows` {layer:
[t, L + r]} (what a latent cache holds: c and the rotated k_r, half-split
lanes) of every layer, `index_keys` {layer: [t, d]} of the full layers,
`scores` {layer: [len(positions), t]} the indexer's I at `positions` (-inf
past a query), `chosen` {layer: bool [len(positions), t]}, and the first
expert layer's `experts` [t, k] (sorted) and `gates` [t, k].
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

ROW_BLOCK = 2048
Q_SLAB = 1024
Q_BLOCK = 128
IDX_BLOCK = 16
HEAD_GROUP = 4
MLP_BLOCK = 1536
FULL = "full_attention"
_NEG = -1e30


def _f32(t):
    return jnp.asarray(t, jnp.float32)


def _linear(x, w):
    """x W^T, the weight upcast here: a layer's weights arrive as they
    are stored and are float32 one use at a time."""
    return x @ _f32(w).T


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(weight)


def _layer_norm(x, weight, bias, eps=1e-6):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(weight) + _f32(bias)


def _rotate_halves(x, pos, theta):
    """x [t, heads, r] at positions pos [t]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rope_interleaved(x, pos, theta):
    """The published rotary of the latent attention: de-interleave the
    pairs, then rotate halves."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    return _rotate_halves(x, pos, theta)


def _by_rows(fn, x, block=ROW_BLOCK):
    """fn over row blocks of x [t, ..], or of every array of a tuple of
    them (fn maps [n, ..] -> [n, ..], or a tuple of such)."""
    t = jax.tree.leaves(x)[0].shape[0]
    n = -(-t // block)
    xp = jax.tree.map(lambda a: jnp.pad(
        a, ((0, n * block - t),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (n, block) + a.shape[1:]), x)
    out = jax.lax.map(fn, xp)
    return jax.tree.map(
        lambda o: o.reshape((n * block,) + o.shape[2:])[:t], out)


def geometry(cfg, kind):
    p = "" if kind == FULL else "swa_"
    return {"heads": cfg[p + "num_attention_heads"],
            "q_rank": cfg[p + "q_lora_rank"],
            "kv_rank": cfg[p + "kv_lora_rank"],
            "n": cfg[p + "qk_nope_head_dim"], "r": cfg[p + "qk_rope_head_dim"],
            "v": cfg[p + "v_head_dim"], "theta": float(cfg[p + "rope_theta"])}


def index_keys(cfg, w, h, pos):
    """kI [t, d]: LayerNorm'ed, the first r dims rotated as halves."""
    r, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    k = _layer_norm(_linear(h, w["self_attn.indexer.wk.weight"]),
                    w["self_attn.indexer.k_norm.weight"],
                    w["self_attn.indexer.k_norm.bias"])
    return jnp.concatenate([_rotate_halves(k[:, None, :r], pos, theta)[:, 0],
                            k[:, r:]], -1)


def index_scores(cfg, w, cq, hq, posq, keys, pos):
    """I [q, t] float32 of queries (their latents cq, normed inputs hq,
    positions posq) against the index keys [t, d] at `pos`; -inf where j >
    p."""
    n, d, r = cfg["index_n_heads"], cfg["index_head_dim"], \
        cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_theta"])
    q = _linear(cq, w["self_attn.indexer.wq_b.weight"]).reshape(-1, n, d)
    q = jnp.concatenate([_rotate_halves(q[..., :r], posq, theta),
                         q[..., r:]], -1)
    wts = _linear(hq, w["self_attn.indexer.weights_proj.weight"]) \
        * (n ** -0.5 * d ** -0.5)
    dots = jnp.einsum("qnd,kd->qnk", q, keys)
    scores = jnp.sum(jax.nn.relu(dots) * wts[..., None], axis=1)
    return jnp.where(pos[None, :] <= posq[:, None], scores, -jnp.inf)


def selection_mask(scores, topk: int):
    """bool [q, t]: the `topk` largest of each row above -inf, ties to the
    lower position: the k-th value out of a FULL sort of the row, then the
    ties at it counted from the left."""
    if scores.shape[-1] < topk:
        return scores > -jnp.inf
    kth = jnp.sort(scores, axis=-1)[:, scores.shape[-1] - topk][:, None]
    above = scores > kth
    ties = (scores == kth) & (scores > -jnp.inf)
    need = topk - jnp.sum(above, axis=-1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=-1) <= need))


def _blocks(n_rows: int, block: int):
    """Row indices [blocks, block] covering n_rows (the last repeated)."""
    blocks = -(-n_rows // block)
    return jnp.minimum(jnp.arange(blocks * block), n_rows - 1).reshape(
        blocks, block)


def _attention(cfg, kind, w, x, pos, given, want, q_from, n_q: int):
    """(out [n_q, hidden], taps) for the `n_q` query rows from `q_from` (a
    traced scalar) of x [t, hidden] (the layer's input, un-normed); the
    keys are every row's. `given`: None, or (rows [m] int32, mask bool [m,
    t]): the positions the queries at those rows may read (rows outside
    the slab are ignored). `want`: rows whose scores and selection are
    tapped (those outside the slab read a neighbour's: the caller keeps a
    row's from the slab that holds it)."""
    g = geometry(cfg, kind)
    t = x.shape[0]
    H, n, r, v, L = g["heads"], g["n"], g["r"], g["v"], g["kv_rank"]
    hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    norm = w["input_layernorm.weight"]

    def key_side(block):
        xb, pb = block
        hb = _rms_norm(xb, norm, eps)
        ckr = _linear(hb, w["self_attn.kv_a_proj_with_mqa.weight"])
        c = math.sqrt(hidden / L) * _rms_norm(
            ckr[:, :L], w["self_attn.kv_a_layernorm.weight"], eps)
        k_r = _rope_interleaved(ckr[:, None, L:], pb, g["theta"])[:, 0]
        keys = index_keys(cfg, w, hb, pb) if kind == FULL \
            else jnp.zeros((xb.shape[0], 0))
        return c, k_r, keys

    # the keys' side of every row, a block of rows at a time (never a
    # whole [t, hidden] beside the input)
    c, k_r, keys = _by_rows(key_side, (x, pos))
    hq = _rms_norm(jax.lax.dynamic_slice_in_dim(x, q_from, n_q), norm, eps)
    posq = q_from + jnp.arange(n_q, dtype=jnp.int32)
    cq = math.sqrt(hidden / g["q_rank"]) * _rms_norm(
        _linear(hq, w["self_attn.q_a_proj.weight"]),
        w["self_attn.q_a_layernorm.weight"], eps)
    gate = jax.nn.sigmoid(_linear(hq, w["self_attn.g_proj.weight"]))
    taps = {"rows": jnp.concatenate([c, k_r], -1)}
    q_rows = _blocks(n_q, Q_BLOCK)
    span = Q_BLOCK + cfg["sliding_window_size"] - 1
    if kind == FULL:
        topk = cfg["index_topk"]

        def block_mask(rows):
            return jnp.packbits(selection_mask(index_scores(
                cfg, w, cq[rows], hq[rows], posq[rows], keys, pos), topk),
                axis=-1)

        # The selection of every query, a bit a key (33,000^2 booleans
        # would be a gigabyte).
        packed = jax.lax.map(block_mask, _blocks(n_q, IDX_BLOCK)).reshape(
            -1, -(-t // 8))[:n_q]
        if given is not None:
            local = given[0] - q_from
            local = jnp.where((local >= 0) & (local < n_q), local, n_q)
            packed = packed.at[local].set(jnp.packbits(given[1], axis=-1),
                                          mode="drop")
        local = jnp.clip(want - q_from, 0, n_q - 1)
        taps["index_keys"] = keys
        taps["scores"] = index_scores(cfg, w, cq[local], hq[local],
                                      posq[local], keys, pos)
        taps["chosen"] = jnp.unpackbits(packed[local], axis=-1)[:, :t] > 0
    group = min(HEAD_GROUP, H)
    groups = H // group
    wq = w["self_attn.q_b_proj.weight"].reshape(groups, group, n + r, -1)
    wkv = w["self_attn.kv_b_proj.weight"].reshape(groups, group, n + v, L)
    wo = w["self_attn.o_proj.weight"].reshape(hidden, groups, group,
                                              v).transpose(1, 0, 2, 3)
    gates = gate.reshape(n_q, groups, group).transpose(1, 0, 2)
    scale = 1.0 / math.sqrt(n + r)
    front = span if kind != FULL else 0
    # (a sliding layer's block reads the `span` keys that end at its last
    # query; rows of padding in front for the first blocks)
    k_rp = jnp.pad(k_r, ((front, 0), (0, 0)))
    k_pos = jnp.pad(pos, (front, 0), constant_values=-(10 ** 9))

    def head_group(out, ws):
        """`HEAD_GROUP` heads at a time (a scan: one body to compile): this
        group's share of o_proj is added to `out`, never a whole [t, H v]."""
        wq_g, wkv_g, wo_g, gate_g = ws
        q = jnp.einsum("tq,hdq->thd", cq, _f32(wq_g))
        q_r = _rope_interleaved(q[..., n:], posq, g["theta"])
        kv = jnp.einsum("tl,hdl->thd", c, _f32(wkv_g))
        k_n = jnp.pad(kv[..., :n], ((front, 0), (0, 0), (0, 0)))
        val = jnp.pad(kv[..., n:], ((front, 0), (0, 0), (0, 0)))

        def block(rows):
            if kind == FULL:
                kn, vv, kr = k_n, val, k_rp
                m = jnp.unpackbits(packed[rows], axis=-1)[:, :t] > 0
            else:
                at = q_from + rows[-1] + 1 + front - span
                kn = jax.lax.dynamic_slice_in_dim(k_n, at, span)
                vv = jax.lax.dynamic_slice_in_dim(val, at, span)
                kr = jax.lax.dynamic_slice_in_dim(k_rp, at, span)
                back = posq[rows][:, None] \
                    - jax.lax.dynamic_slice_in_dim(k_pos, at, span)[None, :]
                m = (back >= 0) & (back < cfg["sliding_window_size"])
            s = (jnp.einsum("qhd,khd->hqk", q[rows][..., :n], kn)
                 + jnp.einsum("qhd,kd->hqk", q_r[rows], kr)) * scale
            p = jax.nn.softmax(jnp.where(m[None], s, _NEG), axis=-1)
            return jnp.einsum("hqk,khd->qhd", jnp.where(m[None], p, 0.0), vv)

        a = jax.lax.map(block, q_rows).reshape(-1, group, v)[:n_q]
        return out + jnp.einsum("qhd,ehd->qe", a * gate_g[:, :, None],
                                _f32(wo_g)), None

    out, _ = jax.lax.scan(head_group, jnp.zeros((n_q, hidden), jnp.float32),
                          (wq, wkv, wo, gates))
    return out, taps


def route(cfg, w, m):
    """(chosen experts [t, k] sorted by expert, gates [t, k]) over ALL the
    routed experts."""
    s = jax.nn.sigmoid(_linear(m, w["mlp.gate.weight"]))
    _, idx = jax.lax.top_k(s + _f32(w["mlp.gate.e_score_correction_bias"]),
                           cfg["num_experts_per_tok"])
    idx = jnp.sort(idx, axis=-1)
    chosen = jnp.take_along_axis(s, idx, -1)
    gates = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    return idx, gates


def _swiglu(m, gate, up, down):
    """down(silu(gate m) * up m), `MLP_BLOCK` columns of the intermediate
    width at a time (13,824 columns of float32 weights are 850 MB)."""
    width = gate.shape[0]
    block = MLP_BLOCK if width % MLP_BLOCK == 0 else width
    n = width // block

    def part(ws):
        g, u, d = ws
        return _linear(jax.nn.silu(_linear(m, g)) * _linear(m, u), d)

    parts = jax.lax.map(part, (gate.reshape(n, block, -1),
                               up.reshape(n, block, -1),
                               down.T.reshape(n, block, -1).transpose(
                                   0, 2, 1)))
    return jnp.sum(parts, axis=0)


def _experts(cfg, w, m, held):
    """The shared expert and the HELD routed experts (`held` = (first,
    count): experts first .. first + count - 1 of the router's), one at a
    time."""
    idx, gates = route(cfg, w, m)
    y = _swiglu(m, w["mlp.shared_experts.gate_proj.weight"],
                w["mlp.shared_experts.up_proj.weight"],
                w["mlp.shared_experts.down_proj.weight"])
    f = cfg["moe_intermediate_size"]
    first, count = held
    for e in range(count):
        weight = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1,
                         keepdims=True)
        gu = m @ _f32(w["mlp.experts.gate_up"][e])
        y = y + weight * ((jax.nn.silu(gu[:, :f]) * gu[:, f:])
                          @ _f32(w["mlp.experts.down"][e]))
    return y, idx, gates


def held_experts(cfg):
    dep = cfg.get("deployment") or {}
    first = int(dep.get("first_expert_held", 0)) if isinstance(dep, dict) \
        else 0
    return first, int(cfg["n_routed_experts"])


def _layer(cfg, kind, w, x, pos, given, want, q_from, held, n_q: int):
    """x [t, hidden] -> the `n_q` rows from `q_from` on after the layer."""
    eps = cfg["rms_norm_eps"]
    out, taps = _attention(cfg, kind, w, x, pos, given, want, q_from, n_q)
    x = jax.lax.dynamic_slice_in_dim(x, q_from, n_q) + out
    norm = w["post_attention_layernorm.weight"]
    if "mlp.gate.weight" not in w:
        return x + _by_rows(lambda xb: _swiglu(
            _rms_norm(xb, norm, eps), w["mlp.gate_proj.weight"],
            w["mlp.up_proj.weight"], w["mlp.down_proj.weight"]), x), taps
    y, taps["experts"], taps["gates"] = _by_rows(
        lambda xb: _experts(cfg, w, _rms_norm(xb, norm, eps), held), x)
    return x + y, taps


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, kind: str, held: tuple, n_q: int):
    cfg = json.loads(cfg_json)
    return jax.jit(functools.partial(_layer, cfg, kind, held=held, n_q=n_q))


def forward(top: Dict[str, Any], layer: Callable[[int], Dict[str, Any]],
            ids, cfg: Dict[str, Any], positions: Optional[List[int]] = None,
            with_taps: bool = False, given: Optional[Dict[int, Any]] = None,
            held=None, prefix: Optional[List[Any]] = None,
            keep_inputs: bool = False, pad_to: Optional[int] = None):
    """Logits of ONE sequence `ids` [1, t] at `positions` (all by default)
    [1, len(positions), vocab]; with `with_taps` also the taps (module
    docstring). `given` {layer: (rows [m], mask bool [m, t])} replaces the
    selection of those queries in that (full) layer. `held` = (first,
    count) of the routed experts that are computed (the configuration's
    share by default).

    BLOCKS OF ROWS: a layer runs `Q_SLAB` query rows a call against the
    keys of every row (one compiled shape a layer kind), so that a layer's
    temporaries do not grow with the sequence. ACROSS CALLS: with
    `keep_inputs` the taps hold `inputs`, every layer's input rows [t,
    hidden] (host arrays). Handed back as `prefix` with a sequence that
    BEGINS with those t ids, only the rows after them are computed (a row
    never depends on a later one): their queries read keys made from the
    prefix's rows and their own. `positions` then lie past the prefix, and
    the taps' `experts` / `gates` cover those rows alone. ONE SHAPE: with
    `pad_to` the sequence is padded to that many ids (rows nobody reads: a
    row never depends on a later one) and every slab is `Q_SLAB` rows, so
    that a document, a request behind it and a short request of its own run
    the same three compiled programs (a minute each to compile, cold)."""
    import numpy as np

    ids = jnp.asarray(ids)
    assert ids.shape[0] == 1, "one sequence at a time"
    real = ids.shape[1]
    if pad_to is not None:
        ids = jnp.pad(ids, ((0, 0), (0, max(0, int(pad_to) - real))))
    t = ids.shape[1]
    first = 0 if prefix is None else int(prefix[0].shape[0])
    rows = jnp.asarray(list(range(first, real)) if positions is None
                       else positions, jnp.int32)
    pos = jnp.arange(t, dtype=jnp.int32)
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    held = tuple(held or held_experts(cfg))
    slim = json.dumps({k: v for k, v in cfg.items()
                       if not isinstance(v, (dict, list))}, sort_keys=True)
    n_q = min(Q_SLAB, t - first)
    # slabs of n_q query rows that cover [first, real): one that would
    # pass the end is moved back so that it is whole (its first rows are
    # computed twice)
    starts = sorted({min(at, t - n_q) for at in range(first, real, n_q)})
    taps: Dict[str, Any] = {"rows": {}, "index_keys": {}, "scores": {},
                            "chosen": {}, "inputs": []}
    with jax.default_matmul_precision("highest"):
        x = _f32(top["model.embed_tokens.weight"][ids[0, first:]])
        for i, kind in enumerate(kinds):
            w = layer(i)
            if keep_inputs:
                taps["inputs"].append(np.asarray(x[:real - first]))
            if prefix is not None:
                x = jnp.concatenate([jnp.asarray(prefix[i]), x])
            fn = _jitted(slim, kind, held, n_q)
            parts, got = {}, {}
            for at in starts:
                out, got = fn(w, x, pos, (given or {}).get(i), rows,
                              jnp.int32(at))
                parts[at] = out
                mine = (rows >= at) & (rows < at + n_q)
                for name in ("scores", "chosen"):
                    if name in got:
                        was = taps[name].get(i)
                        taps[name][i] = got[name] if was is None else \
                            jnp.where(mine[:, None], got[name], was)
                for name in ("experts", "gates"):
                    if name in got and i == _first_expert_layer(cfg):
                        parts[(name, at)] = got[name]
            taps["rows"][i] = np.asarray(got["rows"])[:real]   # host
            if "index_keys" in got:
                taps["index_keys"][i] = np.asarray(got["index_keys"])[:real]
            end = starts[-1] + n_q
            x = _stitch(parts, starts, n_q, first, t)
            if end < t:          # rows past the last slab: padding
                x = jnp.pad(x, ((0, t - end), (0, 0)))
            for name in ("experts", "gates"):
                if (name, starts[0]) in parts:
                    taps[name] = _stitch({at: parts[(name, at)]
                                          for at in starts}, starts, n_q,
                                         first, t)[:real - first]
            del w, got, parts
        x = _rms_norm(x[rows - first], top["model.norm.weight"],
                      cfg["rms_norm_eps"])
        head = top["lm_head.weight"]
        vocab = head.shape[0]
        step = 8192
        logits = jnp.concatenate(
            [x @ _f32(head[r0:min(vocab, r0 + step)]).T
             for r0 in range(0, vocab, step)], axis=-1)[None]
    return (logits, taps) if with_taps else logits


def _first_expert_layer(cfg) -> int:
    return int(cfg["first_k_dense_replace"])


def _stitch(parts, starts, n_q: int, first: int, t: int):
    """The slabs' rows laid end to end as rows [first, t): a slab that was
    moved back gives only the rows the one before it did not."""
    out, covered = [], first
    for at in starts:
        out.append(parts[at][covered - at:])
        covered = at + n_q
    return jnp.concatenate(out) if len(out) > 1 else out[0]


def chosen_token_gaps(rows, generated):
    """How far each served token's logit lies under its position's best."""
    gen = jnp.asarray(generated, jnp.int32)
    return jnp.max(rows, axis=-1) - rows[jnp.arange(gen.shape[0]), gen]
