"""`model_type` `dots3_note`: a pre-norm decoder whose layers attend in one
of two ways (`layer_types`), each a multi-head LATENT attention of its own
geometry, and after `first_k_dense_replace` dense layers a routed expert
layer of which this chip holds a SHARE. Served through the engine
(`inference/engine.py`): this file answers the model contract
(docs/INFERENCE.md) and nothing else is asked of it.

The equations (h the RMS-normed input of a sub-block, eps `rms_norm_eps`,
residuals pre-norm; p a query's position, j a cached one):

A FULL layer (`full_attention`; H heads, n = `qk_nope_head_dim`, r =
`qk_rope_head_dim`, v = `v_head_dim`, L = `kv_lora_rank`, Q =
`q_lora_rank`):

    c_q = s_q RMSNorm_Q(h W_qa),  s_q = sqrt(hidden / Q)   (the rescale)
    [q_n | q_r]^g = c_q W_qb^g;   q_r rotated (rope_theta)
    [c_kv | k_r] = h W_kva;  c = s_kv RMSNorm_L(c_kv), s_kv = sqrt(hidden / L)
    k_r rotated, ONE rope key for all heads;  cache row(p) = [c | k_r]
    [k_n | v]^g(j) = c(j) W_kvb^g          (absorbed, as `deepseek_v3`)
    the indexer (`index_n_heads` heads of `index_head_dim`):
      qI^i = c_q W_qI^i, kI = LayerNorm(h W_kI) (weight and bias), the first
      r dims of each rotated as HALVES; index(p) = kI cached beside the row
      w = (h W_w) n_heads^-1/2 head_dim^-1/2
      I(p, j) = sum_i w_i(p) relu(qI^i(p) . kI(j)),  j <= p, float32
    S(p) = the `index_topk` positions j <= p of largest I(p, j)
    a^g = sum_{j in S(p)} softmax_{S(p)}((q_n.k_n + q_r.k_r) / sqrt(n + r)) v
    x += W_o concat_g(sigmoid(h W_g)_g a^g)               (a gate a head)

A SLIDING layer (`sliding_attention`): the same with the `swa_*` sizes, no
indexer, and S(p) = {j : p - `sliding_window_size` < j <= p}.

    layer < first_k_dense_replace:  W_d (silu(n W_g) * (n W_u))
    else: s = sigmoid(n W_r) f32; E = top-k of (s + b); g_e = scaling * s_e
          / (sum_E s + 1e-20); x += shared(n) + sum_{e in E, HELD} g_e
          expert_e(n): of `experts_routed` experts this chip holds
          `n_routed_experts`, from `first_expert_held`; what the others
          would add is computed elsewhere in the deployment and left out.
    logits = W_head RMSNorm(x)                             untied head

What is run is the ABSORBED form (`models/deepseek_v3.py` says why), so a
full layer caches `[c | k_r]` in a headless arena [blocks, block, 640]
and `kI` in an arena [blocks, block, 128] addressed by the same table; a
sliding layer caches its own `[c | k_r]`, [blocks, block, 1152], in arenas
of ANOTHER KIND: the model states two kinds of paged state (`cache_kinds`)
and is handed a block table a kind, and the engine gives back the pages
that have fallen behind every query's window (docs/INFERENCE.md finding
(j)). A full layer's attention is `ops/sparse_latent_attention.py` (the
indexer's kernel, the selection by counting, a gather and the latent
kernel), a sliding layer's `ops/latent_attention.py` with its lower bound.

The rope lanes of q_r and k_r are held half-split and published
interleaved, as `deepseek_v3`'s; the indexer's are halves as published.

Counters (`cache_counters`, finding (f)): the expert layers' loads as
`deepseek_v3` counts them, beside them the assignments routed to ABSENT
experts; and the keys visible and chosen a full layer, decode steps and
chunks apart. `cache["routing"]` is the first expert layer's routing
record, a column a cached token of the `full` kind.

Precision: parameters, caches and matmul operands `dtype` (bf16) into f32
accumulation; router, norms, LayerNorm, rotary, softmax, the gates'
sigmoid, the indexer's sums and the selection f32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# dots3_controls.py replaces this module's functions by name.
from ray_tpu.models._nn import (RowsOfTransposed, cache_locations, normal,
                                rms_norm as _rms_norm)
from ray_tpu.models._served import PagedModel
from ray_tpu.ops import held_experts as moe
from ray_tpu.ops.latent_attention import latent_attention
from ray_tpu.ops.sparse_latent_attention import (gathered_attention,
                                                 index_scores, select_topk)

_LANES = 128
KINDS = ("decode", "prefill")
FULL, SLIDING = "full_attention", "sliding_attention"
BIAS_STD = 0.1        # the selection bias, as `deepseek_v3` seeds it
_CARRY = 20           # a wide counter is hi * 2^20 + lo, two int32


@dataclass(frozen=True)
class Geometry:
    """One kind of layer's latent attention."""
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float

    @property
    def row(self) -> int:
        return self.kv_rank + self.rope

    @property
    def page_width(self) -> int:
        return -(-self.row // _LANES) * _LANES


@dataclass(frozen=True)
class Dots3Config:
    vocab_size: int = 152064
    hidden_size: int = 5120
    num_hidden_layers: int = 46
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window_size: int = 513
    intermediate_size: int = 13824
    first_k_dense_replace: int = 1
    n_routed_experts: int = 256       # HELD on this chip
    experts_routed: int = 256         # the router's width
    first_expert_held: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 524288
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @staticmethod
    def from_published(cfg: Dict[str, Any], **overrides) -> "Dots3Config":
        """From the keys of a published `config.json` (further keys are
        ignored). What this file does not hold is refused, not dropped."""
        if cfg.get("rope_scaling") is not None:
            raise ValueError("rope_scaling: only the plain rotary is held")
        if cfg.get("scoring_func", "sigmoid") != "sigmoid" \
                or not cfg.get("norm_topk_prob", True):
            raise ValueError("only sigmoid scores renormalised are held")
        for key in ("attention_gate_type", "swa_attention_gate_type"):
            if cfg.get(key, "headwise") != "headwise":
                raise ValueError(f"{key}: only the headwise gate is held")
        if not cfg.get("apply_mla_qkv_lora_rescale", True):
            raise ValueError("only the rescaled latents are held")
        names = set(Dots3Config.__dataclass_fields__) - {"dtype"}
        kw = {k: cfg[k] for k in names if k in cfg}
        kw["layer_types"] = tuple(cfg["layer_types"])
        for key in ("rope_theta", "swa_rope_theta", "routed_scaling_factor"):
            kw[key] = float(kw[key])
        kw.setdefault("experts_routed", kw["n_routed_experts"])
        return Dots3Config(**{**kw, **overrides})

    @staticmethod
    def tiny(**overrides) -> "Dots3Config":
        """Every mechanism at a few tens of thousands of parameters (CPU
        tests): both geometries' pages whole lane tiles, so that the
        kernels take them under the interpreter; a window of 9 and a
        selection of 16, which a sequence of 40 tokens passes."""
        return Dots3Config(**{**dict(
            vocab_size=96, hidden_size=32, num_hidden_layers=5,
            layer_types=(FULL, FULL, SLIDING, SLIDING, SLIDING),
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=128,
            qk_nope_head_dim=16, qk_rope_head_dim=32, v_head_dim=16,
            index_n_heads=8, index_head_dim=128, index_topk=16,
            swa_num_attention_heads=2, swa_q_lora_rank=24,
            swa_kv_lora_rank=256, swa_qk_nope_head_dim=24,
            swa_qk_rope_head_dim=32, swa_v_head_dim=16,
            sliding_window_size=9, intermediate_size=48,
            n_routed_experts=8, experts_routed=8, num_experts_per_tok=2,
            moe_intermediate_size=16, max_position_embeddings=256,
            dtype=jnp.float32), **overrides})

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(self.layer_types[:self.num_hidden_layers])

    def geometry(self, kind: str) -> Geometry:
        if kind == FULL:
            return Geometry(self.num_attention_heads, self.q_lora_rank,
                            self.kv_lora_rank, self.qk_nope_head_dim,
                            self.qk_rope_head_dim, self.v_head_dim,
                            self.rope_theta)
        return Geometry(self.swa_num_attention_heads, self.swa_q_lora_rank,
                        self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                        self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                        self.swa_rope_theta)

    @property
    def held(self) -> Tuple[int, int]:
        return self.first_expert_held, self.n_routed_experts

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def n_full_layers(self) -> int:
        return sum(k == FULL for k in self.kinds)

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #


def init_params(cfg: Dots3Config, key) -> Dict[str, Any]:
    """Seeded parameters: products normal(std 0.02) in `cfg.dtype`, norms
    one (LayerNorm's bias zero), the selection bias normal(std `BIAS_STD`)
    in float32. Only the HELD experts exist. ONE jitted program draws every
    leaf (in blocks): two geometries and an indexer are some forty distinct
    shapes, and a program a shape compiled cold for longer than a replica
    may take to start (120 s)."""
    return jax.jit(lambda k: _init_params(cfg, k))(key)


def _init_params(cfg: Dots3Config, key) -> Dict[str, Any]:
    e, dt = cfg.hidden_size, cfg.dtype
    held, f = cfg.n_routed_experts, cfg.moe_intermediate_size
    draw = normal
    keys = iter(jax.random.split(key, 2 + 20 * cfg.num_hidden_layers))
    params = {"embed": draw(next(keys), (cfg.vocab_size, e), dt),
              "lm_head": draw(next(keys), (e, cfg.vocab_size), dt),
              "final_norm": jnp.ones((e,), dt), "layers": []}
    for i, kind in enumerate(cfg.kinds):
        g = cfg.geometry(kind)
        lp = {"wqa": draw(next(keys), (e, g.q_rank), dt),
              "q_norm": jnp.ones((g.q_rank,), dt),
              "wqb": draw(next(keys), (g.q_rank, g.heads * (g.nope + g.rope)),
                          dt),
              "wkva": draw(next(keys), (e, g.row), dt),
              "kv_norm": jnp.ones((g.kv_rank,), dt),
              "w_uk": draw(next(keys), (g.heads, g.nope, g.kv_rank), dt),
              "w_uv": draw(next(keys), (g.heads, g.kv_rank, g.v), dt),
              "wo": draw(next(keys), (g.heads * g.v, e), dt),
              "w_gate_attn": draw(next(keys), (e, g.heads), dt),
              "input_norm": jnp.ones((e,), dt),
              "mlp_norm": jnp.ones((e,), dt)}
        if kind == FULL:
            n, d = cfg.index_n_heads, cfg.index_head_dim
            lp.update(idx_wq=draw(next(keys), (g.q_rank, n * d), dt),
                      idx_wk=draw(next(keys), (e, d), dt),
                      idx_k_norm=jnp.ones((d,), dt),
                      idx_k_bias=jnp.zeros((d,), dt),
                      idx_w=draw(next(keys), (e, n), dt))
        if i < cfg.first_k_dense_replace:
            lp.update(
                w_gate=draw(next(keys), (e, cfg.intermediate_size), dt),
                w_up=draw(next(keys), (e, cfg.intermediate_size), dt),
                w_down=draw(next(keys), (cfg.intermediate_size, e), dt))
        else:
            lp.update(
                router=draw(next(keys), (e, cfg.experts_routed), dt),
                router_bias=jax.random.normal(
                    next(keys), (cfg.experts_routed,), jnp.float32)
                * BIAS_STD,
                w_gate_up=draw(next(keys), (held, e, 2 * f), dt),
                w_down=draw(next(keys), (held, f, e), dt),
                shared_gate=draw(next(keys), (e, cfg.shared_width), dt),
                shared_up=draw(next(keys), (e, cfg.shared_width), dt),
                shared_down=draw(next(keys), (cfg.shared_width, e), dt))
        params["layers"].append(lp)
    return params


def rope_interleave_order(r: int) -> np.ndarray:
    """`order[j]` = the interleaved (published) lane that half-split lane j
    holds: evens then odds."""
    return np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2)])


def published_weights(cfg: Dots3Config, params
                      ) -> Tuple[Dict[str, Any], Any]:
    """(the top-level tensors, a function layer index -> that layer's
    tensors) under the published names and layouts: products [out, in],
    `kv_b_proj` [H (n + v), L] a head's k_nope rows above its v rows, the
    rope columns of `q_b_proj` and `kv_a_proj_with_mqa` INTERLEAVED, the
    held experts stacked (`mlp.experts.gate_up` [held, in, 2F], expert
    `first_expert_held + e` at row e)."""
    top = {"model.embed_tokens.weight": params["embed"],
           "model.norm.weight": params["final_norm"],
           "lm_head.weight": RowsOfTransposed(params["lm_head"])}

    def layer(i: int) -> Dict[str, Any]:
        lp, g = params["layers"][i], cfg.geometry(cfg.kinds[i])
        back = np.argsort(rope_interleave_order(g.rope))
        wqb = lp["wqb"].reshape(-1, g.heads, g.nope + g.rope)
        wqb = jnp.concatenate([wqb[..., :g.nope],
                               wqb[..., g.nope:][..., back]], axis=-1)
        wkva = jnp.concatenate([lp["wkva"][:, :g.kv_rank],
                                lp["wkva"][:, g.kv_rank:][:, back]], axis=-1)
        kvb = jnp.concatenate([jnp.swapaxes(lp["w_uk"], 1, 2),
                               lp["w_uv"]], axis=-1)
        out = {
            "self_attn.q_a_proj.weight": lp["wqa"].T,
            "self_attn.q_a_layernorm.weight": lp["q_norm"],
            "self_attn.q_b_proj.weight":
                wqb.reshape(-1, g.heads * (g.nope + g.rope)).T,
            "self_attn.kv_a_proj_with_mqa.weight": wkva.T,
            "self_attn.kv_a_layernorm.weight": lp["kv_norm"],
            "self_attn.kv_b_proj.weight":
                kvb.transpose(0, 2, 1).reshape(-1, g.kv_rank),
            "self_attn.o_proj.weight": lp["wo"].T,
            "self_attn.g_proj.weight": lp["w_gate_attn"].T,
            "input_layernorm.weight": lp["input_norm"],
            "post_attention_layernorm.weight": lp["mlp_norm"]}
        if "idx_wq" in lp:
            out.update({
                "self_attn.indexer.wq_b.weight": lp["idx_wq"].T,
                "self_attn.indexer.wk.weight": lp["idx_wk"].T,
                "self_attn.indexer.k_norm.weight": lp["idx_k_norm"],
                "self_attn.indexer.k_norm.bias": lp["idx_k_bias"],
                "self_attn.indexer.weights_proj.weight": lp["idx_w"].T})
        if "router" not in lp:
            out.update({"mlp.gate_proj.weight": lp["w_gate"].T,
                        "mlp.up_proj.weight": lp["w_up"].T,
                        "mlp.down_proj.weight": lp["w_down"].T})
            return out
        out.update({
            "mlp.gate.weight": lp["router"].T,
            "mlp.gate.e_score_correction_bias": lp["router_bias"],
            "mlp.experts.gate_up": lp["w_gate_up"],
            "mlp.experts.down": lp["w_down"],
            "mlp.shared_experts.gate_proj.weight": lp["shared_gate"].T,
            "mlp.shared_experts.up_proj.weight": lp["shared_up"].T,
            "mlp.shared_experts.down_proj.weight": lp["shared_down"].T})
        return out

    return top, layer


# --------------------------------------------------------------------------- #
# The block
# --------------------------------------------------------------------------- #


def _rope(x, positions, theta: float):
    """Rotate the two HALVES of x [b, s, heads, r] at positions [b, s];
    float32 in and out."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer_norm(x, weight, bias, eps: float):
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                            + eps)
    return xf * weight.astype(jnp.float32) + bias.astype(jnp.float32)


def rescale(cfg: Dots3Config, rank: int) -> float:
    """`apply_mla_qkv_lora_rescale`: a normed latent of `rank` is scaled
    by sqrt(hidden / rank)."""
    return math.sqrt(cfg.hidden_size / rank)


def query_latent(cfg, g: Geometry, lp, h):
    """c_q [b, s, Q] in the activations' dtype: normed and rescaled."""
    cq = jnp.dot(h, lp["wqa"], preferred_element_type=jnp.float32)
    return (_rms_norm(cq, lp["q_norm"], cfg.rms_norm_eps)
            * rescale(cfg, g.q_rank)).astype(cfg.dtype)


def latent_rows(cfg, g: Geometry, lp, h, positions):
    """A token's cache row [b, s, page_width]: the normed, rescaled
    latent, the rotated rope key, zero lanes."""
    f32 = jnp.float32
    ckr = jnp.dot(h, lp["wkva"], preferred_element_type=f32)
    c = _rms_norm(ckr[..., :g.kv_rank], lp["kv_norm"], cfg.rms_norm_eps) \
        * rescale(cfg, g.kv_rank)
    k_r = _rope(ckr[..., None, g.kv_rank:], positions, g.theta)[:, :, 0]
    pad = jnp.zeros(c.shape[:-1] + (g.page_width - g.row,), f32)
    return jnp.concatenate([c, k_r, pad], axis=-1).astype(cfg.dtype)


def absorbed_query(cfg, g: Geometry, lp, cq, positions):
    """[q_lat | q_rope] [b, s, H, L + r] in the activations' dtype."""
    f32 = jnp.float32
    b, s, _ = cq.shape
    q = jnp.dot(cq, lp["wqb"], preferred_element_type=f32).reshape(
        b, s, g.heads, g.nope + g.rope)
    q_rope = _rope(q[..., g.nope:], positions, g.theta)
    q_lat = jnp.einsum("bshn,hnl->bshl", q[..., :g.nope].astype(cfg.dtype),
                       lp["w_uk"], preferred_element_type=f32)
    return jnp.concatenate([q_lat, q_rope], axis=-1).astype(cfg.dtype)


def _rope_head(x, positions, r: int, theta: float):
    """An indexer's head(s) [b, s, n, d]: the first r dims rotated."""
    return jnp.concatenate([_rope(x[..., :r], positions, theta), x[..., r:]],
                           axis=-1)


def index_query(cfg, lp, cq, h, positions):
    """(qI [b, s, n, d] in the cache's dtype, w [b, s, n] float32)."""
    b, s, _ = cq.shape
    n, d = cfg.index_n_heads, cfg.index_head_dim
    q = jnp.dot(cq, lp["idx_wq"], preferred_element_type=jnp.float32)
    q = _rope_head(q.reshape(b, s, n, d), positions, cfg.qk_rope_head_dim,
                   cfg.rope_theta)
    w = jnp.dot(h, lp["idx_w"], preferred_element_type=jnp.float32) \
        * (n ** -0.5 * d ** -0.5)
    return q.astype(cfg.dtype), w


def index_keys(cfg, lp, h, positions):
    """kI [b, s, d] in the cache's dtype: LayerNorm'ed, rotated."""
    k = jnp.dot(h, lp["idx_wk"], preferred_element_type=jnp.float32)
    k = _layer_norm(k, lp["idx_k_norm"], lp["idx_k_bias"], 1e-6)
    return _rope_head(k[:, :, None], positions, cfg.qk_rope_head_dim,
                      cfg.rope_theta)[:, :, 0].astype(cfg.dtype)


def index_accumulate(q_idx, w, arena, block_tables, positions, write_mask):
    """I(p, j) [b, s, ctx] float32 (a control replaces it)."""
    return index_scores(q_idx, w, arena, block_tables, positions, write_mask)


def select(scores, positions, topk: int):
    """(chosen [b, s, topk], count [b, s]) (a control replaces it)."""
    return select_topk(scores, topk)


def _scatter(arena, flat, rows):
    nb, bsz, width = arena.shape
    return arena.reshape(nb * bsz, width).at[flat].set(
        rows.reshape(-1, width)).reshape(nb, bsz, width)


_QUERY_GROUP = 64     # queries of a chunk selected and gathered together


def _selected_attention(cfg, g, q, scores, arena, table, positions,
                        write_mask, scale):
    """The selection and the attention over what it chose, for q [b, s, H,
    L + r] with index scores [b, s, ctx]: (o_lat, chosen, count). A chunk
    is taken `_QUERY_GROUP` queries at a time, and a group with no live
    query (the padding past a question) selects and gathers nothing: the
    gather costs what its rows cost, 2,048 a query, live or not."""

    def group(q, scores, positions, live):
        with jax.named_scope("dsa_select"):
            chosen, count = select(scores, positions, cfg.index_topk)
        o_lat = gathered_attention(q, arena, table, chosen, count, live,
                                   latent=g.kv_rank, scale=scale)
        return o_lat, chosen, count

    b, s = positions.shape
    if s == 1 or s % _QUERY_GROUP:
        return group(q, scores, positions, write_mask)

    def nothing(q, scores, positions, live):
        return (jnp.zeros(q.shape[:3] + (g.kv_rank,), q.dtype),
                jnp.zeros(positions.shape + (cfg.index_topk,), jnp.int32),
                jnp.zeros(positions.shape, jnp.int32))

    outs = []
    for at in range(0, s, _QUERY_GROUP):
        part = [a[:, at:at + _QUERY_GROUP]
                for a in (q, scores, positions, write_mask)]
        outs.append(jax.lax.cond(jnp.any(part[3]), group, nothing, *part))
    return tuple(jnp.concatenate(parts, axis=1) for parts in zip(*outs))


def attention_gate(cfg, lp, h):
    """sigmoid(h W_g) [b, s, H, 1] float32: a scalar a head."""
    return jax.nn.sigmoid(jnp.dot(
        h, lp["w_gate_attn"], preferred_element_type=jnp.float32))[..., None]


def _attention(cfg, kind, lp, h, arenas, tables, positions, write_mask,
               flats):
    """One layer's attention: (out [b, s, hidden], the layer's arenas, what
    a full layer chose (scores, chosen, count) or None)."""
    g = cfg.geometry(kind)
    b, s, _ = h.shape
    scale = 1.0 / math.sqrt(g.nope + g.rope)
    chose = None
    with jax.named_scope("mla_q"):
        cq = query_latent(cfg, g, lp, h)
        q = absorbed_query(cfg, g, lp, cq, positions)
    with jax.named_scope("mla_kv"):
        rows = latent_rows(cfg, g, lp, h, positions)
    if kind == FULL:
        arena, idx_arena = arenas
        arena = _scatter(arena, flats["full"], rows)
        with jax.named_scope("dsa_index"):
            idx_arena = _scatter(idx_arena, flats["full"],
                                 index_keys(cfg, lp, h, positions))
            q_idx, w = index_query(cfg, lp, cq, h, positions)
            scores = index_accumulate(q_idx, w, idx_arena, tables["full"],
                                      positions, write_mask)
        o_lat, chosen, count = _selected_attention(
            cfg, g, q, scores, arena, tables["full"], positions, write_mask,
            scale)
        arenas = (arena, idx_arena)
        chose = (scores, chosen, count)
    else:
        arena = _scatter(arenas, flats["window"], rows)
        with jax.named_scope("window_attn"):
            o_lat = latent_attention(
                q, arena, tables["window"], positions, write_mask,
                latent=g.kv_rank, scale=scale,
                window=cfg.sliding_window_size)
        arenas = arena
    with jax.named_scope("mla_out"):
        o = jnp.einsum("bshl,hlv->bshv", o_lat, lp["w_uv"],
                       preferred_element_type=jnp.float32)
        o = (o * attention_gate(cfg, lp, h)).astype(cfg.dtype)
        return o.reshape(b, s, -1) @ lp["wo"], arenas, chose


def _swiglu(n, w_gate, w_up, w_down):
    return (jax.nn.silu(n @ w_gate) * (n @ w_up)) @ w_down


def route(cfg, lp, n):
    """(gates [T, k] f32, index [T, k] int32) over ALL the routed experts
    (a control replaces it)."""
    _, gates, index = moe.route_sigmoid(
        n, lp["router"], lp["router_bias"], cfg.num_experts_per_tok,
        cfg.routed_scaling_factor)
    return gates, index


def routed_experts(cfg, lp, n, live, held=None, weights=None):
    """The HELD share of an expert layer on n [T, hidden] (normed): (y [T,
    hidden] f32, counts, routing f32 [2k, T]: the chosen experts above
    their gates, as the experts were handed them). `held` = (first, count)
    and `weights` = (w_gate_up, w_down) of those experts: by default the
    chip's own share and the parameters' arrays."""
    experts = cfg.experts_routed
    with jax.named_scope("moe_route"):
        gates, index = route(cfg, lp, n)
        index = jnp.where(live[:, None], index, experts)
        routing = jnp.concatenate(
            [index.astype(jnp.float32), gates], axis=-1).T
    held = held or cfg.held
    w_gate_up, w_down = weights or (lp["w_gate_up"], lp["w_down"])
    with jax.named_scope("moe_experts"):
        y, counts = moe.held_expert_forward(
            n, gates, index, w_gate_up, w_down, held, experts)
    counts["routed"] = jnp.sum(index < experts, dtype=jnp.int32)
    return y, counts, routing


def _block(cfg, kind, lp, x, arenas, tables, positions, write_mask, flats):
    dt = cfg.dtype
    h = _rms_norm(x, lp["input_norm"], cfg.rms_norm_eps).astype(dt)
    out, arenas, chose = _attention(cfg, kind, lp, h, arenas, tables,
                                    positions, write_mask, flats)
    x = x + out
    n = _rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps).astype(dt)
    if "router" not in lp:
        with jax.named_scope("mlp"):
            return x + _swiglu(n, lp["w_gate"], lp["w_up"],
                               lp["w_down"]), arenas, chose, None
    b, s, e = n.shape
    rows = n.reshape(b * s, e)
    routed, counts, routing = routed_experts(cfg, lp, rows,
                                             write_mask.reshape(-1))
    with jax.named_scope("moe_shared"):
        shared = _swiglu(rows, lp["shared_gate"], lp["shared_up"],
                         lp["shared_down"])
    y = (routed + shared.astype(jnp.float32)).astype(dt)
    return x + y.reshape(b, s, e), arenas, chose, (counts, routing, rows)


def _wide_add(counter, kind: int, amount):
    """counter [2, 2, ..] (hi, lo) int32 with `amount` [..] added under
    `kind`: hi * 2^20 + lo, so that a sum of billions does not wrap."""
    lo = counter[1, kind] + amount.astype(jnp.int32)
    return counter.at[0, kind].add(lo >> _CARRY).at[1, kind].set(
        lo & ((1 << _CARRY) - 1))


def _wide(counter, kind: int):
    return counter[0][kind].astype(np.int64) * (1 << _CARRY) \
        + counter[1][kind]


def _count(counters, kind: int, per_layer):
    load = jnp.stack([c["load"] for c in per_layer])          # [L, held]
    mean = jnp.maximum(jnp.sum(load, axis=1), 1).astype(jnp.float32) \
        / load.shape[1]
    add = {"steps": jnp.int32(1),
           "assigned": jnp.stack([c["assigned"] for c in per_layer]),
           "placed": jnp.stack([c["placed"] for c in per_layer]),
           "tiles": jnp.stack([c["tiles"] for c in per_layer]),
           "absent": jnp.stack([c["routed"] - c["assigned"]
                                for c in per_layer]),
           "drew": jnp.sum(load > 0, axis=1, dtype=jnp.int32),
           "max_load": jnp.max(load, axis=1),
           "max_over_mean": jnp.max(load, axis=1).astype(jnp.float32) / mean,
           "load": load}
    return {k: v.at[kind].add(add[k]) for k, v in counters.items()}


class Dots3(PagedModel):
    """The model the engine is handed: its configuration and what of the
    model contract differs from `PagedModel`'s defaults: TWO kinds of paged
    state. Parameters are a plain pytree (`init_params`)."""

    def __init__(self, config: Dots3Config):
        self.config = config
        if len(config.kinds) != config.num_hidden_layers:
            raise ValueError("layer_types is shorter than the layers")
        # The contract's optional answer: the kinds of paged state, the
        # first the one whose table bounds a sequence; `window` the keys a
        # query of that kind reads, its own among them (None: all).
        self.cache_kinds = {"full": {"window": None},
                            "window": {"window": config.sliding_window_size}}

    def init(self, key):
        return init_params(self.config, key)

    def paged_cache(self, num_blocks: int, block_size: int, mesh=None,
                    batch_slots: Optional[int] = None, kinds=None):
        """A full layer's arenas [num_blocks, block, 640] and [num_blocks,
        block, 128] (rows and index keys: one table), a sliding layer's
        [kinds["window"], block, 1152]; block 0 of each pool the trash
        block. `kinds`: the blocks of every kind but the first."""
        if mesh is not None:
            raise ValueError("Dots3 serves on one device (tp = 1)")
        cfg = self.config
        wide = int((kinds or {}).get("window", num_blocks))
        full, swa = cfg.geometry(FULL), cfg.geometry(SLIDING)
        i32, n_full = jnp.int32, cfg.n_full_layers
        layers, held = cfg.n_moe_layers, cfg.n_routed_experts
        latent = [jnp.zeros((num_blocks, block_size, full.page_width),
                            cfg.dtype) if k == FULL else
                  jnp.zeros((wide, block_size, swa.page_width), cfg.dtype)
                  for k in cfg.kinds]
        return {
            "latent": latent,
            "index": [jnp.zeros((num_blocks, block_size, cfg.index_head_dim),
                                cfg.dtype) for _ in range(n_full)],
            "routing": jnp.zeros((2 * cfg.num_experts_per_tok,
                                  num_blocks * block_size), jnp.float32),
            "moe": {"steps": jnp.zeros((2,), i32),
                    **{k: jnp.zeros((2, layers), i32)
                       for k in ("assigned", "placed", "tiles", "absent",
                                 "drew", "max_load")},
                    "max_over_mean": jnp.zeros((2, layers), jnp.float32),
                    "load": jnp.zeros((2, layers, held), i32)},
            "dsa": {"queries": jnp.zeros((2,), i32),
                    "visible": jnp.zeros((2, 2, n_full), i32),
                    "chosen": jnp.zeros((2, 2, n_full), i32)}}

    def paged_step(self, params, ids, cache, block_tables, row_pos,
                   write_mask, adapters=None, slots=None, last_idx=None):
        """One step: ids [b, s] at positions row_pos[b] + arange(s), a
        block table a KIND (`block_tables["full"]`, `["window"]`). Returns
        (logits [b, s, vocab], or [b, vocab] at `last_idx` [b]; the
        cache). `slots` is not looked at: nothing is kept per slot."""
        if adapters is not None:
            raise ValueError("Dots3 has no adapter banks")
        return self.paged_step_tapped(params, ids, cache, block_tables,
                                      row_pos, write_mask, last_idx)[:2]

    def paged_step_tapped(self, params, ids, cache, block_tables, row_pos,
                          write_mask, last_idx=None):
        """`paged_step` and what it computed on the way, for whoever jits
        it with these outputs kept: (logits, the cache, for every full
        layer (I [b, s, ctx], chosen [b, s, topk], count [b, s]), and of the
        FIRST expert layer its normed input [b * s, hidden] and what its
        router handed the experts, f32 [2k, b * s]). The same trace: a
        program that drops the taps is `paged_step`'s."""
        return self._step(params, ids, cache, block_tables, row_pos,
                          write_mask, last_idx)

    def forward(self, params, ids, block_size: int = 16):
        b, s = ids.shape
        per_row = -(-s // block_size)
        cache = self.paged_cache(1 + b * per_row, block_size, None, b)
        tables = 1 + jnp.arange(b * per_row, dtype=jnp.int32).reshape(
            b, per_row)
        logits, _ = self.paged_step(
            params, ids, cache, {"full": tables, "window": tables},
            jnp.zeros((b,), jnp.int32), jnp.ones((b, s), bool))
        return logits

    def _step(self, params, ids, cache, tables, row_pos, write_mask,
              last_idx):
        cfg = self.config
        bsz = cache["latent"][0].shape[1]
        positions = row_pos[:, None] + jnp.arange(ids.shape[1])[None, :]
        flats = {kind: cache_locations(table, positions, write_mask, bsz)
                 for kind, table in tables.items()}
        x = params["embed"][ids]
        latent, index, counts, chose, first_routed = [], [], [], [], None
        record, full_at = cache["routing"], 0
        for kind, lp, arena in zip(cfg.kinds, params["layers"],
                                   cache["latent"]):
            arenas = arena
            if kind == FULL:
                arenas = (arena, cache["index"][full_at])
                full_at += 1
            x, arenas, chosen, routed = _block(
                cfg, kind, lp, x, arenas, tables, positions, write_mask,
                flats)
            if kind == FULL:
                latent.append(arenas[0])
                index.append(arenas[1])
                chose.append(chosen)
            else:
                latent.append(arenas)
            if routed is None:
                continue
            if not counts:                     # the first expert layer
                first_routed = (routed[2], routed[1])
                with jax.named_scope("moe_record"):
                    record = record.at[:, flats["full"]].set(routed[1])
            counts.append(routed[0])
        if last_idx is not None:
            x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
            logits = jnp.dot(x.astype(cfg.dtype), params["lm_head"],
                             preferred_element_type=jnp.float32)
        kind = int(ids.shape[1] > 1)
        counters = _count(cache["moe"], kind, counts) if counts \
            else cache["moe"]
        live = write_mask.astype(jnp.int32)
        dsa = cache["dsa"]
        dsa = {"queries": dsa["queries"].at[kind].add(jnp.sum(live)),
               "visible": _wide_add(dsa["visible"], kind, jnp.broadcast_to(
                   jnp.sum((positions + 1) * live), (len(chose),))),
               "chosen": _wide_add(dsa["chosen"], kind, jnp.stack(
                   [jnp.sum(c[2] * live) for c in chose]))}
        return logits, {"latent": latent, "index": index, "routing": record,
                        "moe": counters, "dsa": dsa}, chose, first_routed

    # ------------------------------------------------- counters (finding f)

    def cache_counters(self, cache):
        return {"moe": cache["moe"], "dsa": cache["dsa"]}

    def counter_stats(self, host) -> Dict[str, Any]:
        """`stats()["moe"]` as `deepseek_v3` gives it (cumulative, decode
        steps and chunks apart; `absent`: assignments routed to experts this
        chip does not hold; `max_load`: the fullest held expert's rows), and
        `stats()["dsa"]`: the queries of live rows, and a full layer the
        keys they could see and the keys the selection chose."""
        cfg = self.config
        dsa = {"full_layers": cfg.n_full_layers, "topk": cfg.index_topk}
        for k, kind in enumerate(KINDS):
            visible = _wide(host["dsa"]["visible"], k)
            chosen = _wide(host["dsa"]["chosen"], k)
            dsa[kind] = {"queries": int(host["dsa"]["queries"][k]),
                         "keys_visible": int(np.sum(visible)),
                         "keys_chosen": int(np.sum(chosen))}
        host = host["moe"]
        out: Dict[str, Any] = {"layers": cfg.n_moe_layers,
                               "experts": cfg.experts_routed,
                               "held": list(cfg.held),
                               "top_k": cfg.num_experts_per_tok}
        for k, kind in enumerate(KINDS):
            steps = int(host["steps"][k])
            sums = {name: float(np.sum(host[name][k]))
                    for name in ("assigned", "placed", "tiles", "absent",
                                 "drew", "max_load", "max_over_mean")}
            calls = max(1, steps * cfg.n_moe_layers)
            out[kind] = {
                "steps": steps, **sums,
                "assignments_per_step": sums["assigned"] / calls,
                "experts_drawn_per_step": sums["drew"] / calls,
                "max_load_per_step": sums["max_load"] / calls,
                "load_max_over_mean": sums["max_over_mean"] / calls}
        out["load"] = [int(v) for v in np.sum(host["load"], axis=(0, 1))]
        return {"moe": out, "dsa": dsa}
