"""Llama-family decoder in flax, sharding-annotated, with paged KV-cache
decode.

Second model family (BASELINE.json names a Llama Serve deployment next to
the GPT-2 trainer): RMSNorm, rotary position embeddings, SwiGLU MLP,
grouped-query attention, untied LM head — the same logical-axis annotations
as `gpt2.py` (tp shards heads/mlp, dp/fsdp shard batch, sp shards seq), so
`make_train_step`/`mesh_shardings_for` work unchanged.

Two forward paths share parameters:
- `__call__(input_ids)` — full-sequence training forward (flash attention).
- `decode_paged(input_ids, arenas, block_tables, pos, write_mask)` —
  incremental inference against a PAGED cache (vLLM/PagedAttention shape):
  prefill writes the prompt's K/V once, each decode step attends a 1-token
  query over the cache (O(context) memory reads instead of an O(context^2)
  recompute per token). K/V live in a shared fixed-size block arena; each
  row's block table maps logical blocks to physical ones, so the
  continuous-batching engine (`ray_tpu/inference/`) can admit/evict/preempt
  sequences without ever reshaping the cache — one compiled program per
  (batch, step-width) shape, forever. Writes scatter into the arena in
  place; reads go through `ops/paged_attention.py`, whose Pallas kernel
  walks each row's block table and copies only the live blocks (GQA inside,
  nothing repeated or upcast in HBM). It is the one cache the model has.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models._nn import (RMSNorm, apply_rope, dense,
                                paged_write_and_attend)
from ray_tpu.models._served import PagedModel
from ray_tpu.ops.attention import flash_attention_sharded, mha_reference


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000          # 250 * 128: already MXU-aligned
    n_positions: int = 4096
    n_embd: int = 4096
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 8               # grouped-query attention
    intermediate: int = 11008        # SwiGLU hidden width
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_flash: bool = True
    remat: bool = False
    # Sequence parallelism: a mesh with an "sp" axis routes the training
    # forward's attention through the ring (ops/ring_attention) — each
    # device holds a sequence shard, K/V rotate over ppermute. None (or
    # a mesh without "sp") keeps the flash/reference path.
    sp_mesh: Any = None

    @staticmethod
    def llama7b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def small() -> "LlamaConfig":
        """~110M-param config for single-chip experiments."""
        return LlamaConfig(n_embd=768, n_layer=12, n_head=12, n_kv_head=4,
                           intermediate=2048, n_positions=2048)

    @staticmethod
    def tiny(seq: int = 128) -> "LlamaConfig":
        return LlamaConfig(vocab_size=512, n_positions=seq, n_embd=128,
                           n_layer=2, n_head=4, n_kv_head=2,
                           intermediate=352, use_flash=False)

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


def llama_preset(name: str, seq: int = 256) -> LlamaConfig:
    """The preset a deployment names (`LLMServer`): tiny (at `seq`
    positions), small or 7b."""
    presets = {"tiny": lambda: LlamaConfig.tiny(seq=seq),
               "small": LlamaConfig.small, "7b": LlamaConfig.llama7b}
    if name not in presets:
        raise ValueError(f"unknown model size {name!r} "
                         f"(expected one of {sorted(presets)})")
    return presets[name]()


class _Rows(NamedTuple):
    """One ROW GROUP of a step: b sequences of s positions each (a decode
    step's [slots, 1], a prefill chunk's [1, c]) and what belongs to them
    as sequences: where they write and what they attend."""
    block_tables: Any
    positions: Any
    write_mask: Any


def _cut(t, groups):
    """t [b, s, ...] of one group as it is, or [1, T, ...] of several laid
    end to end cut into each group's [b, s, ...]: slices of rows, the
    products before them run once over all T."""
    if len(groups) == 1:
        return [t]
    out, start = [], 0
    for rows in groups:
        b, s = rows.positions.shape
        out.append(t[0, start:start + b * s].reshape(b, s, *t.shape[2:]))
        start += b * s
    return out


def _joined(parts):
    """The groups' [b, s, n] laid end to end again, [1, T, n]."""
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate([p.reshape(1, -1, p.shape[-1]) for p in parts],
                           axis=1)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, cache: Optional[Tuple] = None,
                 lora: Optional[Tuple] = None):
        """cache=None: full causal forward at `positions`; returns (x,
        None, side).
        cache=(k_arena, v_arena, groups) with arenas [num_blocks,
        block_size, kv_heads, head_dim] and `groups` the step's ROW GROUPS
        (`_Rows`, which state their own positions: `positions` is not
        looked at). One group is a decode step or a prefill chunk, x [b, s,
        embd]; several are laid end to end, x [1, T, embd], through
        everything that is a token's own (the norms and every product: T
        rows against one read of the weights) and cut apart for what
        belongs to a sequence, a `paged_write_and_attend` a group at its
        own program's shape, a later group over the arenas an earlier one
        left. A group's K/V land at the physical slot the row's block
        table maps each position to (masked-off tokens go to trash block
        0), reads are `paged_attention` over the arena as the writes left
        it: each row sees its logical positions <= the query's, out of the
        blocks its table maps, and only those below its live length are
        touched. Returns (x, (k_arena, v_arena), side).

        lora=(aq, bq, ao, bo, adapter_idx): model-multiplexed low-rank
        LATE-FUSION deltas (ladder-style side adapter), for a step of ONE
        group. The block reads two backbone taps — the attn-normed input (aq/bq) and the
        flattened attention mixer output (ao/bo) — and returns their
        low-rank projection as a SIDE contribution instead of adding it
        to the residual stream; the caller accumulates the per-layer
        sides and merges the sum once, before the final norm. Because
        the residual stream itself is untouched, every layer's K/V is
        bit-identical to the base model's no matter which adapter ran:
        the paged arena is ADAPTER-INVARIANT and the radix prefix cache
        shares cached blocks across tenants exactly. (A classic
        in-place q/o delta would NOT have this property: perturbing one
        layer's output perturbs every deeper layer's K/V.) The banks
        hold one row per resident adapter ([n_rows, ...]; row 0 is the
        zero identity) and `adapter_idx` [b] routes each BATCH ROW to
        its adapter — routing is data, so one compiled program serves
        every adapter mix and loading/evicting an adapter never
        recompiles."""
        cfg = self.cfg
        hd = cfg.head_dim
        groups = [_Rows(None, positions, None)] if cache is None \
            else cache[2]

        def heads(t, n):
            return [p.reshape(*p.shape[:2], n, hd).transpose(0, 2, 1, 3)
                    for p in _cut(t, groups)]

        h = RMSNorm(cfg, name="attn_norm")(x)
        q = dense(cfg.n_head * hd, ("embed", "heads"), cfg, "wq")(h)
        k = dense(cfg.n_kv_head * hd, ("embed", "heads"), cfg, "wk")(h)
        v = dense(cfg.n_kv_head * hd, ("embed", "heads"), cfg, "wv")(h)
        # (the three products, then the three layouts, then the rotary: the
        # one-group programs' lowered text is held to what it was)
        q, k, v = heads(q, cfg.n_head), heads(k, cfg.n_kv_head), \
            heads(v, cfg.n_kv_head)
        q = [apply_rope(t, rows.positions, cfg.rope_theta)
             for t, rows in zip(q, groups)]
        k = [apply_rope(t, rows.positions, cfg.rope_theta)
             for t, rows in zip(k, groups)]

        if cache is None:
            (q,), (k,), (v,) = q, k, v
            kv_groups = cfg.n_head // cfg.n_kv_head
            kf = jnp.repeat(k, kv_groups, axis=1)
            vf = jnp.repeat(v, kv_groups, axis=1)
            if cfg.sp_mesh is not None:
                from ray_tpu.ops.ring_attention import ring_attention_sharded

                # GQA repeat happens BEFORE the ring so every sequence
                # shard rotates full-head K/V chunks — same tensors the
                # flash path sees, so sp on/off is a pure schedule change.
                attn = ring_attention_sharded(q, kf, vf, cfg.sp_mesh,
                                              causal=True)
            elif cfg.use_flash:
                attn = flash_attention_sharded(
                    q, kf, vf, nn.logical_to_mesh_axes(
                        ("batch", "heads", None, None)))
            else:
                attn = mha_reference(q, kf, vf, causal=True)
            attn, new_cache = [attn], None
        else:
            k_arena, v_arena, _ = cache
            attn = []
            # A group at a time, each call at its own program's shape.
            for rows, qr, kr, vr in zip(groups, q, k, v):
                out, k_arena, v_arena = paged_write_and_attend(
                    qr, kr, vr, k_arena, v_arena, rows.block_tables,
                    rows.positions, rows.write_mask)
                attn.append(out)
            new_cache = (k_arena, v_arena)
        attn = _joined([a.transpose(0, 2, 1, 3).reshape(
            a.shape[0], a.shape[2], cfg.n_head * hd) for a in attn])
        out = dense(cfg.n_embd, ("heads", "embed"), cfg, "wo")(attn)
        side = None
        if lora is not None:
            aq, bq, ao, bo, aidx = lora
            # Per-row bank gather, then two thin einsums per tap: the
            # delta path costs O(b*s*e*r) next to the dense O(b*s*e*f).
            # Compute in the model dtype end to end — bit-identical to a
            # dedicated replica running the same bank row alone. The sum
            # is RETURNED, never added to x: the residual stream (and so
            # the K/V written above) stays base-model-pure.
            s_in = jnp.einsum("bsr,bre->bse",
                              jnp.einsum("bse,ber->bsr", h, aq[aidx]),
                              bq[aidx])
            s_attn = jnp.einsum("bsr,bre->bse",
                                jnp.einsum("bsf,bfr->bsr", attn, ao[aidx]),
                                bo[aidx])
            side = (s_in + s_attn).astype(cfg.dtype)
        x = x + out

        h2 = RMSNorm(cfg, name="mlp_norm")(x)
        gate = dense(cfg.intermediate, ("embed", "mlp"), cfg, "w_gate")(h2)
        up = dense(cfg.intermediate, ("embed", "mlp"), cfg, "w_up")(h2)
        h2 = nn.silu(gate) * up
        x = x + dense(cfg.n_embd, ("mlp", "embed"), cfg, "w_down")(h2)
        return nn.with_logical_constraint(x, ("batch", "seq", "embed")), \
            new_cache, side


@functools.partial(jax.jit, static_argnums=0)
def _block_traced_once(cfg, layer_params, x, k_arena, v_arena, groups):
    """`LlamaBlock` over several row groups as a jitted function of one
    layer's parameters: the layers are alike, so a program traces and
    lowers the block ONCE and calls it a layer (XLA inlines the calls: the
    executable is the same). The fused step is a THIRD program a replica
    loads before it serves, and this takes 1.2 s of tracing and lowering
    sixteen layers off that (PERF.md section 6, PR 67). The one-group
    programs call the blocks as they always have: their lowered text is
    held to what it was."""
    x, cache, _ = LlamaBlock(cfg, parent=None).apply(
        {"params": layer_params}, x, None, cache=(k_arena, v_arena, groups))
    return x, cache


class Llama(nn.Module, PagedModel):
    config: LlamaConfig

    def setup(self):
        cfg = self.config
        self.embed = self.param(
            "embed",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.vocab_size, cfg.n_embd), cfg.param_dtype)
        block = LlamaBlock
        if cfg.remat:
            block = nn.remat(LlamaBlock, static_argnums=())
        self.blocks = [block(cfg, name=f"layer_{i}")
                       for i in range(cfg.n_layer)]
        self.final_norm = RMSNorm(cfg, name="final_norm")
        self.lm_head = dense(cfg.vocab_size, ("embed", "vocab"), cfg,
                             "lm_head")

    def __call__(self, input_ids):
        cfg = self.config
        b, s = input_ids.shape
        x = self.embed.astype(cfg.dtype)[input_ids]
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        positions = jnp.arange(s)
        for blk in self.blocks:
            x, _, _ = blk(x, positions)
        x = self.final_norm(x)
        logits = self.lm_head(x)
        return nn.with_logical_constraint(logits, ("batch", "seq", "vocab"))

    def decode_paged(self, input_ids, arenas, block_tables, row_pos,
                     write_mask, lora_banks=None, adapter_idx=None,
                     last_idx=None):
        """Step-shaped paged decode: the continuous-batching engine's
        entry point. `input_ids` [b, s] are each row's next s tokens
        (s = 1 for decode steps, s = chunk for chunked prefill),
        `arenas` is the per-layer [(k, v)] block arena shared by every
        sequence, `block_tables` [b, max_blocks] maps each row's logical
        blocks to physical ones, `row_pos` [b] is each row's first write
        position, and `write_mask` [b, s] zeroes batch/chunk padding
        (masked writes land in trash block 0). Returns (logits [b, s,
        vocab], new_arenas) — all shapes static, so one jitted program
        per (b, s) serves the engine forever.

        `lora_banks` (per-layer [(aq, bq, ao, bo)]) + `adapter_idx` [b]
        turn on model multiplexing: each batch row gets its adapter's
        per-layer low-rank LATE-FUSION deltas (row 0 = identity). Every
        layer contributes a side term read off the backbone's
        activations; the accumulated sum merges into the hidden state
        ONCE, before the final norm — the residual stream and all K/V
        writes stay base-model-pure, so cached prefix blocks are
        shareable across adapters exactly. The banks are fixed-shape
        arguments, so N adapters still compile the SAME two programs
        and adapter churn is pure data movement.

        `last_idx` [b] asks for logits at one position a row only: the
        hidden state is gathered there BEFORE the final norm and the
        head, which then run on [b, embd] (returns logits [b, vocab])."""
        def read(x):
            if last_idx is None:
                return x
            return jnp.take_along_axis(x, last_idx[:, None, None],
                                       axis=1)[:, 0]

        return self._step(
            arenas, [(input_ids, block_tables, row_pos, write_mask)], read,
            lora_banks, adapter_idx)

    def decode_paged_with_chunk(self, tokens, chunk_ids, arenas,
                                block_tables, row_pos, write_mask, chunk_bt,
                                chunk_pos, chunk_wmask, last_idx):
        """A decode step with one sequence's prefill chunk aboard:
        `decode_paged` of tokens [b, 1] and `decode_paged` of chunk_ids
        [1, c] at `last_idx` [1] as ONE execution, in which every weight
        is read once for the c + b rows, only the attention is two calls
        and the head runs on b + 1 rows. The chunk's rows lie first: the
        engine masks the chunk's slot among the decode rows, so the two
        groups write disjoint rows and either order is the same step, and
        an operand whose first 512 rows are the chunk's read 0.2 ms under
        the other order on the chip (PERF.md section 6, PR 67). Returns
        (the decode rows' logits [b, vocab], the chunk's [1, vocab],
        new_arenas)."""
        b, c = tokens.shape[0], chunk_ids.shape[1]
        logits, arenas = self._step(
            arenas,
            [(chunk_ids, chunk_bt, chunk_pos, chunk_wmask),
             (tokens, block_tables, row_pos, write_mask)],
            lambda x: jnp.concatenate([x[0, c:], x[0, last_idx]]))
        return logits[:b], logits[b:], arenas

    def _step(self, arenas, groups, read, lora_banks=None,
              adapter_idx=None):
        """The one body of a step over ROW GROUPS, each (ids [b, s],
        block_tables, row_pos, write_mask): (the logits of the rows `read`
        picks from the last block's x, new_arenas). One group is a decode
        step or a prefill chunk, x [b, s, embd]; several are laid end to
        end, x [1, T, embd] (`LlamaBlock`), and their layers are traced
        once (`_block_traced_once`). The adapter banks are one group's."""
        cfg = self.config
        several = len(groups) > 1
        ids = jnp.concatenate([g[0].reshape(1, -1) for g in groups],
                              axis=1) if several else groups[0][0]
        x = self.embed.astype(cfg.dtype)[ids]
        rows = [_Rows(block_tables,
                      row_pos[:, None] + jnp.arange(g_ids.shape[1])[None, :],
                      write_mask)
                for g_ids, block_tables, row_pos, write_mask in groups]
        new_arenas = []
        side_sum = None
        for i, blk in enumerate(self.blocks):
            k_a, v_a = arenas[i]
            lora = None
            if lora_banks is not None:
                aq, bq, ao, bo = lora_banks[i]
                lora = (aq, bq, ao, bo, adapter_idx)
            if several:
                x, layer_cache = _block_traced_once(
                    cfg, blk.variables["params"], x, k_a, v_a, rows)
                side = None
            else:
                x, layer_cache, side = blk(x, None, cache=(k_a, v_a, rows),
                                           lora=lora)
            if side is not None:
                side_sum = side if side_sum is None else side_sum + side
            new_arenas.append(layer_cache)
        if side_sum is not None:
            x = x + side_sum.astype(x.dtype)
        x = self.final_norm(read(x))
        return self.lm_head(x), new_arenas

    # What the serving engine asks of the model it is handed
    # (`PagedModel`): what differs from its defaults, each a call into the
    # functions of this file. A prefix of KV blocks alone restores a
    # sequence and a slot holds nothing of its own, as the base says.
    # `nowrap`: they run on the unbound module, outside `apply`; the
    # mixin's own methods are not the module's, so flax leaves them alone.

    @nn.nowrap
    def paged_cache(self, num_blocks: int, block_size: int, mesh=None,
                    batch_slots: Optional[int] = None):
        """The paged cache, opaque to the engine: `make_paged_arena`,
        sharded with the kv heads under a tp mesh. Nothing in it is per
        slot, so `batch_slots` is not used."""
        sharding = None if mesh is None else arena_sharding(self.config, mesh)
        return make_paged_arena(self.config, num_blocks, block_size,
                                sharding=sharding)

    @nn.nowrap
    def paged_step(self, params, ids, cache, block_tables, row_pos,
                   write_mask, adapters=None, slots=None, last_idx=None):
        """One step, `decode_paged`: (logits [b, s, vocab], cache), or
        logits [b, vocab] at `last_idx` [b] where it is given.
        `adapters` is None or (banks, adapter_idx). `slots` (each row's
        batch slot) is not used: the block tables say everything."""
        banks, adapter_idx = adapters or (None, None)
        return self.apply(params, ids, cache, block_tables, row_pos,
                          write_mask, banks, adapter_idx, last_idx,
                          method=Llama.decode_paged)

    @nn.nowrap
    def paged_step_with_chunk(self, params, tokens, chunk_ids, cache,
                              block_tables, row_pos, write_mask, chunk_bt,
                              chunk_pos, chunk_wmask, chunk_slot, last_idx):
        """A decode step with one sequence's prefill chunk aboard (the
        contract's optional answer), `decode_paged_with_chunk`. The engine
        masks the chunk's own slot among the decode rows and keeps the two
        programs alone where it holds adapter banks or speculates.
        `chunk_slot` is not looked at: nothing is kept per slot."""
        return self.apply(params, tokens, chunk_ids, cache, block_tables,
                          row_pos, write_mask, chunk_bt, chunk_pos,
                          chunk_wmask, last_idx,
                          method=Llama.decode_paged_with_chunk)

    @nn.nowrap
    def place_on_mesh(self, params, mesh):
        """(params in their tp layout, the mesh's tp degree)."""
        return shard_params_tp(self, params, mesh), _mesh_tp(mesh)

    @nn.nowrap
    def early_exit_draft(self, params):
        """(draft model, its params) for speculation when none was
        injected: the first n_layer // 2 blocks under this model's own
        embedding, final norm and head, every leaf shared by reference.
        It agrees with the target on easy tokens for free."""
        cfg = replace(self.config, n_layer=max(1, self.config.n_layer // 2))
        inner = params["params"] if "params" in params else params
        keep = ("embed", "final_norm", "lm_head",
                *(f"layer_{i}" for i in range(cfg.n_layer)))
        return Llama(cfg), {"params": {k: inner[k] for k in keep}}

    @nn.nowrap
    def adapter_banks(self, n_rows: int, rank: int, mesh=None):
        """What `AdapterManager` holds: (layers, one layer's bank
        shapes, their dtype, their shardings on `mesh` or None)."""
        cfg = self.config
        return (cfg.n_layer, lora_bank_shapes(cfg, n_rows, rank),
                jnp.dtype(cfg.dtype),
                None if mesh is None else lora_bank_shardings(cfg, mesh))


# --------------------------------------------------------------------------- #
# Pipeline stages: the model partitioned by layer for cross-process pp
# --------------------------------------------------------------------------- #


def stage_layer_ranges(cfg: LlamaConfig, pp: int):
    """[start, end) layer range per pipeline stage: near-even split, the
    remainder to the EARLIER stages (the last stage already carries the
    final norm + vocab-wide lm_head matmul)."""
    if not 1 <= pp <= cfg.n_layer:
        raise ValueError(f"pp={pp} must be in [1, n_layer={cfg.n_layer}]")
    base, rem = divmod(cfg.n_layer, pp)
    ranges, start = [], 0
    for s in range(pp):
        end = start + base + (1 if s < rem else 0)
        ranges.append((start, end))
        start = end
    return ranges


class LlamaStage(nn.Module):
    """One pipeline stage of :class:`Llama`: stage 0 owns the embedding
    + its layer range, the last stage its range + final norm + lm_head.
    Param names match the monolithic model exactly (``layer_{i}`` keeps
    the GLOBAL layer index), so a full checkpoint splits into stage
    trees — and re-groups across pp widths — by top-level key alone."""

    cfg: LlamaConfig
    stage: int
    pp: int

    def setup(self):
        cfg = self.cfg
        start, end = stage_layer_ranges(cfg, self.pp)[self.stage]
        if self.stage == 0:
            self.embed = self.param(
                "embed",
                nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                             ("vocab", "embed")),
                (cfg.vocab_size, cfg.n_embd), cfg.param_dtype)
        block = LlamaBlock
        if cfg.remat:
            block = nn.remat(LlamaBlock, static_argnums=())
        self.blocks = [block(cfg, name=f"layer_{i}")
                       for i in range(start, end)]
        if self.stage == self.pp - 1:
            self.final_norm = RMSNorm(cfg, name="final_norm")
            self.lm_head = dense(cfg.vocab_size, ("embed", "vocab"), cfg,
                                 "lm_head")

    def __call__(self, x):
        """Stage 0 takes token ids [b, s]; later stages take the
        previous stage's activations [b, s, embd]. The last stage
        returns logits, every other stage its boundary activations."""
        cfg = self.cfg
        if self.stage == 0:
            x = self.embed.astype(cfg.dtype)[x]
            x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        positions = jnp.arange(x.shape[1])
        for blk in self.blocks:
            x, _, _ = blk(x, positions)
        if self.stage == self.pp - 1:
            x = self.final_norm(x)
            logits = self.lm_head(x)
            return nn.with_logical_constraint(logits,
                                              ("batch", "seq", "vocab"))
        return x


def split_stage_params(params, cfg: LlamaConfig, pp: int):
    """Full param dict (``embed``/``layer_i``/``final_norm``/``lm_head``
    at top level) -> one per-stage dict per stage. Pure re-grouping:
    leaves are shared, never copied, and keys keep their global names —
    the inverse of :func:`merge_stage_params` at ANY pp width."""
    inner = params.get("params", params) if isinstance(params, dict) \
        else params
    out = []
    for s, (start, end) in enumerate(stage_layer_ranges(cfg, pp)):
        tree = {}
        if s == 0:
            tree["embed"] = inner["embed"]
        for i in range(start, end):
            tree[f"layer_{i}"] = inner[f"layer_{i}"]
        if s == pp - 1:
            tree["final_norm"] = inner["final_norm"]
            tree["lm_head"] = inner["lm_head"]
        out.append(tree)
    return out


def merge_stage_params(stage_trees):
    """Union of per-stage param dicts back into the full model tree
    (global key names make this a plain dict merge)."""
    out = {}
    for tree in stage_trees:
        dup = set(out) & set(tree)
        if dup:
            raise ValueError(f"stage trees overlap on {sorted(dup)} — "
                             "these are not disjoint stage splits")
        out.update(tree)
    return out


def _partition_rules():
    """The ``match_partition_rules`` regex table for llama params over a
    ("sp", "tp") stage mesh: column-parallel qkv/gate/up (output dim over
    tp), row-parallel wo/w_down (input dim over tp), vocab-sharded embed
    and lm_head, replicated norms. One table serves every stage subtree
    at every (tp, pp) width — rule paths are global param names."""
    from jax.sharding import PartitionSpec

    return (
        (r"embed$", PartitionSpec("tp")),
        (r"(wq|wk|wv)/kernel$", PartitionSpec(None, "tp")),
        (r"wo/kernel$", PartitionSpec("tp")),
        (r"(w_gate|w_up)/kernel$", PartitionSpec(None, "tp")),
        (r"w_down/kernel$", PartitionSpec("tp")),
        (r"lm_head/kernel$", PartitionSpec(None, "tp")),
        (r"(attn_norm|mlp_norm|final_norm)/scale$", PartitionSpec()),
    )


LLAMA_PARTITION_RULES = _partition_rules()


def shard_stage_params(stage_tree, mesh):
    """Place one stage's param subtree on its ("sp", "tp") stage mesh
    via the rule table (axes absent from the mesh prune to replicated,
    so tp=1 stage meshes work unchanged)."""
    from ray_tpu.parallel.sharding import shard_params_by_rules

    return shard_params_by_rules(stage_tree, mesh, LLAMA_PARTITION_RULES)


def make_paged_arena(cfg: LlamaConfig, num_blocks: int, block_size: int,
                     sharding=None):
    """Preallocated per-layer (k, v) paged arena [num_blocks, block_size,
    kv_heads, head_dim]. Block 0 is the trash block (never allocated to a
    sequence): masked writes land there and nothing ever reads it.
    `sharding` (from :func:`arena_sharding`) lays each arena out sharded
    on its kv-head dim — the paged cache shards WITH the attention heads,
    so a tp-sharded decode never gathers K/V across devices."""
    shape = (num_blocks, block_size, cfg.n_kv_head, cfg.head_dim)
    if sharding is None:
        def zeros():
            return jnp.zeros(shape, cfg.dtype)
    else:
        # Allocate DIRECTLY into the sharded layout: a device_put of a
        # host/default-device zeros array would transiently commit the
        # whole arena to one device — at real tp widths that excess can
        # OOM device 0 at startup even though the sharded steady state
        # fits. One jitted zeros program, executed 2*n_layer times.
        zeros = jax.jit(lambda: jnp.zeros(shape, cfg.dtype),
                        out_shardings=sharding)
    return [(zeros(), zeros()) for _ in range(cfg.n_layer)]


# --------------------------------------------------------------------------- #
# LoRA adapter banks: model multiplexing on one compiled program set
# --------------------------------------------------------------------------- #


def lora_bank_shapes(cfg: LlamaConfig, n_rows: int, rank: int):
    """Per-layer bank shapes (aq, bq, ao, bo): one row per resident
    adapter, row 0 reserved as the zero identity. Both pairs are
    LATE-FUSION taps targeting the embedding: aq/bq read the block's
    attn-normed input, ao/bo the flattened attention mixer output. The
    deltas never enter the residual stream (they merge once, before the
    final norm), which keeps the paged KV arena adapter-invariant — the
    radix prefix cache shares cached blocks across tenants because of
    it."""
    return ((n_rows, cfg.n_embd, rank),
            (n_rows, rank, cfg.n_embd),
            (n_rows, cfg.n_head * cfg.head_dim, rank),
            (n_rows, rank, cfg.n_embd))


def lora_bank_shardings(cfg: LlamaConfig, mesh):
    """NamedShardings for one layer's (aq, bq, ao, bo) bank: ao's INPUT
    dim splits over "tp" WITH the flattened heads it reads (its rank-dim
    partial sums reduce exactly where wo's do); aq, bq and bo replicate
    (rank/embed dims are tiny or already replicated). Mirrors
    arena_sharding's no-trailing-None discipline so bank reloads can
    never perturb the jit cache key."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    validate_tp(cfg, _mesh_tp(mesh))
    rep = NamedSharding(mesh, P())
    return (rep,
            rep,
            NamedSharding(mesh, P(None, "tp")),
            rep)


def make_adapter_weights(cfg: LlamaConfig, rank: int, seed: int,
                         scale: float = 0.05):
    """Deterministic per-layer LoRA rows from a seed: the SAME seed
    always yields the SAME weights, so a respawned replica reloading an
    adapter on demand — or a dedicated replica built for the parity
    proof — is bit-identical to the original. Returns per-layer
    (aq_row, bq_row, ao_row, bo_row) numpy arrays in the model dtype."""
    import numpy as np

    rng = np.random.default_rng(seed)
    dt = jnp.dtype(cfg.dtype)
    out = []
    for _ in range(cfg.n_layer):
        rows = []
        for _, *shape in lora_bank_shapes(cfg, 1, rank):
            w = rng.standard_normal(shape, dtype=np.float32) * scale
            rows.append((w * 1.0).astype(dt))  # ml_dtypes casts in numpy
        out.append(tuple(rows))
    return out


# --------------------------------------------------------------------------- #
# Tensor-parallel path: NamedSharding placement over a "tp" mesh axis
# --------------------------------------------------------------------------- #


def validate_tp(cfg: LlamaConfig, tp: int) -> None:
    """Fail fast on widths XLA can't shard evenly: attention heads, KV
    heads (the paged arena shards with them), the SwiGLU hidden width and
    the vocab all split over tp."""
    bad = {name: dim for name, dim in (
        ("n_head", cfg.n_head), ("n_kv_head", cfg.n_kv_head),
        ("intermediate", cfg.intermediate), ("vocab_size", cfg.vocab_size))
        if dim % tp}
    if bad:
        raise ValueError(
            f"tp={tp} does not divide {bad} — pick a tp width that "
            "divides heads, kv heads, the MLP hidden and the vocab")


def tp_shardings(model: "Llama", mesh):
    """NamedSharding pytree for the params on `mesh` (logical axes ->
    mesh axes via the standard rules: heads/mlp/vocab shard over "tp")."""
    from ray_tpu.models.gpt2 import mesh_shardings_for

    return mesh_shardings_for(model, mesh, (1, 8))


def shard_params_tp(model: "Llama", params, mesh):
    """device_put an (un)sharded param pytree into its tp layout —
    resharding is a no-op placement when the layout already matches, so
    this is safe on freshly-initialized and checkpoint-restored trees
    alike."""
    import jax

    validate_tp(model.config, _mesh_tp(mesh))
    return jax.device_put(params, tp_shardings(model, mesh))


def arena_sharding(cfg: LlamaConfig, mesh):
    """NamedSharding for the paged KV arena: kv-head dim over "tp"
    ([num_blocks, block_size, kv_heads, head_dim] -> P(None, None, "tp",
    None)), the same split as the attention heads that read it."""
    import jax

    validate_tp(cfg, _mesh_tp(mesh))
    # No trailing None: jit normalizes output specs by dropping it, and a
    # device_put layout that differs only in the trailing None is a
    # DIFFERENT jit cache key — the engine's compile-once discipline
    # (fresh arenas after fail_all mixing with donated step outputs)
    # depends on the two being identical.
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, None, "tp"))


def _mesh_tp(mesh) -> int:
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(axes.get("tp", 1))


def flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Training FLOPs/token: 6N for the matmuls + attention term."""
    per_layer = (2 * cfg.n_embd * (cfg.n_head + 2 * cfg.n_kv_head)
                 * cfg.head_dim                       # qkv
                 + cfg.n_head * cfg.head_dim * cfg.n_embd  # out proj
                 + 3 * cfg.n_embd * cfg.intermediate)      # swiglu
    n = cfg.n_layer * per_layer + 2 * cfg.vocab_size * cfg.n_embd
    attn = 12 * cfg.n_layer * cfg.n_embd * seq_len
    return 6.0 * n + 2.0 * attn
