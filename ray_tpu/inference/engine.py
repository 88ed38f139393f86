"""Continuous-batching inference engine (Orca-style iteration scheduling).

The serving batch is re-formed every decode step instead of every request:
finished sequences leave their batch slot immediately, queued requests are
admitted into freed slots, and long prompts prefill in fixed-size chunks
interleaved with decode steps so token emission never stalls behind a new
arrival. K/V lives in a paged cache: `kv_cache.BlockManager` keeps the
block tables, the model keeps the tensors. When the cache runs out of
blocks the engine preempts the lowest-priority sequence — frees its blocks
and re-queues it for recompute — so the answer to memory pressure is
degraded latency, never an OOM.

The engine knows nothing of the model's family and imports none. It is
handed `model` and `params`, and what it asks of the model is stated once,
as code: `ray_tpu.models._served.PagedModel` (`paged_cache`, `paged_step`,
the attributes `prefix_restores`, `slot_state_bytes`, `pageless_context`,
the optional `paged_step_with_chunk`, `decode_block`, `cache_kinds` and
`cache_counters`, and `place_on_mesh`, `early_exit_draft`, `adapter_banks`). The engine reads
each of them directly: a model derives from `PagedModel`, whose defaults
say "the model does not offer it", so a misspelt answer is an error and not
a silent no (docs/INFERENCE.md, "The model contract", has the reasons and
the findings).

Two jitted programs serve every request mix, each compiled exactly once:

- prefill: [1, prefill_chunk] tokens of one sequence (padded chunk),
- decode:  [batch_slots, 1] — one token for every running slot.

A model that offers the fused step gets a third, a decode step with a
chunk aboard: where a step has a chunk to run AND a row decoding, the one
execution takes the place of the prefill execution and the decode
execution after it (not under speculation, nor with adapter banks). A
chunk that finds no row decoding runs alone as before; no admission waits
for company. The row whose prompt ends aboard decodes from the next step.

All thread a device-resident int32[batch_slots] vector, each slot's last
token, the way they thread the arenas: a final prefill chunk (alone or
aboard) writes its token into its slot's row, decode reads its input
there and writes its output there. So the engine dispatches decode n+1
before it has read the tokens of decode n (dispatch-ahead): one execution
stays in flight while the host reads the one before, does its
bookkeeping, runs the callbacks and admits. `processed` advances at
dispatch; `generated`, the callbacks and `_finish` happen at harvest, one
execution later. A result that arrives for a row that has gone meanwhile
(EOS, cancel, preemption) is dropped, not emitted.

Speculative decoding (spec_decode_draft_len > 0) swaps the decode step
for three more fixed-shape programs — draft prefill [1, chunk], propose
(k+1 scanned draft steps), verify [batch_slots, k+1] — still compiled
exactly once each; greedy verification makes the emitted tokens
identical to plain decoding, whatever the draft proposes. A round's
positions depend on how many drafts the last one accepted, which the
host must read first, so speculation stays synchronous.

A radix prefix cache (prefix_cache_enabled) keeps finished sequences'
full-block KV prefixes refcounted in the arena; a new request adopts its
longest cached match and prefills only the tail. Cached blocks are
reclaimed LRU-by-leaf under pressure before any live sequence is
preempted.

All shapes are static (batch slots, chunk width, block-table width), so
the engine's per-step work is argument values, never new programs; the
stats track compile counts to prove it — including on the cached path,
which reuses the same programs with fewer invocations.

The engine core is synchronous and single-threaded (`step()`); tests drive
it directly. `EngineLoop` runs it on a background thread and is what the
Serve deployment (`api.py`) uses; token/finish callbacks are fired outside
the engine lock so they may bounce into an asyncio loop safely. The
stepping thread is always in exactly one named step phase (`PHASES`
below): the phases feed the step ledger in `stats()["steps"]` and, while
a profile is being taken, the profiler's own trace
(docs/OBSERVABILITY.md, "Step phases").
"""

from __future__ import annotations

import collections
import itertools
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ray_tpu.inference.kv_cache import (BlockManager, NoBlocks,
                                        RadixPrefixCache,
                                        WindowBlockManager,
                                        WindowedRadixCache)
from ray_tpu.observability import tracing as _tracing
from ray_tpu.observability.phases import PhaseClock

logger = logging.getLogger(__name__)

# Request states.
WAITING = "WAITING"      # queued (fresh, or preempted awaiting recompute)
PREFILL = "PREFILL"      # in a slot, prompt (+ recomputed tokens) mid-chunk
DECODE = "DECODE"        # in a slot, emitting one token per step
FINISHED = "FINISHED"
FAILED = "FAILED"

# Step phases (docs/OBSERVABILITY.md): the engine thread is in exactly one
# of these at any moment, never two. Not to be confused with the
# per-request spans engine.queue/prefill/decode/deliver/preempt.
WAIT_WORK = "engine.wait_work"          # EngineLoop parked, nothing to do
ADMIT = "engine.admit"                  # lock wait, _admit
PREFILL_HOST = "engine.prefill.host"    # block claim, arrays, block table
PREFILL_DISPATCH = "engine.prefill.dispatch"   # the jitted call returns
PREFILL_SYNC = "engine.prefill.sync"    # harvest: a final chunk's token
DECODE_HOST = "engine.decode.host"
DECODE_DISPATCH = "engine.decode.dispatch"     # spec: draft and verify
# Harvest: waiting for the tokens of the execution BEFORE the one just
# dispatched (spec: of the round just dispatched).
DECODE_SYNC = "engine.decode.sync"
DECODE_EMIT = "engine.decode.emit"      # per-row bookkeeping
CALLBACKS = "engine.callbacks"          # on_token/on_finish, lock released
PHASES = (WAIT_WORK, ADMIT, PREFILL_HOST, PREFILL_DISPATCH, PREFILL_SYNC,
          DECODE_HOST, DECODE_DISPATCH, DECODE_SYNC, DECODE_EMIT, CALLBACKS)


@dataclass(frozen=True)
class EngineConfig:
    batch_slots: int = 4            # fixed decode batch width
    block_size: int = 16            # KV tokens per block
    num_blocks: int = 64            # arena size (incl. trash block 0)
    max_blocks_per_seq: int = 8     # block-table width => max context
    prefill_chunk: int = 16         # prompt tokens per prefill step
    eos_id: Optional[int] = None    # stop token (None = budget only)
    use_jit: bool = True            # False = eager smoke mode
    # Model multiplexing (docs/MULTITENANCY.md): >0 hosts that many
    # LoRA-style adapters on this engine — one shared paged arena, the
    # SAME two compiled programs (adapter routing is a per-row index
    # argument), per-replica LRU residency. 0 = classic single model.
    max_adapters: int = 0
    lora_rank: int = 8
    # docs/INFERENCE.md: the radix prefix cache, speculation's draft
    # length k (0 = off), the class of a request that names none, and
    # the slots batch-class admissions leave free for interactive ones.
    prefix_cache_enabled: bool = True
    spec_decode_draft_len: int = 0
    slo_default_class: str = "interactive"
    slo_interactive_reserved_slots: int = 0
    # The pool of a model's AGEING kind of paged state (`cache_kinds`: a
    # kind with a `window`), its trash block included; 0 for every model
    # with one kind.
    window_blocks: int = 0

    @property
    def max_context(self) -> int:
        return self.max_blocks_per_seq * self.block_size


@dataclass
class Request:
    request_id: str
    prompt: List[int]
    max_new_tokens: int
    arrival: int                      # admission priority (lower = older)
    on_token: Optional[Callable] = None    # (req, token) per emitted token
    on_finish: Optional[Callable] = None   # (req) once, FINISHED or FAILED
    state: str = WAITING
    generated: List[int] = field(default_factory=list)
    error: Optional[str] = None
    preemptions: int = 0
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None    # first batch-slot admission
    first_token_at: Optional[float] = None   # token harvested
    # Taken just before this request's first on_token runs: behind
    # first_token_at by the rest of that harvest.
    first_token_delivered_at: Optional[float] = None
    finished_at: Optional[float] = None
    # Trace context captured at submission: the engine's queue/prefill/
    # decode phase spans (a TTFT decomposition) re-parent to it.
    trace_ctx: Optional[Dict] = None
    # Model multiplexing: which adapter this request routes through
    # (None = base model, bank row 0 identity).
    model_id: Optional[str] = None
    adapter_row: int = 0
    # SLO class ("interactive" | "batch"): admission/prefill priority and
    # preemption victim order.
    slo_class: str = "interactive"
    # Prefix-cache accounting: prompt tokens whose KV was adopted from
    # the radix cache instead of prefilled (across all admissions).
    cached_tokens: int = 0
    # ... and, of a model with an ageing kind of paged state, those whose
    # window-kind pages were adopted with them (the tail before the
    # adopted boundary).
    cached_window_tokens: int = 0
    # Scheduler-internal:
    slot: Optional[int] = None
    # Tokens whose KV write has been DISPATCHED: it runs ahead of
    # `generated` by the executions in flight.
    processed: int = 0
    inflight: int = 0                 # tokens dispatched, not yet harvested
    cur_token: Optional[int] = None   # last harvested token (spec input)
    _pinned_node: Any = None          # radix node pinned while scheduled
    # A model that decodes by blocks (`_build_block_programs`): the positions of a
    # block (1: a token a step), the block under way as the host knows it,
    # blocks committed and row-passes run, and, where asked for
    # (`add_request(record_passes=True)`), every pass's buffer.
    block: int = 1
    cur_block: Any = None
    blocks: int = 0
    passes: int = 0
    pass_log: Optional[List[Dict]] = None

    @property
    def total_to_prefill(self) -> int:
        # Recompute after preemption replays prompt + already-generated;
        # whole blocks of them (the tail opens the next block, decoded).
        return (len(self.prompt) + len(self.generated)) // self.block \
            * self.block

    @property
    def done(self) -> bool:
        return self.state in (FINISHED, FAILED)

    @property
    def budget_dispatched(self) -> bool:
        """Its last token is computed or in flight: nothing more to
        dispatch for it, whatever the tokens turn out to be."""
        return len(self.generated) + self.inflight >= self.max_new_tokens


@dataclass
class _InFlight:
    """One dispatched execution whose tokens the host has not read."""
    decode: bool          # a decode step, or a final prefill chunk
    tokens: Any           # device int32[batch_slots] after the execution
    # (request, its slot, its `preemptions` at dispatch): harvest matches
    # on the request and the count, never on the slot alone.
    rows: List[tuple]
    # Of a decode step's rows the one that is no decode row: the request
    # whose final chunk rode aboard, and whose first token this is.
    first: Optional[Request] = None
    # Of a block step, whose `rows` are ROW-PASSES (a row whose commit
    # pass has a denoise pass aboard is there twice, the commit first),
    # beside each: (the masked positions it entered with, its block's
    # first position, the buffer a block just begun started from or None,
    # the given tokens and the tokens to emit of a commit pass or None of
    # a denoise pass, whether a commit pass has the next block's first
    # denoise pass aboard). `before` is the buffer the execution was
    # given: a commit aboard's block, which the execution's own buffer no
    # longer holds.
    passes: Optional[List[tuple]] = None
    before: Any = None


@dataclass
class _Block:
    """A row's block under way, as the host knows it without reading the
    device: prompt tokens it opened with, positions still masked once the
    passes dispatched so far have run, denoise passes dispatched."""
    given: int
    masks: int
    t: int = 0


def block_decode_fn(step, block):
    """The block program of a model that decodes by blocks, unjitted
    (`InferenceEngine._build_block_programs` says what it is; a test
    compiles it for a described chip at a cell's sizes): `step` the model's
    `paged_step`, `block` its `decode_block`."""
    import jax.numpy as jnp

    length, mask_id, select = int(block.length), int(block.mask_id), \
        block.select

    def decode_fn(params, arenas, adapters, tokens, bt, pos, wmask, fresh,
                  start, n):
        aboard = wmask[:, length]               # [b]: both halves are live
        first = jnp.where(fresh[:, None], start, tokens)
        ids = jnp.concatenate([first, start], axis=1)
        logits, arenas = step(
            params, jnp.where(ids < 0, mask_id, ids), arenas, bt, pos, wmask,
            adapters, read_from=jnp.where(aboard, length, 0))
        buf = jnp.where(aboard[:, None], start, first)
        x0, chosen = select(logits, (buf < 0) & wmask[:, :length], n)
        buf = jnp.where(chosen, x0, buf)
        return jnp.where(wmask[:, :length], buf, tokens), arenas

    return decode_fn


class InferenceEngine:
    """Synchronous engine core; every public method takes the engine lock.

    `model` and `params` are what is served (module docstring: what the
    engine asks of `model`). With no model the engine serves
    `api.preset_model()`, `LLMServer`'s default.
    """

    def __new__(cls, config: EngineConfig, model=None, *args, **kwargs):
        # A model that holds more than one KIND of paged state says so
        # once, here: it is served by the subclass below. Every other
        # model has one kind, one pool, and this class as it stands.
        if (cls is InferenceEngine and model is not None
                and model.cache_kinds is not None):
            cls = _KindsEngine
        return super().__new__(cls)

    def __init__(self, config: EngineConfig, model=None, params=None,
                 mesh=None, draft_model=None, draft_params=None):
        cfg = config
        if cfg.slo_default_class not in ("interactive", "batch"):
            raise ValueError(
                f"unknown slo_default_class {cfg.slo_default_class!r}")
        self.config = cfg
        self._draft_len = int(cfg.spec_decode_draft_len)
        self._slo_reserved = min(
            cfg.batch_slots - 1,
            max(0, int(cfg.slo_interactive_reserved_slots)))
        if model is None:
            from ray_tpu.inference.api import preset_model

            model, params = preset_model()
        # Tensor-parallel serving (docs/SHARDED.md): with a mesh the
        # model places its params over the "tp" axis and shards its cache
        # WITH them — the jitted step programs below then compile to
        # partitioned XLA with no code change here (GSPMD does the rest).
        self._mesh = mesh
        self._tp = 1
        if mesh is not None:
            params, self._tp = model.place_on_mesh(params, mesh)
        self._model = model
        self._params = params
        # A model whose cache has no paged part says so with the positions
        # a sequence may reach (`pageless_context`): no block is handed
        # out or counted against admission, and the block table the step
        # programs are given is zero blocks wide.
        pageless = model.pageless_context
        if pageless is None:
            self._bm = BlockManager(cfg.num_blocks, cfg.block_size)
            self._table_width = cfg.max_blocks_per_seq
            self._max_context = cfg.max_context
        else:
            self._bm = NoBlocks(cfg.block_size)
            self._table_width = 0
            self._max_context = int(pageless)
        if self._max_context < cfg.prefill_chunk:
            raise ValueError("prefill_chunk exceeds the per-seq context")
        self._arenas = self._fresh_cache(model)
        import jax

        self._kv_bytes = sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(self._arenas)
        ) - cfg.batch_slots * int(model.slot_state_bytes)
        # Blocks alone do not bring back a sequence whose model keeps
        # state per slot: nothing is adopted, so nothing is kept either
        # (blocks nobody may adopt only fill the arena), and a rejected
        # draft's positions could not be rolled back.
        self._prefix_restores = bool(model.prefix_restores)
        self._slot_state_bytes = int(model.slot_state_bytes)
        if not self._prefix_restores and self._draft_len > 0:
            raise ValueError(
                "spec_decode_draft_len > 0 needs a model whose cache a "
                "prefix of blocks restores: a rejected draft's positions "
                "are overwritten in the paged blocks, and per-slot state "
                "has no rollback")
        self._prefix: Optional[RadixPrefixCache] = None
        if cfg.prefix_cache_enabled and self._prefix_restores:
            self._prefix = self._new_prefix_cache()
        # Speculative decoding: the draft shares the target's BLOCK
        # TABLES (host bookkeeping) but writes its own cache — same
        # geometry, so one table addresses both. With none injected the
        # model gives an early-exit draft. Greedy verify makes the output
        # independent of draft quality either way; a better draft just
        # accepts more.
        self._draft_model = None
        self._draft_params = None
        self._draft_arenas = None
        if self._draft_len > 0:
            if draft_model is None:
                draft_model, draft_params = model.early_exit_draft(params)
            if mesh is not None:
                draft_params, _ = draft_model.place_on_mesh(draft_params,
                                                            mesh)
            self._draft_model = draft_model
            self._draft_params = draft_params
            self._draft_arenas = self._fresh_cache(draft_model)
        # Model multiplexing: the adapter bank + residency bookkeeping.
        # `adapter_source(model_id) -> per-layer rows` is registered by
        # the deployment (api.py) so a miss loads on demand.
        self._adapters = None
        self._adapter_source = None
        if cfg.max_adapters > 0:
            from ray_tpu.inference.adapters import AdapterManager

            self._adapters = AdapterManager(model, cfg.max_adapters,
                                            cfg.lora_rank, mesh=mesh)
        # Each slot's last token, on the device (module docstring), and
        # the executions dispatched whose tokens are not read yet, oldest
        # first. Not donated: an execution's output stays readable after
        # the next one took it as input.
        self._token_shape = (cfg.batch_slots,)
        self._token_block = 1       # positions a decode step gives a row
        self._tokens = self._fresh_tokens()
        self._inflight: collections.deque = collections.deque()
        self._slots: List[Optional[Request]] = [None] * cfg.batch_slots
        self._waiting: List[Request] = []     # kept sorted by arrival
        self._live: Dict[str, Request] = {}   # request_id -> live request
        self._lock = threading.RLock()
        self._arrival_seq = itertools.count()
        self._req_seq = itertools.count()
        # Stats.
        self._tokens_emitted = 0
        self._finished = 0
        self._failed = 0
        self._preemptions = 0
        self._state_resets = 0             # sequences begun from zero state
        self._prefix_refused = 0           # admissions that may adopt nothing
        # The step ledger: written by the stepping thread alone, published
        # whole at the end of every step so that step_stats() needs no
        # lock and never sees half a step.
        self._clock = PhaseClock(PHASES)
        self._ledger = {"n": 0, "decode": 0, "decode_ahead": 0, "prefill": 0,
                        "chunks_aboard": 0, "decode_rows": 0,
                        "dropped_rows": 0, "wall_s": 0.0}
        self._publish_steps()
        self._rate_window: List[tuple] = []   # (t, n) recent emissions
        # Which path the paged attention of each program took when it was
        # traced (ops/paged_attention.py's dispatch records).
        self._paged_attn = {"decode": "not traced", "prefill": "not traced"}
        # ... and which tile of the kernel its shape was given (the records'
        # `tile`; empty for a reference call and for the latent kernel).
        self._paged_tile = dict(self._paged_attn)
        self._shapes = {"prefill": set(), "decode": set(),
                        "decode_with_chunk": set(), "draft_prefill": set(),
                        "propose": set(), "verify": set()}
        # Spec-decode accounting: accepted-length histogram [0..k] per
        # verify round (index a = rounds that accepted exactly a drafts).
        self._spec_rounds = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_hist = [0] * (self._draft_len + 1)
        # Which device answers, as jax reports it: stats() carries it so a
        # caller can tell from outside (chip_smoke.py, benchmarks).
        from ray_tpu._jax_env import device_info

        self._device = device_info()
        # Counters a model keeps ON THE DEVICE in its cache (finding (f) of
        # docs/INFERENCE.md): copied out under the lock when stats() is
        # asked, read on the host once the copy has landed, never waited
        # for by a step.
        self._counters_pending = None
        self._counter_stats: Dict[str, Any] = {}
        self._build_programs()
        self._last_stats = self._stats_locked()
        self._last_stats_at = time.monotonic()

    # ----------------------------------------------------------- programs

    def _build_programs(self):
        import jax
        import jax.numpy as jnp

        step = self._model.paged_step
        # A model that decodes by BLOCKS says so once, here, and gets
        # programs and a decode path of its own (`_build_block_programs`);
        # nothing below, and no step of any other model, asks again.
        self._block = self._model.decode_block
        if self._block is not None:
            return self._build_block_programs(step)

        # `tokens` is the device-resident last token of every slot. The
        # chunk writes its token into its slot's row (a chunk that is not
        # the prompt's last writes one nobody reads: the row decodes only
        # after the last has overwritten it); decode reads its input there
        # and leaves the rows it did not run as they were.
        #
        # `adapters` is None or (banks, adapter_idx): None is an empty
        # pytree to jit, so an engine without adapters compiles programs
        # with no bank in them, and a multiplexed one takes the banks as
        # ARGUMENTS (fixed shape/dtype/sharding): N adapters still mean
        # exactly these programs (docs/MULTITENANCY.md).
        def prefill_fn(params, arenas, adapters, tokens, ids, bt, pos,
                       wmask, last_idx, slot):
            # Logits only where they are read: one position of the chunk.
            logits, arenas = step(params, ids, arenas, bt, pos, wmask,
                                  adapters, slot, last_idx)
            nxt = jnp.argmax(logits, axis=-1)
            return tokens.at[slot].set(nxt.astype(jnp.int32)), arenas

        def decode_fn(params, arenas, adapters, tokens, bt, pos, wmask):
            logits, arenas = step(params, tokens[:, None], arenas, bt, pos,
                                  wmask, adapters)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return jnp.where(wmask[:, 0], nxt, tokens), arenas

        if self.config.use_jit:
            # Arenas are donated: the update is in place on the device,
            # not a fresh copy of the whole cache per step.
            self._prefill_fn = jax.jit(prefill_fn, donate_argnums=(1,))
            self._decode_fn = jax.jit(decode_fn, donate_argnums=(1,))
        else:
            self._prefill_fn = prefill_fn
            self._decode_fn = decode_fn

        # A decode step with a chunk aboard, where the model has one (and
        # the engine neither speculates nor holds adapter banks, which the
        # fused step does not take): `decode_fn` and `prefill_fn` in one
        # execution. It is a decode step to whoever counts them, and a
        # device trace shows it under that name (`jit_decode_fn`, like the
        # program above); in the engine's own books it is a program of its
        # own, compiled once.
        self._decode_with_chunk_fn = None
        fused = self._model.paged_step_with_chunk
        if (fused is not None and self._draft_len == 0
                and self._adapters is None):
            def decode_with_chunk_fn(params, arenas, tokens, bt, pos, wmask,
                                     ids, chunk_bt, chunk_pos, chunk_wmask,
                                     last_idx, slot):
                logits, chunk_logits, arenas = fused(
                    params, tokens[:, None], ids, arenas, bt, pos, wmask,
                    chunk_bt, chunk_pos, chunk_wmask, slot, last_idx)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                first = jnp.argmax(chunk_logits, axis=-1).astype(jnp.int32)
                return jnp.where(wmask[:, 0], nxt, tokens).at[slot].set(
                    first), arenas

            decode_with_chunk_fn.__name__ = decode_fn.__name__
            if self.config.use_jit:
                decode_with_chunk_fn = jax.jit(decode_with_chunk_fn,
                                               donate_argnums=(1,))
            self._decode_with_chunk_fn = decode_with_chunk_fn

        # Speculative decoding adds exactly three more fixed-shape
        # programs, each compiled once: draft prefill [1, chunk] (keeps
        # the draft's KV in lockstep with the target's), propose (k+1
        # draft decode steps under lax.scan, [B, 1] per step), verify
        # (target forward over [B, k+1] = current token + k proposals).
        self._draft_prefill_fn = None
        self._propose_fn = None
        self._verify_fn = None
        if self._draft_len > 0:
            draft_step = self._draft_model.paged_step

            def draft_prefill_fn(dparams, darenas, ids, bt, pos, wmask):
                _, darenas = draft_step(dparams, ids, darenas, bt, pos,
                                        wmask)
                return darenas

            def propose_fn(dparams, darenas, toks, bt, pos, wmask_seq):
                # wmask_seq [k+1, B, 1]: per-step write masks (rows near
                # their context limit mask the tail — masked writes land
                # in the trash block, their logits are never used).
                # Step j writes its INPUT token's KV at pos+j and emits
                # the argmax proposal for position pos+j+1, so the k+1
                # steps leave the draft KV complete through pos+k.
                def body(carry, wm):
                    tok, p, arenas = carry
                    logits, arenas = draft_step(dparams, tok, arenas, bt,
                                                p, wm)
                    nxt = jnp.argmax(logits[:, -1],
                                     axis=-1).astype(jnp.int32)
                    return (nxt[:, None], p + 1, arenas), nxt

                (_, _, darenas), props = jax.lax.scan(
                    body, (toks, pos, darenas), wmask_seq)
                return jnp.transpose(props), darenas     # [B, k+1]

            def verify_fn(params, arenas, adapters, toks, bt, pos, wmask):
                logits, arenas = step(params, toks, arenas, bt, pos, wmask,
                                      adapters)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), arenas

            if self.config.use_jit:
                self._draft_prefill_fn = jax.jit(draft_prefill_fn,
                                                 donate_argnums=(1,))
                self._propose_fn = jax.jit(propose_fn, donate_argnums=(1,))
                self._verify_fn = jax.jit(verify_fn, donate_argnums=(1,))
            else:
                self._draft_prefill_fn = draft_prefill_fn
                self._propose_fn = propose_fn
                self._verify_fn = verify_fn

    # ------------------------------------------------- decoding by blocks

    def _build_block_programs(self, step):
        """The programs and the decode path of a model whose unit of work
        is a BLOCK of `length` positions denoised over several passes
        (`model.decode_block`; docs/INFERENCE.md finding (i)).

        The device-resident buffer is int32[batch_slots, length]: each
        slot's block under way, an id where a position is committed or
        given and -1 where it is still masked (which positions are masked
        is kept beside the ids, so that a given token that happens to be
        the mask id stays as given). A ROW-PASS is one block of one row
        through one execution: a denoise pass, which commits what the
        model's selection rule picks of the `n` positions the host's
        schedule gives the row, or the commit pass of a block with no mask
        left, which chooses nothing and whose keys and values stay because
        the host then advances `processed`. Every book (`decode_rows`,
        `stats()["diffusion"]`, `Request.passes`, `pass_log`) counts
        row-passes.

        ONE block program serves them all, rows of one batch at different
        passes, and it is [batch_slots, 2 x length] wide, because a row
        whose block has no mask left takes its commit pass AND its next
        block's first denoise pass in the same execution (a commit ABOARD:
        two row-passes, one execution; four executions a block of four
        where the schedule alone has five). Such a row's first half is the
        buffer (the block's final ids) and its second the next block, all
        masks (`start`); the model's step scatters the call's keys and
        values before each layer attends, so the second half reads what a
        commit pass of its own would have left. The program tells such a
        row by its write mask (both halves live), reads logits at the half
        that denoises (`paged_step`'s `read_from`) and leaves that half in
        the buffer. Every other row-pass runs in the first half with the
        second dead: the passes of a block after its first, a request's
        first block after its prefill (`fresh`, `start`: the prompt's tail
        is given), and the commit pass that does NOT ride: the request's
        last block, or one whose next block found no page (nobody is
        preempted for a page a pass early). Prefill writes keys and values
        only: no logits are read (no next-token shift), the head is dead
        code to the compiler.

        The path is chosen HERE, once: `_decode_step`, `_harvest` and
        `_chunk_dispatched` are rebound on this engine, and the one-token
        methods of the class are never entered."""
        import jax

        cfg, block = self.config, self._block
        length = int(block.length)
        if cfg.prefill_chunk % length or cfg.block_size % length:
            raise ValueError(
                f"prefill_chunk {cfg.prefill_chunk} and block_size "
                f"{cfg.block_size} must be multiples of the model's block of "
                f"{length} positions: a chunk covers whole blocks, and a "
                f"page boundary is a point a prefix restores a sequence at")
        if self._draft_len > 0:
            raise ValueError(
                "spec_decode_draft_len > 0 with a model that decodes by "
                "blocks: a block's passes are its own speculation")
        if self._adapters is not None:
            raise ValueError("a model that decodes by blocks takes no "
                             "adapter banks")

        def prefill_fn(params, arenas, adapters, tokens, ids, bt, pos,
                       wmask, last_idx, slot):
            _, arenas = step(params, ids, arenas, bt, pos, wmask, adapters,
                             slot, last_idx)
            return tokens, arenas

        decode_fn = block_decode_fn(step, block)

        if cfg.use_jit:
            prefill_fn = jax.jit(prefill_fn, donate_argnums=(1,))
            decode_fn = jax.jit(decode_fn, donate_argnums=(1,))
        self._prefill_fn, self._decode_fn = prefill_fn, decode_fn
        self._decode_with_chunk_fn = None
        self._draft_prefill_fn = self._propose_fn = self._verify_fn = None
        self._token_block = length
        self._token_shape = (cfg.batch_slots, length)
        self._tokens = self._fresh_tokens()
        self._decode_step = self._block_decode_step
        self._harvest = self._block_harvest
        self._chunk_dispatched = self._block_chunk_dispatched
        # Row-passes and what they did (stats()["diffusion"]), counted at
        # the harvest, for the rows that were still there.
        self._diffusion = {
            "block_length": length, "schedule": list(block.schedule),
            "rule": "static" if block.threshold is None else "dynamic",
            "blocks_committed": 0, "denoise_passes": 0, "commit_passes": 0,
            # commit row-passes that rode with the next block's first
            # denoise pass
            "commits_aboard": 0,
            "tokens_committed": 0, "given_tokens": 0, "truncated_tokens": 0,
            # index a: denoise row-passes that committed exactly a
            "committed_hist": [0] * (length + 1)}

    def _block_chunk_dispatched(self, chunk: tuple) -> bool:
        """Book a chunk's tokens as written. Never True: the last chunk
        leaves no token on the device, the request's blocks begin with the
        next block step."""
        req, n, _ = chunk
        req.processed += n
        if req.processed >= req.total_to_prefill:
            req.state = DECODE
        return False

    def _block_decode_step(self, chunk: Optional[tuple] = None) -> bool:
        """Dispatch one block execution: every row that is decoding takes
        the next row-pass of its block (`_build_block_programs`). The host
        knows which without reading the device: a block starts with its
        masks counted (the prompt's tail is given), a denoise pass commits
        what the schedule says, and a row with none left takes its commit
        pass, whose dispatch books the block's positions as processed and
        its tokens as in flight, and with it, unless that was the
        request's last block or the next one finds no page, the next
        block's first denoise pass."""
        import numpy as np

        cfg, block, clock = self.config, self._block, self._clock
        length = block.length
        clock.enter(DECODE_HOST)
        active: List[Request] = []
        for req in list(self._scheduled()):
            if req.state == DECODE and self._ensure_blocks(
                    req, req.processed + length):
                active.append(req)
        active = [r for r in active if r.state == DECODE
                  and r.slot is not None]
        if not active:
            return False
        B = cfg.batch_slots
        pos = np.zeros(B, np.int32)
        wmask = np.zeros((B, 2 * length), bool)
        fresh = np.zeros(B, bool)
        start = np.full((B, length), -1, np.int32)
        n = np.zeros(B, np.int32)
        rows = [None] * B
        # a row-pass each: a row whose commit has a denoise pass aboard is
        # two, the commit first
        tracked, passes, leaving = [], [], []
        for req in active:
            i, at = req.slot, req.processed
            row = (req, i, req.preemptions)
            rows[i] = req
            pos[i] = at
            wmask[i, :length] = True
            began = None
            if req.cur_block is None:
                # The tokens past the whole blocks open this block as
                # given: a prompt's tail, or after a preemption the tail
                # of what was generated.
                tail = (req.prompt + req.generated)[at:]
                req.cur_block = _Block(len(tail), length - len(tail))
                fresh[i] = True
                start[i, :len(tail)] = tail
                began = start[i].tolist()
            cur = req.cur_block
            if not cur.masks:
                emit = min(length - cur.given, req.max_new_tokens
                           - len(req.generated) - req.inflight)
                req.inflight += emit
                req.processed += length
                req.cur_block = None
                aboard = not req.budget_dispatched and self._ensure_blocks(
                    req, req.processed + length, preempt=False)
                tracked.append(row)
                passes.append((0, at, None, (cur.given, emit), aboard))
                if not aboard:
                    if req.budget_dispatched:
                        leaving.append(req)
                    continue
                at += length
                wmask[i] = True
                cur = req.cur_block = _Block(0, length)
                began = [-1] * length
            before = cur.masks
            n[i] = min(block.schedule[cur.t], before)
            cur.masks -= int(n[i])
            cur.t += 1
            tracked.append(row)
            passes.append((before, at, began, None, False))
        bt = self._block_table_rows(rows)
        clock.enter(DECODE_DISPATCH)
        was = self._tokens
        self._tokens, self._arenas = self._call(
            "decode", self._decode_fn, self._params, self._arenas, None,
            was, bt, pos, wmask, fresh, start, n)
        clock.enter(DECODE_HOST)
        self._ledger["decode"] += 1
        self._ledger["decode_ahead"] += bool(self._inflight)
        self._tokens.copy_to_host_async()
        for req in leaving:
            # Its last block is in flight: the slot is the next
            # admission's; the blocks stay until `_finish`.
            self._slots[req.slot] = None
            req.slot = None
        self._inflight.append(_InFlight(True, self._tokens, tracked,
                                        passes=passes, before=was))
        return True

    def _block_harvest(self, emissions, keep: int) -> bool:
        """Read the buffers of the oldest block executions in flight until
        `keep` are left, a row-pass at a time: a commit pass hands its
        block's tokens to its request (cut at the budget, and at EOS, which
        also drops the denoise pass that rode with it), a denoise pass is
        counted. Under the dynamic rule how many positions a pass commits
        is the device's to say, so nothing stays in flight: the host reads
        each row's masks left before it dispatches the next pass."""
        import numpy as np

        clock, book = self._clock, self._diffusion
        dynamic = self._block.threshold is not None
        if dynamic:
            keep = 0
        harvested = False
        while len(self._inflight) > keep:
            rec = self._inflight[0]
            clock.enter(DECODE_SYNC)
            view = np.asarray(rec.tokens)
            clock.enter(DECODE_EMIT)
            self._inflight.popleft()
            harvested = True
            left = (view < 0).sum(axis=1).tolist()
            # A commit aboard ran on the buffer as the execution found it
            # (read at the harvest before this one); what the execution
            # left there is the next block.
            given_view = np.asarray(rec.before) if any(
                p[4] for p in rec.passes) else None
            for (req, slot, preemptions), (before, at, began, commit,
                                           aboard) in zip(rec.rows,
                                                          rec.passes):
                if req.state != DECODE or req.preemptions != preemptions:
                    self._ledger["dropped_rows"] += 1
                    continue
                self._ledger["decode_rows"] += 1
                req.passes += 1
                final = (given_view if aboard else view)[slot]
                if req.pass_log is not None:
                    req.pass_log.append({
                        "start": at, "left": final.tolist(),
                        "entered": began if began is not None
                        else req.pass_log[-1]["left"]})
                if commit is None:
                    took = before - left[slot]
                    book["denoise_passes"] += 1
                    book["tokens_committed"] += took
                    book["committed_hist"][took] += 1
                    if dynamic and req.cur_block is not None:
                        req.cur_block.masks = left[slot]
                    continue
                given, emit = commit
                book["commit_passes"] += 1
                book["commits_aboard"] += aboard
                book["blocks_committed"] += 1
                book["given_tokens"] += given
                book["truncated_tokens"] += self._token_block - given - emit
                req.blocks += 1
                req.inflight -= emit
                for token in final[given:given + emit].tolist():
                    if req.done:
                        break               # EOS inside the block
                    self._emit_token(req, token, emissions)
        return harvested

    def _program_compiles(self, name: str) -> int:
        fn = {"prefill": self._prefill_fn, "decode": self._decode_fn,
              "decode_with_chunk": self._decode_with_chunk_fn,
              "draft_prefill": self._draft_prefill_fn,
              "propose": self._propose_fn,
              "verify": self._verify_fn}[name]
        if fn is None:
            return 0
        size = getattr(fn, "_cache_size", None)
        if callable(size):
            try:
                return int(size())
            except Exception:  # noqa: BLE001 — introspection only
                pass
        return len(self._shapes[name])

    def _fresh_cache(self, model):
        cfg = self.config
        return model.paged_cache(self._bm.num_blocks, cfg.block_size,
                                 self._mesh, cfg.batch_slots)

    def _new_prefix_cache(self) -> RadixPrefixCache:
        return RadixPrefixCache(self._bm)

    def _fresh_tokens(self):
        """The token vector as the programs return it: replicated under a
        tp mesh, so that the first call's argument and every later one
        (an output) are one jit cache key."""
        import jax
        import jax.numpy as jnp

        shape = self._token_shape
        if self._mesh is None:
            return jnp.zeros(shape, jnp.int32)
        replicated = jax.sharding.NamedSharding(
            self._mesh, jax.sharding.PartitionSpec())
        return jax.jit(lambda: jnp.zeros(shape, jnp.int32),
                       out_shardings=replicated)()

    # ---------------------------------------------------------- submission

    def register_adapter_source(self, fn: Callable[[str], list]) -> None:
        """Install the on-demand adapter loader: fn(model_id) returns
        the per-layer (aq, bq, ao, bo) rows (api.py wires the replica's
        registered adapter specs here)."""
        self._adapter_source = fn

    def _resolve_adapter_locked(self, model_id: Optional[str]) -> int:
        if model_id is None:
            return 0
        if self._adapters is None:
            raise ValueError(
                f"request names model {model_id!r} but the engine is not "
                "multiplexed (max_adapters=0)")
        if self._adapter_source is None:
            raise ValueError("no adapter source registered")
        # Rows of live requests are pinned: LRU must never evict weights
        # a mid-flight (or queued) generation still routes through.
        pinned = {r.adapter_row for r in self._live.values()
                  if r.adapter_row}
        return self._adapters.ensure(model_id, self._adapter_source,
                                     pinned_rows=pinned)

    def add_request(self, prompt: List[int],
                    max_new_tokens: int = 16,
                    on_token: Optional[Callable] = None,
                    on_finish: Optional[Callable] = None,
                    request_id: Optional[str] = None,
                    model_id: Optional[str] = None,
                    slo_class: Optional[str] = None,
                    record_passes: bool = False) -> Request:
        cfg = self.config
        prompt = [int(t) for t in prompt] or [0]
        max_new_tokens = max(1, int(max_new_tokens))
        slo = slo_class if slo_class is not None else cfg.slo_default_class
        if slo not in ("interactive", "batch"):
            raise ValueError(f"unknown slo_class {slo!r} "
                             "(expected 'interactive' or 'batch')")
        # ... in whole blocks, where the model decodes by blocks
        total = -(-(len(prompt) + max_new_tokens) // self._token_block) \
            * self._token_block
        if total > self._max_context or not self._bm.fits(total):
            if not self._table_width:
                raise ValueError(
                    f"request needs {total} token slots; the model's "
                    f"context is {self._max_context} positions")
            raise ValueError(
                f"request needs {total} token slots; engine caps at "
                f"{min(cfg.max_context, self._bm.capacity * cfg.block_size)}"
                f" (max_blocks_per_seq={cfg.max_blocks_per_seq}, "
                f"num_blocks={cfg.num_blocks})")
        with self._lock:
            rid = request_id or f"req-{next(self._req_seq)}"
            if rid in self._live:
                # Reject NOW: a duplicate reaching _admit would raise out
                # of step() and trip the circuit breaker for everyone.
                raise ValueError(f"request id {rid!r} is already live")
            # Adapter residency resolves at submit (load-on-miss, LRU
            # evict): a failure rejects THIS request instead of raising
            # out of step() for everyone.
            adapter_row = self._resolve_adapter_locked(model_id)
            req = Request(
                request_id=rid,
                prompt=prompt, max_new_tokens=max_new_tokens,
                arrival=next(self._arrival_seq),
                on_token=on_token, on_finish=on_finish,
                submitted_at=time.monotonic(),
                trace_ctx=_tracing.capture(),
                model_id=model_id, adapter_row=adapter_row,
                slo_class=slo, block=self._token_block,
                pass_log=[] if record_passes and self._block else None)
            self._live[rid] = req
            # Queue order is (class, arrival): interactive ahead of
            # batch, FIFO within a class.
            self._waiting.append(req)
            self._waiting.sort(key=self._prio)
        return req

    def cancel(self, request_id: str) -> bool:
        """Abort one request (client disconnected mid-stream): free its
        slot and blocks immediately so live traffic isn't stuck behind a
        generation nobody is reading. True if it was still live."""
        emissions: List[tuple] = []
        with self._lock:
            req = self._live.get(request_id)
            if req is None or req.done:
                return False
            if req.state == WAITING:
                self._waiting.remove(req)
            self._finish(req, emissions, error="cancelled")
        self._deliver(emissions)
        return True

    def has_work(self) -> bool:
        with self._lock:
            # An execution whose tokens nobody has read is work too.
            return bool(self._waiting) or bool(self._inflight) or any(
                r is not None for r in self._slots)

    # ---------------------------------------------------------------- step

    def step(self) -> bool:
        """One scheduler iteration: admit, dispatch one prefill chunk and
        one decode step (as one execution where the model has a fused step
        and rows are decoding), then harvest (read the tokens of, and do
        the bookkeeping for) every execution but the newest of this step,
        which stays in flight while the callbacks run and the next step
        admits and dispatches. Returns whether any work ran. Callbacks
        fire after the lock is released (they may hop into an asyncio
        loop). One thread steps an engine (the EngineLoop's, or a
        test's): the phase clock and the step ledger are that thread's."""
        clock = self._clock
        t0 = time.perf_counter()
        emissions: List[tuple] = []
        try:
            clock.enter(ADMIT)
            with self._lock:
                self._admit()
                before = len(self._inflight)
                chunk = self._next_chunk()
                if self._draft_len > 0:
                    ran = self._prefill_step(chunk)
                    # A round starts from tokens the host holds.
                    ran = self._harvest(emissions, keep=0) or ran
                    ran = self._spec_decode_step(emissions) or ran
                else:
                    # The chunk rides in the decode step where it can;
                    # else it runs alone and the decode step after it.
                    ran = (self._decode_with_chunk_fn is not None
                           and chunk is not None
                           and self._decode_step(chunk))
                    if not ran:
                        ran = self._prefill_step(chunk)
                        ran = self._decode_step() or ran
                    # With nothing new to keep the device busy, drain.
                    keep = min(1, len(self._inflight) - before)
                    ran = self._harvest(emissions, keep) or ran
            clock.enter(CALLBACKS)
            self._deliver(emissions)
        finally:
            clock.leave()
            self._ledger["wall_s"] += time.perf_counter() - t0
        self._ledger["n"] += bool(ran)
        self._publish_steps()
        return ran

    def _deliver(self, emissions) -> None:
        """Run the callbacks a step (or cancel, or fail_all) collected,
        outside the lock, in order: (on_token or None, req, token), or
        (on_finish, req, None)."""
        for fn, req, token in emissions:
            try:
                if token is None:
                    fn(req)
                    continue
                if req.first_token_delivered_at is None:
                    req.first_token_delivered_at = time.monotonic()
                    self._record_deliver_span(req)
                if fn is not None:
                    fn(req, token)
            except Exception:  # noqa: BLE001 — user callback must not
                pass           # take down the scheduler

    def run_until_idle(self, max_steps: int = 10000) -> int:
        """Drive the loop synchronously (tests / offline batch); returns
        steps taken."""
        steps = 0
        while self.has_work():
            if steps >= max_steps:
                raise RuntimeError(f"engine not idle after {max_steps} steps")
            self.step()
            steps += 1
        return steps

    # ----------------------------------------------------------- admission

    def _scheduled(self) -> List[Request]:
        return [r for r in self._slots if r is not None]

    @staticmethod
    def _prio(req: Request):
        return (0 if req.slo_class == "interactive" else 1, req.arrival)

    def _unpin_req(self, req: Request) -> None:
        if req._pinned_node is not None and self._prefix is not None:
            self._prefix.unpin(req._pinned_node)
        req._pinned_node = None

    def _admit(self):
        while self._waiting:
            free_slots = [i for i, r in enumerate(self._slots) if r is None]
            if not free_slots:
                return
            req = None
            for cand in self._waiting:   # sorted by (class, arrival)
                if (cand.slo_class != "interactive"
                        and len(free_slots) <= self._slo_reserved):
                    # Reserved headroom: batch-class admissions must
                    # leave this many slots open for interactive
                    # arrivals (a bulk flood otherwise owns the batch).
                    continue
                req = cand
                break
            if req is None:
                return
            matched_tokens = self._admit_blocks(req)
            if matched_tokens is None:
                return
            self._waiting.remove(req)
            req.slot = free_slots[0]
            req.processed = matched_tokens
            # (a prompt shorter than a block, or one whose whole blocks
            # were all adopted, has nothing to prefill: blocks only)
            req.state = PREFILL if matched_tokens < req.total_to_prefill \
                else DECODE
            req.cached_tokens += matched_tokens
            if req.admitted_at is None:
                req.admitted_at = time.monotonic()
            self._slots[req.slot] = req

    def _admit_blocks(self, req: Request) -> Optional[int]:
        """Adopt the longest cached prefix of `req` and claim its first
        chunk's blocks: the tokens adopted, or None where the pool is
        exhausted (nothing is kept, and the request stays queued: running
        sequences finishing, or preempting, will free blocks)."""
        cfg = self.config
        rid = req.request_id
        # Longest cached prefix: adopt matched blocks (refcount++)
        # and skip their prefill entirely. Capped one token short of
        # the stream so at least one token still prefills — the
        # first emitted token needs fresh logits.
        matched_tokens = 0
        if cfg.prefix_cache_enabled and not self._prefix_restores:
            self._prefix_refused += 1
        if self._prefix is not None:
            stream = req.prompt + req.generated
            cap = (len(stream) - 1) // cfg.block_size * cfg.block_size
            blocks, pin_node = self._prefix.match(stream[:cap])
            if blocks:
                matched_tokens = len(blocks) * cfg.block_size
                self._bm.register_with_blocks(rid, blocks)
                self._prefix.pin(pin_node)
                req._pinned_node = pin_node
        if not self._bm.registered(rid):
            self._bm.register(rid)
        first = min(req.total_to_prefill,
                    matched_tokens + cfg.prefill_chunk)
        while not self._bm.ensure(rid, first):
            deficit = (self._bm.blocks_for_tokens(first)
                       - len(self._bm.block_table(rid))
                       - self._bm.num_free())
            if (self._prefix is None
                    or self._prefix.evict_for(deficit) == 0):
                self._unpin_req(req)
                self._release(rid)
                return None
        return matched_tokens

    def _release(self, request_id: str) -> None:
        """Give back every block the sequence holds."""
        self._bm.free(request_id)

    # ---------------------------------------------------------- preemption

    def _preempt_one(self) -> bool:
        """Free the lowest-priority scheduled sequence to relieve block
        pressure: batch-class victims before interactive ones, latest
        arrival within a class. The victim may be the requester itself
        (callers detect that via its WAITING state). Returns False when
        there is nothing left to preempt."""
        victims = [r for r in self._scheduled()
                   if r.state in (PREFILL, DECODE)]
        if not victims:
            return False
        victim = max(victims, key=self._prio)
        self._unpin_req(victim)
        self._release(victim.request_id)
        self._slots[victim.slot] = None
        victim.slot = None
        victim.state = WAITING
        victim.processed = 0
        victim.inflight = 0      # their harvest drops them (preemptions)
        victim.cur_token = None
        victim.cur_block = None
        victim.preemptions += 1
        self._preemptions += 1
        if _tracing._ENABLED:
            now = _tracing.epoch_of(time.monotonic())
            _tracing.get_tracer().record_span(
                "engine.preempt", now, now, parent_ctx=victim.trace_ctx,
                attrs={"request": victim.request_id,
                       "tokens_generated": len(victim.generated)})
        self._waiting.append(victim)
        self._waiting.sort(key=self._prio)
        return True

    def _ensure_blocks(self, req: Request, num_tokens: int,
                       preempt: bool = True) -> bool:
        """Grow req's block table — reclaiming cold cached prefixes
        first, then preempting victims — until it fits. False when req
        itself was preempted (caller must drop it), or, with `preempt`
        off, when only a preemption would have made room (nobody is
        preempted and req's table stays as it was)."""
        while not self._bm.ensure(req.request_id, num_tokens):
            deficit = (self._bm.blocks_for_tokens(num_tokens)
                       - len(self._bm.block_table(req.request_id))
                       - self._bm.num_free())
            if (self._prefix is not None
                    and self._prefix.evict_for(deficit) > 0):
                continue
            if not preempt or not self._preempt_one():
                return False
            if req.state == WAITING:   # preempted itself
                return False
        return True

    # ------------------------------------------------------------- prefill

    def _next_chunk(self) -> Optional[tuple]:
        """The step's prefill chunk, its blocks claimed and its arrays
        made: (the request, its tokens in the chunk, the programs'
        arguments), or None when no sequence is prefilling."""
        import numpy as np

        cfg = self.config
        cands = [r for r in self._scheduled() if r.state == PREFILL]
        if not cands:
            return None
        self._clock.enter(PREFILL_HOST)
        req = min(cands, key=self._prio)   # interactive first, then oldest
        chunk = min(cfg.prefill_chunk, req.total_to_prefill - req.processed)
        if not self._ensure_blocks(req, req.processed + chunk):
            return None
        stream = req.prompt + req.generated
        ids = np.zeros((1, cfg.prefill_chunk), np.int32)
        ids[0, :chunk] = stream[req.processed:req.processed + chunk]
        wmask = np.zeros((1, cfg.prefill_chunk), bool)
        wmask[0, :chunk] = True
        bt = self._block_table_rows([req])
        return req, chunk, (
            ids, bt, np.asarray([req.processed], np.int32), wmask,
            np.asarray([chunk - 1], np.int32),
            np.asarray([req.slot], np.int32))

    def _prefill_step(self, chunk: Optional[tuple]) -> bool:
        """Dispatch `_next_chunk`'s chunk as an execution of its own."""
        if chunk is None or chunk[0].state != PREFILL:
            return False        # nothing, or a decode row's claim took it
        req, _, args = chunk
        clock = self._clock
        clock.enter(PREFILL_DISPATCH)
        self._tokens, self._arenas = self._call(
            "prefill", self._prefill_fn, self._params, self._arenas,
            self._adapter_args([req]), self._tokens, *args)
        if self._draft_len > 0:
            # Keep the draft's KV in lockstep: same chunk, same blocks.
            # Cached-prefix blocks carry draft KV from their original
            # prefill (deterministic writes), so hits skip BOTH models.
            self._draft_arenas = self._call(
                "draft_prefill", self._draft_prefill_fn,
                self._draft_params, self._draft_arenas, *args[:4])
        clock.enter(PREFILL_HOST)
        self._ledger["prefill"] += 1
        if self._chunk_dispatched(chunk):
            # Its token is in the slot's row on the device: the row can
            # decode from this step on, before the host has read it.
            self._track(False, [req])
        return True

    def _chunk_dispatched(self, chunk: tuple) -> bool:
        """Book a chunk's tokens as written. True when they were the last
        of the prompt: the request decodes from here."""
        req, n, _ = chunk
        self._state_resets += (req.processed == 0
                               and self._slot_state_bytes > 0)
        req.processed += n
        if req.processed < req.total_to_prefill:
            return False
        req.state = DECODE
        return True

    # -------------------------------------------------------------- decode

    def _decode_step(self, chunk: Optional[tuple] = None) -> bool:
        """Dispatch one decode execution for every row that has a token
        on the device and budget left; reading it is `_harvest`'s. With
        `_next_chunk`'s chunk it goes aboard (the fused program), unless a
        row's block claim preempted its request, and where no row decodes
        nothing is dispatched: the chunk is left to run alone."""
        import numpy as np

        cfg = self.config
        clock = self._clock
        clock.enter(DECODE_HOST)
        active: List[Request] = []
        for req in list(self._scheduled()):
            if req.state != DECODE:
                continue
            # Writing the row's token at position `processed` needs
            # capacity for processed + 1 tokens.
            if self._ensure_blocks(req, req.processed + 1):
                active.append(req)
        # A later sequence's block claim may have preempted one already
        # admitted to this step — keep only the still-scheduled.
        active = [r for r in active if r.state == DECODE
                  and r.slot is not None]
        if not active:
            return False
        if chunk is not None and chunk[0].state != PREFILL:
            chunk = None
        B = cfg.batch_slots
        pos = np.zeros(B, np.int32)
        wmask = np.zeros((B, 1), bool)
        rows = [None] * B
        for req in active:
            i = req.slot
            rows[i] = req
            pos[i] = req.processed
            wmask[i, 0] = True
        bt = self._block_table_rows(rows)
        clock.enter(DECODE_DISPATCH)
        if chunk is None:
            self._tokens, self._arenas = self._call(
                "decode", self._decode_fn, self._params, self._arenas,
                self._adapter_args(rows), self._tokens, bt, pos, wmask)
        else:
            self._tokens, self._arenas = self._call(
                "decode_with_chunk", self._decode_with_chunk_fn,
                self._params, self._arenas, self._tokens, bt, pos, wmask,
                *chunk[2])
        clock.enter(DECODE_HOST)
        self._ledger["decode"] += 1
        self._ledger["decode_ahead"] += any(
            rec.decode for rec in self._inflight)
        for req in active:
            req.processed += 1
        first = None
        if chunk is not None:
            self._ledger["chunks_aboard"] += 1
            if self._chunk_dispatched(chunk):
                # Its first token comes with the rows' tokens, and it
                # decodes from the next step.
                first = chunk[0]
                active = active + [first]
        self._track(True, active, first)
        return True

    def _track(self, decode: bool, reqs: List[Request],
               first: Optional[Request] = None) -> None:
        """Book the execution just dispatched: start its tokens' copy to
        the host, and let go of the slot of every row whose budget ends
        with it, so that the next admission does not wait for the
        harvest. The row keeps its blocks until `_finish`. `first` is the
        request among them whose final chunk rode aboard a decode step."""
        self._tokens.copy_to_host_async()
        rows = []
        for req in reqs:
            rows.append((req, req.slot, req.preemptions))
            req.inflight += 1
            if req.budget_dispatched:
                self._slots[req.slot] = None
                req.slot = None
        self._inflight.append(_InFlight(decode, self._tokens, rows, first))

    def _harvest(self, emissions, keep: int) -> bool:
        """Read the tokens of the oldest executions in flight until
        `keep` are left, and do for each row what the synchronous step
        did right after its dispatch. A row that left meanwhile (EOS at
        the harvest before, cancel, preemption, fail_all) is dropped."""
        import numpy as np

        clock = self._clock
        harvested = False
        while len(self._inflight) > keep:
            rec = self._inflight[0]
            clock.enter(DECODE_SYNC if rec.decode else PREFILL_SYNC)
            tokens = np.asarray(rec.tokens).tolist()
            clock.enter(DECODE_EMIT if rec.decode else PREFILL_HOST)
            self._inflight.popleft()
            harvested = True
            for req, slot, preemptions in rec.rows:
                if req.state != DECODE or req.preemptions != preemptions:
                    self._ledger["dropped_rows"] += 1
                    continue
                req.inflight -= 1
                self._ledger["decode_rows"] += (rec.decode
                                                and req is not rec.first)
                self._emit_token(req, tokens[slot], emissions)
        return harvested

    def _spec_decode_step(self, emissions) -> bool:
        """Speculative round for every DECODE row: draft proposes k
        tokens (k+1 scan steps so the draft KV stays complete), target
        verifies [current, d1..dk] in one [B, k+1] forward. Row i with
        a accepted drafts emits d1..da plus the target's bonus token —
        provably the same tokens plain decoding would emit (greedy
        verify), just more of them per target pass. Rejected proposals
        need no KV rollback: every stale slot is at a position >= the
        row's new `processed`, and the next round's scatter overwrites
        it before any attention read (the causal mask hides it until
        then). Over-provisioned tail blocks stay in the row's table for
        the next round and are released at finish/preemption — never
        leaked."""
        import numpy as np

        cfg = self.config
        k = self._draft_len
        clock = self._clock
        clock.enter(DECODE_HOST)
        active: List[tuple] = []
        for req in list(self._scheduled()):
            if req.state != DECODE:
                continue
            # Rows near the context limit shorten their round: writes
            # never pass max_context (the block table has no slots
            # there; a clipped write would corrupt the last block).
            allow = max(0, min(k, self._max_context - req.processed - 1))
            if self._ensure_blocks(req, req.processed + allow + 1):
                active.append((req, allow))
        active = [(r, a) for r, a in active
                  if r.state == DECODE and r.slot is not None]
        if not active:
            return False
        B = cfg.batch_slots
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        wmask_seq = np.zeros((k + 1, B, 1), bool)
        rows: List[Optional[Request]] = [None] * B
        for req, allow in active:
            i = req.slot
            rows[i] = req
            toks[i, 0] = req.cur_token
            pos[i] = req.processed
            wmask_seq[:allow + 1, i, 0] = True
        bt = self._block_table_rows(rows)
        clock.enter(DECODE_DISPATCH)
        props, self._draft_arenas = self._call(
            "propose", self._propose_fn, self._draft_params,
            self._draft_arenas, toks, bt, pos, wmask_seq)
        clock.enter(DECODE_SYNC)
        props = np.asarray(props)               # [B, k+1]; col j = d_{j+1}
        clock.enter(DECODE_HOST)
        vtoks = np.zeros((B, k + 1), np.int32)
        vmask = np.zeros((B, k + 1), bool)
        for req, allow in active:
            i = req.slot
            vtoks[i, 0] = req.cur_token
            vtoks[i, 1:] = props[i, :k]
            vmask[i, :allow + 1] = True
        clock.enter(DECODE_DISPATCH)
        tgt, self._arenas = self._call(
            "verify", self._verify_fn, self._params, self._arenas,
            self._adapter_args(rows), vtoks, bt, pos, vmask)
        clock.enter(DECODE_SYNC)
        tgt = np.asarray(tgt)                   # [B, k+1] target argmaxes
        clock.enter(DECODE_EMIT)
        self._ledger["decode"] += 1
        self._ledger["decode_rows"] += len(active)
        for req, allow in active:
            i = req.slot
            a = 0
            while a < allow and props[i, a] == tgt[i, a]:
                a += 1
            self._spec_rounds += 1
            self._spec_proposed += allow
            self._spec_accepted += a
            self._spec_hist[a] += 1
            # KV through pos+a is now final; positions beyond hold
            # rejected-draft garbage the next round overwrites.
            req.processed += a + 1
            for j in range(a + 1):
                if req.done:
                    break
                token = int(props[i, j]) if j < a else int(tgt[i, a])
                self._emit_token(req, token, emissions)
        return True

    # ------------------------------------------------------------- helpers

    def _call(self, name: str, fn, *args):
        shape = tuple(getattr(a, "shape", None) for a in args[2:])
        if shape in self._shapes[name]:
            return self._under_mesh(fn, args)
        # A new shape is a new trace: what it adds to the dispatch rule's
        # records is the path this program's attention took.
        from ray_tpu.ops.paged_attention import paged_calls

        self._shapes[name].add(shape)
        before = paged_calls(), paged_calls("tile")
        out = self._under_mesh(fn, args)
        for was, field, said in ((before[0], "path", self._paged_attn),
                                 (before[1], "tile", self._paged_tile)):
            took = {key[1] for key, n in paged_calls(field).items()
                    if n > was.get(key, 0)}
            if name in said and took:
                said[name] = " | ".join(sorted(took))
        return out

    def _under_mesh(self, fn, args):
        """With a tp mesh every program is traced and run under it as
        jax's context mesh: that is where paged attention finds the axis
        to `shard_map` its kernel over (the partitioner cannot split a
        custom call; docs/SHARDED.md)."""
        if self._mesh is None:
            return fn(*args)
        import jax

        with jax.set_mesh(self._mesh):
            return fn(*args)

    def _adapter_args(self, rows):
        """The programs' one `adapters` argument for these batch rows
        (requests, or None where a row is idle): None, or the banks and
        each row's bank row."""
        if self._adapters is None:
            return None
        import numpy as np

        aidx = np.asarray([0 if r is None else r.adapter_row for r in rows],
                          np.int32)
        return self._adapters.device_banks(), aidx

    def _block_table_rows(self, reqs) -> "np.ndarray":  # noqa: F821
        import numpy as np

        bt = np.zeros((len(reqs), self._table_width), np.int32)
        if not self._table_width:
            return bt
        for i, req in enumerate(reqs):
            if req is None or req.done or req.state == WAITING:
                continue
            table = self._bm.block_table(req.request_id)
            bt[i, :len(table)] = table
        return bt

    def _emit_token(self, req: Request, token: int, emissions):
        req.generated.append(token)
        req.cur_token = token
        now = time.monotonic()
        if req.first_token_at is None:
            req.first_token_at = now
        self._tokens_emitted += 1
        self._rate_window.append((now, 1))
        # Prune the stale head here, not just in stats(): an unpolled
        # engine must not grow a tuple per token forever.
        while self._rate_window and now - self._rate_window[0][0] > 5.0:
            self._rate_window.pop(0)
        # Queued even with no callback: delivery is stamped there.
        emissions.append((req.on_token, req, token))
        if (len(req.generated) >= req.max_new_tokens
                or (self.config.eos_id is not None
                    and token == self.config.eos_id)):
            self._finish(req, emissions)

    def _emit_finish(self, req: Request, emissions):
        req.finished_at = time.monotonic()
        if req.on_finish is not None:
            emissions.append((req.on_finish, req, None))

    def _finish(self, req: Request, emissions, error: Optional[str] = None):
        req.state = FAILED if error else FINISHED
        req.error = error
        if error:
            self._failed += 1
        else:
            self._finished += 1
        # Donate the finished sequence's full-block prefix to the radix
        # cache BEFORE freeing: insert increfs the novel suffix, free
        # decrefs the request's own references, net the cache keeps
        # exactly the new blocks. Errors skip the donation (a cancelled
        # stream's KV is valid but its tail may be mid-write).
        if (self._prefix is not None and not error
                and self._bm.registered(req.request_id)):
            stream = req.prompt + req.generated
            nb = min(req.processed, len(stream)) // self.config.block_size
            if nb > 0:
                self._donate(req, stream[:nb * self.config.block_size], nb)
        self._unpin_req(req)
        self._release(req.request_id)
        if req.slot is not None:
            self._slots[req.slot] = None
            req.slot = None
        self._live.pop(req.request_id, None)
        self._emit_finish(req, emissions)
        self._record_phase_spans(req)

    def _donate(self, req: Request, tokens: List[int], nb: int) -> None:
        self._prefix.insert(tokens,
                            self._bm.block_table(req.request_id)[:nb])

    def fail_all(self, error: str) -> int:
        """Abort every scheduled and waiting request with `error` (the
        EngineLoop's circuit breaker after repeated step failures —
        callers must see the failure, not hang on futures nothing will
        resolve). Returns how many requests were failed."""
        emissions: List[tuple] = []
        failed = 0
        with self._lock:
            for req in list(self._scheduled()):
                self._finish(req, emissions, error=error)
                failed += 1
            # Rows that gave up their slot with their last token in flight.
            for rec in self._inflight:
                for req, _, _ in rec.rows:
                    if req.state == DECODE:
                        self._finish(req, emissions, error=error)
                        failed += 1
            self._inflight.clear()
            self._tokens = self._fresh_tokens()
            for req in self._waiting:
                req.state = FAILED
                req.error = error
                self._failed += 1
                failed += 1
                self._live.pop(req.request_id, None)
                self._emit_finish(req, emissions)
            self._waiting.clear()
            # Rebuild the arena: a step that failed mid-execution consumed
            # the DONATED buffers without producing replacements, so the
            # old self._arenas may reference deleted arrays — without this
            # every future request would fail on 'Array has been deleted'
            # and the circuit breaker could never actually recover.
            self._arenas = self._fresh_cache(self._model)
            if self._draft_arenas is not None:
                self._draft_arenas = self._fresh_cache(self._draft_model)
            # Fresh arenas invalidate every cached block's contents: a
            # warm radix tree pointing at zeroed KV would serve garbage.
            if self._prefix is not None:
                self._prefix.clear()
        self._deliver(emissions)
        return failed

    def _record_deliver_span(self, req: Request):
        """engine.deliver: first token harvested -> its on_token about to
        run. It overlaps the head of engine.decode: the rest of the
        harvest that read the token."""
        if not _tracing._ENABLED or req.trace_ctx is None:
            return
        _tracing.get_tracer().record_span(
            "engine.deliver", _tracing.epoch_of(req.first_token_at),
            _tracing.epoch_of(req.first_token_delivered_at),
            parent_ctx=req.trace_ctx, attrs={"request": req.request_id})

    def _adoption_attrs(self, req: Request) -> Dict[str, Any]:
        """What the `engine.prefill` span says of the cached state a
        request adopted, beyond `cached_tokens`."""
        return {}

    def _record_phase_spans(self, req: Request):
        """TTFT decomposition, recorded once per finished request under
        its captured trace context: engine.queue (submit -> first
        admission), engine.prefill (admission -> first token),
        engine.decode (first token -> finish). With engine.preempt
        markers in between, a timeline answers "where did this request's
        latency go" per phase."""
        if not _tracing._ENABLED or req.trace_ctx is None:
            return
        tracer = _tracing.get_tracer()
        eo = _tracing.epoch_of
        end = req.finished_at if req.finished_at is not None \
            else time.monotonic()
        attrs = {"request": req.request_id}
        tracer.record_span(
            "engine.queue", eo(req.submitted_at),
            eo(req.admitted_at if req.admitted_at is not None else end),
            parent_ctx=req.trace_ctx, attrs=attrs, error=req.error)
        if req.admitted_at is not None:
            tracer.record_span(
                "engine.prefill", eo(req.admitted_at),
                eo(req.first_token_at if req.first_token_at is not None
                   else end),
                parent_ctx=req.trace_ctx,
                attrs=dict(attrs, prompt_tokens=len(req.prompt),
                           **self._adoption_attrs(req)))
        if req.first_token_at is not None:
            tracer.record_span(
                "engine.decode", eo(req.first_token_at), eo(end),
                parent_ctx=req.trace_ctx,
                attrs=dict(attrs, tokens=len(req.generated),
                           preemptions=req.preemptions,
                           **({"blocks": req.blocks, "passes": req.passes}
                              if req.passes else {})))

    # --------------------------------------------------------------- stats

    def stats(self) -> Dict[str, Any]:
        """Engine statistics. Non-blocking: a step mid-XLA-compile can
        hold the engine lock for seconds, and the replica's health check
        (stats with a 1s timeout) must not read that as a dead replica —
        fall back to the last snapshot instead of parking, and say how
        old it is (`snapshot_age_s`, 0.0 when fresh). `steps` is fresh
        either way.

        A model that keeps counters in its cache (`cache_counters`) has
        them copied out here and read once the copy has landed: what they
        say is as of an earlier call, and they are cumulative, so a late
        read loses nothing."""
        if self._lock.acquire(timeout=0.2):
            try:
                self._last_stats = self._stats_locked()
                self._last_stats_at = time.monotonic()
                self._copy_counters_locked()
            finally:
                self._lock.release()
            age = 0.0
        else:
            age = time.monotonic() - self._last_stats_at
        self._read_counters()
        return {**self._last_stats, **self._counter_stats,
                "snapshot_age_s": age, "steps": self.step_stats()}

    def _copy_counters_locked(self) -> None:
        """Dispatch a copy of the model's device counters (a few KB). Under
        the lock the cache is not mid-donation; the copy is ordered behind
        the execution in flight and outlives the next donation. One copy
        is outstanding at a time."""
        peek = self._model.cache_counters
        if peek is None or self._counters_pending is not None:
            return
        import jax
        import jax.numpy as jnp

        self._counters_pending = jax.tree.map(jnp.copy, peek(self._arenas))

    def _read_counters(self) -> None:
        import jax

        pending = self._counters_pending
        if pending is None or not all(
                leaf.is_ready() for leaf in jax.tree.leaves(pending)):
            return
        self._counters_pending = None
        self._counter_stats = self._model.counter_stats(
            jax.device_get(pending))

    def step_stats(self) -> Dict[str, Any]:
        """The step ledger as of the end of the last step: cumulative
        counts and seconds, read without the engine lock. Take the
        difference of two reads for a rate: host time of a step is
        (wall_s - the two *.sync phases) / n; decode rows per execution
        is decode_rows / decode."""
        steps = self._steps
        return {**steps, "phase_s": dict(steps["phase_s"])}

    def _publish_steps(self) -> None:
        # One assignment of a dict nobody mutates afterwards: a reader
        # sees all of a step or none of it.
        phase_s = dict(self._clock.seconds)
        self._steps = {**self._ledger, "wait_work_s": phase_s[WAIT_WORK],
                       "phase_s": phase_s}

    def _stats_locked(self) -> Dict[str, Any]:
        now = time.monotonic()
        self._rate_window = [(t, n) for t, n in self._rate_window
                             if now - t <= 5.0]
        window_tokens = sum(n for _, n in self._rate_window)
        span = (now - self._rate_window[0][0]) if self._rate_window else 0.0
        running = [r for r in self._slots if r is not None
                   and r.state in (PREFILL, DECODE)]
        return {
            **self._device,
            "queue_depth": len(self._waiting),
            "running": len(running),
            "tp": self._tp,
            "batch_slots": self.config.batch_slots,
            "tokens_emitted": self._tokens_emitted,
            "tokens_per_sec": (window_tokens / span) if span > 0 else 0.0,
            "requests_finished": self._finished,
            "requests_failed": self._failed,
            "preemptions": self._preemptions,
            "prefill_compiles": self._program_compiles("prefill"),
            "decode_compiles": self._program_compiles("decode"),
            "decode_with_chunk_compiles":
                self._program_compiles("decode_with_chunk"),
            "paged_attn": dict(self._paged_attn),
            "paged_attn_tile": dict(self._paged_tile),
            # `bytes`: what the cache holds beside the per-slot state
            # (the paged arenas; 0 for a cache with no paged part).
            "kv": {**self._bm.stats(), "bytes": self._kv_bytes},
            # What the model keeps per batch slot beside the paged blocks
            # (recurrent state): nothing for a model without any.
            "state": {
                "slots": (self.config.batch_slots
                          if self._slot_state_bytes else 0),
                "bytes": self._slot_state_bytes * self.config.batch_slots,
                "resets": self._state_resets,
                "prefix_adoptions_refused": self._prefix_refused,
            },
            "prefix_cache": (self._prefix.stats() if self._prefix is not None
                             else {"enabled": False, "cached_blocks": 0,
                                   "hit_rate": 0.0, "hit_tokens": 0}),
            "spec_decode": {
                "draft_len": self._draft_len,
                "rounds": self._spec_rounds,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "accept_rate": (self._spec_accepted / self._spec_proposed
                                if self._spec_proposed else 0.0),
                "mean_accepted": (self._spec_accepted / self._spec_rounds
                                  if self._spec_rounds else 0.0),
                "accepted_hist": list(self._spec_hist),
                "draft_prefill_compiles":
                    self._program_compiles("draft_prefill"),
                "propose_compiles": self._program_compiles("propose"),
                "verify_compiles": self._program_compiles("verify"),
            },
            "slo": {
                "reserved_slots": self._slo_reserved,
                "waiting_interactive": sum(
                    1 for r in self._waiting
                    if r.slo_class == "interactive"),
                "waiting_batch": sum(1 for r in self._waiting
                                     if r.slo_class == "batch"),
            },
            **({"adapters": self._adapters.stats()}
               if self._adapters is not None else {}),
            **({"diffusion": {**self._diffusion, "committed_hist": list(
                self._diffusion["committed_hist"])}}
               if self._block is not None else {}),
        }

    def check_no_leaks(self):
        """Test hook: once every request has finished, the only arena
        references left are the radix cache's (its synthetic tables are
        audited by check_consistency like live sequences), nothing is
        pinned, and the cache's own tree matches its tables. Without a
        cache this degenerates to the classic blocks_in_use == 0."""
        with self._lock:
            self._bm.check_consistency()
            cached = (self._prefix.cached_blocks()
                      if self._prefix is not None else 0)
            assert self._bm.blocks_in_use() == cached, (
                self._bm.stats(), cached)
            if self._prefix is not None:
                self._prefix.check_consistency()
                if not self._live:
                    assert self._prefix.total_pins() == 0

    def drop_prefix_cache(self) -> int:
        """Release every cached prefix block back to the pool (test
        drains, memory-pressure escape hatch). Returns blocks freed."""
        with self._lock:
            if self._prefix is None:
                return 0
            return self._prefix.clear()


class _KindsEngine(InferenceEngine):
    """The engine of a model whose cache holds TWO kinds of paged state
    (`cache_kinds`; docs/INFERENCE.md finding (j)): the first as every
    model's (its table bounds a sequence, the radix cache keeps its
    blocks), the second AGEING: a query reads its last `window` positions
    alone, so the kind has a pool and a table of its own
    (`WindowBlockManager`), a live sequence gives back the pages that fall
    behind its window at every claim, and the radix cache keeps the tail
    before a node's end (`WindowedRadixCache`). `paged_step` is handed a
    table a kind. `InferenceEngine(...)` makes one of these where the model
    states kinds; everything that differs is an override here, and asks
    the second pool where a request arrives or leaves, never a row a
    step."""

    def __init__(self, config: EngineConfig, model=None, params=None,
                 **kwargs):
        kinds = model.cache_kinds
        names = list(kinds)
        windows = [kinds[k].get("window") for k in names]
        if len(names) != 2 or windows[0] is not None or not windows[1]:
            raise ValueError(
                f"cache_kinds {kinds}: the engine holds one kind that keeps "
                f"every position and one with a window")
        if model.pageless_context is not None:
            raise ValueError("cache_kinds with a pageless cache")
        if int(config.spec_decode_draft_len) > 0:
            raise ValueError(
                "spec_decode_draft_len > 0 with a model that holds an "
                "ageing kind of paged state: a draft shares the target's "
                "tables, and a rejected position's window page may be gone")
        if config.window_blocks < 2:
            raise ValueError(
                f"the model's {names[1]!r} kind needs a pool: "
                f"EngineConfig.window_blocks >= 2")
        self._kind_names = tuple(names)
        self._wbm = WindowBlockManager(config.window_blocks,
                                       config.block_size, int(windows[1]))
        super().__init__(config, model, params, **kwargs)

    def _fresh_cache(self, model):
        cfg = self.config
        return model.paged_cache(
            self._bm.num_blocks, cfg.block_size, self._mesh, cfg.batch_slots,
            kinds={self._kind_names[1]: self._wbm.num_blocks})

    def _new_prefix_cache(self) -> RadixPrefixCache:
        return WindowedRadixCache(self._bm, self._wbm)

    def _window_claim(self, req: Request, num_tokens: int,
                      preempt: bool) -> bool:
        """The window kind's part of a claim: give back what lies behind
        the window of the first position still to be written, then grow
        the table; cold cached tails go before anybody is preempted."""
        wbm, rid = self._wbm, req.request_id
        wbm.release_below(rid, req.processed // wbm.block_size - wbm.tail)
        while not wbm.ensure(rid, num_tokens):
            deficit = (wbm.blocks_for_tokens(num_tokens)
                       - len(wbm.block_table(rid)) - wbm.num_free())
            if (self._prefix is not None
                    and self._prefix.evict_for(deficit, window=True) > 0):
                continue
            if not preempt or not self._preempt_one():
                return False
            if req.state == WAITING:
                return False
        return True

    def _ensure_blocks(self, req: Request, num_tokens: int,
                       preempt: bool = True) -> bool:
        return super()._ensure_blocks(req, num_tokens, preempt) \
            and self._window_claim(req, num_tokens, preempt)

    def _block_table_rows(self, reqs) -> Dict[str, Any]:
        import numpy as np

        full, window = self._kind_names
        out = {full: super()._block_table_rows(reqs),
               window: np.zeros((len(reqs), self._table_width), np.int32)}
        for i, req in enumerate(reqs):
            if req is None or req.done or req.state == WAITING:
                continue
            table = self._wbm.block_table(req.request_id)
            out[window][i, :len(table)] = table
        return out

    def _admit_blocks(self, req: Request) -> Optional[int]:
        matched_tokens = super()._admit_blocks(req)
        if matched_tokens is None:
            return None
        cfg, wbm, rid = self.config, self._wbm, req.request_id
        if matched_tokens:
            wbm.register_with_blocks(
                rid, self._prefix.window_table(req._pinned_node))
        else:
            wbm.register(rid)
        adopted = wbm.pages_held(rid)
        req.processed = matched_tokens         # where its window ends
        first = min(req.total_to_prefill,
                    matched_tokens + cfg.prefill_chunk)
        if not self._window_claim(req, first, preempt=False):
            req.processed = 0
            self._unpin_req(req)
            self._release(rid)
            return None
        req.cached_window_tokens += cfg.block_size * adopted
        return matched_tokens

    def _release(self, request_id: str) -> None:
        super()._release(request_id)
        self._wbm.free(request_id)

    def _donate(self, req: Request, tokens: List[int], nb: int) -> None:
        self._prefix.insert(tokens,
                            self._bm.block_table(req.request_id)[:nb],
                            self._wbm.block_table(req.request_id)[:nb])

    def _adoption_attrs(self, req: Request) -> Dict[str, Any]:
        full, window = self._kind_names
        return {"adopted_tokens": {full: req.cached_tokens,
                                   window: req.cached_window_tokens}}

    def _stats_locked(self) -> Dict[str, Any]:
        return {**super()._stats_locked(), "kv_kinds": self._kinds_stats()}

    def _kinds_stats(self) -> Dict[str, Any]:
        """`stats()["kv_kinds"]`: a pool a kind. `cached`: the blocks the
        radix cache holds of it; the window kind also says the pages given
        back behind a window (cumulative: its readers difference two
        reads) and the lookups that gave up matched blocks for want of
        window pages."""
        full, window = self._kind_names
        prefix = self._prefix.stats() if self._prefix is not None else {}
        out = {}
        for name, bm, cached in (
                (full, self._bm, prefix.get("cached_blocks", 0)),
                (window, self._wbm, prefix.get("cached_window_blocks", 0))):
            st = bm.stats()
            out[name] = {"blocks": st["num_blocks"],
                         "in_use": st["blocks_in_use"],
                         "peak": st["peak_blocks_in_use"],
                         "free": st["blocks_free"], "cached": cached}
        out[window].update(
            window=self._wbm.window, tail_blocks=self._wbm.tail,
            window_blocks_released=self._wbm.released,
            adoptions_refused=prefix.get("window_adoptions_refused", 0))
        return out

    def check_no_leaks(self):
        super().check_no_leaks()
        with self._lock:
            self._wbm.check_consistency()
            cached = (self._prefix.cached_window_blocks()
                      if self._prefix is not None else 0)
            assert self._wbm.blocks_in_use() == cached, (
                self._wbm.stats(), cached)


class EngineLoop:
    """Background thread driving `engine.step()` while there is work.

    Submissions from any thread; the replica's asyncio loop talks to it
    through thread-safe callbacks (`api.py`)."""

    def __init__(self, engine: InferenceEngine):
        self.engine = engine
        self._cv = threading.Condition()
        self._stopped = False
        self._thread = threading.Thread(target=self._run,
                                        name="inference-engine",
                                        daemon=True)
        self._thread.start()

    # After this many consecutive step failures every in-flight request
    # is failed (fail_all) instead of retrying the same broken state
    # forever while callers hang on futures nothing will resolve.
    MAX_CONSECUTIVE_FAILURES = 3

    def submit(self, *args, **kwargs) -> Request:
        # Check-and-enqueue under the loop's condition: a submit racing
        # stop() must either raise or land before stop's fail_all sweep —
        # never slip into a queue no thread will ever drain.
        with self._cv:
            if self._stopped:
                raise RuntimeError(
                    "engine loop is stopped (replica shutdown)")
            req = self.engine.add_request(*args, **kwargs)
            self._cv.notify()
        return req

    def _run(self):
        failures = 0
        clock = self.engine._clock
        while True:
            clock.enter(WAIT_WORK)
            with self._cv:
                while not self._stopped and not self.engine.has_work():
                    self._cv.wait(timeout=0.05)
                if self._stopped:
                    clock.leave()
                    self.engine._publish_steps()
                    return
            try:
                self.engine.step()
                failures = 0
            except Exception as e:  # noqa: BLE001 — scheduler survives a
                failures += 1       # bad step; circuit-break if persistent
                logger.exception("inference engine step failed (%d/%d)",
                                 failures, self.MAX_CONSECUTIVE_FAILURES)
                if failures >= self.MAX_CONSECUTIVE_FAILURES:
                    self.engine.fail_all(
                        f"engine step failed repeatedly: "
                        f"{type(e).__name__}: {e}")
                    failures = 0
                else:
                    time.sleep(0.01)

    def stop(self, timeout_s: float = 5.0):
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        self._thread.join(timeout=timeout_s)
        # Anything still parked (a request that slipped in as we stopped)
        # must fail fast, not hang its caller.
        self.engine.fail_all("engine loop stopped")
