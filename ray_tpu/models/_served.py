"""The served-model contract as code: `PagedModel` is everything
`InferenceEngine` (`ray_tpu/inference/engine.py`) asks of the model it is
handed, each answer with the default most models give. A served model
derives from it, states `paged_cache` and `paged_step`, and overrides only
what differs; the engine reads the attributes below directly, so one that
is not named here does not exist (`tests/test_models_layering.py`). Why
each answer is what it is, and what was found on the way:
docs/INFERENCE.md, "The model contract".

It lives under `models/` so that the arrow stays `inference -> (handed a)
model`: this file imports jax and nothing of `ray_tpu`.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp


class PagedModel:
    """A model the paged engine can serve. The engine knows nothing of the
    model's family: it is handed `model` and `params` and asks what is
    below, and nothing else."""

    # Whether a prefix of blocks alone restores a sequence. Where not,
    # nothing is adopted from or donated to the radix prefix cache, and
    # speculation is refused: its rejected positions would need a rollback.
    prefix_restores = True
    # What a slot holds beside the blocks, in bytes (for `stats()`).
    slot_state_bytes = 0
    # Answered only by a model whose cache has NO paged part (per-slot
    # state and nothing else): the positions a sequence may reach. The
    # engine then hands out no block (`kv_cache.NoBlocks`: admission is by
    # free slots alone and nothing is ever preempted for blocks), gives the
    # step programs a block table zero blocks wide, and bounds a request by
    # that context and not by `max_blocks_per_seq`.
    pageless_context = None
    # `paged_step_with_chunk(params, tokens[b, 1], chunk_ids[1, c], cache,
    # block_tables, row_pos, write_mask, chunk_bt, chunk_pos, chunk_wmask,
    # chunk_slot, last_idx) -> (logits [b, vocab], the chunk's logits at
    # last_idx [1, vocab], cache)`: a decode step and one sequence's
    # prefill chunk as ONE execution, which reads the weights once for
    # both. Read once, when the engine builds its programs; a model
    # without it keeps the prefill and decode programs alone.
    paged_step_with_chunk = None
    # Of a model whose unit of work is a BLOCK of `length` positions that
    # several passes denoise: its `mask_id`, its `schedule` of positions a
    # pass commits, its `select` rule, which runs on the device
    # (`models/sdar.py BlockDecode`). Read once, when the programs are
    # built; the engine then decodes by blocks
    # (`InferenceEngine._build_block_programs`, docs/INFERENCE.md finding
    # (i)) and hands `paged_step` `read_from` [b], where in its row the
    # block begins whose logits are read (logits[b, length, vocab]).
    decode_block = None
    # Of a model whose cache holds more than one KIND of paged state: an
    # ordered {kind: {"window": None | w}}. The first kind keeps every
    # position (its table bounds a sequence); a kind with a `window` is
    # read for a query's last w positions alone, its own among them. Read
    # once, when the engine is built; it then keeps a pool and a block
    # table a kind (`EngineConfig.window_blocks`), hands `paged_cache`
    # `kinds={kind: blocks}` for every kind but the first and `paged_step`
    # a dict of tables by kind, gives back the pages behind a live
    # sequence's window and keeps, for a cached prefix, the windowed
    # kind's last pages alone (docs/INFERENCE.md finding (j)).
    cache_kinds = None
    # `cache_counters(cache)`: the part of the cache the host may read when
    # `stats()` is asked, small device arrays; `counter_stats` turns a host
    # copy of them into entries of `stats()` (docs/INFERENCE.md finding
    # (f)). A model with no counters leaves both alone.
    cache_counters = None

    def counter_stats(self, host) -> Dict[str, Any]:
        return {}

    def paged_cache(self, num_blocks: int, block_size: int, mesh=None,
                    batch_slots: Optional[int] = None):
        """The paged cache, a pytree the engine donates to every step and
        never looks inside (and builds anew in `fail_all`). It may hold
        state per batch SLOT beside the paged blocks, which is why it is
        told their number."""
        raise NotImplementedError

    def paged_step(self, params, ids, cache, block_tables, row_pos,
                   write_mask, adapters=None, slots=None, last_idx=None):
        """The one step: ids [b, s] at positions row_pos[b] + arange(s) ->
        (logits, cache). `adapters` is None or (banks, adapter_idx[b]);
        `slots` is each row's batch slot (None: row i is slot i, as in
        decode) and `last_idx` the one position a row whose logits are
        read (None: every position, logits[b, s, vocab]; else logits[b,
        vocab]). A slot is the address of a row's per-slot state,
        `write_mask` its hold (a row with no live position keeps its
        state), and a live row whose first position is 0 starts from zero
        state."""
        raise NotImplementedError

    def forward(self, params, ids, block_size: int = 16):
        """Logits [b, s, vocab] of whole sequences from position 0: one
        `paged_step` over a cache of its own (tests, offline scoring)."""
        b, s = ids.shape
        per_row = -(-s // block_size)
        cache = self.paged_cache(1 + b * per_row, block_size, None, b)
        tables = 1 + jnp.arange(b * per_row, dtype=jnp.int32).reshape(
            b, per_row)
        logits, _ = self.paged_step(
            params, ids, cache, tables, jnp.zeros((b,), jnp.int32),
            jnp.ones((b, s), bool), None, jnp.arange(b, dtype=jnp.int32))
        return logits

    def place_on_mesh(self, params, mesh):
        """(params placed for the mesh, its tp degree). The default serves
        on one device: tp = 1 only."""
        axes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if int(axes.get("tp", 1)) != 1:
            raise ValueError(f"{type(self).__name__} serves at tp = 1 only")
        return params, 1

    def early_exit_draft(self, params):
        """(draft model, its params), asked only when speculation is on and
        no draft was injected."""
        raise ValueError(f"{type(self).__name__} has no draft")

    def adapter_banks(self, n_rows: int, rank: int, mesh=None):
        """The adapter banks' (layers, one layer's shapes, their dtype,
        their shardings), asked only by `AdapterManager`."""
        raise ValueError(f"{type(self).__name__} has no adapter banks")
