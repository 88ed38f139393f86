"""The engine is written against the model it is handed (docs/INFERENCE.md,
"The model contract"): it names no model family, spells each program
once, and has one scheduling policy and one way to set its options."""

import ast
import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu.inference.engine import EngineConfig, InferenceEngine
from ray_tpu.models._served import PagedModel

# ------------------------------------------------- a model that is no Llama


class BagModel(PagedModel):
    """Not a flax module, no attention, no layers: a token's logits come
    from its embedding plus the mean of the embeddings cached at its
    row's positions up to its own. Its paged cache is a dict of one
    [blocks, block_size, width] array and a count of the steps that
    wrote it, which nothing but this class reads. It answers the two
    questions every engine asks; the rest is `PagedModel`'s defaults (its
    cache is the paged blocks alone; mesh, draft and adapters are asked
    only of engines that have those)."""

    vocab, width = 64, 8

    def init(self, seed):
        rng = np.random.default_rng(seed)
        return {"embed": rng.standard_normal(
                    (self.vocab, self.width)).astype(np.float32),
                "head": rng.standard_normal(
                    (self.width, self.vocab)).astype(np.float32)}

    def paged_cache(self, num_blocks, block_size, mesh=None,
                    batch_slots=None):
        import jax.numpy as jnp

        assert mesh is None
        return {"mem": jnp.zeros((num_blocks, block_size, self.width),
                                 jnp.float32),
                "steps": jnp.zeros((), jnp.int32)}

    def paged_step(self, params, ids, cache, block_tables, row_pos,
                   write_mask, adapters=None, slots=None, last_idx=None):
        import jax.numpy as jnp

        assert adapters is None
        nb, bs, w = cache["mem"].shape
        b, s = ids.shape
        x = jnp.asarray(params["embed"])[ids]                  # [b, s, w]
        pos = row_pos[:, None] + jnp.arange(s)[None, :]        # [b, s]
        blk = jnp.clip(pos // bs, 0, block_tables.shape[1] - 1)
        phys = jnp.take_along_axis(block_tables, blk, axis=1)
        flat = (jnp.where(write_mask, phys, 0) * bs + pos % bs).reshape(-1)
        mem = cache["mem"].reshape(nb * bs, w).at[flat].set(
            x.reshape(-1, w)).reshape(nb, bs, w)
        seen = mem[block_tables].reshape(b, -1, w)             # logical order
        live = jnp.arange(seen.shape[1])[None, None, :] <= pos[:, :, None]
        ctx = jnp.einsum("bsk,bkw->bsw", live.astype(jnp.float32), seen) \
            / (pos[:, :, None] + 1)
        hidden = jnp.tanh(x + ctx)
        if last_idx is not None:       # logits where they are read only
            hidden = jnp.take_along_axis(
                hidden, last_idx[:, None, None], axis=1)[:, 0]
        logits = hidden @ jnp.asarray(params["head"])
        return logits, {"mem": mem, "steps": cache["steps"] + 1}


class RidingBagModel(BagModel):
    """`BagModel` with the contract's optional sixth answer, spelled as the
    two steps it stands for: the engine lets a prefill chunk ride in the
    decode step of a model that has it, and only of such a model."""

    def paged_step_with_chunk(self, params, tokens, chunk_ids, cache,
                              block_tables, row_pos, write_mask, chunk_bt,
                              chunk_pos, chunk_wmask, chunk_slot, last_idx):
        chunk_logits, cache = self.paged_step(
            params, chunk_ids, cache, chunk_bt, chunk_pos, chunk_wmask, None,
            chunk_slot, last_idx)
        logits, cache = self.paged_step(params, tokens, cache, block_tables,
                                        row_pos, write_mask)
        return logits[:, -1], chunk_logits, cache


@pytest.fixture(scope="module", params=[BagModel, RidingBagModel],
                ids=["two_programs", "a_fused_step_too"])
def bag(request):
    model = request.param()
    return model, model.init(7)


def _bag_engine(bag, **kwargs):
    model, params = bag
    cfg = dict(batch_slots=3, block_size=4, num_blocks=64,
               max_blocks_per_seq=16, prefill_chunk=8,
               prefix_cache_enabled=False)
    cfg.update(kwargs)
    return InferenceEngine(EngineConfig(**cfg), model=model, params=params)


def _plain(bag, engine, prompt, n):
    """One request, one token at a time, through `paged_step` on a cache
    of its own, at the engine's shapes: no scheduler, no other row."""
    import jax

    model, params = bag
    cfg = engine.config
    width = cfg.max_blocks_per_seq
    cache = model.paged_cache(width + 1, cfg.block_size)
    step = jax.jit(model.paged_step)

    def forward(ids, pos, rows, live):
        nonlocal cache
        toks = np.zeros((rows, live), np.int32)
        toks[0, :len(ids)] = ids
        wmask = np.zeros((rows, live), bool)
        wmask[0, :len(ids)] = True
        bt = np.zeros((rows, width), np.int32)
        bt[0] = np.arange(1, width + 1)
        row_pos = np.zeros(rows, np.int32)
        row_pos[0] = pos
        logits, cache = step(params, toks, cache, bt, row_pos, wmask)
        return int(np.argmax(np.asarray(logits)[0, len(ids) - 1]))

    chunk = cfg.prefill_chunk
    for at in range(0, len(prompt), chunk):
        token = forward(prompt[at:at + chunk], at, 1, chunk)
    toks = [token]
    while len(toks) < n:
        toks.append(forward([toks[-1]], len(prompt) + len(toks) - 1,
                            cfg.batch_slots, 1))
    return toks


def _prompt(n, base):
    return [(base + 5 * i) % 60 + 1 for i in range(n)]


def _case_chunked_prefill_and_decode(bag):
    """More requests than slots, prompts of one to three chunks."""
    engine = _bag_engine(bag)
    mix = [(_prompt(5, 3), 9), (_prompt(19, 11), 6), (_prompt(8, 29), 1),
           (_prompt(13, 41), 12), (_prompt(2, 17), 7)]
    reqs = [engine.add_request(p, m) for p, m in mix]
    engine.run_until_idle()
    steps = engine.step_stats()
    assert steps["prefill"] + steps["chunks_aboard"] == 1 + 3 + 1 + 2 + 1
    # The first chunk finds no row decoding; the others ride where they
    # may.
    assert steps["chunks_aboard"] == (
        7 if isinstance(bag[0], RidingBagModel) else 0)
    return engine, list(zip(reqs, mix))


def _case_preemption(bag):
    """A pool too small for both rows: one is freed and recomputed."""
    engine = _bag_engine(bag, batch_slots=2, block_size=2, num_blocks=9,
                         max_blocks_per_seq=8, prefill_chunk=4)
    mix = [([1, 2, 3], 10), ([4, 5, 6], 10)]
    reqs = [engine.add_request(p, m) for p, m in mix]
    engine.run_until_idle()
    assert engine.stats()["preemptions"] >= 1
    return engine, list(zip(reqs, mix))


def _case_fail_all_rebuilds_the_cache(bag):
    """A step that died mid-execution took the donated cache with it:
    `fail_all` asks the model for a new one and the engine serves on."""
    import jax

    engine = _bag_engine(bag)
    lost = [engine.add_request(_prompt(6, i), 8) for i in range(4)]
    for _ in range(3):
        engine.step()
    for leaf in jax.tree.leaves(engine._arenas):
        leaf.delete()
    assert engine.fail_all("the step died") == 4
    assert all(r.error == "the step died" for r in lost)
    assert int(engine._arenas["steps"]) == 0
    mix = [(_prompt(11, 5), 7), (_prompt(4, 23), 9)]
    reqs = [engine.add_request(p, m) for p, m in mix]
    engine.run_until_idle()
    return engine, list(zip(reqs, mix))


def _case_prefix_cache(bag):
    """The second request adopts the first one's blocks out of a cache
    the engine has never looked inside."""
    engine = _bag_engine(bag, prefix_cache_enabled=True)
    shared = _prompt(16, 21)
    first = engine.add_request(shared + [7, 8], 7)
    engine.run_until_idle()
    second = engine.add_request(shared + [7, 8, 9], 5)
    engine.run_until_idle()
    assert second.cached_tokens == 16
    return engine, [(first, (first.prompt, 7)), (second, (second.prompt, 5))]


BAG_CASES = {
    "chunked_prefill_and_decode": _case_chunked_prefill_and_decode,
    "preemption": _case_preemption,
    "fail_all_rebuilds_the_cache": _case_fail_all_rebuilds_the_cache,
    "prefix_cache_hit": _case_prefix_cache,
}


@pytest.mark.parametrize("case", list(BAG_CASES))
def test_the_engine_serves_a_model_that_is_no_llama(bag, case):
    engine, served = BAG_CASES[case](bag)
    for req, (prompt, n) in served:
        assert req.state == "FINISHED"
        assert req.generated == _plain(bag, engine, prompt, n), (
            case, req.request_id)
    assert not engine.has_work()
    engine.check_no_leaks()
    stats = engine.stats()
    assert stats["prefill_compiles"] == stats["decode_compiles"] == 1
    # Every execution of the programs went through the one cache (the
    # rebuilt one has missed those before `fail_all`); a fused execution
    # is this model's two steps.
    steps = engine.step_stats()
    ran = steps["prefill"] + steps["decode"] + steps["chunks_aboard"]
    assert int(engine._arenas["steps"]) == ran or case.startswith("fail_all")
    # The engine asked for the fused step once, when it was built: a model
    # without one has no third program and never reaches that branch.
    if isinstance(bag[0], RidingBagModel):
        assert stats["decode_with_chunk_compiles"] <= 1
    else:
        assert engine._decode_with_chunk_fn is None
        assert steps["chunks_aboard"] == 0 \
            == stats["decode_with_chunk_compiles"]


# ------------------------------- a model whose cache has no paged part


class RunningMeanModel(PagedModel):
    """No blocks at all: its cache is the sum of a slot's embeddings so
    far, and a token's logits come from that sum over its position + 1.
    `pageless_context` says so (docs/INFERENCE.md, finding (e)): the
    engine is to ask for 0 blocks, hand a table 0 blocks wide, admit by
    slots alone and bound a request by this context."""

    vocab, width = 64, 8
    prefix_restores = False
    pageless_context = 40
    slot_state_bytes = 4 * width

    def init(self, seed):
        rng = np.random.default_rng(seed)
        return {"embed": rng.standard_normal(
                    (self.vocab, self.width)).astype(np.float32),
                "head": rng.standard_normal(
                    (self.width, self.vocab)).astype(np.float32)}

    def paged_cache(self, num_blocks, block_size, mesh=None,
                    batch_slots=None):
        import jax.numpy as jnp

        assert num_blocks == 0 and mesh is None
        return {"sum": jnp.zeros((batch_slots, self.width), jnp.float32)}

    def paged_step(self, params, ids, cache, block_tables, row_pos,
                   write_mask, adapters=None, slots=None, last_idx=None):
        import jax.numpy as jnp

        assert adapters is None and block_tables.shape[1] == 0
        rows = jnp.arange(ids.shape[0]) if slots is None else slots
        # hold before reset: a row with no live position keeps its sum
        fresh = write_mask[:, 0] & (row_pos == 0)
        start = jnp.where(fresh[:, None], 0.0, cache["sum"][rows])
        x = jnp.asarray(params["embed"])[ids] * write_mask[..., None]
        run = start[:, None, :] + jnp.cumsum(x, axis=1)
        pos = row_pos[:, None] + jnp.arange(ids.shape[1])[None, :]
        hidden = jnp.tanh(run / (pos[..., None] + 1.0))
        if last_idx is not None:
            hidden = jnp.take_along_axis(
                hidden, last_idx[:, None, None], axis=1)[:, 0]
        return hidden @ jnp.asarray(params["head"]), {
            "sum": cache["sum"].at[rows].set(run[:, -1])}


def _running_mean_tokens(params, prompt, n):
    ids, out = list(prompt), []
    for _ in range(n):
        mean = params["embed"][ids].sum(0) / len(ids)
        out.append(int(np.argmax(np.tanh(mean) @ params["head"])))
        ids.append(out[-1])
    return out


def test_the_engine_serves_a_model_with_no_paged_part():
    """More requests than slots and far more tokens than `num_blocks` x
    `block_size` would hold: nothing is refused for blocks, nothing is
    preempted, and a slot's next owner starts from zero."""
    model = RunningMeanModel()
    params = model.init(11)
    engine = InferenceEngine(
        EngineConfig(batch_slots=2, block_size=4, num_blocks=2,
                     max_blocks_per_seq=1, prefill_chunk=8),
        model=model, params=params)
    assert list(engine._arenas) == ["sum"]
    mix = [(_prompt(5, 3), 9), (_prompt(19, 11), 6), (_prompt(8, 29), 1),
           (_prompt(13, 41), 12), (_prompt(2, 17), 7)]
    reqs = [engine.add_request(p, m) for p, m in mix]
    engine.run_until_idle()
    for req, (prompt, n) in zip(reqs, mix):
        assert req.state == "FINISHED"
        assert req.generated == _running_mean_tokens(params, prompt, n)
    engine.check_no_leaks()
    stats = engine.stats()
    assert stats["preemptions"] == 0
    assert stats["prefill_compiles"] == stats["decode_compiles"] == 1
    assert stats["kv"]["num_blocks"] == 0 and stats["kv"]["bytes"] == 0
    assert stats["state"] == {"slots": 2, "bytes": 2 * 4 * 8, "resets": 5,
                              "prefix_adoptions_refused": 5}
    with pytest.raises(ValueError, match="context is 40 positions"):
        engine.add_request(_prompt(30, 1), 11)


# -------------------------------------------------- nothing under models/

INFERENCE = os.path.join(os.path.dirname(ray_tpu.__file__), "inference")


@pytest.mark.parametrize("name", ["engine.py", "kv_cache.py", "adapters.py"])
def test_the_step_path_imports_no_model(name):
    with open(os.path.join(INFERENCE, name)) as f:
        tree = ast.parse(f.read())
    imported = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported += [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            imported += [f"{node.module}.{alias.name}"
                         for alias in node.names]
    assert imported, name
    assert [m for m in imported if m.startswith("ray_tpu.models")] == []


# ----------------------------------------------------- each program, once


@pytest.fixture(scope="module")
def tiny_llama():
    from ray_tpu.inference.api import preset_model

    return preset_model("tiny", 256)


def _direct_programs(model):
    """`prefill_fn` and `decode_fn` spelled against `Llama.decode_paged`
    with no `adapters` argument: the engine's bank-less programs as they
    were before it stopped naming the model."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama

    def prefill_fn(params, arenas, tokens, ids, bt, pos, wmask, last_idx,
                   slot):
        # Logits at the one position that is read (no banks, no index).
        logits, arenas = model.apply(params, ids, arenas, bt, pos, wmask,
                                     None, None, last_idx,
                                     method=Llama.decode_paged)
        nxt = jnp.argmax(logits, axis=-1)
        return tokens.at[slot].set(nxt.astype(jnp.int32)), arenas

    def decode_fn(params, arenas, tokens, bt, pos, wmask):
        logits, arenas = model.apply(params, tokens[:, None], arenas, bt,
                                     pos, wmask, method=Llama.decode_paged)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return jnp.where(wmask[:, 0], nxt, tokens), arenas

    return {"prefill": jax.jit(prefill_fn, donate_argnums=(1,)),
            "decode": jax.jit(decode_fn, donate_argnums=(1,))}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_without_adapters_the_one_spelling_lowers_to_the_old_text(
        tiny_llama, program):
    model, params = tiny_llama
    engine = InferenceEngine(
        EngineConfig(batch_slots=3, block_size=4, num_blocks=32,
                     max_blocks_per_seq=16, prefill_chunk=8),
        model=model, params=params)
    cfg = engine.config
    # (`Llama`'s fused step is a third program beside these, not in them)
    assert engine._decode_with_chunk_fn is not None
    b, s = {"decode": (cfg.batch_slots, 1),
            "prefill": (1, cfg.prefill_chunk)}[program]
    bt = np.zeros((b, cfg.max_blocks_per_seq), np.int32)
    pos = np.zeros(b, np.int32)
    wmask = np.zeros((b, s), bool)
    tail = {"decode": (bt, pos, wmask),
            "prefill": (np.zeros((b, s), np.int32), bt, pos, wmask,
                        np.zeros(1, np.int32), np.zeros(1, np.int32))}
    head = (engine._params, engine._arenas)
    mine = {"decode": engine._decode_fn, "prefill": engine._prefill_fn}
    got = mine[program].lower(*head, None, engine._tokens, *tail[program])
    want = _direct_programs(model)[program].lower(
        *head, engine._tokens, *tail[program])
    # `as_text()` prints no source locations: the rest must be equal.
    assert f"module @jit_{program}_fn" in got.as_text()
    assert got.as_text() == want.as_text()


# ------------------------------------ one policy, one way to set an option


@pytest.mark.parametrize("gone", ["scheduling", "model_size",
                                  "max_model_len"])
def test_a_removed_engine_option_is_an_unknown_field(gone):
    with pytest.raises(TypeError, match=gone):
        EngineConfig(**{gone: {"scheduling": "static", "model_size": "tiny",
                               "max_model_len": 256}[gone]})


def test_the_four_engine_options_are_set_on_the_config_alone():
    from ray_tpu.core.config import _FLAG_TABLE

    cfg = EngineConfig()
    defaults = {"prefix_cache_enabled": True, "spec_decode_draft_len": 0,
                "slo_default_class": "interactive",
                "slo_interactive_reserved_slots": 0}
    for name, value in defaults.items():
        assert getattr(cfg, name) == value and name not in _FLAG_TABLE
