"""Llama model family: forward, sharded training, paged KV-cache decode,
serving.

Parity target: the second model family next to GPT-2, with the
decode-against-cache inference shape a Serve LLM deployment needs. The
cache is the paged one (`Llama.decode_paged` over `make_paged_arena`): the
model has no other, and every decode test's reference is the full causal
forward, which has no cache at all.
"""

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_compiles_once
from ray_tpu.models.llama import (
    Llama,
    LlamaConfig,
    flops_per_token,
    make_paged_arena,
)


def _paged(cfg, model, params, rows, blocks_per_row=8, block_size=4):
    """(step, arena, the jitted program): `step(tokens [rows, s], pos [rows],
    arena, live=None) -> (logits, arena)` is one jitted `decode_paged` over
    an arena in which row i holds blocks 1 + i * blocks_per_row onwards
    (block 0 is the trash block); `live` [rows, s] masks batch padding
    (default: all live)."""
    arena = make_paged_arena(cfg, 1 + rows * blocks_per_row, block_size)
    tables = 1 + jnp.arange(rows * blocks_per_row, dtype=jnp.int32).reshape(
        rows, blocks_per_row)
    paged = jax.jit(lambda p, tok, arena, pos, live: model.apply(
        p, tok, arena, tables, pos, live, method=Llama.decode_paged))

    def step(tokens, pos, arena, live=None):
        live = jnp.ones(tokens.shape, bool) if live is None else live
        return paged(params, tokens, arena, jnp.asarray(pos, jnp.int32),
                     live)

    return step, arena, paged


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(seq=32)
    model = Llama(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 10), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    return cfg, model, ids, params


def test_forward_shape_and_gqa(tiny_model):
    cfg, model, ids, params = tiny_model
    logits = model.apply(params, ids)
    assert logits.shape == (2, 10, cfg.vocab_size)
    assert cfg.n_head % cfg.n_kv_head == 0 and cfg.n_kv_head < cfg.n_head
    assert flops_per_token(cfg, 32) > 0


def test_train_step_reduces_loss(tiny_model):
    import optax

    from ray_tpu.models.gpt2 import make_train_step

    cfg, model, ids, params = tiny_model
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = make_train_step(model, opt, donate=False)
    batch = {"input_ids": ids, "labels": ids}
    _, _, first = step(params, opt_state, batch)
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, batch)
    assert float(loss) < float(first)


def test_decode_matches_full_forward(tiny_model):
    cfg, model, ids, params = tiny_model
    full = model.apply(params, ids)
    step, arena, _ = _paged(cfg, model, params, rows=2)
    # Prefill in one shot.
    pf, _ = step(ids, [0, 0], arena)
    np.testing.assert_allclose(np.asarray(pf, np.float32),
                               np.asarray(full, np.float32),
                               atol=0.06, rtol=0.05)
    # Token-by-token decode agrees position-wise.
    _, arena2, _ = _paged(cfg, model, params, rows=2)
    for t in range(ids.shape[1]):
        lg, arena2 = step(ids[:, t:t + 1], [t, t], arena2)
        np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                                   np.asarray(full[:, t], np.float32),
                                   atol=0.06, rtol=0.05)


def test_decode_per_row_positions(tiny_model):
    """Rows at different lengths decode against their own offsets."""
    cfg, model, ids, params = tiny_model
    full = model.apply(params, ids)
    step, arena, _ = _paged(cfg, model, params, rows=2)
    # Prefill row 0 with 4 tokens, row 1 with 7 (padded batch prefill:
    # the padding is masked and lands in the trash block).
    lengths = jnp.asarray([4, 7])
    _, arena = step(ids, [0, 0], arena,
                    jnp.arange(ids.shape[1])[None, :] < lengths[:, None])
    # Next-token decode at row-specific positions 4 and 7.
    lg, arena = step(jnp.stack([ids[0, 4:5], ids[1, 7:8]]), [4, 7], arena)
    np.testing.assert_allclose(np.asarray(lg[0, 0], np.float32),
                               np.asarray(full[0, 4], np.float32),
                               atol=0.06, rtol=0.05)
    np.testing.assert_allclose(np.asarray(lg[1, 0], np.float32),
                               np.asarray(full[1, 7], np.float32),
                               atol=0.06, rtol=0.05)


def test_sharded_init_on_mesh(tiny_model):
    from ray_tpu.models.gpt2 import init_sharded
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg, model, ids, _ = tiny_model
    mesh = build_mesh(MeshSpec({"dp": 2, "fsdp": 2, "tp": 2}))
    params = init_sharded(model, mesh, (2, 16))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n > 0


def test_llama_through_serve(ray_start_regular):
    """The one way to serve a language model, `LLMServer`, on the request
    shapes the static-batch sampler deployment took."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.inference import LLMServer

    handle = serve.run(LLMServer.options(num_replicas=1).bind(
        "tiny", 64, 8,
        engine_config={"batch_slots": 4, "block_size": 8, "num_blocks": 33,
                       "max_blocks_per_seq": 8, "prefill_chunk": 8}))
    try:
        out = ray_tpu.get(handle.remote(
            {"ids": [1, 2, 3], "max_new_tokens": 5}), timeout=180)
        assert out["ids"][:3] == [1, 2, 3] and len(out["ids"]) == 8
        outs = ray_tpu.get([handle.remote(
            {"ids": [5 + i], "max_new_tokens": 4}) for i in range(6)],
            timeout=180)
        for i, o in enumerate(outs):
            assert o["ids"][0] == 5 + i and len(o["ids"]) == 5
    finally:
        serve.shutdown()


@pytest.mark.parametrize("n_kv_head", [1, 2, 4])
def test_decode_parity_and_compile_once(n_kv_head):
    """Satellite: prefill + N single-token decode steps must match the
    full causal forward across GQA ratios (MQA=1, grouped=2, MHA=4), and
    the jitted decode step must compile exactly once across steps."""
    cfg = LlamaConfig(vocab_size=128, n_positions=64, n_embd=64,
                      n_layer=2, n_head=4, n_kv_head=n_kv_head,
                      intermediate=96, use_flash=False)
    model = Llama(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(1), ids)
    full = model.apply(params, ids)

    # Two jitted programs over the same block tables: a prefill of the
    # first 4 tokens in one shot, then a decode of one token at a time.
    prefill, arena, _ = _paged(cfg, model, params, rows=2, blocks_per_row=16)
    step, _, decode_step = _paged(cfg, model, params, rows=2,
                                  blocks_per_row=16)
    _, arena = prefill(ids[:, :4], [0, 0], arena)
    for t in range(4, ids.shape[1]):
        lg, arena = step(ids[:, t:t + 1], [t, t], arena)
        np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                                   np.asarray(full[:, t], np.float32),
                                   atol=0.06, rtol=0.05)
    # Shape-stable decode: one XLA program served every step.
    assert_compiles_once(decode_step)


def test_paged_decode_matches_dense(tiny_model):
    """Paged-arena decode over SCATTERED physical blocks (the logical
    order comes from the block table, not from the arena's layout) must
    agree with the full forward token for token."""
    cfg, model, ids, params = tiny_model
    full = model.apply(params, ids)
    arena = make_paged_arena(cfg, 16, 4)
    # Deliberately shuffled physical blocks: logical order comes from the
    # table, not arena layout.
    # (unreached tail entries are trash-padded with 0, as the engine's
    # block tables are)
    bt = jnp.asarray([[3, 1, 6, 2, 5, 4, 9, 0],
                      [7, 13, 8, 12, 11, 14, 15, 0]], jnp.int32)
    wm1 = jnp.ones((2, 1), bool)
    for t in range(ids.shape[1]):
        lg, arena = model.apply(params, ids[:, t:t + 1], arena, bt,
                                jnp.full((2,), t, jnp.int32), wm1,
                                method=Llama.decode_paged)
        np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                                   np.asarray(full[:, t], np.float32),
                                   atol=0.06, rtol=0.05)


# --------------------------------------------------------------------------- #
# a chunk aboard the decode step
# --------------------------------------------------------------------------- #


class _NoFusedStep(Llama):
    """The model with its fused step hidden (`PagedModel`'s "not offered"):
    an engine over it keeps the two programs."""

    paged_step_with_chunk = None


# (the chunk's first position, its live positions of 8)
CHUNKS_ABOARD = {
    # a prompt's first chunk, into blocks that hold whatever was there
    "first": (0, 8),
    # a later chunk of the same prompt, padded: it attends the first's rows
    "later": (8, 5),
}


@pytest.mark.parametrize("case", sorted(CHUNKS_ABOARD))
def test_the_fused_step_is_the_two_steps(case):
    """`paged_step_with_chunk` against `paged_step` called twice, chunk
    first as the engine's two programs run: the decode rows' logits, the
    chunk's at `last_idx` and both K/V arenas of every layer off the trash
    block, with a dead slot among the decode rows (the chunk's own), a
    padded chunk and 4 query heads a KV head, as the Mistral cells have."""
    # (float32: the two spellings differ by a product's rounding, which a
    # bf16 model's own 0.4% would hide a fault under)
    cfg = LlamaConfig(vocab_size=128, n_positions=64, n_embd=64, n_layer=2,
                      n_head=8, n_kv_head=2, intermediate=96,
                      use_flash=False, dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))
    slots, chunk, bsz, width = 3, 8, 4, 12
    start, n_live = CHUNKS_ABOARD[case]
    tables = 1 + jnp.arange((slots + 1) * width, dtype=jnp.int32).reshape(
        slots + 1, width)
    prompt = jax.random.randint(jax.random.PRNGKey(7), (1, 2 * chunk), 1,
                                cfg.vocab_size, dtype=jnp.int32)
    # what came before: rows of K/V, whatever they hold, and under a later
    # chunk the prompt's first
    arenas = model.paged_cache(1 + (slots + 1) * width, bsz)
    leaves, tree = jax.tree.flatten(arenas)
    keys = jax.random.split(jax.random.PRNGKey(8), len(leaves))
    arenas = jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])
    if start:
        _, arenas = model.paged_step(
            params, prompt[:, :start], arenas, tables[3:], jnp.array([0]),
            jnp.ones((1, start), bool))
    tokens = jnp.array([[5], [9], [0]], jnp.int32)
    pos, wmask = jnp.array([21, 37, 0]), jnp.array([[True], [True], [False]])
    chunk_live = jnp.arange(chunk)[None] < n_live
    chunk_ids = jnp.where(chunk_live, prompt[:, start:start + chunk], 0)
    chunk_pos, last = jnp.array([start]), jnp.array([n_live - 1])
    chunk_slot = jnp.array([2], jnp.int32)

    @jax.jit
    def twice(arenas):
        chunk_logits, arenas = model.paged_step(
            params, chunk_ids, arenas, tables[3:], chunk_pos, chunk_live,
            None, chunk_slot, last)
        logits, arenas = model.paged_step(params, tokens, arenas, tables[:3],
                                          pos, wmask)
        return logits[:, -1], chunk_logits, arenas

    @jax.jit
    def fused(arenas):
        return model.paged_step_with_chunk(
            params, tokens, chunk_ids, arenas, tables[:3], pos, wmask,
            tables[3:], chunk_pos, chunk_live, chunk_slot, last)

    want, got = twice(arenas), fused(arenas)
    assert got[0].shape == (slots, cfg.vocab_size)
    assert got[1].shape == (1, cfg.vocab_size)
    f32 = functools.partial(np.asarray, dtype=np.float32)
    np.testing.assert_allclose(f32(got[0][:2]), f32(want[0][:2]), atol=1e-5)
    np.testing.assert_allclose(f32(got[1]), f32(want[1]), atol=1e-5)
    assert float(jnp.abs(want[0][:2]).max()) > 1e-3
    assert float(jnp.abs(want[1]).max()) > 1e-3
    # The arenas everywhere but the trash block, where both write their
    # dead rows; and the live rows' places hold what this step wrote.
    chunk_rows = tables[3, start // bsz] * bsz + start % bsz \
        + np.arange(n_live)
    decode_rows = [int(tables[i, int(pos[i]) // bsz]) * bsz
                   + int(pos[i]) % bsz for i in range(2)]
    for was, wanted, made in zip(arenas, want[2], got[2]):
        for w, a, b in zip(was, wanted, made):
            np.testing.assert_allclose(f32(b[1:]), f32(a[1:]), atol=1e-5)
            w, b = (f32(t).reshape(-1, *t.shape[2:]) for t in (w, b))
            for row in (*chunk_rows, *decode_rows):
                assert np.abs(w[row] - b[row]).max() > 1e-2, row


def test_a_chunk_aboard_changes_nothing_that_is_served():
    """The same engine over the model and over the model with its fused
    step hidden: the same greedy tokens for a mix whose chunks land while
    others decode (prompts of one to three chunks; an early leaver whose
    slot the last request is admitted into). An engine that holds adapter
    banks or speculates keeps the two programs, and a tp mesh serves the
    same tokens through the fused one."""
    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    # (float32: a chunk aboard is another shape of every product, and in
    # bf16 that rounding moves an argmax in ~200; the tokens here must be
    # EQUAL whatever the machine's vector width)
    cfg = replace(LlamaConfig.tiny(seq=128), dtype=jnp.float32)
    params = Llama(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))

    def prompt(n, seed):
        return list(map(int, np.random.default_rng(seed).integers(
            1, cfg.vocab_size, n)))

    mix = [(prompt(5, 1), 12), (prompt(12, 2), 2), (prompt(20, 3), 9),
           (prompt(7, 4), 6)]

    def serve(cls, mesh=None, **kwargs):
        engine = InferenceEngine(
            EngineConfig(batch_slots=3, block_size=4, num_blocks=40,
                         max_blocks_per_seq=10, prefill_chunk=8,
                         prefix_cache_enabled=False, **kwargs),
            model=cls(cfg), params=params, mesh=mesh)
        reqs = [engine.add_request(p, n) for p, n in mix]
        engine.run_until_idle()
        engine.check_no_leaks()
        assert all(r.state == "FINISHED" for r in reqs), \
            [r.error for r in reqs]
        return engine, [r.generated for r in reqs]

    fused, got = serve(Llama)
    plain, want = serve(_NoFusedStep)
    assert got == want
    assert [len(g) for g in got] == [n for _, n in mix]
    steps, plain_steps = fused.step_stats(), plain.step_stats()
    # 1 + 2 + 3 + 1 chunks: all but the first, which found no row
    # decoding, rode (the fourth request's into the slot the second left)
    assert (steps["prefill"], steps["chunks_aboard"]) == (1, 6)
    assert (plain_steps["prefill"], plain_steps["chunks_aboard"]) == (7, 0)
    assert steps["decode_rows"] == plain_steps["decode_rows"] \
        == sum(n - 1 for _, n in mix)
    assert_compiles_once(fused.stats(), "prefill_compiles", "decode_compiles",
                         "decode_with_chunk_compiles")
    assert_compiles_once(plain.stats(), "prefill_compiles", "decode_compiles")
    assert plain.stats()["decode_with_chunk_compiles"] == 0
    # The fused step takes neither adapter banks nor a draft: an engine
    # with either keeps the two programs, and serves the same tokens.
    for kwargs in (dict(max_adapters=2, lora_rank=8),
                   dict(spec_decode_draft_len=2)):
        engine, tokens = serve(Llama, **kwargs)
        assert tokens == want, kwargs
        assert engine._decode_with_chunk_fn is None
        assert engine.step_stats()["chunks_aboard"] == 0
        assert engine.stats()["decode_with_chunk_compiles"] == 0
    # The kv heads (and every product) split over a tp mesh: the fused
    # program is traced and run under it like the other two.
    mesh = build_mesh(MeshSpec({"tp": 2}), devices=jax.devices()[:2])
    sharded, tokens = serve(Llama, mesh=mesh)
    assert tokens == want
    steps = sharded.step_stats()
    assert (steps["prefill"], steps["chunks_aboard"]) == (1, 6)
    assert_compiles_once(sharded.stats(), "decode_with_chunk_compiles")
