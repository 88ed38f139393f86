"""Llama model family: forward, sharded training, paged KV-cache decode,
serving.

Parity target: the second model family next to GPT-2, with the
decode-against-cache inference shape a Serve LLM deployment needs. The
cache is the paged one (`Llama.decode_paged` over `make_paged_arena`): the
model has no other, and every decode test's reference is the full causal
forward, which has no cache at all.
"""

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
