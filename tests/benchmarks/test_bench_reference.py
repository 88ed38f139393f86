"""The plain references against the program's models at tiny sizes on the
CPU, on seeded random weights (on the chip the builders repeat this at
the published widths, outside the window)."""

import dataclasses

import pytest

import _paths  # noqa: F401


@pytest.fixture(scope="module")
def gpt2():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import GPT2, GPT2Config

    cfg = dataclasses.replace(GPT2Config.tiny(seq=64), use_flash=False,
                              dtype=jnp.float32)
    model = GPT2(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    # random biases and scales, so that a swapped one would show
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    leaves = [l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
              for l, k in zip(leaves, keys)]
    return cfg, model, jax.tree.unflatten(tree, leaves), ids


def test_gpt2_plain_matches_the_program_in_float32(gpt2):
    import jax.numpy as jnp

    from benchmarks.builders.gpt2_train import reference_weights
    from benchmarks.reference import gpt2_plain

    cfg, model, params, ids = gpt2
    ours = gpt2_plain.forward(reference_weights(params, cfg.n_layer), ids,
                              cfg.n_layer, cfg.n_head, eps=1e-6)
    theirs = model.apply(params, ids)
    # float32 on both sides, different op order: 1e-4 on logits of size ~1
    assert float(jnp.max(jnp.abs(ours - theirs))) < 1e-4


def test_gpt2_plain_loss_matches_the_programs_loss(gpt2):
    from ray_tpu.models.gpt2 import next_token_loss

    from benchmarks.builders.gpt2_train import reference_weights
    from benchmarks.reference import gpt2_plain

    cfg, model, params, ids = gpt2
    logits = gpt2_plain.forward(reference_weights(params, cfg.n_layer), ids,
                                cfg.n_layer, cfg.n_head, eps=1e-6)
    assert float(gpt2_plain.next_token_loss(logits, ids)) == pytest.approx(
        float(next_token_loss(model.apply(params, ids), ids)), abs=1e-5)


def test_the_published_epsilon_is_a_visible_departure(gpt2):
    """The program's LayerNorm epsilon (1e-6) is not the published 1e-5;
    the reference takes the published one by default and the difference
    stays far inside the builder's tolerance."""
    import jax.numpy as jnp

    from benchmarks.builders.gpt2_train import (LOSS_TOLERANCE,
                                                reference_weights)
    from benchmarks.reference import gpt2_plain

    cfg, _, params, ids = gpt2
    w = reference_weights(params, cfg.n_layer)
    a = gpt2_plain.next_token_loss(
        gpt2_plain.forward(w, ids, cfg.n_layer, cfg.n_head), ids)
    b = gpt2_plain.next_token_loss(
        gpt2_plain.forward(w, ids, cfg.n_layer, cfg.n_head, eps=1e-6), ids)
    assert 0 < abs(float(a - b)) < LOSS_TOLERANCE / 10


@pytest.fixture(scope="module")
def llama():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama, LlamaConfig

    cfg = dataclasses.replace(LlamaConfig.tiny(seq=64), dtype=jnp.float32,
                              rope_theta=1e6)
    model = Llama(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 48), 0,
                             cfg.vocab_size, dtype=jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(0), ids))
    return cfg, model, params, ids


def plain_llama(cfg, params, ids):
    from benchmarks.reference import llama_plain

    p = params["params"]
    top = {"embed": p["embed"], "final_norm": p["final_norm"]["scale"],
           "lm_head": p["lm_head"]["kernel"]}

    def layer(i):
        blk = p[f"layer_{i}"]
        return {"attn_norm": blk["attn_norm"]["scale"],
                "mlp_norm": blk["mlp_norm"]["scale"],
                **{k: blk[k]["kernel"] for k in (
                    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")}}

    return llama_plain.forward(top, layer, ids, cfg.n_layer, cfg.n_head,
                               cfg.n_kv_head, cfg.rope_theta, cfg.rms_eps)


def test_llama_plain_matches_the_training_forward(llama):
    import jax.numpy as jnp

    cfg, model, params, ids = llama
    assert float(jnp.max(jnp.abs(
        plain_llama(cfg, params, ids) - model.apply(params, ids)))) < 1e-4


def test_llama_plain_matches_prefill_then_paged_decode(llama):
    """The served path: chunked prefill and one-token decode steps through
    the paged cache must agree with the reference's full forward."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import Llama, make_paged_arena

    cfg, model, params, ids = llama
    ref = plain_llama(cfg, params, ids)[0]
    arenas = make_paged_arena(cfg, 9, 8)
    bt = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    got = []
    for start, width in [(0, 16), (16, 16)] + [(i, 1) for i in range(32, 48)]:
        chunk = ids[:, start:start + width]
        logits, arenas = model.apply(
            params, chunk, arenas, bt, jnp.asarray([start], jnp.int32),
            jnp.ones((1, width), bool), method=Llama.decode_paged)
        got.append(np.asarray(logits[0]))
    assert float(np.max(np.abs(np.concatenate(got) - np.asarray(ref)))) < 1e-4


def test_chosen_token_gaps():
    import jax.numpy as jnp

    from benchmarks.reference import llama_plain

    logits = jnp.zeros((6, 5)).at[2, 1].set(2.0).at[3, 4].set(1.0) \
        .at[3, 0].set(0.5)
    # prompt of 3, generated [1, 0]: rows 2 and 3 predict them
    gaps = llama_plain.chosen_token_gaps(logits, 3, [1, 0])
    assert [float(g) for g in gaps] == [0.0, 0.5]
