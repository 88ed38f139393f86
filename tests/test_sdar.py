"""`models/sdar.py` (block diffusion over a paged cache, a softmax top-k
expert layer) against `benchmarks/reference/sdar_plain.py` (a dense mask
over the whole sequence, a loop over the experts, no cache), teacher-forced:
the same buffers go through both and logits, confidences, routed experts
and the selection rule are compared; and the mask vis(p) through
`paged_attention` on both of the kernel's tiles in the interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sdar_plain as plain
from ray_tpu.inference.engine import EngineConfig, InferenceEngine
from ray_tpu.models import sdar

PUBLISHED = ("num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
             "num_experts", "num_experts_per_tok", "norm_topk_prob",
             "block_length", "denoising_steps", "remasking_strategy",
             "confidence_threshold", "mask_token_id")


def as_dict(cfg):
    return {k: getattr(cfg, k) for k in PUBLISHED}


@pytest.fixture(scope="module")
def tiny():
    cfg = sdar.SDARConfig.tiny()
    model = sdar.SDAR(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, sdar.published_weights(cfg, params)


def _ids(n, seed=0, vocab=95):
    return [int(t) for t in np.random.default_rng(seed).integers(0, vocab, n)]


def test_forward_is_the_reference_under_the_block_mask(tiny):
    cfg, model, params, (top, layer) = tiny
    ids = jnp.asarray([_ids(24, 1), _ids(24, 2)], jnp.int32)
    want, extra = plain.forward(top, layer, ids, as_dict(cfg), keep=(0, 1),
                                taps=True)
    got = model.forward(params, ids)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # causal token by token it is another function
    assert not bool(jnp.all(plain.block_mask(8, 4) == jnp.tril(
        jnp.ones((8, 8), bool))))
    assert extra["experts"].shape == (2, 24, cfg.num_experts_per_tok)


def _block_step_cache(model, params, prompt, width=6, bs=8):
    """A cache with `prompt` (whole blocks) prefilled, and its one table."""
    cache = model.paged_cache(1 + width, bs)
    tables = jnp.arange(1, width + 1, dtype=jnp.int32)[None]
    chunk = np.zeros((1, 24), np.int32)
    chunk[0, :len(prompt)] = prompt
    _, cache = model.paged_step(
        params, jnp.asarray(chunk), cache, tables, jnp.zeros((1,), jnp.int32),
        jnp.asarray(np.arange(24)[None] < len(prompt)), last_idx=jnp.zeros(
            (1,), jnp.int32))
    return cache, tables


def test_block_steps_through_the_cache_are_the_reference(tiny):
    """A prefill of whole blocks, then a block's passes as the engine runs
    them: at every pass the logits at the block's positions, the
    confidences, the first layer's routing record and what `block_select`
    commits agree with a full forward over the sequence so far."""
    cfg, model, params, (top, layer) = tiny
    d, length, mask_id = as_dict(cfg), cfg.block_length, cfg.mask_token_id
    prompt = _ids(18, 3)
    whole = len(prompt) // length * length
    bs, width = 8, 6
    cache, tables = _block_step_cache(model, params, prompt[:whole], width,
                                      bs)
    select = model.decode_block.select
    seq, buf = list(prompt[:whole]), prompt[whole:] + [-1] * (
        length - len(prompt) + whole)
    for t in range(length + 1):
        ids = [mask_id if v < 0 else v for v in buf]
        logits, cache = model.paged_step(
            params, jnp.asarray([ids], jnp.int32), cache, tables,
            jnp.asarray([len(seq)], jnp.int32), jnp.ones((1, length), bool))
        at = [list(range(len(seq), len(seq) + length))]
        want, extra = plain.forward(
            top, layer, jnp.asarray([seq + ids], jnp.int32), d, at=at,
            taps=True)
        np.testing.assert_allclose(logits, want, atol=2e-6)
        record = cache["routing"].reshape(2 * cfg.num_experts_per_tok,
                                          1 + width, bs)
        k = cfg.num_experts_per_tok
        for p in range(length):
            where = len(seq) + p
            col = record[:, 1 + where // bs, where % bs]
            order = np.argsort(np.asarray(col[:k]))
            np.testing.assert_array_equal(
                np.asarray(col[:k])[order], extra["experts"][0, where])
            np.testing.assert_allclose(
                np.asarray(col[k:])[order], extra["gates"][0, where],
                atol=1e-6)
        masked = [v < 0 for v in buf]
        if not any(masked):
            break
        x0_ref, conf = plain.confidences(want[0])
        x0, chosen = select(logits, jnp.asarray([masked]),
                            jnp.asarray([1], jnp.int32))
        picked = plain.select(d, conf, masked, t)
        assert np.flatnonzero(np.asarray(chosen[0])).tolist() == picked
        for p in picked:
            assert int(x0[0, p]) == int(x0_ref[p])
            buf[p] = int(x0[0, p])
    assert t == buf.count(-1) + length - (len(prompt) - whole)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-6),
                                        (jnp.bfloat16, 3e-2)])
def test_a_commit_aboard_is_the_commit_step_then_the_denoise_step(dtype,
                                                                  atol):
    """One step over TWO blocks of a row, the first final and the second
    all masks (`read_from`: the engine's block program), leaves the keys,
    values and routing record, and gives the logits at the second block,
    of the commit step followed by the denoise step at one block each:
    every layer scatters before it attends. Both are block steps to the
    counters, a prefill chunk is told by its `last_idx`."""
    cfg = sdar.SDARConfig.tiny(dtype=dtype)
    model = sdar.SDAR(cfg)
    params = model.init(jax.random.PRNGKey(0))
    length, mask_id = cfg.block_length, cfg.mask_token_id
    prompt, final = _ids(16, 4), _ids(length, 5)
    at = jnp.asarray([len(prompt)], jnp.int32)
    masks = [mask_id] * length
    # apart: the commit pass of the block, then the next block's denoise
    cache, tables = _block_step_cache(model, params, prompt)
    one = jnp.ones((1, length), bool)
    _, cache = model.paged_step(params, jnp.asarray([final], jnp.int32),
                                cache, tables, at, one)
    want, apart = model.paged_step(params, jnp.asarray([masks], jnp.int32),
                                   cache, tables, at + length, one)
    # aboard: both in one step (a second row idle, a third a plain pass)
    cache, tables = _block_step_cache(model, params, prompt)
    got, aboard = model.paged_step(
        params, jnp.asarray([final + masks], jnp.int32), cache, tables, at,
        jnp.ones((1, 2 * length), bool),
        read_from=jnp.asarray([length], jnp.int32))
    assert got.shape == want.shape == (1, length, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)
    for (k_a, v_a), (k_b, v_b) in zip(aboard["kv"], apart["kv"]):
        np.testing.assert_allclose(np.asarray(k_a[1:], np.float32),
                                   np.asarray(k_b[1:], np.float32), atol=atol)
        np.testing.assert_allclose(np.asarray(v_a[1:], np.float32),
                                   np.asarray(v_b[1:], np.float32), atol=atol)
    # the record of every cached token (block 0 is the trash block)
    bs, k = aboard["kv"][0][0].shape[1], cfg.num_experts_per_tok
    record_a, record_b = (np.asarray(c["routing"])[:, bs:]
                          for c in (aboard, apart))
    if dtype == jnp.float32:
        np.testing.assert_array_equal(record_a[:k], record_b[:k])
    np.testing.assert_allclose(record_a[k:], record_b[k:], atol=atol)
    # [decode, prefill]: one block step for two, the chunk apart
    assert np.asarray(aboard["moe"]["steps"]).tolist() == [1, 1]
    assert np.asarray(apart["moe"]["steps"]).tolist() == [2, 1]
    assert int(np.sum(aboard["moe"]["assigned"][0])) == int(np.sum(
        apart["moe"]["assigned"][0])) == 2 * length * k * 2
    # a row with its second half dead reads the first (a plain pass)
    cache, tables = _block_step_cache(model, params, prompt)
    live = jnp.asarray(np.arange(2 * length)[None] < length)
    plain_logits, half = model.paged_step(
        params, jnp.asarray([final + masks], jnp.int32), cache, tables, at,
        live, read_from=jnp.zeros((1,), jnp.int32))
    cache, tables = _block_step_cache(model, params, prompt)
    alone, whole = model.paged_step(
        params, jnp.asarray([final], jnp.int32), cache, tables, at, one)
    np.testing.assert_allclose(np.asarray(plain_logits, np.float32),
                               np.asarray(alone, np.float32), atol=atol)
    np.testing.assert_allclose(
        np.asarray(half["kv"][-1][0][1:], np.float32),
        np.asarray(whole["kv"][-1][0][1:], np.float32), atol=atol)


def test_the_expert_layers_tile_at_the_block_steps_shape():
    """At the served widths: 32 rows of two blocks are 256 x 8 = 16 x 128
    assignments, where `serve_tile`'s rule by shape flips to a chunk's
    128-row tiles; the block step says what its schedule keeps live (160)
    and gets 16. A chunk and a one-block step keep what they had."""
    from ray_tpu.ops import held_experts

    cfg = sdar.SDARConfig(num_hidden_layers=1)
    model = sdar.SDAR(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.paged_cache(65, 16))
    i32 = jnp.int32

    def tile_of(b, s, **kwargs):
        held_experts.reset_held_experts_status()
        jax.eval_shape(
            lambda p, c: model.paged_step(
                p, jnp.zeros((b, s), i32), c, jnp.zeros((b, 4), i32),
                jnp.zeros((b,), i32), jnp.ones((b, s), bool), **kwargs),
            params, cache)
        (call,) = held_experts.held_experts_status()
        held_experts.reset_held_experts_status()
        return call["tokens"], call["top_k"], call["tile"]

    assert tile_of(32, 8, read_from=jnp.zeros((32,), i32)) == (256, 8, 16)
    assert tile_of(32, 4) == (128, 8, 16)
    assert tile_of(1, 256, last_idx=jnp.zeros((1,), i32)) == (256, 8, 128)
    assert held_experts.serve_tile(256, 8, 128) == 128
    assert held_experts.serve_tile(160, 8, 128) == 16


def test_block_select_rules():
    z = jnp.log(jnp.asarray([[[0.5, 0.3, 0.2], [0.9, 0.05, 0.05],
                              [0.4, 0.35, 0.25], [0.95, 0.03, 0.02]]]))
    masked = jnp.asarray([[True, True, True, False]])
    one = jnp.asarray([1], jnp.int32)
    x0, chosen = sdar.block_select(z, masked, one)
    assert x0.tolist() == [[0, 0, 0, 0]]
    assert chosen.tolist() == [[False, True, False, False]]
    # never more than are masked, never an unmasked position
    _, chosen = sdar.block_select(z, masked, jnp.asarray([4], jnp.int32))
    assert chosen.tolist() == [[True, True, True, False]]
    # a commit pass (n = 0) commits nothing, under either rule
    for threshold in (None, 0.45):
        _, chosen = sdar.block_select(z, masked, one * 0, threshold)
        assert not bool(jnp.any(chosen))
    # dynamic: every masked position over the threshold, where at least n
    _, chosen = sdar.block_select(z, masked, one, 0.45)
    assert chosen.tolist() == [[True, True, False, False]]
    _, chosen = sdar.block_select(z, masked, one, 0.92)
    assert chosen.tolist() == [[False, True, False, False]]
    # ties go to the earlier position
    _, chosen = sdar.block_select(jnp.zeros((1, 4, 3)), masked, one)
    assert chosen.tolist() == [[True, False, False, False]]


def _engine(model, params, **kwargs):
    cfg = dict(batch_slots=3, block_size=8, num_blocks=40,
               max_blocks_per_seq=8, prefill_chunk=16)
    cfg.update(kwargs)
    return InferenceEngine(EngineConfig(**cfg), model=model, params=params)


@pytest.mark.parametrize("tail", [0, 1, 2, 3])
def test_generation_is_upstreams_loop_for_every_prompt_tail(tiny, tail):
    cfg, model, params, (top, layer) = tiny
    engine = _engine(model, params)
    prompt = _ids(12 + tail, 10 + tail)
    req = engine.add_request(prompt, 7, record_passes=True)
    engine.run_until_idle()
    want, passes = plain.block_diffusion_generate(top, layer, prompt,
                                                  as_dict(cfg), 7)
    assert req.generated == want
    assert [(p["start"], p["entered"]) for p in req.pass_log] == \
        [(p["start"], p["entered"]) for p in passes]
    given = engine.stats()["diffusion"]["given_tokens"]
    assert given == tail
    engine.check_no_leaks()


def test_dynamic_rule_commits_several_positions_a_pass():
    """On a head scaled until confidences pass the threshold a denoise
    pass commits more than one position, and the tokens are still the
    reference loop's."""
    cfg = sdar.SDARConfig.tiny(remasking_strategy="low_confidence_dynamic",
                               confidence_threshold=0.9)
    model = sdar.SDAR(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params = {**params, "lm_head": params["lm_head"] * 400.0}
    top, layer = sdar.published_weights(cfg, params)
    engine = _engine(model, params)
    prompts = [_ids(n, 20 + n) for n in (9, 14, 16)]
    reqs = [engine.add_request(p, 10) for p in prompts]
    engine.run_until_idle()
    book = engine.stats()["diffusion"]
    assert book["rule"] == "dynamic"
    assert sum(book["committed_hist"][2:]) > 0
    assert book["denoise_passes"] < book["tokens_committed"]
    # synchronous: nothing is dispatched ahead of a read (finding (i)),
    # and a block the host has read as done commits with the next block's
    # first denoise pass aboard all the same
    assert engine.stats()["steps"]["decode_ahead"] == 0
    assert book["commits_aboard"] == book["commit_passes"] - len(reqs) > 0
    assert engine.stats()["steps"]["decode"] < book["denoise_passes"] \
        + book["commit_passes"]
    for req, prompt in zip(reqs, prompts):
        want, _ = plain.block_diffusion_generate(top, layer, prompt,
                                                 as_dict(cfg), 10)
        assert req.generated == want
    engine.check_no_leaks()


def test_config_from_published_and_counts():
    published = {"attention_bias": False, "decoder_sparse_step": 1,
                 "head_dim": 128, "hidden_size": 2048,
                 "max_position_embeddings": 32768, "mlp_only_layers": [],
                 "moe_intermediate_size": 768, "norm_topk_prob": True,
                 "num_attention_heads": 32, "num_experts": 128,
                 "num_experts_per_tok": 8, "num_hidden_layers": 48,
                 "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
                 "rope_scaling": None, "rope_theta": 1000000,
                 "vocab_size": 151936, "intermediate_size": 6144}
    cfg = sdar.SDARConfig.from_published(published)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.block_length,
            cfg.mask_token_id) == (128, 8, 4, 151669)
    assert cfg.schedule == (1, 1, 1, 1)
    assert sdar.SDARConfig.tiny(block_length=8, denoising_steps=3,
                                mask_token_id=5).schedule == (3, 3, 2)
    assert cfg.kv_bytes_per_token == 48 * 2 * 4 * 128 * 2
    with pytest.raises(ValueError):
        sdar.SDARConfig.from_published({**published, "mlp_only_layers": [0]})
    with pytest.raises(ValueError):
        sdar.SDARConfig.tiny(remasking_strategy="sequential")
    shapes = jax.eval_shape(sdar.SDAR(sdar.SDARConfig.from_published(
        {**published, "num_hidden_layers": 8})).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert n == 8 * 623_120_640 + 622_329_856 + 2_048


# --------------------------------------------------------------------------- #
# the mask through the paged kernel, both tiles
# --------------------------------------------------------------------------- #


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("s,groups,start,tile", [
    (4, 8, 40, "few rows"),         # a block step: 32 query rows a KV head
    (32, 8, 8, "many rows"),        # a chunk: 256 rows a KV head
])
def test_block_mask_through_the_paged_kernel(interpret, s, groups, start,
                                             tile):
    from ray_tpu.ops import paged_attention as pa

    hd, kvh, bs, width, length = 128, 2, 16, 4, 4
    rng = np.random.default_rng(s)
    q = jnp.asarray(rng.normal(size=(2, s, kvh * groups, hd)), jnp.float32)
    k_arena, v_arena = (jnp.asarray(rng.normal(
        size=(1 + 2 * width, bs, kvh, hd)), jnp.float32) for _ in range(2))
    tables = jnp.arange(1, 1 + 2 * width, dtype=jnp.int32).reshape(2, width)
    positions = jnp.asarray([start, start - length])[:, None] + jnp.arange(s)
    sees = (positions // length + 1) * length - 1
    before = pa.paged_calls("tile")
    got = pa.paged_attention(q, k_arena, v_arena, tables, sees)
    took = {key[1] for key, n in pa.paged_calls("tile").items()
            if n > before.get(key, 0)}
    assert len(took) == 1 and took.pop().startswith(tile)
    # the dense definition: query p of row i over keys j <= vis(p)
    for i in range(2):
        keys = k_arena[tables[i]].reshape(width * bs, kvh, hd)
        vals = v_arena[tables[i]].reshape(width * bs, kvh, hd)
        kr, vr = (jnp.repeat(t, groups, axis=1) for t in (keys, vals))
        scores = jnp.einsum("qhd,khd->hqk", q[i], kr) / np.sqrt(hd)
        mask = jnp.arange(width * bs)[None, :] <= sees[i][:, None]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
        want = jnp.einsum("hqk,khd->qhd", probs, vr)
        np.testing.assert_allclose(got[i], want, atol=2e-5)
    # a query sees its block's LATER positions: not the causal answer
    causal = pa.paged_attention(q, k_arena, v_arena, tables, positions)
    assert float(jnp.max(jnp.abs(causal - got))) > 1e-3


# --------------------------------------------------------------------------- #
# the engine's block program compiled for a described v5e at the cell's sizes
# --------------------------------------------------------------------------- #


def test_the_block_program_compiles_for_one_v5e_chip_and_fits(
        one_chip, monkeypatch):
    """`engine.block_decode_fn`, the program `_build_block_programs` jits,
    at the tenth cell's sizes (32 slots, two blocks of four positions a
    row, depth 8): the kernels and not the interpreter, the paged call in
    the few-rows tile at 64 query rows a KV head, the expert layers in
    16-row tiles over 256 rows, the head on one block a row, the cache
    updated in place, arguments and temporaries what the one-block program
    took (11.5 GB)."""
    from ray_tpu.inference.engine import block_decode_fn
    from ray_tpu.ops import attention, grouped_matmul, held_experts

    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    monkeypatch.setattr(grouped_matmul, "_platform", lambda: "tpu")
    with attention._CALLS_LOCK:
        before = dict(attention._CALLS)
        attention._CALLS.clear()
    held_experts.reset_held_experts_status()
    model = sdar.SDAR(sdar.SDARConfig(num_hidden_layers=8))
    slots, width, length, layers = 32, 32, 4, 8

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def shaped(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    params = shaped(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = shaped(jax.eval_shape(lambda: model.paged_cache(1025, 16)))
    i32, flag = jnp.int32, jnp.bool_
    args = (spec((slots, length), i32), spec((slots, width), i32),
            spec((slots,), i32), spec((slots, 2 * length), flag),
            spec((slots,), flag), spec((slots, length), i32),
            spec((slots,), i32))
    fn = block_decode_fn(model.paged_step, model.decode_block)
    try:
        lowered = jax.jit(
            lambda p, c, *rest: fn(p, c, None, *rest),
            donate_argnums=(1,)).lower(params, cache, *args)
        # the head and the selection on one block a row
        assert f"tensor<{slots}x{length}x151936xf32>" in lowered.as_text()
        assert f"x{2 * length}x151936x" not in lowered.as_text()
        compiled = lowered.compile()
        assert compiled.as_text().count("tpu_custom_call") == 3 * layers
        mem = compiled.memory_analysis()
        nbytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(cache))
        assert mem.alias_size_in_bytes >= nbytes - 8
        assert mem.temp_size_in_bytes < 0.2e9, mem
        assert 11.4e9 < mem.argument_size_in_bytes \
            + mem.temp_size_in_bytes < 11.6e9
        calls = {(tuple(c["shape"]), c["path"], c["tile"][:9]): c["calls"]
                 for c in attention.pallas_status()}
        assert calls == {((32, 8, 32, 128), "pallas", "few rows:"): layers}
        held = {(h["tokens"], h["top_k"], h["tile"], h["path"]): h["calls"]
                for h in held_experts.held_experts_status()}
        assert held == {(256, 8, 16, "pallas"): layers}
    finally:
        with attention._CALLS_LOCK:
            attention._CALLS.clear()
            attention._CALLS.update(before)
        held_experts.reset_held_experts_status()
