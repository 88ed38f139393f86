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
    cache = model.paged_cache(1 + width, bs)
    tables = jnp.arange(1, width + 1, dtype=jnp.int32)[None]
    chunk = np.zeros((1, 24), np.int32)
    chunk[0, :whole] = prompt[:whole]
    _, cache = model.paged_step(
        params, jnp.asarray(chunk), cache, tables, jnp.zeros((1,), jnp.int32),
        jnp.asarray(np.arange(24)[None] < whole), last_idx=jnp.zeros(
            (1,), jnp.int32))
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
    # synchronous: nothing is dispatched ahead of a read (finding (i))
    assert engine.stats()["steps"]["decode_ahead"] == 0
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
