"""`models/dots3.py` against `benchmarks/reference/dots3_plain.py` on seeded
weights at tiny sizes: prefill in chunks then decode through the caches
agree with the reference's full forward pass on logits, index scores,
selections (free and given), routing and cached rows, at contexts below the
window, between window and `index_topk`, and above both; the two
geometries; every `assumed` item and every control's fault moves a logit;
the eight chips' shares of an expert layer add up to the uncut layer."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import dots3_plain as plain  # noqa: E402
from ray_tpu.models import dots3 as d3  # noqa: E402
from ray_tpu.models.dots3 import (Dots3, Dots3Config,  # noqa: E402
                                  published_weights)

T, BS = 48, 8                  # window 9, index_topk 16: 48 passes both


def published(cfg: Dots3Config) -> dict:
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
           if f.name != "dtype"}
    out["layer_types"] = list(cfg.layer_types)
    out["deployment"] = {"experts_routed": cfg.experts_routed,
                         "first_expert_held": cfg.first_expert_held}
    return out


@pytest.fixture(scope="module")
def tiny():
    cfg = Dots3Config.tiny()
    model = Dots3(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # norms and biases that are not the identity, so that each matters
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    for lp in params["layers"]:
        for name in ("q_norm", "kv_norm", "input_norm", "mlp_norm",
                     "idx_k_norm"):
            if name in lp:
                lp[name] = 1.0 + 0.2 * jax.random.normal(next(keys),
                                                         lp[name].shape)
        if "idx_k_bias" in lp:
            lp["idx_k_bias"] = 0.3 * jax.random.normal(
                next(keys), lp["idx_k_bias"].shape)
        for name in ("idx_wq", "idx_wk", "idx_w", "w_gate_attn"):
            if name in lp:       # so that the indexer discriminates
                lp[name] = lp[name] * 20.0
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, T), 1, 96)
    top, layer = published_weights(cfg, params)
    logits, taps = plain.forward(top, layer, ids, published(cfg),
                                 with_taps=True)
    return {"cfg": cfg, "model": model, "params": params, "ids": ids,
            "pub": published(cfg), "weights": (top, layer),
            "logits": logits, "taps": taps}


def served(model, params, ids, chunks=(16, 16), block_size=BS):
    """Prefill in `chunks`, then decode the rest a token a step: (logits
    [1, t, vocab], the cache, its tables)."""
    t = ids.shape[1]
    per = -(-t // block_size)
    cache = model.paged_cache(1 + per, block_size, None, 1,
                              kinds={"window": 1 + per})
    table = 1 + jnp.arange(per, dtype=jnp.int32)[None, :]
    tables = {"full": table, "window": table}
    step = jax.jit(model.paged_step)
    outs, at = [], 0
    for n in list(chunks) + [1] * (t - sum(chunks)):
        got, cache = step(params, ids[:, at:at + n], cache, tables,
                          jnp.asarray([at], jnp.int32), jnp.ones((1, n), bool))
        outs.append(got)
        at += n
    return jnp.concatenate(outs, axis=1), cache, tables


@pytest.mark.parametrize("interpret", ["0", "1"])
def test_chunks_then_decode_agree_with_the_plain_reference(tiny, monkeypatch,
                                                           interpret):
    """Logits at every position (below the window, between window and
    `index_topk`, above both), through the kernels (the interpreter) and
    through their definitions."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", interpret)
    got, cache, tables = served(tiny["model"], tiny["params"], tiny["ids"])
    for lo, hi in ((0, 9), (9, 16), (16, T)):
        np.testing.assert_allclose(got[0, lo:hi], tiny["logits"][0, lo:hi],
                                   atol=3e-6, err_msg=f"positions {lo}-{hi}")
    # the cached rows of both geometries and the index keys
    cfg, taps = tiny["cfg"], tiny["taps"]
    full_at = 0
    for i, kind in enumerate(cfg.kinds):
        g = cfg.geometry(kind)
        rows = cache["latent"][i][tables["full"][0]].reshape(-1, g.page_width)
        np.testing.assert_allclose(rows[:T, :g.row], taps["rows"][i],
                                   atol=2e-6)
        assert not np.asarray(rows[:, g.row:]).any()
        if kind == d3.FULL:
            keys = cache["index"][full_at][tables["full"][0]].reshape(
                -1, cfg.index_head_dim)
            np.testing.assert_allclose(keys[:T], taps["index_keys"][i],
                                       atol=2e-6)
            full_at += 1
    assert (g.row, g.page_width) == (288, 384)       # the second geometry
    # the routing record against the reference's router
    k = cfg.num_experts_per_tok
    record = cache["routing"].reshape(2 * k, -1, BS)[:, tables["full"][0]]
    record = np.asarray(record.reshape(2 * k, -1).T[:T])
    order = np.argsort(record[:, :k], axis=-1)
    np.testing.assert_array_equal(
        np.take_along_axis(record[:, :k], order, -1), taps["experts"])
    np.testing.assert_allclose(np.take_along_axis(record[:, k:], order, -1),
                               taps["gates"], rtol=1e-5)


def test_a_tapped_step_reads_scores_and_selections_that_are_the_references(
        tiny):
    """`Dots3.paged_step_tapped` replayed over the cache the steps left:
    the step's own logits, the rows it wrote written again as they were,
    and index scores and selections that are the reference's; the
    reference GIVEN those selections returns its own logits again."""
    model, params, ids = tiny["model"], tiny["params"], tiny["ids"]
    got, cache, tables = served(model, params, ids)
    at = [5, 12, 20, 40]
    for p in at:
        logits, again, chose, routed = model.paged_step_tapped(
            params, ids[:, p:p + 1], cache, tables,
            jnp.asarray([p], jnp.int32), jnp.ones((1, 1), bool))
        np.testing.assert_allclose(logits[0, 0], got[0, p], atol=2e-6)
        for was, now in zip(cache["latent"] + cache["index"],
                            again["latent"] + again["index"]):
            np.testing.assert_allclose(now, was, atol=2e-6)
        # the first expert layer's input and what its router made of it
        want_idx, want_gates = plain.route(
            tiny["pub"], {k: jnp.asarray(v, jnp.float32)
                          for k, v in tiny["weights"][1](1).items()
                          if k.startswith("mlp.gate")}, routed[0])
        k = tiny["cfg"].num_experts_per_tok
        order = np.argsort(np.asarray(routed[1][:k, 0]))
        np.testing.assert_array_equal(np.asarray(routed[1][:k, 0])[order],
                                      np.asarray(want_idx[0]))
        np.testing.assert_allclose(np.asarray(routed[1][k:, 0])[order],
                                   want_gates[0], rtol=1e-5)
        for layer, (scores, chosen, count) in zip((0, 1), chose):
            want = tiny["taps"]["scores"][layer][p]
            seen = np.isfinite(np.asarray(want))
            np.testing.assert_allclose(np.asarray(scores[0, 0, :T])[seen],
                                       np.asarray(want)[seen], atol=1e-5)
            picked = np.zeros(T, bool)
            picked[np.asarray(chosen[0, 0, :int(count[0, 0])])] = True
            np.testing.assert_array_equal(
                picked, np.asarray(tiny["taps"]["chosen"][layer][p]))
            assert int(count[0, 0]) == min(p + 1, tiny["cfg"].index_topk)
    # GIVEN another selection, the reference's logits move
    rows = jnp.asarray(at, jnp.int32)
    given = {0: (rows, jnp.asarray(tiny["taps"]["chosen"][0])[rows])}
    top, layer = tiny["weights"]
    same = plain.forward(top, layer, ids, tiny["pub"], given=given)
    np.testing.assert_allclose(same, tiny["logits"], atol=1e-6)
    recent = jnp.asarray(np.tril(np.ones((T, T), bool))
                         & ~np.tril(np.ones((T, T), bool), -16))[rows]
    moved = plain.forward(top, layer, ids, tiny["pub"],
                          given={0: (rows, recent)})
    assert float(jnp.max(jnp.abs(moved - tiny["logits"])[0, 20:])) > 1e-4


def test_a_prefix_already_computed_is_not_computed_again(tiny):
    """The reference's blocks of rows across calls (`prefix`)."""
    top, layer = tiny["weights"]
    _, taps = plain.forward(top, layer, tiny["ids"][:, :32], tiny["pub"],
                            with_taps=True, keep_inputs=True)
    tail = plain.forward(top, layer, tiny["ids"], tiny["pub"],
                         prefix=taps["inputs"])
    np.testing.assert_allclose(tail, tiny["logits"][:, 32:], atol=1e-6)


def _faults():
    sys.path.insert(0, ROOT)
    from benchmarks import dots3_controls

    return dots3_controls


FAULTS = ["recent_2048", "top_2047", "no_key_layernorm", "index_cache_8bit", "window_512", "window_514",
          "rope_bases_swapped", "no_gate", "no_rescale", "bf16_router",
          "top7_of_8", "absent_expert_computed"]


@pytest.mark.parametrize("name", FAULTS)
def test_every_assumed_item_and_every_fault_moves_a_logit(tiny, name):
    """Each `assumed` item turned off, and each fault that
    `benchmarks/dots3_controls.py` plants in the model, flips at least one
    logit of the tiny sequence."""
    controls = _faults()
    fault = controls.faults()[name]
    cfg = fault.get("config", lambda c: c)(tiny["cfg"])
    params = tiny["params"]
    if name == "absent_expert_computed":     # a chip that holds half
        cfg = dataclasses.replace(cfg, n_routed_experts=4)
        params = {**params, "layers": [
            {**lp, **({"w_gate_up": lp["w_gate_up"][:4],
                       "w_down": lp["w_down"][:4]} if "router" in lp else {})}
            for lp in params["layers"]]}
        clean, _, _ = served(Dots3(cfg), params, tiny["ids"])
    else:
        clean = tiny["logits"]
    with controls.planted(fault):
        got, _, _ = served(Dots3(cfg), params, tiny["ids"])
    assert float(jnp.max(jnp.abs(got - clean))) > 1e-4, name


def test_scores_accumulated_in_bfloat16_are_other_scores():
    """The one fault a tiny sequence's logits need not show: the same
    selection can come out of coarser scores. The scores themselves
    move by a bfloat16's precision, not a float32's."""
    from ray_tpu.ops.sparse_latent_attention import index_scores_reference

    fault = _faults().faults()["bf16_index_scores"]["module"]
    rng = np.random.default_rng(0)
    arena = jnp.asarray(rng.standard_normal((9, 8, 128)), jnp.float32)
    table = 1 + jnp.arange(8, dtype=jnp.int32)[None]
    q = jnp.asarray(rng.standard_normal((1, 40, 8, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((1, 40, 8)), jnp.float32)
    pos = jnp.arange(24, 64, dtype=jnp.int32)[None]
    live = jnp.ones((1, 40), bool)
    got = fault["index_accumulate"](q, w, arena, table, pos, live)
    want = index_scores_reference(q, w, arena, table, pos, live)
    seen = np.asarray(want) > -1e29
    assert ((np.asarray(got) > -1e29) == seen).all()
    err = np.abs(np.asarray(got) - np.asarray(want))[seen]
    scale = np.abs(np.asarray(want))[seen].mean()
    assert 1e-3 < err.mean() / scale < 5e-2


def test_the_pool_fault_is_planted_in_the_window_pool():
    from ray_tpu.inference.kv_cache import WindowBlockManager

    controls = _faults()
    pool = WindowBlockManager(16, 4, 9)
    pool.register("s")
    pool.ensure("s", 40)
    with controls.planted(controls.faults()["window_page_released_early"]):
        assert pool.release_below("s", 3) == 5
    assert pool.release_below("s", 6) == 1


def test_the_eight_shares_add_up_to_the_whole_layer(tiny):
    """`held = (i, 1)` for each of the tiny size's 8 experts (the
    configuration's `(32 i, 32)` for i in 0..7 of 256) and the shared
    expert counted once: the eight chips' parts add up to the uncut
    reference's expert layer."""
    cfg, params = tiny["cfg"], tiny["params"]
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(11), (48, 32))
    live = jnp.ones((48,), bool)
    _, layer = tiny["weights"]
    w = {name: (v if name.startswith("mlp.experts.")
                else jnp.asarray(v, jnp.float32))
         for name, v in layer(1).items()}
    with jax.default_matmul_precision("highest"):
        want, _, _ = plain._experts(tiny["pub"], w, x,
                                    (0, cfg.experts_routed))
    part = jax.jit(lambda first: d3.routed_experts(
        cfg, lp, x, live, (first, 1),
        (lp["w_gate_up"][first:first + 1], lp["w_down"][first:first + 1])),
        static_argnums=0)
    total = d3._swiglu(x, lp["shared_gate"], lp["shared_up"],
                       lp["shared_down"])
    assigned = 0
    for first in range(cfg.experts_routed):
        y, counts, _ = part(first)
        total = total + y
        assigned += int(counts["assigned"])
        assert int(counts["routed"]) == 48 * cfg.num_experts_per_tok
    assert assigned == 48 * cfg.num_experts_per_tok
    np.testing.assert_allclose(total, want, atol=2e-6)


def test_a_share_leaves_the_absent_experts_out_of_both(tiny):
    """Half the experts held: program and reference leave out what the
    other half would add, and the counters say how much was routed
    away."""
    cfg = dataclasses.replace(tiny["cfg"], n_routed_experts=4,
                              first_expert_held=4)
    params = {**tiny["params"], "layers": [
        {**lp, **({"w_gate_up": lp["w_gate_up"][4:],
                   "w_down": lp["w_down"][4:]} if "router" in lp else {})}
        for lp in tiny["params"]["layers"]]}
    model = Dots3(cfg)
    got, cache, _ = served(model, params, tiny["ids"])
    top, layer = published_weights(cfg, params)
    want = plain.forward(top, layer, tiny["ids"], published(cfg))
    np.testing.assert_allclose(got, want, atol=3e-6)
    assert float(jnp.max(jnp.abs(want - tiny["logits"]))) > 1e-4
    moe = model.counter_stats(jax.device_get(
        model.cache_counters(cache)))["moe"]
    routed = T * cfg.num_experts_per_tok * cfg.n_moe_layers
    both = sum(moe[k]["assigned"] + moe[k]["absent"]
               for k in ("decode", "prefill"))
    assert both == routed and moe["held"] == [4, 4]
    assert 0 < sum(moe[k]["absent"] for k in ("decode", "prefill")) < routed


def test_the_counters_say_what_was_visible_and_what_was_chosen(tiny):
    model = tiny["model"]
    _, cache, _ = served(model, tiny["params"], tiny["ids"])
    dsa = model.counter_stats(jax.device_get(
        model.cache_counters(cache)))["dsa"]
    layers, k = 2, tiny["cfg"].index_topk
    pre = list(range(32))
    dec = list(range(32, T))
    assert dsa["prefill"] == {
        "queries": 32, "keys_visible": layers * sum(p + 1 for p in pre),
        "keys_chosen": layers * sum(min(p + 1, k) for p in pre)}
    assert dsa["decode"] == {
        "queries": len(dec), "keys_visible": layers * sum(p + 1 for p in dec),
        "keys_chosen": layers * k * len(dec)}


def test_what_the_file_does_not_hold_is_refused():
    pub = published(Dots3Config.tiny())
    with pytest.raises(ValueError, match="headwise"):
        Dots3Config.from_published({**pub, "attention_gate_type": "none"})
    with pytest.raises(ValueError, match="rope_scaling"):
        Dots3Config.from_published({**pub, "rope_scaling": {"type": "yarn"}})
    with pytest.raises(ValueError, match="rescaled"):
        Dots3Config.from_published(
            {**pub, "apply_mla_qkv_lora_rescale": False})
    cfg = Dots3Config.from_published(pub, experts_routed=8)
    assert cfg == dataclasses.replace(Dots3Config.tiny(), dtype=cfg.dtype)
    with pytest.raises(ValueError, match="shorter"):
        Dots3(dataclasses.replace(cfg, num_hidden_layers=9))
