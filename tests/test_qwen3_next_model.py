"""Qwen3-Next on the CPU at small sizes, seeded weights: the model against
`benchmarks/reference/qwen3_next_plain.py` on logits, loss and gradients
(one period, float32: the same mathematics; bfloat16 through the kernels in
the interpreter: the same within rounding); sixteen shares of one expert
layer against the uncut layer of the reference; a few steps through
`make_train_step`.
"""

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

from benchmarks.reference import qwen3_next_plain as plain  # noqa: E402
from ray_tpu.models.gpt2 import make_train_step  # noqa: E402
from ray_tpu.models.qwen3_next import (  # noqa: E402
    KEPT_BY_REMAT, Qwen3Next, Qwen3NextConfig, make_loss_fn,
    published_weights)
from ray_tpu.ops import attention  # noqa: E402
from ray_tpu.ops import gated_delta as gd  # noqa: E402


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def close(a, b, rel):
    scale = float(jnp.max(jnp.abs(b))) + 1e-12
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) <= rel * scale


def tiny(**kw):
    return Qwen3NextConfig.tiny(dtype=jnp.float32, **kw)


def reference_keys(cfg):
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
           if isinstance(getattr(cfg, f.name), (int, float))}
    return {**out, "router_width": cfg.num_experts}


@pytest.fixture(scope="module")
def both():
    """The program and the plain reference on two seeded sequences, each
    under ONE jitted value-and-grad (what every test below reads)."""
    cfg = tiny(held_experts=(4, 8))
    model = Qwen3Next(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), ids)
    # norms start at 0 and 1: move them, so that a swapped one would show
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    leaves = [l + (0.05 * jax.random.normal(k, l.shape) if l.ndim == 1
                   else 0) for l, k in zip(leaves, keys)]
    params = jax.tree.unflatten(tree, leaves)
    loss_fn = make_loss_fn(model)

    @jax.jit
    def program(params, ids):
        def objective(p):
            logits, aux = model.apply(p, ids[None], return_aux=True)
            total, shown = loss_fn(p, {"input_ids": ids[None],
                                       "labels": ids[None]})
            return total, (shown, logits[0], aux)
        return jax.value_and_grad(objective, has_aux=True)(params)

    @jax.jit
    def reference(w, ids):
        def objective(w):
            logits, picks, balance = plain.forward(
                w, ids, reference_keys(cfg), cfg.held_experts)
            ce = plain.next_token_loss(logits, ids)
            return ce + cfg.router_aux_loss_coef * jnp.mean(balance), (
                ce, logits, picks)
        return jax.value_and_grad(objective, has_aux=True)(w)

    weights = published_weights(params, cfg)
    return cfg, [(program(params, row), reference(weights, row))
                 for row in ids]


def test_the_model_matches_the_plain_reference_in_float32(both):
    cfg, rows = both
    for ((_, (_, logits, aux)), _), ((_, (_, want, picks)), _) in rows:
        assert float(jnp.max(jnp.abs(logits - want))) < 1e-4
        assert bool(jnp.all(jnp.sort(aux["index"], -1)
                            == jnp.sort(picks, -1)))
        assert [int(x) for x in aux["placed"]] == [
            int(x) for x in aux["assigned"]]


def test_loss_and_gradients_match_the_plain_reference(both):
    cfg, rows = both
    for ((total, (shown, _, _)), grads), ((want, (want_ce, _, _)),
                                          want_grads) in rows:
        assert float(total) == pytest.approx(float(want), abs=1e-5)
        assert float(shown["loss"]) == pytest.approx(float(want_ce),
                                                     abs=1e-5)
        assert float(total) > float(shown["loss"])     # the auxiliary loss
        got_grads = published_weights(grads, cfg)
        assert set(got_grads) == set(want_grads)
        for name, want in want_grads.items():
            assert close(got_grads[name], want, 1e-3), name


def test_the_kernel_paths_match_the_reference_in_bfloat16(interpret):
    # one recurrent and one attention layer, at widths the kernels take
    cfg = Qwen3NextConfig.tiny(
        held_experts=(4, 8), linear_key_head_dim=128,
        linear_value_head_dim=128, linear_num_key_heads=1,
        linear_num_value_heads=2, head_dim=64, num_hidden_layers=2,
        full_attention_interval=2)
    model = Qwen3Next(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), ids)
    gd.reset_gated_delta_status()
    logits = jax.jit(model.apply)(params, ids)
    want, _, _ = jax.jit(lambda w, row: plain.forward(
        w, row, reference_keys(cfg), cfg.held_experts))(
        published_weights(params, cfg), ids[0])
    assert close(logits[0], want, 5e-2)
    assert {c["path"] for c in gd.gated_delta_status()} == {"pallas"}


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """One expert layer of 32 experts, cut into sixteen shares of two: what
    the shares give, with the shared expert (which every chip computes
    alike) counted once, is what the uncut reference gives."""
    from ray_tpu.models.qwen3_next import SparseMoe

    base = tiny(num_experts=32, held_experts=(0, 32))
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    x = jax.random.normal(ks[0], (1, 96, base.hidden_size))
    whole = SparseMoe(base).init(ks[1], x)["params"]
    unbox = lambda t: getattr(t, "value", t)
    weights = {
        "gate": whole["router"],
        "experts.gate_proj": whole["experts_gate_up"][..., :32],
        "experts.up_proj": whole["experts_gate_up"][..., 32:],
        "experts.down_proj": whole["experts_down"],
        "shared_expert.gate_proj": unbox(whole["shared_gate"]["kernel"]),
        "shared_expert.up_proj": unbox(whole["shared_up"]["kernel"]),
        "shared_expert.down_proj": unbox(whole["shared_down"]["kernel"]),
        "shared_expert_gate": unbox(whole["shared_expert_gate"]["kernel"])}
    keys = reference_keys(base)
    uncut, *_ = plain.expert_layer(weights, "", x[0], keys, (0, 32))
    shared_alone, *_ = plain.expert_layer(weights, "", x[0], keys, (0, 0))
    total = jnp.zeros_like(uncut)
    for share in range(16):
        cfg = dataclasses.replace(base, held_experts=(2 * share, 2))
        mine = {**whole,
                "experts_gate_up": whole["experts_gate_up"][
                    2 * share:2 * share + 2],
                "experts_down": whole["experts_down"][
                    2 * share:2 * share + 2]}
        out, aux = jax.jit(SparseMoe(cfg).apply)({"params": mine}, x)
        assert int(aux["placed"]) == int(aux["assigned"])
        total = total + out[0]
    assert float(jnp.max(jnp.abs(total - 15 * shared_alone - uncut))) < 1e-4


def test_trained_through_make_train_step_the_loss_falls():
    import optax

    cfg = Qwen3NextConfig.tiny(held_experts=(4, 8), remat=True,
                               num_hidden_layers=2,
                               full_attention_interval=2)
    model = Qwen3Next(cfg)
    ids = (cfg.vocab_size * np.random.default_rng(0).random((2, 64)) ** 3
           ).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(ids))
    opt = optax.adamw(3e-3)
    step = make_train_step(model, opt, donate=False,
                           loss_fn=make_loss_fn(model))
    opt_state = opt.init(params)
    batch = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(ids)}
    losses = []
    for _ in range(5):
        params, opt_state, shown = step(params, opt_state, batch)
        losses.append(float(shown["loss"]))
    assert losses[-1] < losses[0]
    # the routed counts are outputs of the step
    assert shown["moe"]["load"].shape == (2, 8)
    assert [int(x) for x in shown["moe"]["placed"]] == [
        int(x) for x in shown["moe"]["assigned"]]
    assert shown["load_balance"].shape == (2,)


# --------------------------------------------------------------------------- #
# What a rematerialised layer keeps (`KEPT_BY_REMAT`): one recurrent and one
# softmax layer at widths the kernels take, the kernels in the interpreter.
# --------------------------------------------------------------------------- #

FORWARD_KERNELS = ("gdn_chunk_fwd", "flash_fwd", "moe_gmm", "gdn_prep_fwd",
                   "gdn_gate_fwd")
ROUTING = ("top_k", "sort")     # primitives of the plan, counted as kernels
BACKWARD_KERNELS = ("gdn_chunk_bwd", "flash_bwd_dq", "flash_bwd_dkv",
                    "moe_gmm_dlhs", "moe_gmm_drhs", "gdn_prep_bwd",
                    "gdn_gate_bwd")
# calls a recurrent layer: the elementwise kernels beside the recurrence
# run once a kind of head (q, k, v), the gate's once
CALLS_A_LAYER = {"gdn_prep_fwd": 3, "gdn_prep_bwd": 3, "gdn_gate_fwd": 1,
                 "gdn_gate_bwd": 1, "gdn_chunk_fwd": 1, "gdn_chunk_bwd": 1}


def kernel_calls(jaxpr, found=None):
    """`pallas_call` equations by kernel name and the `ROUTING` primitives
    by their own, inner jaxprs included."""
    import collections

    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
        elif eqn.primitive.name in ROUTING:
            found[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    kernel_calls(inner, found)
    return found


def two_kernel_layers(remat, dtype=jnp.bfloat16):
    cfg = Qwen3NextConfig.tiny(
        held_experts=(4, 8), linear_key_head_dim=128,
        linear_value_head_dim=128, linear_num_key_heads=1,
        linear_num_value_heads=2, head_dim=64, num_hidden_layers=2,
        full_attention_interval=2, remat=remat, dtype=dtype)
    model = Qwen3Next(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), ids)
    loss_fn = make_loss_fn(model)
    return params, lambda p: loss_fn(p, {"input_ids": ids,
                                         "labels": ids})[0]


@pytest.fixture(scope="module")
def remat_and_not():
    """{remat: (kernel calls in the gradient's jaxpr, loss, gradients,
    saved residuals)}. The values in float32 activations: XLA's CPU
    backend keeps a bf16 elementwise chain in f32 inside a fusion, and a
    remat moves the fusions' borders, so bf16 gradients differ by an ulp
    with or without this PR; the kernels round their operands to bf16
    themselves either way."""
    from jax._src.ad_checkpoint import saved_residuals

    was = os.environ.get("RAY_TPU_PALLAS_INTERPRET")
    os.environ["RAY_TPU_PALLAS_INTERPRET"] = "1"
    try:
        out = {}
        for remat in (False, True):
            params, loss = two_kernel_layers(remat)
            calls = kernel_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
            kept = saved_residuals(loss, params)
            params, loss = two_kernel_layers(remat, jnp.float32)
            out[remat] = (calls, *jax.jit(jax.value_and_grad(loss))(params),
                          kept)
        return out
    finally:
        if was is None:
            del os.environ["RAY_TPU_PALLAS_INTERPRET"]
        else:
            os.environ["RAY_TPU_PALLAS_INTERPRET"] = was


@pytest.mark.parametrize("kernel",
                         FORWARD_KERNELS + BACKWARD_KERNELS + ROUTING)
def test_remat_runs_no_kernel_more_often_than_no_remat(remat_and_not, kernel):
    # the parent ran every forward kernel, the top-k and the plan's sorts
    # twice a layer under remat
    plain, kept = remat_and_not[False][0], remat_and_not[True][0]
    assert set(plain) == set(FORWARD_KERNELS + BACKWARD_KERNELS + ROUTING)
    assert kept[kernel] == plain[kernel] > 0
    # one recurrent layer: no forward beside the recurrence runs twice
    assert kept[kernel] == CALLS_A_LAYER.get(kernel, kept[kernel])


def test_remat_changes_no_bit_of_loss_or_gradients(remat_and_not):
    (_, loss, grads, _), (_, kept_loss, kept_grads, _) = (
        remat_and_not[False], remat_and_not[True])
    assert float(loss) == float(kept_loss)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(kept_grads)):
        assert bool(jnp.all(a == b)), jax.tree_util.keystr(path)


# name -> (dtype, shape) of what goes by it in `two_kernel_layers`: tokens
# 128, one key head / two value heads of 128 (2 chunks), 4 heads of 64,
# 8 held experts of width 32 in a block of 512 + 8 x 128 rows
KEPT_ARRAYS = {
    # the projection [q 128 | k 128 | v 256 | z 256], ONCE: `gdn_prep`'s
    # and `gdn_gate`'s one large residual
    "gdn_in": [("bfloat16", (1, 128, 768))],
    "gdn_qkv": [("bfloat16", (1, 128, 1, 128)),
                ("bfloat16", (1, 128, 2, 128))],
    "gdn_gated": [("bfloat16", (1, 128, 256))],
    "gdn_out": [("bfloat16", (1, 128, 256))],
    "gdn_states": [("float32", (1, 2, 2, 128, 128))],
    "flash_out": [("bfloat16", (1, 128, 256))],
    "flash_lse": [("float32", (1, 4, 1, 128))],
    "moe_plan": [("float32", (128, 16)), ("float32", (128, 4)),
                 ("int32", (128, 4)), ("int32", (12,)), ("int32", (1,)),
                 ("int32", (1536,))],
    "moe_h": [("bfloat16", (1536, 64))],
    "moe_y": [("bfloat16", (1536, 64))],
}


@pytest.mark.parametrize("name", KEPT_BY_REMAT)
def test_a_rematerialised_layer_keeps_what_goes_by(remat_and_not, name):
    kept = remat_and_not[True][3]
    made = [(aval.dtype.name, aval.shape) for aval, _ in kept]
    for want in KEPT_ARRAYS[name]:
        assert want in made, (name, want)
    # jax says "named" of a kept array that nothing else of the forward
    # reads, and "reduce_precision" (the identity it puts on the others)
    assert any(f"named '{name}'" in why or "reduce_precision" in why
               for aval, why in kept
               if (aval.dtype.name, aval.shape) in KEPT_ARRAYS[name])


def test_a_rematerialised_layer_keeps_the_bf16_projection_once_and_no_f32_convolution_output(
        remat_and_not):
    def wide(kept):
        # in_proj_qkvz's output is 768 wide here (12,288 in the cell) and
        # the convolution's output 512 (8,192); nothing else is
        return [(aval.dtype.name, aval.shape, why) for aval, why in kept
                if aval.ndim == 3 and aval.shape[-1] in (768, 512)
                and "argument" not in why]

    # with or without remat the fused ops' residual is the projection as
    # the product made it: bf16, once, and no f32 array of the chain
    # (jax's own rules kept the convolution's and SiLU's f32 outputs)
    for remat in (False, True):
        kept = wide(remat_and_not[remat][3])
        assert kept and {(dtype, shape) for dtype, shape, _ in kept} == {
            ("bfloat16", (1, 128, 768))}, kept
    # once: `gdn_prep`'s residual and `gdn_gate`'s are one named array
    assert len(wide(remat_and_not[True][3])) == 1
    # nor a head's f32 q, k, v or z, [1, 128, heads, 128], which the norms'
    # and the gate's rules kept
    for remat in (False, True):
        for aval, why in remat_and_not[remat][3]:
            assert not (aval.dtype == jnp.float32 and aval.ndim == 4
                        and aval.shape[:2] == (1, 128)
                        and aval.shape[-1] == 128), (aval, why)


def how_cut(jaxpr, found=None):
    """Every `pallas_call` of a jaxpr, inner jaxprs included, as (kernel
    name, grid, the text of each operand's index map): how a call is cut
    into grid steps and which block of each operand a step reads."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            mapping = eqn.params["grid_mapping"]
            found.append((eqn.params["name"], tuple(mapping.grid), tuple(
                str(b.index_map_jaxpr) for b in mapping.block_mappings)))
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    how_cut(inner, found)
    return found


def test_the_names_change_nothing_of_a_gpt2_train_step(interpret,
                                                       monkeypatch):
    """GPT-2 trains with `remat=False`: a name is the identity there. The
    step's jaxpr holds the same kernels and its outputs the same bits as
    with `checkpoint_name` taken out of `ops/attention.py`. Nor does what
    PR 44 gave long sequences and grouped queries reach it: one block a
    (batch, column block), q, k and v three views of one array, every
    index map the grid step's own indices and a constant shift (no
    division by a group, no nearest live block), and with `_live` taken
    out the same cut and the same bits."""
    import optax

    from ray_tpu.models.gpt2 import GPT2, GPT2Config

    cfg = GPT2Config(vocab_size=256, n_positions=128, n_embd=128, n_layer=2,
                     n_head=2, use_flash=True)
    model = GPT2(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 256)
    params = model.init(jax.random.PRNGKey(0), ids)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    batch = {"input_ids": ids, "labels": ids}

    def step_of():
        step = make_train_step(model, opt, donate=False)
        jaxpr = jax.make_jaxpr(step)(params, opt_state, batch).jaxpr
        return (kernel_calls(jaxpr), how_cut(jaxpr),
                step(params, opt_state, batch))

    named_calls, named_cut, named = step_of()
    monkeypatch.setattr(attention, "checkpoint_name", lambda made, _: made)
    bare_calls, bare_cut, bare = step_of()
    monkeypatch.setattr(attention, "_live",
                        lambda *a, **kw: lambda i, j: (i, j))
    jax.clear_caches()       # `_live` is read inside the jitted wrappers
    _, uncut, plain_step = step_of()
    assert named_calls == bare_calls == {
        "flash_fwd": 2, "flash_bwd_dq": 2, "flash_bwd_dkv": 2}
    assert named_cut == bare_cut == uncut
    for name, grid, maps in named_cut:
        assert grid == (2, 1, 1, 1), name      # two heads of 64 a block
        assert not any(op in text for text in maps
                       for op in ("div", "rem", "min", "max")), (name, maps)
    for other in (bare, plain_step):
        for a, b in zip(jax.tree.leaves(named), jax.tree.leaves(other)):
            assert bool(jnp.all(a == b))


def test_the_attention_layer_reads_a_kv_head_as_it_read_its_repeat(
        interpret, monkeypatch):
    """`GatedAttention` hands the kernels k and v as `k_proj` / `v_proj`
    make them. At a head width where a column block is one head (4 query
    on 2 KV heads of 128) the layer's output and every gradient equal the
    layer that repeats k and v first, to the order of a sum."""
    from ray_tpu.models import qwen3_next

    cfg = tiny(head_dim=128, hidden_size=128)
    layer = qwen3_next.GatedAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 256, cfg.hidden_size))
    params = layer.init(jax.random.PRNGKey(0), x)

    def value_and_grads():
        attention.reset_pallas_status()
        out, vjp = jax.vjp(layer.apply, params, x)
        made = [out, *jax.tree.leaves(vjp(jnp.cos(out)))]
        return made, {(e["pass"], e["path"], e["kv_heads"])
                      for e in attention.pallas_status()}

    got, status = value_and_grads()
    assert status == {("fwd", "pallas", 2), ("bwd", "pallas", 2)}
    entry = attention.flash_attention_bse

    def repeated(qkv, d, causal=True):
        q, k, v = qkv
        group = q.shape[-1] // k.shape[-1]
        k, v = (jnp.repeat(t.reshape(*t.shape[:2], -1, d), group,
                           axis=2).reshape(q.shape) for t in (k, v))
        return entry((q, k, v), d, causal)

    monkeypatch.setattr(qwen3_next, "flash_attention_bse", repeated)
    want, status = value_and_grads()
    assert status == {("fwd", "pallas", 4), ("bwd", "pallas", 4)}
    for a, b in zip(got, want):
        assert a.shape == b.shape and close(a, b, 1e-5)
