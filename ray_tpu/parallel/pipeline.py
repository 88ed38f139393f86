"""Pipeline parallelism: GPipe microbatch schedule over the `pp` mesh axis.

The reference delegates pipeline parallelism to engines run on top of its
actors (Alpa/DeepSpeed, `release/alpa_tests/train_opt_2_7b_minimum.py:39`);
here it is a first-class transform built the TPU way:

- The model's repeated trunk (L identical layers) is stacked into per-leaf
  `[n_stages, layers_per_stage, ...]` arrays whose leading dim carries the
  "stage" logical axis (rule "stage" -> pp).
- `gpipe` wraps a single-layer apply into an SPMD program via `shard_map`:
  each device along pp holds one stage and scans its local layers; a
  `lax.scan` over `n_microbatches + n_stages - 1` ticks moves activations
  stage-to-stage with `ppermute`. Everything is statically shaped, and
  `jax.grad` through scan+ppermute yields the pipelined backward (1F1B-ish
  memory can be recovered with `remat_stage=True`, which wraps each stage
  in `jax.checkpoint`).
- Embedding/LM-head run outside the pipelined trunk in the surrounding
  GSPMD region, so dp/tp/sp compose with pp: the pipeline is over layers,
  XLA still shards each stage's matmuls over tp and its batch over dp.

The first-stage feed selects microbatch `t` while later ticks feed from
the ring; the last stage's outputs are collected tick-aligned and summed
back over pp (zeros elsewhere), which keeps the schedule a pure function
of statically-known indices — no data-dependent control flow under jit.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp


def stack_stage_params(layer_params: Any, n_stages: int) -> Any:
    """Reshape a scanned-layers pytree `[L, ...]` to `[P, L/P, ...]`."""

    def reshape(leaf):
        l = leaf.shape[0]
        if l % n_stages:
            raise ValueError(
                f"{l} layers not divisible by {n_stages} pipeline stages")
        return leaf.reshape(n_stages, l // n_stages, *leaf.shape[1:])

    return jax.tree.map(reshape, layer_params)


def unstack_stage_params(staged_params: Any) -> Any:
    """Inverse of `stack_stage_params`: `[P, L/P, ...]` -> `[L, ...]`."""
    return jax.tree.map(
        lambda leaf: leaf.reshape(leaf.shape[0] * leaf.shape[1],
                                  *leaf.shape[2:]),
        staged_params)


def gpipe(layer_fn: Callable[[Any, jnp.ndarray], jnp.ndarray], mesh,
          n_microbatches: int, axis: str = "pp",
          remat_stage: bool = False) -> Callable[[Any, jnp.ndarray],
                                                 jnp.ndarray]:
    """Build `(staged_params, x) -> y` running layer_fn's stack pipelined.

    `staged_params` leaves are `[P, L/P, ...]` (see stack_stage_params) and
    must enter sharded over `axis` on the leading dim; `x` is `[B, ...]`
    with B divisible by n_microbatches. The returned y equals the
    sequential application of all L layers (same math, pipelined
    schedule).
    """
    from jax.sharding import PartitionSpec as P

    def stage_fn(stage_params, x):
        # Scan this stage's local layers in order.
        def body(h, p):
            return layer_fn(p, h), None

        out, _ = jax.lax.scan(body, x, stage_params)
        return out

    if remat_stage:
        stage_fn = jax.checkpoint(stage_fn)

    n_stages = mesh.shape[axis]

    def spmd(staged_params, x_mb):
        # Local views: params [1, L/P, ...] -> [L/P, ...]; x_mb is the full
        # [M, mb, ...] microbatched input (replicated over pp).
        stage_params = jax.tree.map(lambda a: a[0], staged_params)
        idx = jax.lax.axis_index(axis)
        m = x_mb.shape[0]
        ticks = m + n_stages - 1
        zero_mb = jnp.zeros_like(x_mb[0])

        def tick(buf, t):
            # Stage 0 feeds microbatch t (while available); other stages
            # consume what the ring delivered last tick.
            feed = jnp.where(t < m, x_mb[jnp.minimum(t, m - 1)], zero_mb)
            inp = jnp.where(idx == 0, feed, buf)
            out = stage_fn(stage_params, inp)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            nxt = jax.lax.ppermute(out, axis, perm)
            return nxt, out

        _, outs = jax.lax.scan(tick, zero_mb, jnp.arange(ticks))
        # The last stage emitted microbatch j's output at tick j + P - 1.
        tail = jax.lax.dynamic_slice_in_dim(outs, n_stages - 1, m, axis=0)
        y = jnp.where(idx == n_stages - 1, tail, jnp.zeros_like(tail))
        return jax.lax.psum(y, axis)

    # Batch dim of each microbatch shards over dp(+fsdp): every dp slice
    # pipelines only its share of the batch (pp shards layers, dp shards
    # data — the composition the mesh promises).
    batch_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
    x_spec = P(None, batch_axes if batch_axes else None)

    def run(staged_params, x_mb):
        in_specs = (jax.tree.map(lambda _: P(axis), staged_params), x_spec)
        return jax.shard_map(spmd, mesh=mesh, in_specs=in_specs,
                             out_specs=x_spec, check_vma=False)(
                                 staged_params, x_mb)

    return run


def to_microbatches(x: jnp.ndarray, n_microbatches: int) -> jnp.ndarray:
    """[B, ...] -> [M, B/M, ...]."""
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} not divisible by {n_microbatches}"
                         " microbatches")
    return x.reshape(n_microbatches, b // n_microbatches, *x.shape[1:])


def from_microbatches(y: jnp.ndarray) -> jnp.ndarray:
    return y.reshape(y.shape[0] * y.shape[1], *y.shape[2:])


# --------------------------------------------------------------------------- #
# A pipelined transformer LM built from functional blocks
# --------------------------------------------------------------------------- #
#
# The trunk blocks are written as pure functions over a params dict (rather
# than flax modules) so they run unmodified inside shard_map's per-device
# world; embed/head stay in the outer GSPMD region.


def init_block_params(key, d_model: int, n_head: int, d_ff: int,
                      dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 6)
    s = 0.02
    return {
        "ln1_scale": jnp.ones((d_model,), dtype),
        "ln2_scale": jnp.ones((d_model,), dtype),
        "qkv": jax.random.normal(ks[0], (d_model, 3 * d_model), dtype) * s,
        "proj": jax.random.normal(ks[1], (d_model, d_model), dtype) * s,
        "fc": jax.random.normal(ks[2], (d_model, d_ff), dtype) * s,
        "fc_out": jax.random.normal(ks[3], (d_ff, d_model), dtype) * s,
    }


def _rms(x, scale, eps=1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            * scale).astype(x.dtype)


def block_apply(p: dict, x: jnp.ndarray, n_head: int) -> jnp.ndarray:
    """Pre-norm causal attention + MLP block on [b, s, d]."""
    b, s, d = x.shape
    hd = d // n_head
    h = _rms(x, p["ln1_scale"])
    qkv = h @ p["qkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(b, s, n_head, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (hd ** 0.5)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -1e30)
    attn = jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(scores, axis=-1), v)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + attn @ p["proj"]
    h2 = _rms(x, p["ln2_scale"])
    return x + jax.nn.gelu(h2 @ p["fc"]) @ p["fc_out"]


def init_pp_lm(key, vocab: int, d_model: int, n_layer: int, n_head: int,
               d_ff: int, n_positions: int, n_stages: int) -> dict:
    """Params for the pipelined LM: stacked trunk + embed/head."""
    kl, ke, kp, kh = jax.random.split(key, 4)
    layer_params = jax.vmap(
        lambda k: init_block_params(k, d_model, n_head, d_ff))(
            jax.random.split(kl, n_layer))
    return {
        "stages": stack_stage_params(layer_params, n_stages),
        "embed": jax.random.normal(ke, (vocab, d_model)) * 0.02,
        "pos": jax.random.normal(kp, (n_positions, d_model)) * 0.01,
        "head": jax.random.normal(kh, (d_model, vocab)) * 0.02,
    }


def make_pp_train_step(mesh, n_head: int, n_microbatches: int,
                       optimizer, remat_stage: bool = False,
                       axis: str = "pp"):
    """Jitted pipelined train step (params, opt_state, batch) -> (...).

    Stage weights stay sharded over pp; embed/head live in the outer GSPMD
    region (sharded by dp/tp rules as usual). Loss is next-token CE.
    """
    from ray_tpu.models.gpt2 import next_token_loss

    pipe = gpipe(functools.partial(_pp_block, n_head=n_head), mesh,
                 n_microbatches, axis=axis, remat_stage=remat_stage)

    def forward(params, input_ids):
        b, s = input_ids.shape
        x = params["embed"][input_ids] + params["pos"][None, :s]
        x_mb = to_microbatches(x, n_microbatches)
        y_mb = pipe(params["stages"], x_mb)
        y = from_microbatches(y_mb)
        return y @ params["head"]

    def step(params, opt_state, batch):
        def loss_fn(p):
            logits = forward(p, batch["input_ids"])
            return next_token_loss(logits, batch["labels"])

        loss, grads = jax.value_and_grad(loss_fn)(params)
        import optax

        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step), forward


def _pp_block(p, x, n_head):
    return block_apply(p, x, n_head)


def sequential_forward(params: dict, input_ids, n_head: int):
    """Reference: apply the same stacked layers without the pipeline."""
    b, s = input_ids.shape
    x = params["embed"][input_ids] + params["pos"][None, :s]
    layers = unstack_stage_params(params["stages"])

    def body(h, p):
        return block_apply(p, h, n_head), None

    x, _ = jax.lax.scan(body, x, layers)
    return x @ params["head"]


def stage_shardings(mesh, params: dict, axis: str = "pp"):
    """NamedSharding pytree: stages over pp, everything else replicated."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    staged = jax.tree.map(
        lambda _: NamedSharding(mesh, P(axis)), params["stages"])
    out = {k: jax.tree.map(lambda _: NamedSharding(mesh, P()), v)
           for k, v in params.items() if k != "stages"}
    out["stages"] = staged
    return out
