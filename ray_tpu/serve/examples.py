"""Reference deployments: batched JAX inference replicas.

The serving counterpart of the flagship model (BASELINE.json names a Serve
LLM deployment): a GPT-2 sampler replica that owns its accelerator, pads
incoming prompts into fixed shape buckets (stable shapes = one XLA
compilation), and rides `@serve.batch` so concurrent HTTP requests share
one MXU forward pass per decode step.
"""

from __future__ import annotations

from typing import Any, Dict, List

from ray_tpu import serve

# One compiled batch shape: @serve.batch caps request batches here and the
# samplers pad row counts to exactly this.
SAMPLER_BATCH = 8


def _prompts_and_budgets(requests: List[Dict[str, Any]], max_seq: int,
                         default_new: int):
    """Truncated prompts + per-request decode budgets (shared by all
    sampler deployments so clamping semantics can't drift)."""
    import numpy as np

    prompts = [list(r.get("ids", []))[: max_seq - 1] or [0]
               for r in requests]
    budgets = np.zeros(len(prompts), np.int32)
    for i, r in enumerate(requests):
        budgets[i] = max(1, min(int(r.get("max_new_tokens", default_new)),
                                max_seq - 1 - len(prompts[i])))
    return prompts, budgets


class _SamplerMetrics:
    _batches_served = 0
    _batch_size_sum = 0

    def _observe_batch(self, n: int):
        self._batches_served += 1
        self._batch_size_sum += n

    def metrics(self, _=None) -> Dict[str, Any]:
        served = self._batches_served
        return {
            "batches_served": served,
            "mean_batch_size":
                (self._batch_size_sum / served) if served else 0.0,
        }


@serve.deployment(max_concurrent_queries=32)
class GPT2Sampler(_SamplerMetrics):
    """Greedy sampler over a GPT-2 checkpoint (randomly initialized by
    default — serving-path benchmarking doesn't need trained weights).

    Request: {"ids": [int, ...], "max_new_tokens": int} -> {"ids": [...]}.
    """

    def __init__(self, model_size: str = "tiny", max_seq: int = 256,
                 default_new_tokens: int = 8):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.gpt2 import GPT2, GPT2Config

        cfg = {"tiny": GPT2Config.tiny(seq=max_seq),
               "small": GPT2Config.small(),
               "medium": GPT2Config.medium()}[model_size]
        self._cfg = cfg
        self._max_seq = min(max_seq, cfg.n_positions)
        self._default_new = default_new_tokens
        self._model = GPT2(cfg)
        rng = jax.random.PRNGKey(0)
        sample = jnp.zeros((1, self._max_seq), jnp.int32)
        self._params = jax.jit(
            lambda: self._model.init(rng, sample))()

        def next_token(params, ids, lengths):
            # ids: [b, max_seq] padded; lengths: [b] current lengths.
            logits = self._model.apply(params, ids)
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
            return jnp.argmax(last, axis=-1).astype(jnp.int32)

        max_pos = self._max_seq - 1

        def decode(params, ids, lengths, budgets):
            # The WHOLE decode loop is one compiled program: the
            # masking/append glue between forwards must not run as eager
            # ops — each eager dispatch is a host round trip with the
            # device idle, and the per-step glue outweighed the forward. A
            # while_loop with a TRACED bound (max budget) gives exactly
            # one XLA compilation for every batch shape and exactly
            # max-budget forwards — no static step count to recompile on,
            # no masked-out padding passes.
            import jax.lax as lax

            def cond(carry):
                step, _, _ = carry
                return step < jnp.max(budgets)

            def body(carry):
                step, ids, lengths = carry
                nxt = next_token(params, ids, lengths)
                active = (step < budgets) & (lengths < max_pos)
                appended = ids.at[jnp.arange(ids.shape[0]), lengths].set(nxt)
                ids = jnp.where(active[:, None], appended, ids)
                lengths = jnp.where(active, lengths + 1, lengths)
                return step + 1, ids, lengths

            _, ids, lengths = lax.while_loop(
                cond, body, (jnp.int32(0), ids, lengths))
            return ids, lengths

        self._decode = jax.jit(decode)

    @serve.batch(max_batch_size=SAMPLER_BATCH, batch_wait_timeout_s=0.02)
    async def __call__(self, requests: List[Dict[str, Any]]):
        import jax.numpy as jnp
        import numpy as np

        self._observe_batch(len(requests))
        prompts, budgets = _prompts_and_budgets(requests, self._max_seq,
                                                self._default_new)
        # Pad the batch dim to the decorator's cap: one XLA compilation for
        # every batch the flusher can produce, not one per distinct size.
        padded_b = SAMPLER_BATCH
        ids = np.zeros((padded_b, self._max_seq), np.int32)
        lengths = np.ones(padded_b, np.int32)
        lengths[: len(prompts)] = [len(p) for p in prompts]
        for i, p in enumerate(prompts):
            ids[i, : len(p)] = p
        full_budgets = np.zeros(padded_b, np.int32)
        full_budgets[: len(prompts)] = budgets
        ids = jnp.asarray(ids)
        lengths = jnp.asarray(lengths)
        full_budgets = jnp.asarray(full_budgets)
        ids, lengths = self._decode(self._params, ids, lengths,
                                    full_budgets)
        out_ids = np.asarray(ids)
        out_lens = np.asarray(lengths)
        return [{"ids": out_ids[i, : out_lens[i]].tolist()}
                for i in range(len(prompts))]


@serve.deployment(max_concurrent_queries=32)
class LlamaSampler(_SamplerMetrics):
    """KV-cached greedy sampler over a Llama-family model (BASELINE.json's
    Serve Llama deployment). Unlike GPT2Sampler's recompute-per-token
    loop, this prefills the prompt K/V once and then runs O(1)-attention
    decode steps against the cache — the TPU-serving decode shape.

    Request: {"ids": [int, ...], "max_new_tokens": int} -> {"ids": [...]}.
    """

    def __init__(self, model_size: str = "tiny", max_seq: int = 256,
                 default_new_tokens: int = 8):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.llama import Llama, llama_preset, make_cache

        cfg = llama_preset(model_size, max_seq)
        self._cfg = cfg
        self._max_seq = min(max_seq, cfg.n_positions)
        self._default_new = default_new_tokens
        self._model = Llama(cfg)
        rng = jax.random.PRNGKey(0)
        self._params = jax.jit(lambda: self._model.init(
            rng, jnp.zeros((1, 8), jnp.int32)))()
        # One preallocated cache, reused across batches: every slot a query
        # can see is rewritten during its own call (prefill writes the
        # prompt span, decode overwrites onward; the position mask hides
        # the rest), so cross-batch reuse is safe and avoids re-zeroing
        # gigabytes per request batch on big configs.
        self._cache = make_cache(self._cfg, SAMPLER_BATCH, self._max_seq)

        def prefill(params, ids, cache, lens):
            logits, cache = self._model.apply(
                params, ids, cache, jnp.zeros(ids.shape[0], jnp.int32),
                method=Llama.decode)
            # Each row's next token comes from ITS last real position.
            first = jnp.argmax(jnp.take_along_axis(
                logits, (lens - 1)[:, None, None], axis=1)[:, 0],
                axis=-1).astype(jnp.int32)
            return first, cache

        def decode_step(params, tok, cache, out, lens, budgets, step):
            # Append tok at each active row's position, then decode the
            # next token — all on-device, no host sync per token.
            active = (step < budgets) & (lens < self._max_seq - 1)
            rows = jnp.arange(out.shape[0])
            appended = out.at[rows, lens].set(tok)
            out = jnp.where(active[:, None], appended, out)
            lens = jnp.where(active, lens + 1, lens)
            logits, cache = self._model.apply(params, tok[:, None], cache,
                                              lens - 1, method=Llama.decode)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return nxt, cache, out, lens

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode_step)

    @serve.batch(max_batch_size=SAMPLER_BATCH, batch_wait_timeout_s=0.02)
    async def __call__(self, requests: List[Dict[str, Any]]):
        import jax.numpy as jnp
        import numpy as np

        self._observe_batch(len(requests))
        prompts, budgets = _prompts_and_budgets(requests, self._max_seq,
                                                self._default_new)
        b = SAMPLER_BATCH
        # Prompt pad to a power of two: a handful of prefill programs total.
        plen = max(len(p) for p in prompts)
        pad = 8
        while pad < plen:
            pad *= 2
        pad = min(pad, self._max_seq)
        ids = np.zeros((b, pad), np.int32)
        lens = np.ones(b, np.int32)
        for i, p in enumerate(prompts):
            ids[i, : len(p)] = p
            lens[i] = len(p)
        full_budgets = np.zeros(b, np.int32)
        full_budgets[: len(prompts)] = budgets

        tok, self._cache = self._prefill(self._params, jnp.asarray(ids),
                                         self._cache, jnp.asarray(lens))
        out = jnp.zeros((b, self._max_seq), jnp.int32)
        out = out.at[:, :pad].set(jnp.asarray(ids))
        lens_j = jnp.asarray(lens)
        budgets_j = jnp.asarray(full_budgets)
        for step in range(int(budgets.max())):
            tok, self._cache, out, lens_j = self._decode(
                self._params, tok, self._cache, out, lens_j, budgets_j,
                jnp.int32(step))
        out_np = np.asarray(out)
        out_lens = np.asarray(lens_j)
        return [{"ids": out_np[i, : out_lens[i]].tolist()}
                for i in range(len(prompts))]
