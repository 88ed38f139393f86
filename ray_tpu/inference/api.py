"""Serve integration for the continuous-batching engine.

`LLMServer` is a `@serve.deployment` hosting one `InferenceEngine` per
replica (the replica's actor owns the chip; the engine thread owns the
jitted step programs). Two entry points:

- `__call__` / `generate`: complete the whole generation, return
  ``{"ids": [...]}``.
- `stream`: an async generator yielding one event per produced token;
  the existing replica/handle/proxy stream plumbing carries them to
  Python callers (``handle.options(stream=True)``) and HTTP clients
  (chunked JSON lines) as they are emitted — time-to-first-token is one
  scheduler step, not one full generation.

The replica exports the engine's queue depth through the
``__serve_metrics__`` hook, so the controller's autoscaler sees queued
requests (not just in-flight RPCs) and scales replicas on real backlog.
``__serve_shutdown__`` stops the engine thread at replica teardown.
"""

from __future__ import annotations

import asyncio
import collections
from typing import Any, Dict, Optional

from ray_tpu import serve
from ray_tpu.inference.engine import EngineConfig, EngineLoop, InferenceEngine


def preset_model(model_size: str = "tiny", max_model_len: int = 256):
    """(model, params) of a preset named the way `LLMServer` names it:
    a Llama at `llama_preset`'s widths with randomly initialised weights
    from key 0 (same name, same weights, on every replica and every rank
    of a gang)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import Llama, llama_preset

    model = Llama(llama_preset(model_size, max_model_len))
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))()
    return model, params


def _parse(payload: Optional[Dict[str, Any]], default_new: int):
    payload = payload or {}
    ids = [int(t) for t in payload.get("ids", [])] or [0]
    max_new = max(1, int(payload.get("max_new_tokens", default_new)))
    model_id = payload.get("model_id") or payload.get("model")
    slo = payload.get("slo_class") or payload.get("slo")
    return (ids, max_new,
            (str(model_id) if model_id is not None else None),
            (str(slo) if slo is not None else None))


class _LoopMailbox:
    """Hands items from the engine thread to asyncio queues of one loop.
    One wake-up of the loop serves every item posted before it ran: a
    decode step posts a token for every row, and a
    `call_soon_threadsafe` each wrote the loop's self-pipe, and gave the
    GIL up to it, once a token (64 times a step at 64 slots)."""

    def __init__(self, loop):
        self.loop = loop
        self._items: collections.deque = collections.deque()
        self._waking = False

    def post(self, queue: asyncio.Queue, item) -> None:
        # Append before the test: a drain that has not cleared the flag
        # yet has not started popping either, so it will see this item.
        self._items.append((queue, item))
        if not self._waking:
            self._waking = True
            self.loop.call_soon_threadsafe(self._drain)

    def _drain(self) -> None:
        self._waking = False
        while self._items:
            queue, item = self._items.popleft()
            queue.put_nowait(item)


@serve.deployment(max_concurrent_queries=64)
class LLMServer:
    """Continuous-batching LLM deployment.

    Request: ``{"ids": [int, ...], "max_new_tokens": int}``;
    response: ``{"ids": [prompt + generated]}`` (generate) or a stream of
    ``{"token": int}`` events followed by ``{"done": true, "ids": [...]}``
    (stream).
    """

    def __init__(self, model_size: str = "tiny",
                 max_model_len: int = 256,
                 default_new_tokens: int = 16,
                 engine_config: Optional[Dict[str, Any]] = None,
                 adapters: Optional[Dict[str, Dict[str, Any]]] = None,
                 max_resident_adapters: int = 0):
        kwargs = dict(engine_config or {})
        # Model multiplexing: `adapters` registers the replica's servable
        # LoRA models ({model_id: {"seed": int, "rank": r, "scale": s}}).
        # Weights are DERIVED (deterministically, from the seed) on
        # demand, loaded into the shared bank LRU-style — a respawned
        # replica reloads an adapter the moment a request names it, bit-
        # identical to before the crash. max_resident_adapters bounds
        # bank rows (default: all registered adapters resident at once).
        self._adapter_specs = {str(k): dict(v or {})
                               for k, v in (adapters or {}).items()}
        if self._adapter_specs:
            ranks = {int(s.get("rank", 8))
                     for s in self._adapter_specs.values()}
            if len(ranks) > 1:
                raise ValueError(
                    f"all adapters of a replica share one bank rank; "
                    f"got {sorted(ranks)}")
            kwargs.setdefault("max_adapters",
                              max_resident_adapters
                              or len(self._adapter_specs))
            kwargs.setdefault("lora_rank", ranks.pop())
        self._default_new = default_new_tokens
        self._config = EngineConfig(**kwargs)
        # Sharded replica groups: when this replica is a gang rank the
        # shard context was activated before this ctor ran; the gang's
        # tp mesh turns on the engine's tensor-parallel path (params and
        # the paged KV arena shard over the mesh, same seed -> same
        # weights as an unsharded replica).
        from ray_tpu import shardgroup

        model, params = preset_model(model_size, max_model_len)
        self._engine = InferenceEngine(self._config, model=model,
                                       params=params,
                                       mesh=shardgroup.current_mesh())
        if self._adapter_specs:
            self._engine.register_adapter_source(self._load_adapter)
        self._loop = EngineLoop(self._engine)

    def _load_adapter(self, model_id: str):
        """Engine adapter source: spec -> deterministic weights (the
        parity and chaos tests depend on seed => same bytes)."""
        from ray_tpu.models.llama import make_adapter_weights

        spec = self._adapter_specs.get(model_id)
        if spec is None:
            raise ValueError(
                f"unknown model {model_id!r} (registered: "
                f"{sorted(self._adapter_specs)})")
        return make_adapter_weights(
            self._engine._model.config,
            rank=int(spec.get("rank", 8)),
            seed=int(spec.get("seed", 0)),
            scale=float(spec.get("scale", 0.05)))

    # ------------------------------------------------------------ complete

    async def __call__(self, payload=None):
        # HTTP clients reach methods only through __call__: a
        # ``"stream": true`` field switches to the token stream (the
        # replica pumps the returned async generator, the proxy relays
        # it as chunked JSON lines).
        if isinstance(payload, dict) and payload.get("stream"):
            return self.stream(payload)
        return await self.generate(payload)

    async def generate(self, payload=None):
        ids, max_new, model_id, slo = _parse(payload, self._default_new)
        loop = asyncio.get_running_loop()
        fut = loop.create_future()

        def on_finish(req):
            def _resolve():
                if fut.done():
                    return
                if req.error:
                    fut.set_exception(RuntimeError(req.error))
                else:
                    fut.set_result(None)
            loop.call_soon_threadsafe(_resolve)

        req = self._loop.submit(ids, max_new, on_finish=on_finish,
                                model_id=model_id, slo_class=slo)
        try:
            await fut
        except asyncio.CancelledError:
            # Caller abandoned the request: release its slot and blocks.
            self._engine.cancel(req.request_id)
            raise
        return {"ids": list(req.prompt) + list(req.generated)}

    # -------------------------------------------------------------- stream

    async def stream(self, payload=None):
        """Async generator: one ``{"token": t}`` per produced token, then
        ``{"done": True, "ids": [...]}`` — replica pumps it through the
        stream queue, the proxy relays chunked JSON lines, handles iterate
        it with ``options(stream=True)``."""
        ids, max_new, model_id, slo = _parse(payload, self._default_new)
        loop = asyncio.get_running_loop()
        queue: asyncio.Queue = asyncio.Queue()
        # One mailbox a replica (its streams share the replica's loop);
        # made here because a subclass may not run this class's __init__.
        mailbox = getattr(self, "_mailbox", None)
        if mailbox is None or mailbox.loop is not loop:
            mailbox = self._mailbox = _LoopMailbox(loop)

        def on_token(req, token):
            mailbox.post(queue, ("token", token))

        def on_finish(req):
            mailbox.post(queue, ("end", req))

        req = self._loop.submit(ids, max_new, on_token=on_token,
                                on_finish=on_finish, model_id=model_id,
                                slo_class=slo)
        try:
            while True:
                kind, item = await queue.get()
                if kind == "token":
                    yield {"token": item}
                else:
                    if item.error:
                        raise RuntimeError(item.error)
                    yield {"done": True,
                           "ids": list(item.prompt) + list(item.generated)}
                    return
        finally:
            # Client gone mid-stream (the replica's pump was cancelled /
            # the generator closed): abort the engine request so its
            # batch slot and KV blocks go back to live traffic instead
            # of decoding to budget for nobody. No-op when finished.
            self._engine.cancel(req.request_id)

    # ------------------------------------------------------------- control

    def metrics(self, _=None) -> Dict[str, Any]:
        return self._engine.stats()

    def __serve_metrics__(self) -> Dict[str, Any]:
        """Autoscaling signal (replica merges this into its stats): queued
        requests count toward pressure exactly like in-flight ones. For
        multiplexed replicas the resident adapter ids ride along — the
        controller pushes them in the routing table so routers prefer a
        replica that already holds the request's adapter."""
        stats = self._engine.stats()
        out = {"queue_depth": stats["queue_depth"],
               "running": stats["running"],
               "tokens_per_sec": stats["tokens_per_sec"],
               "prefix_hit_rate": stats["prefix_cache"].get("hit_rate", 0.0)}
        adapters = stats.get("adapters")
        if adapters is not None:
            out["adapters"] = adapters["resident"]
        return out

    def __serve_shutdown__(self) -> None:
        self._loop.stop()
