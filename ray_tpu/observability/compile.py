"""What jax spends per program before it can run: trace, lowering, and
backend compile or load from the persistent cache, read from jax's own
`jax.monitoring` events.

jax 0.9 reports the duration of every function's trace
(`/jax/core/compile/jaxpr_trace_duration`), every module's lowering
(`jaxpr_to_mlir_module_duration`) and every backend compile
(`backend_compile_duration`, which a persistent-cache hit also passes
through), each with `fun_name`, and the cache's hits, misses and
retrieval seconds. `install()` (called by `_jax_env.claim_devices()`, the
one place every process that computes passes) listens to them:

- an event of 10 ms or more becomes a lifecycle span (`jax.trace`,
  `jax.lower`, `jax.compile`; the first 256 of a process), so a
  start-up's timeline names the program;
- every event adds to a per-process counter set, `compile_watch()`, which
  is how "which program compiled just now" is answered: `programs` moves,
  and the span names it.

The listeners run where jax traces and compiles, never on a cached call.
Events nest in time (a function traced inside another's trace, an eager
op compiled while a function is traced): each event's seconds are counted
EXCLUSIVE of the events inside it, so the four sums add up to wall time.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.observability import tracing

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

SPAN_MIN_S = 0.010
# A process's `jax.*` spans stop here (half its lifecycle ring), so that a
# process that compiles for hours never pushes its own start-up out of
# the ring; the counters go on counting.
SPAN_CAP = 256
TOP_FUNS = 20
_KIND = {TRACE_EVENT: "trace", LOWER_EVENT: "lower", COMPILE_EVENT: "compile"}

_lock = threading.Lock()
_installed = False


def _sums() -> Dict[str, float]:
    return {"trace_s": 0.0, "lower_s": 0.0, "load_s": 0.0, "cold_s": 0.0}


_totals = _sums()
_counts: Dict[str, int] = {"programs": 0, "hits": 0, "misses": 0,
                           "spans": 0}
# program -> {"trace_s", "lower_s", "load_s", "cold_s", "programs"}; jax
# names a function `f` where it traces it and `jit(f)` where it lowers
# and compiles it: one row, under the bare name.
_by_fun: Dict[str, Dict[str, float]] = {}
# What the spans (events of SPAN_MIN_S or more) hold of the totals: a
# reader adds the rest, `total - in_spans`, to the spans it kept.
_in_spans = _sums()
_local = threading.local()


def _stack() -> List[List[float]]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _on_scalar(event: str, value, **_):
    # jax records the start time of each of the three as a scalar when
    # the timed block is entered: that is where nesting is seen.
    if event in _KIND:
        _stack().append([0.0])


def _on_event(event: str, **_):
    if event == CACHE_HIT_EVENT:
        _local.cache = "hit"
    elif event == CACHE_MISS_EVENT:
        _local.cache = "miss"


def _on_duration(event: str, duration: float, **kw):
    if event == CACHE_RETRIEVAL_EVENT:
        _local.retrieval_s = duration
        return
    kind = _KIND.get(event)
    if kind is None:
        return
    now = time.monotonic()
    stack = _stack()
    inside = stack.pop()[0] if stack else 0.0
    if stack:
        stack[-1][0] += duration
    own = max(0.0, duration - inside)
    fun = str(kw.get("fun_name") or "?")
    attrs: Dict[str, Any] = {"fun_name": fun}
    if own < duration:
        attrs["self_s"] = own
    key = kind + "_s"
    if kind == "compile":
        cache = getattr(_local, "cache", None)
        retrieval_s = getattr(_local, "retrieval_s", None)
        _local.cache = _local.retrieval_s = None
        # Anything that was not served from the cache was compiled.
        key = "load_s" if cache == "hit" else "cold_s"
        attrs["cache"] = cache or "off"
        if retrieval_s is not None:
            attrs["retrieval_s"] = retrieval_s
    with _lock:
        _totals[key] += own
        if kind == "compile":
            _counts["programs"] += 1
            if attrs["cache"] == "hit":
                _counts["hits"] += 1
            elif attrs["cache"] == "miss":
                _counts["misses"] += 1
        program = fun[4:-1] if fun.startswith("jit(") and fun.endswith(
            ")") else fun
        per = _by_fun.get(program)
        if per is None:
            per = _by_fun[program] = {**_sums(), "programs": 0}
        per[key] += own
        if kind == "compile":
            per["programs"] += 1
        as_span = duration >= SPAN_MIN_S and _counts["spans"] < SPAN_CAP
        if as_span:
            _counts["spans"] += 1
            _in_spans[key] += own
    tracing.mark_unwritten()
    if as_span:
        tracing.get_tracer().record_lifecycle(
            "jax." + kind, now - duration, now, attrs=attrs, always=True)


def install() -> bool:
    """Register the listeners, once a process. True when this call did."""
    global _installed
    with _lock:
        if _installed:
            return False
        _installed = True
    import jax

    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    tracing.add_header_source("compile_watch", compile_watch)
    return True


def compile_watch() -> Dict[str, Any]:
    """This process's compile counters since `install()`: seconds spent
    tracing (`trace_s`), lowering (`lower_s`), loading programs the
    persistent cache held (`load_s`: backend-compile time on cache hits)
    and compiling the rest (`cold_s`), the number of `programs` that went
    through the backend with the cache's `hits` and `misses`, the number
    of `spans` recorded, the same by
    program (`fun_name`, without the `jit(...)` jax puts around it from
    lowering on) for the twenty largest, and `in_spans`: the part of each
    sum that the `jax.*` lifecycle spans hold."""
    with _lock:
        by_fun = sorted(
            _by_fun.items(),
            key=lambda kv: -(kv[1]["trace_s"] + kv[1]["lower_s"]
                             + kv[1]["load_s"] + kv[1]["cold_s"]))[:TOP_FUNS]
        return {**_totals, **_counts, "installed": _installed,
                "in_spans": dict(_in_spans),
                "by_fun": {name: dict(per) for name, per in by_fun}}
