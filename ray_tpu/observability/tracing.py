"""Distributed tracing: spans, context propagation, flight recorder.

The tracing plane gives the cluster per-request causality that the
aggregate counters in `ray_tpu.util.metrics` cannot: every cross-process
boundary (RPC framing, task specs, actor calls, serve requests, collective
ops, forge spawns, object pulls, inference engine phases) opens a named
span tied to one trace id, and the resulting span trees are exported as
JSON (`/api/traces/<id>`) or a Chrome trace-event timeline
(`/api/timeline`, Perfetto-loadable).

Design constraints (reference `ray/util/tracing/tracing_helper.py`, but
self-contained — no OpenTelemetry dependency):

- **Disabled is near-free.** Every instrumentation site starts with a
  single module-bool guard; when `tracing_enabled` is off, `start_span`
  returns one shared no-op singleton and nothing allocates.
- **Bounded memory.** Spans land in a per-process ring buffer (the
  *flight recorder*): fixed capacity, drop-oldest with a drop counter.
  Spans that recorded an error are kept in a separate small ring so
  drop-oldest under a span storm cannot evict the evidence
  (always-sample-on-error at the buffer level).
- **Head-based sampling.** The sampling decision is made once, where a
  trace is rooted, and travels with the context (`sampled`); sampled-out
  requests return the no-op singleton everywhere downstream.
- **W3C-style propagation.** Context is `{trace_id, span_id, sampled}`;
  HTTP carries it as a `traceparent` header, internal RPC framing as a
  compact `t` envelope key, task specs as `spec.trace_ctx`.

Spans are flushed to the GCS piggybacked on the `MetricsPusher` cadence
(one RPC carries metrics + spans), so tracing adds no new background
threads or connections.

**Lifecycle spans** are the third kind of record (after request spans
and error spans): the start-up path of a `fit()` / `serve.run()` across
driver, GCS, raylet, controller and workers. As error spans are exempt
from sampling and eviction, lifecycle spans are exempt from
`tracing_enabled`: they are few by construction (a site records only
inside a start-up some root opened, or where the event is itself rare:
a worker spawned, a program compiled), live in a small ring of their
own, carry `time.monotonic()` stamps (one clock for every process of a
host) and are written to `<session_dir>/lifecycle/` when a start-up
finishes and at exit. `observability/startup.py` merges and reads them.
"""

from __future__ import annotations

import contextvars
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.core.config import GLOBAL_CONFIG

# --------------------------------------------------------------------- state

# Hot-path guard: instrumentation sites check this module bool before
# doing anything else. Refreshed from GLOBAL_CONFIG by refresh_from_config
# (called from ray_tpu.init / CoreRuntime startup, so workers pick the
# flag up from the propagated RAY_TPU_TRACING_ENABLED env).
_ENABLED: bool = False
_SAMPLE_RATE: float = 1.0

# Maps monotonic timestamps (the engine's Request clock) onto the epoch
# timeline every span uses.
_MONO_OFFSET = time.time() - time.monotonic()

# Process-global current trace context. A ContextVar, not a thread-local:
# async actor methods interleave on one event-loop thread and each asyncio
# task needs its own copy.
_trace_cv: "contextvars.ContextVar[Optional[Dict[str, Any]]]" = \
    contextvars.ContextVar("ray_tpu_trace", default=None)

# Shared singleton for "a context exists but the trace is sampled out":
# wire propagation restores it without allocating per request.
_UNSAMPLED_CTX: Dict[str, Any] = {"sampled": False}


def _rand_hex(nbytes: int) -> str:
    from ray_tpu.core.ids import _random_bytes

    return _random_bytes(nbytes).hex()


def epoch_of(monotonic_ts: float) -> float:
    """Translate a time.monotonic() stamp onto the span epoch timeline."""
    return monotonic_ts + _MONO_OFFSET


# ----------------------------------------------------------- flight recorder


class FlightRecorder:
    """Bounded per-process span buffer: fixed memory, drop-oldest.

    Error spans go to their own small ring so a storm of healthy spans
    cannot evict them before the next flush. All methods are leaf-locked
    (the recorder never calls out while holding its lock), so record()
    is safe from any context, including under control-plane locks.
    """

    ERROR_CAP = 256

    def __init__(self, cap: int = 4096):
        self._lock = threading.Lock()
        self._cap = max(1, int(cap))
        self._spans: deque = deque()
        self._errors: deque = deque()
        self._dropped = 0

    def resize(self, cap: int):
        with self._lock:
            self._cap = max(1, int(cap))
            while len(self._spans) > self._cap:
                self._spans.popleft()
                self._dropped += 1

    def record(self, span: Dict[str, Any]):
        with self._lock:
            if span.get("error") is not None:
                if len(self._errors) >= self.ERROR_CAP:
                    self._errors.popleft()
                    self._dropped += 1
                self._errors.append(span)
                return
            if len(self._spans) >= self._cap:
                self._spans.popleft()
                self._dropped += 1
            self._spans.append(span)

    def drain(self) -> Tuple[List[Dict[str, Any]], int]:
        """Pop every buffered span (errors first) + the drop count since
        the last drain. Called by the MetricsPusher flush."""
        with self._lock:
            spans = list(self._errors) + list(self._spans)
            self._errors.clear()
            self._spans.clear()
            dropped, self._dropped = self._dropped, 0
            return spans, dropped

    def restore(self, spans: List[Dict[str, Any]], dropped: int):
        """Put a failed flush's drained spans (and their drop count)
        back, so a GCS hiccup delays delivery instead of silently losing
        the spans AND the accounting. Still bounded: re-recording runs
        through the normal caps."""
        with self._lock:
            self._dropped += dropped
        for span in spans:
            self.record(span)

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans) + len(self._errors)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"buffered": len(self._spans) + len(self._errors),
                    "cap": self._cap, "dropped": self._dropped}


RECORDER = FlightRecorder(4096)


# -------------------------------------------------------------------- spans


class _NoopSpan:
    """Shared do-nothing span: the disabled/sampled-out path returns this
    exact singleton from every call site — no allocation."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set_attr(self, key: str, value: Any):
        return self

    def end(self, error: Optional[str] = None):
        pass

    @property
    def ctx(self) -> None:
        return None


NOOP_SPAN = _NoopSpan()


class Span:
    """One recorded operation. Use as a context manager; a raised
    exception marks the span errored. Ending restores the previous
    context, so nesting works naturally."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start",
                 "attrs", "error", "_token", "_ctx")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: Optional[str],
                 attrs: Optional[Dict[str, Any]] = None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = time.time()
        self.attrs = dict(attrs) if attrs else None
        self.error: Optional[str] = None
        self._ctx = {"trace_id": trace_id, "span_id": span_id,
                     "sampled": True}
        self._token = _trace_cv.set(self._ctx)

    @property
    def ctx(self) -> Dict[str, Any]:
        """Propagation context for children of this span."""
        return self._ctx

    def set_attr(self, key: str, value: Any) -> "Span":
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value
        return self

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc is not None and self.error is None:
            self.error = f"{exc_type.__name__}: {exc}"
        self.end()
        return False

    def end(self, error: Optional[str] = None):
        if self._token is None:
            return  # already ended (with-block + explicit end)
        if error is not None:
            self.error = error
        try:
            _trace_cv.reset(self._token)
        except ValueError:
            # Ended in a different context than it started (e.g. a span
            # handed across threads): current ctx is not ours to restore.
            pass
        self._token = None
        RECORDER.record({
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start": self.start,
            "end": time.time(),
            "thread": threading.current_thread().name,
            "attrs": self.attrs,
            "error": self.error,
        })


# ---------------------------------------------------------- lifecycle spans

# (startup_id, span_id) of the lifecycle span that is current here: the
# start-up this context takes part in (None outside one, where only the
# rare always-recorded events nest) and the span that causes whatever
# this context submits next. None on every ordinary path.
_startup_cv: "contextvars.ContextVar[Optional[Tuple[Optional[str], str]]]" \
    = contextvars.ContextVar("ray_tpu_startup", default=None)

# What this process is, stamped on the spans it records unless the site
# names a role of its own (the GCS and the raylet of a head node live in
# the driver's process).
_ROLE = "driver"
# Where this process writes its lifecycle file. Set by whoever owns the
# session (the raylet), read from the spawn environment by workers, and
# kept after shutdown() so that the driver can still read its last run.
_SESSION_DIR: Optional[str] = None
_WRITE_LOCK = threading.Lock()
_FILE_TAG: Optional[str] = None
# Something was recorded (or a registered counter moved) since the last
# write-out: the metrics pusher's cadence writes the file again.
_UNWRITTEN = False
# name -> callable returning a JSON-able dict, put in the file's header
# at every write-out (the compile counters register here).
_HEADER_SOURCES: Dict[str, Any] = {}


def set_role(role: str):
    global _ROLE
    _ROLE = role


def role() -> str:
    return _ROLE


def set_session_dir(path: Optional[str]):
    global _SESSION_DIR
    if path:
        _SESSION_DIR = path


def session_dir() -> Optional[str]:
    return _SESSION_DIR or os.environ.get("RAY_TPU_SESSION_DIR") or None


def add_header_source(name: str, fn) -> None:
    _HEADER_SOURCES[name] = fn


def mark_unwritten() -> None:
    """A span was recorded, or a counter that rides in the file's header
    moved."""
    global _UNWRITTEN
    _UNWRITTEN = True


class LifecycleRing:
    """The lifecycle spans of this process: small, bounded, drop-oldest
    with a counter, never drained (a write-out rewrites the whole file,
    so a later one supersedes an earlier one)."""

    def __init__(self, cap: int = 512):
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=max(1, int(cap)))
        self._dropped = 0

    def record(self, span: Dict[str, Any]):
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self._dropped += 1
            self._spans.append(span)

    def snapshot(self) -> Tuple[List[Dict[str, Any]], int]:
        with self._lock:
            return list(self._spans), self._dropped

    def clear(self):
        with self._lock:
            self._spans.clear()
            self._dropped = 0

    def __len__(self) -> int:
        return len(self._spans)


LIFECYCLE = LifecycleRing(512)


def _record_lifecycle(name: str, startup_id: Optional[str], span_id: str,
                      parent_id: Optional[str], start: float, end: float,
                      span_role: Optional[str],
                      attrs: Optional[Dict[str, Any]],
                      error: Optional[str], flush: bool):
    LIFECYCLE.record({
        "name": name, "start": start, "end": end, "span_id": span_id,
        "parent_id": parent_id, "startup_id": startup_id,
        "pid": os.getpid(), "role": span_role or _ROLE,
        "attrs": attrs, "error": error})
    mark_unwritten()
    if _ENABLED:
        # Beside the requests on /api/timeline, on the epoch line.
        RECORDER.record({
            "name": name, "trace_id": startup_id or "lifecycle",
            "span_id": span_id, "parent_id": parent_id,
            "start": epoch_of(start), "end": epoch_of(end),
            "thread": threading.current_thread().name,
            "attrs": {**(attrs or {}), "role": span_role or _ROLE,
                      "lifecycle": True},
            "error": error})
    if flush:
        write_lifecycle()


class LifecycleSpan:
    """One step of a start-up, on `time.monotonic()`. A context manager
    like `Span`; while open it is the parent of every lifecycle span
    opened, and of every task or actor submitted, from its context."""

    __slots__ = ("name", "startup_id", "span_id", "parent_id", "start",
                 "attrs", "role", "error", "_token", "_flush", "_open")

    def __init__(self, name: str, startup_id: Optional[str],
                 parent_id: Optional[str],
                 attrs: Optional[Dict[str, Any]], span_role: Optional[str],
                 flush: bool):
        self.name = name
        self.startup_id = startup_id
        self.span_id = _rand_hex(8)
        self.parent_id = parent_id
        self.attrs = dict(attrs) if attrs else None
        self.role = span_role
        self.error: Optional[str] = None
        self._flush = flush
        self._open = True
        self._token = _startup_cv.set((startup_id, self.span_id))
        self.start = time.monotonic()

    @property
    def ctx(self) -> Tuple[Optional[str], str]:
        """What a child recorded from another thread, loop task or
        process names as its parent."""
        return (self.startup_id, self.span_id)

    def set_attr(self, key: str, value: Any) -> "LifecycleSpan":
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value
        return self

    def __enter__(self) -> "LifecycleSpan":
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc is not None and self.error is None:
            self.error = f"{exc_type.__name__}: {exc}"
        self.end()
        return False

    def end(self, error: Optional[str] = None):
        if not self._open:
            return
        self._open = False
        end = time.monotonic()
        if error is not None:
            self.error = error
        try:
            _startup_cv.reset(self._token)
        except ValueError:
            pass  # ended in another context than it began in
        self._token = None
        _record_lifecycle(self.name, self.startup_id, self.span_id,
                          self.parent_id, self.start, end, self.role,
                          self.attrs, self.error, self._flush)


def _lifecycle_where(ctx: Optional[Tuple[Optional[str], str]],
                     always: bool) -> Optional[Tuple[Optional[str],
                                                     Optional[str]]]:
    """The (startup_id, parent span id) a lifecycle record made here
    carries: those of `ctx`, else of this context, when they belong to a
    start-up; (None, the current span if any) for an event recorded
    `always`; None where nothing is to be recorded."""
    parent = ctx if ctx is not None else _startup_cv.get()
    if parent is not None and parent[0]:
        return parent[0], parent[1]
    if always:
        return None, parent[1] if parent is not None else None
    return None


def startup_ctx() -> Optional[Tuple[Optional[str], str]]:
    """The current lifecycle context when it belongs to a start-up, else
    None: what a site stashes to parent spans it records later from
    another thread or loop task."""
    ctx = _startup_cv.get()
    return ctx if ctx is not None and ctx[0] else None


def leave_startup() -> None:
    """This context outlives the start-up that began it (a control loop
    submitted from inside one): what it does from here on is no part of
    it."""
    _startup_cv.set(None)


def spec_startup_ctx(spec) -> Optional[Tuple[str, str]]:
    """The start-up context a task spec carries, if any."""
    ctx = getattr(spec, "trace_ctx", None)
    st = ctx.get("startup") if ctx else None
    return (st[0], st[1]) if st else None


def write_lifecycle() -> Optional[str]:
    """Write this process's lifecycle ring to
    `<session_dir>/lifecycle/<pid>-<tag>.jsonl`: a header line (process,
    drop counter, the registered counter snapshots) and one span a line.
    The whole file is rewritten through a rename, so a reader never sees
    half of one and a process stopped right after still leaves its last
    complete write. Returns the path, or None with nothing to write or
    nowhere to write it."""
    global _FILE_TAG, _UNWRITTEN
    base = session_dir()
    if not base or not len(LIFECYCLE):
        return None
    with _WRITE_LOCK:
        _UNWRITTEN = False
        spans, dropped = LIFECYCLE.snapshot()
        header: Dict[str, Any] = {
            "kind": "process", "pid": os.getpid(), "role": _ROLE,
            "dropped": dropped, "written_at": time.monotonic(),
            "epoch_offset": _MONO_OFFSET}
        for name, fn in list(_HEADER_SOURCES.items()):
            try:
                header[name] = fn()
            except Exception:  # noqa: BLE001 — a counter must not cost the spans
                header[name] = None
        if _FILE_TAG is None:
            _FILE_TAG = f"{os.getpid()}-{_rand_hex(3)}"
        folder = os.path.join(base, "lifecycle")
        path = os.path.join(folder, _FILE_TAG + ".jsonl")
        try:
            os.makedirs(folder, exist_ok=True)
            with open(path + ".tmp", "w", encoding="utf-8") as f:
                f.write(json.dumps(header, default=str) + "\n")
                for span in spans:
                    f.write(json.dumps(span, default=str) + "\n")
            os.replace(path + ".tmp", path)
        except OSError:
            return None  # the session directory is gone: nothing to keep
        return path


def write_lifecycle_if_unwritten() -> None:
    """The metrics pusher's cadence (util/metrics.py): a process that is
    stopped without a word has still left all but its last two seconds,
    and one that compiled after its start-up closed has said so."""
    if _UNWRITTEN:
        write_lifecycle()


def _forget_lifecycle_for_tests():
    """A fresh ring and file name (tests that count spans or files)."""
    global _FILE_TAG
    LIFECYCLE.clear()
    _FILE_TAG = None


# ------------------------------------------------------------------- tracer


class Tracer:
    """Process-wide span factory. All methods are cheap no-ops while
    tracing is disabled; use :func:`get_tracer` for the singleton."""

    def start_span(self, name: str,
                   attrs: Optional[Dict[str, Any]] = None,
                   child_of: Optional[Dict[str, Any]] = None,
                   ctx: Optional[Dict[str, Any]] = None):
        """Open a span.

        - default: child of the current context; with no current context
          this roots a new trace (head sampling decides here).
        - ``child_of``: explicit parent context (e.g. parsed traceparent).
        - ``ctx``: ADOPT the ids in a pre-minted context (a task spec's
          ``trace_ctx``): the span IS that context's span, so the
          submitter-side ids and the executed span line up.

        Always use as a context manager or end() in a finally block —
        raylint RL008 flags anything else.
        """
        if not _ENABLED:
            return NOOP_SPAN
        if ctx is not None:
            if not ctx.get("sampled"):
                return NOOP_SPAN
            return Span(name, ctx["trace_id"], ctx["span_id"],
                        ctx.get("parent_span_id"), attrs)
        parent = child_of if child_of is not None else _trace_cv.get()
        if parent is None:
            if not self._sample():
                return NOOP_SPAN
            return Span(name, _rand_hex(16), _rand_hex(8), None, attrs)
        if not parent.get("sampled", False):
            return NOOP_SPAN
        return Span(name, parent["trace_id"], _rand_hex(8),
                    parent.get("span_id"), attrs)

    @staticmethod
    def _sample() -> bool:
        if _SAMPLE_RATE >= 1.0:
            return True
        if _SAMPLE_RATE <= 0.0:
            return False
        import random

        return random.random() < _SAMPLE_RATE

    def record_span(self, name: str, start: float, end: float,
                    ctx: Optional[Dict[str, Any]] = None,
                    parent_ctx: Optional[Dict[str, Any]] = None,
                    attrs: Optional[Dict[str, Any]] = None,
                    error: Optional[str] = None,
                    thread: Optional[str] = None):
        """Record a retrospective span from explicit timestamps (epoch
        seconds) — the engine's TTFT decomposition and the raylet's queue
        spans are reconstructed after the fact, not context-managed.

        ``ctx`` adopts ids (span IS the context); ``parent_ctx`` mints a
        fresh child span id under that parent. Unsampled/absent context
        records nothing.
        """
        if not _ENABLED:
            return
        if ctx is not None:
            if not ctx.get("sampled"):
                return
            trace_id, span_id = ctx["trace_id"], ctx["span_id"]
            parent_id = ctx.get("parent_span_id")
        elif parent_ctx is not None:
            if not parent_ctx.get("sampled"):
                return
            trace_id, span_id = parent_ctx["trace_id"], _rand_hex(8)
            parent_id = parent_ctx.get("span_id")
        else:
            return
        RECORDER.record({
            "name": name, "trace_id": trace_id, "span_id": span_id,
            "parent_id": parent_id, "start": start, "end": end,
            "thread": thread or threading.current_thread().name,
            "attrs": dict(attrs) if attrs else None, "error": error,
        })

    # ------------------------------------------------------ lifecycle spans

    def lifecycle_span(self, name: str,
                       attrs: Optional[Dict[str, Any]] = None, *,
                       root: bool = False,
                       ctx: Optional[Tuple[Optional[str], str]] = None,
                       always: bool = False, role: Optional[str] = None,
                       flush: bool = False):
        """Open a lifecycle span: recorded whatever `tracing_enabled`
        says, but only inside a start-up.

        - ``root``: this IS a start-up (`fit()`, `serve.run()`): mints
          the `startup_id` every span it causes will share, and writes
          this process's file when it closes. Inside another start-up a
          root is an ordinary child of it.
        - ``ctx``: explicit ``(startup_id, parent span id)``, for a site
          that got it from a task spec or stashed it earlier.
        - ``always``: the event is rare in itself (a worker spawned, a
          backend started): recorded outside a start-up too, with no
          `startup_id`.
        - ``flush``: the last thing this process adds to the start-up:
          write the file when it closes.

        Anywhere else this returns the no-op singleton.
        """
        where = _lifecycle_where(ctx, always)
        if root and (where is None or where[0] is None):
            where = (_rand_hex(8), None)
        if where is None:
            return NOOP_SPAN
        return LifecycleSpan(name, where[0], where[1], attrs, role,
                             flush or root)

    def record_lifecycle(self, name: str, start: float, end: float, *,
                         ctx: Optional[Tuple[Optional[str], str]] = None,
                         attrs: Optional[Dict[str, Any]] = None,
                         always: bool = False, role: Optional[str] = None,
                         span_id: Optional[str] = None,
                         error: Optional[str] = None,
                         flush: bool = False) -> Optional[str]:
        """Record a lifecycle span whose ends (`time.monotonic()`) are
        only known after the fact: a wait that turned out to belong to a
        start-up, a process's boot. Same rules as `lifecycle_span`;
        returns the span's id, or None when nothing was recorded."""
        where = _lifecycle_where(ctx, always)
        if where is None:
            return None
        span_id = span_id or _rand_hex(8)
        _record_lifecycle(name, where[0], span_id, where[1], start, end,
                          role, dict(attrs) if attrs else None, error,
                          flush)
        return span_id

    def lifecycle_mark(self, name: str,
                       attrs: Optional[Dict[str, Any]] = None, *,
                       flush: bool = True) -> Optional[str]:
        """An instant of a start-up (a rank reaching its train function):
        a zero-length lifecycle span, written out at once."""
        now = time.monotonic()
        return self.record_lifecycle(name, now, now, attrs=attrs,
                                     flush=flush)


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def enabled() -> bool:
    return _ENABLED


def refresh_from_config():
    """Re-read the tracing flags (called at runtime startup; workers see
    the driver's _system_config through the propagated env)."""
    global _ENABLED, _SAMPLE_RATE
    _ENABLED = bool(GLOBAL_CONFIG.tracing_enabled)
    _SAMPLE_RATE = float(GLOBAL_CONFIG.trace_sample_rate)
    RECORDER.resize(GLOBAL_CONFIG.trace_buffer_spans)


# ------------------------------------------------------ context propagation


def capture() -> Optional[Dict[str, Any]]:
    """Current trace context (None when disabled or no trace active) —
    stash it to re-enter the trace from another thread/queue."""
    if not _ENABLED:
        return None
    return _trace_cv.get()


def set_current(ctx: Optional[Dict[str, Any]]):
    """Install `ctx` as the current trace context (a task spec's
    trace_ctx, or a captured context crossing a thread boundary). A spec
    submitted from inside a start-up carries it (`startup`), and what
    runs the spec is part of that start-up."""
    _trace_cv.set(ctx)
    st = ctx.get("startup") if ctx else None
    if st is not None or _startup_cv.get() is not None:
        _startup_cv.set((st[0], st[1]) if st else None)


def current_ctx() -> Optional[Dict[str, Any]]:
    return _trace_cv.get()


def child_spec_ctx() -> Dict[str, str]:
    """A fresh propagation context for a task spec being submitted from
    the current context: same trace (or a new sampled-or-not root), the
    current span as parent. Always returns ids — task events use them
    for timeline grouping even with tracing off."""
    span_id = _rand_hex(8)
    cur = _trace_cv.get()
    if cur and cur.get("trace_id"):
        ctx = {"trace_id": cur["trace_id"], "span_id": span_id,
               "parent_span_id": cur.get("span_id"),
               "sampled": bool(cur.get("sampled"))}
    else:
        ctx = {"trace_id": _rand_hex(16), "span_id": span_id,
               "parent_span_id": None,
               "sampled": bool(_ENABLED and Tracer._sample())}
    startup = _startup_cv.get()
    if startup is not None and startup[0]:
        ctx["startup"] = startup
    return ctx


# Wire form on RPC envelopes: key "t" is [trace_id, span_id] for a sampled
# context, or the int 0 for "context present but sampled out" (so the far
# side suppresses head sampling instead of re-rolling mid-trace).


def wire_ctx():
    """Compact trace context for the RPC envelope, or None."""
    ctx = _trace_cv.get()
    if ctx is None:
        return None
    if not ctx.get("sampled"):
        return 0
    return [ctx["trace_id"], ctx["span_id"]]


def activate(ctx: Optional[Dict[str, Any]]) -> "contextvars.Token":
    """Install `ctx` and return the token for :func:`deactivate` — for
    carrying a captured context across an executor/thread boundary."""
    return _trace_cv.set(ctx)


def activate_wire(t) -> "contextvars.Token":
    """Server side: install the envelope's wire context; returns the
    token for :func:`deactivate`."""
    if t == 0 or not isinstance(t, (list, tuple)) or len(t) < 2:
        return _trace_cv.set(_UNSAMPLED_CTX)
    return _trace_cv.set({"trace_id": t[0], "span_id": t[1],
                          "sampled": True})


def deactivate(token: "contextvars.Token"):
    try:
        _trace_cv.reset(token)
    except ValueError:
        pass


# --------------------------------------------------------- W3C traceparent


def parse_traceparent(header: Optional[str]) -> Optional[Dict[str, Any]]:
    """``00-<32 hex trace>-<16 hex span>-<2 hex flags>`` -> context dict
    (flags bit 0 = sampled), or None if malformed."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
        return None
    try:
        flags = int(parts[3], 16)
        int(parts[1], 16)
        int(parts[2], 16)
    except ValueError:
        return None
    return {"trace_id": parts[1], "span_id": parts[2],
            "sampled": bool(flags & 1)}


def format_traceparent(ctx: Optional[Dict[str, Any]] = None
                       ) -> Optional[str]:
    """Render the current (or given) context as a traceparent header."""
    ctx = ctx if ctx is not None else _trace_cv.get()
    if not ctx or not ctx.get("trace_id"):
        return None
    flags = "01" if ctx.get("sampled") else "00"
    trace = ctx["trace_id"].ljust(32, "0")[:32]
    span = ctx["span_id"].ljust(16, "0")[:16]
    return f"00-{trace}-{span}-{flags}"


# ------------------------------------------------------------------- flush


def drain_for_flush() -> Tuple[List[Dict[str, Any]], int]:
    """(spans, dropped) since the last flush; empty when disabled (the
    recorder may still hold spans from a just-disabled session — drain
    them so memory is released)."""
    if not _ENABLED and not len(RECORDER):
        return [], 0
    return RECORDER.drain()


# A process that ends normally leaves its last spans behind; one that is
# stopped by a signal writes from its handler (core/worker.py).
import atexit as _atexit  # noqa: E402

_atexit.register(write_lifecycle)
