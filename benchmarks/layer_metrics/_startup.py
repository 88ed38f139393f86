"""What the nine start-up readers share: the run's lifecycle spans.

The program keeps a start-up timeline itself (`ray_tpu/observability`:
lifecycle spans, always on, one file a process under
`<session_dir>/lifecycle/`). `view(facts)` finds this run's session
through the program's own `startup_report()` (the process that called
`init()` still knows its last session directory after `shutdown()`),
picks the start-up that led to `facts["setup_end"]` and the process that
holds the chip(s) in it, and cuts that process's compile spans at
`setup_end`. Every stamp is `time.monotonic()`, the clock of the
builders' own marks, so nothing is mapped.

A reader returns None where `facts` holds no `setup_end`, where the
program has no such spans (a parent commit from before them), or where
the span it reads is not there: it never raises.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

_KEY = "_startup_view"
# The last span of its own that the chip-holding process adds to the
# start-up, by root.
_LAST_OWN = {"serve.run": "serve.replica.ctor",
             "serve.deploy": "serve.replica.ctor",
             "train.startup": "train.backend.on_start"}
_COMPILE_KEY = {"jax.trace": "trace_s", "jax.lower": "lower_s"}


def _attrs(span: Dict[str, Any]) -> Dict[str, Any]:
    return span.get("attrs") or {}


def _build(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    setup_end = facts.get("setup_end")
    if setup_end is None:
        return None
    try:
        from ray_tpu.observability import startup_report
    except ImportError:
        return None             # a program from before the spans
    report = startup_report()
    startups = [s for s in report["startups"]
                if s["root"]["start"] <= setup_end]
    if not startups:
        return None
    st = startups[-1]
    root, spans = st["root"], st["spans"]
    # The process that holds the chip(s): the one whose constructor (serve)
    # or whose entry into the train function (train; the latest rank)
    # closed the start-up.
    if root["name"] == "train.startup":
        ends = [s for s in spans if s["name"] == "train.loop.enter"]
    else:
        ends = [s for s in spans if s["name"] == "serve.replica.ctor"]
    if not ends:
        return None
    holder = max(ends, key=lambda s: s["end"])["pid"]
    header = next((p for p in report["processes"]
                   if p.get("pid") == holder), None)
    return {"startup": st, "root": root, "spans": spans, "holder": holder,
            "all_spans": report["spans"], "header": header,
            "setup_end": setup_end}


def view(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if _KEY not in facts:
        try:
            facts[_KEY] = _build(facts)
        except Exception:  # noqa: BLE001 — a reader never raises
            facts[_KEY] = None
    return facts[_KEY]


def holder_spans(v: Dict[str, Any], name: str) -> List[Dict[str, Any]]:
    """The start-up's spans of that name in the chip-holding process."""
    return [s for s in v["spans"]
            if s["name"] == name and s["pid"] == v["holder"]]


def lease_s(facts) -> Optional[float]:
    """Seconds in `raylet.lease` spans on the way to the chip holder: the
    lease that handed it its creation task, and every refused attempt of
    the same actor before it."""
    v = view(facts)
    if v is None:
        return None
    leases = [s for s in v["spans"] if s["name"] == "raylet.lease"]
    granted = [s for s in leases
               if _attrs(s).get("worker_pid") == v["holder"]]
    if not granted:
        return None
    parents = {s.get("parent_id") for s in granted}
    return sum(s["end"] - s["start"] for s in leases
               if s in granted or (s.get("parent_id") in parents
                                   and "worker_pid" not in _attrs(s)))


def spawn_s(facts) -> Optional[float]:
    """`worker.spawn` start -> `worker.boot` end of the chip holder."""
    v = view(facts)
    if v is None:
        return None
    from ray_tpu.observability.startup import spawn_of

    boot = next((s for s in v["all_spans"] if s["name"] == "worker.boot"
                 and s["pid"] == v["holder"]), None)
    if boot is None:
        return None
    return boot["end"] - (spawn_of(v["all_spans"], boot) or boot)["start"]


def backend_s(facts) -> Optional[float]:
    """`jax.claim_devices` (and `jax.distributed` where one was formed)
    in the chip holder; 0 where that process claimed nothing (a CPU
    replica without a grant starts its backend inside its constructor)."""
    v = view(facts)
    if v is None:
        return None
    return sum(s["end"] - s["start"] for s in v["all_spans"]
               if s["pid"] == v["holder"] and s["end"] <= v["setup_end"]
               and s["name"] in ("jax.claim_devices", "jax.distributed"))


def ready_lag_s(facts) -> Optional[float]:
    """End of the chip holder's last start-up span -> the root's end."""
    v = view(facts)
    if v is None:
        return None
    own = holder_spans(v, _LAST_OWN.get(v["root"]["name"], ""))
    if not own:
        return None
    return max(0.0, v["root"]["end"] - max(s["end"] for s in own))


def uncovered_s(facts) -> Optional[float]:
    v = view(facts)
    return None if v is None else v["startup"]["uncovered_s"]


def compile_s(facts, key: str) -> Optional[float]:
    """One of the four compile sums of the chip holder from its first
    line to `setup_end`: its `jax.*` spans that end before it (each
    counted without what nests inside it), plus the events under 10 ms,
    which the process's counter snapshot holds and its spans do not."""
    v = view(facts)
    if v is None or not v["header"]:
        return None
    watch = v["header"].get("compile_watch")
    if not watch or not watch.get("installed"):
        return None
    total = watch[key] - watch["in_spans"][key]
    for s in v["all_spans"]:
        if s["pid"] != v["holder"] or s["end"] > v["setup_end"]:
            continue
        if s["name"] == "jax.compile":
            k = "load_s" if _attrs(s).get("cache") == "hit" else "cold_s"
        else:
            k = _COMPILE_KEY.get(s["name"])
        if k == key:
            total += _attrs(s).get("self_s", s["end"] - s["start"])
    return max(0.0, total)
