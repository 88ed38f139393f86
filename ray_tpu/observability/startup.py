"""Reading a start-up: merge the lifecycle files of one session and say
where the time from `fit()` / `serve.run()` to ready went.

Every process writes its lifecycle spans (`tracing.write_lifecycle`) to
`<session_dir>/lifecycle/`. `startup_report()` merges them, with no
cluster up: it reads files. For a root span (`train.startup`,
`serve.run`) it gives

- each span's **self time**: its duration minus what its children cover;
- the **critical path** to the root's end: walking back from the end,
  the child that finished last (a polling wait yields to what it waited
  for), then into it, then the child that finished last before that one
  began, ... Each step carries the seconds that belong to nobody below
  it;
- **uncovered** time: seconds of the root's interval that no other span
  of any process covers. Time nobody has put a name to.

A wait loop records `polls` / `retries` and `slept_s` on the span it
serves: `slept_s` near the span's self time says the time was spent
asleep between polls, and `slept_s / polls` is the period. All stamps
are `time.monotonic()`: one clock for every process of a host.

    python -m ray_tpu.observability startup [--session DIR] [--json]
"""

from __future__ import annotations

import glob
import json
import os
from typing import Any, Dict, Iterable, List, Optional, Tuple

from ray_tpu.observability import tracing

# `serve.deploy` is a root where nothing caused it: a deployment woken
# from zero replicas by a request.
ROOT_NAMES = ("train.startup", "serve.run", "serve.deploy")
# The mark each rank records when its wrapper is about to call the user's
# train function: `fit()` returns only when training ends, so the train
# root ends at the latest rank's mark.
TRAIN_ENTER = "train.loop.enter"
# A polling wait ends up to one look after what it waits for.
WAIT_SLACK_S = 0.1

Span = Dict[str, Any]
Interval = Tuple[float, float]


def load_session(session_dir: Optional[str] = None) -> Dict[str, Any]:
    """Every process's header and every span of a session directory (by
    default this process's own: kept after `shutdown()`, or the one its
    raylet handed it). Half-written or foreign lines are skipped, never
    raised on."""
    base = session_dir or tracing.session_dir()
    processes: List[Dict[str, Any]] = []
    spans: List[Span] = []
    if base:
        for path in sorted(glob.glob(
                os.path.join(base, "lifecycle", "*.jsonl"))):
            try:
                with open(path, encoding="utf-8") as f:
                    lines = f.read().splitlines()
            except OSError:
                continue
            for line in lines:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if not isinstance(rec, dict):
                    continue
                if rec.get("kind") == "process":
                    processes.append({**rec, "file": path})
                elif "span_id" in rec and "start" in rec and "end" in rec:
                    spans.append(rec)
    return {"session_dir": base, "processes": processes, "spans": spans}


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _covered(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    return sum(b - a for a, b in _union(
        (max(a, lo), min(b, hi)) for a, b in intervals))


def roots_of(spans: List[Span]) -> List[Span]:
    """The start-ups of a session, oldest first."""
    return sorted((s for s in spans
                   if s["name"] in ROOT_NAMES and s.get("startup_id")
                   and not s.get("parent_id")), key=lambda s: s["start"])


def spawn_of(spans: List[Span], boot: Span) -> Optional[Span]:
    """The `worker.spawn` that made the process of a `worker.boot`: both
    carry the worker's id."""
    worker = (boot.get("attrs") or {}).get("worker")
    return next((s for s in spans if s["name"] == "worker.spawn"
                 and (s.get("attrs") or {}).get("worker") == worker), None)


def link_boots(spans: List[Span]) -> None:
    """A `worker.boot` lies beside its `worker.spawn`, under what caused
    both, when the raylet could tell the worker (the registration
    reply); one that was not told is put there by the worker id."""
    for s in spans:
        if s["name"] == "worker.boot" and not s.get("parent_id"):
            spawn = spawn_of(spans, s)
            if spawn is not None:
                s["parent_id"] = spawn.get("parent_id")
                s["startup_id"] = s.get("startup_id") \
                    or spawn.get("startup_id")


def _by_cause(spans: List[Span], root: Span) -> Dict[str, List[Span]]:
    """span id -> the spans it caused; one whose cause is not among
    `spans` hangs under the root."""
    ids = {s["span_id"] for s in spans}
    out: Dict[str, List[Span]] = {}
    for s in spans:
        if s is not root:
            parent = s.get("parent_id")
            out.setdefault(parent if parent in ids else root["span_id"],
                           []).append(s)
    return out


def analyse(spans: List[Span], root: Span) -> Dict[str, Any]:
    """Self times, critical path and uncovered time of one start-up.

    `spans` may hold more than the start-up's own: what shares the root's
    `startup_id` is taken, plus the always-recorded spans (`jax.*`,
    `worker.boot`) of its processes that descend from one of its spans.
    """
    sid = root["startup_id"]
    mine = [s for s in spans if s.get("startup_id") == sid]
    by_id = {s["span_id"]: s for s in mine}
    # Always-recorded spans with no start-up of their own, hanging under
    # one of this start-up's spans (a program compiled inside a ctor).
    grew = True
    while grew:
        grew = False
        for s in spans:
            if s["span_id"] not in by_id and not s.get("startup_id") \
                    and s.get("parent_id") in by_id:
                by_id[s["span_id"]] = s
                mine.append(s)
                grew = True
    root = dict(root)
    marks = [s["end"] for s in mine if s["name"] == TRAIN_ENTER]
    if root["name"] == "train.startup" and marks:
        root["end"] = max(marks)
    by_id[root["span_id"]] = root
    mine = [root if s["span_id"] == root["span_id"] else s for s in mine]
    lo, hi = root["start"], root["end"]

    children = _by_cause(mine, root)

    self_s: Dict[str, float] = {}
    for s in mine:
        a, b = max(s["start"], lo), min(s["end"], hi)
        if s is root:
            a, b = lo, hi
        kids = children.get(s["span_id"], ())
        self_s[s["span_id"]] = max(0.0, (b - a) - _covered(
            ((k["start"], k["end"]) for k in kids), a, b)) if b > a else 0.0

    uncovered = max(0.0, (hi - lo) - _covered(
        ((s["start"], s["end"]) for s in mine if s is not root), lo, hi))

    path: List[Dict[str, Any]] = []

    def walk(span: Span, since: float, until: float):
        # Only [since, until] is this span's to account for: what its
        # parent handed it (a child may begin before its parent did, a
        # boot stamped in the kernel's coarser ticks).
        cursor = min(span["end"], until) if span is not root else until
        floor = max(span["start"], since)
        own = 0.0
        kids = list(children.get(span["span_id"], ()))
        steps: List[Tuple[Span, float]] = []
        while cursor > floor:
            live = [k for k in kids if k["start"] < cursor
                    and k["end"] > floor and k["end"] > k["start"]]
            if not live:
                break
            # The child that finished last; a pure wait (it counts
            # `polls`) yields to what it waited for when both finished
            # within one look of each other.
            latest = max(min(c["end"], cursor) for c in live)
            k = max((c for c in live
                     if min(c["end"], cursor) >= latest - WAIT_SLACK_S),
                    key=lambda c: ("polls" not in (c.get("attrs") or {}),
                                   min(c["end"], cursor), c["start"]))
            k_end = min(k["end"], cursor)
            own += cursor - k_end
            steps.append((k, k_end))
            kids.remove(k)
            cursor = max(k["start"], floor)
        own += max(0.0, cursor - floor)
        for k, k_end in reversed(steps):
            walk(k, floor, k_end)
        path.append({"span_id": span["span_id"], "name": span["name"],
                     "role": span.get("role"), "pid": span.get("pid"),
                     "start": span["start"],
                     "end": min(span["end"], until), "path_s": own,
                     "attrs": span.get("attrs")})

    walk(root, lo, hi)
    path.sort(key=lambda p: (p["start"], -p["end"]))
    return {"root": root, "spans": sorted(mine, key=lambda s: s["start"]),
            "self_s": self_s, "critical_path": path,
            "uncovered_s": uncovered, "duration_s": hi - lo}


def startup_report(session_dir: Optional[str] = None,
                   startup_id: Optional[str] = None) -> Dict[str, Any]:
    """The session's spans merged, and each start-up analysed (or the one
    asked for). Works after `shutdown()` and from any process that can
    read the directory."""
    session = load_session(session_dir)
    spans = session["spans"]
    link_boots(spans)
    startups = [analyse(spans, root) for root in roots_of(spans)
                if startup_id in (None, root["startup_id"])]
    return {**session, "startups": startups}


def format_waterfall(report: Dict[str, Any], width: int = 40) -> str:
    """The start-ups of a report as text: one span a line, indented by
    cause, with its offset from the root's start, its duration, its self
    time, a bar, `*` where it lies on the critical path, and the
    attributes that explain a wait."""
    out: List[str] = []
    if not report["startups"]:
        out.append(f"no start-up recorded under {report['session_dir']!r}")
    for st in report["startups"]:
        root, spans = st["root"], st["spans"]
        total = max(st["duration_s"], 1e-9)
        on_path = {p["span_id"]: p["path_s"] for p in st["critical_path"]}
        kids = _by_cause(spans, root)
        out.append(f"{root['name']}  startup_id={root['startup_id']}  "
                   f"{total:.3f} s  uncovered {st['uncovered_s']:.3f} s  "
                   f"{(root.get('attrs') or {})}")
        out.append(f"  {'offset':>8} {'dur':>8} {'self':>8}  "
                   f"{'':{width}}  span")

        def line(s: Span, depth: int):
            a = max(0.0, s["start"] - root["start"])
            d = s["end"] - s["start"] if s is not root else total
            lo = min(width - 1, int(a / total * width))
            n = max(1, min(width - lo, round(d / total * width)))
            bar = " " * lo + "#" * n
            attrs = {k: v for k, v in (s.get("attrs") or {}).items()
                     if k != "startup"}
            star = "*" if s["span_id"] in on_path else " "
            out.append(
                f"  {a:8.3f} {d:8.3f} {st['self_s'][s['span_id']]:8.3f}  "
                f"{bar:{width}} {star}{'  ' * depth}{s['name']} "
                f"[{s.get('role')} {s.get('pid')}]"
                + (f" {attrs}" if attrs else ""))
            for k in sorted(kids.get(s["span_id"], ()),
                            key=lambda c: c["start"]):
                line(k, depth + 1)

        line(root, 0)
        out.append("  critical path (seconds that belong to no span below):")
        for p in st["critical_path"]:
            if p["path_s"] >= 0.0005:
                out.append(f"    {p['path_s']:8.3f}  {p['name']} "
                           f"[{p['role']} {p['pid']}]")
    dropped = sum(int(p.get("dropped") or 0) for p in report["processes"])
    if dropped:
        out.append(f"note: {dropped} lifecycle span(s) dropped (ring of "
                   f"{tracing.LIFECYCLE._spans.maxlen} a process)")
    return "\n".join(out)
