"""Reduction of a jax profiler trace (`*.xplane.pb`) to the numbers the
per-layer metrics read.

Two steps, so that the arithmetic can be tested without a chip:

1. `load(path)` reads the protobuf with `jax.profiler.ProfileData` into a
   plain structure: `{"planes": [{"name", "lines": [{"name", "events":
   [[name, start_ns, duration_ns, detail], ...]}]}]}`. On the TPU the
   profiler names a device op by its whole HLO text (`%fusion.11 =
   (f32[50304,1024]{...}, ...) fusion(...), kind=kOutput, ...`); `name`
   is the instruction's name (`fusion.11`) and `detail` its result shape
   and opcode (`f32[50304,1024] fusion`). A small recorded trace in this
   form lives beside the tests (`tests/benchmarks/data/`).
2. `digest(trace)` is pure Python over that structure: busy/idle union per
   device, time by XLA module and by op, exposed collective time, and the
   longest idle gaps named by what the host was doing in them.

A TPU device plane is named `/device:TPU:<n>`. Its `XLA Ops` line holds
one event per executed HLO op (kernels carry their stable names:
`flash_fwd`, `flash_bwd_dq`, `flash_bwd_dkv`), its `XLA Modules` line one
event per executed program (`jit_<fn>(<id>)`). A CPU trace has no device
plane: `digest` then returns None and every trace-sourced metric is left
out of the line, never faked.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|send|recv)(-start|-done)?(\.|$)")
# Host-side names the benchmark's own TraceAnnotations carry.
BENCH_SPAN = "bench."
_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<rest>.*)$", re.S)
_SHAPE = re.compile(r"\(?([a-z0-9]+\[[0-9,]*\])")
_OPCODE = re.compile(r"[\s)}]([a-z][a-z0-9\-]*)\(")


def split_hlo(text: str) -> Tuple[str, str]:
    """(`fusion.11`, `f32[50304,1024] fusion`) from an op's HLO text; a
    plain name comes back as it is."""
    m = _HLO.match(text)
    if not m:
        return text.lstrip("%"), ""
    rest = m.group("rest")
    shape = _SHAPE.match(rest)
    opcode = _OPCODE.search(rest)
    return m.group("name"), " ".join(
        x.group(1) for x in (shape, opcode) if x)


Interval = Tuple[int, int]


# --------------------------------------------------------------------------- #
# step 1: protobuf -> plain structure
# --------------------------------------------------------------------------- #


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest `.xplane.pb` under a `jax.profiler.start_trace` directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path: str, host_prefixes: Iterable[str] = (BENCH_SPAN,)) -> Dict:
    """Read an `.xplane.pb` into the plain structure. Device planes are
    kept whole (ops and modules lines); of the host planes only events
    whose name starts with one of `host_prefixes` are kept, which is what
    names an idle gap."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_prefixes = tuple(host_prefixes)
    planes = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                name, detail = ev.name, ""
                if not is_device and not name.startswith(host_prefixes):
                    continue
                if is_device and line.name == OPS_LINE:
                    name, detail = split_hlo(name)
                events.append([name, int(ev.start_ns), int(ev.duration_ns),
                               detail])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def outline(path: str, names: int = 6) -> Dict:
    """What a trace holds, for a reader who has not seen one: planes,
    their lines, event counts, a few names and the stat keys."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            count, seen, stats = 0, [], set()
            for ev in line.events:
                count += 1
                if len(seen) < names and ev.name not in seen:
                    seen.append(ev.name)
                    stats.update(str(k) for k, _ in ev.stats)
            lines.append({"name": line.name, "events": count, "first": seen,
                          "stat_keys": sorted(stats)})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


# --------------------------------------------------------------------------- #
# step 2: arithmetic
# --------------------------------------------------------------------------- #


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[int]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The parts of union(a) that union(b) does not cover."""
    out = []
    cover = union(b)
    j = 0
    for start, end in union(a):
        cur = start
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < end:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def gaps(busy: List[Interval], window: Interval) -> List[Interval]:
    """The idle intervals of `window` given merged busy intervals."""
    return subtract([window], busy)


def strip_id(name: str) -> str:
    """`jit_decode_fn(1234)` -> `jit_decode_fn`; `%fusion.3` -> `fusion.3`."""
    return re.sub(r"\(\d+\)$", "", name).lstrip("%")


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(strip_id(name)))


def _line(plane: Dict, name: str) -> List[List]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _ivals(events: Iterable[List]) -> List[Interval]:
    return [(e[1], e[1] + e[2]) for e in events]


def _by_name(events: Iterable[List], label) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    for e in events:
        rec = out.setdefault(label(e), [0, 0.0])
        rec[0] += 1
        rec[1] += e[2] / 1e9
    return out


def op_label(event: List) -> str:
    """What an op is found by: its HLO name and its detail, `fusion.12 |
    f32[8,1024] fusion`. A kernel's stable name is its HLO name
    (`flash_fwd.3`)."""
    name = strip_id(event[0])
    return f"{name} | {event[3]}" if event[3] else name


def exposed_collective_ns(ops: List[List]) -> int:
    """Device time of collective ops during which no compute op runs on
    that device."""
    coll = [e for e in ops if is_collective(e[0])]
    comp = [e for e in ops if not is_collective(e[0])]
    return total(subtract(_ivals(coll), _ivals(comp)))


def name_gap(gap: Interval, host_events: List[List]) -> str:
    """What the host was doing in an idle gap: the benchmark span that
    overlaps it most (`bench.*` annotations are all the program gives us
    today), else `unattributed`."""
    best, best_overlap = "unattributed", 0
    for name, start, dur, _ in host_events:
        overlap = min(gap[1], start + dur) - max(gap[0], start)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def digest(trace: Dict, top: int = 10) -> Optional[Dict]:
    """Everything the per-layer readers and the `breakdown` need, or None
    when no operation ran on a TPU device in the trace."""
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])
               and _line(p, OPS_LINE)]
    if not devices:
        return None
    host_events = [e for p in trace["planes"]
                   if not DEVICE_PLANE.match(p["name"])
                   for line in p["lines"] for e in line["events"]]
    # One window for all chips: first device op start to last device op
    # end (the harness brackets the traced window with a marker op).
    starts = [e[1] for p in devices for e in _line(p, OPS_LINE)]
    ends = [e[1] + e[2] for p in devices for e in _line(p, OPS_LINE)]
    window = (min(starts), max(ends))
    per_device = []
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, List[float]] = {}
    gap_time: Dict[str, float] = {}
    gap_count: Dict[str, int] = {}
    longest_gap_ns = 0
    for plane in devices:
        op_events = _line(plane, OPS_LINE)
        busy = union(_ivals(op_events))
        idle = gaps(busy, window)
        per_device.append({
            "plane": plane["name"],
            "busy_s": total(busy) / 1e9,
            "exposed_collective_s": exposed_collective_ns(op_events) / 1e9,
            "collective_s": total(union(_ivals(
                e for e in op_events if is_collective(e[0])))) / 1e9,
            "n_ops": len(op_events),
        })
        for name, (n, s) in _by_name(_line(plane, MODULES_LINE),
                                     lambda e: strip_id(e[0])).items():
            rec = modules.setdefault(name, [0, 0.0])
            rec[0] += n
            rec[1] += s
        for name, (n, s) in _by_name(op_events, op_label).items():
            rec = ops.setdefault(name, [0, 0.0])
            rec[0] += n
            rec[1] += s
        for gap in idle:
            what = name_gap(gap, host_events)
            gap_time[what] = gap_time.get(what, 0.0) + (gap[1] - gap[0]) / 1e9
            gap_count[what] = gap_count.get(what, 0) + 1
            longest_gap_ns = max(longest_gap_ns, gap[1] - gap[0])
    n = len(devices)
    # Per-chip averages: a module or op that runs on every chip is
    # counted once per chip above.
    for table in (modules, ops):
        for rec in table.values():
            rec[0] /= n
            rec[1] /= n
    # The breakdown groups ops of one kind (`fusion.12`, `fusion.40`, ...
    # of one result shape) so that ten lines say where a step goes.
    kinds: Dict[str, List[float]] = {}
    for label, (count, seconds) in ops.items():
        name, _, detail = label.partition(" | ")
        rec = kinds.setdefault(
            f"{re.sub(r'[.][0-9]+$', '', name)} {detail}".strip(), [0, 0.0])
        rec[0] += count
        rec[1] += seconds
    device_ops = sorted(
        [[f"module:{k}", v[1]] for k, v in modules.items()]
        + [[f"{k} x{v[0]:g}", v[1]] for k, v in kinds.items()],
        key=lambda kv: -kv[1])[:top]
    idle_gaps = sorted(([k, v / n] for k, v in gap_time.items()),
                       key=lambda kv: -kv[1])[:top]
    return {
        "n_devices": n,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "exposed_collective_s":
            sum(d["exposed_collective_s"] for d in per_device) / n,
        "collective_s": sum(d["collective_s"] for d in per_device) / n,
        "per_device": per_device,
        "modules": modules,          # name -> [executions, seconds] per chip
        "ops": ops,                  # label -> [executions, seconds] per chip
        "idle_gap_counts": gap_count,
        "longest_idle_gap_s": longest_gap_ns / 1e9,
        "breakdown": {"device_ops": device_ops, "idle_gaps": idle_gaps},
    }


def reduce_dir(trace_dir: str, sample_dir: Optional[str] = None,
               span_ns: int = int(0.6e9), max_events: int = 4000
               ) -> Optional[Dict]:
    """The digest of the trace `jax.profiler` left under `trace_dir`, or
    None. With `sample_dir`, also writes there a cut of the trace in the
    plain form the tests read, and its outline."""
    import json

    path = find_xplane(trace_dir)
    if path is None:
        return None
    loaded = load(path)
    if sample_dir is not None:
        with open(os.path.join(sample_dir, "trace_sample.json"), "w") as f:
            json.dump(sample(loaded, span_ns, max_events), f,
                      separators=(",", ":"))
        with open(os.path.join(sample_dir, "trace_outline.json"), "w") as f:
            json.dump(outline(path), f)
    return digest(loaded)


def ops_matching(dig: Dict, pattern: str) -> Tuple[float, float]:
    """(executions, seconds) per chip of the ops whose label matches."""
    rx = re.compile(pattern)
    n = s = 0.0
    for label, (count, seconds) in dig["ops"].items():
        if rx.search(label):
            n += count
            s += seconds
    return n, s


def module_matching(dig: Dict, pattern: str) -> Tuple[float, float]:
    """(executions, seconds) per chip of the XLA modules whose name matches."""
    rx = re.compile(pattern)
    n = s = 0.0
    for name, (count, seconds) in dig["modules"].items():
        if rx.search(name):
            n += count
            s += seconds
    return n, s


def sample(trace: Dict, span_ns: int, max_events: int = 4000) -> Dict:
    """A cut of a trace small enough to keep beside the tests: the events
    that start in the first `span_ns` of the device window."""
    starts = [e[1] for p in trace["planes"] if DEVICE_PLANE.match(p["name"])
              for line in p["lines"] for e in line["events"]]
    if not starts:
        return {"planes": []}
    t0 = min(starts)
    planes = []
    for p in trace["planes"]:
        lines = []
        for line in p["lines"]:
            events = [[e[0], e[1] - t0, e[2], e[3]] for e in line["events"]
                      if 0 <= e[1] - t0 < span_ns][:max_events]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}
