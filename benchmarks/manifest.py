"""`BENCHMARK.json`: how the harness finds a cell's files by name, and the
contract the file is held to (`validate`, run by the tier-1 tests).

Whatever belongs to one configuration, one traffic mix or one per-layer
metric sits in a file of its own:

- configuration `<c>`:   the `file` its entry names (sizes, changed and
  assumed keys, engine or trainer settings, `builder`, `reference`);
- traffic mix `<t>`:     `benchmarks/traffic/<t>.json`;
- per-layer metric `<m>`: `benchmarks/layer_metrics/<m>.py` with a
  `read(facts)` that returns a number, or None when there is nothing to
  read (the metric is then left out of the line);
- builder `<b>`:         `benchmarks/builders/<b>.py` with `run(ctx)`.

A later PR adds files and entries; nothing here names a cell.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MAX_RUN_SECONDS = 51
WIDTH_SIZES = ("hidden", "intermediate", "latent", "state", "proj", "head")


def names_a_width(key: str) -> bool:
    """A key `reduced` may never name: a hidden, intermediate, latent,
    state, projection or head SIZE, a `_dim` or `_rank`, an expansion
    factor, or the number of experts per token."""
    return key.endswith(("_dim", "_rank")) \
        or "expansion" in key or "experts_per_tok" in key \
        or (key.endswith(("_size", "_width"))
            and any(w in key for w in WIDTH_SIZES))


def load(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def cell_of(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (has "
                   f"{[c['name'] for c in manifest['workloads']]})")


def config_of(manifest: Dict[str, Any], cell: Dict[str, Any],
              root: str = ROOT) -> Dict[str, Any]:
    for entry in manifest["configs"]:
        if entry["name"] == cell["config"]:
            return _read_json(os.path.join(root, entry["file"]))
    raise KeyError(f"no configuration {cell['config']!r}")


def traffic_of(cell: Dict[str, Any], here: str = HERE) -> Dict[str, Any]:
    return _read_json(os.path.join(here, "traffic",
                                   f"{cell['traffic']}.json"))


def metrics_of(manifest: Dict[str, Any], cell_name: str, kind: str
               ) -> List[Dict[str, Any]]:
    """The `end_to_end` or `per_layer` metrics that `cell_name` reports."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def reader_of(metric_name: str, here: str = HERE):
    """The `read(facts)` of a per-layer metric, from its own file."""
    path = os.path.join(here, "layer_metrics", f"{metric_name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + re.sub(r"\W", "_", metric_name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def builder_of(config: Dict[str, Any]):
    return importlib.import_module(f"benchmarks.builders.{config['builder']}")


def apply_rehearsal(data: Dict[str, Any]) -> Dict[str, Any]:
    """A file's `rehearsal` block laid over it (one level of nesting): the
    tiny sizes of the labelled CPU rehearsal."""
    out = dict(data)
    for key, value in (data.get("rehearsal") or {}).items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = {**out[key], **value}
        else:
            out[key] = value
    return out


# --------------------------------------------------------------------------- #
# the contract
# --------------------------------------------------------------------------- #


def _line_ok(text: Any) -> bool:
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def validate(manifest: Dict[str, Any], root: str = ROOT) -> List[str]:
    """Every breach of the benchmark's contract this file can show
    without a chip. Empty = none found."""
    bad: List[str] = []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != want:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(want)}")
        return bad
    paths = manifest["paths"]
    if not (1 <= len(paths) <= 16) or not all(
            PATH.match(p) and not p.startswith("/") and ".." not in p
            for p in paths):
        bad.append(f"paths {paths}")
    if not (1 <= len(manifest["command"]) <= 32) or not all(
            _line_ok(w) for w in manifest["command"]):
        bad.append("command")
    for word in manifest["command"]:
        if word.startswith("/") or ".." in word.split("/"):
            bad.append(f"command word {word!r} leaves the repo")
        names_a_file = "/" in word or os.path.exists(os.path.join(root, word))
        if names_a_file and not any(
                word == p or word.startswith(p.rstrip("/") + "/")
                for p in paths):
            bad.append(f"command names {word!r} outside paths")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= MAX_RUN_SECONDS):
        bad.append(f"run_seconds {rs}")

    def under_paths(f: str) -> bool:
        return any(f.startswith(p.rstrip("/") + "/") for p in paths)

    def unique(kind: str, entries) -> None:
        names = [e.get("name") for e in entries]
        for n in names:
            if not (isinstance(n, str) and NAME.match(n)):
                bad.append(f"{kind} name {n!r}")
        if len(set(names)) != len(names):
            bad.append(f"duplicate {kind} names")

    configs, cells = manifest["configs"], manifest["workloads"]
    e2e, layers = manifest["end_to_end"], manifest["per_layer"]
    unique("config", configs)
    unique("workload", cells)
    unique("metric", e2e + layers)
    if not 1 <= len(configs) <= 24 or not 1 <= len(cells) <= 24:
        bad.append("1 to 24 configs and workloads")
    if not 1 <= len(e2e) <= 16 or not 1 <= len(layers) <= 128:
        bad.append("1 to 16 end_to_end and 1 to 128 per_layer metrics")
    files = [c.get("file") for c in configs]
    if len(set(files)) != len(files):
        bad.append("two configurations share a file")
    for c in configs:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        if not under_paths(c["file"]) or not os.path.isfile(
                os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: file {c['file']}")
        if not (_line_ok(c["source"]) and _line_ok(c["why"])):
            bad.append(f"config {c['name']}: source/why")
        if len(c["reduced"]) > 16:
            bad.append(f"config {c['name']}: over 16 reduced keys")
        for key in c["reduced"]:
            if not NAME.match(key) or names_a_width(key):
                bad.append(f"config {c['name']}: reduced names {key!r}")
        if not any(w.get("config") == c["name"] for w in cells):
            bad.append(f"config {c['name']} is used by no cell")
    config_names = {c["name"] for c in configs}
    pairs = set()
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        if w["config"] not in config_names:
            bad.append(f"workload {w['name']}: unknown config")
        if not NAME.match(w["traffic"]) or not os.path.isfile(os.path.join(
                root, paths[0], "traffic", f"{w['traffic']}.json")):
            bad.append(f"workload {w['name']}: traffic file")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if not _line_ok(w["why"]):
            bad.append(f"workload {w['name']}: why")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}")
    cell_names = [w["name"] for w in cells]

    def cells_reporting(metric) -> List[str]:
        return list(metric.get("workloads", cell_names))

    for m in e2e:
        extra = set(m) - {"name", "unit", "better", "bound", "source",
                          "workloads"}
        if extra or not {"name", "unit", "better", "bound",
                         "source"} <= set(m):
            bad.append(f"end_to_end {m.get('name')}: keys {sorted(m)}")
            continue
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end_to_end {m['name']}: source {m['source']}")
        if not (isinstance(m["bound"], (int, float))
                and 0.01 <= m["bound"] <= 0.1):
            bad.append(f"end_to_end {m['name']}: bound {m['bound']}")
    for m in layers:
        extra = set(m) - {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if extra or not {"name", "unit", "better", "source", "layer",
                         "moves"} <= set(m):
            bad.append(f"per_layer {m.get('name')}: keys {sorted(m)}")
            continue
        if m["source"] not in SOURCES:
            bad.append(f"per_layer {m['name']}: source {m['source']}")
        if not _line_ok(m["layer"]):
            bad.append(f"per_layer {m['name']}: layer")
        target = [e for e in e2e if e["name"] == m["moves"]]
        if not target:
            bad.append(f"per_layer {m['name']}: moves unknown "
                       f"{m['moves']!r}")
            continue
        missing = set(cells_reporting(m)) - set(cells_reporting(target[0]))
        if missing:
            bad.append(f"per_layer {m['name']}: moves {m['moves']}, which "
                       f"{sorted(missing)} do not report")
        if not os.path.isfile(os.path.join(root, paths[0], "layer_metrics",
                                           f"{m['name']}.py")):
            bad.append(f"per_layer {m['name']}: no reader file")
        if m["name"].endswith("_roofline") and m["unit"] != "%":
            bad.append(f"per_layer {m['name']}: a roofline share is in %")
    for m in e2e + layers:
        if not UNIT.match(str(m.get("unit", ""))):
            bad.append(f"metric {m.get('name')}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m.get('name')}: better")
        for w in m.get("workloads", []):
            if w not in cell_names:
                bad.append(f"metric {m.get('name')}: unknown workload {w}")
    if not any(m.get("name") == "setup_s" and "workloads" not in m
               for m in e2e):
        bad.append("setup_s must be an end_to_end metric of every cell")
    for name in cell_names:
        others = [m for m in metrics_of(manifest, name, "end_to_end")
                  if m["name"] != "setup_s"]
        if not others:
            bad.append(f"workload {name}: no end_to_end metric but setup_s")
        if not metrics_of(manifest, name, "per_layer"):
            bad.append(f"workload {name}: no per_layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        bad.append("file over 64 KiB")
    return bad


def check_budget(manifest: Dict[str, Any], cells: int = 24) -> Optional[str]:
    """A full check of `cells` cells must fit 43200 s."""
    rs = manifest["run_seconds"]
    need = (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200
    return None if need <= 43200 else f"{need} s > 43200 s"
