"""BENCHMARK.json and the data files it names, loaded and checked.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name the manifest
gives it:

    benchmark/configs/<config>.json        sizes, source, reduced, assumed,
                                           limits, the builder's name
    benchmark/builders/<builder>.py        build(config, seed, split)
    benchmark/traffic/<traffic>.json       the drive's name + parameters
    benchmark/drives/<drive>.py            warm(...), run(...) -> Window
    benchmark/layer_metrics/<metric>.json  reader name + its arguments
    benchmark/readers/<reader>.py          read(ctx, **args) -> number|None

so a later PR adds a cell, a mix or a metric by adding files and manifest
entries, and edits none.
"""

from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names breaks the contract."""


def _keys(entry: Dict[str, Any], required: set, what: str,
          optional: frozenset = frozenset()) -> None:
    got = set(entry)
    if got - required - optional:
        raise ManifestError(f"{what}: unknown key(s) "
                            f"{sorted(got - required - optional)}")
    if required - got:
        raise ManifestError(f"{what}: missing key(s) "
                            f"{sorted(required - got)}")


def _name(value: Any, what: str) -> str:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise ManifestError(f"{what}: {value!r} is not a name (letters, "
                            "digits, '_', '.', '-'; at most 64)")
    return value


def _metric(entry: Dict[str, Any], keys: set, what: str) -> None:
    _keys(entry, keys, what, optional=frozenset({"workloads"}))
    _name(entry["name"], what)
    if not isinstance(entry["unit"], str) or not UNIT_RE.match(entry["unit"]):
        raise ManifestError(f"{what}: unit {entry['unit']!r} is not 1-16 of "
                            "letters, digits, '_', '/', '%', '.', '-'")
    if entry["better"] not in ("lower", "higher"):
        raise ManifestError(f"{what}: better must be lower or higher")
    if entry["source"] not in SOURCES:
        raise ManifestError(f"{what}: source {entry['source']!r} is not one "
                            f"of {SOURCES}")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _load_json(path: Path, what: str) -> Dict[str, Any]:
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"{what}: {path} does not exist") from None
    if not isinstance(doc, dict):
        raise ManifestError(f"{what}: {path} is not a JSON object")
    return doc


def validate(doc: Dict[str, Any]) -> None:
    """Raise :class:`ManifestError` where ``doc`` breaks the contract's
    shape: keys, names, units, references between entries."""
    _keys(doc, TOP_KEYS, "BENCHMARK.json")
    if not isinstance(doc["run_seconds"], int) \
            or not 1 <= doc["run_seconds"] <= 51:
        raise ManifestError("run_seconds must be a whole number in 1..51")
    configs = {}
    for c in doc["configs"]:
        _keys(c, CONFIG_KEYS, f"config {c.get('name')!r}")
        configs[_name(c["name"], "config name")] = c
        for key in c["reduced"]:
            _name(key, f"config {c['name']}: reduced key")
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in doc["paths"]):
            raise ManifestError(f"config {c['name']}: file {c['file']!r} is "
                                "not under paths")
    if len(configs) != len(doc["configs"]):
        raise ManifestError("two configurations share a name")
    cells = {}
    for w in doc["workloads"]:
        _keys(w, WORKLOAD_KEYS, f"workload {w.get('name')!r}")
        cells[_name(w["name"], "workload name")] = w
        _name(w["traffic"], f"workload {w['name']}: traffic")
        if w["config"] not in configs:
            raise ManifestError(f"workload {w['name']}: unknown config "
                                f"{w['config']!r}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"workload {w['name']}: chips must be 1 or 4")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            raise ManifestError(f"workload {w['name']}: why must be one line "
                                "of 1-200 characters")
    if len(cells) != len(doc["workloads"]):
        raise ManifestError("two workloads share a name")
    pairs = {(w["config"], w["traffic"]) for w in doc["workloads"]}
    if len(pairs) != len(doc["workloads"]):
        raise ManifestError("a pair of configuration and traffic appears "
                            "twice")
    e2e = {}
    for m in doc["end_to_end"]:
        _metric(m, E2E_KEYS, f"end_to_end {m.get('name')!r}")
        if m["source"] not in ("host_clock", "device_trace"):
            raise ManifestError(f"end_to_end {m['name']}: source must be "
                                "host_clock or device_trace")
        if not 0 < m["bound"] <= 0.1:
            raise ManifestError(f"end_to_end {m['name']}: bound must be in "
                                "(0, 0.1]")
        e2e[m["name"]] = m
    if "setup_s" not in e2e:
        raise ManifestError("end_to_end must hold setup_s")
    names = list(e2e)
    for m in doc["per_layer"]:
        _metric(m, LAYER_KEYS, f"per_layer {m.get('name')!r}")
        if m["moves"] not in e2e:
            raise ManifestError(f"per_layer {m['name']}: moves unknown "
                                f"end-to-end metric {m['moves']!r}")
        names.append(m["name"])
    if len(set(names)) != len(names):
        raise ManifestError("two metrics share a name")
    for m in doc["end_to_end"] + doc["per_layer"]:
        for w in m.get("workloads", ()):
            if w not in cells:
                raise ManifestError(f"metric {m['name']}: unknown workload "
                                    f"{w!r}")


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(checkout: Optional[Path] = None) -> Dict[str, Any]:
    doc = _load_json((checkout or CHECKOUT) / "BENCHMARK.json",
                     "BENCHMARK.json")
    validate(doc)
    return doc


def config(doc: Dict[str, Any], name: str,
           checkout: Optional[Path] = None) -> Dict[str, Any]:
    """The configuration ``name`` as its file holds it."""
    try:
        c = next(c for c in doc["configs"] if c["name"] == name)
    except StopIteration:
        raise ManifestError(
            f"no config {name!r} in BENCHMARK.json (known: "
            f"{[c['name'] for c in doc['configs']]})") from None
    return _load_json((checkout or CHECKOUT) / c["file"], f"config {name}")


def cell(doc: Dict[str, Any], name: str,
         checkout: Optional[Path] = None) -> Cell:
    """The cell ``name`` with its configuration, its traffic mix and the
    metrics that apply to it, each read from its own file."""
    checkout = checkout or CHECKOUT
    try:
        w = next(w for w in doc["workloads"] if w["name"] == name)
    except StopIteration:
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json (known: "
            f"{[w['name'] for w in doc['workloads']]})") from None
    c = next(c for c in doc["configs"] if c["name"] == w["config"])
    cfg = config(doc, c["name"], checkout)
    traffic = _load_json(ROOT / "traffic" / f"{w['traffic']}.json",
                         f"traffic {w['traffic']}")
    e2e = [m for m in doc["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in doc["per_layer"]
             if _applies(m, name) and m["moves"] in reported]
    return Cell(name=name, chips=w["chips"], config_name=c["name"],
                traffic_name=w["traffic"], config=cfg, traffic=traffic,
                end_to_end=e2e, per_layer=layer)


def layer_metric_spec(name: str) -> Dict[str, Any]:
    """``{"reader": <module under readers/>, "args": {...}}`` of one
    per-layer metric."""
    spec = _load_json(ROOT / "layer_metrics" / f"{name}.json",
                      f"layer metric {name}")
    _keys(spec, {"reader"}, f"layer metric {name}",
          optional=frozenset({"args", "what"}))
    _name(spec["reader"], f"layer metric {name}: reader")
    return spec
