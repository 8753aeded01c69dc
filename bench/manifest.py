"""``BENCHMARK.json`` and the files it names, found by name.

* ``bench/configs/<config>.json``    one deployment: source, family, engine
                                     settings, ``reduced``, ``assumed``;
* ``bench/workloads/<cell>.json``    one cell: config, query kind, sizes,
                                     batch, chips, why;
* ``bench/queries/<kind>.py``        traffic, driver, reference, work count;
* ``bench/metrics/<metric>.py``      one metric's reader, end-to-end or
                                     per-layer.

A later cell, configuration, query kind or metric is a new file and a new
entry, never an edit of one that is there.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Dict, List, NamedTuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class Cell(NamedTuple):
    name: str
    entry: dict          # the cell's entry in BENCHMARK.json
    spec: dict           # bench/workloads/<cell>.json
    config: dict         # bench/configs/<config>.json
    end_to_end: List[dict]
    per_layer: List[dict]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its files and the metrics it reports."""
    m = manifest(root)
    entries = {w["name"]: w for w in m["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(entries)}")
    entry = entries[name]
    spec = load_json(BENCH / "workloads" / f"{name}.json")
    config = load_json(BENCH / "configs" / f"{entry['config']}.json")
    if spec["config"] != entry["config"] or spec["chips"] != entry["chips"]:
        raise ValueError(f"{name}: workload file and BENCHMARK.json "
                         f"disagree on config or chips")
    return Cell(name, entry, spec, config,
                [x for x in m["end_to_end"] if _reports(x, name)],
                [x for x in m["per_layer"] if _reports(x, name)])


def _module(kind: str, name: str) -> ModuleType:
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def query_module(kind: str) -> ModuleType:
    return _module("queries", kind)


def metric_module(name: str) -> ModuleType:
    return _module("metrics", name)


def check_names(m: Dict) -> List[str]:
    """Every name and unit of ``m`` that breaks the character rules."""
    bad = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for x in m[group]:
            if not NAME.match(x["name"]):
                bad.append(f"{group}: name {x['name']!r}")
            if "unit" in x and not UNIT.match(x["unit"]):
                bad.append(f"{group}: unit {x['unit']!r}")
    for w in m["workloads"]:
        for key in ("config", "traffic"):
            if not NAME.match(w[key]):
                bad.append(f"workloads: {key} {w[key]!r}")
    for c in m["configs"]:
        bad += [f"configs: reduced {k!r}" for k in c["reduced"]
                if not NAME.match(k)]
    return bad
