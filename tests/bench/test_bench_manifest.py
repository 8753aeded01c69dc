"""``BENCHMARK.json``: every cell resolves its configuration, workload,
query and metric files by name, and the file keeps to the benchmark's
rules on keys, names, units, sources and bounds."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import manifest as mf  # noqa: E402

M = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in M["workloads"]]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_size():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51


def test_command_and_paths():
    assert 1 <= len(M["command"]) <= 32
    assert (ROOT / M["command"][1]).is_file()
    assert any(M["command"][1].startswith(p + "/") for p in M["paths"])
    for p in M["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") \
            and ".." not in p.split("/")


def test_names_and_units_use_allowed_characters():
    assert mf.check_names(M) == []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in M[group]]
        assert len(names) == len(set(names)), group


def test_configs():
    used = {w["config"] for w in M["workloads"]}
    files = set()
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert len(c["reduced"]) <= 16
        files.add(c["file"])
    assert len(files) == len(M["configs"])


def test_metrics_rules():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert m["better"] in {"lower", "higher"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in M["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["better"] in {"lower", "higher"}
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files(name):
    cell = mf.cell(name, ROOT)
    assert set(cell.entry) == {"name", "config", "traffic", "chips", "why"}
    assert cell.entry["chips"] in (1, 4)
    assert 1 <= len(cell.entry["why"]) <= 200
    assert cell.spec["why"] == cell.entry["why"]
    assert callable(mf.query_module(cell.spec["query"]).Query)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    for m in cell.end_to_end:
        assert callable(mf.metric_module(m["name"]).read)
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in reported
        assert callable(mf.metric_module(m["name"]).read)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        mf.cell("no_such.cell", ROOT)
