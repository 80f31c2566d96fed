"""BENCHMARK.json keeps to the contract, and every piece is found by name."""
import json
import re
import shutil

import pytest

from bench import harness
from bench.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_entries_and_names():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for section, want in keys.items():
        names = [e["name"] for e in BENCH[section]]
        assert len(names) == len(set(names)), section
        for e in BENCH[section]:
            assert set(e) - {"workloads"} == want, (section, e["name"])
            assert NAME.match(e["name"]), e["name"]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_reports_enough():
    e2e = BENCH["end_to_end"]
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = harness.load_cell(w["name"], ROOT)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]
    for m in BENCH["per_layer"]:
        moved = [x for x in e2e if x["name"] == m["moves"]]
        assert moved, m["name"]
        for cell in m.get("workloads", []):
            assert cell in moved[0].get("workloads", [cell]), m["name"]


def test_pieces_found_by_name():
    used = set()
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"], ROOT)
        used.add(w["config"])
        assert hasattr(cell.driver(), "build")
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.metric_reader(cell, m["name"]))
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        path = ROOT / c["file"]
        assert path.is_relative_to(ROOT / "bench")
        assert json.loads(path.read_text())["reduced"] == c["reduced"]


def test_unknown_cell_is_refused():
    with pytest.raises(harness.SpecError):
        harness.load_cell("no_such_cell", ROOT)


def test_new_cell_and_metric_need_no_edit(tmp_path):
    """A later PR adds a cell and a metric with new files and entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic" / "audit_a1.json").write_text(
        json.dumps({"alpha": 1.0, "sets": 2}))
    (tmp_path / "bench" / "metrics" / "audits_done.py").write_text(
        "def read(run):\n    return float(len(run.latencies))\n")
    bench["workloads"].append({"name": "static_a1", "config":
                               "paper_static_1d", "traffic": "audit_a1",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "static_a100" in m.get("workloads", []):
            m["workloads"].append("static_a1")
    bench["per_layer"].append({
        "name": "audits_done", "unit": "1", "better": "higher",
        "source": "host_clock", "layer": "device", "moves": "audit_s",
        "workloads": ["static_a1"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("static_a1", tmp_path)
    assert cell.traffic["alpha"] == 1.0
    assert cell.config["name"] == "paper_static_1d"
    assert [m["name"] for m in cell.per_layer] == ["audits_done"]
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "audit_s"}
    run = harness.Run(1.0, 1.0, [0.1, 0.2], [], {}, None, {})
    assert harness.metric_reader(cell, "audits_done")(run) == 2.0
