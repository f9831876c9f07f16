"""Every name in BENCHMARK.json resolves to its own file, the names and
units keep to the contract's characters, and a cell added as new files
is found without an edit to any existing file."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import run as R

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    res = R.resolve(cell, ROOT)
    assert res["driver"].is_file()
    for path in res["metric_files"].values():
        assert path.is_file()
    assert res["end_to_end"] and res["per_layer"]
    assert "setup_s" in {m["name"] for m in res["end_to_end"]}
    driver = R.import_file(res["driver"], "driver_under_test")
    assert driver.RATE_METRIC in {m["name"] for m in res["end_to_end"]}
    moved = {m["moves"] for m in res["per_layer"]}
    assert moved <= {m["name"] for m in res["end_to_end"]}


def test_names_units_and_entry_keys():
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        names.append(c["name"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for n in names:
        assert NAME.match(n), n
    assert len(SPEC["configs"]) == len({c["name"] for c in SPEC["configs"]})
    assert len(CELLS) == len(set(CELLS))
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(CELLS) // 2)


def test_new_cell_is_found_from_new_files_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    traffic = json.loads(
        (ROOT / "bench/traffic/websearch-ladder.json").read_text())
    traffic["workload"] = "datamining"
    (tmp_path / "bench/traffic/datamining-ladder.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/limits/flows-648-datamining.json").write_text(
        (ROOT / "bench/limits/flows-648-websearch.json").read_text())
    spec["workloads"].append(dict(
        name="flows-648-datamining", config="opera-648",
        traffic="datamining-ladder", chips=1, why="a cell added as files"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "flows-648-websearch" in m.get("workloads", []):
            m["workloads"].append("flows-648-datamining")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    res = R.resolve("flows-648-datamining", tmp_path)
    assert res["traffic"]["workload"] == "datamining"
    assert res["driver"] == tmp_path / "bench/drivers/flows.py"
    assert {m["name"] for m in res["per_layer"]} == {
        m["name"] for m in R.resolve("flows-648-websearch",
                                     ROOT)["per_layer"]}


def test_unknown_cell_is_refused():
    with pytest.raises(R.SpecError, match="no workload"):
        R.resolve("no-such-cell", ROOT)
