"""`BENCHMARK.json` keeps to the form the harness and its checkers read:
the keys, the names, the files each entry points to, and that every metric
a cell reports has its reader and every per-layer metric moves an
end-to-end metric that the same cells report."""
import json
import os
import re

import pytest

from bench.spec import BENCH, ROOT, load_cell, reports

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    B = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")
CELLS = [w["name"] for w in B["workloads"]]


def test_top_level():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"][:2] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"] and 1 <= B["run_seconds"] <= 51
    assert len(json.dumps(B)) <= 64 * 1024


def test_configs_and_cells():
    names = {c["name"] for c in B["configs"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["why"])
        assert c["file"].startswith("bench/configs/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in B["workloads"])
    pairs = set()
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert LINE.match(w["why"])
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(B["workloads"]) == len(set(CELLS))


def test_metrics():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and LINE.match(m["layer"])
        for w in m.get("workloads", CELLS):
            assert reports(e2e[m["moves"]], w), (m["name"], w)
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    all_names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(all_names) == len(set(all_names))


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_reports_enough(name):
    cell = load_cell(name)
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert cell.config["limits"]
