"""A cell as `BENCHMARK.json` names it: the workload entry, the configuration
file it points to, the traffic file found by the traffic's name
(`bench/traffic/<traffic>.json`) and each metric's reader found by the
metric's name (`bench/metrics/<metric>.py`). A new cell, traffic mix or
metric is a new file and a new entry; nothing here names one."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file as it is run
    traffic_name: str
    traffic: dict         # bench/traffic/<traffic>.json
    per_layer: tuple      # the per-layer metric entries that list this cell
    end_to_end: tuple     # the end-to-end metric entries this cell reports
    root: str = ROOT      # the checkout the files were read from


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def reports(metric: dict, workload: str) -> bool:
    """A metric without a `workloads` list is reported by every cell."""
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=load_json(os.path.join(root, conf["file"])),
        traffic_name=w["traffic"],
        traffic=load_json(os.path.join(root, "bench", "traffic",
                                       w["traffic"] + ".json")),
        per_layer=tuple(m for m in bench["per_layer"] if reports(m, name)),
        end_to_end=tuple(m for m in bench["end_to_end"] if reports(m, name)),
        root=root)


def reader(name: str, root: str = ROOT):
    """The metric's own reader: `read(run)` of bench/metrics/<name>.py,
    which returns the number, or None where the run holds nothing to read."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
