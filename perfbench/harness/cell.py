"""Finding what belongs to one cell, by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Everything else is found by name, so that a later change adds a cell, a
configuration, a traffic mix or a per-layer metric by adding files:

* the configuration: the ``file`` its entry in ``configs`` names; its
  ``family`` key picks ``models/<family>.py`` (weights, inputs, the plain
  reference and the operation counts);
* the traffic mix: ``traffic/<traffic>.json``; its ``kind`` key picks
  ``drivers/<kind>.py``, the generator and driver of that kind;
* the limits of the correctness check: ``limits/<workload>.json``;
* each per-layer metric: ``metrics/<metric name>.py`` with ``read(run)``;
  where that file is missing, the name without its last ``.`` part, so
  that one reader serves a quantity split by the end-to-end metric it
  moves (``device_idle_share.serve``, ``device_idle_share.train``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list   # the end_to_end entries this cell reports
    per_layer: list    # the per_layer entries this cell reports

    def driver(self):
        kind = self.traffic["kind"]
        return load_module(os.path.join(BENCH_DIR, "drivers", f"{kind}.py"),
                           f"perfbench_driver_{kind}")

    def model(self):
        fam = self.config["family"]
        return load_module(os.path.join(BENCH_DIR, "models", f"{fam}.py"),
                           f"perfbench_model_{fam}")

    def reader(self, metric: str):
        name = metric
        if not os.path.exists(os.path.join(BENCH_DIR, "metrics", f"{name}.py")):
            name = metric.rpartition(".")[0] or metric
        return load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"),
                           f"perfbench_metric_{name}").read


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load(workload: str, bench_path: str | None = None) -> Cell:
    bench = _load_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in names and _reports(m, workload)]
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=_load_json(os.path.join(ROOT, conf["file"])),
        traffic=_load_json(os.path.join(BENCH_DIR, "traffic", f"{w['traffic']}.json")),
        limits=_load_json(os.path.join(BENCH_DIR, "limits", f"{workload}.json")),
        end_to_end=e2e,
        per_layer=per_layer,
    )
