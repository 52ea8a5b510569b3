"""A cell as data, found by name.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to it sits in files of its own:

* ``bench/configs/<config>.json`` — the configuration as it is run, with
  the named scopes inside its model under ``"scopes"`` where it has any
  (``scopes.scope_of``);
* ``bench/configs/<config>.py``   — its model pieces, data maker, plain
  reference and per-round operation counts;
* ``bench/traffic/<traffic>.json`` — the traffic mix (population,
  availability, budget, chunk), read by the one generator in ``build.py``;
* ``bench/limits/<workload>.json`` — the limits of the comparison that
  decides ``correct``;
* ``bench/metrics/<metric>.py`` — one reader per metric.

So a later change adds a configuration, a mix, a cell or a metric by
adding files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    module: object
    limits: dict
    end_to_end: tuple = ()      # metric entries this cell reports, trace 0
    per_layer: tuple = ()       # metric entries this cell reports, trace 1

    @property
    def n_clients(self) -> int:
        """Population: the traffic may scale the configuration's dataset."""
        return int(self.traffic.get("n_clients", self.config["n_clients"]))

    @property
    def k(self) -> int:
        return int(self.traffic["budget_kwargs"]["k"])

    @property
    def chunk_size(self) -> int:
        return int(self.traffic["chunk_size"])


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return _json(path)


def _reports(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", (workload,))


def load_cell(workload: str, benchmark: dict | None = None) -> Cell:
    bench = benchmark or load_benchmark()
    entries = [w for w in bench["workloads"] if w["name"] == workload]
    if not entries:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    entry = entries[0]
    cfg_name = entry["config"]
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=_json(BENCH / "configs" / f"{cfg_name}.json"),
        traffic=_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        module=importlib.import_module(f"bench.configs.{cfg_name}"),
        limits=_json(BENCH / "limits" / f"{workload}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _reports(m, workload)))
