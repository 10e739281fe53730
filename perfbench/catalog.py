"""Find a cell, its configuration, its traffic mix and its metrics by name.

Everything the harness runs is named in ``BENCHMARK.json`` at the root of the
checkout and lives in files of its own under ``perfbench/``:

* a configuration is ``perfbench/configs/<config>.json`` (the entry's ``file``);
* a traffic mix is ``perfbench/traffic/<traffic>.json``, and its ``kind`` names a
  generator module ``perfbench/generators/<kind>.py``;
* a metric ``<name>`` is read by ``perfbench/metrics/<name>.py``, or, for a
  quantity split by cells (``device_idle_share.rate``), by the reader of the
  part before the first dot (``metrics/device_idle_share.py``).

A later PR adds a cell, a mix, a generator or a metric by adding files and
entries: nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class CatalogError(Exception):
    pass


@dataclass
class Cell:
    name: str
    config: dict           # the configuration file, as run
    traffic: dict          # the traffic file
    chips: int
    end_to_end: list = field(default_factory=list)  # metric entries this cell reports
    per_layer: list = field(default_factory=list)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise CatalogError(f"no BENCHMARK.json at {root}")
    return load_json(path)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = ROOT, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CatalogError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CatalogError(f"workload {name!r} names unknown config {w['config']!r}")
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_traffic(w["traffic"], root)
    return Cell(
        name=name,
        config=config,
        traffic=traffic,
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def load_traffic(name: str, root: str = ROOT) -> dict:
    path = os.path.join(root, "perfbench", "traffic", f"{name}.json")
    if not os.path.exists(path):
        raise CatalogError(f"no traffic file {path}")
    return load_json(path)


def _load_module(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(kind: str, root: str = ROOT):
    path = os.path.join(root, "perfbench", "generators", f"{kind}.py")
    if not os.path.exists(path):
        raise CatalogError(f"no generator kind {kind!r} ({path})")
    return _load_module(path, f"perfbench_generator_{kind}")


def reader(metric_name: str, root: str = ROOT):
    """The reader module of a metric: ``metrics/<name>.py``, else the reader
    of the quantity before the first dot."""
    base = os.path.join(root, "perfbench", "metrics")
    for stem in (metric_name, metric_name.split(".", 1)[0]):
        path = os.path.join(base, f"{stem}.py")
        if os.path.exists(path):
            return _load_module(path, "perfbench_metric_" + stem.replace(".", "_"))
    raise CatalogError(f"no reader for metric {metric_name!r} under {base}")
