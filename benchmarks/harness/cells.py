"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

A cell names a configuration and a traffic mix.  Everything else hangs off
those two names, as files of their own under the benchmark's directory:

* ``configs/<config>.json``  (the path is ``configs[].file``): the
  deployment; names its ``datagen`` module.
* ``traffic/<traffic>.json``: the mix; names its ``query`` module.
* ``queries/<query>.py``, ``datagen/<source>.py``,
  ``layer_metrics/<metric>.py``: code, one file each.

So a later PR adds a cell by adding files and entries, and edits none that
is there.  ``root`` is the directory that holds ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from types import ModuleType
from typing import Dict, List


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    query: ModuleType
    datagen: ModuleType
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)
    readers: Dict[str, ModuleType] = field(default_factory=dict)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: str) -> ModuleType:
    """Import one file by its path (names with dots in them are fine)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark: no such file: {path}")
    mod_name = "benchfile_" + "".join(
        c if c.isalnum() else "_" for c in os.path.abspath(path))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return module


def _in_cell(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    home = os.path.join(root, bench["paths"][0])
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(home, "traffic",
                                     w["traffic"] + ".json"))
    query = load_module(os.path.join(home, "queries",
                                     traffic["query"] + ".py"))
    datagen = load_module(os.path.join(home, "datagen",
                                       config["datagen"] + ".py"))
    per_layer = [m for m in bench["per_layer"] if _in_cell(m, workload)]
    readers = {m["name"]: load_module(os.path.join(
        home, "layer_metrics", m["name"] + ".py")) for m in per_layer}
    return Cell(name=workload, chips=int(w["chips"]),
                config=config, traffic=traffic, query=query,
                datagen=datagen,
                end_to_end=[m for m in bench["end_to_end"]
                            if _in_cell(m, workload)],
                per_layer=per_layer, readers=readers)
