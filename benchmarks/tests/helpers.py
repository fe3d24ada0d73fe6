"""A temporary benchmark root: a copy of ``benchmarks/`` and of
``BENCHMARK.json`` to which a test ADDS files and entries, editing none
that is there, the way a later PR would."""

import json
import os
import shutil

from conftest import ROOT

TINY_SF = 0.02   # 30,000 orders, about 120,000 lines


def copy_root(tmp_path) -> str:
    root = str(tmp_path / "root")
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
        ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def add_entries(root: str, **lists) -> None:
    """Append entries to the lists of the root's ``BENCHMARK.json``."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for key, entries in lists.items():
        bench[key].extend(entries)
    with open(path, "w") as f:
        json.dump(bench, f)


def add_tiny_config(root: str, name: str, partitions: int):
    """A new configuration file: ``tpch_sf5_1chip`` at a scale the CPU can
    run; with four partitions, the four-chip deployment kept for later
    (``PERF.md``, Open questions, cells, row 0): the ICI transport, and a
    plan that must hold ``IciAggregateExec`` over four devices."""
    with open(os.path.join(root, "benchmarks", "configs",
                           "tpch_sf5_1chip.json")) as f:
        config = json.load(f)
    config.update(name=name, scale_factor=TINY_SF, chips=partitions,
                  num_partitions=partitions)
    if partitions > 1:
        config["session_conf"]["spark.rapids.shuffle.transport"] = "ici"
        config["guarantees"]["plan_must_hold"] = {"q18sub": [
            {"exec": "IciAggregateExec",
             "stage_input_devices": partitions}]}
    rel = f"benchmarks/configs/{name}.json"
    with open(os.path.join(root, rel), "w") as f:
        json.dump(config, f)
    return {"name": name, "source": "test", "file": rel, "reduced": [],
            "why": "tiny scale for the CPU"}


class FakeDevice:
    """Stands where a chip would: the CPU backend reports no memory."""
    platform = "cpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 123}
