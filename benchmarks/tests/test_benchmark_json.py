"""BENCHMARK.json against the limits of the contract that a file can be held
to without a run, and against the files it names."""

import json
import os
import re

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def one_line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_names_and_limits():
    b = load()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert b["paths"] == ["benchmarks"] and len(b["command"]) <= 32
    assert all(one_line(w) for w in b["command"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    cells_n = 24
    assert (2 + 14 * cells_n) * (b["run_seconds"] + 60) + cells_n * 180 \
        + 1200 <= 43200
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("benchmarks/")
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        for key in ("source", "reduced", "assumed", "guarantees", "chips"):
            assert key in conf, (c["name"], key)
        assert set(c["reduced"]) == set(conf["reduced"])
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    configs = {c["name"]: c for c in b["configs"]}
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert one_line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
            assert json.load(f)["chips"] == w["chips"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
    assert {w["config"] for w in b["workloads"]} == set(configs)
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(b["workloads"]) // 2)
    cell_names = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = set()
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert m["name"] not in names
        names.add(m["name"])
        assert set(m.get("workloads", cell_names)) <= cell_names
    layers = set()
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert one_line(m["layer"]) and m["moves"] in e2e
        # the moved metric is reported wherever this one is
        moved = set(e2e[m["moves"]].get("workloads", cell_names))
        assert set(m.get("workloads", cell_names)) <= moved
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
        layers.add(m["layer"])
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(f"**{layer}**" in perf for layer in layers)


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, dirs, files in os.walk(os.path.join(ROOT, "benchmarks")):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), ROOT)
            assert ok.match(rel), rel
