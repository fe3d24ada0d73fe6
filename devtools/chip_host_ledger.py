#!/usr/bin/env python3
"""The host ledger on the chip: what a cell's queries cost the host by
segment, and what the profiler sink costs when it is on.

    python devtools/chip_host_ledger.py segments --workload <cell> --seed <n>
    python devtools/chip_host_ledger.py onoff --workload <cell> --seed <n> \
        [--window 15] [--plan off,on,off,on] [--root .chip_parent] [--conf key=value ...]

``segments`` is one traced run of the cell through the benchmark's own
``runner.run_cell`` (the result line comes out as it does from
``benchmarks/run.py --trace 1``), then the ledger's records of the window:
the median of every segment and span, whether each record's segments sum
to its wall to the nanosecond, whether the ids rise by one a query.

``onoff`` loads the cell once, warms it up (the warm-up's answer is held
to the reference), then asks queries through ``Bench.ask`` in windows that
alternate ``spark.rapids.sql.profile.traceAnnotations`` off and on (or
follow ``--plan``), with no profiler session: the sink's cost on one table
in one process.  Hold the off path of two checkouts against each other
with ``--plan off,off``: a window that follows one with the sink on runs
with the ledger's records in the heap.  The answers of these windows are
not held to the reference (a `.q1` check is two minutes of NumPy); a query
that raises or breaks the plan's guarantees still fails the run.  ``--root`` runs another checkout's program and
benchmark (the parent's, unpacked from ``git archive``) with this script;
``--conf`` adds session settings for this run alone (ROADMAP A8's A/B).

One process, chip only.  The last line of standard output is one JSON
object; ``chiprun_out/host_ledger/`` keeps a copy.
"""

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _value(text: str):
    return {"true": True, "false": False}.get(text.lower(), text)


def _keep(name: str, doc: dict) -> None:
    out = os.path.join(HERE, "chiprun_out", "host_ledger")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, name + ".json"), "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc), flush=True)


def _ms(ns_values) -> float:
    return statistics.median(ns_values) / 1e6


def segments(args, cell, devices, runner) -> dict:
    from spark_rapids_tpu.obs import tracer
    line = runner.run_cell(cell, args.seed, args.seconds, True,
                           time.perf_counter(), devices)
    records = tracer.host_ledger().records()
    asked = line["attempted"]
    window = records[-asked:]
    seg_names = sorted({s for r in window for s in r["segments"]})
    span_names = sorted({s for r in window for s in r["spans"]})
    ids = [int(r["id"][1:]) for r in records]
    walls = [r["wall_ns"] for r in window]
    return {
        "workload": cell.name, "seed": args.seed, "line": line,
        "records": len(records), "window_records": len(window),
        "ids_rise_by_one": ids == list(range(ids[0], ids[0] + len(ids))),
        "first_id": records[0]["id"], "last_id": records[-1]["id"],
        "segments_sum_to_wall": all(
            sum(r["segments"].values()) == r["wall_ns"] for r in records),
        "wall_ms_median": _ms(walls),
        "segment_ms_median": {
            s: _ms([r["segments"].get(s, 0) for r in window])
            for s in seg_names},
        "span_median": {
            s: {"count": statistics.median(
                    [r["spans"].get(s, (0, 0))[0] for r in window]),
                "inclusive_ms": _ms(
                    [r["spans"].get(s, (0, 0))[1] for r in window])}
            for s in span_names},
        "off_thread_ms_total": sum(
            r["off_thread_ns"] for r in window) / 1e6,
    }


def onoff(args, cell, devices, runner) -> dict:
    from spark_rapids_tpu.obs import tracer
    for pair in args.conf:
        key, _, text = pair.partition("=")
        cell.config.setdefault("session_conf", {})[key] = _value(text)
    bench = runner.Bench(cell, args.seed, trace=False)
    bench.load()
    bench.warm_up(devices)
    if bench.problems:
        raise SystemExit("the warm-up failed: " + bench.problems[0])
    windows = []
    plan = args.plan.split(",") if args.plan else \
        (["off", "on"] if args.seed % 2 == 0 else ["on", "off"]) * 2
    for step in plan:
        if step == "forget":
            # drop the ledger's records: does a heap that holds them slow
            # the windows that follow?
            import gc
            tracer.host_ledger()._ring.clear()
            gc.collect()
            continue
        sink_on = {"off": False, "on": True}[step]
        tracer.set_trace_annotations(sink_on)
        times = []
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < args.window:
            q = bench.ask(next(bench.stream))
            if q.error:
                raise SystemExit("a query failed: " + q.error)
            times.append(q.ms)
        windows.append({
            "sink_on": sink_on, "queries": len(times),
            "answer_ms_p50": statistics.median(times),
            "queries_per_s": len(times)
            / (time.perf_counter() - t_open)})
    tracer.set_trace_annotations(False)
    out = {"workload": cell.name, "seed": args.seed, "root": args.root,
           "conf": args.conf, "windows": windows}
    for sink_on, key in ((False, "off"), (True, "on")):
        of_kind = [w["answer_ms_p50"] for w in windows
                   if w["sink_on"] == sink_on]
        if of_kind:
            out[f"answer_ms_p50_{key}"] = statistics.median(of_kind)
    ledger = getattr(tracer, "host_ledger", None)
    if ledger is not None and ledger().records():
        records = ledger().records()
        out["segment_ms_median_on"] = {
            s: _ms([r["segments"].get(s, 0) for r in records])
            for s in sorted({s for r in records for s in r["segments"]})}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("segments", "onoff"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--window", type=float, default=15.0)
    ap.add_argument("--plan", default="",
                    help="the windows, in order: off,on,...; 'forget' "
                         "drops the ledger's records between two")
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--conf", action="append", default=[])
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)
    args.root = os.path.abspath(args.root)
    sys.path.insert(0, args.root)
    from benchmarks.harness import cells, device, runner
    cell = cells.load_cell(args.root, args.workload)
    devices = device.require_tpu(cell.chips)
    import spark_rapids_tpu  # noqa: F401  (turns 64-bit lanes on)
    doc = {"segments": segments, "onoff": onoff}[args.mode](
        args, cell, devices, runner)
    _keep("-".join(x for x in (args.mode, args.workload, str(args.seed),
                               args.tag) if x), doc)
    return 0


if __name__ == "__main__":
    sys.exit(main())
