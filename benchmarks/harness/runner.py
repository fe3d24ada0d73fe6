"""One run of one cell: set-up, warm-up, the measured window, the check of
every answer, the result line.

The harness reaches the program through ``TpuSession`` (and the plan it
leaves in ``last_plan``), ``CompileObservatory.get().snapshot()`` and
``metrics.registry()``, nothing else.  ``run.py`` demands the chip before
it calls in here; the functions themselves run wherever JAX runs, which is
how ``benchmarks/tests`` drive them at a tiny scale.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import tempfile
import time
from typing import Iterator, List, Optional

from . import device as dev
from . import trace_reduce
from .cells import Cell
from .facts import RunFacts
from .stats import MIN_SAMPLES_P95, median, percentile
from .traffic import parameter_stream


def emit(**facts) -> None:
    """A line of facts worth keeping; the result line comes last."""
    print(json.dumps(facts, default=str), flush=True)


def arrow_table(columns: dict, schema: dict):
    """The generated NumPy columns as one Arrow table, without a copy."""
    import pyarrow as pa
    arrays = []
    for name, values in columns.items():
        arr = pa.array(values)
        if schema[name] == "date32":
            arr = arr.view(pa.date32())
        elif str(arr.type) != schema[name].replace("float64", "double"):
            raise TypeError(f"{name}: generated {arr.type}, the schema "
                            f"says {schema[name]}")
        arrays.append(arr)
    return pa.table(arrays, names=list(columns))


def program_counters() -> dict:
    from spark_rapids_tpu.obs import metrics
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    reg = metrics.registry()
    return {
        "builds": int(CompileObservatory.get().snapshot()["builds"]),
        "cache_hits": int(reg.counter(
            "tpu_jit_persistent_cache_hits_total").value()),
        "cache_misses": int(reg.counter(
            "tpu_jit_persistent_cache_misses_total").value()),
        "fetch_crossings": int(reg.counter(
            "tpu_fetch_crossings_total").value()),
    }


class Asked:
    """One query of the stream: what was asked, what came back."""

    __slots__ = ("params", "ms", "answer", "error")

    def __init__(self, params):
        self.params = params
        self.ms = None
        self.answer = None
        self.error = None


class Bench:
    def __init__(self, cell: Cell, seed: int, trace: bool):
        self.cell = cell
        self.seed = seed
        self.trace = trace
        self.asked: List[Asked] = []       # the window's queries
        self.warm: List[Asked] = []        # the warm-up's
        self.n_traced = 0
        self.first_call_s = 0.0
        self.pinned_bytes = 0
        self.window_s = 0.0
        self.at_window: dict = {}
        self.at_end: dict = {}
        self.summary: Optional[trace_reduce.TraceSummary] = None
        self.problems: List[str] = []      # what makes the run not correct
        self._references: dict = {}

    # -- set-up -----------------------------------------------------------

    def load(self) -> None:
        """Generate the table from the seed and hand it to a session."""
        from spark_rapids_tpu.api.session import TpuSession
        cell = self.cell
        t0 = time.perf_counter()
        self.columns = cell.datagen.generate(cell.config, self.seed)
        table = arrow_table(self.columns, cell.datagen.SCHEMA)
        self.n_rows = table.num_rows
        self.table_bytes = table.nbytes
        t1 = time.perf_counter()
        builder = TpuSession.builder()
        conf = dict(cell.config.get("session_conf", {}))
        if self.trace:
            # host ranges per operator in the profiler's trace; host-side
            # only, the programs are the same
            conf["spark.rapids.sql.profile.traceAnnotations"] = True
        for key, value in conf.items():
            builder = builder.config(key, value)
        self.session = builder.get_or_create()
        self.df = self.session.create_dataframe(
            table, num_partitions=int(cell.config["num_partitions"]))
        self.stream: Iterator[dict] = parameter_stream(
            cell.traffic, self.seed)
        emit(rows=self.n_rows, table_bytes=self.table_bytes,
             generate_s=t1 - t0, session_s=time.perf_counter() - t1,
             partitions=cell.config["num_partitions"], session_conf=conf)

    def ask(self, params: dict) -> Asked:
        """One query, timed from the call to the Arrow table, then (outside
        the time) held to the configuration's guarantees on its plan."""
        from jax.profiler import TraceAnnotation
        q = Asked(params)
        try:
            with TraceAnnotation("bench:build_query"):
                frame = self.cell.query.build(self.df, params)
            t0 = time.perf_counter()
            with TraceAnnotation("bench:collect"):
                table = frame.collect()
            q.ms = (time.perf_counter() - t0) * 1e3
            with TraceAnnotation("bench:keep_answer"):
                q.answer = self.cell.query.answer(table)
                q.error = self.plan_fault(self.session.last_plan)
        except Exception as e:   # the boundary: a failed query is counted
            q.error = f"{type(e).__name__}: {e}"
        return q

    def plan_fault(self, plan) -> Optional[str]:
        g = self.cell.config["guarantees"]
        stray = sorted(set(dev.placements(plan))
                       - set(g["cpu_ops_allowed"]))
        if stray:
            return f"operators ran on the CPU engine: {stray}"
        for need in g.get("plan_must_hold", {}).get(
                self.cell.traffic["query"], []):
            found = dev.plan_execs(plan, need["exec"])
            if not found:
                return f"the plan holds no {need['exec']}"
            for e in found:
                for attr, want in need.items():
                    if attr != "exec" and getattr(e, attr, None) != want:
                        return (f"{need['exec']}.{attr} is "
                                f"{getattr(e, attr, None)!r}, not {want!r}")
        return None

    def pinned_fault(self, devices) -> Optional[str]:
        """The table's lanes sit pinned on the cell's chips."""
        import jax
        leaves = {id(a): a for a in
                  dev.pinned_scan_arrays(self.session.last_plan)}
        if not leaves:
            return "no scan pinned its device batches"
        allowed = set(devices)
        for leaf in leaves.values():
            if not isinstance(leaf, jax.Array) or \
                    not leaf.devices() <= allowed:
                return f"a pinned scan batch is not on {devices}: {leaf!r}"
        self.pinned_bytes = sum(a.nbytes for a in leaves.values())
        need = sum(self.columns[c].nbytes for c in self.cell.query.COLUMNS)
        if self.pinned_bytes < need:
            return (f"{self.pinned_bytes} bytes pinned, the query's "
                    f"columns are {need}")
        return None

    def warm_up(self, devices) -> None:
        """The query's first call: upload, pin, load or compile every
        program; its answer is held to the reference before the window
        opens.  One call is the whole warm-up: literals are hoisted
        (expr/params.py), so other parameters reuse the programs, and a
        program built inside the window makes the run not correct.  A
        second warm call would cost every run of every later check a
        query's time (17-23 s in the first cells)."""
        first = self.ask(next(self.stream))
        self.warm.append(first)
        self.first_call_s = (first.ms or 0.0) / 1e3
        emit(warm_call=1, ms=first.ms, **program_counters())
        fault = self.pinned_fault(devices)
        if fault:
            self.problems.append(fault)
        self.check(self.warm, "warm-up")

    # -- the window -------------------------------------------------------

    def window(self, seconds: float) -> None:
        self.at_window = program_counters()
        trace_dir = None
        t_open = time.perf_counter()
        if self.trace:
            trace_dir = self.traced_queries()
        while time.perf_counter() - t_open < seconds:
            self.asked.append(self.ask(next(self.stream)))
        # the query in flight at the bell was finished and is counted;
        # the rate divides by the time that really passed
        self.window_s = time.perf_counter() - t_open
        self.at_end = program_counters()
        if trace_dir:
            try:
                self.summary = self.read_trace(trace_dir)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)

    @staticmethod
    def read_trace(trace_dir: str) -> trace_reduce.TraceSummary:
        files = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            raise FileNotFoundError("the profiler wrote no trace")
        return trace_reduce.reduce_trace(trace_reduce.read_xplane(files[0]))

    def traced_queries(self) -> str:
        """A few queries of the steady window under the profiler."""
        import jax
        from jax.profiler import TraceAnnotation
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0    # spans, not every Python call
        options.host_tracer_level = 2
        n = int(self.cell.traffic.get("trace_queries", 3))
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
                for _ in range(n):
                    self.asked.append(self.ask(next(self.stream)))
        finally:
            jax.profiler.stop_trace()
        self.n_traced = n
        return trace_dir

    # -- after the window -------------------------------------------------

    def reference(self, params: dict):
        key = json.dumps(params, sort_keys=True)
        if key not in self._references:
            self._references[key] = self.cell.query.reference(
                self.columns, params)
        return self._references[key]

    def check(self, asked: List[Asked], where: str) -> None:
        """Hold every answer to the plain reference; a query that raised,
        broke a guarantee or answered otherwise has failed."""
        for i, q in enumerate(asked):
            if q.error is None:
                q.error = self.cell.query.mismatch(
                    q.answer, self.reference(q.params))
            if q.error:
                self.problems.append(
                    f"{where} query {i} {q.params}: {q.error}")

    def facts(self, devices) -> RunFacts:
        done = [q for q in self.asked if q.ms is not None]
        return RunFacts(
            cell=self.cell.name, chips=self.cell.chips,
            device_kind=devices[0].device_kind, n_rows=self.n_rows,
            query=self.cell.query,
            times_ms=[q.ms for q in done],
            traced_times_ms=[q.ms for q in self.asked[:self.n_traced]
                             if q.ms is not None],
            answer_rows=[self.cell.query.answer_rows(q.answer)
                         for q in done if q.answer is not None],
            window_s=self.window_s, first_call_s=self.first_call_s,
            builds_at_window=self.at_window["builds"],
            builds_at_end=self.at_end["builds"],
            crossings_at_window=self.at_window["fetch_crossings"],
            crossings_at_end=self.at_end["fetch_crossings"],
            peak_bytes=dev.peak_device_bytes(devices),
            trace=self.summary)


def end_to_end(bench: Bench, setup_s: float) -> dict:
    """The cell's end-to-end metrics, by the names ``BENCHMARK.json``
    lists for it.  Times and rates are over every query of the window."""
    good = [q for q in bench.asked if not q.error]
    times = [q.ms for q in bench.asked if q.ms is not None]
    known = {
        "setup_s": lambda: setup_s,
        "answer_ms_p50": lambda: median(times),
        "queries_per_s": lambda: len(good) / bench.window_s,
        "answer_ms_p95": lambda: percentile(times, 95.0)
        if len(times) >= MIN_SAMPLES_P95 else None,
    }
    out = {}
    for m in bench.cell.end_to_end:
        if m["name"] not in known:
            raise SystemExit(f"benchmark: no arithmetic for the end-to-end "
                             f"metric {m['name']!r}")
        value = known[m["name"]]() if (times or m["name"] == "setup_s") \
            else None
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def per_layer(bench: Bench, facts: RunFacts) -> dict:
    out = {}
    for m in bench.cell.per_layer:
        value = bench.cell.readers[m["name"]].read(facts)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices) -> dict:
    """The whole run; returns the result line's object."""
    bench = Bench(cell, seed, trace)
    bench.load()
    bench.warm_up(devices)
    if bench.problems:
        # a cell that is wrong before the window is not worth timing
        emit(problems=bench.problems[:10])
        raise SystemExit("benchmark: the warm-up failed: "
                         + bench.problems[0])
    setup_s = time.perf_counter() - t_start
    bench.window(seconds)
    bench.check(bench.asked, "window")
    facts = bench.facts(devices)
    compiled = facts.builds_at_end - facts.builds_at_window
    if compiled:
        bench.problems.append(f"{compiled} program(s) built in the window")
    failed = sum(1 for q in bench.asked if q.error)
    emit(queries=len(bench.asked), window_s=bench.window_s,
         times_ms=facts.times_ms, at_window=bench.at_window,
         at_end=bench.at_end, pinned_bytes=bench.pinned_bytes,
         peak_bytes=facts.peak_bytes,
         params=[q.params for q in bench.asked[:8]],
         answer_rows=facts.answer_rows[:8], problems=bench.problems[:10])
    device = dev.device_facts()
    device["memory_peak_bytes"] = max(facts.peak_bytes)
    line = {"correct": not bench.problems, "attempted": len(bench.asked),
            "failed": failed, "device": device}
    if trace:
        line["metrics"] = per_layer(bench, facts)
        s = bench.summary
        device["busy_s"] = s.busy_mean_s
        device["window_s"] = s.window_s
        line["breakdown"] = {
            "device_ops": [[n, t] for n, t in s.device_ops()],
            "idle_gaps": [[n, t] for n, t in s.idle_gaps]}
        emit(chips=[{"chip": c.index, "busy_s": c.busy_s,
                     "collective_s": c.collective_s,
                     "collective_exposed_s": c.collective_exposed_s,
                     "program_s": c.program_s}
                    for c in s.chips], traced_queries=bench.n_traced)
    else:
        line["metrics"] = end_to_end(bench, setup_s)
    return line
