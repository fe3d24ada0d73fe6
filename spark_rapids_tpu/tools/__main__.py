"""CLI for the offline tools (ref QualificationMain / ProfileMain):

    python -m spark_rapids_tpu.tools qualification <eventlogs...> [-o DIR]
    python -m spark_rapids_tpu.tools profiling     <eventlogs...> [-o DIR] [-c] [--accuracy]
    python -m spark_rapids_tpu.tools trace         <eventlog> [--export chrome|text] [-o FILE] [--merged]
    python -m spark_rapids_tpu.tools fleet         <eventlog|trace.json> [--json]
    python -m spark_rapids_tpu.tools lint --repo   [--baseline FILE]
    python -m spark_rapids_tpu.tools lint --plan   <fixture.py...> [--infer] [--memsan] [--determinism]
    python -m spark_rapids_tpu.tools lint --determinism [-o FILE]
    python -m spark_rapids_tpu.tools regress --history DIR --record <eventlog...> [--label L]
    python -m spark_rapids_tpu.tools regress --history DIR --check [--wall-threshold PCT]
    python -m spark_rapids_tpu.tools compile-report --ledger PATH [--top N] [--json]
    python -m spark_rapids_tpu.tools tail-report    --ledger PATH [--top N] [--json]
    python -m spark_rapids_tpu.tools estimator-report --ledger PATH [--top N] [--json]
    python -m spark_rapids_tpu.tools kernel-report  --compile-ledger PATH --estimator-ledger PATH [--top N] [--json]
    python -m spark_rapids_tpu.tools prewarm        --ledger DIR [--top K]
    python -m spark_rapids_tpu.tools postmortem     <bundle.json|dir> [--json] [--last N]
    python -m spark_rapids_tpu.tools top            [--url HOST:PORT] [--watch] [--json]

`top` renders the progress observatory's live view (obs/progress.py;
served as `GET /queries` on the health endpoint): one row per
in-flight query with phase, blended progress ratio, ETA, rows vs the
planner's predicted rows, the deepest open operator span, and
stall/cancel flags from the stuck-query watchdog.

`postmortem` renders the failure black box's bundles
(obs/postmortem.py; dumped to <historyDir>/postmortems/ on query
failure, dirty memsan ledger or admission timeout): the failing
operator, its tenant/query, the per-tenant HBM occupancy split at
failure time and the memory-timeline window leading up to it.  Given a
directory it renders the newest bundle (or the newest --last N).

`compile-report` aggregates the compile observatory's cross-session
ledger (obs/compileprof.py; `--ledger` takes the JSONL file or the
history dir holding compile_ledger.jsonl) into top-programs-by-compile-
cost, miss causes, churn offenders and the bucket-canonicalization
dedupe projection — the evidence for the persistent-program-cache key
design (ROADMAP item 1).

`tail-report` aggregates the latency observatory's per-query ledger
(obs/slo.py; `--ledger` takes latency_ledger.jsonl or the history dir
holding it) into per-tenant p50-vs-p99 segment mixes and names each
tenant's dominant tail segment — the whale-victim evidence ROADMAP
item 4's weighted-fair admission will be judged against.

`estimator-report` is its planner-side twin: it aggregates the
estimator observatory's ledger (obs/estimator.py; `--ledger` takes the
JSONL file or the history dir holding estimator_ledger.jsonl) into the
planner calibration score, the exec kinds with the worst row-estimate
error (where feedback blending buys the most), the peak-HBM
bound-vs-measured error, and the exchange-boundary re-plan decisions
by (decision, cause).

`kernel-report` is the tpuxsan headline artifact: it joins the compile
ledger's per-program cost_analysis() figures against the estimator
ledger's measured span seconds and padding-waste bytes, computes each
exec kind's speed-of-light gap (analysis/hlocost.py), and ranks the
kinds and the named fusion pipelines (hash build/probe,
filter->project, grouped aggregate) by projected kernel savings — the
evidence that decides which Pallas kernel to write first.

`regress` is the cross-run watchdog (obs/history.py): --record distills
self-emitted event logs into per-query fingerprints appended to the
history dir; --check diffs the two most recent runs and exits nonzero
on DETERMINISTIC drift (new fallbacks, fetch-crossing growth, operator
row drift, plan/lint changes).  Wall-clock comparison is opt-in via
--wall-threshold and never fails CI.

`profiling --accuracy` and `trace` consume the engine's SELF-emitted
event logs (spark.rapids.tpu.eventLog.dir): predicted-vs-actual
rows/bytes per operator, and the flight-recorder span tree exported as
Chrome-trace JSON (chrome://tracing / Perfetto) or a text timeline.

Lint fixtures are Python files defining ``plan_*()`` builders, each
returning ``(exec_root, conf_dict)`` — the checked-in golden bad plans
under tests/goldens/lint/ are the reference examples.
"""

import argparse
import sys


def _run_plan_lint(paths, infer=False, memsan=False,
                   determinism=False):
    import runpy

    from ..analysis.diagnostics import format_diagnostics
    from ..analysis.plan_lint import lint_plan
    from ..config import RapidsConf

    any_error = False
    for path in paths:
        ns = runpy.run_path(path)
        builders = sorted(k for k in ns if k.startswith("plan_")
                          and callable(ns[k]))
        if not builders:
            sys.stderr.write(f"{path}: no plan_*() builders found\n")
            return 2
        for name in builders:
            root, conf_map = ns[name]()
            conf = RapidsConf(conf_map)
            diags = lint_plan(root, conf)
            sys.stdout.write(f"== {path}::{name}\n")
            if infer:
                # print the abstract interpreter's per-subtree states
                # (schema / residency / distribution / rows / liveness)
                from ..analysis.interp import format_states, infer_plan
                sys.stdout.write(format_states(root, infer_plan(root,
                                                                conf)))
            if memsan:
                # print the lifetime pass's per-subtree peak-byte bounds
                from ..analysis.lifetime import (analyze_memory,
                                                 format_memory)
                sys.stdout.write(format_memory(
                    root, analyze_memory(root, conf)))
            if determinism:
                # print per-subtree replay classes, then show what the
                # L016 in-place repair (canonical keyed merge) achieves
                from ..analysis.determinism import (classify_plan,
                                                    format_classes,
                                                    try_stabilize_repair)
                sys.stdout.write(format_classes(root, conf))
                res = classify_plan(root, conf)
                for d in res.diags:
                    if d.code != "TPU-L016" or d.node is None:
                        continue
                    if try_stabilize_repair(root, d.node, conf):
                        after = classify_plan(root, conf)
                        sys.stdout.write(
                            f"TPU-L016 repair applied at "
                            f"{d.node.name}: subtree now "
                            f"{after.effective(d.node.children[0])} "
                            f"(canonical keyed merge forced)\n")
                    else:
                        sys.stdout.write(
                            f"TPU-L016 at {d.node.name}: no "
                            f"stabilizing repair available\n")
            sys.stdout.write(format_diagnostics(diags))
            any_error |= any(d.is_error for d in diags)
    return 1 if any_error else 0


def _run_lock_graph(output):
    """Dump the tpucsan static lock-order artifact (the relation the
    runtime lock witness validates against) as JSON."""
    import json

    from ..analysis.concurrency import lock_order_artifact

    art = lock_order_artifact()
    text = json.dumps(art, indent=2, sort_keys=True) + "\n"
    if output:
        with open(output, "w", encoding="utf-8") as f:
            f.write(text)
        sys.stdout.write(
            f"lock graph: {len(art['locks'])} lock(s), "
            f"{len(art['edges'])} edge(s), {len(art['cycles'])} "
            f"cycle(s) -> {output}\n")
    else:
        sys.stdout.write(text)
    return 1 if art["cycles"] else 0


def _run_raise_graph(output):
    """Dump the tpufsan exception-flow artifact (what the fault-
    injection gate enumerates) as JSON."""
    import json

    from ..analysis.raiseflow import raise_graph_artifact

    art = raise_graph_artifact()
    text = json.dumps(art, indent=2, sort_keys=True) + "\n"
    leaks = sum(len(s["untyped"]) for s in art["seams"].values())
    if output:
        with open(output, "w", encoding="utf-8") as f:
            f.write(text)
        sys.stdout.write(
            f"raise graph: {len(art['seams'])} seam(s), "
            f"{len(art['taxonomy'])} typed error(s), "
            f"{len(art['injections'])} planned injection(s), "
            f"{leaks} untyped leak(s) -> {output}\n")
    else:
        sys.stdout.write(text)
    return 1 if leaks else 0


def _run_determinism_artifact(output):
    """Dump the tpudsan replay-class artifact (declared determinism of
    every registered operator + fingerprint hygiene) as JSON — the
    sibling of --lock-graph / --raise-graph."""
    import json

    from ..analysis.determinism import determinism_artifact

    art = determinism_artifact()
    text = json.dumps(art, indent=2, sort_keys=True) + "\n"
    hygiene = art["fingerprint_hygiene"]
    if output:
        with open(output, "w", encoding="utf-8") as f:
            f.write(text)
        sys.stdout.write(
            f"determinism artifact: {len(art['declarations'])} "
            f"operator declaration(s) over the "
            f"{len(art['lattice'])}-class lattice, "
            f"{len(hygiene)} fingerprint-hygiene finding(s) "
            f"-> {output}\n")
    else:
        sys.stdout.write(text)
    return 1 if hygiene else 0


def _run_repo_lint(baseline_path, update):
    from ..analysis.diagnostics import format_diagnostics
    from ..analysis.repo_lint import (lint_repo, load_baseline,
                                      new_violations, save_baseline)

    diags = lint_repo()
    if update:
        save_baseline(baseline_path, diags)
        sys.stdout.write(f"baseline updated: {len(diags)} violation(s) "
                         f"-> {baseline_path}\n")
        return 0
    baseline = load_baseline(baseline_path)
    fresh = new_violations(diags, baseline)
    if fresh:
        sys.stdout.write(format_diagnostics(fresh))
        sys.stdout.write(f"{len(fresh)} NEW violation(s) not in baseline "
                         f"({baseline_path})\n")
        return 1
    sys.stdout.write(f"repo lint clean ({len(diags)} baselined "
                     f"violation(s))\n")
    return 0


def _run_trace_export(log, fmt, output, sql_id, merged=False):
    import json

    from ..obs.export import spans_to_chrome, spans_to_text
    from .eventlog import parse_event_log

    app = parse_event_log(log)
    spans = [s for s in app.spans
             if sql_id is None or s.get("executionId") == sql_id]
    if not merged:
        # default view: THIS process's spans only; --merged includes
        # the remote serve spans grafted in by the fleet observatory
        # (they carry "proc" — the producing executor's identity)
        spans = [s for s in spans if not s.get("proc")]
    if not spans:
        sys.stderr.write(f"{log}: no flight-recorder spans "
                         f"(self-emitted logs only; was "
                         f"spark.rapids.tpu.eventLog.dir set?)\n")
        return 2
    if fmt == "text":
        text = spans_to_text(spans)
        if output:
            with open(output, "w") as f:
                f.write(text)
        else:
            sys.stdout.write(text)
        return 0
    out_path = output or (log + ".trace.json")
    with open(out_path, "w") as f:
        json.dump(spans_to_chrome(spans), f)
    sys.stdout.write(f"{len(spans)} span(s) -> {out_path}\n")
    return 0


def _run_fleet_summary(log, sql_id, as_json=False):
    import json

    from ..obs.export import fleet_summary, format_fleet_summary

    spans = None
    if log.endswith(".json"):
        # a raw span dump (bench.py --dist writes one): either a bare
        # span-dict list or {"spans": [...]}
        try:
            with open(log) as f:
                doc = json.load(f)
            spans = doc if isinstance(doc, list) else doc.get("spans")
        except (OSError, ValueError):
            spans = None
    if spans is None:
        from .eventlog import parse_event_log
        app = parse_event_log(log)
        spans = [s for s in app.spans
                 if sql_id is None or s.get("executionId") == sql_id]
    if not spans:
        sys.stderr.write(f"{log}: no flight-recorder spans\n")
        return 2
    summary = fleet_summary(spans)
    if as_json:
        sys.stdout.write(json.dumps(summary, indent=2) + "\n")
    else:
        sys.stdout.write(format_fleet_summary(summary))
    return 0


def _run_regress(history_dir, record_logs, check, wall_threshold,
                 label=""):
    from ..obs.history import (HistoryDir, deterministic_drift,
                               diff_runs, distill_event_log)
    from .eventlog import find_event_logs

    hist = HistoryDir(history_dir)
    if record_logs:
        fps = []
        for log in find_event_logs(record_logs):
            fps += distill_event_log(log)
        if not fps:
            sys.stderr.write("regress --record: no queries found in "
                             "the given event log(s)\n")
            return 2
        path = hist.record(fps, label=label)
        sys.stdout.write(f"recorded {len(fps)} query fingerprint(s) "
                         f"-> {path}\n")
        if not check:
            return 0
    runs = hist.runs()
    if len(runs) < 2:
        sys.stderr.write(f"regress --check: need >= 2 recorded runs in "
                         f"{history_dir}, have {len(runs)}\n")
        return 2
    old, new = hist.load(runs[-2]), hist.load(runs[-1])
    drifts = diff_runs(old, new, wall_threshold_pct=wall_threshold)
    for d in drifts:
        sys.stdout.write(d.render() + "\n")
    hard = deterministic_drift(drifts)
    if hard:
        sys.stdout.write(f"regress: {len(hard)} deterministic drift "
                         f"signal(s) between {runs[-2].rsplit('/')[-1]} "
                         f"and {runs[-1].rsplit('/')[-1]}\n")
        return 1
    timing = len(drifts) - len(hard)
    sys.stdout.write(
        f"regress clean: no deterministic drift across "
        f"{len(new.get('queries', ()))} quer(ies)"
        + (f" ({timing} timing-only signal(s) above)" if timing
           else "") + "\n")
    return 0


def _run_prewarm(ledger, top):
    import os

    path = ledger
    if os.path.isdir(path):
        from ..obs.history import HistoryDir
        path = HistoryDir(path).compile_ledger_path()
    if not os.path.exists(path):
        sys.stderr.write(f"{ledger}: no compile ledger found\n")
        return 2
    if not os.environ.get("SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE"):
        # the one cache a session reads (plugin.init_compilation_cache):
        # the entries this replay writes are the ones it will find
        from ..plugin import init_compilation_cache
        init_compilation_cache()
    from ..obs.compileprof import CompileObservatory
    from ..obs.prewarm import prewarm_from_ledger
    CompileObservatory.get().configure(enabled=True, ledger_path=path)
    stats = prewarm_from_ledger(path, top_k=top)
    sys.stdout.write(
        f"prewarm: {stats['recipes']} recipe(s) replayed, "
        f"{stats['programs']} program(s) compiled in "
        f"{stats['seconds']:.2f}s ({stats['skipped']} without recipes, "
        f"{stats['errors']} error(s))\n")
    if stats["recipes"] == 0 and stats["errors"] == 0:
        sys.stdout.write(
            "no recipes found — run a session with "
            "spark.rapids.tpu.compile.ledgerDir set to record some\n")
    return 1 if stats["errors"] else 0


def _run_postmortem(target, as_json=False, last=1):
    import json
    import os

    from ..obs.postmortem import (list_bundles, load_bundle,
                                  render_postmortem)

    if os.path.isdir(target):
        paths = list_bundles(target)[-max(last, 1):]
        if not paths:
            sys.stderr.write(f"{target}: no post-mortem bundles "
                             f"(pm_*.json) found — was "
                             f"spark.rapids.tpu.hbm.postmortem.dir (or "
                             f"regress.historyDir) set when the query "
                             f"failed?\n")
            return 2
    else:
        paths = [target]
    rc = 0
    for path in paths:
        try:
            bundle = load_bundle(path)
        except (OSError, ValueError) as ex:
            sys.stderr.write(f"{path}: unreadable bundle: {ex}\n")
            rc = 2
            continue
        if as_json:
            sys.stdout.write(json.dumps(bundle, indent=2) + "\n")
        else:
            sys.stdout.write(f"== {path}\n")
            sys.stdout.write(render_postmortem(bundle))
    return rc


def _default_baseline():
    import os
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "devtools", "lint_baseline.txt")


def main(argv=None):
    p = argparse.ArgumentParser(prog="spark_rapids_tpu.tools")
    sub = p.add_subparsers(dest="cmd", required=True)
    q = sub.add_parser("qualification",
                       help="score apps for TPU acceleration benefit")
    q.add_argument("logs", nargs="+")
    q.add_argument("-o", "--output", default="qual_output")
    pr = sub.add_parser("profiling", help="profile apps from event logs")
    pr.add_argument("logs", nargs="+")
    pr.add_argument("-o", "--output", default="profile_output")
    pr.add_argument("-c", "--compare", action="store_true")
    pr.add_argument("-a", "--accuracy", action="store_true",
                    help="print the predicted-vs-actual report "
                         "(self-emitted logs embed the CBO/tmsan "
                         "model and measured rows/bytes per operator)")
    tr = sub.add_parser("trace",
                        help="export the flight-recorder span tree "
                             "from a self-emitted event log")
    tr.add_argument("log")
    tr.add_argument("--export", choices=["chrome", "text"],
                    default="chrome")
    tr.add_argument("-o", "--output", default=None,
                    help="output file (default: <log>.trace.json for "
                         "chrome; stdout for text)")
    tr.add_argument("--sql", type=int, default=None,
                    help="only this SQL execution id")
    tr.add_argument("--merged", action="store_true",
                    help="include the remote serve spans the fleet "
                         "observatory merged into the trace (one "
                         "clock-aligned multi-process timeline; each "
                         "producer gets its own Chrome process lane)")
    fl = sub.add_parser("fleet",
                        help="per-peer wire vs serve vs compute "
                             "summary of a merged trace")
    fl.add_argument("log", help="self-emitted event log (or a raw "
                                ".trace.json span dump)")
    fl.add_argument("--sql", type=int, default=None,
                    help="only this SQL execution id")
    fl.add_argument("--json", action="store_true",
                    help="emit the summary as JSON instead of text")
    li = sub.add_parser("lint",
                        help="static plan/repo analysis (tpulint)")
    li.add_argument("--repo", action="store_true",
                    help="run the repo invariant lint over the package")
    li.add_argument("--plan", nargs="*", metavar="FIXTURE",
                    help="lint physical plans built by plan_*() "
                         "functions in the given Python files")
    li.add_argument("--infer", action="store_true",
                    help="with --plan: print the abstract "
                         "interpreter's inferred per-subtree states "
                         "(schema/residency/partitioning/rows) before "
                         "the diagnostics")
    li.add_argument("--memsan", action="store_true",
                    help="with --plan: print the lifetime pass's "
                         "per-subtree peak-device-byte bounds "
                         "(hold/retained/peak vs the HBM budget) "
                         "before the diagnostics")
    li.add_argument("--determinism", action="store_true",
                    help="dump the tpudsan replay-class artifact "
                         "(declared determinism per operator + "
                         "fingerprint hygiene) as JSON; with --plan, "
                         "print per-subtree replay classes and the "
                         "TPU-L016 repair outcome instead")
    li.add_argument("--baseline", default=None,
                    help="repo-lint baseline file "
                         "(default: devtools/lint_baseline.txt)")
    li.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from current violations")
    li.add_argument("--lock-graph", action="store_true",
                    help="dump the tpucsan static lock-order artifact "
                         "(locks, acquisition edges, cycles, thread "
                         "roots) as JSON; exits 1 if the graph has a "
                         "cycle")
    li.add_argument("--raise-graph", action="store_true",
                    help="dump the tpufsan exception-flow artifact "
                         "(per-seam typed/untyped escape sets, the "
                         "typed-error taxonomy with raise sites, and "
                         "the fault-injection plan) as JSON; exits 1 "
                         "when any seam leaks an untyped operational "
                         "exception")
    li.add_argument("-o", "--output", default=None,
                    help="with --lock-graph/--raise-graph: write the "
                         "JSON here instead of stdout")
    rg = sub.add_parser("regress",
                        help="cross-run regression watchdog over "
                             "self-emitted event-log fingerprints")
    rg.add_argument("--history", required=True,
                    help="append-only fingerprint history directory "
                         "(spark.rapids.tpu.regress.historyDir)")
    rg.add_argument("--record", nargs="*", metavar="EVENTLOG",
                    default=None,
                    help="distill these event logs into one run "
                         "appended to the history")
    rg.add_argument("--check", action="store_true",
                    help="diff the two most recent runs; exit 1 on "
                         "deterministic drift")
    rg.add_argument("--wall-threshold", type=float, default=None,
                    metavar="PCT",
                    help="also report wall-clock regressions above "
                         "this percentage (advisory: timing drift "
                         "never fails the check)")
    rg.add_argument("--label", default="",
                    help="free-form label stored on the recorded run")
    cr = sub.add_parser("compile-report",
                        help="aggregate the compile observatory "
                             "ledger into the compile-cost report")
    cr.add_argument("--ledger", required=True,
                    help="compile_ledger.jsonl or the history dir "
                         "containing it "
                         "(spark.rapids.tpu.compile.ledgerDir)")
    cr.add_argument("--top", type=int, default=10,
                    help="rows per ranking section")
    cr.add_argument("--json", action="store_true",
                    help="emit the aggregate as JSON instead of text")
    tr = sub.add_parser("tail-report",
                        help="contrast per-tenant p50 vs p99 segment "
                             "mixes from the latency observatory "
                             "ledger and name each tenant's dominant "
                             "tail segment")
    tr.add_argument("--ledger", required=True,
                    help="latency_ledger.jsonl or the history dir "
                         "containing it "
                         "(spark.rapids.tpu.regress.historyDir)")
    tr.add_argument("--top", type=int, default=3,
                    help="slowest queries listed per tenant")
    tr.add_argument("--json", action="store_true",
                    help="emit the aggregate as JSON instead of text")
    kr = sub.add_parser("kernel-report",
                        help="rank compiled programs by kernel gap x "
                             "measured seconds x padding waste (the "
                             "Pallas target list)")
    kr.add_argument("--compile-ledger", required=True,
                    help="compile_ledger.jsonl or the dir containing "
                         "it (spark.rapids.tpu.compile.ledgerDir)")
    kr.add_argument("--estimator-ledger", required=True,
                    help="estimator_ledger.jsonl or the dir containing "
                         "it (spark.rapids.tpu.regress.historyDir)")
    kr.add_argument("--top", type=int, default=10,
                    help="rows per ranking section")
    kr.add_argument("--tolerance", type=float, default=8.0,
                    help="cost-model agreement ratio "
                         "(spark.rapids.tpu.xsan.costTolerance)")
    kr.add_argument("--json", action="store_true",
                    help="emit the aggregate as JSON instead of text")
    er = sub.add_parser("estimator-report",
                        help="aggregate the estimator observatory "
                             "ledger into the planner calibration "
                             "report")
    er.add_argument("--ledger", required=True,
                    help="estimator_ledger.jsonl or the history dir "
                         "containing it "
                         "(spark.rapids.tpu.regress.historyDir)")
    er.add_argument("--top", type=int, default=10,
                    help="rows per ranking section")
    er.add_argument("--json", action="store_true",
                    help="emit the aggregate as JSON instead of text")
    pw = sub.add_parser("prewarm",
                        help="replay the top-K ledger program recipes "
                             "to populate the persistent compile cache "
                             "out-of-band")
    pw.add_argument("--ledger", required=True,
                    help="compile_ledger.jsonl or the history dir "
                         "containing it (recipes live in its programs/ "
                         "subdirectory)")
    pw.add_argument("--top", type=int, default=32,
                    help="how many programs to replay, ranked by "
                         "cumulative compile seconds")
    tp = sub.add_parser("top",
                        help="live in-flight query view (phase, "
                             "progress, ETA, deepest open operator, "
                             "watchdog flags) from a running engine's "
                             "GET /queries endpoint")
    tp.add_argument("--url", default="127.0.0.1:9090",
                    help="health endpoint host:port or full URL "
                         "(spark.rapids.tpu.metrics.port)")
    tp.add_argument("--watch", action="store_true",
                    help="refresh in place every --interval seconds "
                         "until Ctrl-C (default: one snapshot)")
    tp.add_argument("--interval", type=float, default=2.0,
                    help="refresh period with --watch (seconds)")
    tp.add_argument("--json", action="store_true",
                    help="emit the raw /queries JSON instead of the "
                         "table")
    pm = sub.add_parser("postmortem",
                        help="render a failure black-box bundle "
                             "(failing operator, tenant, HBM occupancy "
                             "at failure time)")
    pm.add_argument("target",
                    help="a pm_*.json bundle, or a directory (history "
                         "dir or its postmortems/ subdir) — renders "
                         "the newest bundle(s)")
    pm.add_argument("--json", action="store_true",
                    help="emit the raw bundle JSON instead of the "
                         "report")
    pm.add_argument("--last", type=int, default=1,
                    help="with a directory: render the newest N "
                         "bundles (default 1)")
    args = p.parse_args(argv)

    if args.cmd == "qualification":
        from .qualification import format_summary, qualify
        results = qualify(args.logs, args.output)
        sys.stdout.write(format_summary(results))
    elif args.cmd == "profiling":
        from .profiling import profile
        reports = profile(args.logs, args.output, compare=args.compare)
        sys.stdout.write(f"profiled {len(reports)} application(s) -> "
                         f"{args.output}\n")
        if args.accuracy:
            from .eventlog import find_event_logs, parse_event_log
            from .profiling import format_accuracy
            for log in find_event_logs(args.logs):
                sys.stdout.write(format_accuracy(parse_event_log(log)))
    elif args.cmd == "trace":
        return _run_trace_export(args.log, args.export, args.output,
                                 args.sql, merged=args.merged)
    elif args.cmd == "fleet":
        return _run_fleet_summary(args.log, args.sql,
                                  as_json=args.json)
    elif args.cmd == "regress":
        if args.record is None and not args.check:
            p.error("regress needs --record and/or --check")
        return _run_regress(args.history, args.record, args.check,
                            args.wall_threshold, label=args.label)
    elif args.cmd == "compile-report":
        from .compile_report import run_compile_report
        return run_compile_report(args.ledger, top=args.top,
                                  as_json=args.json)
    elif args.cmd == "tail-report":
        from .tail_report import run_tail_report
        return run_tail_report(args.ledger, top=args.top,
                               as_json=args.json)
    elif args.cmd == "kernel-report":
        from .kernel_report import run_kernel_report
        return run_kernel_report(args.compile_ledger,
                                 args.estimator_ledger, top=args.top,
                                 as_json=args.json,
                                 tolerance=args.tolerance)
    elif args.cmd == "estimator-report":
        from .estimator_report import run_estimator_report
        return run_estimator_report(args.ledger, top=args.top,
                                    as_json=args.json)
    elif args.cmd == "prewarm":
        return _run_prewarm(args.ledger, args.top)
    elif args.cmd == "top":
        from .top import run_top
        return run_top(args.url, interval=args.interval,
                       watch=args.watch, as_json=args.json)
    elif args.cmd == "postmortem":
        return _run_postmortem(args.target, as_json=args.json,
                               last=args.last)
    else:
        if args.lock_graph:
            return _run_lock_graph(args.output)
        if args.raise_graph:
            return _run_raise_graph(args.output)
        if args.determinism and not args.plan:
            return _run_determinism_artifact(args.output)
        if args.plan:
            return _run_plan_lint(args.plan, infer=args.infer,
                                  memsan=args.memsan,
                                  determinism=args.determinism)
        # --repo is the default lint mode
        return _run_repo_lint(args.baseline or _default_baseline(),
                              args.update_baseline)
    return 0


if __name__ == "__main__":
    sys.exit(main())
