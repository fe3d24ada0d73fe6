"""Plan lint: walk a converted physical plan before execution and report
hazards as structured TPU-Lxxx diagnostics.

The rule classes target what round 5 showed actually breaks queries:

  TPU-L001  planning gate admits dtypes a collective kernel raises on
            (the ICI ungrouped array/map aggregate admit/crash mismatch)
  TPU-L002  device<->host ping-pong: a host island inside a device pipe
  TPU-L003  expression admitted on a TPU-placed operator with no device
            lowering (would evaluate on host per batch, or fail)
  TPU-L004  driver-side whole-build collect above the size threshold
  TPU-L005  shape-bucket / schema churn that defeats the JIT residency
            cache (the round-5 multichip compile-churn killer)
  TPU-L006  partitioning/ordering contract consumed above a subtree
            whose establishing exchange was rewritten away
  TPU-L007  ICI transport silently staging an exchange through host
            Arrow because of its column types
  TPU-L008  opaque Python-UDF boundary inside a device pipeline

The flow-sensitive rules TPU-L009..L012 (schema mismatch at a boundary,
dead exchange columns, contract violation after rewrite, residency
ping-pong totals) live in ``analysis/interp.py`` — the abstract
interpreter whose per-subtree states also upgrade L002/L006/L007 here
from syntactic to flow-sensitive (see docs/static-analysis.md).

``lint_plan`` is pure analysis; ``downgrade_hazards`` applies the safe
repairs (host fallback by placement flip — the CPU engine runs the
identical xp-parameterized kernels) for the rules where that is sound,
which is what ``spark.rapids.tpu.lint.enabled`` wires into
plan/overrides.py as an opt-in pre-flight.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

from .. import config as cfg
from .. import types as t
from ..exec import base as eb
from .capabilities import ALLGATHER_BATCH, EXCHANGE_BY_PID
from .diagnostics import (ERROR, INFO, WARN, Diagnostic, filter_suppressed,
                          register_rule, sort_diagnostics)

# ---------------------------------------------------------------------------
# rule registrations (catalog entries feed docsgen + suppression)
# ---------------------------------------------------------------------------

L001 = register_rule(
    "TPU-L001", ERROR, "ICI admit/capability mismatch",
    "An ungrouped aggregate's partial buffers pass the exchange admission "
    "gate but contain types the allgather kernel raises "
    "NotImplementedError on; under spark.rapids.shuffle.transport=ici the "
    "plan would pass planning and crash mid-query.  Derived from the "
    "capability table (analysis/capabilities.py) mirroring "
    "parallel/alltoall.py's actual dtype branches.")

L002 = register_rule(
    "TPU-L002", WARN, "device-host ping-pong",
    "A CPU-placed operator sits between TPU-placed producer and consumer: "
    "every batch crosses between device and host twice, each crossing a "
    "sync that drains the dispatch pipeline, for one host operator.")

L003 = register_rule(
    "TPU-L003", ERROR, "host-only expression on a device operator",
    "A TPU-placed operator carries an expression with no device lowering "
    "(unregistered, disabled, or tagged host-only, e.g. regex).  The "
    "overrides engine should have kept the operator on CPU; executing it "
    "on device would fail or silently ship rows to host per batch.")

L004 = register_rule(
    "TPU-L004", ERROR, "driver-side whole-build collect above threshold",
    "A broadcast/build side whose estimated size exceeds "
    "spark.rapids.tpu.lint.maxDriverCollectBytes is collected whole "
    "(driver/device-resident single batch).  Spark chose a non-broadcast "
    "plan for such inputs precisely because they OOM the collector.")

L005 = register_rule(
    "TPU-L005", WARN, "JIT residency cache churn",
    "The plan's distinct (operator, schema) signatures exceed the "
    "compiled-program budget, or a scan pins an off-bucket batch "
    "capacity: each novel shape compiles a fresh XLA program family, "
    "evicting the residency cache (the round-5 multichip dryrun "
    "timeout).  Budget: spark.rapids.tpu.lint.maxCompiledPrograms; "
    "buckets: spark.rapids.tpu.batchCapacityBuckets.")

L006 = register_rule(
    "TPU-L006", ERROR, "partitioning contract consumed above rewrite",
    "An operator that assumes co-located/routed input (colocated hash "
    "join, FINAL-mode aggregate) sits above a subtree with no exchange "
    "to establish that contract — a rewrite stripped or reordered it, so "
    "the operator would silently merge unrouted rows (the bridge "
    "full-outer/per-partition class of wrong results).")

L007 = register_rule(
    "TPU-L007", WARN, "ICI exchange staging through host",
    "spark.rapids.shuffle.transport=ici is on but this exchange's column "
    "types cannot ride the all_to_all kernel, so rows silently stage "
    "through host Arrow — the accelerated transport is bypassed exactly "
    "where the plan moves the most data.")

L008 = register_rule(
    "TPU-L008", WARN, "opaque Python-UDF boundary in a device pipeline",
    "An out-of-process Python exchange operator (Arrow worker) consumes "
    "device-resident batches: every batch serializes to Arrow, crosses "
    "to the worker pool, and re-uploads.  Consider the UDF compiler "
    "(spark.rapids.sql.udfCompiler.enabled) or moving the UDF before "
    "upload.")

# rules whose host-fallback repair is sound (placement flip runs the
# identical xp-parameterized kernels on the host engine).  TPU-L011
# (contract broken by a rewrite) repairs exactly like L006: the flip
# clears the co-location assumption and the host path re-merges whole.
# TPU-L014 (peak over the HBM budget) first tries the cheaper repair —
# forcing the operator's out-of-core path (lifetime.try_outofcore_repair)
# — and host-flips only when no such path exists; the flip is sound
# because host RAM backs the working set instead of HBM.
# TPU-L009 is NOT here — a stale bind is wrong on either engine.
# TPU-L013/L015 are NOT here — a broken handle protocol (use-after-close
# / leak) is broken on either engine; only re-deriving the consumer
# count fixes it.
DOWNGRADE_CODES = {"TPU-L001", "TPU-L003", "TPU-L006", "TPU-L011",
                   "TPU-L014"}


# ---------------------------------------------------------------------------
# walk helpers
# ---------------------------------------------------------------------------

class LintContext:
    """What every rule check sees: the session conf plus (when the
    abstract interpreter ran) the per-node inferred states and liveness,
    so rules can be flow-sensitive with a syntactic fallback."""

    def __init__(self, conf: cfg.RapidsConf, interp=None):
        self.conf = conf
        self.interp = interp  # analysis.interp.InterpResult or None

    def get(self, entry):
        return self.conf.get(entry)

    def residency(self, node: eb.Exec) -> str:
        from .absdomain import DEVICE, HOST
        if self.interp is not None:
            return self.interp.residency(node)
        return DEVICE if node.placement == eb.TPU else HOST

    def live_names(self, node: eb.Exec):
        if self.interp is None:
            return None
        return self.interp.live_names(node)


def _walk(node: eb.Exec, parent: Optional[eb.Exec] = None, path: str = ""
          ) -> Iterator[Tuple[eb.Exec, Optional[eb.Exec], str]]:
    here = f"{path} > {node.name}" if path else node.name
    yield node, parent, here
    for c in node.children:
        yield from _walk(c, node, here)


def _aggregate_buffer_types(node) -> List[t.DataType]:
    out: List[t.DataType] = []
    for ae in getattr(node, "aggregates", []) or []:
        fn = getattr(ae, "func", None)
        if fn is None:
            continue
        try:
            out.extend(fn.buffer_types())
        except Exception:
            pass  # unbound aggregate: nothing provable about its buffers
    return out


def _is_exchange(node: eb.Exec) -> bool:
    from ..parallel.ici_exec import IciExchangeExec
    from ..shuffle.exchange import ShuffleExchangeExec
    return isinstance(node, (ShuffleExchangeExec, IciExchangeExec))


# ---------------------------------------------------------------------------
# per-node rule checks
# ---------------------------------------------------------------------------

def _check_ici_admit_mismatch(ctx, node, parent, path):
    if ctx.get(cfg.SHUFFLE_TRANSPORT) != "ici":
        return
    if not hasattr(node, "aggregates") or getattr(node, "grouping", None):
        return
    from ..parallel.alltoall import allgather_supported, exchange_supported
    bufs = _aggregate_buffer_types(node)
    if not bufs:
        return
    if exchange_supported(bufs) is None:
        reason = allgather_supported(bufs)
        if reason:
            bad = ", ".join(dt.name for dt in
                            ALLGATHER_BATCH.unsupported(bufs))
            yield L001.diag(
                f"ungrouped aggregate buffers [{bad}] pass the exchange "
                f"admission gate but {ALLGATHER_BATCH.name} raises on "
                f"them ({reason}); the ICI replicate path would crash "
                f"mid-query — route this aggregate to the host path",
                loc=path, node=node)


def _check_ping_pong(ctx, node, parent, path):
    # flow-sensitive: decided on the INFERRED residency (which knows
    # forwarding operators and transitions), not the raw placement flag
    from .absdomain import DEVICE, HOST
    if ctx.residency(node) != HOST or parent is None:
        return
    if getattr(node, "deliberate_cpu", False):
        return  # python exchange: TPU-L008's finding, not a planning slip
    if ctx.residency(parent) == DEVICE and \
            any(ctx.residency(c) == DEVICE for c in node.children):
        yield L002.diag(
            f"{node.name} runs on host between device-resident "
            f"{parent.name} and a device-resident child: two "
            f"interconnect crossings per batch", loc=path, node=node)


def _check_host_expr_on_device(ctx, node, parent, path):
    if node.placement != eb.TPU:
        return
    exprs = _node_expressions(node)
    if not exprs:
        return
    from ..plan.overrides import ExprMeta
    child = node.children[0] if node.children else None
    names = child.output_names if child is not None else []
    dtypes = child.output_types if child is not None else []
    for e in exprs:
        try:
            meta = ExprMeta(e, ctx.conf, names, dtypes)
            meta.tag()
        except Exception:
            continue  # unbindable here != hazard; tagging owns that call
        if not meta.can_replace_tree:
            reasons = "; ".join(meta.all_reasons()[:3])
            yield L003.diag(
                f"{type(e).__name__} on device-placed {node.name}: "
                f"{reasons}", loc=path, node=node)


def _node_expressions(node: eb.Exec):
    from ..exec.basic import FilterExec, ProjectExec
    if isinstance(node, ProjectExec):
        return list(node.exprs)
    if isinstance(node, FilterExec):
        return [node.condition]
    return []


def _check_driver_collect(ctx, node, parent, path):
    from ..exec.broadcast import BroadcastExchangeExec
    from ..exec.join import HashJoinExec
    cap = ctx.get(cfg.LINT_MAX_DRIVER_COLLECT)
    build = None
    if isinstance(node, BroadcastExchangeExec):
        build = node.children[0]
    elif isinstance(node, HashJoinExec) and \
            not getattr(node, "colocated", False):
        # plain hash join concatenates its whole build side into one
        # batch (the bridge's executeCollect analog)
        build = node.children[1]
        if isinstance(build, BroadcastExchangeExec):
            build = None  # already reported at the exchange itself
    if build is None:
        return
    est = build.estimated_size_bytes()
    if est is not None and est > cap:
        yield L004.diag(
            f"{node.name} collects a ~{max(est >> 10, 1)} KiB build "
            f"side whole (threshold {cap >> 10} KiB); gate the "
            f"translation on the size estimate or broadcast-partition "
            f"it", loc=path, node=node)


def _check_ici_host_staging(ctx, node, parent, path):
    if ctx.get(cfg.SHUFFLE_TRANSPORT) != "ici":
        return
    from ..shuffle.exchange import ShuffleExchangeExec
    if not isinstance(node, ShuffleExchangeExec):
        return
    from ..parallel.alltoall import exchange_supported
    reason = exchange_supported(node.output_types)
    if reason:
        # flow-sensitive refinement: if only columns nothing above reads
        # block the transport, the real fix is dropping them (TPU-L010)
        hint = ""
        live = ctx.live_names(node)
        if live is not None:
            live_types = [dt for n, dt in zip(node.output_names,
                                              node.output_types)
                          if n in live]
            if exchange_supported(live_types) is None:
                hint = (" — only columns nothing above the exchange "
                        "reads block the transport; dropping them "
                        "(see TPU-L010) restores ICI")
        yield L007.diag(
            f"exchange falls off the ICI transport: {reason}{hint}",
            loc=path, node=node)


def _check_udf_boundary(ctx, node, parent, path):
    from ..exec.python_udf import ArrowEvalPythonExec
    opaque = getattr(node, "deliberate_cpu", False) or \
        isinstance(node, ArrowEvalPythonExec)
    if not opaque:
        return
    if any(c.placement == eb.TPU for c in node.children):
        yield L008.diag(
            f"{node.name} consumes device-resident batches through the "
            f"Arrow worker boundary (serialize + re-upload per batch)",
            loc=path, node=node)


def _check_partition_contract(ctx, node, parent, path):
    # flow-sensitive mode subsumes this: interp evaluates the operator's
    # declared input_contracts() against the INFERRED distribution (so a
    # filter/project between exchange and consumer no longer hides the
    # contract, and a wrong-keyed exchange no longer satisfies it)
    if ctx.interp is not None:
        return
    from ..exec.aggregate import TpuHashAggregateExec
    from ..exec.join import HashJoinExec
    from ..expr.aggregates import FINAL
    if isinstance(node, HashJoinExec) and \
            getattr(node, "colocated", False):
        if not all(_is_exchange(c) for c in node.children):
            yield L006.diag(
                "colocated hash join without an establishing exchange "
                "under both sides: matching keys are not co-located, "
                "per-partition results would be wrong", loc=path,
                node=node)
    if isinstance(node, TpuHashAggregateExec) and node.mode == FINAL \
            and node.grouping:
        child = node.children[0]
        if not (_is_exchange(child) or
                isinstance(child, TpuHashAggregateExec)):
            yield L006.diag(
                "FINAL-mode aggregate above a non-exchange child: "
                "partial buffers for one group may live in several "
                "partitions and would never merge", loc=path, node=node)


_NODE_CHECKS = [
    _check_ici_admit_mismatch,
    _check_ping_pong,
    _check_host_expr_on_device,
    _check_driver_collect,
    _check_ici_host_staging,
    _check_udf_boundary,
    _check_partition_contract,
]


# ---------------------------------------------------------------------------
# plan-level checks
# ---------------------------------------------------------------------------

def _check_compile_churn(conf, root) -> Iterator[Diagnostic]:
    budget = conf.get(cfg.LINT_MAX_PROGRAMS)
    shapes = set()
    buckets = set(conf.capacity_buckets)
    from ..exec.basic import LocalScanExec
    for node, _parent, path in _walk(root):
        if node.placement == eb.TPU:
            try:
                shapes.add((type(node).__name__, eb.schema_sig(node)))
            except Exception:
                pass
        if isinstance(node, LocalScanExec) and node.batch_rows and \
                node.batch_rows not in buckets:
            yield L005.diag(
                f"scan pins off-bucket batch capacity "
                f"{node.batch_rows} (buckets: "
                f"{sorted(buckets)}): every such capacity compiles a "
                f"fresh program family per operator above it",
                loc=path, node=node)
    if len(shapes) > budget:
        yield L005.diag(
            f"plan spans ~{len(shapes)} distinct compiled-program "
            f"shapes (budget {budget}); the JIT residency cache will "
            f"churn — coalesce schemas or raise "
            f"spark.rapids.tpu.lint.maxCompiledPrograms", loc=root.name,
            node=None)


# ---------------------------------------------------------------------------
# front end
# ---------------------------------------------------------------------------

def lint_plan(root: eb.Exec, conf: cfg.RapidsConf,
              infer: Optional[bool] = None) -> List[Diagnostic]:
    """Analyze a converted physical plan; returns sorted diagnostics
    (most severe first).  Pure — never mutates (or executes) the plan.

    `infer` controls the flow-sensitive mode: the abstract interpreter
    (analysis/interp.py) runs first, its per-node states upgrade
    L002/L006/L007 from syntactic to flow-sensitive and add the
    boundary rules L009-L012.  Default comes from
    spark.rapids.tpu.lint.infer (on); a failed interpretation degrades
    to the syntactic rules rather than killing planning."""
    if infer is None:
        infer = conf.get(cfg.LINT_INFER)
    diags: List[Diagnostic] = []
    interp_result = None
    if infer:
        try:
            from .interp import infer_plan
            interp_result = infer_plan(root, conf)
            diags.extend(interp_result.diags)
        except Exception as ex:  # degrade to syntactic, never kill planning
            interp_result = None
            diags.append(Diagnostic(
                "TPU-L000", INFO,
                f"abstract interpreter failed ({ex}); syntactic rules "
                f"only", loc=root.name))
        if interp_result is not None:
            # tmsan lifetime/peak pass (TPU-L013..L015) rides the same
            # inferred states; a failure degrades like the interpreter
            try:
                from .lifetime import analyze_memory
                diags.extend(
                    analyze_memory(root, conf, interp_result).diags)
            except Exception as ex:
                diags.append(Diagnostic(
                    "TPU-L000", INFO,
                    f"lifetime pass failed ({ex}); memory rules "
                    f"skipped", loc=root.name))
    ctx = LintContext(conf, interp_result)
    for node, parent, path in _walk(root):
        for check in _NODE_CHECKS:
            try:
                diags.extend(check(ctx, node, parent, path) or ())
            except Exception as ex:  # a broken rule must not kill planning
                diags.append(Diagnostic(
                    "TPU-L000", INFO,
                    f"lint rule {check.__name__} failed: {ex}", loc=path))
    diags.extend(_check_compile_churn(conf, root))
    if conf.get(cfg.DSAN_ENABLED):
        # tpudsan replay-class composition (TPU-L016) rides the same
        # pre-flight; a failed pass degrades like the interpreter
        try:
            from .determinism import classify_plan
            diags.extend(classify_plan(root, conf).diags)
        except Exception as ex:
            diags.append(Diagnostic(
                "TPU-L000", INFO,
                f"determinism pass failed ({ex}); replay rules "
                f"skipped", loc=root.name))
    if conf.get(cfg.XSAN_ENABLED) and interp_result is not None:
        # tpuxsan program-efficiency rules (TPU-L018/L020) ride the
        # same interp states; a failed pass degrades like the others
        try:
            from .hloaudit import audit_plan
            diags.extend(audit_plan(root, conf, interp_result))
        except Exception as ex:
            diags.append(Diagnostic(
                "TPU-L000", INFO,
                f"tpuxsan pass failed ({ex}); efficiency rules "
                f"skipped", loc=root.name))
    disabled = conf.raw("spark.rapids.tpu.lint.disable", "") or ""
    return sort_diagnostics(filter_suppressed(diags, disabled.split(",")))


def downgrade_hazards(root: eb.Exec, diags: List[Diagnostic],
                      conf: Optional[cfg.RapidsConf] = None) -> eb.Exec:
    """Apply the sound repairs: flagged subtrees (DOWNGRADE_CODES with
    error severity) fall back to the host engine — placement flips to
    CPU (the xp-parameterized kernels run identically on numpy), fused
    ICI stages restore their host-path originals, and broken co-location
    assumptions are cleared.  insert_transitions then brackets the
    boundary as usual.

    TPU-L014 (peak over the HBM budget) gets the cheaper repair first:
    operators with a spill-managed fallback are forced out-of-core
    (oc_budget) and stay on device; only nodes without such a path
    host-flip."""
    repaired: set = set()
    if conf is not None:
        from .lifetime import try_outofcore_repair
        for d in diags:
            if d.code == "TPU-L014" and d.node is not None:
                try:
                    if try_outofcore_repair(root, d.node, conf):
                        repaired.add(id(d.node))
                except Exception:
                    pass  # fall through to the host flip
        # TPU-L016 has its own in-place repair (force the aggregate's
        # canonical keyed merge under the flagged boundary); a host
        # flip would NOT help — order dependence is engine-independent
        # — so L016 never joins the flip set below
        from .determinism import try_stabilize_repair
        for d in diags:
            if d.code == "TPU-L016" and d.node is not None:
                try:
                    if try_stabilize_repair(root, d.node, conf):
                        repaired.add(id(d.node))
                except Exception:
                    pass  # unrepairable: diagnostic stands
        # TPU-L018's repair re-buckets the nearest filter speculatively
        # (hloaudit.try_rebucket_repair); a host flip would trade
        # padding for losing the device entirely, so like L016 it never
        # joins the flip set below
        from .hloaudit import try_rebucket_repair
        for d in diags:
            if d.code == "TPU-L018" and d.node is not None:
                try:
                    if try_rebucket_repair(root, d.node, conf):
                        repaired.add(id(d.node))
                except Exception:
                    pass  # unrepairable: diagnostic stands
    flagged = {id(d.node) for d in diags
               if d.node is not None and d.is_error and
               d.code in DOWNGRADE_CODES and id(d.node) not in repaired}
    if not flagged:
        return root

    from ..parallel import ici_exec as ici

    def restore_host(node: eb.Exec) -> eb.Exec:
        if isinstance(node, ici.IciAggregateExec):
            return node.final_agg
        if isinstance(node, ici.IciSortExec):
            return node.sort_exec
        if isinstance(node, ici.IciJoinExec):
            return node.join_exec
        if isinstance(node, ici.IciExchangeExec):
            return node.exchange
        return node

    def to_host(node: eb.Exec) -> eb.Exec:
        node = restore_host(node)
        node.placement = eb.CPU
        if hasattr(node, "colocated"):
            node.colocated = False
        for c in node.children:
            to_host(c)
        return node

    def fix(node: eb.Exec) -> eb.Exec:
        if id(node) in flagged:
            return to_host(node)
        new_children = [fix(c) for c in node.children]
        if any(a is not b for a, b in zip(new_children, node.children)):
            node = node.with_new_children(new_children)
        return node

    return fix(root)


# ---------------------------------------------------------------------------
# event-log front end (qualification surfacing)
# ---------------------------------------------------------------------------

# marker -> (rule, message); matched against lowercased node text of a
# parsed Spark plan (tools/eventlog.py PlanNode) — the offline analog of
# the exec-tree rules above, so qualification reports carry the same
# TPU-Lxxx vocabulary
_SPARK_PLAN_MARKERS = [
    (("rlike", "regexp_extract", "regexp_replace"), L003,
     "regex expression evaluates on the host engine"),
    (("udf",), L008, "opaque UDF forces an Arrow worker boundary"),
    (("cartesianproduct", "broadcastnestedloopjoin"), L004,
     "whole-side collect/replication join"),
]


def lint_spark_plan(plan) -> List[Diagnostic]:
    """Heuristic text-level lint of a parsed event-log plan (PlanNode).
    Severities are capped at WARN: without types/configs nothing here is
    provably fatal — the codes exist so qualification output speaks the
    same rule vocabulary as the live plan lint."""
    diags: List[Diagnostic] = []
    seen = set()
    for node in plan.walk():
        text = (node.node_name + " " + node.simple_string).lower()
        for markers, rule, msg in _SPARK_PLAN_MARKERS:
            if any(m in text for m in markers):
                key = (rule.code, node.node_name)
                if key in seen:
                    continue
                seen.add(key)
                sev = WARN if rule.severity == ERROR else rule.severity
                diags.append(rule.diag(f"{msg} ({node.node_name})",
                                       loc=node.node_name,
                                       severity=sev))
        if "hashaggregate(keys=[]" in text.replace(" ", "") and \
                ("collect_list" in text or "collect_set" in text):
            key = ("TPU-L001", node.node_name)
            if key not in seen:
                seen.add(key)
                diags.append(L001.diag(
                    "global collect_list/collect_set: array buffers "
                    "cannot ride the ICI replicate path "
                    f"({node.node_name})", loc=node.node_name,
                    severity=WARN))
    return sort_diagnostics(diags)
