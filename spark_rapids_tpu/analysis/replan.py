"""Exchange-boundary re-planning: act on measured map-output sizes
BEFORE the reduce side launches.

A shuffle materializes its map stage the first time any reduce
partition is requested (shuffle/aqe.py), which means real per-partition
byte counts exist at exactly the point Spark's AQE re-plans between
query stages.  The coalesce/skew rules already consume them locally;
this module closes the loop for the three decisions that live ABOVE
the reader:

  * **strategy_switch** — the measured exchange output is off the
    predicted size by at least ``feedback.replan.misestimateFactor``
    (either direction): pin ``no_speculation`` on the query's execution
    context so the operators still to run size their outputs exactly (a
    filter's armed re-bucket is skipped) instead of gambling on a
    capacity guess they would lose.  This supersedes the after-the-fact
    ``SpeculativeSizingMiss`` retry on this path — the misestimate is
    caught from the map statistics, not from a failed guard after the
    reduce side already ran.
  * **oc_repair** — re-run the abstract interpreter over the plan with
    the exchange's row estimate overridden by the measured one; if the
    re-derived peak-HBM bound overshoots the admission budget, force
    the out-of-core repair (TPU-L014) on the repairable frontier now,
    while the reduce side is still unlaunched.
  * **ticket_reprice** — hand the sharpened bound to
    ``AdmissionController.reprice`` so the live ticket's reservation
    is truthful for the rest of the query.

Every decision is triple-sunk — a ``replan`` span in the flight
recorder, ``tpu_replan_total{decision,cause}`` in the metrics registry,
and a ``replan`` event in the estimator ledger — so the three surfaces
can be cross-checked (the --feedback CI gate does exactly that).

The context is installed thread-locally by the session around
``execute_collect``; partition iteration is driver-threaded, so the
reader's ``specs()`` call lands on the installing thread.  Everything
here is advisory: any failure degrades to the static plan, never the
query.
"""

from __future__ import annotations

import logging
import threading
from typing import List, Optional

from .. import config as cfg

log = logging.getLogger(__name__)

_tls = threading.local()


class ReplanContext:
    """One query's re-planning state: the plan being executed, its
    admission ticket, and the shuffles already considered (each
    exchange boundary is re-planned at most once per execution)."""

    __slots__ = ("plan_root", "conf", "ticket", "controller", "tracer",
                 "exec_ctx", "seen", "decisions")

    def __init__(self, plan_root, conf, ticket, controller, tracer,
                 exec_ctx):
        self.plan_root = plan_root
        self.conf = conf
        self.ticket = ticket
        self.controller = controller
        self.tracer = tracer
        self.exec_ctx = exec_ctx
        self.seen = set()
        self.decisions: List = []


def install(ctx: ReplanContext) -> None:
    _tls.ctx = ctx


def uninstall() -> None:
    _tls.ctx = None


def current() -> Optional[ReplanContext]:
    return getattr(_tls, "ctx", None)


def on_map_stage_materialized(read_node, shuffle_id: int,
                              sizes: List[int]) -> None:
    """The AQE reader's callback, right after ``partition_stats``
    measured the freshly written map output."""
    ctx = current()
    if ctx is None:
        return
    try:
        _replan(ctx, read_node, shuffle_id, sizes)
    except Exception:
        log.debug("exchange-boundary replan skipped", exc_info=True)


def scan_materialized(ctx: ReplanContext) -> None:
    """Replay boundaries that materialized BEFORE the context existed:
    plan surgery (overrides' transition insertion) queries the root's
    ``num_partitions``, which walks down to the probe-side AQE reader
    and forces its map stage at plan time — before admission has issued
    a ticket or the session could install this context.  The session
    calls this right after installing, still ahead of the first reduce
    partition, so those boundaries get the same treatment as ones that
    materialize mid-execution."""
    try:
        from ..shuffle.aqe import partition_stats

        def visit(node):
            if not (hasattr(node, "exchange")
                    and hasattr(node, "_specs")):
                return
            if getattr(node, "replicate_for", None) is not None:
                return  # mirrors its partner; no stats of its own
            sid = getattr(node.exchange, "_shuffle_id", None)
            if sid is None or sid in ctx.seen:
                return  # map stage not written yet: specs() will call
            sizes = partition_stats(sid, node.exchange.num_partitions)
            _replan(ctx, node, sid, sizes)

        ctx.plan_root.foreach(visit)
    except Exception:
        log.debug("replan scan skipped", exc_info=True)


def _replan(ctx: ReplanContext, read_node, shuffle_id: int,
            sizes: List[int]) -> None:
    conf = ctx.conf
    if not conf.get(cfg.FEEDBACK_ENABLED):
        return
    if shuffle_id in ctx.seen:
        return
    ctx.seen.add(shuffle_id)

    exchange = getattr(read_node, "exchange", None)
    preds = getattr(ctx.tracer, "predictions", {}) \
        if ctx.tracer is not None else {}
    pred = preds.get(id(exchange)) if exchange is not None else None
    measured_bytes = int(sum(sizes))
    measured_rows = _measured_rows(shuffle_id, len(sizes))
    pred_bytes = pred.get("bytes") if pred else None
    pred_rows = pred.get("rows") if pred else None

    # the misestimate factor keys on ROWS when both sides know them
    # (the row model is what feedback sharpens; byte totals can be
    # right for the wrong reasons), bytes otherwise
    factor = None
    if measured_rows is not None and pred_rows:
        factor = measured_rows / max(float(pred_rows), 1.0)
    elif pred_bytes:
        factor = measured_bytes / max(float(pred_bytes), 1.0)

    rf = conf.get(cfg.FEEDBACK_REPLAN_FACTOR)
    tripped = factor is not None and \
        (factor >= rf or factor <= 1.0 / rf)
    cause = "row_misestimate" if tripped else "sizing_update"

    def sink(decision: str, **extra) -> None:
        # triple sink: span + metric + ledger must always agree
        from ..obs.estimator import EstimatorLedger
        from ..obs.tracer import trace_span
        ctx.decisions.append((decision, cause))
        with trace_span("replan", kind="replan", decision=decision,
                        cause=cause, shuffle_id=shuffle_id,
                        measured_bytes=measured_bytes,
                        predicted_bytes=pred_bytes,
                        factor=None if factor is None
                        else round(factor, 4), **extra):
            pass
        EstimatorLedger.get().record_replan(
            decision, cause, shuffle_id=shuffle_id,
            measured_bytes=measured_bytes, predicted_bytes=pred_bytes,
            factor=None if factor is None else round(factor, 4),
            **extra)

    if tripped and ctx.exec_ctx is not None and \
            not ctx.exec_ctx.task_context.get("no_speculation"):
        # exact sizing for every operator still to run — the reduce
        # side shares this context
        ctx.exec_ctx.task_context["no_speculation"] = True
        sink("strategy_switch")

    if measured_rows is None or exchange is None or \
            ctx.ticket is None or ctx.controller is None:
        return
    overrides = {id(exchange): float(measured_rows)}
    bound = _rebound(ctx, conf, overrides)
    if bound is None:
        return
    if bound > ctx.controller.budget_bytes:
        if _oc_repair(ctx, overrides):
            sink("oc_repair", new_bound=bound)
            bound = _rebound(ctx, conf, overrides) or bound
    delta = ctx.controller.reprice(ctx.ticket, bound)
    if delta:
        sink("ticket_reprice", new_bound=int(bound), delta=delta)


def _measured_rows(shuffle_id: int, n_parts: int) -> Optional[int]:
    """Exact row count of the materialized map output, straight from
    the shuffle catalog's block metadata (same walk as
    ``partition_stats``, reading rows instead of bytes)."""
    try:
        from ..shuffle.manager import TpuShuffleManager
        mgr = TpuShuffleManager.get()
        total = 0
        for rid in range(n_parts):
            for blk in mgr.catalog.blocks_for_reduce(shuffle_id, rid):
                for b in mgr.catalog.get(blk):
                    total += getattr(b, "num_rows", 0) or 0
        return total
    except Exception:
        return None


def _rebound(ctx: ReplanContext, conf, overrides) -> Optional[int]:
    """The plan's peak-HBM bound with the measured exchange rows
    substituted into the abstract interpretation."""
    try:
        from .interp import infer_plan
        from .lifetime import analyze_memory
        interp = infer_plan(ctx.plan_root, conf,
                            row_overrides=overrides)
        mem = analyze_memory(ctx.plan_root, conf, interp)
        b = mem.bound(ctx.plan_root)
        return None if b is None else int(b)
    except Exception:
        return None


def _oc_repair(ctx: ReplanContext, overrides) -> bool:
    """Force out-of-core mode on the repairable frontier against the
    ADMISSION budget (mirrors the session's pre-admission repair, but
    driven by measured rows and run before the reduce side starts)."""
    try:
        from .interp import infer_plan
        from .lifetime import analyze_memory, try_outofcore_repair
        conf2 = ctx.conf.set(cfg.MEMSAN_HBM_BUDGET.key,
                             int(ctx.controller.budget_bytes))
        interp = infer_plan(ctx.plan_root, conf2,
                            row_overrides=overrides)
        res = analyze_memory(ctx.plan_root, conf2, interp)
        done = False
        for d in res.diags:
            if d.code == "TPU-L014" and d.node is not None:
                try:
                    done = try_outofcore_repair(
                        ctx.plan_root, d.node, conf2) or done
                except Exception:
                    pass  # unrepairable node: keep the honest bound
        return done
    except Exception:
        return False
