"""tpuxsan: compiled-program efficiency pass.

The compile observatory (obs/compileprof.py) already answers *when* we
compile and *what it costs in seconds*; nothing answered whether the
programs we compile are any good.  This pass closes that gap with three
static checks over the artifacts the observatory now persists — lowered
StableHLO text and XLA's own ``cost_analysis()`` per program — plus the
interp's row/byte states for the plan-side twin:

* **padding waste** (TPU-L018) — the capacity-bucket discipline that
  keeps compile counts finite also pads every launch; when the interp
  says a subtree's live rows are a sliver of the bucket it lands in,
  most of the memory traffic is padding.  Repairable: the pre-flight
  re-buckets the nearest filter through the speculative-sizing
  machinery (ExecContext's deferred guards: the guarded shrink
  re-executes on a missed guess).
* **host round-trips inside programs** (TPU-L019) — a host callback or
  send/recv lowered INTO a compiled program serializes every launch on
  the host; found by parsing the persisted StableHLO, not by guessing
  from Python source.
* **fusion / materialization hazards** (TPU-L020) — adjacent
  memory-bound programs over a shared intermediate pay two sweeps where
  one fused kernel would pay none for the handoff; plus broadcasts that
  materialize above ``spark.rapids.tpu.xsan.broadcastBytesMax``.  These
  are the Pallas targets the kernel-gap report ranks.
* **kernel-table bypass** (TPU-R017) — a raw ``jnp.*``/``lax.*`` call
  in exec// ops/ outside a function registered in the device-kernel
  table (analysis/capabilities.py DEVICE_KERNELS) is a kernel the audit
  cannot see or cost; register it or annotate the deliberate exception.

The analytic cost model lives in analysis/hlocost.py; the --hlo gate
(devtools/run_lint.py) cross-validates it against cost_analysis() on
the golden corpus and fails on drift — a lying cost model is worse
than none.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from .diagnostics import Diagnostic, ERROR, WARN, register_rule
from . import hlocost

# ---------------------------------------------------------------------------
# rule registrations
# ---------------------------------------------------------------------------

L018 = register_rule(
    "TPU-L018", ERROR, "launch padding dominates a subtree's traffic",
    "The interp's row estimate for a subtree is a sliver of the "
    "capacity bucket its launches pad to: the waste ratio exceeds "
    "spark.rapids.tpu.xsan.padWasteMax and the wasted bytes clear "
    "spark.rapids.tpu.xsan.padWasteMinBytes, so most of the memory "
    "traffic (and HBM residency) is padding.  Repairable: the "
    "pre-flight re-buckets the nearest filter speculatively — output "
    "shrinks to a right-sized bucket under a deferred guard, and a "
    "missed guess re-executes without speculation.  The runtime twin "
    "is the "
    "tpu_pad_waste_bytes_total{exec} counter booked by obs/tracer.py.")

L019 = register_rule(
    "TPU-L019", ERROR, "host transfer inside a compiled program",
    "The persisted StableHLO for a compiled program contains a host "
    "callback custom_call or a send/recv on the result path: every "
    "launch of this program serializes on a device->host->device round "
    "trip, which defeats the async dispatch pipeline the engine is "
    "built around.  Found in the artifact XLA actually compiles, not "
    "inferred from Python source.  Hoist the host work out of the "
    "jitted function or replace the callback with a device kernel.")

L020 = register_rule(
    "TPU-L020", WARN, "fusion break between memory-bound programs",
    "Two adjacent memory-bound programs share an intermediate large "
    "enough that writing it out of one program and reading it back "
    "into the next costs more than either program's own arithmetic: a "
    "single fused kernel (the Pallas target list) would erase the "
    "handoff entirely.  Also flags a broadcast_in_dim that "
    "materializes above spark.rapids.tpu.xsan.broadcastBytesMax "
    "inside one program.  Advisory: these rank the kernel-gap report "
    "(tools kernel-report), they do not block a plan.")

R017 = register_rule(
    "TPU-R017", ERROR, "raw jnp/lax call bypasses the kernel table",
    "A jnp.* / lax.* call in exec/ or ops/ sits outside any function "
    "registered in the device-kernel table "
    "(analysis/capabilities.py DEVICE_KERNELS): the efficiency audit "
    "can neither cost nor gate a kernel it does not know exists, and "
    "the xp-parameterization convention (kernels take `xp` so the host "
    "path runs the same code on numpy) silently breaks.  Register the "
    "entry point or annotate the deliberate exception "
    "`# tpulint: allow[TPU-R017]` in place.  Dtype constructors "
    "(jnp.int64 and friends) and asarray are exempt — they carry no "
    "kernel semantics.")

# ---------------------------------------------------------------------------
# StableHLO text hazards (the artifact XLA actually compiles)
# ---------------------------------------------------------------------------

# `stablehlo.custom_call @target(...)` / `call_target_name = "target"`
_CUSTOM_CALL = re.compile(
    r"custom_call\s*@([\w.$-]+)|call_target_name\s*=\s*\"([^\"]+)\"")
_HOST_TARGET = re.compile(r"callback|host|infeed|outfeed", re.I)
_SEND_RECV = re.compile(r"\bstablehlo\.(send|recv)\b")
_BROADCAST = re.compile(r"broadcast_in_dim")
# result tensor types: `tensor<4000x8xi64>`, `tensor<f32>` (scalar)
_TENSOR = re.compile(r"tensor<([0-9]+(?:x[0-9]+)*x)?([a-z][a-z0-9]*)>")


def _elem_bytes(mlir_dtype: str) -> int:
    """Width of one MLIR element type name ('i64' -> 8, 'f32' -> 4,
    'i1' -> 1)."""
    m = re.search(r"(\d+)$", mlir_dtype)
    if not m:
        return 4
    return max(1, int(m.group(1)) // 8)


def _tensor_bytes(dims: Optional[str], dtype: str) -> int:
    n = 1
    for d in (dims or "").split("x"):
        if d.isdigit():
            n *= max(int(d), 1)
    return n * _elem_bytes(dtype)


def parse_hlo_hazards(text: str, broadcast_max: int) -> Dict[str, List]:
    """Line-oriented hazard scan over one persisted StableHLO module.

    Returns {"host_transfers": [(lineno, target)],
             "big_broadcasts": [(lineno, bytes)]}.  Pure text — no MLIR
    bindings required, so the audit runs on a cold CI checkout against
    artifacts recorded on any backend."""
    host: List[Tuple[int, str]] = []
    casts: List[Tuple[int, int]] = []
    for lineno, line in enumerate(text.splitlines(), start=1):
        m = _SEND_RECV.search(line)
        if m:
            host.append((lineno, f"stablehlo.{m.group(1)}"))
            continue
        if "custom_call" in line:
            cm = _CUSTOM_CALL.search(line)
            target = (cm.group(1) or cm.group(2)) if cm else ""
            if target and _HOST_TARGET.search(target):
                host.append((lineno, target))
            continue
        if _BROADCAST.search(line):
            # the result type is the LAST tensor type on the line
            # (`... -> tensor<...>`); operands come first
            types = _TENSOR.findall(line)
            if types:
                dims, dtype = types[-1]
                b = _tensor_bytes(dims, dtype)
                if b > broadcast_max:
                    casts.append((lineno, b))
    return {"host_transfers": host, "big_broadcasts": casts}


def audit_ledger(records: Iterable[Dict], hlo_dir: Optional[str],
                 broadcast_max: int) -> List[Diagnostic]:
    """TPU-L019 / TPU-L020(broadcast) over a compile ledger's persisted
    programs.  Records without a persisted artifact are skipped — the
    observatory caps and dedupes what it writes, and absence of an
    artifact is absence of evidence, never a clean bill."""
    diags: List[Diagnostic] = []
    if not hlo_dir or not os.path.isdir(hlo_dir):
        return diags
    seen: set = set()
    for rec in records:
        if rec.get("event") != "build":
            continue
        h = rec.get("hlo_hash")
        if not h or h in seen:
            continue
        seen.add(h)
        from ..obs.compileprof import HLO_SUFFIX
        path = os.path.join(hlo_dir, f"{h}{HLO_SUFFIX}")
        if not os.path.exists(path):
            continue
        try:
            with open(path, encoding="utf-8") as f:
                text = f.read()
        except OSError:
            continue
        kind = rec.get("exec", "?")
        haz = parse_hlo_hazards(text, broadcast_max)
        for lineno, target in haz["host_transfers"]:
            diags.append(L019.diag(
                f"compiled {kind} program {h} lowers a host transfer "
                f"({target}) on its result path: every launch "
                f"serializes on the host round trip",
                loc=f"{kind}:{h}:{lineno}"))
        for lineno, nbytes in haz["big_broadcasts"]:
            diags.append(L020.diag(
                f"compiled {kind} program {h} materializes a "
                f"{nbytes / (1 << 20):.1f} MiB broadcast_in_dim "
                f"(budget {broadcast_max / (1 << 20):.0f} MiB): a "
                f"fused kernel would never write the expansion",
                loc=f"{kind}:{h}:{lineno}"))
    return diags


# ---------------------------------------------------------------------------
# plan-side audit (TPU-L018 padding waste, TPU-L020 fusion breaks)
# ---------------------------------------------------------------------------

def audit_plan(root, conf, infer_result) -> List[Diagnostic]:
    """Static efficiency rules over one converted plan, riding the
    interp states the pre-flight already computed.  Pure — the L018
    repair mutates only inside downgrade_hazards, like every other
    repairable rule."""
    from .. import config as cfg
    diags: List[Diagnostic] = []
    if infer_result is None:
        return diags

    max_ratio = conf.get(cfg.XSAN_PAD_WASTE_MAX)
    min_bytes = conf.get(cfg.XSAN_PAD_WASTE_MIN_BYTES)
    for w in hlocost.plan_pad_waste(root, conf, infer_result):
        if w["waste_ratio"] > max_ratio and w["waste_bytes"] >= min_bytes:
            diags.append(L018.diag(
                f"~{w['rows']:.0f} live rows pad to a "
                f"{w['capacity']}-row bucket: "
                f"{100 * w['waste_ratio']:.1f}% of the launch "
                f"(~{w['waste_bytes'] / (1 << 20):.1f} MiB/batch) is "
                f"padding traffic (budget {100 * max_ratio:.0f}%); "
                f"re-bucketing repairs this pre-flight",
                loc=w["path"], node=w["node"]))

    diags.extend(_fusion_breaks(root, conf, infer_result, min_bytes))
    return diags


def _fusion_breaks(root, conf, infer_result,
                   min_bytes: int) -> List[Diagnostic]:
    """TPU-L020: parent/child pairs of memory-bound device programs
    whose shared intermediate is large enough that the handoff (one
    write + one read of the intermediate) dominates either side's
    arithmetic — the cost model's fused estimate beats the sum."""
    from ..exec import base as eb
    from .absdomain import schema_width
    diags: List[Diagnostic] = []

    def walk(node, path):
        here = f"{path} > {node.name}" if path else node.name
        for c in node.children:
            pk = type(node).__name__
            ck = type(c).__name__
            if (pk in hlocost.KIND_PASSES and ck in hlocost.KIND_PASSES
                    and getattr(node, "placement", None) == eb.TPU
                    and getattr(c, "placement", None) == eb.TPU):
                st = infer_result.states.get(id(c))
                rows = getattr(st, "rows", None) if st is not None \
                    else None
                if rows and rows > 0:
                    inter = float(rows) * schema_width(c.output_types)
                    if inter >= min_bytes:
                        diags.append(L020.diag(
                            f"{ck} -> {pk} hand off a "
                            f"~{inter / (1 << 20):.1f} MiB intermediate "
                            f"between two memory-bound programs: a "
                            f"fused kernel saves "
                            f"~{2 * inter / (1 << 20):.1f} MiB of "
                            f"traffic per pass (kernel-gap report "
                            f"target)", loc=here, node=node))
            walk(c, here)

    walk(root, "")
    return diags


# ---------------------------------------------------------------------------
# the TPU-L018 repair: speculative re-bucketing
# ---------------------------------------------------------------------------

def try_rebucket_repair(root, node, conf) -> bool:
    """Arm the nearest FilterExec at-or-below the flagged subtree with a
    speculative output bucket sized from the interp's survivor
    estimate.  The filter then shrinks its compacted output to the
    right-sized bucket under a deferred guard
    (ExecContext.add_spec_guard); an undershoot raises
    SpeculativeSizingMiss and the session re-executes with speculation
    disabled — results built on a missed guess are never surfaced.
    Returns True when a repair was armed."""
    from ..columnar.device import bucket_for
    from ..exec.basic import FilterExec

    target = None
    stack = [node]
    while stack:
        n = stack.pop()
        if isinstance(n, FilterExec):
            target = n
            break
        stack.extend(n.children)
    if target is None:
        return False

    from .interp import infer_plan
    states = infer_plan(root, conf).states
    st = states.get(id(target))
    rows = getattr(st, "rows", None) if st is not None else None
    if not rows or rows <= 0:
        return False
    # 1.5x headroom over the estimate: estimates are calibrated, not
    # exact, and a re-execution costs far more than half a bucket
    cap = bucket_for(max(int(rows * 1.5), int(rows) + 1),
                     conf.capacity_buckets)
    child_st = states.get(id(target.children[0]))
    in_rows = getattr(child_st, "rows", None) \
        if child_st is not None else None
    if in_rows and in_rows > 0:
        in_cap = bucket_for(int(in_rows), conf.capacity_buckets)
        if cap >= in_cap:
            return False  # no shrink: the repair would be a no-op
    target.rebucket_cap = int(cap)
    return True


# ---------------------------------------------------------------------------
# TPU-R017: raw jnp/lax calls outside the kernel table
# ---------------------------------------------------------------------------

_R017_PATHS = ("exec/", "ops/")
# dtype constructors / wrappers carry no kernel semantics
_BENIGN_TAILS = {
    "int8", "int16", "int32", "int64", "uint8", "uint16", "uint32",
    "uint64", "float16", "float32", "float64", "bool_", "asarray",
    "dtype", "ndarray", "issubdtype",
}


def _func_chain(f) -> List[str]:
    parts: List[str] = []
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
    return list(reversed(parts))


class _RawXlaCallVisitor:
    """TPU-R017 over one module (scope tracking via repo_lint's
    _ScopedVisitor, shared with every other repo rule)."""

    def __init__(self, relpath: str):
        from .capabilities import device_kernel_functions
        from .repo_lint import _ScopedVisitor
        outer = self

        class V(_ScopedVisitor):
            def visit_Call(self, node):
                outer._call(node, self.scope)
                self.generic_visit(node)

        self.relpath = relpath
        self._registered = device_kernel_functions(relpath)
        self.diags: List[Diagnostic] = []
        self._v = V()

    def visit(self, tree):
        self._v.visit(tree)

    def _call(self, node, scope: str):
        chain = _func_chain(node.func)
        if len(chain) < 2:
            return
        head = chain[0]
        if head == "jax" and len(chain) >= 3 and chain[1] in ("lax",
                                                              "numpy"):
            head, chain = chain[1], chain[1:]
        if head not in ("jnp", "lax"):
            return
        tail = chain[-1]
        if tail in _BENIGN_TAILS:
            return
        # nested helpers inside a registered kernel entry point pass:
        # the table registers the public surface, not every closure
        top = scope.split(".", 1)[0]
        if top in self._registered:
            return
        self.diags.append(R017.diag(
            f"raw {'.'.join(chain)}() in {scope} bypasses the kernel "
            f"table: register the entry point in "
            f"analysis/capabilities.py DEVICE_KERNELS or annotate the "
            f"deliberate exception", loc=f"{self.relpath}:{node.lineno}"))


def repo_diagnostics(root: Optional[str] = None) -> List[Diagnostic]:
    """TPU-R017 over exec/ and ops/; appended to lint_repo like the
    tpucsan/tpufsan/tpudsan passes."""
    from .repo_lint import _allowed_lines, _package_root, _py_files
    root = root or _package_root()
    diags: List[Diagnostic] = []
    for path in _py_files(root):
        relpath = os.path.relpath(path, root).replace(os.sep, "/")
        if not any(relpath.startswith(p) for p in _R017_PATHS):
            continue
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
            tree = ast.parse(source, filename=relpath)
        except SyntaxError:
            continue  # TPU-R000 already reported by the core pass
        v = _RawXlaCallVisitor(relpath)
        v.visit(tree)
        if not v.diags:
            continue
        allowed = _allowed_lines(source)
        for d in v.diags:
            lineno = int(d.loc.rsplit(":", 1)[-1]) if ":" in d.loc else -1
            if lineno in allowed.get(d.code, ()):
                continue
            diags.append(d)
    return diags


def module_diagnostics(source: str, relpath: str) -> List[Diagnostic]:
    """Run the R017 visitor against one synthetic source (test
    fixtures, the --hlo anti-vacuity injections)."""
    from .repo_lint import _allowed_lines
    if not any(relpath.startswith(p) for p in _R017_PATHS):
        return []
    tree = ast.parse(source, filename=relpath)
    v = _RawXlaCallVisitor(relpath)
    v.visit(tree)
    allowed = _allowed_lines(source)
    out = []
    for d in v.diags:
        lineno = int(d.loc.rsplit(":", 1)[-1]) if ":" in d.loc else -1
        if lineno in allowed.get(d.code, ()):
            continue
        out.append(d)
    return out
