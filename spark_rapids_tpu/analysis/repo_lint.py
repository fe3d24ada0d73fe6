"""Repo lint: AST + registry pass enforcing codebase invariants.

  TPU-R001  no implicit host sync (np.asarray / jax.device_get /
            .block_until_ready) inside exec/ and ops/ hot paths — the
            single-round-trip fetch path (columnar/fetch.py) is the only
            sanctioned device->host crossing
  TPU-R002  every SPARK_RAPIDS_* env var read is declared in
            config.DECLARED_ENV_KEYS (env knobs must be documented
            config surface, not scattered literals)
  TPU-R003  every public Expression subclass under expr/ is registered
            with a TypeSig in the overrides registry (an expression
            without a declared dtype coverage is un-taggable: the
            planner cannot prove where it runs)
  TPU-R004  every planning-time admission gate is no weaker than the
            kernel it guards (capabilities.verify_gates — the check that
            catches the round-5 alltoall admit/crash drift)
  TPU-R005  device allocations in exec/ and ops/ route through the
            catalog/arena APIs (SpillCatalog.register, batch_to_device,
            the shared staging arena) — an unrouted buffer is invisible
            to spill pressure, leak_report and the tmsan ledger
  TPU-R006  raw time.perf_counter*/TraceAnnotation in exec/, ops/,
            shuffle/, parallel/ must route through MetricTimer or the
            obs/ flight recorder (one timing path for metrics, traces
            and the self-emitted event log)

Pre-existing violations live in a checked-in baseline
(devtools/lint_baseline.txt, fingerprint per line); devtools/run_lint.py
exits nonzero only on NEW violations, so the invariant ratchets.
Deliberate single-site exceptions are annotated in place with
``# tpulint: allow[TPU-Rxxx] <reason>`` instead of baselined — the
annotation travels with the code it sanctions.
"""

from __future__ import annotations

import ast
import os
from typing import Iterable, List, Optional, Set

from .diagnostics import Diagnostic, ERROR, WARN, register_rule

R001 = register_rule(
    "TPU-R001", ERROR, "implicit host sync in hot path",
    "np.asarray / jax.device_get / .block_until_ready inside exec/ or "
    "ops/ forces a device round trip, a sync that drains the dispatch "
    "pipeline, per call site; device->host crossings belong to "
    "columnar/fetch.py's batched two-round-trip path.")

R002 = register_rule(
    "TPU-R002", ERROR, "undeclared environment-variable config",
    "A SPARK_RAPIDS_* environment variable is read without being listed "
    "in config.DECLARED_ENV_KEYS; env knobs are config surface and must "
    "be declared and documented like every other key.")

R003 = register_rule(
    "TPU-R003", WARN, "expression without registered dtype coverage",
    "A public Expression subclass under expr/ has no entry in the "
    "overrides EXPR_RULES registry: the tagging engine cannot reason "
    "about its dtype coverage, so plans using it are un-analyzable.")

R004 = register_rule(
    "TPU-R004", ERROR, "planning gate weaker than kernel coverage",
    "A registered admission gate (capabilities.registered_gates) admits "
    "a dtype its runtime kernel raises on — plans pass planning and "
    "crash mid-query.  Tighten the gate or extend the kernel.")

R006 = register_rule(
    "TPU-R006", ERROR, "raw timing primitive outside MetricTimer/tracer",
    "time.perf_counter/perf_counter_ns or jax.profiler.TraceAnnotation "
    "used directly in exec/, ops/, shuffle/ or parallel/: operator "
    "timing must route through MetricTimer (which owns the sanctioned "
    "clock reads) or the obs/ tracer (which owns the one NVTX-analog "
    "annotation, obs/tracer.open_range), so the engine has ONE timing "
    "path that metrics, traces, the profiler's ranges and the "
    "self-emitted event log all agree on.")

R007 = register_rule(
    "TPU-R007", ERROR, "ad-hoc module-level metric tally",
    "A module-level mutable counter (integer tally, Counter(), "
    "defaultdict tally, or a dict/list/set whose name says it counts) "
    "in exec/, ops/, shuffle/, parallel/ or memory/: process-wide "
    "statistics must route through obs.metrics.MetricsRegistry so they "
    "are thread-safe, cardinality-bounded, and visible to the "
    "Prometheus/health exposition and the regression watchdog — an "
    "ad-hoc global is invisible to all three.  Sanctioned sinks are "
    "annotated `# tpulint: allow[TPU-R007]` in place.")

R005 = register_rule(
    "TPU-R005", ERROR, "device allocation outside the catalog/arena APIs",
    "Code in exec/ or ops/ constructs a SpillableBatch directly, calls "
    "jax.device_put, or builds a private HostArena: device buffers must "
    "enter through SpillCatalog.register/register_pinned (budgeted, "
    "spillable, visible to the tmsan shadow ledger), uploads through "
    "columnar.device.batch_to_device / HostToDeviceExec, and staging "
    "through the plugin's shared arena — an unrouted allocation is "
    "invisible to every memory-safety layer (spill pressure, "
    "leak_report, the TPU-L014 peak bound).")

# hot-path packages for TPU-R001/R005 (module-relative, forward slashes)
_HOT_PATHS = ("spark_rapids_tpu/exec/", "spark_rapids_tpu/ops/")
_SYNC_RECEIVERS = {"asarray": {"np", "numpy"}, "device_get": {"jax"}}
# one-timing-path packages for TPU-R006 (everywhere operator work runs)
_TIMING_PATHS = ("spark_rapids_tpu/exec/", "spark_rapids_tpu/ops/",
                 "spark_rapids_tpu/shuffle/", "spark_rapids_tpu/parallel/")
_TIMING_CALLS = {"perf_counter", "perf_counter_ns"}
# one-metrics-path packages for TPU-R007 (engine-statistics producers)
_TALLY_PATHS = _TIMING_PATHS + ("spark_rapids_tpu/memory/",)

# `# tpulint: allow[TPU-Rxxx] <reason>` on the flagged line or the line
# above sanctions one deliberate violation (the annotated-sink analog of
# the baseline, for sites that are the POINT of the rule's exception —
# e.g. maybe_sync IS the sanctioned device-timing sync)
import re as _re

_ALLOW_RE = _re.compile(r"tpulint:\s*allow\[([A-Z0-9-]+)\]")


def _allowed_lines(source: str) -> dict:
    """rule code -> set of line numbers (1-based) the annotation covers:
    its own line, any immediately following comment lines, and the first
    code line after them (so a multi-line reason can sit above the
    call)."""
    out: dict = {}
    lines = source.splitlines()
    for i, line in enumerate(lines, start=1):
        for code in _ALLOW_RE.findall(line):
            covered = out.setdefault(code, set())
            covered.add(i)
            j = i + 1
            while j <= len(lines) and \
                    lines[j - 1].lstrip().startswith("#"):
                covered.add(j)
                j += 1
            covered.add(j)
    return out


def _package_root() -> str:
    """Directory CONTAINING the spark_rapids_tpu package."""
    import spark_rapids_tpu
    return os.path.dirname(os.path.dirname(
        os.path.abspath(spark_rapids_tpu.__file__)))


def _py_files(root: str) -> Iterable[str]:
    pkg = os.path.join(root, "spark_rapids_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for f in sorted(files):
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


class _ScopedVisitor(ast.NodeVisitor):
    """Tracks the enclosing class/function qualname so fingerprints
    survive line-number churn."""

    def __init__(self):
        self._scope: List[str] = []

    @property
    def scope(self) -> str:
        return ".".join(self._scope) or "<module>"

    def visit_ClassDef(self, node):
        self._scope.append(node.name)
        self.generic_visit(node)
        self._scope.pop()

    def visit_FunctionDef(self, node):
        self._scope.append(node.name)
        self.generic_visit(node)
        self._scope.pop()

    visit_AsyncFunctionDef = visit_FunctionDef


class _HostSyncVisitor(_ScopedVisitor):
    def __init__(self, relpath: str):
        super().__init__()
        self.relpath = relpath
        self.diags: List[Diagnostic] = []

    def visit_Call(self, node):
        f = node.func
        if isinstance(f, ast.Attribute):
            call = None
            if f.attr == "block_until_ready":
                call = ".block_until_ready"
            elif f.attr in _SYNC_RECEIVERS and \
                    isinstance(f.value, ast.Name) and \
                    f.value.id in _SYNC_RECEIVERS[f.attr]:
                call = f"{f.value.id}.{f.attr}"
            if call is not None:
                self.diags.append(R001.diag(
                    f"implicit host sync {call} in {self.scope}",
                    loc=f"{self.relpath}:{node.lineno}"))
        self.generic_visit(node)


class _DeviceAllocVisitor(_ScopedVisitor):
    """TPU-R005: direct device-buffer acquisition in exec//ops/ that
    bypasses the catalog/arena routing."""

    def __init__(self, relpath: str):
        super().__init__()
        self.relpath = relpath
        self.diags: List[Diagnostic] = []

    def visit_Call(self, node):
        f = node.func
        call = None
        if isinstance(f, ast.Name) and f.id in ("SpillableBatch",
                                                "HostArena"):
            call = f"{f.id}(...)"
        elif isinstance(f, ast.Attribute):
            if f.attr in ("SpillableBatch", "HostArena"):
                call = f"{f.attr}(...)"
            elif f.attr == "device_put" and \
                    isinstance(f.value, ast.Name) and \
                    f.value.id in ("jax", "jnp"):
                call = f"{f.value.id}.device_put"
        if call is not None:
            self.diags.append(R005.diag(
                f"unrouted device allocation {call} in {self.scope}; "
                f"route through SpillCatalog.register / "
                f"batch_to_device / the shared arena",
                loc=f"{self.relpath}:{node.lineno}"))
        self.generic_visit(node)


class _TimingVisitor(_ScopedVisitor):
    """TPU-R006: raw clock reads / profiler annotations in the operator
    packages that bypass the single timing path (MetricTimer + the
    obs/ tracer)."""

    def __init__(self, relpath: str):
        super().__init__()
        self.relpath = relpath
        self.diags: List[Diagnostic] = []

    def visit_Call(self, node):
        f = node.func
        call = None
        if isinstance(f, ast.Attribute) and f.attr in _TIMING_CALLS and \
                isinstance(f.value, ast.Name) and \
                f.value.id.lstrip("_") == "time":
            call = f"time.{f.attr}"
        elif isinstance(f, ast.Name) and f.id == "TraceAnnotation":
            call = "TraceAnnotation(...)"
        elif isinstance(f, ast.Attribute) and \
                f.attr == "TraceAnnotation":
            call = "TraceAnnotation(...)"
        if call is not None:
            self.diags.append(R006.diag(
                f"raw timing primitive {call} in {self.scope}; route "
                f"through MetricTimer or the obs/ tracer",
                loc=f"{self.relpath}:{node.lineno}"))
        self.generic_visit(node)


_TALLY_NAME = _re.compile(
    r"(^|_)(n|num|count(er)?s?|totals?|tall(y|ies)|hits?|miss(es)?|"
    r"calls?|stats?)(_|$|\d)", _re.I)


def _is_tally_name(name: str) -> bool:
    return bool(_TALLY_NAME.search(name))


def module_tally_diagnostics(source_or_tree, relpath: str):
    """TPU-R007 over ONE module's top level (factored out so tests can
    run it against synthetic sources).  Flags:

      * a module-level Counter()/defaultdict(int|float) binding — these
        containers exist to count, whatever the name says;
      * a module-level int/float literal, empty dict/list/set literal
        or dict()/list()/set() call bound to a counter-ish name
        (``_FOO_COUNT``, ``TOTALS``, ``_hits`` ...);
      * a module-level augmented assignment to a counter-ish name
        (``_N_CALLS += 1``).

    Lookup tables, caches and registries (names without a counting
    word) stay legal: the rule targets tallies, not constants.
    """
    tree = source_or_tree if isinstance(source_or_tree, ast.Module) \
        else ast.parse(source_or_tree, filename=relpath)
    diags: List[Diagnostic] = []

    def _is_counting_container(v) -> bool:
        if not isinstance(v, ast.Call):
            return False
        f = v.func
        name = f.id if isinstance(f, ast.Name) else \
            f.attr if isinstance(f, ast.Attribute) else ""
        if name == "Counter":
            return True
        if name == "defaultdict" and v.args and \
                isinstance(v.args[0], ast.Name) and \
                v.args[0].id in ("int", "float"):
            return True
        return False

    def _is_mutable_zero(v) -> bool:
        if isinstance(v, ast.Constant) and \
                isinstance(v.value, (int, float)) and \
                not isinstance(v.value, bool):
            return True
        if isinstance(v, (ast.Dict, ast.List, ast.Set)):
            return not (getattr(v, "keys", None) or
                        getattr(v, "elts", None))
        if isinstance(v, ast.Call) and isinstance(v.func, ast.Name) and \
                v.func.id in ("dict", "list", "set") and not v.args \
                and not v.keywords:
            return True
        return False

    for node in tree.body:
        targets = []
        value = None
        if isinstance(node, ast.Assign):
            targets = [t for t in node.targets
                       if isinstance(t, ast.Name)]
            value = node.value
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name) and \
                node.value is not None:
            targets = [node.target]
            value = node.value
        elif isinstance(node, ast.AugAssign) and \
                isinstance(node.target, ast.Name):
            if _is_tally_name(node.target.id):
                diags.append(R007.diag(
                    f"module-level tally mutation "
                    f"{node.target.id} {type(node.op).__name__}=; "
                    f"route through obs.metrics.MetricsRegistry",
                    loc=f"{relpath}:{node.lineno}"))
            continue
        if not targets or value is None:
            continue
        for t in targets:
            if _is_counting_container(value):
                diags.append(R007.diag(
                    f"module-level counting container {t.id}; route "
                    f"through obs.metrics.MetricsRegistry",
                    loc=f"{relpath}:{node.lineno}"))
            elif _is_tally_name(t.id) and _is_mutable_zero(value):
                diags.append(R007.diag(
                    f"module-level mutable tally {t.id}; route "
                    f"through obs.metrics.MetricsRegistry",
                    loc=f"{relpath}:{node.lineno}"))
    return diags


class _EnvReadVisitor(_ScopedVisitor):
    def __init__(self, relpath: str, declared: Set[str]):
        super().__init__()
        self.relpath = relpath
        self.declared = declared
        self.diags: List[Diagnostic] = []

    @staticmethod
    def _is_environ(node) -> bool:
        return isinstance(node, ast.Attribute) and \
            node.attr == "environ" and \
            isinstance(node.value, ast.Name) and \
            node.value.id.lstrip("_") == "os"

    def _check_key(self, key_node, lineno: int):
        if isinstance(key_node, ast.Constant) and \
                isinstance(key_node.value, str) and \
                key_node.value.startswith("SPARK_RAPIDS") and \
                key_node.value not in self.declared:
            self.diags.append(R002.diag(
                f"undeclared env key {key_node.value} read in "
                f"{self.scope}", loc=f"{self.relpath}:{lineno}"))

    def visit_Subscript(self, node):
        if self._is_environ(node.value):
            self._check_key(node.slice, node.lineno)
        self.generic_visit(node)

    def visit_Call(self, node):
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in ("get", "pop") and \
                self._is_environ(f.value) and node.args:
            self._check_key(node.args[0], node.lineno)
        self.generic_visit(node)


def _ast_diagnostics(root: str) -> List[Diagnostic]:
    from .. import config as cfg_mod
    declared = set(getattr(cfg_mod, "DECLARED_ENV_KEYS", ()))
    diags: List[Diagnostic] = []
    for path in _py_files(root):
        relpath = os.path.relpath(path, root).replace(os.sep, "/")
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
            tree = ast.parse(source, filename=relpath)
        except SyntaxError as ex:
            diags.append(Diagnostic("TPU-R000", ERROR,
                                    f"unparsable module: {ex.msg}",
                                    loc=relpath))
            continue
        file_diags: List[Diagnostic] = []
        if any(relpath.startswith(h) for h in _HOT_PATHS):
            v = _HostSyncVisitor(relpath)
            v.visit(tree)
            file_diags.extend(v.diags)
            dv = _DeviceAllocVisitor(relpath)
            dv.visit(tree)
            file_diags.extend(dv.diags)
        if any(relpath.startswith(h) for h in _TIMING_PATHS):
            tv = _TimingVisitor(relpath)
            tv.visit(tree)
            file_diags.extend(tv.diags)
        if any(relpath.startswith(h) for h in _TALLY_PATHS):
            file_diags.extend(module_tally_diagnostics(tree, relpath))
        ev = _EnvReadVisitor(relpath, declared)
        ev.visit(tree)
        file_diags.extend(ev.diags)
        allowed = _allowed_lines(source) if file_diags else {}
        for d in file_diags:
            lineno = int(d.loc.rsplit(":", 1)[-1]) if ":" in d.loc else -1
            if lineno in allowed.get(d.code, ()):
                continue  # annotated sanctioned sink
            diags.append(d)
    return diags


def _registry_diagnostics() -> List[Diagnostic]:
    """TPU-R003/R004: checks against the LIVE registries, so they can
    never drift from the code the way a parallel table would."""
    import importlib
    import inspect
    import pkgutil

    diags: List[Diagnostic] = []
    from ..expr.core import Expression
    from ..plan.overrides import EXPR_RULES

    import spark_rapids_tpu.expr as expr_pkg
    for info in pkgutil.iter_modules(expr_pkg.__path__):
        mod = importlib.import_module(f"spark_rapids_tpu.expr.{info.name}")
        for name, cls in sorted(vars(mod).items()):
            if not (inspect.isclass(cls) and issubclass(cls, Expression)):
                continue
            if cls.__module__ != mod.__name__ or name.startswith("_"):
                continue
            if inspect.isabstract(cls) or cls in EXPR_RULES:
                continue
            # abstract-by-convention bases: anything further subclassed
            # within the package is a base, not a leaf operator
            if any(c is not cls and issubclass(c, cls)
                   for m2 in (vars(mod),) for c in m2.values()
                   if inspect.isclass(c)):
                continue
            diags.append(R003.diag(
                f"expression {name} has no registered TypeSig rule",
                loc=f"spark_rapids_tpu/expr/{info.name}.py"))

    from .capabilities import verify_gates
    for gate, kernel, dt in verify_gates():
        diags.append(R004.diag(
            f"gate {gate} admits {dt.name} but kernel {kernel} raises "
            f"on it", loc="spark_rapids_tpu/analysis/capabilities.py"))
    return diags


def lint_repo(root: Optional[str] = None) -> List[Diagnostic]:
    """Run every repo rule over the package source; returns ALL
    violations (baseline subtraction is the caller's concern)."""
    root = root or _package_root()
    from .diagnostics import sort_diagnostics
    from . import concurrency, determinism, hloaudit, raiseflow
    return sort_diagnostics(_ast_diagnostics(root) +
                            _registry_diagnostics() +
                            concurrency.repo_diagnostics(root) +
                            raiseflow.repo_diagnostics(root) +
                            determinism.repo_diagnostics(root) +
                            hloaudit.repo_diagnostics(root))


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str) -> Set[str]:
    if not os.path.exists(path):
        return set()
    with open(path, encoding="utf-8") as f:
        return {line.rstrip("\n") for line in f
                if line.strip() and not line.startswith("#")}


def save_baseline(path: str, diags: List[Diagnostic]) -> None:
    lines = sorted({d.fingerprint() for d in diags})
    with open(path, "w", encoding="utf-8") as f:
        f.write("# tpulint repo baseline: pre-existing violations, one "
                "fingerprint per line.\n# Regenerate with: python "
                "devtools/run_lint.py --update-baseline\n")
        for line in lines:
            f.write(line + "\n")


def new_violations(diags: List[Diagnostic],
                   baseline: Set[str]) -> List[Diagnostic]:
    return [d for d in diags if d.fingerprint() not in baseline]
