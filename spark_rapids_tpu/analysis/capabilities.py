"""Machine-readable kernel capability table + gate cross-checker.

Round-5's biggest correctness class was planning-time gates admitting a
plan the runtime then crashed on: ``parallel/alltoall.py``'s
``exchange_supported`` admitted array/map aggregate buffers that
``allgather_batch`` raises ``NotImplementedError`` on mid-query.  The
root cause is structural — the admission predicate and the kernel's
dtype branches live far apart and drift independently.

This module closes that gap: every collective kernel in ``parallel/``
(and, as they grow capability-sensitive branches, the kernels in
``ops/``) registers a ``KernelCapability`` whose ``supports(dtype)``
mirrors the kernel's ACTUAL branch structure (the branch that raises is
the branch that returns False here).  ``verify_gates()`` then probes
every planning-time admission gate against the kernel it guards over a
representative dtype catalog: a gate that admits a dtype its kernel
raises on is a lint error (TPU-R004 in the repo lint; the plan lint's
TPU-L001 is the same check specialized to a concrete plan).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .. import types as t

# ---------------------------------------------------------------------------
# representative dtype catalog
# ---------------------------------------------------------------------------
# One probe per structurally-distinct dtype shape the engine models.  A
# gate/kernel mismatch on ANY real schema is a mismatch on one of these:
# the kernels branch on type STRUCTURE (flat / span / struct / nesting),
# never on widths beyond the flat/64-bit split the flat probes cover.

PROBE_TYPES: List[t.DataType] = [
    t.BOOLEAN, t.INT, t.LONG, t.DOUBLE, t.DATE, t.TIMESTAMP,
    t.DecimalType(18, 2), t.DecimalType(38, 2),
    t.STRING, t.BINARY,
    t.ArrayType(t.INT), t.ArrayType(t.STRING),
    t.ArrayType(t.ArrayType(t.INT)),
    t.MapType(t.INT, t.LONG), t.MapType(t.INT, t.STRING),
    t.StructType([t.StructField("f", t.INT)]),
    t.StructType([t.StructField("s", t.STRING)]),
    t.StructType([t.StructField("a", t.ArrayType(t.INT))]),
]


def _is_flat(dt: t.DataType) -> bool:
    return not isinstance(dt, (t.StringType, t.BinaryType, t.ArrayType,
                               t.MapType, t.StructType))


class KernelCapability:
    """Dtype coverage of one runtime kernel, mirroring its branch
    structure.  `supports(dt)` is True exactly when the kernel carries a
    column of that type without raising."""

    def __init__(self, name: str, module: str, doc: str,
                 supports: Callable[[t.DataType], bool]):
        self.name = name
        self.module = module
        self.doc = " ".join(doc.split())
        self.supports = supports

    def unsupported(self, dtypes: Sequence[t.DataType]) -> List[t.DataType]:
        return [dt for dt in dtypes if not self.supports(dt)]


# --- parallel/alltoall.py: exchange_by_pid -------------------------------
# move(): flat lanes ride directly; strings/binaries via the span packer;
# structs recurse per field; arrays/maps of FLAT elements via
# _flat_child_lanes (nested span elements raise NotImplementedError).

def _exchange_by_pid_supports(dt: t.DataType) -> bool:
    if isinstance(dt, (t.StringType, t.BinaryType)):
        return True
    if isinstance(dt, t.StructType):
        return all(_exchange_by_pid_supports(f.data_type)
                   for f in dt.fields)
    if isinstance(dt, t.ArrayType):
        return _is_flat(dt.element_type)
    if isinstance(dt, t.MapType):
        return _is_flat(dt.key_type) and _is_flat(dt.value_type)
    return True


# --- parallel/alltoall.py: allgather_batch -------------------------------
# gather_col(): flat lanes and strings/binaries ride; structs recurse;
# arrays/maps raise NotImplementedError unconditionally (the span
# receive layout is only implemented for exchange_by_pid).

def _allgather_batch_supports(dt: t.DataType) -> bool:
    if isinstance(dt, (t.ArrayType, t.MapType)):
        return False
    if isinstance(dt, t.StructType):
        return all(_allgather_batch_supports(f.data_type)
                   for f in dt.fields)
    return True


CAPABILITIES: Dict[str, KernelCapability] = {}


def _register(cap: KernelCapability) -> KernelCapability:
    CAPABILITIES[cap.name] = cap
    return cap


EXCHANGE_BY_PID = _register(KernelCapability(
    "exchange_by_pid", "spark_rapids_tpu/parallel/alltoall.py",
    "ICI all_to_all row redistribution: flat lanes, strings/binaries, "
    "structs of carried types, arrays/maps of flat elements.",
    _exchange_by_pid_supports))

ALLGATHER_BATCH = _register(KernelCapability(
    "allgather_batch", "spark_rapids_tpu/parallel/alltoall.py",
    "ICI replication (broadcast analog): flat lanes, strings/binaries, "
    "structs of carried types; NO arrays/maps (span receive layout not "
    "implemented for the gather path).",
    _allgather_batch_supports))


# ---------------------------------------------------------------------------
# gate cross-check
# ---------------------------------------------------------------------------

# a planning gate takes a dtype list and returns a fallback reason string
# (None = admitted), the exchange_supported convention
GateFn = Callable[[Sequence[t.DataType]], Optional[str]]


def gate_weaker_than_kernel(gate: GateFn, kernel: KernelCapability,
                            probes: Optional[Sequence[t.DataType]] = None
                            ) -> List[t.DataType]:
    """Dtypes the gate ADMITS but the kernel RAISES on — each one is a
    plan shape that passes planning and crashes mid-query.  Empty list =
    the gate is provably no weaker than the kernel over the catalog."""
    out = []
    for dt in (probes if probes is not None else PROBE_TYPES):
        if gate([dt]) is None and not kernel.supports(dt):
            out.append(dt)
    return out


def registered_gates() -> List[Tuple[str, GateFn, KernelCapability]]:
    """Every planning-time admission gate paired with the kernel whose
    coverage it promises.  New gates MUST register here — TPU-R004 fails
    the repo lint when a listed gate drifts weaker than its kernel."""
    from ..parallel.alltoall import allgather_supported, exchange_supported

    def ungrouped_aggregate_gate(dtypes) -> Optional[str]:
        # DistributedAggregate's construction gate for the ungrouped
        # (replicate) path: exchange admission AND allgather admission
        return exchange_supported(dtypes) or allgather_supported(dtypes)

    return [
        ("parallel.exchange_supported", exchange_supported,
         EXCHANGE_BY_PID),
        ("parallel.DistributedAggregate[ungrouped]",
         ungrouped_aggregate_gate, ALLGATHER_BATCH),
    ]


def verify_gates() -> List[Tuple[str, str, t.DataType]]:
    """Cross-check every registered gate: returns (gate, kernel, dtype)
    mismatches.  Empty = all planning admissions are runtime-safe."""
    out = []
    for name, gate, kernel in registered_gates():
        for dt in gate_weaker_than_kernel(gate, kernel):
            out.append((name, kernel.name, dt))
    return out


# ---------------------------------------------------------------------------
# device-kernel table (TPU-R017)
# ---------------------------------------------------------------------------
# The xp-parameterization convention keeps exec// ops/ backend-agnostic:
# kernels take `xp` and run identically on numpy for the host path.  The
# few entry points that NEED a jax-only primitive (today: the sort
# passes of ops/carry.py, lax.sort with both operands keys, which numpy
# has no analogue for — the host path branches around it) register here so the tpuxsan repo rule
# (TPU-R017, analysis/hloaudit.py) can tell a sanctioned kernel from an
# accidental bypass.  Keys are package-relative paths; values map the
# entry-point function name to the one-line reason it is device-only.
# Nested helpers inside a registered entry point are covered by it.

DEVICE_KERNELS: Dict[str, Dict[str, str]] = {
    "ops/carry.py": {
        "sort_rows": "rows moved by lax.sort, a pass per 32-bit word "
                     "keyed by the rank; host path uses np.lexsort + "
                     "fancy indexing instead",
        "move_lanes": "a lane put in place by one 2-operand (uint32, "
                      "int32) lax.sort keyed by the destination rank; "
                      "the host path assigns through the rank",
        "stable_argsort": "the device argsort, a radix sort whose "
                          "every pass is one 2-operand (uint32, int32) "
                          "lax.sort; host paths use np.argsort",
    },
    "ops/join_kernels.py": {
        "count_matches": "sort-based hash-match counting rides "
                         "carry's sort passes; the host path uses "
                         "np.searchsorted",
    },
    "ops/segmented.py": {
        "lexsort": "multi-word lexicographic sort is "
                   "carry.stable_argsort; the host path uses np.lexsort",
    },
}


def device_kernel_functions(relpath: str) -> frozenset:
    """Sanctioned jnp/lax-calling entry points for one module, by
    package-relative path.  Empty for modules with no registration —
    every raw call there is a TPU-R017 finding."""
    return frozenset(DEVICE_KERNELS.get(relpath, ()))
