"""What one run hands to the per-layer readers.

A reader is ``layer_metrics/<metric>.py`` with one function,
``read(run) -> Optional[float]``; ``run`` is a ``RunFacts``.  A reader that
finds nothing to read (no trace, no counter) returns ``None`` and the
harness leaves the metric out of the line.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from types import ModuleType
from typing import List, Optional

from .trace_reduce import TraceSummary


@dataclass
class RunFacts:
    cell: str
    chips: int
    device_kind: str
    #: rows of the table as generated (not the padded bucket)
    n_rows: int
    #: the cell's query module (``least_bytes`` lives there)
    query: ModuleType
    #: wall ms of every query of the window, in order
    times_ms: List[float] = field(default_factory=list)
    #: wall ms of the queries that ran under the profiler
    traced_times_ms: List[float] = field(default_factory=list)
    #: rows of each answer of the window
    answer_rows: List[int] = field(default_factory=list)
    #: seconds the window really took
    window_s: float = 0.0
    #: wall of the query's first call in set-up: upload, pin, program load
    first_call_s: float = 0.0
    #: ``CompileObservatory`` builds at the window's start and end
    builds_at_window: int = 0
    builds_at_end: int = 0
    #: ``tpu_fetch_crossings_total`` at the window's start and end
    crossings_at_window: int = 0
    crossings_at_end: int = 0
    #: ``peak_bytes_in_use`` of each chip after the window
    peak_bytes: List[int] = field(default_factory=list)
    #: the reduced profiler trace, with ``--trace 1``
    trace: Optional[TraceSummary] = None

    @property
    def device_s_per_query(self) -> Optional[float]:
        """Device time per traced query: the union of the device-operation
        intervals on the busiest chip in the traced window by the queries
        traced.  ``None`` without a trace."""
        if self.trace is None or not self.traced_times_ms:
            return None
        return self.trace.busiest.busy_s / len(self.traced_times_ms)
