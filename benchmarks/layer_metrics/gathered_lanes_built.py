"""Operators (ops/carry.py): lanes the programs built so far move by a
row-at-a-time gather, and string columns they move by offsets and gather,
as counted while each program was traced
(``CompileObservatory.snapshot()["programs"]``: ``lane_moves_gathered``
and ``string_cols_gathered``).  A gather of one row-aligned lane at
33,554,432 rows cost 0.93 s where a sort pass costs 0.079 (PERF.md).
Nothing to read where the program keeps no such counts."""


def read(run):
    try:
        from spark_rapids_tpu.obs.compileprof import CompileObservatory
    except ImportError:
        return None
    programs = CompileObservatory.get().snapshot().get("programs")
    counts = [p[k] for p in programs or ()
              for k in ("lane_moves_gathered", "string_cols_gathered")
              if p.get(k) is not None]
    if not counts:
        return None
    return float(sum(counts))
