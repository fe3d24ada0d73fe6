"""Operators (ops/gather.gather_column, ops/carry.py): string columns the
programs built so far move by offsets and a gather of their bytes, as
counted while each program was traced
(``CompileObservatory.snapshot()["programs"]``): ``string_cols_gathered``
(a ``sort_rows``: the sorts and the grouped aggregate) and
``join_string_cols_gathered`` (a join's expansion, through the span
repack).  Nothing to read where the programs keep no count of the joins'
(the sum would be a part taken for the whole)."""

COUNTS = ("string_cols_gathered", "join_string_cols_gathered")


def read(run):
    try:
        from spark_rapids_tpu.obs.compileprof import CompileObservatory
    except ImportError:
        return None
    programs = CompileObservatory.get().snapshot().get("programs") or ()
    if not any(p.get(COUNTS[1]) is not None for p in programs):
        return None
    return float(sum(p.get(k) or 0 for p in programs for k in COUNTS))
