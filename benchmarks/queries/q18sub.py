"""The subquery of TPC-H Q18, large volume customer (spec clause 2.4.18)::

    select l_orderkey from lineitem
    group by l_orderkey having sum(l_quantity) > :1

Parameter (clause 2.4.18.3): ``quantity`` 312..315.  About 7.5 million
groups at SF5 and a few dozen keys out.  Q18's outer joins to ``orders``
and ``customer`` and its top-100 are left out (see the mix's ``reduced``).

Quantities are whole numbers up to 50 held as float64, at most 7 to an
order: every sum is exact, so the key set is compared exactly.
"""

from __future__ import annotations

import numpy as np

COLUMNS = ("l_orderkey", "l_quantity")


def build(df, params: dict):
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col, lit
    return (df.group_by(col("l_orderkey"))
            .agg(F.sum(col("l_quantity")).alias("sum_quantity"))
            .filter(col("sum_quantity") > lit(float(params["quantity"])))
            .select(col("l_orderkey")))


def answer(table):
    """The engine's Arrow table as the value to compare: the sorted keys."""
    if table.column_names != ["l_orderkey"]:
        raise ValueError(f"the subquery answers l_orderkey, got "
                         f"{table.column_names}")
    keys = table.column("l_orderkey").to_numpy()
    return np.sort(keys.astype(np.int64))


def reference(columns: dict, params: dict) -> np.ndarray:
    keys = columns["l_orderkey"]
    # dbgen emits lineitem clustered by order: a group is a run of keys
    starts = np.flatnonzero(np.concatenate(([True], keys[1:] != keys[:-1])))
    group_keys = keys[starts]
    if not np.all(group_keys[1:] > group_keys[:-1]):
        raise ValueError("l_orderkey is not clustered in rising order")
    sums = np.add.reduceat(columns["l_quantity"], starts)
    return np.sort(group_keys[sums > float(params["quantity"])])


def mismatch(got, want):
    if got.shape != want.shape or not np.array_equal(got, want):
        extra = np.setdiff1d(got, want)[:3].tolist()
        missing = np.setdiff1d(want, got)[:3].tolist()
        return (f"{got.shape[0]} keys, the reference {want.shape[0]}; "
                f"not in the reference {extra}, missing {missing}")
    return None


def answer_rows(got) -> int:
    return int(got.shape[0])


def least_bytes(n_rows: int, out_rows: int) -> int:
    """One read of the key and the quantity (8 B each) and the keys
    written.  Bandwidth-bound: one add a row."""
    return n_rows * (8 + 8) + out_rows * 8
