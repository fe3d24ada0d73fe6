"""TPC-H Q6, the forecasting revenue change query (spec clause 2.4.6)::

    select sum(l_extendedprice * l_discount) as revenue
    from lineitem
    where l_shipdate >= date ':1' and l_shipdate < date ':1' + interval '1' year
      and l_discount between :2 - 0.01 and :2 + 0.01
      and l_quantity < :3

Parameters (clause 2.4.6.3): ``year`` 1993..1997 (:1 is its first of
January), ``discount`` 0.02..0.09, ``quantity`` 24 or 25.

``decimal(15,2)`` is float64 here, and ``0.06 + 0.01`` in doubles is
0.06999999999999999, which would drop every 0.07 row.  So the bounds of the
BETWEEN are rounded to two places before they become literals, in the query
and in the reference alike (``discount_bounds``).

A query module gives the harness: ``COLUMNS``, ``build``, ``answer``,
``reference``, ``mismatch`` and ``least_bytes``.  Only ``build`` touches
the program; ``reference`` is NumPy over the generated columns.
"""

from __future__ import annotations

import datetime

import numpy as np

COLUMNS = ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")

#: Relative tolerance on the revenue: the one ``chip_smoke.py`` holds this
#: engine's float64 sums to on the chip (PR 21).  The engine's sum and
#: NumPy's pairwise sum add the same ~550,000 positive products in another
#: order; each order's error is at most about n * 2**-48 relative on the
#: TPU, whose float64 is a pair of float32 (48 bits): 550,000 * 3.6e-15 =
#: 2e-9 in the worst case and about sqrt(n) * 3.6e-15 = 3e-12 as expected
#: of rounding errors, so 1e-9 catches any dropped row (a single row is
#: about 2e-6 of the sum) and leaves rounding room.
REL_TOLERANCE = 1e-9

_EPOCH = datetime.date(1970, 1, 1)


def discount_bounds(discount: float):
    return round(discount - 0.01, 2), round(discount + 0.01, 2)


def year_bounds(year: int):
    return datetime.date(year, 1, 1), datetime.date(year + 1, 1, 1)


def build(df, params: dict):
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col, lit
    first, after = year_bounds(int(params["year"]))
    lo, hi = discount_bounds(float(params["discount"]))
    return (df.filter((col("l_shipdate") >= lit(first))
                      & (col("l_shipdate") < lit(after))
                      & (col("l_discount") >= lit(lo))
                      & (col("l_discount") <= lit(hi))
                      & (col("l_quantity") < lit(float(params["quantity"]))))
            .agg(F.sum(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))


def answer(table):
    """The engine's Arrow table as the value to compare: the revenue."""
    if table.num_rows != 1 or table.column_names != ["revenue"]:
        raise ValueError(f"Q6 answers one row of revenue, got "
                         f"{table.num_rows} row(s) of {table.column_names}")
    return table.column("revenue")[0].as_py()


def reference(columns: dict, params: dict) -> float:
    first, after = year_bounds(int(params["year"]))
    lo, hi = discount_bounds(float(params["discount"]))
    ship = columns["l_shipdate"]
    disc = columns["l_discount"]
    keep = (ship >= (first - _EPOCH).days) & (ship < (after - _EPOCH).days)
    keep &= (disc >= lo) & (disc <= hi)
    keep &= columns["l_quantity"] < float(params["quantity"])
    return float(np.sum(columns["l_extendedprice"][keep] * disc[keep]))


def mismatch(got, want):
    """None when the answer is the reference's, else what differs."""
    if got is None or not np.isfinite(got):
        return f"revenue {got!r}, the reference {want!r}"
    if abs(got - want) > REL_TOLERANCE * abs(want):
        return (f"revenue {got!r}, the reference {want!r} "
                f"(relative {abs(got - want) / abs(want):.3e})")
    return None


def answer_rows(got) -> int:
    return 1


def least_bytes(n_rows: int, out_rows: int) -> int:
    """The least the query must move through HBM: one read of the four
    columns it touches (date32 4 B, three float64 8 B) and the one value
    it writes.  Bandwidth-bound: 2 flops a surviving row are nothing."""
    return n_rows * (4 + 8 + 8 + 8) + out_rows * 8
