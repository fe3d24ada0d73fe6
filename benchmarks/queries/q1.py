"""TPC-H Q1, the pricing summary report query (spec clause 2.4.1)::

    select l_returnflag, l_linestatus,
           sum(l_quantity) as sum_qty,
           sum(l_extendedprice) as sum_base_price,
           sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
           avg(l_quantity) as avg_qty,
           avg(l_extendedprice) as avg_price,
           avg(l_discount) as avg_disc,
           count(*) as count_order
    from lineitem
    where l_shipdate <= date '1998-12-01' - interval ':1' day (3)
    group by l_returnflag, l_linestatus
    order by l_returnflag, l_linestatus

Parameter (clause 2.4.1.3): ``delta`` 60..120 days.  The filter keeps 97-99%
of the table; four groups come back, ordered by the two ``char(1)`` keys.

A query module gives the harness: ``COLUMNS``, ``build``, ``answer``,
``reference``, ``mismatch``, ``answer_rows`` and ``least_bytes``.  Only
``build`` touches the program; ``reference`` is NumPy over the generated
columns.
"""

from __future__ import annotations

import datetime

import numpy as np

COLUMNS = ("l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
           "l_extendedprice", "l_discount", "l_tax")

KEYS = ("l_returnflag", "l_linestatus")
SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge")
AVGS = ("avg_qty", "avg_price", "avg_disc")
COUNT = "count_order"
ANSWER_COLUMNS = KEYS + SUMS + AVGS + (COUNT,)

#: Relative tolerance on every sum and average; keys, their order and the
#: counts are exact.  Q6's reasoning (``queries/q6.py``) at this size: a
#: group adds 0.3-15 million positive terms.  The engine's segmented scan
#: and NumPy's pairwise sum both add them as a tree about 24 levels deep,
#: so each total's error is at most about 24 roundings of its own
#: arithmetic: 24 * 2**-48 = 9e-14 relative on the TPU, whose float64 is a
#: pair of float32, and 24 * 2**-53 = 3e-15 in NumPy; the products
#: ``price * (1 - discount) * (1 + tax)`` add three roundings a term, which
#: do not grow with the count.  1e-9 leaves four orders of room above that
#: and still catches one dropped line (about 7e-8 of the smallest group's
#: sum, 1e-7 to 3e-6 of an average's numerator) or an accumulation in
#: float32 (2**-24 = 6e-8 a rounding: the reference recomputed in float32
#: is off by 1e-6 and more, see benchmarks/tests/test_q1.py).
REL_TOLERANCE = 1e-9

_EPOCH = datetime.date(1970, 1, 1)
_BASE = datetime.date(1998, 12, 1)


def cut_date(delta: int) -> datetime.date:
    return _BASE - datetime.timedelta(days=int(delta))


def build(df, params: dict):
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col, lit
    from spark_rapids_tpu.columnar import device
    if not hasattr(device, "FIXED_WIDTH_MAX"):
        # the deployment (configs/tpch_q1_1chip.json, "layout") keeps its
        # char(1) keys as one-byte row-aligned lanes.  An engine without
        # that layout moves them by offsets and gather (18 gathers of 0.93 s
        # at 33,554,432 rows, a 1.07 GB prefix matrix a column to order
        # them, PERF.md): it cannot run this cell, and the run ends here,
        # non-zero, as the harness ends one that finds no TPU
        raise SystemExit(
            "benchmark: Q1's deployment needs fixed-width string columns "
            "(spark_rapids_tpu.columnar.device.FIXED_WIDTH_MAX); this "
            "engine has none")
    disc_price = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    charge = disc_price * (lit(1.0) + col("l_tax"))
    return (df.filter(col("l_shipdate") <= lit(cut_date(params["delta"])))
            .group_by(col("l_returnflag"), col("l_linestatus"))
            .agg(F.sum(col("l_quantity")).alias("sum_qty"),
                 F.sum(col("l_extendedprice")).alias("sum_base_price"),
                 F.sum(disc_price).alias("sum_disc_price"),
                 F.sum(charge).alias("sum_charge"),
                 F.avg(col("l_quantity")).alias("avg_qty"),
                 F.avg(col("l_extendedprice")).alias("avg_price"),
                 F.avg(col("l_discount")).alias("avg_disc"),
                 F.count("*").alias("count_order"))
            .order_by(col("l_returnflag"), col("l_linestatus")))


def answer(table) -> dict:
    """The engine's Arrow table as the value to compare: the key pairs in
    the order they came, and one NumPy array a measure."""
    if tuple(table.column_names) != ANSWER_COLUMNS:
        raise ValueError(f"Q1 answers {ANSWER_COLUMNS}, got "
                         f"{table.column_names}")
    out = {"keys": list(zip(*(table.column(k).to_pylist() for k in KEYS)))}
    for name in SUMS + AVGS:
        out[name] = table.column(name).to_numpy(zero_copy_only=False) \
            .astype(np.float64)
    out[COUNT] = table.column(COUNT).to_numpy(zero_copy_only=False) \
        .astype(np.int64)
    return out


def _group_codes(columns: dict) -> np.ndarray:
    """One code a row: the two flags' code points, the return flag above,
    so that codes order as (l_returnflag, l_linestatus) does."""
    rf = columns["l_returnflag"].view(np.uint32)
    ls = columns["l_linestatus"].view(np.uint32)
    return (rf << np.uint32(8)) | ls


def reference(columns: dict, params: dict, dtype=np.float64) -> dict:
    """Q1 in NumPy: a mask, a code of the two flags, pairwise sums under
    each group's mask.  ``dtype`` is the precision of the arithmetic
    (float64; the tests recompute in float32 to show the tolerance bites)."""
    from concurrent.futures import ThreadPoolExecutor
    cut = (cut_date(params["delta"]) - _EPOCH).days
    keep = columns["l_shipdate"] <= cut
    code = _group_codes(columns)
    qty, price, disc, tax = (columns[c] for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    one = dtype(1.0)

    def group(g: int):
        sel = keep & (code == g)
        n = int(np.count_nonzero(sel))
        q, p, d, x = (c[sel].astype(dtype, copy=False)
                      for c in (qty, price, disc, tax))
        disc_price = p * (one - d)
        sums = [np.sum(q, dtype=dtype), np.sum(p, dtype=dtype),
                np.sum(disc_price, dtype=dtype),
                np.sum(disc_price * (one + x), dtype=dtype)]
        avgs = [sums[0] / dtype(n), sums[1] / dtype(n),
                np.sum(d, dtype=dtype) / dtype(n)]
        return [float(v) for v in sums + avgs], n

    # the groups that the filter leaves, in key order
    present = np.unique(code[keep])
    with ThreadPoolExecutor(4) as pool:
        rows = list(pool.map(group, present.tolist()))
    out = {"keys": [(chr(g >> 8), chr(g & 0xFF)) for g in present.tolist()]}
    for i, name in enumerate(SUMS + AVGS):
        out[name] = np.array([r[0][i] for r in rows], np.float64)
    out[COUNT] = np.array([r[1] for r in rows], np.int64)
    return out


def deviation(got: dict, want: dict) -> float:
    """The largest relative difference over every sum and average of two
    answers with the same keys (what ``mismatch`` holds to the tolerance)."""
    worst = 0.0
    for name in SUMS + AVGS:
        rel = np.abs(got[name] - want[name]) / np.abs(want[name])
        worst = max(worst, float(np.max(rel, initial=0.0)))
    return worst


def mismatch(got, want):
    """None when the answer is the reference's, else what differs."""
    if got["keys"] != want["keys"]:
        return (f"groups {got['keys']}, the reference {want['keys']} "
                f"(pairs and their order are exact)")
    if not np.array_equal(got[COUNT], want[COUNT]):
        return (f"{COUNT} {got[COUNT].tolist()}, the reference "
                f"{want[COUNT].tolist()}")
    for name in SUMS + AVGS:
        g, w = got[name], want[name]
        bad = ~np.isfinite(g) | (np.abs(g - w) > REL_TOLERANCE * np.abs(w))
        if bad.any():
            i = int(np.flatnonzero(bad)[0])
            return (f"{name} of group {want['keys'][i]} {g[i]!r}, the "
                    f"reference {w[i]!r} (relative "
                    f"{abs(g[i] - w[i]) / abs(w[i]):.3e})")
    return None


def answer_rows(got) -> int:
    return len(got["keys"])


def least_bytes(n_rows: int, out_rows: int) -> int:
    """One read of the seven columns (four float64, the date, two one-byte
    flags) and the ten values a group written.  Bandwidth-bound: a dozen
    flops a row are nothing."""
    return n_rows * (4 * 8 + 4 + 1 + 1) + out_rows * 10 * 8
