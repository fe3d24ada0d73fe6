"""TPC-H Q3, the shipping priority query (spec clause 2.4.3)::

    select l_orderkey, sum(l_extendedprice*(1-l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = ':1' and c_custkey = o_custkey
      and l_orderkey = o_orderkey
      and o_orderdate < date ':2' and l_shipdate > date ':2'
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate          -- the first 10 rows

Parameters (clause 2.4.3.3): ``segment`` one of the five market segments,
``day`` 1..31 (:2 is that day of March 1995).  A fifth of the customers
are in the segment; their orders before the date (about 730,000 of
7,500,000 at SF5) meet the lines shipped after it: about 150,000 lines of
some 57,000 orders, all placed within the 121 days before the date.

**Three tables, one DataFrame from the harness.**  ``build`` gets
LINEITEM's DataFrame; ORDERS and CUSTOMER come from the generator's
hand-off (``datagen/tpch_q3_tables.py``, "The hand-off"): ``build`` reaches
the module the harness called through ``harness.cells.load_module``, makes
the two side DataFrames ONCE for that ``df`` (so they upload and pin in
the warm-up call, like lineitem, and stay resident) and checks their Arrow
types as ``runner.arrow_table`` checks lineitem's.  No accepted file of the
benchmark is edited for it.

A query module gives the harness: ``COLUMNS``, ``build``, ``answer``,
``reference``, ``mismatch``, ``answer_rows`` and ``least_bytes``; this one
also ``join_least_bytes`` (``layer_metrics/join_hbm_roofline_share.py``).
Only ``build`` touches the program; ``reference`` is NumPy over the
generated columns.
"""

from __future__ import annotations

import datetime
import os

import numpy as np

#: LINEITEM's columns (the table the harness makes and sums the bytes of)
COLUMNS = ("l_orderkey", "l_extendedprice", "l_discount", "l_shipdate")
ANSWER_COLUMNS = ("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
LIMIT = 10

#: Relative tolerance on ``revenue``; the keys, the dates, the priorities
#: and the rows' order are exact.  A group adds one to seven positive
#: products ``price * (1 - discount)``: two roundings a product and at
#: most six a sum, each 2**-48 = 3.6e-15 on the TPU (whose float64 is a
#: pair of float32) and 2**-53 in NumPy, so an honest answer lies within
#: about 3e-14.  1e-9 leaves four orders of room above that and catches a
#: dropped or doubled line (at least a seventh of a group's revenue less
#: its discount: 1e-2 and more) and arithmetic in float32 (2**-24 = 6e-8
#: a rounding: the reference recomputed in float32 is off by 1e-8 and
#: more in some row of every answer, tests/test_q3_query.py and PERF.md).
REL_TOLERANCE = 1e-9

_EPOCH = datetime.date(1970, 1, 1)
_DATAGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "datagen", "tpch_q3_tables.py")

# (lineitem's DataFrame, the generator's tables, orders, customer): the
# side DataFrames of the one `df` the harness asks with
_FRAMES: tuple = ()


def cut_date(params: dict) -> datetime.date:
    return datetime.date(1995, 3, int(params["day"]))


def tables():
    """What the generator made last, from the module the harness called."""
    from benchmarks.harness.cells import load_module
    last = load_module(_DATAGEN).LAST
    if last is None:
        raise RuntimeError("Q3's tables have not been generated: "
                           "datagen/tpch_q3_tables.generate comes first")
    return last


def side_table(name: str, columns: dict):
    """One side table's NumPy columns as an Arrow table, held to the
    generator's ``SIDE_SCHEMAS`` as ``runner.arrow_table`` holds
    lineitem to ``SCHEMA``."""
    from benchmarks.harness.cells import load_module
    from benchmarks.harness.runner import arrow_table
    return arrow_table(columns, load_module(_DATAGEN).SIDE_SCHEMAS[name])


def side_frames(df):
    """ORDERS and CUSTOMER as DataFrames of ``df``'s session, made once."""
    global _FRAMES
    made = tables()
    if not _FRAMES or _FRAMES[0] is not df or _FRAMES[1] is not made:
        frames = [df.session.create_dataframe(
            side_table(name, made.side[name]), num_partitions=1)
            for name in ("orders", "customer")]
        _FRAMES = (df, made, *frames)
    return _FRAMES[2], _FRAMES[3]


def grouped_frame(df, params: dict):
    """Q3 before its ORDER BY and its limit: every group
    (``devtools/chip_q3_full.py`` holds all of them to ``grouped``)."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col, lit
    orders, customer = side_frames(df)
    day = lit(cut_date(params))
    segment = (customer.filter(col("c_mktsegment") == lit(params["segment"]))
               .select("c_custkey"))
    open_orders = (orders.filter(col("o_orderdate") < day)
                   .join(segment, on=col("o_custkey") == col("c_custkey"),
                         how="inner")
                   .select("o_orderkey", "o_orderdate", "o_shippriority"))
    revenue = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return (df.filter(col("l_shipdate") > day)
            .join(open_orders, on=col("l_orderkey") == col("o_orderkey"),
                  how="inner")
            .group_by(col("l_orderkey"), col("o_orderdate"),
                      col("o_shippriority"))
            .agg(F.sum(revenue).alias("revenue"))
            .select(*ANSWER_COLUMNS))


def build(df, params: dict):
    from spark_rapids_tpu.api.column import col
    return (grouped_frame(df, params)
            .order_by(col("revenue").desc(), col("o_orderdate"))
            .limit(LIMIT))


def answer(table) -> dict:
    """The engine's Arrow table as the value to compare: one NumPy array
    a column, the dates as days since 1970-01-01, rows in answer order."""
    import pyarrow as pa
    if tuple(table.column_names) != ANSWER_COLUMNS:
        raise ValueError(f"Q3 answers {ANSWER_COLUMNS}, got "
                         f"{table.column_names}")
    table = table.combine_chunks()

    def lane(name, dtype, arrow_type=None):
        column = table.column(name)
        if column.null_count:
            raise ValueError(f"Q3's {name} holds nulls")
        if arrow_type is not None:
            column = column.cast(arrow_type)
        return column.to_numpy().astype(dtype)
    return {"l_orderkey": lane("l_orderkey", np.int64),
            "revenue": lane("revenue", np.float64),
            "o_orderdate": lane("o_orderdate", np.int32, pa.int32()),
            "o_shippriority": lane("o_shippriority", np.int32)}


def order_index(orderkey: np.ndarray) -> np.ndarray:
    """The row of ORDERS that holds each key: the inverse of dbgen's
    sparse keys (``tpch_lineitem.sparse_orderkeys``)."""
    k = orderkey - 1
    return (k >> 5 << 3) + (k & 7)


def _open_orders(columns, params: dict, cut: int) -> np.ndarray:
    """A boolean lane over ORDERS: placed before the date by a customer
    of the segment."""
    customer, orders = columns.side["customer"], columns.side["orders"]
    in_segment = np.zeros(int(customer["c_custkey"].max(initial=0)) + 1,
                          bool)
    in_segment[customer["c_custkey"]] = \
        customer["c_mktsegment"] == params["segment"]
    return (orders["o_orderdate"] < cut) & in_segment[orders["o_custkey"]]


def grouped(columns, params: dict, dtype=np.float64) -> dict:
    """Q3 before its ORDER BY and its limit, in NumPy: every group, in
    rising ``l_orderkey``.  ``dtype`` is the precision of the arithmetic
    (float64; the tests recompute in float32 to show the tolerance
    bites)."""
    orders = columns.side["orders"]
    cut = (cut_date(params) - _EPOCH).days
    open_order = _open_orders(columns, params, cut)
    row = order_index(columns["l_orderkey"])
    keep = (columns["l_shipdate"] > cut) & open_order[row]
    row = row[keep]
    one = dtype(1.0)
    product = columns["l_extendedprice"][keep].astype(dtype) * \
        (one - columns["l_discount"][keep].astype(dtype))
    found, group = np.unique(row, return_inverse=True)
    if dtype is np.float64:
        revenue = np.bincount(group, weights=product, minlength=len(found))
    else:
        revenue = np.zeros(len(found), dtype)
        np.add.at(revenue, group, product)
    return {"l_orderkey": orders["o_orderkey"][found],
            "revenue": revenue.astype(np.float64),
            "o_orderdate": orders["o_orderdate"][found],
            "o_shippriority": orders["o_shippriority"][found]}


def reference(columns, params: dict, dtype=np.float64) -> dict:
    """The first ten of ``grouped`` by (revenue descending, o_orderdate)."""
    groups = grouped(columns, params, dtype)
    first = np.lexsort((groups["o_orderdate"], -groups["revenue"]))[:LIMIT]
    return {name: lane[first] for name, lane in groups.items()}


def _row(rows: dict, i: int) -> tuple:
    return (int(rows["l_orderkey"][i]), int(rows["o_orderdate"][i]),
            int(rows["o_shippriority"][i]))


def mismatch(got, want):
    """None when the answer is the reference's, else what differs: as many
    rows as the reference (ten, or every group where there are fewer),
    ``l_orderkey``, ``o_orderdate`` and ``o_shippriority`` exact and in the
    reference's order, ``revenue`` within ``REL_TOLERANCE``.  Two
    neighbouring rows may come swapped only where the reference's own
    revenues differ by less than the tolerance and their dates are equal:
    the ORDER BY does not tell them apart at the engine's precision."""
    n = len(want["l_orderkey"])
    if len(got["l_orderkey"]) != n:
        return f"{len(got['l_orderkey'])} rows, the reference {n}"
    rev = want["revenue"]
    at = list(range(n))         # the reference's row that got's row i is
    i = 0
    while i < n:
        if _row(got, i) != _row(want, i):
            tie = (i + 1 < n
                   and abs(rev[i] - rev[i + 1]) <= REL_TOLERANCE * rev[i]
                   and want["o_orderdate"][i] == want["o_orderdate"][i + 1]
                   and _row(got, i) == _row(want, i + 1)
                   and _row(got, i + 1) == _row(want, i))
            if not tie:
                return (f"row {i} is (l_orderkey, o_orderdate, "
                        f"o_shippriority) {_row(got, i)}, the reference "
                        f"{_row(want, i)} (keys and order are exact)")
            at[i], at[i + 1] = i + 1, i
            i += 1
        i += 1
    for i, j in enumerate(at):
        g, w = float(got["revenue"][i]), float(rev[j])
        if not np.isfinite(g) or abs(g - w) > REL_TOLERANCE * abs(w):
            return (f"revenue of order {int(want['l_orderkey'][j])} "
                    f"{g!r}, the reference {w!r} (relative "
                    f"{abs(g - w) / abs(w):.3e})")
    return None


def deviation(got: dict, want: dict) -> float:
    """The largest relative difference of two answers' revenues, row for
    row (what ``mismatch`` holds to the tolerance)."""
    rel = np.abs(got["revenue"] - want["revenue"]) / np.abs(want["revenue"])
    return float(np.max(rel, initial=0.0))


def answer_rows(got) -> int:
    return len(got["l_orderkey"])


def _side_bytes() -> int:
    """One read of ORDERS' four columns and CUSTOMER's two as generated:
    24 B an order; a customer's key, its segment's characters and the
    4-byte offset that finds them."""
    side = tables().side
    segment = side["customer"]["c_mktsegment"]
    return (len(side["orders"]["o_orderkey"]) * (8 + 8 + 4 + 4)
            + len(segment) * (8 + 4) + int(np.char.str_len(segment).sum()))


def least_bytes(n_rows: int, out_rows: int) -> int:
    """The least the query must move through HBM: one read of the ten
    columns it touches (LINEITEM's four: int64, two float64, a date32;
    ORDERS' and CUSTOMER's by the rows the generator made, not by a
    ratio) and the answer's four columns written.  Bandwidth-bound: two
    flops a surviving line are nothing."""
    return (n_rows * (8 + 8 + 8 + 4) + _side_bytes()
            + out_rows * (8 + 8 + 4 + 4))


#: the parameter sets ``join_least_bytes`` averages over: every segment at
#: the middle of the month (the date moves the live rows by under 1%)
_TYPICAL_DAY = 16


def join_least_bytes() -> float:
    """The least bytes Q3's two joins must move, whatever implements them:
    each side's key and carried columns of the LIVE rows read once, the
    output written once.  ORDERS before the date (custkey, and orderkey,
    date, priority carried: 24 B) meet the segment's customers (8 B) and
    give the open orders (16 B: the customer's key has done its work);
    LINEITEM shipped after the date (orderkey, price, discount: 24 B; the
    ship date has done its work) meets them and gives the lines to add up
    (32 B).  The mean over the five segments at the middle of the month,
    counted in NumPy over the generator's tables."""
    columns = tables()
    orders = columns.side["orders"]
    customer = columns.side["customer"]
    total = 0
    segments = sorted(set(customer["c_mktsegment"].tolist()))
    for segment in segments:
        params = {"segment": segment, "day": _TYPICAL_DAY}
        cut = (cut_date(params) - _EPOCH).days
        open_order = _open_orders(columns, params, cut)
        before = int(np.count_nonzero(orders["o_orderdate"] < cut))
        in_segment = int(np.count_nonzero(
            customer["c_mktsegment"] == segment))
        opened = int(np.count_nonzero(open_order))
        shipped = columns["l_shipdate"] > cut
        lines = int(np.count_nonzero(
            shipped & open_order[order_index(columns["l_orderkey"])]))
        total += (before * 24 + in_segment * 8 + opened * 16
                  + int(np.count_nonzero(shipped)) * 24 + opened * 16
                  + lines * 32)
    return total / max(len(segments), 1)
