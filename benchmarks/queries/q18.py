"""TPC-H Q18, the large volume customer query (spec clause 2.4.18)::

    select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    from customer, orders, lineitem
    where o_orderkey in (select l_orderkey from lineitem
                         group by l_orderkey having sum(l_quantity) > :1)
      and c_custkey = o_custkey and o_orderkey = l_orderkey
    group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    order by o_totalprice desc, o_orderdate        -- the first 100 rows

Parameter (clause 2.4.18.3): ``quantity`` 312..315.  Of 7,500,000 orders
at SF5 a few dozen (23-58) hold more than that; each has seven lines (six
of 50 add up to 300), so a few hundred lines reach the last aggregate and
the limit of 100 does not bind.

The ``IN`` is a ``left_semi`` join of ORDERS on the subquery, written as
``queries/q18sub.build`` writes it; the tables join in the specification's
order (customer, orders, lineitem) and the planner picks each join's build
side (``configs/tpch_q18_1chip.json``, ``assumed.build_side``).

**Three tables, one DataFrame from the harness**: as in ``queries/q3.py``.
``build`` gets LINEITEM's DataFrame, which the plan reads twice (under the
subquery's aggregate and as a join side; one upload, one pin); ORDERS and
CUSTOMER come from the generator's hand-off
(``datagen/tpch_q18_tables.py``) and are made DataFrames once for that
``df``.

**The join types are held here.**  ``harness/runner.plan_fault`` asks an
attribute of EVERY operator of a class, so ``plan_must_hold`` cannot ask
for one ``left_semi`` and two ``inner`` ``HashJoinExec`` in one plan;
``answer`` reads the session's last plan (by class name and ``how``, no
import) and raises where ``JOINS_MUST_HOLD`` is not what ran, which the
harness counts as a failed query.

A query module gives the harness: ``COLUMNS``, ``build``, ``answer``,
``reference``, ``mismatch``, ``answer_rows`` and ``least_bytes``; this one
also ``semi_join_least_bytes``
(``layer_metrics/semi_join_hbm_roofline_share.py``).  Only ``build``
touches the program; ``reference`` is NumPy over the generated columns.
"""

from __future__ import annotations

import os

import numpy as np

#: LINEITEM's columns (the table the harness makes and sums the bytes of)
COLUMNS = ("l_orderkey", "l_quantity")
KEY_COLUMNS = ("c_name", "c_custkey", "o_orderkey", "o_orderdate",
               "o_totalprice")
ANSWER_COLUMNS = KEY_COLUMNS + ("sum_quantity",)
LIMIT = 100

#: ``o_totalprice`` is equal to the cent.  It is a stored double that no
#: arithmetic touches; it crosses the chip as a pair of float32 (2**-48
#: relative: 2e-9 of the largest price, 600,000), so an honest answer is
#: within a millionth of a cent.  Half a cent catches a price kept in
#: float32 (steps of 0.03 at 500,000: off by more than half a cent in two
#: rows of three) and a price from a neighbouring order.  Everything else
#: is exact: ``sum(l_quantity)`` adds at most seven whole numbers up to
#: 50.
PRICE_TOLERANCE = 0.005

#: the ``HashJoinExec`` the executed plan must hold, by ``how``
JOINS_MUST_HOLD = {"left_semi": 1, "inner": 2}

_DATAGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "datagen", "tpch_q18_tables.py")

# (lineitem's DataFrame, the generator's tables, orders, customer): the
# side DataFrames of the one `df` the harness asks with
_FRAMES: tuple = ()


def tables():
    """What the generator made last, from the module the harness called."""
    from benchmarks.harness.cells import load_module
    last = load_module(_DATAGEN).LAST
    if last is None:
        raise RuntimeError("Q18's tables have not been generated: "
                           "datagen/tpch_q18_tables.generate comes first")
    return last


def side_frames(df):
    """ORDERS and CUSTOMER as DataFrames of ``df``'s session, made once,
    their Arrow types held to the generator's ``SIDE_SCHEMAS`` as
    ``runner.arrow_table`` holds lineitem's to ``SCHEMA``."""
    global _FRAMES
    from benchmarks.harness.cells import load_module
    from benchmarks.harness.runner import arrow_table
    made = tables()
    if not _FRAMES or _FRAMES[0] is not df or _FRAMES[1] is not made:
        schemas = load_module(_DATAGEN).SIDE_SCHEMAS
        frames = [df.session.create_dataframe(
            arrow_table(made.side[name], schemas[name]), num_partitions=1)
            for name in ("orders", "customer")]
        _FRAMES = (df, made, *frames)
    return _FRAMES[2], _FRAMES[3]


def grouped_frame(df, params: dict):
    """Q18 before its ORDER BY and its limit: every group
    (``devtools/chip_q18_full.py`` holds all of them to ``grouped``)."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col, lit
    orders, customer = side_frames(df)
    # the subquery, as queries/q18sub.build writes it
    large = (df.group_by(col("l_orderkey"))
             .agg(F.sum(col("l_quantity")).alias("sum_quantity"))
             .filter(col("sum_quantity") > lit(float(params["quantity"])))
             .select(col("l_orderkey")))
    large_orders = orders.join(
        large, on=col("o_orderkey") == col("l_orderkey"), how="left_semi")
    return (customer
            .join(large_orders, on=col("c_custkey") == col("o_custkey"),
                  how="inner")
            .join(df, on=col("o_orderkey") == col("l_orderkey"),
                  how="inner")
            .group_by(*(col(name) for name in KEY_COLUMNS))
            .agg(F.sum(col("l_quantity")).alias("sum_quantity"))
            .select(*ANSWER_COLUMNS))


def build(df, params: dict):
    from spark_rapids_tpu.api.column import col
    return (grouped_frame(df, params)
            .order_by(col("o_totalprice").desc(), col("o_orderdate"))
            .limit(LIMIT))


def joins_fault(plan):
    """None where the plan holds ``JOINS_MUST_HOLD``'s hash joins, else
    what it holds instead."""
    found: dict = {}

    def visit(e):
        if type(e).__name__ == "HashJoinExec":
            found[e.how] = found.get(e.how, 0) + 1
    plan.foreach(visit)
    if found != JOINS_MUST_HOLD:
        return (f"the plan's HashJoinExec are {found}, the deployment's "
                f"{JOINS_MUST_HOLD}")
    return None


def answer(table) -> dict:
    """The engine's Arrow table as the value to compare: one NumPy array
    a column, the names as Python strings, the dates as days since
    1970-01-01, rows in answer order.  Raises where the plan that made it
    is not the deployment's (see "The join types are held here")."""
    import pyarrow as pa
    if tuple(table.column_names) != ANSWER_COLUMNS:
        raise ValueError(f"Q18 answers {ANSWER_COLUMNS}, got "
                         f"{table.column_names}")
    if _FRAMES and _FRAMES[0].session.last_plan is not None:
        fault = joins_fault(_FRAMES[0].session.last_plan)
        if fault:
            raise ValueError(fault)
    table = table.combine_chunks()

    def lane(name, dtype, arrow_type=None):
        column = table.column(name)
        if column.null_count:
            raise ValueError(f"Q18's {name} holds nulls")
        if arrow_type is not None:
            column = column.cast(arrow_type)
        return column.to_numpy(zero_copy_only=False).astype(dtype)
    return {"c_name": np.array(lane("c_name", object), dtype=str),
            "c_custkey": lane("c_custkey", np.int64),
            "o_orderkey": lane("o_orderkey", np.int64),
            "o_orderdate": lane("o_orderdate", np.int32, pa.int32()),
            "o_totalprice": lane("o_totalprice", np.float64),
            "sum_quantity": lane("sum_quantity", np.float64)}


def order_sums(columns, dtype=np.float64):
    """(the first row of every order in the clustered LINEITEM, each
    order's ``sum(l_quantity)``).  Orders lie in rising key, so run ``i``
    is row ``i`` of ORDERS; ``grouped`` checks that."""
    keys = columns["l_orderkey"]
    starts = np.flatnonzero(np.concatenate(([True], keys[1:] != keys[:-1])))
    return starts, np.add.reduceat(columns["l_quantity"].astype(dtype),
                                   starts)


def grouped(columns, params: dict, price_dtype=np.float64,
            drop_line=None) -> dict:
    """Q18 before its ORDER BY and its limit, in NumPy: every group, in
    rising ``o_orderkey``.  ``price_dtype`` is the precision
    ``o_totalprice`` is kept in, and ``drop_line`` a row of LINEITEM the
    outer join loses (the tests recompute with float32 and with a line
    dropped to show that ``mismatch`` bites)."""
    orders, customer = columns.side["orders"], columns.side["customer"]
    starts, sums = order_sums(columns)
    large = np.flatnonzero(sums > float(params["quantity"]))
    if not np.array_equal(orders["o_orderkey"][large],
                          columns["l_orderkey"][starts[large]]):
        raise ValueError("ORDERS is not LINEITEM's orders in rising key")
    custkey = orders["o_custkey"][large]
    who = custkey - 1                  # c_custkey is 1..n in rising order
    if not np.array_equal(customer["c_custkey"][who], custkey):
        raise ValueError("CUSTOMER is not in rising c_custkey from 1")
    joined = sums[large].copy()
    if drop_line is not None:
        hit = np.searchsorted(starts, drop_line, side="right") - 1
        joined[large == hit] -= columns["l_quantity"][drop_line]
    price = orders["o_totalprice"][large]
    return {"c_name": customer["c_name"][who].astype(str),
            "c_custkey": custkey,
            "o_orderkey": orders["o_orderkey"][large],
            "o_orderdate": orders["o_orderdate"][large],
            "o_totalprice": price.astype(price_dtype).astype(np.float64),
            "sum_quantity": joined}


def reference(columns, params: dict, **how) -> dict:
    """The first hundred of ``grouped`` by (o_totalprice descending,
    o_orderdate)."""
    groups = grouped(columns, params, **how)
    first = np.lexsort((groups["o_orderdate"],
                        -groups["o_totalprice"]))[:LIMIT]
    return {name: lane[first] for name, lane in groups.items()}


def _row(rows: dict, i: int) -> tuple:
    return (str(rows["c_name"][i]), int(rows["c_custkey"][i]),
            int(rows["o_orderkey"][i]), int(rows["o_orderdate"][i]),
            float(rows["sum_quantity"][i]))


def mismatch(got, want):
    """None when the answer is the reference's, else what differs: as many
    rows as the reference (a hundred, or every group where there are
    fewer), ``c_name``, ``c_custkey``, ``o_orderkey``, ``o_orderdate`` and
    ``sum_quantity`` exact and in the reference's order, ``o_totalprice``
    within ``PRICE_TOLERANCE``.  Two neighbouring rows may come swapped
    only where both sort keys are equal in the reference: the ORDER BY
    does not tell them apart."""
    n = len(want["o_orderkey"])
    if len(got["o_orderkey"]) != n:
        return f"{len(got['o_orderkey'])} rows, the reference {n}"
    price, date = want["o_totalprice"], want["o_orderdate"]
    at = list(range(n))         # the reference's row that got's row i is
    i = 0
    while i < n:
        if _row(got, i) != _row(want, i):
            tie = (i + 1 < n and price[i] == price[i + 1]
                   and date[i] == date[i + 1]
                   and _row(got, i) == _row(want, i + 1)
                   and _row(got, i + 1) == _row(want, i))
            if not tie:
                return (f"row {i} is (c_name, c_custkey, o_orderkey, "
                        f"o_orderdate, sum_quantity) {_row(got, i)}, the "
                        f"reference {_row(want, i)} (all exact, in order)")
            at[i], at[i + 1] = i + 1, i
            i += 1
        i += 1
    for i, j in enumerate(at):
        g, w = float(got["o_totalprice"][i]), float(price[j])
        if not abs(g - w) < PRICE_TOLERANCE:
            return (f"o_totalprice of order {int(want['o_orderkey'][j])} "
                    f"{g!r}, the reference {w!r} (equal to the cent)")
    return None


def answer_rows(got) -> int:
    return len(got["o_orderkey"])


#: bytes of a row of ORDERS as Q18 reads it (two int64, a date32, a
#: float64) and of a row of the answer (those less the customer's key in
#: ORDERS, plus c_custkey, the name's 18 characters and its 4-byte offset,
#: and the sum)
_ORDER_BYTES = 8 + 8 + 4 + 8
_ANSWER_BYTES = 18 + 4 + 8 + 8 + 4 + 8 + 8


def least_bytes(n_rows: int, out_rows: int) -> int:
    """The least the query must move through HBM: LINEITEM's key and
    quantity read once (16 B a row: the subquery and the last join can
    share the read), ORDERS' four columns read once, the answer's rows
    written (their customers are read by position: as many rows again).
    Bandwidth-bound: one add a line."""
    n_orders = len(tables().side["orders"]["o_orderkey"])
    return (n_rows * (8 + 8) + n_orders * _ORDER_BYTES
            + 2 * out_rows * _ANSWER_BYTES)


def semi_join_least_bytes() -> float:
    """The least bytes Q18's semi join must move, whatever implements it:
    ORDERS' key read once, the subquery's live keys read once, the kept
    rows' four carried columns read and written once.  The mean over the
    mix's four thresholds (312..315), counted in NumPy over the
    generator's tables."""
    columns = tables()
    _, sums = order_sums(columns)
    n_orders = len(columns.side["orders"]["o_orderkey"])
    total = 0
    thresholds = (312, 313, 314, 315)
    for quantity in thresholds:
        kept = int(np.count_nonzero(sums > float(quantity)))
        total += n_orders * 8 + kept * 8 + 2 * kept * _ORDER_BYTES
    return total / len(thresholds)
