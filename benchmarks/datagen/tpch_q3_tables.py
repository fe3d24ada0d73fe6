"""TPC-H LINEITEM, ORDERS and CUSTOMER, the ten columns Q3 reads, from a seed.

LINEITEM is ``tpch_lineitem.generate``'s own, called as it stands, less
``l_quantity`` (Q3 does not read it): the same seed gives the accepted
deployments' fact table, row for row.  ORDERS and CUSTOMER follow clause
4.2.3 of the specification:

* ``O_ORDERKEY``: one row an order in rising key, dbgen's sparse keys
  (``tpch_lineitem.sparse_orderkeys``), so every ``l_orderkey`` finds its
  order and every order has one to seven lines.
* ``O_ORDERDATE``: **the order date the accepted generator drew** for the
  order's lines: the first draw of each of its 16 streams, re-drawn here
  from the same ``SeedSequence([seed, 0x7C9])`` spawn.  So
  ``1 <= l_shipdate - o_orderdate <= 121`` holds for every line, which is
  what lets Q3's two date predicates leave anything.
* ``O_CUSTKEY``: uniform over the customer keys 1..SF*150,000 that are not
  divisible by three (a third of the customers have no order).
* ``O_SHIPPRIORITY``: 0.
* ``C_CUSTKEY``: 1..SF*150,000; ``C_MKTSEGMENT``: uniform over the five
  ``SEGMENTS``, at their own lengths (8-10 bytes, no padding, no nulls).

The new columns come from streams of their own
(``SeedSequence([seed, 0x03C3])``), one for ``o_custkey`` and one for
``c_mktsegment``; like the stream count of ``tpch_lineitem``, that is part
of the data's definition.

**The hand-off.**  The harness makes ONE table per configuration
(``runner.Bench.load``): ``generate`` returns the four LINEITEM columns as
``Tables``, a ``dict`` that also carries ``.side = {"orders": {...},
"customer": {...}}`` (NumPy arrays by column), and keeps the object as the
module's ``LAST``.  ``queries/q3.py`` reads ``columns.side`` in its
reference, and in ``build`` reaches this module object through
``harness.cells.load_module`` (which memoises by path, so it is the
instance the harness called) to make the two side DataFrames.  NumPy only;
nothing from the engine.
"""

from __future__ import annotations

import numpy as np

from benchmarks.datagen import tpch_lineitem as base

#: LINEITEM, column -> Arrow type name, in schema order
SCHEMA = {
    "l_orderkey": "int64",
    "l_extendedprice": "float64",
    "l_discount": "float64",
    "l_shipdate": "date32",
}

#: the tables the harness does not make itself
SIDE_SCHEMAS = {
    "orders": {
        "o_orderkey": "int64",
        "o_custkey": "int64",
        "o_orderdate": "date32",
        "o_shippriority": "int32",
    },
    "customer": {
        "c_custkey": "int64",
        "c_mktsegment": "string",
    },
}

CUSTOMERS_PER_SF = 150_000
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")


class Tables(dict):
    """LINEITEM's columns, with ORDERS and CUSTOMER beside them."""

    side: dict


#: what the last ``generate`` returned (see "The hand-off")
LAST: Tables = None


def order_dates(seed: int, n_orders: int) -> np.ndarray:
    """``o_orderdate`` of orders 0..n_orders: what ``tpch_lineitem.generate``
    drew first from each of its streams over its slices of the orders."""
    _, *streams = np.random.SeedSequence(
        [int(seed), 0x7C9]).spawn(base.N_STREAMS + 1)
    cuts = np.linspace(0, n_orders, base.N_STREAMS + 1).astype(np.int64)
    out = np.empty(n_orders, np.int32)
    for i, stream in enumerate(streams):
        out[cuts[i]:cuts[i + 1]] = np.random.default_rng(stream).integers(
            base.STARTDATE, base.LAST_ORDERDATE + 1,
            int(cuts[i + 1] - cuts[i]), dtype=np.int32)
    return out


def generate(config: dict, seed: int) -> Tables:
    global LAST
    sf = float(config["scale_factor"])
    n_orders = int(round(sf * base.ORDERS_PER_SF))
    n_customers = int(round(sf * CUSTOMERS_PER_SF))
    lineitem = base.generate(config, seed)
    custkey_stream, segment_stream = np.random.SeedSequence(
        [int(seed), 0x03C3]).spawn(2)
    # the j-th key that three does not divide: 1, 2, 4, 5, 7, 8, ...
    j = np.random.default_rng(custkey_stream).integers(
        0, n_customers - n_customers // 3, n_orders, dtype=np.int64)
    segment = np.random.default_rng(segment_stream).integers(
        0, len(SEGMENTS), n_customers, dtype=np.int8)
    tables = Tables((name, lineitem[name]) for name in SCHEMA)
    tables.side = {
        "orders": {
            "o_orderkey": base.sparse_orderkeys(0, n_orders),
            "o_custkey": 3 * (j >> 1) + (j & 1) + 1,
            "o_orderdate": order_dates(seed, n_orders),
            "o_shippriority": np.zeros(n_orders, np.int32),
        },
        "customer": {
            "c_custkey": np.arange(1, n_customers + 1, dtype=np.int64),
            "c_mktsegment": np.array(SEGMENTS)[segment],
        },
    }
    LAST = tables
    return tables
