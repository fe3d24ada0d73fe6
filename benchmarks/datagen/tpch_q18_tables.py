"""TPC-H LINEITEM, ORDERS and CUSTOMER, the eight columns Q18 reads, from a
seed.

Nothing is drawn here.  The accepted generators are called as they stand
and their columns picked, so the same seed gives the accepted deployments'
tables row for row:

* LINEITEM ``l_orderkey``: ``tpch_q3_tables.generate``'s (which is
  ``tpch_lineitem.generate``'s); ``l_quantity``:
  ``tpch_lineitem_q1.generate``'s (``tpch_lineitem``'s too: Q3's tables
  leave it out, Q1's keep it and leave the key out).
* ORDERS ``o_orderkey``, ``o_custkey``, ``o_orderdate``:
  ``tpch_q3_tables``' (dbgen's sparse keys in rising order; a customer key
  that three does not divide; the date the lines' ship dates were drawn
  from).
* ``O_TOTALPRICE`` (clause 4.2.3): the sum over the order's lines of
  ``l_extendedprice * (1 + l_tax) * (1 - l_discount)``, the price and the
  discount as ``tpch_lineitem`` draws them and the tax as
  ``tpch_lineitem_q1`` does, added up in float64 over the clustered runs
  and rounded to cents by one correctly rounded division, as the
  hundredths of the other ``decimal(15,2)`` columns are made.
* CUSTOMER ``c_custkey`` 1..SF*150,000; ``C_NAME`` = "Customer#" and the
  key in nine digits (clause 4.2.3): 18 bytes a name, no nulls.

**The hand-off** is ``tpch_q3_tables``' (its docstring has the why): the
harness makes ONE table a configuration, so ``generate`` returns LINEITEM's
two columns as ``Tables``, a ``dict`` that also carries ``.side = {"orders":
{...}, "customer": {...}}``, and keeps the object as this module's ``LAST``
for ``queries/q18.py``.  NumPy only; nothing from the engine.
"""

from __future__ import annotations

import numpy as np

from benchmarks.datagen import tpch_lineitem_q1, tpch_q3_tables

#: LINEITEM, column -> Arrow type name, in schema order
SCHEMA = {
    "l_orderkey": "int64",
    "l_quantity": "float64",
}

#: the tables the harness does not make itself
SIDE_SCHEMAS = {
    "orders": {
        "o_orderkey": "int64",
        "o_custkey": "int64",
        "o_orderdate": "date32",
        "o_totalprice": "float64",
    },
    "customer": {
        "c_custkey": "int64",
        "c_name": "string",
    },
}


class Tables(dict):
    """LINEITEM's columns, with ORDERS and CUSTOMER beside them."""

    side: dict


#: what the last ``generate`` returned (see "The hand-off")
LAST: Tables = None


def run_starts(orderkey: np.ndarray) -> np.ndarray:
    """The first row of every order in the clustered LINEITEM."""
    return np.flatnonzero(np.concatenate(
        ([True], orderkey[1:] != orderkey[:-1])))


def total_price(orderkey, extendedprice, discount, tax) -> np.ndarray:
    """``O_TOTALPRICE`` of every order of the clustered LINEITEM, in the
    orders' own order, to the cent."""
    charge = extendedprice * (1.0 + tax) * (1.0 - discount)
    return np.rint(np.add.reduceat(charge, run_starts(orderkey))
                   * 100.0) / 100.0


def customer_names(custkey: np.ndarray) -> np.ndarray:
    """``C_NAME``: "Customer#%09d" of each key."""
    return np.char.add("Customer#", np.char.zfill(custkey.astype(str), 9))


def generate(config: dict, seed: int) -> Tables:
    global LAST
    q3 = tpch_q3_tables.generate(config, seed)
    q1 = tpch_lineitem_q1.generate(config, seed)
    orders = q3.side["orders"]
    custkey = q3.side["customer"]["c_custkey"]
    tables = Tables(l_orderkey=q3["l_orderkey"],
                    l_quantity=q1["l_quantity"])
    tables.side = {
        "orders": {
            "o_orderkey": orders["o_orderkey"],
            "o_custkey": orders["o_custkey"],
            "o_orderdate": orders["o_orderdate"],
            "o_totalprice": total_price(
                q3["l_orderkey"], q1["l_extendedprice"],
                q1["l_discount"], q1["l_tax"]),
        },
        "customer": {
            "c_custkey": custkey,
            "c_name": customer_names(custkey),
        },
    }
    LAST = tables
    return tables
