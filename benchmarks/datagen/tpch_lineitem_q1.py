"""TPC-H LINEITEM, the seven columns Q1 reads, from a seed.

Four of them (``l_quantity``, ``l_extendedprice``, ``l_discount``,
``l_shipdate``) are ``tpch_lineitem.generate``'s own, called as it stands:
the same seed gives the same values, row for row, as the accepted
deployments' table (its ``l_orderkey`` is drawn and dropped: Q1 does not
read it).  The three Q1 adds follow clause 4.2.3 of the specification:

* ``L_TAX``: uniform 0.00..0.08 in steps of 0.01, the hundredths made by
  one correctly rounded division as the discount's are.
* ``L_RECEIPTDATE`` = ship date + uniform 1..30 days (drawn, not kept).
* ``L_RETURNFLAG``: 'R' or 'A' at random where the receipt date is on or
  before CURRENTDATE (1995-06-17), else 'N'.
* ``L_LINESTATUS``: 'O' where the ship date is after CURRENTDATE, else 'F'.

Four groups result: (A,F) and (R,F) a quarter of the table each, (N,O)
half, and (N,F) a sliver (shipped on or before CURRENTDATE, received
after it: about 1% of the lines).  That skew is the source's.

``char(1)`` columns are NumPy ``<U1`` arrays (the harness's
``arrow_table`` makes Arrow ``string`` of them; an ``S1`` array would
become ``binary``).  They are filled as code points and viewed as text,
which costs nothing.

The new columns are drawn in ``N_STREAMS`` streams of their own over equal
slices of the rows, filled by ``N_THREADS`` threads; like the stream count
of the module above, that is part of the data's definition.  NumPy only;
nothing from the engine.
"""

from __future__ import annotations

import datetime

import numpy as np

from benchmarks.datagen import tpch_lineitem as base

#: column -> Arrow type name, in schema order
SCHEMA = {
    "l_quantity": "float64",
    "l_extendedprice": "float64",
    "l_discount": "float64",
    "l_tax": "float64",
    "l_shipdate": "date32",
    "l_returnflag": "string",
    "l_linestatus": "string",
}

CURRENTDATE = (datetime.date(1995, 6, 17) - datetime.date(1970, 1, 1)).days
N_STREAMS = 16
N_THREADS = 8


def generate(config: dict, seed: int) -> dict:
    from concurrent.futures import ThreadPoolExecutor
    shared = base.generate(config, seed)
    ship = shared["l_shipdate"]
    n = ship.shape[0]
    streams = np.random.SeedSequence([int(seed), 0x51A6]).spawn(N_STREAMS)
    cuts = np.linspace(0, n, N_STREAMS + 1).astype(np.int64)
    tax = np.empty(n, np.float64)
    # UTF-32 code points, which is what a '<U1' array holds
    returnflag = np.empty(n, np.uint32)
    linestatus = np.empty(n, np.uint32)

    def fill(i: int) -> None:
        rng = np.random.default_rng(streams[i])
        r0, r1 = cuts[i], cuts[i + 1]
        m = int(r1 - r0)
        np.divide(rng.integers(0, 9, m, dtype=np.int8), 100.0,
                  out=tax[r0:r1])
        receipt = ship[r0:r1] + rng.integers(1, 31, m, dtype=np.int32)
        returned = np.where(rng.integers(0, 2, m, dtype=np.int8) == 0,
                            np.uint32(ord("R")), np.uint32(ord("A")))
        returnflag[r0:r1] = np.where(receipt <= CURRENTDATE, returned,
                                     np.uint32(ord("N")))
        linestatus[r0:r1] = np.where(ship[r0:r1] > CURRENTDATE,
                                     np.uint32(ord("O")),
                                     np.uint32(ord("F")))

    with ThreadPoolExecutor(N_THREADS) as pool:
        list(pool.map(fill, range(N_STREAMS)))   # list(): raise what failed
    return {
        "l_quantity": shared["l_quantity"],
        "l_extendedprice": shared["l_extendedprice"],
        "l_discount": shared["l_discount"],
        "l_tax": tax,
        "l_shipdate": ship,
        "l_returnflag": returnflag.view("<U1"),
        "l_linestatus": linestatus.view("<U1"),
    }
