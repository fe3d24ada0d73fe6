"""TPC-H LINEITEM, the five columns Q6 and Q18's subquery read, from a seed.

The distributions are the specification's (clause 4.2.3), drawn by NumPy's
PCG64 and not by dbgen's streams, so the spec's validation answers do not
apply: the plain reference beside each query decides what is correct.

* ``O_ORDERKEY``: dbgen's sparse keys, the first 8 of every 32.
* lines per order: uniform 1..7, emitted clustered by ``l_orderkey``.
* ``L_QUANTITY``: uniform 1..50.
* ``L_PARTKEY``: uniform 1..SF*200,000; ``P_RETAILPRICE`` =
  (90000 + (partkey/10 mod 20001) + 100*(partkey mod 1000)) / 100.
* ``L_EXTENDEDPRICE`` = quantity * retail price.
* ``L_DISCOUNT``: uniform 0.00..0.10 in steps of 0.01.
* ``O_ORDERDATE``: uniform 1992-01-01 .. 1998-08-02 (ENDDATE - 151 days);
  ``L_SHIPDATE`` = order date + uniform 1..121 days.

``decimal(15,2)`` columns are float64 (the configuration's ``reduced`` says
why).  The hundredths are made by one correctly rounded division, so a
column's 0.07 is the same double as the literal 0.07.

The table is drawn in ``N_STREAMS`` independent streams, one per slice of
orders, which ``N_THREADS`` threads fill side by side (NumPy releases the
interpreter lock): set-up is most of what a run costs, and one stream took
three times as long.  The same seed gives the same table on any machine.

A datagen module has one entry point, ``generate(config, seed)``, which
returns a dict of equally long NumPy arrays in schema order; ``SCHEMA`` names
each column's Arrow type.  It imports nothing from the engine.
"""

from __future__ import annotations

import datetime

import numpy as np

#: column -> Arrow type name, in schema order
SCHEMA = {
    "l_orderkey": "int64",
    "l_quantity": "float64",
    "l_extendedprice": "float64",
    "l_discount": "float64",
    "l_shipdate": "date32",
}

ORDERS_PER_SF = 1_500_000
PARTS_PER_SF = 200_000
_EPOCH = datetime.date(1970, 1, 1)
STARTDATE = (datetime.date(1992, 1, 1) - _EPOCH).days
ENDDATE = (datetime.date(1998, 12, 31) - _EPOCH).days
#: the last order date dbgen draws: ENDDATE - 151 days
LAST_ORDERDATE = ENDDATE - 151


def sparse_orderkeys(start: int, stop: int) -> np.ndarray:
    """dbgen's keys of orders ``start..stop``: of every 32 consecutive keys
    only the first 8 are used (mk_sparse), starting at 1."""
    i = np.arange(start, stop, dtype=np.int64)
    return (i >> 3 << 5) + (i & 7) + 1


#: the table is drawn in this many independent streams, one per slice of
#: orders, so that threads can fill it side by side.  Part of the data's
#: definition: another count gives another table from the same seed.
N_STREAMS = 16
N_THREADS = 8


def retail_cents(n_parts: int) -> np.ndarray:
    """``P_RETAILPRICE`` in cents for part keys 0..n_parts (0 is unused)."""
    pk = np.arange(n_parts + 1, dtype=np.int64)
    return 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)


def generate(config: dict, seed: int) -> dict:
    from concurrent.futures import ThreadPoolExecutor
    sf = float(config["scale_factor"])
    n_orders = int(round(sf * ORDERS_PER_SF))
    n_parts = int(round(sf * PARTS_PER_SF))
    root = np.random.SeedSequence([int(seed), 0x7C9])
    head, *streams = root.spawn(N_STREAMS + 1)

    lines = np.random.default_rng(head).integers(
        1, 8, n_orders, dtype=np.int8)
    order_cuts = np.linspace(0, n_orders, N_STREAMS + 1).astype(np.int64)
    row_ends = np.cumsum(lines, dtype=np.int64)
    row_cuts = np.concatenate(([0], row_ends[order_cuts[1:] - 1]))
    n = int(row_ends[-1])
    price = retail_cents(n_parts)
    out = {
        "l_orderkey": np.empty(n, np.int64),
        "l_quantity": np.empty(n, np.float64),
        "l_extendedprice": np.empty(n, np.float64),
        "l_discount": np.empty(n, np.float64),
        "l_shipdate": np.empty(n, np.int32),
    }

    def fill(i: int) -> None:
        rng = np.random.default_rng(streams[i])
        o0, o1 = order_cuts[i], order_cuts[i + 1]
        r0, r1 = row_cuts[i], row_cuts[i + 1]
        m = int(r1 - r0)
        per_order = lines[o0:o1]
        orderdate = rng.integers(STARTDATE, LAST_ORDERDATE + 1,
                                 int(o1 - o0), dtype=np.int32)
        out["l_orderkey"][r0:r1] = np.repeat(
            sparse_orderkeys(o0, o1), per_order)
        quantity = rng.integers(1, 51, m, dtype=np.int64)
        out["l_quantity"][r0:r1] = quantity
        partkey = rng.integers(1, n_parts + 1, m, dtype=np.int32)
        # cents are exact in int64; one division rounds the line's price
        # to the nearest double
        np.divide(quantity * price[partkey], 100.0,
                  out=out["l_extendedprice"][r0:r1])
        np.divide(rng.integers(0, 11, m, dtype=np.int8), 100.0,
                  out=out["l_discount"][r0:r1])
        np.add(np.repeat(orderdate, per_order),
               rng.integers(1, 122, m, dtype=np.int32),
               out=out["l_shipdate"][r0:r1])

    with ThreadPoolExecutor(N_THREADS) as pool:
        list(pool.map(fill, range(N_STREAMS)))   # list(): raise what failed
    return out
