import numpy as np

from benchmarks.datagen import tpch_lineitem as gen


def test_same_seed_same_table_other_seed_other_table():
    a = gen.generate({"scale_factor": 0.02}, 2**31 + 11)
    b = gen.generate({"scale_factor": 0.02}, 2**31 + 11)
    c = gen.generate({"scale_factor": 0.02}, 12)
    assert list(a) == list(gen.SCHEMA)
    for name in a:
        assert np.array_equal(a[name], b[name]), name
    assert any(a[n].shape != c[n].shape or not np.array_equal(a[n], c[n])
               for n in a)


def test_spec_ranges_and_shapes():
    sf = 0.02
    t = gen.generate({"scale_factor": sf}, 5)
    n = t["l_orderkey"].shape[0]
    n_orders = int(sf * gen.ORDERS_PER_SF)
    assert all(v.shape == (n,) for v in t.values())
    assert {k: str(v.dtype) for k, v in t.items()} == {
        "l_orderkey": "int64", "l_quantity": "float64",
        "l_extendedprice": "float64", "l_discount": "float64",
        "l_shipdate": "int32"}
    keys, counts = np.unique(t["l_orderkey"], return_counts=True)
    # clustered and rising, sparse (8 of every 32), 1..7 lines an order
    assert np.all(np.diff(t["l_orderkey"]) >= 0)
    assert keys.shape[0] == n_orders
    assert np.all((keys - 1) % 32 < 8)
    assert counts.min() == 1 and counts.max() == 7
    assert abs(n / n_orders - 4.0) < 0.05
    q = t["l_quantity"]
    assert q.min() == 1 and q.max() == 50 and np.all(q == np.round(q))
    d = t["l_discount"]
    assert set(np.unique(d)) == {h / 100.0 for h in range(11)}
    assert 0.07 in set(np.unique(d))       # the same double as the literal
    ship = t["l_shipdate"]
    assert ship.min() >= gen.STARTDATE + 1
    assert ship.max() <= gen.LAST_ORDERDATE + 121
    # price = quantity x retail price, retail in [900.00, 2098.99]
    unit = t["l_extendedprice"] / q
    assert unit.min() >= 900.0 - 1e-9 and unit.max() <= 2098.99 + 1e-9
    cents = t["l_extendedprice"] * 100
    assert np.allclose(cents, np.round(cents), atol=1e-6)
