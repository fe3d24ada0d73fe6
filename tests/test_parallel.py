"""ICI mesh shuffle + distributed stage tests on the virtual 4-device
CPU mesh of tests/conftest.py (`chip_smoke.py --chips 4` is the same
mesh on real chips)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from spark_rapids_tpu.parallel import (DistributedAggregate,
                                       DistributedExchange, build_mesh,
                                       exchange_by_pid, allgather_batch,
                                       stack_shards, unstack_shards)
from spark_rapids_tpu.columnar.device import batch_to_arrow
from spark_rapids_tpu.expr.core import AttributeReference as A
from spark_rapids_tpu.expr.aggregates import (AggregateExpression, Average,
                                              Count, Sum)

N_DEV = 4

# every test here runs collectives across the mesh's device threads
pytestmark = pytest.mark.time_limit(300)


def the_mesh():
    assert len(jax.devices()) >= N_DEV
    return build_mesh(N_DEV)


def shard_tables(table, n=N_DEV):
    per = table.num_rows // n
    return [table.slice(i * per, per if i < n - 1 else
                        table.num_rows - per * (n - 1)) for i in range(n)]


def run_exchange(table, pid_of_row):
    """Drive exchange_by_pid under shard_map; return per-device tables."""
    mesh = the_mesh()
    tables = shard_tables(table)
    stacked = stack_shards(tables)
    # pids derive from a designated int column via a pure function
    def step(shard):
        b = jax.tree_util.tree_map(lambda x: x[0], shard)
        pids = pid_of_row(b)
        out = exchange_by_pid(b, pids, N_DEV, "data")
        return jax.tree_util.tree_map(lambda x: x[None], out)

    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False))
    out = fn(stacked)
    return [batch_to_arrow(b) for b in unstack_shards(out)]


def test_exchange_routes_all_rows():
    n = 800
    rng = np.random.default_rng(0)
    table = pa.table({
        "k": pa.array(rng.integers(0, 64, n).astype(np.int64)),
        "v": pa.array(rng.random(n)),
    })
    outs = run_exchange(table, lambda b: b.columns[0].data % N_DEV)
    # every row lands exactly once, on the right device
    total = 0
    for d, rb in enumerate(outs):
        ks = rb.column("k").to_numpy()
        assert (ks % N_DEV == d).all()
        total += rb.num_rows
    assert total == n
    # multiset of (k, v) preserved
    got = pa.concat_tables(
        [pa.Table.from_batches([rb]) for rb in outs]).sort_by(
        [("k", "ascending"), ("v", "ascending")])
    want = table.sort_by([("k", "ascending"), ("v", "ascending")])
    assert got.equals(want)


def test_exchange_carries_nulls_and_strings():
    n = 160
    rng = np.random.default_rng(1)
    ks = rng.integers(0, 32, n)
    strs = [None if i % 7 == 0 else f"s{ks[i]}_" + "x" * (i % 5)
            for i in range(n)]
    vs = [None if i % 5 == 0 else int(i) for i in range(n)]
    table = pa.table({
        "k": pa.array(ks.astype(np.int64)),
        "s": pa.array(strs, type=pa.string()),
        "v": pa.array(vs, type=pa.int64()),
    })
    outs = run_exchange(table, lambda b: b.columns[0].data % N_DEV)
    got = pa.concat_tables(
        [pa.Table.from_batches([rb]) for rb in outs]).to_pydict()
    want = table.to_pydict()
    key = lambda r: (r[0], r[1] is None, r[1] or "", r[2] is None, r[2] or 0)  # noqa: E731
    got_rows = sorted(zip(got["k"], got["s"], got["v"]), key=key)
    want_rows = sorted(zip(want["k"], want["s"], want["v"]), key=key)
    assert got_rows == want_rows


def run_exchange_guarded(table, pid_of_row, slot):
    """exchange_by_pid with a sub-capacity slot under on_overflow='guard';
    returns (per-device tables, per-device ok bools)."""
    mesh = the_mesh()
    stacked = stack_shards(shard_tables(table))

    def step(shard):
        b = jax.tree_util.tree_map(lambda x: x[0], shard)
        pids = pid_of_row(b)
        out, ok = exchange_by_pid(b, pids, N_DEV, "data", slot=slot,
                                  on_overflow="guard")
        return (jax.tree_util.tree_map(lambda x: x[None], out), ok[None])

    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=P("data"),
                           out_specs=(P("data"), P("data")),
                           check_vma=False))
    out, oks = fn(stacked)
    return ([batch_to_arrow(b) for b in unstack_shards(out)],
            [bool(x) for x in np.asarray(oks)])


def test_exchange_guard_mode_clean_when_budget_fits():
    """A sub-capacity slot that every destination fits under must route
    all rows AND report ok=True on every shard (the speculative-sizing
    fast path: ~slot/capacity of the full exchange footprint)."""
    n = 100 * N_DEV  # 100 rows/shard; round-robin pids -> 25 per destination
    table = pa.table({
        "k": pa.array((np.arange(n) % N_DEV).astype(np.int64)),
        "v": pa.array(np.arange(n, dtype=np.int64)),
    })
    outs, oks = run_exchange_guarded(
        table, lambda b: b.columns[0].data % N_DEV, slot=32)
    assert all(oks), oks
    total = 0
    for d, rb in enumerate(outs):
        assert (rb.column("k").to_numpy() % N_DEV == d).all()
        total += rb.num_rows
    assert total == n


def test_exchange_guard_mode_flags_overflow():
    """A skewed destination that exceeds the slot budget must flip the
    sending shards' guard to False — the caller's signal to re-run at
    slot=capacity — never silently drop rows without a flag."""
    n = 100 * N_DEV  # every row targets device 0: 100 sends/shard > slot=32
    table = pa.table({
        "k": pa.array(np.zeros(n, dtype=np.int64)),
        "v": pa.array(np.arange(n, dtype=np.int64)),
    })
    outs, oks = run_exchange_guarded(
        table, lambda b: b.columns[0].data % N_DEV, slot=32)
    assert not any(oks), oks


# -- exchange_by_pid against a NumPy routing, lane by lane, bit for bit -------

X_CAP = 32          # rows a shard
X_SLOT = 16         # the sub-capacity budget of the guarded cases


def _x_payload(kind: str, nulls: bool, rng):
    """A stacked [N_DEV, X_CAP] column of NumPy lanes of one lane type.
    Nulls keep whatever data lies under them: the exchange carries a
    lane as it is."""
    from spark_rapids_tpu import types as t
    from spark_rapids_tpu.columnar.device import DeviceColumn
    shape = (N_DEV, X_CAP)

    def flat(dtype, data):
        validity = rng.integers(0, 3, shape) > 0 if nulls else None
        return DeviceColumn(dtype, data=data, validity=validity)

    def doubles():
        d = rng.standard_normal(shape) * 1e6
        d[:, :7] = [np.nan, 0.0, -0.0, np.inf, -np.inf, 1 + 2.0**-40, 0.07]
        return rng.permuted(d, axis=1)
    makers = {
        "int64": lambda: flat(t.LONG, rng.integers(-2**62, 2**62, shape)),
        "float64": lambda: flat(t.DOUBLE, doubles()),
        "int32": lambda: flat(t.INT, rng.integers(
            -2**31, 2**31 - 1, shape).astype(np.int32)),
        "bool": lambda: flat(t.BOOLEAN, rng.integers(0, 2, shape) == 1),
        "date": lambda: flat(t.DATE, rng.integers(
            0, 20000, shape).astype(np.int32)),
    }
    if kind != "struct":
        return makers[kind]()
    st = t.StructType([t.StructField("a", t.LONG),
                       t.StructField("b", t.DOUBLE)])
    return DeviceColumn(
        st, validity=rng.integers(0, 4, shape) > 0 if nulls else None,
        children=(makers["int64"](), makers["float64"]()))


def _x_destinations(case: str, rng):
    """(pids [N_DEV, X_CAP], rows a shard [N_DEV], slot or None)."""
    rows = np.array([29, 32, 17, 30], dtype=np.int32)
    slot = None
    if case == "uniform":
        pids = rng.integers(0, N_DEV, (N_DEV, X_CAP))
    elif case == "all_to_one_chip":
        # every run starts at 0, and the full shard's fills the slot
        pids = np.full((N_DEV, X_CAP), 2)
    elif case == "a_chip_receives_nothing":
        pids = rng.choice([0, 1, 3], (N_DEV, X_CAP))
    elif case == "an_empty_shard":
        pids = rng.integers(0, N_DEV, (N_DEV, X_CAP))
        rows = np.array([31, 0, 32, 5], dtype=np.int32)
    elif case == "guard_fits":
        pids = np.tile(np.arange(X_CAP) % N_DEV, (N_DEV, 1))
        slot = X_SLOT
    elif case == "guard_overflows":
        pids = rng.integers(0, N_DEV, (N_DEV, X_CAP))
        pids[0, :] = 1                  # 29 rows for a budget of 16
        pids[2, :5] = 3
        slot = X_SLOT
    else:
        raise AssertionError(case)
    return pids.astype(np.int32), rows, slot


def _x_lanes(col):
    """Every lane of a row-aligned column tree, validity first; a node
    without a validity lane counts as all valid."""
    v = col.validity if col.validity is not None else \
        np.ones((N_DEV, X_CAP), dtype=bool)
    out = [v] + ([] if col.data is None else [col.data])
    for ch in col.children:
        out += _x_lanes(ch)
    return out


def _x_routed(lane, pids, rows, slot, dest):
    """NumPy routing of one stacked lane to chip `dest`: its rows in
    source-chip order, then source order, at most `slot` from a source;
    the padding zero."""
    out = np.zeros((N_DEV * slot,), dtype=lane.dtype)
    parts = [lane[s, :rows[s]][pids[s, :rows[s]] == dest][:slot]
             for s in range(N_DEV)]
    got = np.concatenate(parts)
    out[:got.shape[0]] = got
    return out, got.shape[0]


@functools.lru_cache(maxsize=None)
def _x_program(slot):
    """One jitted exchange a slot (jit keys the column tree itself)."""
    def step(shard):
        b = jax.tree_util.tree_map(lambda x: x[0], shard)
        pids = b.columns[0].data
        if slot is None:
            out, ok = exchange_by_pid(b, pids, N_DEV, "data"), jnp.bool_(True)
        else:
            out, ok = exchange_by_pid(b, pids, N_DEV, "data", slot=slot,
                                      on_overflow="guard")
        return jax.tree_util.tree_map(lambda x: x[None], out), ok[None]
    return jax.jit(shard_map(step, mesh=the_mesh(), in_specs=P("data"),
                             out_specs=(P("data"), P("data")),
                             check_vma=False))


def _bits(x):
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}") if x.dtype.kind == "f" else x


@pytest.mark.parametrize("case", ["uniform", "all_to_one_chip",
                                  "a_chip_receives_nothing",
                                  "an_empty_shard", "guard_fits",
                                  "guard_overflows"])
@pytest.mark.parametrize("nulls", [False, True], ids=["no_nulls", "nulls"])
@pytest.mark.parametrize("kind", ["int64", "float64", "int32", "bool",
                                  "date", "struct"])
def test_exchange_equals_numpy_routing(kind, nulls, case):
    """Column by column and bit for bit: each chip holds its rows in
    source-chip order then source order, zero and invalid behind them,
    whatever the lane type, the nulls, the destinations and the slot."""
    from spark_rapids_tpu import types as t
    from spark_rapids_tpu.columnar.device import DeviceBatch, DeviceColumn
    rng = np.random.default_rng(
        [sum(map(ord, kind)), int(nulls), sum(map(ord, case))])
    pids, rows, slot = _x_destinations(case, rng)
    payload = _x_payload(kind, nulls, rng)
    stacked = DeviceBatch(
        [DeviceColumn(t.INT, data=pids), payload], rows, ["pid", "x"])
    stacked = jax.tree_util.tree_map(jnp.asarray, stacked)
    out, oks = _x_program(slot)(stacked)
    slot = slot or X_CAP

    sent = np.stack([np.bincount(pids[s, :rows[s]], minlength=N_DEV)
                     for s in range(N_DEV)])
    assert [bool(x) for x in np.asarray(oks)] == \
        [bool((sent[s] <= slot).all()) for s in range(N_DEV)]
    got_cols = [np.asarray(x) for c in out.columns for x in _x_lanes(c)]
    want_lanes = [pids] + _x_lanes(payload)
    # the pid column came without a validity lane and leaves with one
    want_lanes.insert(0, np.ones((N_DEV, X_CAP), dtype=bool))
    assert len(got_cols) == len(want_lanes)
    for dest in range(N_DEV):
        n_here = np.minimum(sent[:, dest], slot).sum()
        assert int(np.asarray(out.num_rows)[dest]) == n_here
        for got, lane in zip(got_cols, want_lanes):
            want, n = _x_routed(lane, pids, rows, slot, dest)
            assert n == n_here
            assert got[dest].dtype == want.dtype
            np.testing.assert_array_equal(_bits(got[dest]), _bits(want))


def test_allgather_broadcast():
    table = pa.table({"b": pa.array(np.arange(64, dtype=np.int64))})
    mesh = the_mesh()
    stacked = stack_shards(shard_tables(table))

    def step(shard):
        b = jax.tree_util.tree_map(lambda x: x[0], shard)
        out = allgather_batch(b, "data", N_DEV)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"), check_vma=False))
    outs = [batch_to_arrow(b) for b in unstack_shards(fn(stacked))]
    for rb in outs:
        assert sorted(rb.column("b").to_pylist()) == list(range(64))


def test_distributed_aggregate_matches_single_host():
    n = 4000
    rng = np.random.default_rng(2)
    table = pa.table({
        "k": pa.array(rng.integers(0, 97, n).astype(np.int64)),
        "v": pa.array(rng.integers(-1000, 1000, n).astype(np.int64)),
        "f": pa.array(rng.random(n)),
    })
    dagg = DistributedAggregate(
        grouping=[A("k")],
        aggregates=[AggregateExpression(Sum(A("v")), "sv"),
                    AggregateExpression(Average(A("f")), "af"),
                    AggregateExpression(Count(None), "c")],
        in_names=["k", "v", "f"],
        in_types=_types(table),
        mesh=the_mesh())
    got = dagg.run(shard_tables(table)).sort_by("k")

    import pyarrow.compute as pc
    gb = pa.TableGroupBy(table, ["k"], use_threads=False).aggregate(
        [("v", "sum"), ("f", "mean"), ("k", "count")])
    want = gb.sort_by("k")
    assert got.column("k").to_pylist() == want.column("k").to_pylist()
    assert got.column("sv").to_pylist() == want.column("v_sum").to_pylist()
    np.testing.assert_allclose(np.array(got.column("af")),
                               np.array(want.column("f_mean")), rtol=1e-9)
    assert got.column("c").to_pylist() == want.column("k_count").to_pylist()


def test_distributed_global_aggregate():
    n = 1000
    table = pa.table({"v": pa.array(np.arange(n, dtype=np.int64))})
    dagg = DistributedAggregate(
        grouping=[], aggregates=[AggregateExpression(Sum(A("v")), "sv"),
                                 AggregateExpression(Count(None), "c")],
        in_names=["v"], in_types=_types(table), mesh=the_mesh())
    got = dagg.run(shard_tables(table))
    assert got.num_rows == 1
    assert got.column("sv").to_pylist() == [n * (n - 1) // 2]
    assert got.column("c").to_pylist() == [n]


def test_distributed_exchange_partitions_by_key():
    n = 512
    rng = np.random.default_rng(3)
    table = pa.table({
        "k": pa.array(rng.integers(0, 50, n).astype(np.int64)),
        "v": pa.array(rng.random(n)),
    })
    dx = DistributedExchange([A("k")], ["k", "v"], _types(table),
                             mesh=the_mesh())
    outs = dx.run(shard_tables(table))
    # same key never appears on two devices
    seen = {}
    total = 0
    for d, tb in enumerate(outs):
        total += tb.num_rows
        for k in set(tb.column("k").to_pylist()):
            assert seen.setdefault(k, d) == d
    assert total == n


def _types(table):
    from spark_rapids_tpu.columnar.interop import from_arrow_type
    return [from_arrow_type(f.type) for f in table.schema]


def test_distributed_sort_balances_shards():
    """Routing uses the VALUE key word (nulls pinned to the boundary), so
    uniform data spreads across shards instead of all landing on one
    device (code-review round-3 finding: routing on the null-indicator
    word sent every non-null row to the last shard)."""
    import jax
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.columnar.interop import from_arrow_type
    from spark_rapids_tpu.expr.core import AttributeReference as A
    from spark_rapids_tpu.parallel.distributed import (DistributedSort,
                                                       stack_shards,
                                                       unstack_shards)
    from spark_rapids_tpu.parallel.mesh import build_mesh

    n_dev = N_DEV
    rng = np.random.default_rng(9)
    n = 4096
    vals = rng.integers(-10**6, 10**6, n).astype(np.int64)
    tb = pa.table({"v": pa.array(vals)})
    per = n // n_dev
    shards = [tb.slice(i * per, per) for i in range(n_dev)]
    ds = DistributedSort([(A("v"), True, True)], ["v"],
                         [from_arrow_type(tb.schema[0].type)],
                         mesh=build_mesh(n_dev))
    out = ds._compiled(stack_shards(shards))
    per_shard = [int(np.asarray(b.num_rows)) for b in unstack_shards(out)]
    assert sum(per_shard) == n
    nonempty = sum(1 for c in per_shard if c > 0)
    assert nonempty >= n_dev // 2, per_shard     # spread, not one hot shard
    assert max(per_shard) < n // 2, per_shard    # no shard holds half
    # and the concatenation is still the total order
    allv = []
    for b in unstack_shards(out):
        m = int(np.asarray(b.num_rows))
        allv += list(np.asarray(b.columns[0].data)[:m])
    assert allv == sorted(vals.tolist())


def test_exchange_carries_structs():
    """Struct-of-flat columns ride the ICI exchange: row-aligned children
    move under the same permutation (round-5 widening; arrays/maps still
    stage via host)."""
    n = 240
    rng = np.random.default_rng(9)
    ks = rng.integers(0, 24, n)
    table = pa.table({
        "k": pa.array(ks.astype(np.int64)),
        "st": pa.array(
            [None if i % 11 == 0 else
             {"a": int(i), "b": None if i % 6 == 0 else float(i) / 3}
             for i in range(n)],
            type=pa.struct([("a", pa.int64()), ("b", pa.float64())])),
    })
    from spark_rapids_tpu.parallel.alltoall import exchange_supported
    from spark_rapids_tpu.columnar.interop import from_arrow_type
    assert exchange_supported(
        [from_arrow_type(f.type) for f in table.schema]) is None
    outs = run_exchange(table, lambda b: b.columns[0].data % N_DEV)
    for d, rb in enumerate(outs):
        assert (rb.column("k").to_numpy() % N_DEV == d).all()
    got = pa.concat_tables([pa.Table.from_batches([rb]) for rb in outs])
    key = lambda r: (r[0], repr(r[1]))  # noqa: E731
    got_rows = sorted(zip(got.column("k").to_pylist(),
                          got.column("st").to_pylist()), key=key)
    want_rows = sorted(zip(table.column("k").to_pylist(),
                           table.column("st").to_pylist()), key=key)
    assert got_rows == want_rows


def test_exchange_carries_arrays_and_maps():
    """Array/map columns of fixed-width elements ride the ICI exchange:
    child lanes move through the generalized span layout (round-5;
    string/struct elements still stage via host)."""
    n = 200
    rng = np.random.default_rng(15)
    ks = rng.integers(0, 16, n)
    arrs = [None if i % 13 == 0 else
            [int(x) if x % 4 else None
             for x in range(i % 5)]        # empty lists + null elements
            for i in range(n)]
    maps = [None if i % 9 == 0 else
            {int(j): float(i + j) / 7 for j in range(i % 3)}
            for i in range(n)]
    table = pa.table({
        "k": pa.array(ks.astype(np.int64)),
        "a": pa.array(arrs, type=pa.list_(pa.int64())),
        "m": pa.array(maps, type=pa.map_(pa.int64(), pa.float64())),
    })
    from spark_rapids_tpu.parallel.alltoall import exchange_supported
    from spark_rapids_tpu.columnar.interop import from_arrow_type
    assert exchange_supported(
        [from_arrow_type(f.type) for f in table.schema]) is None
    outs = run_exchange(table, lambda b: b.columns[0].data % N_DEV)
    for d, rb in enumerate(outs):
        assert (rb.column("k").to_numpy() % N_DEV == d).all()
    got = pa.concat_tables([pa.Table.from_batches([rb]) for rb in outs])
    key = lambda r: (r[0], repr(r[1]), repr(r[2]))  # noqa: E731
    got_rows = sorted(zip(got.column("k").to_pylist(),
                          got.column("a").to_pylist(),
                          got.column("m").to_pylist()), key=key)
    want_rows = sorted(zip(table.column("k").to_pylist(),
                           table.column("a").to_pylist(),
                           table.column("m").to_pylist()), key=key)
    assert got_rows == want_rows


def test_stack_shards_puts_each_shard_on_its_own_device():
    """On real chips nothing may start on chip 0: shard i is uploaded
    to mesh device i, and unstacking hands back each device's own
    buffer (an index into the sharded array would run one SPMD gather,
    collectives included, per shard)."""
    n = 40 * N_DEV
    table = pa.table({"k": pa.array(np.arange(n, dtype=np.int64)),
                      "s": pa.array([f"s{i % 7}" * (1 + i % 3)
                                     for i in range(n)])})
    mesh = the_mesh()
    stacked = stack_shards(shard_tables(table), mesh=mesh)
    devices = list(mesh.devices.flat)
    for leaf in jax.tree_util.tree_leaves(stacked):
        assert leaf.shape[0] == N_DEV
        assert [s.device for s in sorted(
            leaf.addressable_shards,
            key=lambda s: s.index[0].start)] == devices
    shards = unstack_shards(stacked)
    for i, b in enumerate(shards):
        assert {d for leaf in jax.tree_util.tree_leaves(b)
                for d in leaf.devices()} == {devices[i]}
        assert batch_to_arrow(b).column("k").to_pylist() == \
            list(range(40 * i, 40 * (i + 1)))
    home = unstack_shards(stacked, device=devices[0])
    assert {d for b in home for leaf in jax.tree_util.tree_leaves(b)
            for d in leaf.devices()} == {devices[0]}
    with pytest.raises(ValueError, match="shards for a"):
        stack_shards(shard_tables(table)[:2], mesh=mesh)
