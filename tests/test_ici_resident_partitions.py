"""A table that a mesh stage reads is kept one partition a mesh device,
and the stage takes its shards where they lie: no concatenation, no
reshard program, no table lane moved between chips (four virtual
devices; counts and answers only)."""

import jax
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col, lit
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.obs import metrics
from spark_rapids_tpu.obs.compileprof import CompileObservatory

# every test here runs collectives across the mesh's device threads
pytestmark = pytest.mark.time_limit(300)

N_DEV = 4
THRESHOLD = 150.0


def _session(transport="ici", **extra):
    b = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.shuffle.transport", transport))
    for k, v in extra.items():
        b = b.config(k, v)
    return b.get_or_create()


def _lineitem(n_rows, seed, string_key=False):
    """A key-clustered table, one to seven lines an order, so that equal
    row ranges cut orders in two."""
    rng = np.random.default_rng(seed)
    lines = rng.integers(1, 8, n_rows)
    keys = np.repeat(np.arange(len(lines), dtype=np.int64) * 4 + 1,
                     lines)[:n_rows]
    quantity = rng.integers(1, 51, n_rows).astype(np.float64)
    key_col = pa.array([f"order-{k:07d}" for k in keys]) if string_key \
        else pa.array(keys)
    return pa.table({"l_orderkey": key_col,
                     "l_quantity": pa.array(quantity)}), keys, quantity


def _q18sub(df):
    return (df.group_by(col("l_orderkey"))
            .agg(F.sum(col("l_quantity")).alias("sum_quantity"))
            .filter(col("sum_quantity") > lit(THRESHOLD))
            .select(col("l_orderkey")))


def _reference(keys, quantity):
    starts = np.flatnonzero(np.concatenate(([True], keys[1:] != keys[:-1])))
    sums = np.add.reduceat(quantity, starts)
    return keys[starts][sums > THRESHOLD]


def _execs(s, name):
    found = []
    s.last_plan.foreach(lambda e: found.append(e)
                        if type(e).__name__ == name else None)
    return found


def _programs(kind):
    return [p for p in CompileObservatory.get().snapshot()["programs"]
            if p["exec"] == kind]


def _counter(name):
    return sum(f.total() for f in metrics.registry().families()
               if f.name == name)


def _boundaries_cut_orders(keys, parts):
    per = -(-len(keys) // parts)
    return any(keys[i * per - 1] == keys[i * per]
               for i in range(1, parts) if i * per < len(keys))


@pytest.mark.parametrize("transport,devices", [("ici", N_DEV), ("tcp", 1)])
def test_scan_under_a_mesh_stage_pins_one_partition_a_device(transport,
                                                             devices):
    s = _session(transport)
    table, keys, quantity = _lineitem(6000, seed=11)
    out = _q18sub(s.create_dataframe(table, num_partitions=4)).collect()
    scan, = _execs(s, "LocalScanExec")
    assert scan.pinned_devices == devices
    assert bool(_execs(s, "IciAggregateExec")) == (transport == "ici")
    pinned = [scan.pin_cache[scan._pin_key(pid)] for pid in range(4)]
    on = [{d.id for leaf in jax.tree_util.tree_leaves(
        [b.columns for b in batches]) for d in leaf.devices()}
        for batches in pinned]
    if transport == "ici":
        assert on == [{d.id} for d in jax.devices()[:4]]
    else:
        assert all(x == on[0] for x in on)
    assert np.array_equal(np.sort(out.column("l_orderkey").to_numpy()),
                          _reference(keys, quantity))


def test_a_scan_no_mesh_stage_reads_stays_on_one_device():
    """A host exchange brings partitions together on one device: a table
    spread over the chips would not get through it."""
    s = _session()
    table, _, quantity = _lineitem(3000, seed=12)
    out = (s.create_dataframe(table, num_partitions=4)
           .agg(F.sum(col("l_quantity")).alias("total")).collect())
    scan, = _execs(s, "LocalScanExec")
    assert not scan.mesh_resident and scan.pinned_devices == 1
    assert out.column("total")[0].as_py() == quantity.sum()


@pytest.mark.parametrize("case,n_rows,parts,string_key,path", [
    ("four_partitions", 6000, 4, False, "resident"),
    ("eight_partitions_on_four_devices", 6000, 8, False, "stacked"),
    ("fewer_rows_than_devices", 3, 4, False, "resident"),
    ("string_key", 2400, 4, True, "resident"),
])
def test_q18sub_over_cut_orders_equals_the_reference(case, n_rows, parts,
                                                     string_key, path):
    s = _session(**{"spark.rapids.tpu.trace.enabled": True})
    table, keys, quantity = _lineitem(n_rows, seed=len(case),
                                      string_key=string_key)
    if n_rows > 100:
        assert _boundaries_cut_orders(keys, parts)
    reshards = len(_programs("ici_reshard"))
    df = s.create_dataframe(table, num_partitions=parts)
    moved = []
    real_put = jax.device_put

    def counting_put(x, device=None, *a, **k):
        moved.extend((id(leaf), device)
                     for leaf in jax.tree_util.tree_leaves(x)
                     if isinstance(leaf, jax.Array))
        return real_put(x, device, *a, **k)

    _q18sub(df).collect()        # uploads and pins
    jax.device_put = counting_put
    try:
        out = _q18sub(df).collect()
    finally:
        jax.device_put = real_put
    stage, = _execs(s, "IciAggregateExec")
    assert stage.stage_input_devices == N_DEV
    got = out.column("l_orderkey").to_numpy(zero_copy_only=False)
    want = _reference(keys, quantity)
    if string_key:
        want = np.array([f"order-{k:07d}" for k in want], dtype=object)
    assert sorted(got.tolist()) == sorted(want.tolist())
    span, = [sp for sp in s.last_query_trace().spans
             if sp.name == "ici.stage:aggregate"]
    assert span.attrs["path"] == path
    scan, = _execs(s, "LocalScanExec")
    lanes = {id(leaf) for pid in range(parts)
             for leaf in jax.tree_util.tree_leaves(
                 [b.columns for b in df._lp.device_cache[
                     scan._pin_key(pid)]])}
    if path == "resident":
        # no reshard program was built; the pinned query put no table lane
        # anywhere and spread nothing over the mesh: the stage's outputs,
        # brought to the first chip, are all that moved
        assert len(_programs("ici_reshard")) == reshards
        assert moved and not [leaf for leaf, _ in moved if leaf in lanes]
        assert {to for _, to in moved} == {jax.devices()[0]}
        assert _execs(s, "LocalScanExec")[0].pinned_devices == N_DEV
    else:
        assert len(_programs("ici_reshard")) >= 1


@pytest.mark.parametrize("string_key", [False, True],
                         ids=["flat_columns", "string_key"])
def test_step_build_record_counts_its_lane_moves(string_key):
    """The mesh step of a flat-column aggregate moves every lane by sort
    pass (the exchange and the merge's canonical order gather none), and
    puts on the wire what it did before PR 28; a key with offsets keeps
    its gathers, and the record counts them."""
    s = _session()
    table, _, _ = _lineitem(5000, seed=28, string_key=string_key)
    _q18sub(s.create_dataframe(table, num_partitions=4)).collect()
    steps = [p for p in _programs("DistributedAggregate")
             if p.get("ici_wire_bytes")]
    assert steps and all(p["lane_moves_sorted"] > 0 and p["sort_passes"] > 0
                         for p in steps)
    if string_key:
        assert any(p["lane_moves_gathered"] > 0 for p in steps)
        return
    # [4, 8192] slots a chip of the slot's valid flag and, for the key and
    # the partial sum, a data word and a validity byte; three of the four
    # slices leave each chip (PR 27's figure for these shapes)
    flat = [p for p in steps
            if p["ici_wire_bytes"] == N_DEV * 8192 * (1 + 9 + 9) * 3]
    assert flat and all(p["lane_moves_gathered"] == 0 for p in flat)


def test_stage_span_and_wire_bytes_counter():
    s = _session(**{"spark.rapids.tpu.trace.enabled": True})
    table, _, _ = _lineitem(5000, seed=5)
    df = s.create_dataframe(table, num_partitions=4)
    _q18sub(df).collect()
    # 5000 rows in four partitions pad to the 8192-row bucket; the
    # exchange sends, a chip, [4, 8192] slots of: the slot's valid flag,
    # and a data word (8 B) and a validity byte for the key and for the
    # partial sum; three of the four slices leave the chip
    step = {"ici_wire_bytes": N_DEV * 8192 * (1 + 9 + 9) * 3}
    assert step["ici_wire_bytes"] in {
        p.get("ici_wire_bytes") for p in _programs("DistributedAggregate")}
    assert not any(p.get("ici_wire_bytes") for p in _programs("FilterExec"))
    for _ in range(2):
        before = _counter("tpu_ici_wire_bytes_total")
        stages = _counter("tpu_ici_stage_total")
        _q18sub(df).collect()
        assert _counter("tpu_ici_wire_bytes_total") - before == \
            step["ici_wire_bytes"]
        assert _counter("tpu_ici_stage_total") - stages == 1
    span, = [sp for sp in s.last_query_trace().spans
             if sp.name == "ici.stage:aggregate"]
    assert span.attrs["op"] == "aggregate" and span.attrs["chips"] == N_DEV
    assert span.attrs["path"] == "resident"
    assert span.attrs["rows"] == 5000
    assert span.attrs["wire_bytes"] == step["ici_wire_bytes"]
    # the dispatch of the step lies inside the stage's span
    inner = [sp for sp in s.last_query_trace().spans
             if sp.name == "jit.dispatch:DistributedAggregate"]
    assert inner and all(sp.t0_ns >= span.t0_ns and sp.t1_ns <= span.t1_ns
                         for sp in inner)


@pytest.mark.parametrize("operator,name", [
    ("aggregate", "jit_IciAggregateExec"),
    ("sort", "jit_IciSortExec"),
    ("exchange", "jit_IciExchangeExec"),
])
def test_the_step_carries_its_operators_name(operator, name):
    from spark_rapids_tpu import types as t
    from spark_rapids_tpu.expr.aggregates import AggregateExpression, Sum
    from spark_rapids_tpu.expr.core import AttributeReference as A
    from spark_rapids_tpu.parallel import (DistributedAggregate,
                                           DistributedExchange, build_mesh,
                                           stack_shards)
    from spark_rapids_tpu.parallel.distributed import DistributedSort
    mesh = build_mesh(N_DEV)
    names, types = ["k", "v"], [t.LONG, t.DOUBLE]
    stage = {
        "aggregate": lambda: DistributedAggregate(
            [A("k")], [AggregateExpression(Sum(A("v")), "s")], names, types,
            mesh=mesh),
        "sort": lambda: DistributedSort([(A("v"), True, True)], names,
                                        types, mesh=mesh),
        "exchange": lambda: DistributedExchange([A("k")], names, types,
                                                mesh=mesh),
    }[operator]()
    shards = [pa.table({"k": pa.array(np.arange(8, dtype=np.int64) + i),
                        "v": pa.array(np.arange(8, dtype=np.float64))})
              for i in range(N_DEV)]
    stacked = stack_shards(shards, mesh=mesh)
    text = stage._compiled._jitted.lower(stacked).as_text()
    assert text.startswith(f"module @{name} ")


def test_the_fallback_reshard_is_named_by_the_stage_that_built_it():
    s = _session()
    table, _, _ = _lineitem(7000, seed=21)
    # a shape of its own, so that this test builds the reshard
    (s.create_dataframe(table.append_column(
        "pad", pa.array(np.zeros(7000, np.int8))), num_partitions=8)
     .group_by(col("l_orderkey")).agg(F.sum(col("l_quantity")).alias("s"))
     .collect())
    from spark_rapids_tpu.exec import base as eb
    reshards = [f for k, f in eb._JIT_CACHE.items() if "ici_reshard" in k]
    assert reshards
    assert all(f._fn.__name__.endswith(".reshard") and
               f._fn.__name__.startswith("Ici") for f in reshards)


def test_pinned_partitions_are_booked_against_their_own_chips():
    from spark_rapids_tpu.memory.device import DeviceManager
    from spark_rapids_tpu.memory.spill import ALL_CHIPS, SpillCatalog
    s = _session()
    table, _, _ = _lineitem(4000, seed=31)
    cat = SpillCatalog.get()
    before = {d.id: cat.pinned_bytes(d.id) for d in jax.devices()}
    _q18sub(s.create_dataframe(table, num_partitions=4)).collect()
    grown = {d.id: cat.pinned_bytes(d.id) - before[d.id]
             for d in jax.devices()}
    assert len(set(grown.values())) == 1 and grown[0] > 0
    assert cat.pinned_bytes(ALL_CHIPS) == cat.pinned_bytes()
    # one chip over its budget gives up its own partition, not the others'
    held = {d.id: cat.pinned_bytes(d.id) for d in jax.devices()}
    cat.chip_budgets = {1: 0}
    try:
        cat.maybe_spill()
    finally:
        cat.chip_budgets = {}
    assert cat.pinned_bytes(1) == 0
    assert all(cat.pinned_bytes(i) == held[i] for i in (0, 2, 3))
    dm = DeviceManager.get()
    assert set(dm.hbm_limits) == {d.id for d in jax.devices()}
    assert dm.hbm_limit == dm.hbm_limits[jax.devices()[0].id]
    assert set(dm.memory_in_use_by_device()) == set(dm.hbm_limits)
