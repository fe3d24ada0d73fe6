"""Fixed-width strings (`DeviceColumn.fixed_width`): a string column whose
every value has one byte width of 1..4 and no null is ONE row-aligned word
lane.  It must answer exactly as the general layout of offsets and bytes
does, through the upload, a filter's compaction, a grouped aggregate (as a
key and as a value), a sort either way and the fetch; a column with a
null, an empty string or mixed widths keeps the general layout; and a
grouped aggregate whose key types bound the group count hands up the row
bucket of that bound, not its input's capacity."""

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as t
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col, lit
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar import device as dev
from spark_rapids_tpu.columnar.device import (DeviceBatch, DeviceColumn,
                                              batch_to_arrow,
                                              batch_to_device)
from spark_rapids_tpu.columnar.fetch import fetch_batch
from spark_rapids_tpu.exec import aggregate as agg
from spark_rapids_tpu.obs.compileprof import CompileObservatory
from spark_rapids_tpu.ops import carry
from spark_rapids_tpu.ops import segmented as seg
from spark_rapids_tpu.testing.asserts import assert_tables_equal

N = 3000
WIDTHS = [1, 2, 3, 4]
_ALPHABET = "ANRFO"


def _strings(width: int, n: int = N, seed: int = 3, kinds: int = 7):
    """`n` ASCII strings of `width` bytes over a few distinct values."""
    rng = np.random.default_rng(seed + width)
    pool = ["".join(_ALPHABET[(k * 3 + j * (k + 1)) % 5]
                    for j in range(width)) for k in range(kinds)]
    pool = sorted(set(pool))
    return [pool[i] for i in rng.integers(0, len(pool), n)]


def _table(width: int, n: int = N, seed: int = 3) -> pa.Table:
    rng = np.random.default_rng(seed)
    return pa.table({
        "s": pa.array(_strings(width, n, seed), pa.string()),
        "g": pa.array(_strings(width, n, seed + 50, kinds=4), pa.string()),
        "x": pa.array(rng.integers(-50, 50, n).astype(np.int64)),
        "f": pa.array(np.round(rng.standard_normal(n) * 100, 2)),
    })


def _session(enabled=True):
    return TpuSession.builder().config(
        "spark.rapids.sql.enabled", enabled).get_or_create()


_LAST = {}       # the session of the newest `_collect`, for its plan


def _collect(query, table, monkeypatch, fixed=True, enabled=True):
    """`query(df)` collected, the upload taking fixed-width strings or (with
    `fixed=False`) holding every string in the general layout."""
    with monkeypatch.context() as m:
        if not fixed:
            m.setattr(dev, "_uniform_width", lambda offs, n: None)
        session = _session(enabled)
        _LAST["session"] = session
        df = session.create_dataframe(table, num_partitions=1)
        return query(df).collect()


# -- the upload ---------------------------------------------------------------

@pytest.mark.parametrize("arrow_type", [pa.string(), pa.large_string(),
                                        pa.binary()])
@pytest.mark.parametrize("width", WIDTHS)
def test_upload_holds_a_uniform_width_as_one_word_lane(width, arrow_type):
    values = _strings(width, 300)
    if arrow_type == pa.binary():
        values = [v.encode() for v in values]
    rb = pa.record_batch({"s": pa.array(values, arrow_type)})
    for xp in (np, jnp):
        c = batch_to_device(rb, xp=xp).columns[0]
        assert c.fixed_width == width and not c.has_offsets
        assert c.capacity == 1024
        assert np.dtype(c.word.dtype) == dev.fixed_word_dtype(width)
        # big-endian: word order is byte order
        raw = [v if isinstance(v, bytes) else v.encode() for v in values]
        want = [int.from_bytes(v, "big") for v in raw]
        assert np.asarray(c.word)[:300].tolist() == want
        assert not np.asarray(c.word)[300:].any()
        # what code that knows only the general layout reads
        general = batch_to_device(rb, xp=xp,
                                  fixed_width_strings=False).columns[0]
        assert general.fixed_width is None and general.has_offsets
        assert (np.asarray(c.offsets)[:301]
                == np.asarray(general.offsets)[:301]).all()
        assert (np.asarray(c.data)[:300 * width]
                == np.asarray(general.data)[:300 * width]).all()
        assert c.offsets.shape == (1025,)
        assert c.data.shape == (1024 * width,)
        # and back
        back = batch_to_arrow(DeviceBatch([c], 300, ["s"])).column(0)
        assert back.to_pylist() == values


@pytest.mark.parametrize("case,values", [
    ("a null", ["A", None, "R"]),
    ("an empty string", ["A", "", "R"]),
    ("mixed widths", ["A", "NO", "R"]),
    ("all empty", ["", "", ""]),
    ("five bytes", ["ABCDE", "FGHIJ", "KLMNO"]),
    ("two code points of one and two bytes", ["A", "é", "R"]),
    ("no rows", []),
])
def test_other_columns_keep_the_general_layout(case, values):
    rb = pa.record_batch({"s": pa.array(values, pa.string())})
    for xp in (np, jnp):
        c = batch_to_device(rb, xp=xp).columns[0]
        assert c.fixed_width is None and c.word is None, case
        assert c.has_offsets
        assert batch_to_arrow(DeviceBatch([c], len(values), ["s"])) \
            .column(0).to_pylist() == values


def test_a_string_inside_an_array_or_a_struct_keeps_the_general_layout():
    rb = pa.record_batch({
        "a": pa.array([["A", "B"], ["C"]], pa.list_(pa.string())),
        "st": pa.array([{"k": "A"}, {"k": "B"}],
                       pa.struct([("k", pa.string())]))})
    b = batch_to_device(rb, xp=np)
    assert b.columns[0].children[0].fixed_width is None
    assert b.columns[1].children[0].fixed_width is None


def test_the_upload_span_counts_its_fixed_width_columns():
    from spark_rapids_tpu.obs import tracer
    trace = tracer.install(tracer.QueryTrace())
    try:
        batch_to_device(pa.record_batch({
            "a": pa.array(["A", "B"]), "b": pa.array(["x", "yy"]),
            "c": pa.array([1, 2])}))
    finally:
        tracer.uninstall()
    span, = [s for s in trace.span_dicts() if s["name"] == "scan.upload"]
    assert span["attrs"]["fixed_width_string_cols"] == 1
    assert span["attrs"]["rows"] == 2


# -- the pytree, the moves, the key word ------------------------------------

@pytest.mark.parametrize("width", WIDTHS)
def test_the_width_rides_the_pytree_and_the_rows_move_by_sort_pass(width):
    rb = pa.record_batch({"s": pa.array(_strings(width), pa.string())})
    fixed = batch_to_device(rb).columns[0]
    general = batch_to_device(rb, fixed_width_strings=False).columns[0]
    leaves, treedef = jax.tree_util.tree_flatten(fixed)
    assert len(leaves) == 2                       # the word, the validity
    again = jax.tree_util.tree_unflatten(treedef, leaves)
    assert again.fixed_width == width and again.dtype == t.STRING
    # a general column's treedef is what it was before fixed widths
    assert jax.tree_util.tree_structure(general) == \
        jax.tree_util.tree_structure(DeviceColumn(
            t.STRING, data=general.data, validity=general.validity,
            offsets=general.offsets))
    assert carry.carriable(fixed) and not carry.carriable(general)
    cap = fixed.capacity
    key = jnp.asarray(np.random.default_rng(1).integers(0, 9, cap)
                      .astype(np.int32))
    before = carry.lane_move_counts()
    _, (moved,), _ = jax.jit(
        lambda k, c: carry.sort_rows(jnp, [k], [c], cap))(key, fixed)
    mid = carry.lane_move_counts()
    _, (gathered,), _ = jax.jit(
        lambda k, c: carry.sort_rows(jnp, [k], [c], cap))(key, general)
    after = carry.lane_move_counts()
    assert moved.fixed_width == width and gathered.fixed_width is None
    assert mid["string_cols_row_aligned"] \
        - before["string_cols_row_aligned"] == 1
    assert mid["lane_moves_gathered"] == before["lane_moves_gathered"]
    assert after["string_cols_gathered"] - mid["string_cols_gathered"] == 1
    assert after["lane_moves_gathered"] - mid["lane_moves_gathered"] == 3
    n = rb.num_rows
    order = np.argsort(np.asarray(key), kind="stable")
    live = order[order < n]
    want = [rb.column(0)[int(i)].as_py() for i in live]
    for c in (moved, gathered):
        got = batch_to_arrow(DeviceBatch(
            [jax.tree_util.tree_map(np.asarray, c)], cap, ["s"])).column(0)
        valid = np.asarray(c.validity)
        assert [v for v, ok in zip(got.to_pylist(), valid) if ok] == want
    # the numpy engine's move is the same move
    _, (np_moved,), _ = carry.sort_rows(
        np, [np.asarray(key)],
        [jax.tree_util.tree_map(np.asarray, fixed)], cap)
    assert (np.asarray(np_moved.word) == np.asarray(moved.word)).all()


@pytest.mark.parametrize("kinds", [
    ("uint8", "uint8"), ("uint8", "bool", "uint16"), ("int8", "int16"),
    ("uint8",) * 5, ("bool",) * 30 + ("uint8", "uint16", "uint8")])
def test_narrow_lanes_share_a_word_and_equal_the_gather(kinds):
    n = 2500
    rng = np.random.default_rng(len(kinds))
    order = rng.permutation(n).astype(np.int32)
    rank = np.empty_like(order)
    rank[order] = np.arange(n, dtype=np.int32)
    lanes = []
    for kind in kinds:
        if kind == "bool":
            lanes.append(rng.integers(0, 2, n).astype(bool))
        else:
            info = np.iinfo(kind)
            x = rng.integers(info.min, info.max, n, dtype=kind,
                             endpoint=True)
            x[:2] = [info.min, info.max]
            lanes.append(x)
    bits = sum(1 if k == "bool" else 8 * np.dtype(k).itemsize
               for k in kinds)
    before = carry.lane_move_counts()
    got = jax.jit(lambda r, ls: carry.move_lanes(jnp, r, ls))(
        jnp.asarray(rank), [jnp.asarray(x) for x in lanes])
    after = carry.lane_move_counts()
    assert after["lane_moves_sorted"] - before["lane_moves_sorted"] \
        == len(kinds)
    passes = after["sort_passes"] - before["sort_passes"]
    assert -(-bits // 32) <= passes <= -(-bits // 32) + 1
    for x, g in zip(lanes, got):
        assert np.asarray(g).dtype == x.dtype
        assert (np.asarray(g) == x[order]).all()


@pytest.mark.parametrize("ascending", [True, False])
@pytest.mark.parametrize("for_grouping", [True, False])
@pytest.mark.parametrize("width", WIDTHS)
def test_the_key_is_one_exact_word_that_orders_as_the_bytes_do(
        width, for_grouping, ascending):
    values = sorted(set(_strings(width, 400, kinds=9)))
    rb = pa.record_batch({"s": pa.array(values, pa.string())})
    fixed = batch_to_device(rb, xp=np).columns[0]
    general = batch_to_device(rb, xp=np,
                              fixed_width_strings=False).columns[0]
    live = np.arange(fixed.capacity) < len(values)
    words = seg.key_words_for_column(np, fixed, live, for_grouping,
                                     ascending=ascending)
    assert len(words) == 2 and words[0].dtype == np.bool_
    assert words[1].dtype == dev.fixed_word_dtype(width)
    n = len(values)
    w = words[1][:n].astype(np.int64)
    assert (np.diff(w) > 0).all() if ascending else (np.diff(w) < 0).all()
    # the general layout's ordering words agree on the order
    gwords = seg.key_words_for_column(np, general, live, False,
                                      ascending=ascending)
    assert (np.lexsort(tuple(reversed([x[:n] for x in gwords])))
            == np.lexsort((w,))).all()


# -- through the operators, against the general layout ------------------------

QUERIES = {
    "filter": lambda df: df.filter(col("x") > lit(0)),
    "filter_on_the_string": lambda df: df.filter(
        col("s") == lit("zz")).select(col("s"), col("x")),
    "group_key": lambda df: df.group_by(col("s")).agg(
        F.sum(col("x")).alias("sx"), F.count("*").alias("n")),
    "two_group_keys": lambda df: df.group_by(col("g"), col("s")).agg(
        F.sum(col("f")).alias("sf"), F.avg(col("x")).alias("ax")),
    "first_and_last_value": lambda df: df.group_by(col("x")).agg(
        F.first(col("s")).alias("fs"), F.last(col("s")).alias("ls")),
    "min_and_max_value": lambda df: df.group_by(col("g")).agg(
        F.min(col("s")).alias("mn"), F.max(col("s")).alias("mx")),
    "ungrouped_min_first": lambda df: df.agg(
        F.min(col("s")).alias("mn"), F.max(col("s")).alias("mx"),
        F.first(col("s")).alias("fs")),
    "sort_ascending": lambda df: df.order_by(col("s"), col("x"), col("f")),
    "sort_descending": lambda df: df.order_by(
        col("s"), col("x"), col("f"), ascending=False),
    "filter_aggregate_sort": lambda df: df.filter(col("x") < lit(30))
    .group_by(col("g"), col("s")).agg(F.sum(col("f")).alias("sf"),
                                      F.count("*").alias("n"))
    .order_by(col("g"), col("s")),
    "length_and_upper": lambda df: df.select(
        F.length(col("s")).alias("n"), F.lower(col("s")).alias("l"),
        F.concat(col("s"), col("g")).alias("c")),
}
_ORDERED = {"sort_ascending", "sort_descending", "filter_aggregate_sort",
            "filter", "length_and_upper", "filter_on_the_string"}
#: a float sum's additions meet in a tree whose shape follows the
#: group's place in the sorted batch, and the general layout orders
#: groups by hash, the fixed one by value: equal to rounding, not to the bit
_FLOAT_SUMS = {"two_group_keys", "filter_aggregate_sort"}


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("name", sorted(QUERIES))
def test_fixed_width_answers_as_the_general_layout_does(name, width,
                                                        monkeypatch):
    table = _table(width)
    query = QUERIES[name]
    if name == "filter_on_the_string":
        some = table.column("s")[5].as_py()
        query = lambda df: df.filter(col("s") == lit(some)).select(  # noqa
            col("s"), col("x"))
    fixed = _collect(query, table, monkeypatch, fixed=True)
    general = _collect(query, table, monkeypatch, fixed=False)
    # bit for bit: the same rows, and where the query orders them, in
    # the same order
    assert_tables_equal(general, fixed, ignore_order=name not in _ORDERED,
                        approximate_float=1e-12
                        if name in _FLOAT_SUMS else 0.0)
    if name in _ORDERED - _FLOAT_SUMS:
        assert fixed.equals(general)
    cpu = _collect(query, table, monkeypatch, enabled=False)
    assert_tables_equal(cpu, fixed, ignore_order=name not in _ORDERED,
                        approximate_float=1e-12)
    assert fixed.num_rows > 0


@pytest.mark.parametrize("case", ["a null", "an empty string",
                                  "mixed widths"])
@pytest.mark.parametrize("name", ["group_key", "min_and_max_value",
                                  "sort_descending"])
def test_a_column_that_falls_back_answers_the_same(name, case, monkeypatch):
    table = _table(2, n=500)
    s = table.column("s").to_pylist()
    s[7] = {"a null": None, "an empty string": "",
            "mixed widths": "NNN"}[case]
    table = table.set_column(0, "s", pa.array(s, pa.string()))
    rb = table.to_batches()[0]
    assert batch_to_device(rb, xp=np).columns[0].fixed_width is None
    assert batch_to_device(rb, xp=np).columns[1].fixed_width == 2
    got = _collect(QUERIES[name], table, monkeypatch)
    cpu = _collect(QUERIES[name], table, monkeypatch, enabled=False)
    assert_tables_equal(cpu, got, ignore_order=name not in _ORDERED)


@pytest.mark.parametrize("width", WIDTHS)
def test_the_fetch_sends_the_word_and_no_offsets(width):
    rb = pa.record_batch({"s": pa.array(_strings(width, 700), pa.string()),
                          "x": pa.array(np.arange(700))})
    fixed = batch_to_device(rb)
    out = fetch_batch(fixed)
    assert out.columns[0].fixed_width == width
    assert isinstance(out.columns[0].word, np.ndarray)
    assert out.capacity == 1024
    assert batch_to_arrow(out).equals(rb.cast(pa.schema(
        [("s", pa.large_string()), ("x", pa.int64())])))
    general = fetch_batch(batch_to_device(rb, fixed_width_strings=False))
    assert batch_to_arrow(general).equals(batch_to_arrow(out))


# -- the static bound on a grouped aggregate's output ------------------------

def test_group_bound_reads_the_key_types():
    u8 = DeviceColumn.fixed_string(t.STRING, np.zeros(8, np.uint8),
                                   np.ones(8, bool), 1)
    u16 = DeviceColumn.fixed_string(t.STRING, np.zeros(8, np.uint16),
                                    np.ones(8, bool), 2)
    flag = DeviceColumn(t.BOOLEAN, data=np.zeros(8, bool),
                        validity=np.ones(8, bool))
    byte = DeviceColumn(t.BYTE, data=np.zeros(8, np.int8),
                        validity=np.ones(8, bool))
    long_ = DeviceColumn(t.LONG, data=np.zeros(8, np.int64),
                         validity=np.ones(8, bool))
    assert agg._group_bound([u8, u8]) == 257 * 257
    assert agg._group_bound([flag, byte]) == 3 * 257
    assert agg._group_bound([u16]) == 65537
    assert agg._group_bound([u8, long_]) is None
    assert agg._group_bound([long_]) is None
    cap = 33_554_432
    assert agg._group_capacity([u8, u8], cap) == 262_144
    assert agg._group_capacity([flag, byte], cap) == 1024
    assert agg._group_capacity([u8, u8], 65_536) == 65_536
    assert agg._group_capacity([u8, u8], 262_144) == 262_144
    assert agg._group_capacity([long_], cap) == cap
    assert agg._group_capacity([u8, long_], cap) == cap


def _plan_aggregate():
    found = []
    _LAST["session"].last_plan.foreach(
        lambda e: found.append(e)
        if type(e).__name__ == "TpuHashAggregateExec" else None)
    return found[0]


def test_as_many_groups_as_the_bound_with_null_keys(monkeypatch):
    """A nullable boolean and a nullable byte form at most 3 x 257 groups:
    all 771 of them come back, out of the 1,024-row bucket and not the
    input's 8,192."""
    flags = [None, False, True]
    bytes_ = [None] + list(range(-128, 128))
    rows = [(f, b) for f in flags for b in bytes_] * 7
    rng = np.random.default_rng(2)
    rng.shuffle(rows)
    table = pa.table({
        "f": pa.array([r[0] for r in rows], pa.bool_()),
        "b": pa.array([r[1] for r in rows], pa.int8()),
        "v": pa.array(rng.integers(0, 100, len(rows)).astype(np.int64))})
    assert 4096 < table.num_rows <= 8192
    query = lambda df: df.group_by(col("f"), col("b")).agg(  # noqa: E731
        F.sum(col("v")).alias("sv"), F.count("*").alias("n"))
    from spark_rapids_tpu.obs import metrics as m
    counter = m.registry().counter("tpu_aggregate_output_rebucket_total")
    before = counter.value()
    got = _collect(query, table, monkeypatch)
    node = _plan_aggregate()
    assert counter.value() == before + 1
    assert got.num_rows == 771 and set(got.column("n").to_pylist()) == {7}
    cpu = _collect(query, table, monkeypatch, enabled=False)
    assert_tables_equal(cpu, got)
    cap = 8192
    b = batch_to_device(table.to_batches()[0])
    assert b.capacity == cap
    out = node._jit_complete(b)
    assert out.capacity == 1024 and int(out.num_rows) == 771
    assert all(leaf.shape[0] == 1024
               for leaf in jax.tree_util.tree_leaves(out.columns))


@pytest.mark.parametrize("width", [1, 2])
def test_a_string_key_cuts_the_output_and_an_int64_key_does_not(
        width, monkeypatch):
    table = _table(width, n=5000)
    query = lambda df: df.group_by(col("g"), col("s")).agg(  # noqa: E731
        F.sum(col("x")).alias("sx"), F.min(col("f")).alias("mf"),
        F.first(col("x")).alias("fx"))
    got = _collect(query, table, monkeypatch)
    node = _plan_aggregate()
    general = _collect(query, table, monkeypatch, fixed=False)
    assert_tables_equal(general, got)
    b = batch_to_device(table.to_batches()[0])
    assert b.capacity == 8192
    out = node._jit_complete(b)
    bound = (256 ** width + 1) ** 2
    assert out.capacity == (8192 if bound >= 8192 else 1024)
    out_general = node._jit_complete(batch_to_device(
        table.to_batches()[0], fixed_width_strings=False))
    assert out_general.capacity == 8192
    assert int(out.num_rows) == int(out_general.num_rows)


def test_an_int64_keyed_aggregate_builds_the_program_it_built_before(
        monkeypatch):
    """`.q18sub`'s aggregate: 21 sort passes, its output at the input's
    capacity, no string column moved either way (PERF.md section 5)."""
    rng = np.random.default_rng(4)
    table = pa.table({
        "k": pa.array(np.sort(rng.integers(0, 900, 4000)).astype(np.int64)),
        "q": pa.array(rng.integers(1, 51, 4000).astype(np.float64))})
    n_before = len(CompileObservatory.get().snapshot()["programs"])
    _collect(lambda df: df.group_by(col("k")).agg(
        F.sum(col("q")).alias("s")).filter(col("s") > lit(250.0))
        .select(col("k")), table, monkeypatch)
    node = _plan_aggregate()
    out = node._jit_complete(batch_to_device(table.to_batches()[0]))
    assert out.capacity == 8192
    programs = CompileObservatory.get().snapshot()["programs"][n_before:]
    mine = [p for p in programs if p["exec"] == "TpuHashAggregateExec"]
    if mine:        # built by this test and not by an earlier one
        assert mine[0]["sort_passes"] == 21
        assert mine[0]["string_cols_row_aligned"] == 0
        assert mine[0]["string_cols_gathered"] == 0
        assert mine[0]["lane_moves_gathered"] == 0


def test_build_records_count_string_columns_either_way(monkeypatch):
    snap = CompileObservatory.get().snapshot
    n_before = len(snap()["programs"])
    table = _table(3, n=600, seed=91)
    query = lambda df: df.filter(col("f") > lit(-1e9)).order_by(  # noqa
        col("s"), col("f"))
    _collect(query, table, monkeypatch, fixed=True)
    fixed_programs = snap()["programs"][n_before:]
    _collect(query, table, monkeypatch, fixed=False)
    general_programs = snap()["programs"][n_before + len(fixed_programs):]
    for kind in ("FilterExec", "SortExec"):
        f = [p for p in fixed_programs if p["exec"] == kind]
        g = [p for p in general_programs if p["exec"] == kind]
        if f:
            assert f[0]["string_cols_row_aligned"] == 2
            assert f[0]["string_cols_gathered"] == 0
            assert f[0]["lane_moves_gathered"] == 0
        if g:
            assert g[0]["string_cols_row_aligned"] == 0
            assert g[0]["string_cols_gathered"] == 2
            assert g[0]["lane_moves_gathered"] == 6
