"""ops/carry.py: a row permutation moves its lanes with sort passes of the
one (uint32, int32) signature, never with a gather by the order.  Every
move must equal the gather bit for bit; the programs built from it must
hold no capacity-length gather; and each program's build says how many
lanes it moved which way."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as t
from spark_rapids_tpu.columnar.device import (DEFAULT_ROW_BUCKETS, DeviceBatch,
                                              DeviceColumn)
from spark_rapids_tpu.ops import carry


N = 2500
_F_SPECIALS = [np.nan, -np.nan, 0.0, -0.0, np.inf, -np.inf]


def _bits(a):
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}") if a.dtype.kind == "f" else a


def _counted_since(before):
    """(lanes by sort pass, lanes by gather, sort passes) traced since."""
    now = carry.lane_move_counts()
    return tuple(now[k] - before[k] for k in (
        "lane_moves_sorted", "lane_moves_gathered", "sort_passes"))


def _perm(seed=11, n=N):
    order = np.random.default_rng(seed).permutation(n).astype(np.int32)
    rank = np.empty_like(order)
    rank[order] = np.arange(n, dtype=np.int32)
    return order, rank


def _lane(kind, n=N, seed=7):
    rng = np.random.default_rng(seed)
    if kind == "bool":
        return rng.integers(0, 2, n).astype(bool)
    if kind in ("int8", "int16", "int32", "int64", "uint32", "uint64"):
        info = np.iinfo(kind)
        x = rng.integers(info.min, info.max, n, dtype=kind, endpoint=True)
        x[:2] = [info.min, info.max]
        return x
    x = (rng.standard_normal(n)
         * 10.0 ** rng.integers(-30, 30, n)).astype(kind)
    tiny = np.finfo(kind).smallest_subnormal
    x[:10] = np.array(_F_SPECIALS + [tiny, -tiny, tiny * 3, 99999999999.99],
                      dtype=kind)
    return x


@pytest.mark.parametrize("kind", ["bool", "int8", "int16", "int32", "int64",
                                  "uint32", "uint64", "float32", "float64"])
def test_move_lanes_is_the_gather_by_the_order_bit_for_bit(kind):
    order, rank = _perm()
    x = _lane(kind)
    got, again = jax.jit(lambda r, a: carry.move_lanes(jnp, r, [a, a]))(
        jnp.asarray(rank), jnp.asarray(x))
    assert np.asarray(got).dtype == x.dtype
    assert (_bits(got) == _bits(x[order])).all()
    assert (_bits(again) == _bits(got)).all()
    # the numpy engine's move is the same move
    assert (_bits(carry.move_lanes(np, rank, [x])[0])
            == _bits(x[order])).all()


def test_forty_bool_lanes_move_in_two_words():
    order, rank = _perm()
    flags = [_lane("bool", seed=s) for s in range(40)]
    before = carry.lane_move_counts()
    got = jax.jit(lambda r, fs: carry.move_lanes(jnp, r, fs))(
        jnp.asarray(rank), [jnp.asarray(f) for f in flags])
    assert _counted_since(before) == (40, 0, 2)
    for f, g in zip(flags, got):
        assert (np.asarray(g) == f[order]).all()


def test_float64_as_the_tpu_holds_it_survives_the_split():
    """The TPU branch of a double's move: the (float32, remainder) pair.
    Exact for what such a pair holds (the chip's doubles are such pairs),
    NaN, both zeros and both infinities included."""
    rng = np.random.default_rng(3)
    f32 = rng.standard_normal(500).astype(np.float32).astype(np.float64)
    x = np.concatenate([
        f32, f32 + rng.integers(-2**20, 2**20, 500) * 2.0**-45,
        rng.integers(-2**47, 2**47, 500).astype(np.float64),
        np.array(_F_SPECIALS + [3.0e38, -3.0e38, 1.0 + 2.0**-40])])
    w0, w1 = carry._f64_split_words(jnp.asarray(x))
    assert w0.dtype == np.int32 and w1.dtype == np.int32
    back = np.asarray(carry._f64_join_split(w0, w1))
    same = (back == x) | (np.isnan(back) & np.isnan(x))
    assert same.all()
    assert (np.signbit(back) == np.signbit(x))[~np.isnan(x)].all()


def _columns():
    rng = np.random.default_rng(5)
    valid = rng.integers(0, 4, N) > 0
    strs = ["", "a", "bc", "def", "ghij"]
    lens = rng.integers(0, 5, N)
    chars = np.frombuffer("".join(strs[k] for k in lens).encode(), np.uint8)
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    return {
        "dec128": DeviceColumn(t.DecimalType(38, 2),
                               data=_lane("uint64").view(np.int64),
                               data_hi=_lane("int64", seed=8),
                               validity=valid),
        "struct": DeviceColumn(
            t.StructType([t.StructField("a", t.INT),
                          t.StructField("b", t.DOUBLE)]),
            validity=valid,
            children=(DeviceColumn(t.INT, data=_lane("int32"),
                                   validity=valid),
                      DeviceColumn(t.DOUBLE, data=_lane("float64"),
                                   validity=_lane("bool")))),
        "string": DeviceColumn(t.STRING, data=np.pad(chars, (0, 64)),
                               offsets=offs, validity=valid),
    }


@pytest.mark.parametrize("name", ["dec128", "struct", "string"])
def test_sort_rows_moves_a_column_as_the_numpy_engine_does(name):
    """decimal128's two words and a struct's children ride sort passes;
    a string column has offsets and must still take `gather_column`."""
    col = _columns()[name]
    key = np.random.default_rng(9).integers(0, 50, N).astype(np.int32)
    want_order, want_cols, _ = carry.sort_rows(np, [key], [col], N)
    before = carry.lane_move_counts()
    order, cols, _ = jax.jit(
        lambda k, c: carry.sort_rows(jnp, [k], [c], N))(
            jnp.asarray(key), jax.tree_util.tree_map(jnp.asarray, col))
    sorted_, gathered, _ = _counted_since(before)
    assert (np.asarray(order) == want_order).all()
    got = jax.tree_util.tree_leaves(cols[0])
    want = jax.tree_util.tree_leaves(want_cols[0])
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert (_bits(g) == _bits(w)).all()
    if name == "string":
        assert (sorted_, gathered) == (0, 3)     # data, validity, offsets
    else:
        assert gathered == 0 and sorted_ == len(got)


@pytest.mark.parametrize("cap,n_rows,keep_kind", [
    (1024, 700, "some"), (1024, 1024, "some"), (8192, 5000, "none"),
    (8192, 5000, "all"), (1, 1, "all"), (1000, 999, "some")])
def test_compaction_rank_is_numpys_stable_partition(cap, n_rows, keep_kind):
    rng = np.random.default_rng(cap + n_rows)
    live = np.arange(cap) < n_rows
    keep = {"some": rng.integers(0, 3, cap) == 0,
            "none": np.zeros(cap, bool), "all": np.ones(cap, bool)}[
                keep_kind] & live
    want_order = np.argsort(~keep, kind="stable")
    rank = np.asarray(jax.jit(
        lambda k: carry.compaction_rank(jnp, k, cap))(jnp.asarray(keep)))
    assert rank.dtype == np.int32
    assert (want_order[rank] == np.arange(cap)).all()
    assert (np.asarray(carry.compaction_rank(np, keep, cap)) == rank).all()
    # and the compaction built on it moves rows as the numpy engine does
    x = rng.integers(-9, 9, cap).astype(np.int64)
    col = DeviceColumn(t.LONG, data=x, validity=live)
    order, cols, extras = jax.jit(lambda k, c, e: carry.compact_rows(
        jnp, k, [c], cap, extras=[e], need_order=True))(
            jnp.asarray(keep), jax.tree_util.tree_map(jnp.asarray, col),
            jnp.asarray(x))
    assert (np.asarray(order) == want_order).all()
    assert (np.asarray(cols[0].data) == x[want_order]).all()
    assert (np.asarray(cols[0].validity) == live[want_order]).all()
    assert (np.asarray(extras[0]) == x[want_order]).all()


def _key_cases(rng, n):
    i64 = rng.integers(-2**62, 2**62, n).astype(np.int64)
    i64[::5] = -1
    few = rng.integers(0, 40, n).astype(np.int64)
    flag = lambda p: rng.integers(0, p, n) > 0       # noqa: E731
    return {
        # the subquery's key: live flag, null flag, an int64
        "flag_flag_i64": [flag(20), flag(10), few],
        "flag_flag_i64_wide": [flag(20), flag(10), i64],
        "i64_i64": [few, i64],
        "u8_i32_u16_flag": [rng.integers(0, 3, n).astype(np.uint8),
                            rng.integers(-5, 5, n).astype(np.int32),
                            rng.integers(0, 9, n).astype(np.uint16),
                            flag(2)],
        "flag": [flag(3)],
        "i16": [rng.integers(-300, 300, n).astype(np.int16)],
    }


@pytest.mark.parametrize("n", [3000, 4096, 1])
@pytest.mark.parametrize("case", ["flag_flag_i64", "flag_flag_i64_wide",
                                  "i64_i64", "u8_i32_u16_flag", "flag",
                                  "i16"])
def test_lean_perm_is_numpys_lexsort_and_its_inverse(case, n):
    """Digits are packed (a pass sorts 64 bits less the tie-break's), so
    the cases cross the one-operand and two-operand digit forms."""
    words = _key_cases(np.random.default_rng(n), n)[case]
    want = np.lexsort(tuple(reversed(words))).astype(np.int32)
    order, rank = jax.jit(lambda *ws: carry._lean_perm(jnp, list(ws), n))(
        *[jnp.asarray(w) for w in words])
    assert (np.asarray(order) == want).all()
    assert (want[np.asarray(rank)] == np.arange(n)).all()
    only_rank = jax.jit(lambda *ws: carry._lean_perm(
        jnp, list(ws), n, want_order=False))(*[jnp.asarray(w) for w in words])
    assert (np.asarray(only_rank[1]) == np.asarray(rank)).all()


def test_the_subquerys_key_is_two_digits_at_the_largest_bucket():
    cap = 33_554_432
    flag = jax.ShapeDtypeStruct((cap,), np.bool_)
    key = jax.ShapeDtypeStruct((cap,), np.uint64)
    before = carry.lane_move_counts()
    jax.eval_shape(lambda a, b, k: carry._lean_perm(
        jnp, [a, b, k], cap, want_order=False), flag, flag, key)
    # sort, inverse; sort, inverse, compose the rank
    assert _counted_since(before)[2] == 5


@pytest.mark.parametrize("case", ["flag_flag_i64", "u8_i32_u16_flag"])
def test_sort_rows_against_lexsort(case):
    n = 3000
    rng = np.random.default_rng(21)
    words = _key_cases(rng, n)[case]
    want = np.lexsort(tuple(reversed(words)))
    live = np.arange(n) < 2900
    col = DeviceColumn(t.DOUBLE, data=_lane("float64", n), validity=live)
    extra = _lane("int64", n)
    order, cols, extras = jax.jit(
        lambda ws, c, e: carry.sort_rows(jnp, ws, [c, c], n, extras=[e]))(
            [jnp.asarray(w) for w in words],
            jax.tree_util.tree_map(jnp.asarray, col), jnp.asarray(extra))
    assert (np.asarray(order) == want).all()
    for c in cols:
        assert (_bits(c.data) == _bits(col.data[want])).all()
        assert (np.asarray(c.validity) == live[want]).all()
    assert (np.asarray(extras[0]) == extra[want]).all()


# -- no gather creeps back ---------------------------------------------------

_GATHER = re.compile(r'stablehlo\.(dynamic_)?gather"?\(?[^\n]*')


def _capacity_gathers(lowered_text: str, cap: int):
    return [m.group(0)[:200] for m in _GATHER.finditer(lowered_text)
            if f"tensor<{cap}x" in m.group(0)]


def _flat_batch(cap):
    schema = (("k", t.LONG), ("q", t.DOUBLE), ("p", t.DOUBLE),
              ("d", t.DOUBLE), ("s", t.DATE))
    cols = [DeviceColumn(dt, data=jnp.zeros((cap,), t.to_np_dtype(dt)),
                         validity=jnp.ones((cap,), bool))
            for _, dt in schema]
    return DeviceBatch(cols, jnp.int32(cap - 7), [n for n, _ in schema])


def test_compact_and_group_reduce_lower_without_a_capacity_gather():
    from spark_rapids_tpu.exec.aggregate import _group_reduce
    from spark_rapids_tpu.exec.filter_common import compact
    cap = 4096
    batch = _flat_batch(cap)
    text = jax.jit(lambda b, k: compact(jnp, b, k, b.names)).lower(
        batch, jnp.ones((cap,), bool)).as_text()
    assert "stablehlo.sort" in text
    assert _capacity_gathers(text, cap) == []

    def grouped(b):
        live = jnp.arange(cap, dtype=jnp.int32) < b.num_rows
        return _group_reduce(jnp, [b.columns[0]], [b.columns[1], b.columns[4]],
                             ["sum", "countvalid"], cap, live, False)

    def ungrouped(b):
        live = jnp.arange(cap, dtype=jnp.int32) < b.num_rows
        return _group_reduce(jnp, [], [b.columns[1]], ["sum"], cap, live,
                             True)
    text = jax.jit(grouped).lower(batch).as_text()
    assert "stablehlo.sort" in text
    assert _capacity_gathers(text, cap) == []
    # one group orders nothing: a reduction under the mask, one row out in
    # the smallest bucket (Q6's sum)
    lowered = jax.jit(ungrouped).lower(batch)
    text = lowered.as_text()
    assert "stablehlo.sort" not in text
    assert _capacity_gathers(text, cap) == []
    _, values, _ = lowered.out_info
    assert [leaf.shape for leaf in jax.tree_util.tree_leaves(values)] == \
        [(DEFAULT_ROW_BUCKETS[0],)] * 2
    # the check can see one: the gather path of a string column
    col = jax.tree_util.tree_map(jnp.asarray, _columns()["string"])
    text = jax.jit(lambda c, k: carry.compact_rows(jnp, k, [c], N)).lower(
        col, jnp.ones((N,), bool)).as_text()
    assert _capacity_gathers(text, N) != []


# -- the counter on a program's build ----------------------------------------

def _filter_programs(with_string: bool):
    import pyarrow as pa
    from spark_rapids_tpu.api.column import col, lit
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    rng = np.random.default_rng(1)
    n = 600
    data = {"a": rng.integers(0, 9, n), "b": rng.standard_normal(n),
            "c": rng.integers(0, 9, n).astype(np.int32),
            "d": rng.standard_normal(n).astype(np.float32),
            "e": rng.integers(0, 2, n).astype(bool)}
    if with_string:
        data["s"] = [f"s{i % 13}" for i in range(n)]
    s = TpuSession.builder().config("spark.rapids.sql.enabled", True) \
        .config("spark.rapids.tpu.trace.enabled", True).get_or_create()
    df = s.create_dataframe(pa.table(data), num_partitions=1)
    # a predicate of its own, so that the program is built here
    out = df.filter(col("a") > lit(3 if with_string else 4)).collect()
    assert out.num_rows == int((data["a"] > (3 if with_string else 4)).sum())
    programs = [p for p in CompileObservatory.get().snapshot()["programs"]
                if p["exec"] == "FilterExec" and "lane_moves_sorted" in p]
    spans = [sp for sp in s.last_query_trace().spans
             if sp.name == "jit.build:FilterExec"]
    return programs, spans


def test_a_flat_filter_builds_a_program_that_gathers_no_lane():
    programs, spans = _filter_programs(with_string=False)
    assert programs, "the filter built no program of its own"
    last = programs[-1]
    # five data lanes and five validity lanes, all by sort pass
    assert last["lane_moves_gathered"] == 0
    assert last["lane_moves_sorted"] == 10
    assert last["sort_passes"] == 7     # a:2 b:2 c:1 d:1, six flags: 1
    assert spans and spans[-1].attrs["lane_moves_gathered"] == 0
    assert spans[-1].attrs["lane_moves_sorted"] == 10


def _ungrouped_programs(agg):
    """The records of the aggregate programs that one ungrouped query
    built, and its answer."""
    import pyarrow as pa
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    rng = np.random.default_rng(3)
    n = 700
    data = {"p": rng.uniform(900.0, 105000.0, n), "d": rng.uniform(0, 0.1, n)}
    s = TpuSession.builder().config("spark.rapids.sql.enabled", True) \
        .get_or_create()
    seen = {(p["key"], p["shape"])
            for p in CompileObservatory.get().snapshot()["programs"]}
    out = s.create_dataframe(pa.table(data), num_partitions=1).agg(agg) \
        .collect()
    built = [p for p in CompileObservatory.get().snapshot()["programs"]
             if p["exec"] == "TpuHashAggregateExec" and
             (p["key"], p["shape"]) not in seen]
    return built, out, data


def test_an_ungrouped_sum_builds_a_program_that_sorts_nothing():
    """Q6's shape: sum(price * discount) over one batch, no keys."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    built, out, data = _ungrouped_programs(
        F.sum(col("p") * col("d")).alias("revenue"))
    assert len(built) == 1
    assert built[0]["ungrouped_reduced"] == 1
    assert built[0]["ungrouped_sorted"] == 0
    assert built[0]["sort_passes"] == 0
    assert built[0]["lane_moves_sorted"] == 0
    assert built[0]["lane_moves_gathered"] == 0
    want = float((data["p"] * data["d"]).sum())
    assert abs(out.column("revenue")[0].as_py() - want) <= 1e-12 * want


def test_an_ungrouped_collect_list_still_takes_the_sort_arm():
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    built, out, data = _ungrouped_programs(F.collect_list(col("p")).alias("l"))
    assert built
    assert sum(p["ungrouped_reduced"] for p in built) == 0
    assert sum(p["ungrouped_sorted"] for p in built) >= 1
    assert sum(p["sort_passes"] for p in built) > 0
    assert out.column("l")[0].as_py() == list(data["p"])


def _grouped_programs(key_values):
    """The records of the aggregate programs that one grouped query built
    over a key column of `key_values`."""
    import pyarrow as pa
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    rng = np.random.default_rng(4)
    n = 700
    data = {"k": rng.choice(key_values, n), "p": rng.uniform(1.0, 9.0, n)}
    s = TpuSession.builder().config("spark.rapids.sql.enabled", True) \
        .get_or_create()
    seen = {(p["key"], p["shape"])
            for p in CompileObservatory.get().snapshot()["programs"]}
    out = s.create_dataframe(pa.table(data), num_partitions=1) \
        .group_by(col("k")).agg(F.sum(col("p")).alias("s"),
                                F.count("*").alias("n")).collect()
    assert out.num_rows == len(key_values)
    return [p for p in CompileObservatory.get().snapshot()["programs"]
            if p["exec"] == "TpuHashAggregateExec" and
            (p["key"], p["shape"]) not in seen]


def test_a_bounded_key_builds_a_program_that_holds_the_dense_arm():
    """Q1's shape: a one-byte string key.  The sort arm is in the program
    too, behind the conditional, and its passes are counted."""
    built = _grouped_programs(np.array(["A", "N", "R"]))
    assert len(built) == 1
    assert built[0]["grouped_dense"] == 1
    assert built[0]["grouped_sorted"] == 0
    assert built[0]["sort_passes"] > 0
    assert built[0]["lane_moves_gathered"] == 0
    assert built[0]["ungrouped_reduced"] == built[0]["ungrouped_sorted"] == 0


def test_an_int64_key_builds_the_sort_arm_alone():
    built = _grouped_programs(np.array([3, 1 << 40, -7]))
    assert len(built) == 1
    assert built[0]["grouped_dense"] == 0
    assert built[0]["grouped_sorted"] == 1
    assert built[0]["sort_passes"] > 0


def test_a_string_column_counts_as_gathered():
    programs, _ = _filter_programs(with_string=True)
    assert programs
    assert programs[-1]["lane_moves_gathered"] == 3
    assert programs[-1]["lane_moves_sorted"] == 10
