"""The `left_semi` / `left_anti` arm of `HashJoinExec` (PR 37): the probe's
selection runs inside a program of the join named for its role (`semi`,
and the count before it `semi_count`), same rows, same order and same
capacity as the eager compaction it replaces; and a general-layout string
of mixed lengths carried through two join expansions into a group key.

The referees: NumPy's `isin` over the generated keys, the eager
`HashJoinExec._select` over the same counts, and the CPU engine."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spark_rapids_tpu.api import functions as F  # noqa: E402
from spark_rapids_tpu.api.column import col, lit  # noqa: E402
from spark_rapids_tpu.api.session import TpuSession  # noqa: E402
from spark_rapids_tpu.columnar.interop import to_arrow_schema  # noqa: E402
from spark_rapids_tpu.exec.base import (TPU, ExecContext,  # noqa: E402
                                        _JIT_CACHE, to_host_batch)
from spark_rapids_tpu.exec.basic import (FilterExec,  # noqa: E402
                                         LocalScanExec, ProjectExec)
from spark_rapids_tpu.exec.join import HashJoinExec  # noqa: E402
from spark_rapids_tpu.obs import compileprof, metrics  # noqa: E402
from spark_rapids_tpu.obs.compileprof import CompileObservatory  # noqa: E402
from spark_rapids_tpu.testing.asserts import assert_tables_equal  # noqa: E402

HOWS = ["left_semi", "left_anti"]
MASKS = ["none", "probe", "build", "both"]


def _tables(seed=37, n_p=400, n_b=90):
    """(probe, build): keys 0..15 on the probe and 6..21 on the build,
    duplicates on both sides (every build key about four times), nulls
    among both, a string and a number carried on the probe, and the
    predicates' columns `p` and `q`, which know nothing of the keys."""
    rng = np.random.default_rng(seed)

    def keys(n, lo, hi):
        return pa.array(rng.integers(lo, hi, n).astype(np.int64),
                        mask=rng.random(n) < 0.1)
    probe = pa.table({
        "k": keys(n_p, 0, 16),
        "va": pa.array(np.arange(n_p, dtype=np.int64)),
        "sa": pa.array([None if i % 13 == 0 else "a" * (i % 7) + str(i)
                        for i in range(n_p)], pa.string()),
        "p": pa.array(rng.integers(-100, 100, n_p).astype(np.int64))})
    build = pa.table({
        "k2": keys(n_b, 6, 22),
        "q": pa.array(rng.integers(-100, 100, n_b).astype(np.int64))})
    return probe, build


def _plan(tables, how, mask):
    """scan (-> filter) on the probe, scan (-> filter -> bare selection
    of the key) on the build, as a subquery's HAVING output lies under a
    semi join; every operator on the TPU engine."""
    probe_table, build_table = tables
    probe = LocalScanExec(probe_table, 1)
    build = LocalScanExec(build_table, 1)
    if mask in ("probe", "both"):
        probe = FilterExec((col("p") > lit(0)).expr, probe)
    if mask in ("build", "both"):
        build = ProjectExec([col("k2").expr],
                            FilterExec((col("q") > lit(0)).expr, build))
    join = HashJoinExec([col("k").expr], [col("k2").expr], how, None,
                        probe, build)
    join.foreach(lambda e: setattr(e, "placement", TPU))
    return join


def _collect(plan) -> pa.Table:
    ctx = ExecContext()
    batches = [to_host_batch(b, plan.output_names)
               for pid in range(plan.num_partitions)
               for b in plan.execute_partition(pid, ctx)]
    return pa.Table.from_batches(
        batches, to_arrow_schema(plan.output_names, plan.output_types))


def _isin_rows(tables, how, mask):
    """The probe rows the join keeps, by NumPy's `isin`: a row under the
    probe's filter whose key is (semi) or is not (anti) among the build's
    kept, non-null keys; a null key is among nothing."""
    probe, build = tables
    k = probe["k"].to_numpy(zero_copy_only=False)
    k_null = np.asarray(probe["k"].is_null())
    k2 = build["k2"].to_numpy(zero_copy_only=False)
    live_b = ~np.asarray(build["k2"].is_null())
    if mask in ("build", "both"):
        live_b &= build["q"].to_numpy() > 0
    live_p = np.ones(len(k), bool)
    if mask in ("probe", "both"):
        live_p &= probe["p"].to_numpy() > 0
    hit = ~k_null & np.isin(np.where(k_null, -1, k), k2[live_b])
    return np.flatnonzero(live_p & (hit if how == "left_semi" else ~hit))


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("how", HOWS)
def test_the_arm_keeps_what_isin_keeps_once_and_in_order(how, mask):
    tables = _tables()
    plan = _plan(tables, how, mask)
    assert [s is not None for s in plan.masked_sources()] == \
        [mask in ("probe", "both"), mask in ("build", "both")]
    got = _collect(plan)
    rows = _isin_rows(tables, how, mask)
    assert 0 < len(rows) < tables[0].num_rows
    # every build key lies there about four times: a semi join that
    # expanded would give a probe row once a duplicate
    assert got.num_rows == len(rows)
    assert_tables_equal(tables[0].take(pa.array(rows)), got,
                        ignore_order=False)


@pytest.mark.parametrize("mask", ["none", "both"])
@pytest.mark.parametrize("how", HOWS)
def test_the_jitted_selection_is_the_eager_one_to_the_bit(how, mask):
    """What `_probe_batch` did before PR 37, the selection as a string
    of eager operations, against the program `jit_HashJoinExec.semi`:
    every lane, flag and count of the output batch, padding included.
    Since PR 38 both hand up the kept rows' bucket, and where it is over
    1/`NARROW_SHARE` of the probe's capacity (here: 1,024 of 1,024) they
    are compacted at the probe's capacity and sliced to it."""
    from spark_rapids_tpu.columnar.device import bucket_for
    from spark_rapids_tpu.exec.join import _kept_fetch, _narrow_bucket
    plan = _plan(_tables(seed=38), how, mask)
    ctx = ExecContext()
    build, bkeep, _ = plan._collect_build(0, ctx)
    source = plan.masked_sources()[0]
    if source is not None:
        (m,) = list(source.execute_masked(0, ctx, plan))
        probe, pkeep = m.batch, m.keep
    else:
        (probe,) = list(plan.children[0].execute_partition(0, ctx))
        pkeep = None
    _, _, counts, sizes, _ = plan._count_call(jnp, build, probe, pkeep,
                                              bkeep)
    caps = _kept_fetch(sizes, probe)
    kept = int(np.asarray(sizes)[0])
    assert caps[0] == min(bucket_for(kept, (1024,)), probe.capacity)
    assert not _narrow_bucket(kept, probe.capacity)
    eager = plan._select(jnp, probe, counts, caps, pkeep)
    jitted = plan._select_call(jnp, probe, counts, caps, pkeep)
    assert jitted.capacity == eager.capacity == caps[0]
    assert int(jitted.num_rows) == kept
    assert jitted.names == eager.names
    a, b = jax.tree_util.tree_leaves(eager), jax.tree_util.tree_leaves(
        jitted)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y, equal_nan=x.dtype.kind == "f")
    assert 0 < int(jitted.num_rows) < int(probe.num_rows)


def test_a_selecting_joins_programs_carry_roles_of_their_own():
    """`semi` and `semi_count` are roles `program_name` knows, a semi and
    an anti join build one of each (their `how` is in the key), an
    expanding join keeps `count` / `expand`, and the observatory counts
    the selection's build and its passes."""
    assert {"semi", "semi_count"} <= compileprof._ROLES
    tables = _tables(seed=39)
    # a schema of this test's own, so that the programs are built here
    tables = (tables[0].append_column("only_here", tables[0]["va"]),
              tables[1])
    obs = CompileObservatory.get()
    before = len(obs.snapshot()["programs"])
    for how in HOWS + ["inner"]:
        _collect(_plan(tables, how, "none"))
    sig = _plan(tables, "inner", "none")._jit_key[2]
    names = sorted(
        (next(p for p in key if p in ("left_semi", "left_anti", "inner")),
         compileprof.program_name(key))
        for key in _JIT_CACHE if "HashJoinExec" in key and sig in key)
    assert names == [
        ("inner", "HashJoinExec.count"), ("inner", "HashJoinExec.expand"),
        ("left_anti", "HashJoinExec.semi"),
        ("left_anti", "HashJoinExec.semi_count"),
        ("left_semi", "HashJoinExec.semi"),
        ("left_semi", "HashJoinExec.semi_count")]
    built = [p for p in obs.snapshot()["programs"][before:]
             if p["exec"] == "HashJoinExec"]
    assert len(built) == 6
    # the selections move the probe's lanes by sort pass and sort nothing
    # else; the counts hold the build's order and the one sort of both
    selections = [p for p in built if p["lane_moves_sorted"] > 3]
    assert len(selections) == 2
    for p in built:
        assert p["join_string_cols_gathered"] == (
            1 if p["join_cols_gathered"] else 0)   # `sa`, in the expand


def test_the_span_and_the_counter_say_what_the_count_sorted():
    session = TpuSession.builder().config(
        "spark.rapids.sql.enabled", True).config(
        "spark.rapids.tpu.trace.enabled", True).get_or_create()
    probe, build = _tables(seed=40)
    fact = session.create_dataframe(probe, num_partitions=1)
    dim = session.create_dataframe(build, num_partitions=1)

    def sorted_slots():
        return sum(f.total() for f in metrics.registry().families()
                   if f.name == "tpu_join_sorted_slots_total")
    before = sorted_slots()
    fact.join(dim, col("k") == col("k2"), "left_semi").collect()
    spans = session.last_query_trace().spans
    attrs = next(sp for sp in spans if sp.name == "join.probe").attrs
    assert attrs["how"] == "left_semi" and attrs["path"] == "select"
    # 90 build rows in 1,024 slots and 400 probe rows in 1,024: no side is
    # sparse enough to narrow (PR 38), so the count sorts both capacities
    build = next(sp for sp in spans if sp.name == "join.build").attrs
    assert build["narrowed_capacity"] == build["capacity"] == \
        attrs["build_capacity"]
    assert attrs["sorted_slots"] == \
        attrs["probe_capacity"] + attrs["build_capacity"]
    # the semi join waits for its kept rows and hands up their bucket,
    # here the probe's capacity
    size = next(sp for sp in spans if sp.name == "join.size").attrs
    assert attrs["out_capacity"] == size["out_capacity"] == \
        attrs["probe_capacity"]
    assert 0 < size["total"] < probe.num_rows
    assert sorted_slots() - before == attrs["sorted_slots"]
    fact.join(dim, col("k") == col("k2"), "inner").collect()
    attrs = next(sp for sp in session.last_query_trace().spans
                 if sp.name == "join.probe").attrs
    assert attrs["path"] == "two_phase"
    assert sorted_slots() - before == 2 * attrs["sorted_slots"]


def test_the_new_names_are_in_the_table_of_spans_and_readers():
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        text = f.read()
    table = text.split("<!-- spans-and-readers -->")[1]
    assert re.search(r"\| counter \| `tpu_join_sorted_slots_total` \|.*"
                     r"`join_sorted_mslots_per_query`", table)
    assert "`sorted_slots`" in table
    for name in ("jit_HashJoinExec.semi", "jit_HashJoinExec.semi_count",
                 "join_string_cols_gathered"):
        assert name in text, name


# -- a general-layout string through two expansions into a group key ----------

def _named_tables():
    """customers (key, a name of 3..25 bytes, some null), orders (key,
    customer) and lines (order, amount): every customer's name is carried
    through the join with orders and the join with lines, then grouped."""
    rng = np.random.default_rng(41)
    n_c, n_o, n_l = 60, 200, 700
    names = [None if i % 9 == 0 else
             "N" + "x" * int(rng.integers(0, 23)) + f"{i:02d}"
             for i in range(n_c)]
    assert {len(s) for s in names if s} >= {3, 25} and None in names
    customers = pa.table({"c": pa.array(np.arange(n_c, dtype=np.int64)),
                          "name": pa.array(names, pa.string())})
    orders = pa.table({
        "o": pa.array(np.arange(n_o, dtype=np.int64)),
        "oc": pa.array(rng.integers(0, n_c, n_o).astype(np.int64))})
    lines = pa.table({
        "lo": pa.array(rng.integers(0, n_o + 20, n_l).astype(np.int64)),
        "amount": pa.array(rng.integers(1, 50, n_l).astype(np.float64))})
    return customers, orders, lines


def _grouped_names(enabled: bool) -> pa.Table:
    session = TpuSession.builder().config(
        "spark.rapids.sql.enabled", enabled).get_or_create()
    customers, orders, lines = (
        session.create_dataframe(t, num_partitions=1)
        for t in _named_tables())
    return (customers.join(orders, col("c") == col("oc"), "inner")
            .join(lines, col("o") == col("lo"), "inner")
            .group_by(col("name"), col("c"))
            .agg(F.sum(col("amount")).alias("total"),
                 F.count(col("lo")).alias("lines"))
            .order_by(col("c"))).collect()


def test_a_mixed_length_string_rides_two_expansions_into_a_group_key():
    got = _grouped_names(True)
    customers, orders, lines = _named_tables()
    oc = orders["oc"].to_numpy()
    lo, amount = lines["lo"].to_numpy(), lines["amount"].to_numpy()
    names = customers["name"].to_pylist()
    want = {}
    for o, a in zip(lo, amount):
        if o < len(oc):
            c = int(oc[o])
            total, n = want.get(c, (0.0, 0))
            want[c] = (total + a, n + 1)
    assert got.column("c").to_pylist() == sorted(want)
    assert got.column("name").to_pylist() == [names[c] for c in sorted(want)]
    assert got.column("name").null_count > 0
    assert got.column("total").to_pylist() == \
        [want[c][0] for c in sorted(want)]
    assert got.column("lines").to_pylist() == \
        [want[c][1] for c in sorted(want)]
    assert_tables_equal(_grouped_names(False), got, ignore_order=False)
    # the expansions said what they carried: both moved the name
    programs = CompileObservatory.get().snapshot()["programs"]
    assert sum(p.get("join_string_cols_gathered", 0)
               for p in programs) >= 2
