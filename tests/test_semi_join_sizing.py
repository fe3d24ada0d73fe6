"""A semi or an anti join sizes its sides by what is live (PR 38): its count
gathers the build's live rows into their bucket where that bucket is at
most 1/32 of the build's capacity (`NARROW_SHARE`,
`HashJoinExec._count_narrowed`),
and its selection waits for the kept count and hands up the kept rows in
their bucket, gathered there under the same rule or compacted and sliced
above it (`_kept_fetch`, `_select`; `filter_common.gather_kept` over
`carry.kept_slots`).

The referees: `CpuJoinExec` over the NumPy engine's filters (the pyarrow
join), and NumPy's `isin` for the order; every test runs both joins."""

import os
import sys

import numpy as np
import pyarrow as pa
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spark_rapids_tpu.api.column import col, lit  # noqa: E402
from spark_rapids_tpu.columnar.device import (  # noqa: E402
    DEFAULT_ROW_BUCKETS, bucket_for)
from spark_rapids_tpu.columnar.interop import to_arrow_schema  # noqa: E402
from spark_rapids_tpu.exec.base import (CPU, TPU, ExecContext,  # noqa: E402
                                        to_host_batch)
from spark_rapids_tpu.exec.basic import (FilterExec,  # noqa: E402
                                         LocalScanExec, ProjectExec)
from spark_rapids_tpu.exec.join import (NARROW_SHARE,  # noqa: E402
                                        CpuJoinExec, HashJoinExec)
from spark_rapids_tpu.obs import metrics, tracer  # noqa: E402
from spark_rapids_tpu.obs.compileprof import CompileObservatory  # noqa: E402
from spark_rapids_tpu.testing.asserts import assert_tables_equal  # noqa: E402

HOWS = ["left_semi", "left_anti"]
#: 60,000 rows a side: both lie in 65,536 slots, so a side whose live rows
#: fit 1,024 slots narrows (1,024 x 32 <= 65,536) and one of 1,025, whose
#: bucket is 8,192, does not
ROWS = 60000
CAP = 65536


def _tables(live: int, seed: int = 38, n_keys: int = 400):
    """(probe, build): the build's `q` is a permutation, so the filter
    `q < live` keeps exactly `live` rows, spread over the capacity; among
    them a key twice and a null key.  Probe keys 0..n_keys-1, a tenth
    null, a string carried; `p` for a probe filter."""
    rng = np.random.default_rng(seed)
    q = rng.permutation(ROWS)
    k2 = rng.integers(0, 200, ROWS)
    k2[q == 1] = k2[q == 0]                       # a build key twice
    null2 = (rng.random(ROWS) < 0.05) | (q == 2)  # a null key among them
    build = pa.table({
        "k2": pa.array(k2.astype(np.int64), mask=null2),
        "q": pa.array(q.astype(np.int64))})
    probe = pa.table({
        "k": pa.array(rng.integers(0, n_keys, ROWS).astype(np.int64),
                      mask=rng.random(ROWS) < 0.1),
        "va": pa.array(np.arange(ROWS, dtype=np.int64)),
        "sa": pa.array([None if i % 13 == 0 else "s" * (i % 5) + str(i)
                        for i in range(ROWS)], pa.string()),
        "p": pa.array(rng.integers(-100, 100, ROWS).astype(np.int64))})
    return probe, build


def _plan(tables, how, live, build_kind="masked", probe_filter=False,
          engine=TPU, join_cls=HashJoinExec):
    """scan (-> filter) on the probe; on the build a filter keeping `live`
    rows under a bare selection of the key (`masked`: it hands up its
    flags, as Q18's HAVING does) or under a projection that computes
    (`compacted`: the filter compacts, the row count stays on the
    device)."""
    probe_table, build_table = tables
    probe = LocalScanExec(probe_table, 1)
    if probe_filter:
        probe = FilterExec((col("p") > lit(0)).expr, probe)
    kept = FilterExec((col("q") < lit(live)).expr,
                      LocalScanExec(build_table, 1))
    key = col("k2") if build_kind == "masked" else \
        (col("k2") + lit(0)).alias("k2")
    build = ProjectExec([key.expr], kept)
    join = join_cls([col("k").expr], [col("k2").expr], how, None, probe,
                    build)
    join.foreach(lambda e: setattr(e, "placement", engine))
    return join


def _collect(plan):
    """(the answer, the join's spans)."""
    trace = tracer.install(tracer.QueryTrace())
    try:
        ctx = ExecContext()
        batches = [to_host_batch(b, plan.output_names)
                   for pid in range(plan.num_partitions)
                   for b in plan.execute_partition(pid, ctx)]
    finally:
        tracer.uninstall()
    spans = {}
    for sp in trace.span_dicts():
        if sp["name"].startswith("join."):
            spans.setdefault(sp["name"], []).append(sp["attrs"])
    return pa.Table.from_batches(
        batches, to_arrow_schema(plan.output_names, plan.output_types)), \
        spans


def _counter(name, **labels):
    for family in metrics.registry().families():
        if family.name == name:
            return family.value(**labels) if labels else family.total()
    return 0


def _narrowed():
    return {side: _counter("tpu_join_narrowed_total", side=side)
            for side in ("build", "output")}


def _checked(tables, how, live, **shape):
    """The TPU join's answer, held to `CpuJoinExec`'s and, in its order,
    to the probe's: every kept row once, in probe order."""
    got, spans = _collect(_plan(tables, how, live, **shape))
    cpu, _ = _collect(_plan(tables, how, live, engine=CPU,
                            join_cls=CpuJoinExec, **shape))
    assert_tables_equal(cpu, got, ignore_order=True)
    va = got.column("va").to_numpy()
    assert np.all(np.diff(va) > 0)
    return got, spans


@pytest.mark.parametrize("build_kind", ["masked", "compacted"])
@pytest.mark.parametrize("how", HOWS)
def test_a_few_live_build_rows_are_gathered_into_their_bucket(how,
                                                             build_kind):
    """40 live build rows in 65,536 slots: the count sorts 1,024 of them,
    whether the flags came up with the batch or a compaction left the
    count on the device (one counted fetch)."""
    before = _narrowed()
    crossings = _counter("tpu_fetch_crossings_total")
    got, spans = _checked(_tables(40), how, 40, build_kind=build_kind)
    build, = spans["join.build"]
    assert build["capacity"] == CAP and build["live_rows"] == 40
    assert build["narrowed_capacity"] == 1024
    assert build["masked"] is (build_kind == "masked")
    probe, = spans["join.probe"]
    assert probe["sorted_slots"] == CAP + 1024
    assert probe["build_capacity"] == CAP and probe["path"] == "select"
    assert _narrowed()["build"] == before["build"] + 1
    # the build's live count and the kept count: two waits, and the
    # answer's own fetch
    assert _counter("tpu_fetch_crossings_total") - crossings >= 2
    assert 0 < got.num_rows < ROWS


@pytest.mark.parametrize("how", HOWS)
def test_null_keys_and_a_twice_held_key_under_a_filtered_probe(how):
    """The live build rows hold a null key and one key twice; the probe
    comes up masked.  A null matches nothing (an anti join keeps its
    row), and a probe row whose key the build holds twice is kept once."""
    tables = _tables(40)
    got, spans = _checked(tables, how, 40, probe_filter=True)
    probe, build = tables
    q = build["q"].to_numpy()
    live_keys = build["k2"].filter(pa.array(q < 40)).to_pylist()
    assert None in live_keys
    assert len(live_keys) > len(set(live_keys))
    k = probe["k"].to_pylist()
    p = probe["p"].to_numpy()
    keys = {x for x in live_keys if x is not None}
    want = [i for i in range(ROWS) if p[i] > 0 and (
        (k[i] is not None and k[i] in keys) == (how == "left_semi"))]
    assert got.column("va").to_pylist() == want
    assert spans["join.probe"][0]["probe_masked"] is True


@pytest.mark.parametrize("how", HOWS)
def test_no_live_build_row(how):
    """A filter that keeps nothing: the count sorts the 1,024-row bucket
    of no row; a semi join keeps nothing, an anti join every probe row."""
    got, spans = _checked(_tables(0), how, 0)
    assert spans["join.build"][0]["live_rows"] == 0
    assert spans["join.build"][0]["narrowed_capacity"] == 1024
    size, = spans["join.size"]
    if how == "left_semi":
        assert got.num_rows == 0 and size["total"] == 0
        assert size["out_capacity"] == 1024     # gathered: 1,024 x 8
    else:
        assert got.num_rows == ROWS and size["out_capacity"] == CAP


@pytest.mark.parametrize("how", HOWS)
def test_no_kept_probe_row(how):
    """Probe keys the build never holds (semi) or all of which it holds
    (anti: one live build row a key): the selection keeps no row and
    hands up 1,024 empty slots."""
    tables = _tables(ROWS if how == "left_anti" else 40, n_keys=400)
    probe, build = tables
    if how == "left_semi":
        probe = probe.set_column(0, "k", pa.array(
            np.full(ROWS, 10**6, np.int64)))
        live = 40
    else:
        keys = np.arange(ROWS, dtype=np.int64) % 200
        build = pa.table({"k2": pa.array(keys), "q": build["q"]})
        probe = probe.set_column(0, "k", pa.array(keys[::-1].copy()))
        live = ROWS
    before = _narrowed()
    got, spans = _checked((probe, build), how, live)
    assert got.num_rows == 0
    size, = spans["join.size"]
    assert size["total"] == 0 and size["out_capacity"] == 1024
    assert spans["join.probe"][0]["out_capacity"] == 1024
    assert _narrowed()["output"] == before["output"] + 1


@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("live", [1024, 1025])
def test_the_share_rule_picks_the_gather_or_the_capacity(how, live):
    """1,024 live build rows in 65,536 slots narrow (1,024 x 32 <= 65,536),
    1,025 do not (their bucket is 8,192): the count then sorts the
    build's capacity under its flags, as before PR 38.  The kept rows
    follow the same rule: a bucket of 1,024 is gathered, a larger one
    compacted at the probe's capacity and sliced to it."""
    assert NARROW_SHARE == 32
    before = _narrowed()
    got, spans = _checked(_tables(live), how, live)
    build, = spans["join.build"]
    assert build["live_rows"] == live
    narrowed = live <= 1024
    assert build["narrowed_capacity"] == (1024 if narrowed else CAP)
    assert spans["join.probe"][0]["sorted_slots"] == \
        CAP + build["narrowed_capacity"]
    size, = spans["join.size"]
    out_cap = min(bucket_for(size["total"], DEFAULT_ROW_BUCKETS), CAP)
    assert size["out_capacity"] == out_cap == \
        spans["join.probe"][0]["out_capacity"]
    assert size["total"] == got.num_rows
    assert _narrowed() == {
        "build": before["build"] + narrowed,
        "output": before["output"] + (out_cap * NARROW_SHARE <= CAP)}


@pytest.mark.parametrize("how", HOWS)
def test_a_kept_bucket_over_the_share_is_compacted_and_sliced(how):
    """5,000-odd kept rows of 60,000 (semi) or 55,000-odd (anti): their
    bucket, 8,192 or 65,536, is over 1/32 of the probe's 65,536 slots, so
    the selection compacts at the capacity and hands up the bucket's
    prefix; no output is counted as narrowed."""
    before = _narrowed()
    got, spans = _checked(_tables(40), how, 40)
    size, = spans["join.size"]
    assert size["out_capacity"] == (8192 if how == "left_semi" else CAP)
    assert size["total"] == got.num_rows
    assert _narrowed()["output"] == before["output"]


def test_live_counts_in_one_bucket_share_their_programs():
    """Two filters' worth of live build rows, 30 and 50, and the kept
    rows they leave, all in the 1,024 bucket: the second query builds
    nothing."""
    obs = CompileObservatory.get()
    for how in HOWS:
        _collect(_plan(_tables(30), how, 30))
        builds = obs.snapshot()["builds"]
        got, spans = _collect(_plan(_tables(50), how, 50))
        assert obs.snapshot()["builds"] == builds
        assert spans["join.build"][0]["narrowed_capacity"] == 1024
