"""The TPU compiler's verdict on the main path's programs, asked here
without a chip: each program is lowered from shapes for a described
`v5e:2x2` topology and compiled.  What the compiler refuses (a 64-bit
float bitcast did, before PR 21) or cannot fit fails here, at no chip
time.  A compile that passes is not a chip run and says nothing of
results or times.

Only the process that runs this file loads the TPU's library, and only
once a test has started: the topology is described in a fixture, and
everything built from it is built in fixtures and tests."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from spark_rapids_tpu import types as t
from spark_rapids_tpu.columnar.device import DeviceBatch, DeviceColumn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)        # __graft_entry__

# compiles take tens of seconds each where a sort is involved
pytestmark = pytest.mark.time_limit(900)

M1 = 1_048_576
M4 = 4_194_304

FACT = (("k", t.LONG), ("v", t.LONG), ("f", t.DOUBLE))
DIM = (("k", t.LONG), ("w", t.DOUBLE))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("data",))


@pytest.fixture(scope="module", autouse=True)
def quiet_compiles():
    """The persistent cache off around the compiles (an entry written
    for a described chip cannot be read back without one and warns)."""
    from jax.experimental.compilation_cache import compilation_cache
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def abstract_batch(schema, cap, sharding, lead=()):
    """A DeviceBatch of shapes: every column a data and a validity lane
    at capacity `cap` (behind the leading axes `lead`, for a stacked
    mesh input)."""
    def lane(dtype):
        return jax.ShapeDtypeStruct(lead + (cap,), dtype, sharding=sharding)
    cols = [DeviceColumn(dt, data=lane(t.to_np_dtype(dt)),
                         validity=lane(np.bool_)) for _, dt in schema]
    rows = jax.ShapeDtypeStruct(lead, np.int32, sharding=sharding)
    return DeviceBatch(cols, rows, [n for n, _ in schema])


def compile_for_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held < 16 * (1 << 30), f"does not fit a 16 GB chip: {mem}"
    return compiled


def _source(schema):
    from spark_rapids_tpu.parallel.distributed import _SchemaSource
    return _SchemaSource([n for n, _ in schema], [d for _, d in schema])


def test_filter_and_partial_aggregate_step(one_chip):
    """The step `__graft_entry__.entry()` returns, at the 1M bucket."""
    import __graft_entry__ as graft
    step, _ = graft.entry()
    compile_for_chip(step, abstract_batch(FACT, M1, one_chip))


def test_global_sort(one_chip):
    from spark_rapids_tpu.exec.sort import SortExec
    from spark_rapids_tpu.expr.core import AttributeReference as A
    sort = SortExec([(A("k"), True, True), (A("v"), True, True)],
                    _source(FACT))
    compile_for_chip(lambda b: sort._sort_batch(jnp, b),
                     abstract_batch(FACT, M1, one_chip))


def test_join_count(one_chip):
    from spark_rapids_tpu.exec.join import HashJoinExec
    from spark_rapids_tpu.expr.core import AttributeReference as A
    join = HashJoinExec([A("k")], [A("k")], "inner", None,
                        _source(FACT), _source(DIM))
    compile_for_chip(lambda build, probe: join._count(jnp, build, probe),
                     abstract_batch(DIM, 262_144, one_chip),
                     abstract_batch(FACT, M1, one_chip))


def test_result_fetch_pack_at_the_largest_bucket(one_chip):
    from spark_rapids_tpu.columnar.fetch import (_make_shrink_pack_fn,
                                                 _make_sizes_fn)
    batch = abstract_batch(FACT, M4, one_chip)
    compile_for_chip(_make_sizes_fn(), batch)
    # k and v narrowed to 32 bits, all-valid validity lanes skipped
    plan = (("narrow", 4), ("skip",), ("narrow", 4), ("skip",),
            ("none",), ("skip",))
    compile_for_chip(_make_shrink_pack_fn(M4, (), plan), batch)


def test_float64_sort_key_needs_no_bit_view(one_chip):
    """Refused before PR 21: `bitcast_convert f64 -> s64` is
    UNIMPLEMENTED in the TPU's 64-bit rewrite."""
    from spark_rapids_tpu.ops import segmented as seg
    f = jax.ShapeDtypeStruct((M1,), np.float64, sharding=one_chip)
    compile_for_chip(lambda d: seg.encode_float_ordered(jnp, d), f)


def test_argsort_pass_at_the_largest_bucket(one_chip):
    """The one sort signature every device sort is built from."""
    from spark_rapids_tpu.ops import carry
    k = jax.ShapeDtypeStruct((M4,), np.uint64, sharding=one_chip)
    pad = jax.ShapeDtypeStruct((M4,), np.uint8, sharding=one_chip)
    compile_for_chip(lambda p, w: carry.stable_argsort(jnp, [p, w], M4),
                     pad, k)


def test_compact_at_the_largest_bucket(one_chip, record_property):
    """The filter's compaction as a prefix sum and sort passes of the one
    signature (ten of them here): its compile time must stay that of one
    sort, whatever the number of lanes."""
    import time
    from spark_rapids_tpu.exec.filter_common import compact
    batch = abstract_batch(FACT + (("d", t.DATE), ("g", t.DOUBLE)), M4,
                           one_chip)
    keep = jax.ShapeDtypeStruct((M4,), np.bool_, sharding=one_chip)
    t0 = time.perf_counter()
    compiled = compile_for_chip(lambda b, k: compact(jnp, b, k, b.names),
                                batch, keep)
    seconds = time.perf_counter() - t0
    record_property("compact_4194304_compile_s", round(seconds, 1))
    print(f"compact at {M4} rows for v5e: {seconds:.1f} s")
    text = compiled.as_text()
    assert " sort(" in text and " gather(" not in text
    assert seconds < 240, "a lane took a new sort signature"


@pytest.mark.parametrize("grouping", [(), ("k",)],
                         ids=["ungrouped", "sort_arm"])
def test_mask_and_the_aggregate_under_it_at_the_largest_bucket(
        one_chip, grouping):
    """A filter directly under an aggregate: its mask program holds no
    sort and no gather, and writes one bool lane and a count; the
    aggregate takes the batch where it lay with the flags beside it."""
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.basic import FilterExec
    from spark_rapids_tpu.expr.aggregates import (COMPLETE,
                                                  AggregateExpression, Sum)
    from spark_rapids_tpu.expr.core import AttributeReference as A, Literal
    from spark_rapids_tpu.expr.predicates import GreaterThan
    flt = FilterExec(GreaterThan(A("v"), Literal(100)), _source(FACT))
    agg = TpuHashAggregateExec([A(g) for g in grouping], [
        AggregateExpression(Sum(A("f")), "sf")], COMPLETE, flt)
    flt.placement = "tpu"
    assert agg.masked_source() is flt
    batch = abstract_batch(FACT, M4, one_chip)
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(np.shape(p), np.asarray(p).dtype,
                                       sharding=one_chip), flt._params)
    mask = compile_for_chip(
        lambda b, ps: flt._compute_mask(jnp, b, params=ps), batch, params)
    text = mask.as_text()
    assert " sort(" not in text and " gather(" not in text
    out = mask.memory_analysis().output_size_in_bytes
    assert out < 2 * M4, f"more than a bool lane and a count: {out} bytes"
    keep = jax.ShapeDtypeStruct((M4,), np.bool_, sharding=one_chip)
    reduced = compile_for_chip(
        lambda b, k: agg._evaluate_batch(jnp, agg._update_batch(jnp, b, k)),
        batch, keep)
    assert (" sort(" in reduced.as_text()) == bool(grouping)


def test_exchange_and_aggregate_over_four_chips(mesh4, record_property):
    """`DistributedAggregate`'s SPMD step (partial aggregate,
    `exchange_by_pid` all_to_all, final aggregate) on a 4-device mesh of
    the described chips, one 262144-row shard each.  Its columns are flat,
    so every lane moves by sort pass, slice or contiguous copy: the
    compiled step holds no gather."""
    import time
    from spark_rapids_tpu.expr.aggregates import (AggregateExpression,
                                                  Count, Sum)
    from spark_rapids_tpu.expr.core import AttributeReference as A
    from spark_rapids_tpu.parallel import DistributedAggregate
    dagg = DistributedAggregate(
        grouping=[A("k")],
        aggregates=[AggregateExpression(Sum(A("v")), "sv"),
                    AggregateExpression(Count(None), "c")],
        in_names=[n for n, _ in FACT], in_types=[d for _, d in FACT],
        mesh=mesh4)
    stacked = abstract_batch(FACT, 262_144, NamedSharding(mesh4, P("data")),
                             lead=(4,))
    step = jax.shard_map(dagg._step, mesh=mesh4, in_specs=P("data"),
                         out_specs=P("data"), check_vma=False)
    t0 = time.perf_counter()
    compiled = compile_for_chip(step, stacked)
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    record_property("mesh_step_262144_compile_s", round(seconds, 1))
    record_property("mesh_step_262144_sorts", text.count(" sort("))
    print(f"the mesh step at 262144 rows a chip for v5e:2x2: {seconds:.1f} s,"
          f" {text.count(' sort(')} sorts")
    assert "all-to-all" in text
    assert " sort(" in text and " gather(" not in text
