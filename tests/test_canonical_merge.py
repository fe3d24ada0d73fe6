"""The merge's canonical order (`TpuHashAggregateExec.stable_merge`): the
partial buffers reach `_group_reduce` sorted by key and buffer words, so a
float sum folds in an order the content decides and not the arrival.

`_canonicalize_merge_input` moves the rows with the sort (`carry.sort_rows`);
until PR 28 it sorted for an order and gathered every lane by it.  The
tests hold the new rows to that formulation bit for bit, on the device and
in the NumPy engine, and the merged sums to one set of bits whatever order
the partials arrive in."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.device import DeviceBatch, batch_to_device
from spark_rapids_tpu.columnar.interop import from_arrow_type
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.concat import concat_batches
from spark_rapids_tpu.expr.aggregates import (FINAL, PARTIAL,
                                              AggregateExpression,
                                              CollectList, Sum)
from spark_rapids_tpu.expr.core import AttributeReference as A
from spark_rapids_tpu.ops import segmented as seg
from spark_rapids_tpu.ops.gather import gather_batch
from spark_rapids_tpu.parallel.distributed import _SchemaSource

ENGINES = {"device": jnp, "numpy": np}
N_ROWS = 360        # three chunks of partials in the 1024-row bucket


def _table(key: str, buffer: str, nulls: bool, rng) -> pa.Table:
    ks = rng.integers(0, 40, N_ROWS)
    if key == "string":
        keys = pa.array([None if nulls and k == 7 else f"k{k % 13}" * (1 + k % 3)
                         for k in ks], type=pa.string())
    else:
        keys = pa.array([None if nulls and k == 7 else int(k) * 10**10
                         for k in ks], type=pa.int64())
    if buffer == "sum_double":
        vs = rng.standard_normal(N_ROWS) * 10.0 ** rng.integers(-8, 16,
                                                                N_ROWS)
        vs[:6] = [1e16, -1e16, 1.0, np.inf, -0.0, 0.0]
        vals = pa.array([None if nulls and i % 9 == 0 else float(v)
                         for i, v in enumerate(rng.permutation(vs))],
                        type=pa.float64())
    else:
        vals = pa.array([None if nulls and i % 9 == 0 else int(v)
                         for i, v in enumerate(
                             rng.integers(-2**40, 2**40, N_ROWS))],
                        type=pa.int64())
    return pa.table({"k": keys, "v": vals})


def _stages(table: pa.Table, buffer: str):
    fn = CollectList(A("v")) if buffer == "collect_list" else Sum(A("v"))
    src = _SchemaSource(table.column_names,
                        [from_arrow_type(f.type) for f in table.schema])
    partial = TpuHashAggregateExec([A("k")], [AggregateExpression(fn, "a")],
                                   PARTIAL, src)
    final = TpuHashAggregateExec([A("k")], partial.aggregates, FINAL,
                                 partial)
    return partial, final


def _partials(xp, partial, table: pa.Table, chunks=3):
    per = table.num_rows // chunks
    out = []
    for i in range(chunks):
        rb = table.slice(i * per, per).combine_chunks().to_batches()[0]
        out.append(partial._update_batch(xp, batch_to_device(rb, xp=xp)))
    return out


def _concat(xp, partial, batches) -> DeviceBatch:
    return concat_batches(xp, batches, partial.output_names,
                          partial.output_types)


def _gathered_by_the_order(final, xp, batch: DeviceBatch) -> DeviceBatch:
    """The canonical order as PR 27 made it: an order from the words,
    then every lane gathered by it."""
    cap = batch.capacity
    live = xp.arange(cap, dtype=np.int32) < batch.num_rows
    words = [(~live).astype(xp.uint64)]
    k = len(final.grouping)
    for kc in batch.columns[:k]:
        words += seg.key_words_for_column(xp, kc, live, for_grouping=True)
    for vc in batch.columns[k:]:
        try:
            words += seg.key_words_for_column(xp, vc, live,
                                              for_grouping=True)
        except Exception:
            continue
    order = seg.lexsort(xp, words, cap)
    return gather_batch(xp, batch, order, live[order], batch.num_rows)


def _bits(x):
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}") if x.dtype.kind == "f" else x


def assert_same_bits(got: DeviceBatch, want: DeviceBatch):
    got_leaves, got_tree = jax.tree_util.tree_flatten(got)
    want_leaves, want_tree = jax.tree_util.tree_flatten(want)
    assert got_tree == want_tree
    for g, w in zip(got_leaves, want_leaves):
        assert np.asarray(g).dtype == np.asarray(w).dtype
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("nulls", [False, True], ids=["no_nulls", "nulls"])
@pytest.mark.parametrize("buffer", ["sum_double", "sum_long",
                                    "collect_list"])
@pytest.mark.parametrize("key", ["int64", "string"])
@pytest.mark.parametrize("engine", list(ENGINES))
def test_rows_equal_the_order_and_gather(engine, key, buffer, nulls):
    """Every lane of the canonical batch, padding and nulls included,
    equals the lexsort-then-gather it replaces."""
    xp = ENGINES[engine]
    rng = np.random.default_rng([len(key), len(buffer), int(nulls)])
    table = _table(key, buffer, nulls, rng)
    partial, final = _stages(table, buffer)
    batch = _concat(xp, partial, _partials(xp, partial, table))
    assert int(batch.num_rows) < batch.capacity      # a batch with padding
    if xp is np:
        got = final._canonicalize_merge_input(np, batch)
        want = _gathered_by_the_order(final, np, batch)
    else:
        got = jax.jit(
            lambda b: final._canonicalize_merge_input(jnp, b))(batch)
        want = jax.jit(
            lambda b: _gathered_by_the_order(final, jnp, b))(batch)
    assert_same_bits(got, want)


ARRIVALS = [p for p in itertools.permutations(range(3)) if p != (0, 1, 2)]


@pytest.mark.parametrize("arrival", ARRIVALS[:4],
                         ids=lambda p: "".join(map(str, p)))
@pytest.mark.parametrize("key", ["int64", "string"])
@pytest.mark.parametrize("engine", list(ENGINES))
def test_merged_float_sum_is_the_same_bits_in_any_arrival_order(
        engine, key, arrival):
    """What `stable_merge` is for: partials that arrive shuffled fold to
    the sums of the first arrival order, in their last bit."""
    xp = ENGINES[engine]
    rng = np.random.default_rng([len(key), 28])
    table = _table(key, "sum_double", True, rng)
    partial, final = _stages(table, "sum_double")
    parts = _partials(xp, partial, table)

    def merged(order):
        batch = _concat(xp, partial, [parts[i] for i in order])
        if xp is np:
            return final._merge_batch(np, batch)
        return jax.jit(lambda b: final._merge_batch(jnp, b))(batch)
    assert_same_bits(merged(arrival), merged((0, 1, 2)))
