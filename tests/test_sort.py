"""Sort differential tests (model: integration_tests/sort_test.py)."""

import numpy as np
import pytest

from spark_rapids_tpu.api.column import col
from spark_rapids_tpu.testing.asserts import (
    assert_tpu_and_cpu_are_equal_collect)
from spark_rapids_tpu.testing.data_gen import (
    DoubleGen, IntegerGen, LongGen, StringGen, gen_df)


def test_sort_int_asc():
    def q(spark):
        df = gen_df(spark, [("a", IntegerGen()), ("b", LongGen())],
                    length=512)
        return df.order_by(col("a"), col("b"))
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=False)


def test_sort_desc_and_nulls():
    def q(spark):
        df = gen_df(spark, [("a", IntegerGen(null_prob=0.3)),
                            ("b", LongGen())], length=512)
        return df.order_by(col("a").desc(), col("b").asc())
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=False)


def test_sort_doubles_with_nan():
    def q(spark):
        df = gen_df(spark, [("d", DoubleGen()), ("x", IntegerGen())],
                    length=512)
        return df.order_by(col("d"), col("x"))
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=False)


def test_sort_strings():
    def q(spark):
        df = gen_df(spark, [("s", StringGen(max_len=10)),
                            ("x", IntegerGen())], length=512)
        return df.order_by(col("s"), col("x"))
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=False)


def test_sort_multi_partition_global():
    def q(spark):
        df = gen_df(spark, [("a", IntegerGen()), ("b", LongGen())],
                    length=1024, num_partitions=4)
        return df.order_by(col("a"), col("b"))
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=False)


# -- the sort primitive -------------------------------------------------------

def _lean_words(rng, n):
    dup = rng.integers(0, 2**63, n, dtype=np.uint64)
    dup[::7] = dup[0]
    neg = rng.integers(-2**62, 2**62, n).astype(np.int64)
    neg[::5] = -1
    return {
        "u8": [rng.integers(0, 3, n).astype(np.uint8)],
        "u64": [dup * np.uint64(2) + np.uint64(1)],
        "u8_u64": [rng.integers(0, 2, n).astype(np.uint8), dup],
        "i32": [rng.integers(-5, 5, n).astype(np.int32)],
        "i64_u8": [neg, rng.integers(0, 3, n).astype(np.uint8)],
        "bool_i32_u64": [rng.integers(0, 2, n).astype(bool),
                         rng.integers(-2, 2, n).astype(np.int32), dup],
        "no_words": [],
        # one bool word is a stable partition: its rank is a prefix sum
        "bool": [rng.integers(0, 3, n) == 0],
        # the grouped aggregate's key (live flag, null flag, int64): two
        # packed digits, the second wider than one operand
        "bool_bool_i64": [rng.integers(0, 9, n) == 0,
                          rng.integers(0, 5, n) == 0, neg // 2**40],
        "u16_i64_i64": [rng.integers(0, 4, n).astype(np.uint16), neg,
                        neg[::-1].copy()],
    }


@pytest.mark.parametrize("case", ["u8", "u64", "u8_u64", "i32", "i64_u8",
                                  "bool_i32_u64", "no_words", "bool",
                                  "bool_bool_i64", "u16_i64_i64"])
@pytest.mark.parametrize("n", [3000, 4096])
def test_stable_argsort_is_numpys_stable_lexsort(case, n):
    """Every device sort in the engine goes through this: a radix sort of
    (uint32, int32) passes over packed digits.  At a power of two the
    tie-break fills its `pos_bits` to the last one (row 4095 is all
    ones under the mask that takes it out of a two-operand digit)."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import carry
    words = _lean_words(np.random.default_rng(5), n)[case]
    want = np.lexsort(tuple(reversed(words))).astype(np.int32) \
        if words else np.arange(n, dtype=np.int32)
    got = jax.jit(lambda *ws: carry.stable_argsort(jnp, list(ws), n))(
        *[jnp.asarray(w) for w in words])
    assert (np.asarray(got) == want).all()


def test_stable_argsort_refuses_float_words():
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import carry
    with pytest.raises(TypeError, match="integers"):
        carry.stable_argsort(jnp, [jnp.ones(4)], 4)


def test_float64_keys_order_without_a_bit_view():
    """The TPU lowering of encode_float_ordered orders by the (float32,
    remainder) pair; it must order as the IEEE-bit encoding does."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import segmented as seg
    rng = np.random.default_rng(3)
    x = np.concatenate([
        rng.standard_normal(2000) * 10.0 ** rng.integers(-30, 30, 2000),
        [0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, 1.0 + 2**-40,
         -1.0 - 2**-40, 3.0e38, -3.0e38]])
    bits = seg.encode_float_ordered(np, x)
    split = np.asarray(seg._float_split_ordered(jnp.asarray(x)))
    # the split is as fine as a float32 pair: it never inverts an order
    # and it tells apart what a float32 pair tells apart
    order = np.argsort(bits, kind="stable")
    assert (np.diff(split[order].astype(np.float64)) >= 0).all()
    zero, neg_zero, inf, _, nan, one, one_up = split[2000:2007]
    assert zero == neg_zero
    assert nan > inf                        # NaN sorts last
    assert one < one_up                     # 1.0 < 1.0 + 2**-40


@pytest.mark.parametrize("dtype", [np.uint64, np.float64, np.bool_])
def test_one_place_shifts_match_the_concatenate_idiom(dtype):
    """ops/scan's shifts replace `concatenate([fill, v[:-1]])`, which the
    v5e compiler miscompiled inside a fused program (PR 21, chip)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import scan
    v = (np.random.default_rng(2).integers(0, 3, 257) > 0).astype(dtype) \
        if dtype is np.bool_ else \
        np.random.default_rng(2).integers(0, 5, 257).astype(dtype)
    for xp, arr in ((np, v), (jnp, jnp.asarray(v))):
        assert np.array_equal(
            np.asarray(scan.shift_right(xp, arr, 1)),
            np.concatenate([np.ones(1, dtype), v[:-1]]))
        assert np.array_equal(
            np.asarray(scan.shift_left(xp, arr)),
            np.concatenate([v[1:], np.zeros(1, dtype)]))
        assert np.array_equal(
            np.asarray(scan.differs_from_prev(xp, arr)),
            np.concatenate([[False], v[1:] != v[:-1]]))
