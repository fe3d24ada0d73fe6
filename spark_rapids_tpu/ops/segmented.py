"""Segmented reductions + order-preserving key encodings.

The TPU replacement for cuDF's hash-based groupby (ref aggregate.scala's
cudf groupBy calls): sort rows by an order-preserving word encoding of
the keys, detect segment boundaries, then segment-reduce.  Sort+segment
maps perfectly onto XLA (lax.sort is a native TPU op) and needs no
dynamic shapes.

Kernel-structure rules learned from profiling the real chip (round 4):

* 64-bit scatters (segment_sum on int64/float64/uint64) are ~1000x the
  cost of 32-bit scatters on TPU — the X64 rewrite emulates the combiner
  with carry chains.  Every reduction here is therefore built from
  32-bit scatters, elementwise ops, gathers, and Hillis-Steele scans:
  - sums of 64-bit values go through `cumsum_fast` (pad-shift scan:
    log2(n) elementwise adds; compiles in ~2s vs ~180s for the stock
    cumsum lowering and runs at memory speed for every dtype) plus two
    boundary gathers;
  - min/max of 64-bit values run a two-pass (high word, low word)
    tournament over int32-ordered halves, then gather the winning row;
  - first/last reduce int32 positions.
* Counts are int32 scatters widened to int64 at the boundary, keeping
  the external (out, cnt:int64) contract.

All entry points take `xp` so the numpy CPU engine shares the semantics.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from .. import types as t
from ..columnar.device import DeviceColumn
from . import strings as sops
from .scan import cumsum_fast, cumprod_fast  # noqa: F401  (re-export)

_I32_MAX = np.int32(2**31 - 1)


# ---------------------------------------------------------------------------
# order-preserving encodings
# ---------------------------------------------------------------------------

def encode_int_ordered(xp, data):
    """int -> uint64 preserving order (flip sign bit)."""
    return (data.astype(xp.int64).astype(xp.uint64)
            ^ xp.uint64(0x8000000000000000))


def encode_float_ordered(xp, data):
    """float64 -> uint64 with Spark's total order (NaN last; Spark treats
    -0.0 == 0.0 in comparisons — normalize first).

    The host and XLA:CPU read the IEEE bits.  The TPU has no bit view of
    a float64 (its compiler refuses every 64-bit float bitcast: doubles
    live there as pairs of float32), so the program lowered for it orders
    by that pair instead: the nearest float32 and the float32 remainder,
    which is the whole value there and orders as the value does."""
    d = data.astype(xp.float64)
    if xp is np:
        return _float_bits_ordered(xp, d)
    from jax import lax
    return lax.platform_dependent(d, tpu=_float_split_ordered,
                                  default=lambda x: _float_bits_ordered(
                                      xp, x))


def _float_bits_ordered(xp, d):
    d = xp.where(d == 0.0, xp.zeros_like(d), d)          # -0.0 -> +0.0
    d = xp.where(xp.isnan(d), xp.full_like(d, xp.nan), d)  # canonical NaN
    bits = d.view(xp.int64) if hasattr(d, "view") else d.view(np.int64)
    neg = bits < 0
    enc = xp.where(neg, ~bits, bits | np.int64(-(2**63)))
    return enc.astype(xp.uint64)


def _float_split_ordered(d):
    import jax.numpy as jnp
    hi = d.astype(jnp.float32)
    # rounding to float32 is monotonic, so the pair orders as the value
    # does; an infinite or NaN head has no remainder
    lo = jnp.where(jnp.isfinite(hi), d - hi.astype(jnp.float64),
                   0.0).astype(jnp.float32)
    return (encode_float_ordered32(jnp, hi).astype(jnp.uint64)
            << np.uint64(32)) | \
        encode_float_ordered32(jnp, lo).astype(jnp.uint64)


def encode_int_ordered32(xp, data):
    """int (<=32 bit) -> uint32 preserving order."""
    return (data.astype(xp.int32).astype(xp.uint32) ^ xp.uint32(0x80000000))


def encode_float_ordered32(xp, data):
    """float32 -> uint32 total order (NaN last, -0 == +0)."""
    d = data.astype(xp.float32)
    d = xp.where(d == 0.0, xp.zeros_like(d), d)
    d = xp.where(xp.isnan(d), xp.full_like(d, xp.nan), d)
    bits = d.view(xp.int32) if hasattr(d, "view") else d.view(np.int32)
    neg = bits < 0
    enc = xp.where(neg, ~bits, bits | np.int32(-(2**31)))
    return enc.astype(xp.uint32)


_NARROW_INTS = (t.ByteType, t.ShortType, t.IntegerType, t.DateType)


def key_words_for_column(xp, col: DeviceColumn, live_mask,
                         for_grouping: bool = True, nulls_first: bool = True,
                         ascending: bool = True):
    """Sort-key words (most-significant first) for one column.

    Word 0 is the null indicator (bool: one bit of a sort pass's digit;
    nulls group/sort together);
    remaining words encode the value — uint32 for types that fit 32 bits
    (half the sort-comparator cost on TPU), uint64 otherwise.  Strings
    use content hashes when only grouping (equality) is needed, or
    prefix words for true ordering; a fixed-width string
    (`DeviceColumn.fixed_width`) is its own one exact word for both."""
    dtype = col.dtype
    validity = col.validity
    if validity is None:
        validity = xp.ones((col.capacity,), dtype=bool)
    null_word = validity if nulls_first else ~validity
    words = [null_word]
    if col.fixed_width is not None:
        # the value itself, big-endian: word order is byte order and word
        # equality is string equality, for grouping and for ordering alike
        words.append(col.word)
    elif isinstance(dtype, (t.StringType, t.BinaryType)):
        if for_grouping:
            h1, h2 = sops.string_hashes(xp, col.offsets, col.data)
            words += [h1, h2]
        else:
            words += sops.order_keys(xp, col.offsets, col.data)
    elif isinstance(dtype, t.FloatType):
        words.append(encode_float_ordered32(xp, col.data))
    elif isinstance(dtype, t.DoubleType):
        words.append(encode_float_ordered(xp, col.data))
    elif isinstance(dtype, t.BooleanType):
        words.append(col.data.astype(xp.uint8))
    elif isinstance(dtype, t.NullType):
        pass
    elif isinstance(dtype, t.DecimalType) and col.data_hi is not None:
        # decimal128: order by (hi signed, lo unsigned) word pair
        words.append(encode_int_ordered(xp, col.data_hi))
        words.append(col.data.astype(xp.uint64))
    elif isinstance(dtype, t.StructType):
        for ch in col.children:
            words += key_words_for_column(xp, ch, live_mask, for_grouping,
                                          nulls_first, True)
    elif isinstance(dtype, _NARROW_INTS):
        words.append(encode_int_ordered32(xp, col.data))
    else:
        words.append(encode_int_ordered(xp, col.data))
    if not ascending:
        # descending: invert value words; the null word already encodes the
        # requested nulls_first/last placement independently
        words = [words[0]] + [~w for w in words[1:]]
    return words


def lexsort(xp, key_words, capacity: int):
    """Stable ascending lexicographic argsort over key word lists
    (most-significant first): carry.stable_argsort on the device,
    np.lexsort on the host."""
    if xp is np:
        # np.lexsort: last key is primary
        return np.lexsort(tuple(reversed(key_words))).astype(np.int32)
    from .carry import stable_argsort
    return stable_argsort(xp, key_words, capacity)


# ---------------------------------------------------------------------------
# segmented reduce
# ---------------------------------------------------------------------------

def segment_boundaries(xp, sorted_words, live_sorted):
    """new_group flags over sorted rows: first live row or any key word
    differs from the previous row's."""
    n = sorted_words[0].shape[0]
    diff = xp.zeros((n,), dtype=bool)
    from .scan import differs_from_prev
    for w in sorted_words:
        diff = diff | differs_from_prev(xp, w)
    first = xp.zeros((n,), dtype=bool)
    if n > 0:
        first = xp.arange(n) == 0
    new_group = (diff | first) & live_sorted
    return new_group


def segment_ids(xp, new_group):
    if xp is np:
        return (np.cumsum(new_group.astype(np.int32), dtype=np.int32)
                - 1).astype(np.int32)
    return cumsum_fast(xp, new_group.astype(xp.int32)) - 1


def _seg_scatter_min(xp, vals_i32, seg, num_segments: int):
    import jax
    return jax.ops.segment_min(vals_i32, seg, num_segments=num_segments,
                               indices_are_sorted=False)


def _seg_scatter_max(xp, vals_i32, seg, num_segments: int):
    import jax
    return jax.ops.segment_max(vals_i32, seg, num_segments=num_segments,
                               indices_are_sorted=False)


def _park(xp, seg_ids, valid, num_segments: int):
    """Segment ids with invalid rows parked on the last slot (the 32-bit
    scatter init values make parked rows no-ops)."""
    return xp.where(valid, seg_ids, num_segments - 1).astype(xp.int32)


def _ordered_words32(xp, values, descending: bool) -> List:
    """int32-ordered word list (most-significant first) whose joint
    lexicographic order equals the value order.  1 word for <=32-bit
    dtypes, 2 words for 64-bit ones.  `descending` flips the order so a
    min-tournament computes a max."""
    dt = np.dtype(values.dtype)
    if dt.kind == "b":
        w = values.astype(xp.int32)
        return [-w] if descending else [w]
    if dt == np.float32:
        enc = encode_float_ordered32(xp, values)
        if descending:
            enc = ~enc
        return [(enc ^ xp.uint32(0x80000000)).astype(xp.int32)]
    if dt == np.float64:
        enc = encode_float_ordered(xp, values)
        if descending:
            enc = ~enc
        hi = (enc >> xp.uint64(32)).astype(xp.uint32)
        lo = enc.astype(xp.uint32)
        return [(hi ^ xp.uint32(0x80000000)).astype(xp.int32),
                (lo ^ xp.uint32(0x80000000)).astype(xp.int32)]
    if dt.itemsize <= 4:
        enc = encode_int_ordered32(xp, values)
        if descending:
            enc = ~enc
        return [(enc ^ xp.uint32(0x80000000)).astype(xp.int32)]
    enc = values.astype(xp.uint64) if dt.kind == "u" else \
        encode_int_ordered(xp, values)
    if descending:
        enc = ~enc
    hi = (enc >> xp.uint64(32)).astype(xp.uint32)
    lo = enc.astype(xp.uint32)
    return [(hi ^ xp.uint32(0x80000000)).astype(xp.int32),
            (lo ^ xp.uint32(0x80000000)).astype(xp.int32)]


def _argext_rows(xp, values, seg, num_segments: int, valid, is_min: bool):
    """Row index of the per-segment extreme value (ties -> first row),
    via a word-at-a-time int32 tournament.  Works for any seg layout."""
    words = _ordered_words32(xp, values, descending=not is_min)
    sel = valid
    iota = xp.arange(values.shape[0], dtype=xp.int32)
    for w in words:
        masked = xp.where(sel, w, _I32_MAX)
        best = _seg_scatter_min(xp, masked, seg, num_segments)
        sel = sel & (w == best[seg])
    pos = xp.where(sel, iota, _I32_MAX)
    row = _seg_scatter_min(xp, pos, seg, num_segments)
    return xp.clip(row, 0, values.shape[0] - 1).astype(xp.int32)


def masked_argext(xp, values, valid, is_min: bool):
    """Row index of the extreme value among the `valid` rows of ONE
    segment (ties -> first row; 0 where none is valid): `_argext_rows`'
    tournament over the same ordered words, so the same order of NaN and
    of -0.0, as plain reductions instead of scatters."""
    sel = valid
    for w in _ordered_words32(xp, values, descending=not is_min):
        sel = sel & (w == xp.min(xp.where(sel, w, _I32_MAX)))
    iota = xp.arange(values.shape[0], dtype=xp.int32)
    row = xp.min(xp.where(sel, iota, _I32_MAX))
    return xp.clip(row, 0, values.shape[0] - 1).astype(xp.int32)


def _counts(xp, seg, num_segments: int, valid):
    import jax
    c = jax.ops.segment_sum(valid.astype(xp.int32), seg,
                            num_segments=num_segments)
    return c.astype(xp.int64)


def segment_reduce(xp, op: str, values, seg_ids, num_segments: int, valid,
                   sorted_ids: bool = False, ctx: Optional["SegContext"] = None):
    """Reduce `values` per segment.  Returns (out[num_segments],
    count_valid[num_segments]).  op in {sum, min, max, first, last}.
    Invalid rows don't contribute.

    `sorted_ids=True` asserts seg_ids is non-decreasing over rows (true
    for every sort-then-segment caller) and unlocks the scan-based sum
    path; `ctx` shares the per-kernel segment structure across ops."""
    if xp is np:
        cnt = np.zeros((num_segments,), np.int64)
        np.add.at(cnt, seg_ids[valid], 1)
        if op == "sum":
            out = np.zeros((num_segments,), values.dtype)
            np.add.at(out, seg_ids[valid], values[valid])
        elif op == "min" or op == "max":
            init = _extreme_init(np, values.dtype, op == "min")
            out = np.full((num_segments,), init, values.dtype)
            fn = np.minimum if op == "min" else np.maximum
            fn.at(out, seg_ids[valid], values[valid])
        elif op in ("first", "last"):
            idx = np.full((num_segments,),
                          2**31 - 1 if op == "first" else -1, np.int64)
            pos = np.arange(values.shape[0], dtype=np.int64)
            (np.minimum if op == "first" else np.maximum).at(
                idx, seg_ids[valid], pos[valid])
            safe = np.clip(idx, 0, values.shape[0] - 1).astype(np.int64)
            out = values[safe]
        else:
            raise ValueError(op)
        return out, cnt

    # jax path — 32-bit scatters / scans only
    seg = _park(xp, seg_ids, valid, num_segments)
    cnt = ctx.counts_for(xp, seg, valid) if ctx is not None else \
        _counts(xp, seg, num_segments, valid)
    if op == "sum":
        dt = np.dtype(values.dtype)
        if dt.itemsize <= 4:
            import jax
            out = jax.ops.segment_sum(
                xp.where(valid, values, xp.zeros_like(values)), seg,
                num_segments=num_segments)
            return out, cnt
        vals0 = xp.where(valid, values, xp.zeros_like(values))
        if sorted_ids or ctx is not None:
            is_float = dt.kind == "f"
            if is_float:
                # prefix-sum differencing would let one segment's inf/nan
                # poison every later segment (inf - inf = nan).  Scan only
                # the finite values and rebuild IEEE addition semantics
                # from per-segment flags (int32 scatter-max is free).
                finite = xp.isfinite(vals0)
                scan_vals = xp.where(finite, vals0, xp.zeros_like(vals0))
                flag = xp.where(
                    valid & xp.isnan(values), xp.int32(4),
                    xp.where(valid & (values == xp.inf), xp.int32(1),
                             xp.where(valid & (values == -xp.inf),
                                      xp.int32(2), xp.int32(0))))
                has_pi = _seg_scatter_max(
                    xp, (flag == 1).astype(xp.int32), seg, num_segments)
                has_ni = _seg_scatter_max(
                    xp, (flag == 2).astype(xp.int32), seg, num_segments)
                has_nan = _seg_scatter_max(
                    xp, (flag == 4).astype(xp.int32), seg, num_segments)
            else:
                scan_vals = vals0
            cs = cumsum_fast(xp, scan_vals)
            iota = xp.arange(values.shape[0], dtype=xp.int32)
            if ctx is not None:
                # ctx start/end bracket every live row of the segment;
                # vals0 is masked to this op's own validity, so the span
                # sum is exact for any valid subset of live rows
                sp, ep = ctx.startpos, ctx.endpos
            else:
                sp = _seg_scatter_min(
                    xp, xp.where(valid, iota, _I32_MAX), seg, num_segments)
                ep = _seg_scatter_max(
                    xp, xp.where(valid, iota, -_I32_MAX), seg, num_segments)
            spc = xp.clip(sp, 0, values.shape[0] - 1)
            epc = xp.clip(ep, 0, values.shape[0] - 1)
            out = cs[epc] - cs[spc] + scan_vals[spc]
            if is_float:
                out = xp.where(has_nan + (has_pi & has_ni) > 0,
                               xp.full_like(out, xp.nan), out)
                out = xp.where((has_pi > 0) & (has_ni == 0) & (has_nan == 0),
                               xp.full_like(out, xp.inf), out)
                out = xp.where((has_ni > 0) & (has_pi == 0) & (has_nan == 0),
                               xp.full_like(out, -xp.inf), out)
            out = xp.where(cnt > 0, out, xp.zeros_like(out))
            return out, cnt
        # unsorted 64-bit sum: emulated scatter (rare; only reached by
        # callers that didn't sort — every engine path sorts first)
        import jax
        out = jax.ops.segment_sum(vals0, seg, num_segments=num_segments)
        return out, cnt
    if op in ("min", "max"):
        row = _argext_rows(xp, values, seg, num_segments, valid,
                           is_min=(op == "min"))
        return values[row], cnt
    if op in ("first", "last"):
        iota = xp.arange(values.shape[0], dtype=xp.int32)
        if op == "first":
            pos = xp.where(valid, iota, _I32_MAX)
            idx = _seg_scatter_min(xp, pos, seg, num_segments)
        else:
            pos = xp.where(valid, iota, -_I32_MAX)
            idx = _seg_scatter_max(xp, pos, seg, num_segments)
        safe = xp.clip(idx, 0, values.shape[0] - 1).astype(xp.int32)
        return values[safe], cnt
    raise ValueError(op)


class SegContext:
    """Per-kernel segment structure shared across segment_reduce calls:
    start/end row positions per slot and a per-validity-mask count cache.
    Valid for sorted seg_ids only (rows of a segment contiguous)."""

    def __init__(self, startpos, endpos, live_sorted):
        self.startpos = startpos
        self.endpos = endpos
        self._live = live_sorted
        self._cnt_cache: dict = {}

    def matches(self, valid) -> bool:
        return valid is self._live

    def counts_for(self, xp, seg, valid):
        # cache retains the mask: a bare id() key could alias a NEW mask
        # after a temporary is collected (np engine path)
        key = id(valid)
        hit = self._cnt_cache.get(key)
        if hit is not None and hit[0] is valid:
            return hit[1]
        cnt = _counts(xp, seg, self.startpos.shape[0], valid)
        self._cnt_cache[key] = (valid, cnt)
        return cnt


def build_segment_ctx(xp, seg_ids, num_segments: int, live_sorted):
    """Shared (startpos, endpos) per slot for a sorted segment layout."""
    iota = xp.arange(seg_ids.shape[0], dtype=xp.int32)
    seg = _park(xp, seg_ids, live_sorted, num_segments)
    sp = _seg_scatter_min(xp, xp.where(live_sorted, iota, _I32_MAX),
                          seg, num_segments)
    ep = _seg_scatter_max(xp, xp.where(live_sorted, iota, -_I32_MAX),
                          seg, num_segments)
    return SegContext(sp, ep, live_sorted)


def segment_sum128(xp, lo, hi, seg_ids, num_segments: int, valid,
                   sorted_ids: bool = False):
    """128-bit segmented sum over (lo: int64 bit-pattern of the unsigned
    low word, hi: int64 high word) columns.  Carries propagate through
    32-bit partial sums, so per-segment row counts up to 2^31 are exact.
    Returns (lo_out, hi_out, count_valid)."""
    mask32 = xp.uint64(0xFFFFFFFF)
    lo_u = lo.astype(xp.uint64)
    lo32 = lo_u & mask32
    hi32 = (lo_u >> xp.uint64(32)) & mask32
    zero_u = xp.zeros((), xp.uint64)
    lo32 = xp.where(valid, lo32, zero_u)
    hi32 = xp.where(valid, hi32, zero_u)
    hi_v = xp.where(valid, hi, xp.zeros_like(hi))
    if xp is np:
        seg = np.where(valid, seg_ids, num_segments - 1)
        s0 = np.zeros((num_segments,), np.uint64)
        s1 = np.zeros((num_segments,), np.uint64)
        sh = np.zeros((num_segments,), np.int64)
        cnt = np.zeros((num_segments,), np.int64)
        np.add.at(s0, seg_ids[valid], lo32[valid])
        np.add.at(s1, seg_ids[valid], hi32[valid])
        np.add.at(sh, seg_ids[valid], hi_v[valid])
        np.add.at(cnt, seg_ids[valid], 1)
    else:
        # one shared (startpos, endpos) pair serves all three word sums;
        # the span-based fast path is only valid for contiguous segments
        ctx = build_segment_ctx(xp, seg_ids, num_segments, valid) \
            if sorted_ids else None
        s0, cnt = segment_reduce(xp, "sum", lo32, seg_ids, num_segments,
                                 valid, sorted_ids=sorted_ids, ctx=ctx)
        s1, _ = segment_reduce(xp, "sum", hi32, seg_ids, num_segments,
                               valid, sorted_ids=sorted_ids, ctx=ctx)
        sh, _ = segment_reduce(xp, "sum", hi_v, seg_ids, num_segments,
                               valid, sorted_ids=sorted_ids, ctx=ctx)
    low32 = s0 & mask32
    c0 = s0 >> xp.uint64(32)
    tmid = s1 + c0
    high32 = tmid & mask32
    c1 = (tmid >> xp.uint64(32)).astype(xp.int64)
    lo_out = (low32 | (high32 << xp.uint64(32))).astype(xp.int64)
    hi_out = sh + c1
    return lo_out, hi_out, cnt


def _extreme_init(xp, dtype, is_min: bool):
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return np.array(np.inf if is_min else -np.inf, dt)
    if dt.kind == "b":
        return np.array(True if is_min else False, dt)
    info = np.iinfo(dt)
    return np.array(info.max if is_min else info.min, dt)


def first_index_per_segment(xp, seg_ids, num_segments: int, live,
                            ctx: Optional[SegContext] = None):
    """Index of the first row of each segment (for gathering group keys)."""
    if xp is np:
        pos = np.arange(seg_ids.shape[0], dtype=np.int64)
        idx = np.full((num_segments,), 2**31 - 1, np.int64)
        np.minimum.at(idx, seg_ids[live], pos[live])
        return np.clip(idx, 0, seg_ids.shape[0] - 1).astype(np.int32)
    if ctx is not None and ctx.matches(live):
        return xp.clip(ctx.startpos, 0, seg_ids.shape[0] - 1)
    iota = xp.arange(seg_ids.shape[0], dtype=xp.int32)
    seg = _park(xp, seg_ids, live, num_segments)
    idx = _seg_scatter_min(xp, xp.where(live, iota, _I32_MAX), seg,
                           num_segments)
    return xp.clip(idx, 0, seg_ids.shape[0] - 1).astype(xp.int32)
