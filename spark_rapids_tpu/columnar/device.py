"""Device-resident columnar data (the TPU analog of GpuColumnVector).

Re-design of the reference's L1 columnar layer
(ref: sql-plugin/src/main/java/com/nvidia/spark/rapids/GpuColumnVector.java)
for XLA's compilation model:

* A `DeviceColumn` is a pytree of JAX arrays padded to a static *capacity*
  bucket; the batch's true row count travels as a traced int32 scalar.
  XLA therefore compiles each operator once per (schema, capacity bucket),
  never per row count — the TPU answer to cuDF's dynamic-size kernels.
* Null handling: a bool `validity` lane per column; data under a null is
  canonical zero.  Rows at index >= num_rows are padding: validity False.
* Strings/binary are (offsets:int32[cap+1], data:uint8[char_cap]) tensors.
* DECIMAL(p<=18) is int64 unscaled values; (p<=38) adds a `data_hi` lane.
* ARRAY adds an offsets lane over a child column; STRUCT holds children.

Everything registers with jax.tree_util so batches flow through jit/shard_map
transparently.
"""

from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import types as t
from .interop import from_arrow_type, to_arrow_type

DEFAULT_ROW_BUCKETS = (1024, 8192, 65536, 262144, 1048576, 4194304)
DEFAULT_CHAR_BUCKETS = (16384, 131072, 1048576, 8388608, 67108864, 268435456)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= n; beyond the largest, round up to a power of two."""
    n = max(int(n), 1)
    for b in buckets:
        if n <= b:
            return b
    return 1 << math.ceil(math.log2(n))


def bucket_floor(target: int, buckets: Sequence[int]) -> int:
    """Largest bucket <= target; below the smallest, the smallest bucket.
    The dual of ``bucket_for``: sizing DOWN to a capacity that fits a
    budget (sort's spill chunk sizing, the TPU-L018 re-bucket repair)
    instead of UP to one that fits the data."""
    target = int(target)
    floor = buckets[0]
    for b in buckets:
        if b <= target:
            floor = b
    return floor


#: the widest string that is held as one row-aligned word
FIXED_WIDTH_MAX = 4
_WORD_DTYPES = {1: np.uint8, 2: np.uint16, 3: np.uint32, 4: np.uint32}


def fixed_word_dtype(width: int):
    """The unsigned integer that holds a fixed-width string of `width`
    bytes as one big-endian value."""
    return _WORD_DTYPES[width]


def fixed_word_bytes(word, width: int):
    """uint8[cap * width]: the bytes of a fixed-width string's word lane,
    row after row (the general layout's char buffer)."""
    if width == 1:
        return word
    xp = np if isinstance(word, np.ndarray) else jnp
    dt = fixed_word_dtype(width)
    parts = [(word >> dt(8 * (width - 1 - k))).astype(np.uint8)
             for k in range(width)]
    return xp.stack(parts, axis=1).reshape(-1)


def fixed_word_from_bytes(xp, chars, width: int):
    """The inverse: a word lane from `cap * width` bytes."""
    if width == 1:
        return chars
    dt = fixed_word_dtype(width)
    b = chars.reshape(-1, width).astype(dt)
    word = b[:, 0]
    for k in range(1, width):
        word = (word << dt(8)) | b[:, k]
    return word


class DeviceColumn:
    """One column of device data.  A pytree; static aux is the SQL dtype
    (and, for a fixed-width string, its byte width).

    **Fixed-width strings.**  A string or binary column whose every value
    has the same byte width w, 1 <= w <= `FIXED_WIDTH_MAX`, and no null
    (seen from the Arrow offsets at upload) is held as ONE row-aligned
    lane, `word`: the value itself as a big-endian unsigned integer
    (uint8 for w = 1, uint16 for 2, uint32 for 3 and 4), so that word
    order is byte order and word equality is string equality.  Its
    `fixed_width` is w (static), it stores no offsets (they are
    `arange * w`) and it moves through sorts and compactions like any
    other 32-bit word (`ops/carry.py`).  Code that knows only the
    general layout reads `data` and `offsets` and gets them, computed
    from the word: every row r is the span [r*w, (r+1)*w), padding rows
    too (their bytes are zero and their validity false).  A null that an
    operator makes later (validity false) keeps its w zero bytes."""

    __slots__ = ("dtype", "_data", "validity", "_offsets", "data_hi",
                 "children", "fixed_width")

    def __init__(self, dtype: t.DataType, data=None, validity=None,
                 offsets=None, data_hi=None,
                 children: Tuple["DeviceColumn", ...] = (),
                 fixed_width: Optional[int] = None):
        self.dtype = dtype
        self._data = data
        self.validity = validity
        self._offsets = offsets
        self.data_hi = data_hi
        self.children = tuple(children)
        self.fixed_width = fixed_width

    @classmethod
    def fixed_string(cls, dtype: t.DataType, word, validity,
                     width: int) -> "DeviceColumn":
        """A fixed-width string column from its word lane."""
        return cls(dtype, data=word, validity=validity, fixed_width=width)

    # -- the general string layout, stored or computed -----------------------
    @property
    def data(self):
        if self.fixed_width is None:
            return self._data
        return fixed_word_bytes(self._data, self.fixed_width)

    @property
    def offsets(self):
        if self.fixed_width is None:
            return self._offsets
        xp = np if isinstance(self._data, np.ndarray) else jnp
        return xp.arange(int(self._data.shape[0]) + 1,
                         dtype=np.int32) * np.int32(self.fixed_width)

    def with_word(self, word, validity) -> "DeviceColumn":
        """This fixed-width string with another word lane and validity."""
        return DeviceColumn(self.dtype, data=word, validity=validity,
                            fixed_width=self.fixed_width)

    @property
    def word(self):
        """The row-aligned lane of a fixed-width string (None otherwise)."""
        return None if self.fixed_width is None else self._data

    def stored_lanes(self):
        """(kind, lane) of the four lanes as they are STORED, in the
        tree_flatten leaf order; a lane that is absent is None.  A
        fixed-width string's are its word and its validity."""
        return (("data", self._data), ("validity", self.validity),
                ("offsets", self._offsets), ("hi", self.data_hi))

    @property
    def has_offsets(self) -> bool:
        """Whether the column STORES offsets, so that its rows cannot ride
        a row permutation as lanes (a fixed-width string stores none)."""
        return self._offsets is not None

    # -- pytree -------------------------------------------------------------
    def tree_flatten(self):
        leaves = (self._data, self.validity, self._offsets, self.data_hi,
                  self.children)
        aux = self.dtype if self.fixed_width is None else \
            (self.dtype, self.fixed_width)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        data, validity, offsets, data_hi, children = leaves
        dtype, width = aux if isinstance(aux, tuple) else (aux, None)
        return cls(dtype, data, validity, offsets, data_hi, children, width)

    # -- properties ---------------------------------------------------------
    @property
    def capacity(self) -> int:
        if self._data is not None and (
                self.fixed_width is not None or
                not isinstance(self.dtype, (t.StringType, t.BinaryType))):
            return int(self._data.shape[0])
        if self._offsets is not None:
            return int(self._offsets.shape[0]) - 1
        if self.validity is not None:
            return int(self.validity.shape[0])
        raise ValueError("empty column")

    def row_mask(self, num_rows) -> jnp.ndarray:
        return jnp.arange(self.capacity, dtype=jnp.int32) < num_rows

    def __repr__(self):
        fixed = "" if self.fixed_width is None else \
            f", fixed_width={self.fixed_width}"
        return f"DeviceColumn({self.dtype.name}, cap={self.capacity}{fixed})"


jax.tree_util.register_pytree_node(
    DeviceColumn, DeviceColumn.tree_flatten, DeviceColumn.tree_unflatten)


class DeviceBatch:
    """A batch of device columns + traced row count (analog of ColumnarBatch
    over GpuColumnVector, ref GpuColumnVector.java / ColumnarBatch)."""

    __slots__ = ("columns", "num_rows", "names")

    def __init__(self, columns: Sequence[DeviceColumn], num_rows,
                 names: Optional[Sequence[str]] = None):
        self.columns = tuple(columns)
        if isinstance(num_rows, (int, np.integer)):
            num_rows = np.int32(num_rows)
        self.num_rows = num_rows
        self.names = tuple(names) if names is not None else tuple(
            f"c{i}" for i in range(len(self.columns)))

    def tree_flatten(self):
        return (self.columns, self.num_rows), self.names

    @classmethod
    def tree_unflatten(cls, names, leaves):
        columns, num_rows = leaves
        return cls(columns, num_rows, names)

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        if not self.columns:
            return 0
        return self.columns[0].capacity

    @property
    def dtypes(self) -> List[t.DataType]:
        return [c.dtype for c in self.columns]

    def row_mask(self) -> jnp.ndarray:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    def column(self, i: int) -> DeviceColumn:
        return self.columns[i]

    def with_columns(self, columns, names=None) -> "DeviceBatch":
        return DeviceBatch(columns, self.num_rows,
                           names if names is not None else None)

    def __repr__(self):
        return (f"DeviceBatch(cap={self.capacity}, cols="
                f"{[c.dtype.name for c in self.columns]})")


jax.tree_util.register_pytree_node(
    DeviceBatch, DeviceBatch.tree_flatten, DeviceBatch.tree_unflatten)


# ---------------------------------------------------------------------------
# capacity shrink (the TPU-L018 speculative re-bucket)
# ---------------------------------------------------------------------------

def shrink_column(col: DeviceColumn, cap: int) -> DeviceColumn:
    """Slice the leading `cap` rows of a column's row-dimension arrays
    (static shapes: `cap` is a Python int known at trace time).  Only
    sound when the live rows sit at the front (a compacted filter
    output) and their count is <= cap — the caller guards that with the
    speculation machinery.  Char data and span children keep their own
    capacities (they are byte/element-bucketed, not row-bucketed)."""
    dtype = col.dtype
    validity = None if col.validity is None else col.validity[:cap]
    if col.fixed_width is not None:
        return col.with_word(col.word[:cap], validity)
    if isinstance(dtype, (t.StringType, t.BinaryType)):
        return DeviceColumn(dtype, data=col.data, validity=validity,
                            offsets=col.offsets[:cap + 1])
    if isinstance(dtype, (t.ArrayType, t.MapType)):
        return DeviceColumn(dtype, validity=validity,
                            offsets=col.offsets[:cap + 1],
                            children=col.children)
    if isinstance(dtype, t.StructType):
        return DeviceColumn(dtype, validity=validity,
                            children=tuple(shrink_column(c, cap)
                                           for c in col.children))
    return DeviceColumn(
        dtype,
        data=None if col.data is None else col.data[:cap],
        validity=validity,
        data_hi=None if col.data_hi is None else col.data_hi[:cap])


def shrink_batch(batch: DeviceBatch, cap: int) -> DeviceBatch:
    """Re-bucket a batch DOWN to row capacity `cap` by slicing every
    column's leading rows.  num_rows rides along unchanged (still the
    traced live count); correctness requires num_rows <= cap, which the
    caller asserts via a speculation guard (exec/base.py
    SpeculativeSizingMiss re-executes on a missed guess)."""
    if cap >= batch.capacity:
        return batch
    return DeviceBatch([shrink_column(c, cap) for c in batch.columns],
                       batch.num_rows, batch.names)


# ---------------------------------------------------------------------------
# Host (Arrow) -> device
# ---------------------------------------------------------------------------

def _np_pad(arr: np.ndarray, cap: int, fill=0) -> np.ndarray:
    n = arr.shape[0]
    if n == cap:
        return arr
    out = np.full((cap,), fill, dtype=arr.dtype)
    out[:n] = arr
    return out


def _valid_np(arr: pa.Array) -> np.ndarray:
    if arr.null_count == 0:
        return np.ones(len(arr), dtype=np.bool_)
    return np.asarray(arr.is_valid())


def _decimal_unscaled(arr: pa.Array) -> Tuple[np.ndarray, np.ndarray]:
    """Extract (lo:int64, hi:int64) unscaled little-endian halves of a
    decimal128 array directly from its buffer."""
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    buf = arr.buffers()[1]
    raw = np.frombuffer(buf, dtype=np.int64,
                        count=2 * (len(arr) + arr.offset))
    raw = raw.reshape(-1, 2)[arr.offset:arr.offset + len(arr)]
    lo = raw[:, 0].copy()
    hi = raw[:, 1].copy()
    return lo, hi


def _uniform_width(offs: np.ndarray, n: int) -> Optional[int]:
    """The byte width every one of `n` strings has, if they all have the
    same one and it fits a word (1..FIXED_WIDTH_MAX); else None."""
    if n == 0:
        return None
    w = int(offs[1] - offs[0])
    if not 1 <= w <= FIXED_WIDTH_MAX or int(offs[n]) != n * w:
        return None
    if not np.array_equal(offs[:n + 1],
                          np.arange(n + 1, dtype=offs.dtype) * w):
        return None
    return w


def column_to_device(arr: pa.Array, dtype: t.DataType, cap: int,
                     char_buckets: Sequence[int] = DEFAULT_CHAR_BUCKETS,
                     xp=jnp, fixed_width_strings: bool = True
                     ) -> DeviceColumn:
    """One Arrow array as a device column of capacity `cap`.  A string or
    binary array without nulls whose values all have one byte width of
    1..FIXED_WIDTH_MAX becomes a fixed-width column (`DeviceColumn`'s
    docstring); `fixed_width_strings=False` keeps the general layout (the
    tests hold the two to each other)."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    n = len(arr)
    validity = xp.asarray(_np_pad(_valid_np(arr), cap, False))

    if isinstance(dtype, (t.StringType, t.BinaryType)):
        target = pa.large_binary() if isinstance(dtype, t.BinaryType) else pa.large_string()
        sarr = arr.cast(target)
        if sarr.null_count:
            sarr = sarr.fill_null(b"" if isinstance(dtype, t.BinaryType) else "")
        bufs = sarr.buffers()
        offs64 = np.frombuffer(bufs[1], dtype=np.int64,
                               count=n + 1 + sarr.offset)[sarr.offset:]
        base = offs64[0]
        offs = (offs64 - base).astype(np.int32)
        nbytes = int(offs[-1])
        if bufs[2] is not None:
            chars = np.frombuffer(bufs[2], dtype=np.uint8,
                                  count=base + nbytes)[base:]
        else:
            chars = np.zeros(0, dtype=np.uint8)
        width = _uniform_width(offs, n) \
            if fixed_width_strings and not arr.null_count else None
        if width is not None:
            word = fixed_word_from_bytes(np, chars[:nbytes], width)
            return DeviceColumn.fixed_string(
                dtype, xp.asarray(_np_pad(word, cap)), validity, width)
        char_cap = bucket_for(max(nbytes, 1), char_buckets)
        offs_p = np.full((cap + 1,), offs[-1] if n else 0, dtype=np.int32)
        offs_p[:n + 1] = offs
        return DeviceColumn(dtype,
                            data=xp.asarray(_np_pad(chars, char_cap)),
                            validity=validity,
                            offsets=xp.asarray(offs_p))

    if isinstance(dtype, t.DecimalType):
        lo, hi = _decimal_unscaled(arr)
        lo = np.where(np.asarray(_valid_np(arr)), lo, 0)
        col = DeviceColumn(dtype, data=xp.asarray(_np_pad(lo, cap)),
                           validity=validity)
        if not dtype.is64:
            hi = np.where(np.asarray(_valid_np(arr)), hi, 0)
            col.data_hi = xp.asarray(_np_pad(hi, cap))
        return col

    if isinstance(dtype, t.ArrayType):
        larr = arr.cast(pa.large_list(to_arrow_type(dtype.element_type)))
        if larr.null_count:
            larr = larr.fill_null([])
        offs64 = np.asarray(larr.offsets)
        base = offs64[0]
        offs = (offs64 - base).astype(np.int32)
        child = larr.values[base: base + int(offs[-1])]
        child_cap = bucket_for(len(child), DEFAULT_ROW_BUCKETS)
        child_col = column_to_device(child, dtype.element_type, child_cap,
                                     char_buckets, xp, False)
        offs_p = np.full((cap + 1,), offs[-1] if n else 0, dtype=np.int32)
        offs_p[:n + 1] = offs
        return DeviceColumn(dtype, validity=validity,
                            offsets=xp.asarray(offs_p),
                            children=(child_col,))

    if isinstance(dtype, t.MapType):
        # map<K,V> lowers as ARRAY<STRUCT<key,value>> minus the struct
        # wrapper: offsets + (keys child, values child).  pyarrow's
        # MapArray gives slice-adjusted offsets and full children.
        offs64 = np.asarray(arr.offsets).astype(np.int64)
        base = int(offs64[0])
        offs = (offs64 - base).astype(np.int32)
        keys_src = arr.keys
        items_src = arr.items
        if arr.null_count:
            # Arrow only RECOMMENDS zero-length spans under null slots;
            # a producer emitting kv pairs under null rows would inflate
            # nkv and break the engine invariant that null rows span
            # zero entries — drop those entries and rebuild offsets
            valid_np = _valid_np(arr)
            spans = offs[1:] - offs[:-1]
            spans0 = np.where(valid_np, spans, 0)
            if not np.array_equal(spans0, spans):
                keep = np.repeat(valid_np, spans)
                keep_idx = np.flatnonzero(keep) + base
                keys_src = keys_src.take(pa.array(keep_idx))
                items_src = items_src.take(pa.array(keep_idx))
                base = 0
                offs = np.concatenate(
                    [np.zeros(1, np.int32),
                     np.cumsum(spans0, dtype=np.int32)])
        nkv = int(offs[-1]) if n else 0
        child_cap = bucket_for(max(nkv, 1), DEFAULT_ROW_BUCKETS)
        kcol = column_to_device(keys_src.slice(base, nkv), dtype.key_type,
                                child_cap, char_buckets, xp, False)
        vcol = column_to_device(items_src.slice(base, nkv), dtype.value_type,
                                child_cap, char_buckets, xp, False)
        offs_p = np.full((cap + 1,), offs[-1] if n else 0, dtype=np.int32)
        offs_p[:n + 1] = offs
        return DeviceColumn(dtype, validity=validity,
                            offsets=xp.asarray(offs_p),
                            children=(kcol, vcol))

    if isinstance(dtype, t.StructType):
        children = []
        for i, f in enumerate(dtype.fields):
            children.append(column_to_device(arr.field(i), f.data_type, cap,
                                             char_buckets, xp, False))
        return DeviceColumn(dtype, validity=validity, children=tuple(children))

    if isinstance(dtype, t.NullType):
        return DeviceColumn(dtype, data=xp.zeros((cap,), xp.int8),
                            validity=xp.zeros((cap,), bool))

    # flat types
    np_dt = t.to_np_dtype(dtype)
    if arr.null_count:
        arr = arr.fill_null(False if isinstance(dtype, t.BooleanType) else 0)
    if isinstance(dtype, t.DateType):
        npdata = np.asarray(arr.cast(pa.int32()))
    elif isinstance(dtype, t.TimestampType):
        npdata = np.asarray(arr.cast(pa.timestamp("us", tz="UTC")).cast(pa.int64()))
    else:
        npdata = arr.to_numpy(zero_copy_only=False).astype(np_dt, copy=False)
    return DeviceColumn(dtype, data=xp.asarray(_np_pad(npdata, cap)),
                        validity=validity)


def batch_to_device(rb: pa.RecordBatch,
                    row_buckets: Sequence[int] = DEFAULT_ROW_BUCKETS,
                    char_buckets: Sequence[int] = DEFAULT_CHAR_BUCKETS,
                    capacity: Optional[int] = None, xp=jnp,
                    device=None, fixed_width_strings: bool = True
                    ) -> DeviceBatch:
    """Upload an Arrow RecordBatch, padding to a capacity bucket.

    With ``xp=jnp`` this is the host->device crossing: the span
    ``scan.upload`` and the counter ``tpu_upload_bytes_total`` (every
    lane placed on the device, validity included).  The ``xp=np``
    callers (UDF and CPU-engine paths) upload nothing and count
    nothing.  ``device`` sends the lanes to that chip and commits them
    there, so what is computed from them runs there; without it they go
    to JAX's default device, uncommitted."""
    n = rb.num_rows
    cap = capacity if capacity is not None else bucket_for(n, row_buckets)

    def place() -> DeviceBatch:
        cols = []
        for i, f in enumerate(rb.schema):
            dtype = from_arrow_type(f.type)
            cols.append(column_to_device(rb.column(i), dtype, cap,
                                         char_buckets, xp,
                                         fixed_width_strings))
        return DeviceBatch(cols, n, names=rb.schema.names)

    if xp is not jnp:
        return place()
    from ..obs import metrics as m
    from ..obs.tracer import trace_span
    attrs = {} if device is None else {"device": device.id}
    with trace_span("scan.upload", rows=n, **attrs) as sp:
        if device is None:
            batch = place()
        else:
            with jax.default_device(device):
                batch = place()
            # the lanes are there already: committing copies nothing
            # (the row count stays the host scalar a scan batch carries)
            batch = DeviceBatch(jax.device_put(batch.columns, device), n,
                                batch.names)
        nbytes = sum(int(leaf.nbytes)
                     for leaf in jax.tree_util.tree_leaves(batch)
                     if isinstance(leaf, jax.Array))
        sp.set(bytes=nbytes, fixed_width_string_cols=sum(
            1 for c in batch.columns if c.fixed_width is not None))
    m.counter("tpu_upload_bytes_total",
              "bytes placed on the device by batch_to_device, validity "
              "lanes included").inc(nbytes)
    return batch


# ---------------------------------------------------------------------------
# Device -> host (Arrow)
# ---------------------------------------------------------------------------

def column_to_arrow(col: DeviceColumn, n: int) -> pa.Array:
    validity = np.asarray(col.validity)[:n] if col.validity is not None else None
    mask = None if validity is None else ~validity
    dtype = col.dtype

    if isinstance(dtype, (t.StringType, t.BinaryType)):
        if col.fixed_width is not None:
            # the word lane's first n values as bytes; offsets are a ramp
            w = col.fixed_width
            chars = fixed_word_bytes(np.asarray(col.word)[:n], w)
            offs = np.arange(n + 1, dtype=np.int64) * w
        else:
            offs = np.asarray(col.offsets)[:n + 1].astype(np.int64)
            chars = np.asarray(col.data)
        nbytes = int(offs[-1]) if n else 0
        pa_type = pa.large_binary() if isinstance(dtype, t.BinaryType) else pa.large_string()
        arr = pa.Array.from_buffers(
            pa_type, n,
            [None, pa.py_buffer(offs.tobytes()),
             pa.py_buffer(chars[:max(nbytes, 1)].tobytes())])
        if mask is not None and mask.any():
            arr = pa.array(
                [None if m else v for v, m in zip(arr.to_pylist(), mask)],
                type=pa_type)
        return arr

    if isinstance(dtype, t.DecimalType):
        lo = np.asarray(col.data)[:n]
        if dtype.is64:
            vals = [None if (mask is not None and m) else int(v)
                    for v, m in zip(lo, mask if mask is not None else np.zeros(n, bool))]
        else:
            hi = np.asarray(col.data_hi)[:n]
            vals = []
            msk = mask if mask is not None else np.zeros(n, bool)
            for v_lo, v_hi, m in zip(lo, hi, msk):
                if m:
                    vals.append(None)
                else:
                    vals.append((int(v_hi) << 64) | (int(v_lo) & ((1 << 64) - 1)))
        import decimal as pydec
        scale = dtype.scale
        py = [None if v is None else
              pydec.Decimal(v).scaleb(-scale) for v in vals]
        return pa.array(py, type=pa.decimal128(dtype.precision, dtype.scale))

    if isinstance(dtype, t.ArrayType):
        offs = np.asarray(col.offsets)[:n + 1].astype(np.int64)
        child_n = int(offs[-1]) if n else 0
        child = column_to_arrow(col.children[0], child_n)
        arr = pa.LargeListArray.from_arrays(pa.array(offs, type=pa.int64()),
                                            child)
        if mask is not None and mask.any():
            arr = pa.array([None if m else v
                            for v, m in zip(arr.to_pylist(), mask)],
                           type=pa.large_list(to_arrow_type(dtype.element_type)))
        return arr

    if isinstance(dtype, t.MapType):
        offs = np.asarray(col.offsets)[:n + 1].astype(np.int32)
        child_n = int(offs[-1]) if n else 0
        keys = column_to_arrow(col.children[0], child_n)
        items = column_to_arrow(col.children[1], child_n)
        arr = pa.MapArray.from_arrays(pa.array(offs, type=pa.int32()),
                                      keys, items)
        if mask is not None and mask.any():
            arr = pa.array([None if m else v
                            for v, m in zip(arr.to_pylist(), mask)],
                           type=to_arrow_type(dtype))
        return arr

    if isinstance(dtype, t.StructType):
        children = [column_to_arrow(c, n) for c in col.children]
        names = [f.name for f in dtype.fields]
        arr = pa.StructArray.from_arrays(children, names=names)
        if mask is not None and mask.any():
            arr = pa.array([None if m else v
                            for v, m in zip(arr.to_pylist(), mask)],
                           type=to_arrow_type(dtype))
        return arr

    if isinstance(dtype, t.NullType):
        return pa.nulls(n)

    data = np.asarray(col.data)[:n]
    if isinstance(dtype, t.DateType):
        return pa.array(data.astype(np.int32), type=pa.date32(),
                        mask=mask)
    if isinstance(dtype, t.TimestampType):
        return pa.array(data.astype(np.int64),
                        type=pa.timestamp("us", tz="UTC"), mask=mask)
    if isinstance(dtype, t.BooleanType):
        data = data.astype(np.bool_)
    return pa.array(data, type=to_arrow_type(dtype), mask=mask)


def batch_to_arrow(batch: DeviceBatch) -> pa.RecordBatch:
    n = int(batch.num_rows)
    arrays = [column_to_arrow(c, n) for c in batch.columns]
    names = list(batch.names)
    return pa.RecordBatch.from_arrays(arrays, names=names)
