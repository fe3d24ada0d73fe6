"""Single-round-trip device->host batch fetch.

The reference copies result batches over PCIe one buffer at a time
(GpuColumnarToRowExec.scala:358 pulls each column's buffers).  Here every
host<->device crossing is a sync that drains the dispatch pipeline, and
batches are padded to capacity buckets, so the naive per-buffer fetch (one
transfer per data/validity/offsets lane, padding included) pays many syncs
and moves bytes that carry nothing.  What a crossing costs on a local chip
has not been measured on this code.  This module fetches a whole
DeviceBatch in exactly TWO round trips, transferring only the rows that
exist AND only the bytes that carry information:

  1. `sizes`: one jitted call returns [num_rows, var_len_0, ...] (char
     counts for strings, child row counts for arrays) plus per-lane stats
     (all-valid flags for bool lanes; min/max for integer lanes) as a
     single tiny array — one sync that also acts as the pipeline barrier.
  2. `shrink_pack`: a jitted function (cached per schema/capacity/plan)
     slices every lane to the smallest capacity bucket holding num_rows,
     then applies the transfer plan the host derived from the stats:
       * bool lanes that are all-true up to num_rows are SKIPPED (the
         host resynthesizes them from num_rows);
       * remaining bool lanes bit-pack 8 rows per byte;
       * integer lanes whose value range fits a narrower width travel as
         (lane - min) in uint8/16/32 — the device re-derives min so the
         plan key stays value-independent; the host adds back the min it
         already fetched with the sizes;
     and concatenates the lanes into one buffer PER TRANSFERRED DTYPE.
     No 64-bit bitcasting — the TPU X64-rewrite pass cannot compile it.

The host then rebuilds numpy-backed DeviceColumns from views of those
buffers; Arrow conversion proceeds on host exactly as before.
"""

from __future__ import annotations

import contextlib
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as t
from .device import DeviceBatch, DeviceColumn, bucket_for, \
    DEFAULT_CHAR_BUCKETS, DEFAULT_ROW_BUCKETS


def _is_device(x) -> bool:
    return isinstance(x, jax.Array)


@contextlib.contextmanager
def _crossing():
    """One device->host fetch, as the span ``fetch.crossing`` around the
    blocking transfer, plus the continuous counters.  Yields a callback
    for the bytes that came back.  The per-query crossing count is a
    DETERMINISTIC regression-watchdog field (obs/history.py counts the
    spans by name), so every sanctioned crossing must pass through
    here; a transfer that raises is no crossing."""
    from ..obs import metrics as m
    from ..obs.tracer import trace_span
    fetched: List[int] = []
    with trace_span("fetch.crossing", transfers=1) as sp:
        yield fetched.append
        nbytes = sum(fetched)
        sp.set(bytes=nbytes)
    m.counter("tpu_fetch_crossings_total",
              "device->host transfer round trips through the "
              "sanctioned fetch path").inc(1)
    m.counter("tpu_fetch_bytes_total",
              "bytes moved device->host through the sanctioned fetch "
              "path").inc(nbytes)


def fetch_ints(scalars: Sequence) -> List[int]:
    """Resolve a mixed list of host/device integer scalars to python ints
    in at most ONE device transfer.

    This is the sanctioned crossing for host-driven control flow that
    needs a handful of device scalars (span byte counts, slice bounds):
    callers stack every scalar they need and pay a single sync instead
    of one per value (TPU-R001's whole point)."""
    dev_idx: List[int] = []
    dev_vals: List = []
    out: List[Optional[int]] = []
    for s in scalars:
        if _is_device(s):
            out.append(None)
            dev_idx.append(len(out) - 1)
            dev_vals.append(jnp.asarray(s).astype(jnp.int64))
        else:
            out.append(int(s))
    if dev_vals:
        stacked = jnp.stack(dev_vals)
        with _crossing() as got:
            fetched = np.asarray(stacked)  # one transfer
            got(fetched.nbytes)
        for i, v in zip(dev_idx, fetched):
            out[i] = int(v)
    return out  # type: ignore[return-value]


def fetch_array(x) -> np.ndarray:
    """Sanctioned single-transfer host materialization of one device
    array (e.g. the join count phase's stacked sizes vector)."""
    if not _is_device(x):
        return np.asarray(x)
    with _crossing() as got:
        out = np.asarray(x)
        got(out.nbytes)
    return out


def batch_is_device(batch: DeviceBatch) -> bool:
    return any(_is_device(l) for l in jax.tree_util.tree_leaves(batch))


class FetchLayoutError(RuntimeError):
    """Device pack and host unpack disagreed about the buffer layout."""


# ---------------------------------------------------------------------------
# canonical lane walk (matches DeviceColumn.tree_flatten leaf order)
# ---------------------------------------------------------------------------

def _walk_lanes(col: DeviceColumn):
    """Yield (kind, lane) for every present lane: data, validity, offsets,
    data_hi, then children recursively — the tree_flatten leaf order."""
    for kind, lane in col.stored_lanes():
        if lane is not None:
            yield (kind, lane)
    for ch in col.children:
        yield from _walk_lanes(ch)


def _np_dtype_of(x) -> np.dtype:
    return np.dtype(x.dtype.name if hasattr(x.dtype, "name") else x.dtype)


# ---------------------------------------------------------------------------
# sizes + stats: [num_rows, varlen..., lane stats...] in walk order
# ---------------------------------------------------------------------------

def _var_sizes(col: DeviceColumn, n) -> List:
    """Device scalars for every variable-length lane under `col`, in a
    deterministic walk order shared with _shrink_column."""
    out: List = []
    dt = col.dtype
    if isinstance(dt, (t.StringType, t.BinaryType)):
        if col.fixed_width is None:     # (a fixed width varies nothing)
            out.append(col.offsets[n].astype(jnp.int64))
    elif isinstance(dt, t.ArrayType):
        m = col.offsets[n]
        out.append(m.astype(jnp.int64))
        out += _var_sizes(col.children[0], m)
    elif isinstance(dt, t.MapType):
        m = col.offsets[n]
        out.append(m.astype(jnp.int64))
        out += _var_sizes(col.children[0], m)
        out += _var_sizes(col.children[1], m)
    elif isinstance(dt, t.StructType):
        for c in col.children:
            out += _var_sizes(c, n)
    return out


def _lane_stats(col: DeviceColumn, n) -> List:
    """Two device scalars per lane in walk order: bool lanes report
    (all_true_up_to_n, 0); integer data lanes report (min, max) over the
    LIVE rows only — padding rows are never read back (hosts slice to
    num_rows), so zero padding must not drag the range and defeat the
    narrowing; null rows within num_rows hold canonical zeros and are
    included, keeping null-zero reconstruction exact.  Offsets lanes use
    the full lane (their padding repeats the last live value).  Others
    report (0, 0).

    The device-side pack subtracts _narrow_min on the SAME masked lane,
    so host and device agree on the offset exactly.

    `n` is the live-row count at this column's level; children of span
    columns use their own child counts."""
    stats: List = []

    def visit(c: DeviceColumn, live_n):
        for kind, lane in c.stored_lanes():
            if lane is None:
                continue
            dt = _np_dtype_of(lane)
            if dt == np.bool_:
                io = jnp.arange(lane.shape[0], dtype=jnp.int32)
                allv = jnp.all(lane | (io >= live_n))
                stats.append(allv.astype(jnp.int64))
                stats.append(jnp.int64(0))
            elif dt.kind in "iu" and dt.itemsize >= 2:
                if kind == "offsets":
                    stats.append(jnp.min(lane).astype(jnp.int64))
                    stats.append(jnp.max(lane).astype(jnp.int64))
                else:
                    stats.append(_narrow_min(lane, live_n).astype(
                        jnp.int64))
                    io = jnp.arange(lane.shape[0], dtype=jnp.int32)
                    lo = np.iinfo(dt).min
                    mx = jnp.max(jnp.where(io < live_n, lane,
                                           lane.dtype.type(lo)))
                    stats.append(mx.astype(jnp.int64))
            else:
                stats.append(jnp.int64(0))
                stats.append(jnp.int64(0))
        cdt = c.dtype
        if isinstance(cdt, (t.ArrayType, t.MapType)):
            m = c.offsets[jnp.clip(live_n, 0, c.capacity)]
            for ch in c.children:
                visit(ch, m)
        else:
            for ch in c.children:
                visit(ch, live_n)

    visit(col, n)
    return stats


def _narrow_min(lane, live_n):
    """Min over live rows — the shared offset for integer narrowing.
    Empty batches degrade to dtype-max, making span negative so the plan
    never narrows."""
    dt = _np_dtype_of(lane)
    io = jnp.arange(lane.shape[0], dtype=jnp.int32)
    hi = np.iinfo(dt).max
    return jnp.min(jnp.where(io < live_n, lane, lane.dtype.type(hi)))


def _make_sizes_fn():
    def sizes(batch: DeviceBatch, extras=()):
        n = jnp.asarray(batch.num_rows).astype(jnp.int64)
        parts = [n]
        for col in batch.columns:
            parts += _var_sizes(col, jnp.asarray(batch.num_rows))
        for col in batch.columns:
            parts += _lane_stats(col, jnp.asarray(batch.num_rows))
        # ride-along scalars (speculation guards): verified by the caller
        # from the same transfer, so deferred checks cost no extra trip
        parts += [jnp.asarray(e).astype(jnp.int64) for e in extras]
        return jnp.stack(parts)
    return sizes


# ---------------------------------------------------------------------------
# transfer plan: one entry per lane in walk order
# ---------------------------------------------------------------------------
# entry: ("none",) | ("skip",) | ("bit",) | ("narrow", out_itemsize)
# host-side companions (not in the jit key): min values for narrowed lanes

_NARROW_NP = {1: np.uint8, 2: np.uint16, 4: np.uint32}
_NARROW_JNP = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def _build_plan(batch: DeviceBatch, stats: np.ndarray):
    """Per-lane transfer plan + per-lane host minima, in walk order."""
    plan: List[tuple] = []
    mins: List[int] = []
    i = 0
    for col in batch.columns:
        for kind, lane in _walk_lanes(col):
            s1, s2 = int(stats[2 * i]), int(stats[2 * i + 1])
            i += 1
            dt = _np_dtype_of(lane)
            if dt == np.bool_:
                if s1:
                    plan.append(("skip",))
                elif lane.shape[0] % 8 == 0:
                    plan.append(("bit",))
                else:
                    plan.append(("none",))
                mins.append(0)
                continue
            if dt.kind in "iu" and dt.itemsize >= 2:
                span = s2 - s1
                if 0 <= span < (1 << 8) and dt.itemsize > 1:
                    plan.append(("narrow", 1))
                elif 0 <= span < (1 << 16) and dt.itemsize > 2:
                    plan.append(("narrow", 2))
                elif 0 <= span < (1 << 32) and dt.itemsize > 4:
                    plan.append(("narrow", 4))
                else:
                    plan.append(("none",))
                mins.append(s1)
                continue
            plan.append(("none",))
            mins.append(0)
    return tuple(plan), mins


# ---------------------------------------------------------------------------
# shrink to bucket + pack per transferred dtype
# ---------------------------------------------------------------------------

def _slice_or_pad(a, cap: int):
    if a.shape[0] == cap:
        return a
    if a.shape[0] > cap:
        return a[:cap]
    pad = [(0, cap - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad)


def _shrink_column(col: DeviceColumn, out_cap: int, var_caps) -> DeviceColumn:
    """Copy of `col` with every lane sliced/padded to its output bucket.
    `var_caps` is an iterator of buckets in _var_sizes walk order."""
    dt = col.dtype
    validity = None if col.validity is None else \
        _slice_or_pad(col.validity, out_cap)
    if col.fixed_width is not None:
        return col.with_word(_slice_or_pad(col.word, out_cap), validity)
    if isinstance(dt, (t.StringType, t.BinaryType)):
        char_cap = next(var_caps)
        return DeviceColumn(dt, data=_slice_or_pad(col.data, char_cap),
                            validity=validity,
                            offsets=_slice_or_pad(col.offsets, out_cap + 1))
    if isinstance(dt, t.ArrayType):
        child_cap = next(var_caps)
        child = _shrink_column(col.children[0], child_cap, var_caps)
        return DeviceColumn(dt, validity=validity,
                            offsets=_slice_or_pad(col.offsets, out_cap + 1),
                            children=(child,))
    if isinstance(dt, t.MapType):
        child_cap = next(var_caps)
        kcol = _shrink_column(col.children[0], child_cap, var_caps)
        vcol = _shrink_column(col.children[1], child_cap, var_caps)
        return DeviceColumn(dt, validity=validity,
                            offsets=_slice_or_pad(col.offsets, out_cap + 1),
                            children=(kcol, vcol))
    if isinstance(dt, t.StructType):
        children = tuple(_shrink_column(c, out_cap, var_caps)
                         for c in col.children)
        return DeviceColumn(dt, validity=validity, children=children)
    out = DeviceColumn(dt,
                       data=None if col.data is None else
                       _slice_or_pad(col.data, out_cap),
                       validity=validity)
    if col.data_hi is not None:
        out.data_hi = _slice_or_pad(col.data_hi, out_cap)
    return out


def _transferred_dtype(lane_dtype: np.dtype, step: tuple) -> Optional[str]:
    """Wire dtype name for a lane under its plan step; None = skipped."""
    if step[0] == "skip":
        return None
    if step[0] == "bit":
        return "uint8"
    if step[0] == "narrow":
        return np.dtype(_NARROW_NP[step[1]]).name
    return "uint8" if lane_dtype == np.bool_ else lane_dtype.name


def _make_shrink_pack_fn(out_cap: int, var_caps: Tuple[int, ...],
                         plan: Tuple[tuple, ...]):
    def shrink_pack(batch: DeviceBatch):
        it = iter(var_caps)
        cols = [_shrink_column(c, out_cap, it) for c in batch.columns]
        groups: dict = {}  # insertion-ordered: wire dtype -> 1-D pieces
        pi = iter(plan)

        def visit(c: DeviceColumn, orig: DeviceColumn, live_n):
            for (kind, leaf), (_, oleaf) in zip(c.stored_lanes(),
                                                 orig.stored_lanes()):
                if leaf is None:
                    continue
                step = next(pi)
                if step[0] == "skip":
                    continue
                if step[0] == "bit":
                    w = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
                    leaf = jnp.sum(
                        leaf.reshape(-1, 8).astype(jnp.uint8) * w,
                        axis=1, dtype=jnp.uint8)
                elif step[0] == "narrow":
                    # subtract exactly the offset the host fetched in the
                    # sizes stats: live-masked min for data/hi lanes,
                    # full-lane min for offsets
                    minv = jnp.min(oleaf) if kind == "offsets" else \
                        _narrow_min(oleaf, live_n)
                    leaf = (leaf - minv).astype(_NARROW_JNP[step[1]])
                elif leaf.dtype == jnp.bool_:
                    leaf = leaf.astype(jnp.uint8)
                key = _np_dtype_of(leaf).name
                groups.setdefault(key, []).append(leaf.reshape(-1))
            cdt = orig.dtype
            if isinstance(cdt, (t.ArrayType, t.MapType)):
                m = orig.offsets[jnp.clip(live_n, 0, orig.capacity)]
                for ch, och in zip(c.children, orig.children):
                    visit(ch, och, m)
            else:
                for ch, och in zip(c.children, orig.children):
                    visit(ch, och, live_n)

        n0 = jnp.asarray(batch.num_rows)
        for c, orig in zip(cols, batch.columns):
            visit(c, orig, n0)
        return tuple(
            jnp.concatenate(ls) if len(ls) > 1 else ls[0]
            for ls in groups.values())
    return shrink_pack


class _BufReader:
    """Per-dtype cursors over the fetched buffer group (walk order on host
    mirrors the device pack exactly, so sequential slices line up)."""

    def __init__(self, bufs_by_key: dict):
        self._bufs = bufs_by_key
        self._pos = {k: 0 for k in bufs_by_key}

    def take(self, count: int, wire_dtype: str) -> np.ndarray:
        buf, pos = self._bufs[wire_dtype], self._pos[wire_dtype]
        view = buf[pos:pos + count]
        if len(view) != count:
            raise FetchLayoutError(
                f"fetch underrun: wanted {count} x {wire_dtype}, "
                f"buffer has {len(buf) - pos} left")
        self._pos[wire_dtype] = pos + count
        return view


def _unpack_column(col: DeviceColumn, rd: _BufReader, out_cap: int,
                   var_caps, plan_it, mins_it, live_n: int) -> DeviceColumn:
    """Rebuild a numpy-backed shrunk column from the packed buffers,
    reversing each lane's transfer transform.  `live_n` is this level's
    live row count (for resynthesizing skipped validity lanes)."""
    dt = col.dtype

    def lane(template, cap: int) -> Optional[np.ndarray]:
        if template is None:
            return None
        step = next(plan_it)
        minv = next(mins_it)
        ldt = _np_dtype_of(template)
        if step[0] == "skip":
            return np.arange(cap, dtype=np.int32) < live_n
        if step[0] == "bit":
            raw = rd.take(cap // 8, "uint8")
            return np.unpackbits(raw, bitorder="little")[:cap].astype(
                np.bool_)
        if step[0] == "narrow":
            raw = rd.take(cap, np.dtype(_NARROW_NP[step[1]]).name)
            return raw.astype(ldt) + ldt.type(minv)
        wire = "uint8" if ldt == np.bool_ else ldt.name
        raw = rd.take(cap, wire)
        return raw.astype(np.bool_) if ldt == np.bool_ else raw

    if col.fixed_width is not None:
        word = lane(col.word, out_cap)
        return col.with_word(word, lane(col.validity, out_cap))
    if isinstance(dt, (t.StringType, t.BinaryType)):
        char_cap = next(var_caps)
        data = lane(col.data, char_cap)
        validity = lane(col.validity, out_cap)
        offsets = lane(col.offsets, out_cap + 1)
        return DeviceColumn(dt, data=data, validity=validity,
                            offsets=offsets)
    if isinstance(dt, (t.ArrayType, t.MapType)):
        child_cap = next(var_caps)
        validity = lane(col.validity, out_cap)
        offsets = lane(col.offsets, out_cap + 1)
        child_n = int(offsets[min(live_n, len(offsets) - 1)])
        children = tuple(
            _unpack_column(ch, rd, child_cap, var_caps, plan_it, mins_it,
                           child_n)
            for ch in col.children)
        return DeviceColumn(dt, validity=validity, offsets=offsets,
                            children=children)
    if isinstance(dt, t.StructType):
        validity = lane(col.validity, out_cap)
        children = tuple(
            _unpack_column(ch, rd, out_cap, var_caps, plan_it, mins_it,
                           live_n)
            for ch in col.children)
        return DeviceColumn(dt, validity=validity, children=children)
    data = lane(col.data, out_cap)
    validity = lane(col.validity, out_cap)
    out = DeviceColumn(dt, data=data, validity=validity)
    if col.data_hi is not None:
        out.data_hi = lane(col.data_hi, out_cap)
    return out


def _schema_key(batch: DeviceBatch) -> tuple:
    def col_key(c: DeviceColumn):
        (_, data), _, (_, offsets), _ = c.stored_lanes()
        key = (repr(c.dtype), None if data is None else
               (str(data.dtype), tuple(data.shape)),
               c.validity is not None,
               None if offsets is None else
               (str(offsets.dtype), tuple(offsets.shape)),
               None if c.data_hi is None else str(c.data_hi.dtype),
               tuple(col_key(ch) for ch in c.children))
        return key if c.fixed_width is None else key + (c.fixed_width,)
    return tuple(col_key(c) for c in batch.columns)


# last successful (out_cap, var_caps, plan) per schema key: lets a warm
# repeat dispatch the pack SPECULATIVELY alongside the sizes probe and
# pay ONE sync instead of two serial round trips.  The sizes
# still arrive and must re-derive the identical plan, or the
# speculative buffers are discarded (a narrowed lane under a stale
# narrower width would wrap silently — never trusted without the check).
_LAST_PLAN: dict = {}


def fetch_batch(batch: DeviceBatch,
                row_buckets: Sequence[int] = DEFAULT_ROW_BUCKETS,
                char_buckets: Sequence[int] = DEFAULT_CHAR_BUCKETS,
                extra_scalars: Sequence = ()):
    """Bring a device batch to host as numpy-backed DeviceBatch in two
    round trips (ONE when the speculative plan validates), transferring
    only bucket_for(num_rows) rows per lane and only
    information-carrying bytes per lane (see module doc).

    `extra_scalars` (device scalars, e.g. deferred speculation guards)
    ride the sizes transfer; when given, returns (batch, extras_array)."""
    n_extra = len(extra_scalars)
    if not batch_is_device(batch):
        # already host-side: just normalize num_rows to a python int
        out = DeviceBatch(batch.columns, int(batch.num_rows), batch.names)
        if n_extra:
            vals = np.asarray([int(np.asarray(e)) for e in extra_scalars])
            return out, vals
        return out
    from ..exec.base import process_jit
    skey = _schema_key(batch)
    sizes_fn = process_jit(("fetch_sizes", skey, n_extra), _make_sizes_fn)
    extras_t = tuple(extra_scalars)
    # plan memo key includes the bucket ladders: a caller alternating
    # bucket configs for one schema must not arm doomed speculation
    pkey = (skey, tuple(row_buckets), tuple(char_buckets))
    entry = _LAST_PLAN.get(pkey)
    spec = None
    spec_bufs = None
    if entry is not None and entry[1] >= 1:
        # speculate only after the plan repeated — a misprediction moves
        # a full wasted payload to the host, so alternating shapes must
        # not thrash
        spec = entry[0]
        s_cap, s_vc, s_plan = spec
        spec_fn = process_jit(("fetch_pack", skey, s_cap, s_vc, s_plan),
                              lambda: _make_shrink_pack_fn(s_cap, s_vc,
                                                           s_plan))
        sizes_dev = sizes_fn(batch, extras_t)
        spec_out = spec_fn(batch)
        with _crossing() as got:
            fetched = jax.device_get(
                (sizes_dev,) + tuple(spec_out))  # 1 sync
            got(sum(int(b.nbytes) for b in fetched))
        sizes = np.asarray(fetched[0])
        spec_bufs = fetched[1:]
    else:
        sizes_dev = sizes_fn(batch, extras_t)
        with _crossing() as got:
            sizes = np.asarray(sizes_dev)  # round trip 1
            got(sizes.nbytes)
    extra_vals = sizes[len(sizes) - n_extra:] if n_extra else None
    if n_extra:
        sizes = sizes[:len(sizes) - n_extra]
    n = int(sizes[0])
    out_cap = bucket_for(n, row_buckets)
    # decode var sizes in walk order -> buckets (char lanes use char
    # buckets; array-child row lanes use row buckets)
    var_caps: List[int] = []

    def walk(col: DeviceColumn, it):
        dt = col.dtype
        if isinstance(dt, (t.StringType, t.BinaryType)):
            if col.fixed_width is None:
                var_caps.append(bucket_for(int(next(it)), char_buckets))
        elif isinstance(dt, t.ArrayType):
            m = int(next(it))
            var_caps.append(bucket_for(m, row_buckets))
            walk(col.children[0], it)
        elif isinstance(dt, t.MapType):
            m = int(next(it))
            var_caps.append(bucket_for(m, row_buckets))
            walk(col.children[0], it)
            walk(col.children[1], it)
        elif isinstance(dt, t.StructType):
            for c in col.children:
                walk(c, it)

    it = iter(sizes[1:])
    for c in batch.columns:
        walk(c, it)
    vc = tuple(var_caps)
    stats = sizes[1 + len(var_caps):]
    plan, mins = _build_plan(batch, stats)
    if spec_bufs is not None and spec == (out_cap, vc, plan):
        bufs = spec_bufs                         # speculation validated
    else:
        pack_fn = process_jit(("fetch_pack", skey, out_cap, vc, plan),
                              lambda: _make_shrink_pack_fn(out_cap, vc,
                                                           plan))
        packed = pack_fn(batch)
        with _crossing() as got:
            bufs = jax.device_get(packed)    # round trip 2 (one sync)
            got(sum(int(b.nbytes) for b in bufs))
    this_plan = (out_cap, vc, plan)
    prev = _LAST_PLAN.get(pkey)
    if len(_LAST_PLAN) > 256 and pkey not in _LAST_PLAN:
        # bounded memo: drop the oldest entry (insertion order)
        _LAST_PLAN.pop(next(iter(_LAST_PLAN)))
    _LAST_PLAN[pkey] = (this_plan,
                        (prev[1] + 1) if prev and prev[0] == this_plan
                        else 0)
    # reconstruct the device-side wire-dtype-group order from the template
    order: List[str] = []
    pi = iter(plan)
    for c in batch.columns:
        for kind, leaf in _walk_lanes(c):
            wd = _transferred_dtype(_np_dtype_of(leaf), next(pi))
            if wd is not None and wd not in order:
                order.append(wd)
    if len(order) != len(bufs):
        raise FetchLayoutError(
            f"fetch layout drift: host expects {order}, device sent "
            f"{[str(b.dtype) for b in bufs]}")
    rd = _BufReader(dict(zip(order, bufs)))
    caps_it = iter(vc)
    plan_it = iter(plan)
    mins_it = iter(mins)
    cols = [_unpack_column(c, rd, out_cap, caps_it, plan_it, mins_it, n)
            for c in batch.columns]
    out = DeviceBatch(cols, n, batch.names)
    return (out, extra_vals) if n_extra else out
