"""Row gather over device columns (the TPU analog of cuDF gather maps,
ref JoinGatherer.scala / cudf Table.gather usage throughout the reference).

`gather_column(xp, col, indices, valid)` builds a new column whose row i is
`col[indices[i]]` (null when `valid[i]` is false).  Variable-length types
(strings, arrays) re-pack their child buffers with the searchsorted span
technique from ops/strings.py — O(out_cap + out_child_cap), static shapes.
"""

from __future__ import annotations

import numpy as np

from .. import types as t
from ..columnar.device import DeviceBatch, DeviceColumn
from . import strings as sops
from .scan import cumsum_fast


def gather_spans(xp, offsets, indices, valid, out_child_cap: int):
    """(new_offsets, src_positions, in_range) for span-structured columns."""
    idx = xp.clip(indices, 0, offsets.shape[0] - 2)
    src_start = offsets[idx]
    src_len = xp.where(valid, offsets[idx + 1] - src_start,
                       xp.zeros((), dtype=offsets.dtype))
    new_offs = xp.concatenate([
        xp.zeros((1,), offsets.dtype),
        cumsum_fast(xp, src_len, dtype=offsets.dtype)])
    p = xp.arange(out_child_cap, dtype=xp.int32)
    if xp is np:
        row = np.clip(np.searchsorted(new_offs[1:], p, side="right"),
                      0, indices.shape[0] - 1).astype(np.int32)
    else:
        from .scan import fill_rows_from_starts
        row = xp.clip(
            fill_rows_from_starts(xp, new_offs[:-1].astype(xp.int32),
                                  src_len > 0, out_child_cap),
            0, indices.shape[0] - 1)
    src_pos = src_start[row] + (p - new_offs[row])
    in_range = p < new_offs[-1]
    return new_offs, src_pos, in_range


def gather_column(xp, col: DeviceColumn, indices, valid,
                  out_char_cap: int = 0) -> DeviceColumn:
    dtype = col.dtype
    out_n = indices.shape[0]
    idx = xp.clip(indices, 0, col.capacity - 1)
    if col.validity is not None:
        new_valid = valid & col.validity[idx]
    else:
        new_valid = valid

    if col.fixed_width is not None:
        # a fixed-width string is one row-aligned lane: no spans to repack
        word = xp.where(new_valid, col.word[idx],
                        xp.zeros((), dtype=col.word.dtype))
        return col.with_word(word, new_valid)

    if isinstance(dtype, (t.StringType, t.BinaryType)):
        cap = out_char_cap or int(col.data.shape[0])
        new_offs, src_pos, in_range = gather_spans(
            xp, col.offsets, idx, new_valid, cap)
        src_pos = xp.clip(src_pos, 0, col.data.shape[0] - 1)
        chars = xp.where(in_range, col.data[src_pos],
                         xp.zeros((), dtype=xp.uint8))
        return DeviceColumn(dtype, data=chars, offsets=new_offs,
                            validity=new_valid)

    if isinstance(dtype, t.ArrayType):
        child = col.children[0]
        cap = out_char_cap or child.capacity
        new_offs, src_pos, in_range = gather_spans(
            xp, col.offsets, idx, new_valid, cap)
        src_pos = xp.clip(src_pos, 0, child.capacity - 1).astype(xp.int32)
        new_child = gather_column(xp, child, src_pos, in_range)
        return DeviceColumn(dtype, offsets=new_offs, validity=new_valid,
                            children=(new_child,))

    if isinstance(dtype, t.MapType):
        kcol, vcol = col.children
        cap = out_char_cap or kcol.capacity
        new_offs, src_pos, in_range = gather_spans(
            xp, col.offsets, idx, new_valid, cap)
        src_pos = xp.clip(src_pos, 0, kcol.capacity - 1).astype(xp.int32)
        return DeviceColumn(dtype, offsets=new_offs, validity=new_valid,
                            children=(gather_column(xp, kcol, src_pos,
                                                    in_range),
                                      gather_column(xp, vcol, src_pos,
                                                    in_range)))

    if isinstance(dtype, t.StructType):
        children = tuple(gather_column(xp, c, idx, new_valid)
                         for c in col.children)
        return DeviceColumn(dtype, validity=new_valid, children=children)

    if isinstance(dtype, t.NullType):
        return DeviceColumn(dtype, data=xp.zeros((out_n,), xp.int8),
                            validity=xp.zeros((out_n,), dtype=bool))

    data = xp.where(new_valid, col.data[idx],
                    xp.zeros((), dtype=col.data.dtype))
    out = DeviceColumn(dtype, data=data, validity=new_valid)
    if col.data_hi is not None:
        out.data_hi = xp.where(new_valid, col.data_hi[idx],
                               xp.zeros((), dtype=col.data_hi.dtype))
    return out


def gather_batch(xp, batch: DeviceBatch, indices, valid, new_num_rows,
                 char_caps=None) -> DeviceBatch:
    cols = []
    for i, c in enumerate(batch.columns):
        cc = 0 if char_caps is None else char_caps[i]
        cols.append(gather_column(xp, c, indices, valid, cc))
    return DeviceBatch(cols, new_num_rows, batch.names)
