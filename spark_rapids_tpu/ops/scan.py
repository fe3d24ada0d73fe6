"""Pad-shift (Hillis-Steele) prefix scans.

TPU kernel-structure note: the stock jnp.cumsum/cumprod lowering compiles
in minutes for 64-bit dtypes on this platform and the emulated scan HLO
runs far off memory speed.  log2(n) elementwise pad+combine steps compile
in ~2s and run at bandwidth for every dtype, so all engine prefix sums
route through here.
"""

from __future__ import annotations

import numpy as np


# One-place shifts.  The obvious `concatenate([fill, v[:-1]])` is not used
# on the device: inside a fused program the v5e compiler miscompiled it for
# a 64-bit lane of 16,777,216 rows — every 197,632nd element was compared
# with a wrong neighbour, so a grouped aggregate cut 83 groups in two
# (chip run, PR 21; `jnp.roll` and an optimization barrier in front did no
# better).  Comparing the two slices directly, and pad + slice (the form
# the scans below are built from), came out right.

def differs_from_prev(xp, w):
    """w[i] != w[i-1], and False at 0."""
    return xp.concatenate([xp.zeros((1,), dtype=bool), w[1:] != w[:-1]])


def shift_right(xp, v, fill=0):
    """v one place toward higher indices, `fill` at index 0."""
    return xp.pad(v, (1, 0), constant_values=fill)[:v.shape[0]]


def shift_left(xp, v, fill=0):
    """v one place toward lower indices, `fill` at the end."""
    return xp.pad(v, (0, 1), constant_values=fill)[1:]


def cumsum_fast(xp, v, dtype=None, axis=None):
    """Inclusive prefix sum via pad-shift doubling.  On TPU this lowers
    to log2(n) elementwise adds (no reduce-window / scan HLO), which both
    compiles ~100x faster than jnp.cumsum for 64-bit dtypes and avoids
    the emulated-scan slow path."""
    if axis is None:
        axis = 0
    if xp is np:
        return np.cumsum(v, axis=axis, dtype=dtype)
    if dtype is not None:
        v = v.astype(dtype)
    n = v.shape[axis]
    d = 1
    index = [slice(None)] * v.ndim
    index[axis] = slice(0, n)
    index = tuple(index)
    while d < n:
        pad = [(0, 0)] * v.ndim
        pad[axis] = (d, 0)
        v = v + xp.pad(v, pad)[index]
        d *= 2
    return v


def cumprod_fast(xp, v, dtype=None):
    """Inclusive prefix product, same pad-shift structure (pads with 1)."""
    if xp is np:
        return np.cumprod(v, dtype=dtype)
    if dtype is not None:
        v = v.astype(dtype)
    n = v.shape[0]
    d = 1
    while d < n:
        v = v * xp.pad(v, (d, 0), constant_values=1)[:n]
        d *= 2
    return v

def segmented_cumsum_fast(xp, v, seg_start):
    """Inclusive PER-SEGMENT prefix sum (segments restart where seg_start
    is True) via the segmented Hillis-Steele recurrence:

        v[i] += F[i] ? 0 : v[i-d];   F[i] |= F[i-d]

    Floats need this instead of global-scan differencing: a global prefix
    sum lets one segment's magnitude cancel catastrophically against
    another's (and inf/nan poison everything downstream)."""
    n = v.shape[0]
    f = seg_start.astype(bool)
    d = 1
    while d < n:
        if xp is np:
            pv = np.concatenate([np.zeros((d,), v.dtype), v[:-d]])
            pf = np.concatenate([np.ones((d,), bool), f[:-d]])
        else:
            pv = xp.pad(v, (d, 0))[:n]
            pf = xp.pad(f, (d, 0), constant_values=True)[:n]
        v = xp.where(f, v, v + pv)
        f = f | pf
        d *= 2
    return v

def cummax_i32(xp, v):
    """Running max of an int32 array via pad-shift doubling."""
    n = v.shape[0]
    d = 1
    lo = np.iinfo(np.int32).min
    while d < n:
        if xp is np:
            prev = np.concatenate([np.full((d,), lo, v.dtype), v[:-d]])
        else:
            prev = xp.pad(v, (d, 0), constant_values=lo)[:n]
        v = xp.maximum(v, prev)
        d *= 2
    return v


def fill_rows_from_starts(xp, starts_i32, active, out_cap: int):
    """For output positions p, the index of the input row whose span
    contains p: rows scatter their index at their span start (skipped
    when inactive/empty), then a running max fills the span — the
    scatter+scan replacement for the per-position binary search
    (searchsorted costs ~log(n) gather rounds on TPU; this is one int32
    scatter plus log2(n) elementwise maxes)."""
    n = starts_i32.shape[0]
    iota = xp.arange(n, dtype=xp.int32)
    if xp is np:
        seed = np.zeros((out_cap,), np.int32)
        tgt = np.where(active, np.clip(starts_i32, 0, out_cap), out_cap)
        keep = tgt < out_cap
        np.maximum.at(seed, tgt[keep], iota[keep])
        return np.maximum.accumulate(seed)
    tgt = xp.where(active, xp.clip(starts_i32, 0, out_cap), out_cap)
    seed = xp.zeros((out_cap,), xp.int32).at[tgt].max(iota, mode="drop")
    return cummax_i32(xp, seed)


def child_row_ids(xp, offsets, cap: int, child_cap: int):
    """(row_ids[child_cap], in_range[child_cap]): the owning row of each
    child/element position under a span-offsets column."""
    pos = xp.arange(child_cap, dtype=xp.int32)
    if xp is np:
        row = np.clip(np.searchsorted(offsets[1:], pos, side="right"),
                      0, cap - 1).astype(np.int32)
    else:
        spans = offsets[1:] - offsets[:-1]
        row = xp.clip(
            fill_rows_from_starts(xp, offsets[:-1].astype(xp.int32),
                                  spans > 0, child_cap), 0, cap - 1)
    return row, pos < offsets[-1]
