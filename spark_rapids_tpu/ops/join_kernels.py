"""Equi-join kernels: a sort-and-scan probe and pair expansion.

TPU replacement for cuDF's hash join (ref GpuHashJoin.scala /
JoinGatherer.scala): instead of a device hash table, each side's keys
collapse to a single 64-bit combined hash.  The build side's hashes are
sorted once (`carry.stable_argsort`), and ONE stable sort of both sides'
(hash, side, row) puts every probe row behind the build rows of its hash
(`carry.sort_lanes`): two scans then give each probe row its match count
and the start of its run in the sorted build order, and two int32
scatters put them back at the probe rows' own places.  No per-row binary
search: a `searchsorted` is log(n) rounds of gathers on this chip (the
NumPy engine keeps it; it is the reference).  Pair expansion fills each
probe row's span of the output from its start by a running maximum
(`scan.fill_rows_from_starts`) — all static shapes.

Two-phase protocol (one host sync, like cuDF sizing its gather maps):
  phase 1 (jitted `count_matches`): per-probe match ranges + totals;
  host picks a bucketed output capacity;
  phase 2 (jitted `expand_pairs`): materialize (probe_idx, build_idx,
  probe_valid, build_valid) gather maps at that static capacity.

Key hashing, and what "equal" means (`combined_key_hash`): per-column
64-bit words (the value for integers, an ordered encoding for floats, a
content hash for strings) mixed with a splitmix-style combiner.  Equal
keys always land on equal hashes.

  - ONE integer-typed key (bool, byte .. long, date, timestamp): the hash
    is a composition of bijections of the 64-bit value (`_mix64` is
    splitmix64's finaliser, three xor-shifts and two odd multiplications;
    `h0 ^ (w + c)` with constants `h0`, `c`), so unequal keys have unequal
    hashes and the join is EXACT (tests/test_join.py holds it to that).
  - Several key columns, floats' NaNs aside, and strings: unequal keys
    collide with probability about 2^-64 a pair, the same trade as the
    string-equality design.

No hash VALUE means anything: rows that must not match (slots past a
batch's rows, rows with a null key) are kept apart by flags that ride the
sort beside the hash, never by a parking value or a sentinel that a live
key's hash could equal.
"""

from __future__ import annotations

import numpy as np

from .. import types as t
from ..columnar.device import DeviceColumn
from . import strings as sops
from .scan import cumsum_fast

_MIX = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)
_H0 = np.uint64(0x12345678DEADBEEF)
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def _mix64(xp, h):
    h = (h ^ (h >> np.uint64(30))) * _MIX
    h = (h ^ (h >> np.uint64(27))) * _MIX2
    return h ^ (h >> np.uint64(31))


def combined_key_hash(xp, key_cols, cap):
    """(uint64[cap] combined hash over the key columns, bool[cap] rows
    with a null in any key).  A null key matches nothing: the caller takes
    those rows out of the matching by the flag (`HashJoinExec._count`),
    whatever their hash.  For one integer-typed column the hash is an
    injective function of the value (module doc)."""
    from .segmented import encode_float_ordered, encode_int_ordered
    h = xp.full((cap,), _H0, dtype=xp.uint64)
    any_null = xp.zeros((cap,), dtype=bool)
    for col in key_cols:
        dtype = col.dtype
        if isinstance(dtype, (t.StringType, t.BinaryType)):
            h1, h2 = sops.string_hashes(xp, col.offsets, col.data)
            w = _mix64(xp, h1 ^ (h2 * _MIX))
        elif isinstance(dtype, (t.FloatType, t.DoubleType)):
            w = _mix64(xp, encode_float_ordered(xp, col.data))
        elif isinstance(dtype, t.NullType):
            w = xp.zeros((cap,), dtype=xp.uint64)
        else:
            w = _mix64(xp, encode_int_ordered(xp, col.data))
        h = _mix64(xp, h ^ (w + _GOLDEN +
                            (h << np.uint64(6)) + (h >> np.uint64(2))))
        if col.validity is not None:
            any_null = any_null | ~col.validity
    return h, any_null


# the combined sort's side lane: within one hash, the build rows that can
# match sort first, then the probe rows, then the build rows that cannot
_SIDE_BUILD, _SIDE_PROBE, _SIDE_DEAD = 0, 1, 2


def count_matches(xp, build_hash, build_live, probe_hash, probe_live):
    """Per-probe-row match ranges against the sorted build side.

    Returns (sorted_build_order, lo, counts) where build rows
    sorted_build_order[lo[i]:lo[i]+counts[i]] match probe row i.
    `build_live` / `probe_live` are the rows that may match (in the batch
    and with no null key); a row outside them matches nothing whatever
    its hash, because liveness rides the sorts as a flag: the build order
    is by (dead, hash), so the live rows are its prefix in hash order.

    TPU path: ONE combined stable sort over (hash, side, index) finds
    every probe row's build run — within a hash segment live build rows
    sort first, so a probe row's running build count minus the count at
    the segment start is exactly its match count, and the count at the
    segment start is its `lo` into the hash-sorted build order.  A
    per-position binary search (searchsorted) would cost ~log(n) gather
    rounds; this is one sort + two scans + two int32 scatters."""
    cap_b = build_hash.shape[0]
    dead = ~build_live
    if xp is np:
        order = np.lexsort((build_hash, dead)).astype(np.int32)
        sorted_h = build_hash[order[:int(np.count_nonzero(build_live))]]
        lo = np.searchsorted(sorted_h, probe_hash, side="left").astype(
            np.int32)
        hi = np.searchsorted(sorted_h, probe_hash, side="right").astype(
            np.int32)
        counts = np.where(probe_live, hi - lo, 0).astype(np.int64)
        return order, lo, counts
    from .carry import sort_lanes, stable_argsort
    from .scan import cummax_i32, cumsum_fast
    cap_p = probe_hash.shape[0]
    allh = xp.concatenate([build_hash, probe_hash])
    side = xp.concatenate([
        xp.where(build_live, xp.uint8(_SIDE_BUILD), xp.uint8(_SIDE_DEAD)),
        xp.full((cap_p,), _SIDE_PROBE, xp.uint8)])
    idx = xp.concatenate([xp.arange(cap_b, dtype=xp.int32),
                          xp.arange(cap_p, dtype=xp.int32)])
    order = stable_argsort(xp, [dead, build_hash], cap_b)
    _, (sh, ss, si) = sort_lanes(xp, [allh, side], [allh, side, idx],
                                 cap_b + cap_p, need_order=False)
    is_b = (ss == _SIDE_BUILD).astype(xp.int32)
    from .scan import differs_from_prev
    nb = differs_from_prev(xp, sh)
    n_all = cap_b + cap_p
    if n_all > 0:
        nb = nb | (xp.arange(n_all) == 0)
    # running build count, exclusive of the current row
    bexcl = cumsum_fast(xp, is_b) - is_b
    # broadcast the segment-start value (bexcl is non-decreasing)
    seg_start_excl = cummax_i32(xp, xp.where(nb, bexcl, xp.int32(-1)))
    cnt_row = bexcl - seg_start_excl        # builds before row in its seg
    # probe rows sort after every live build row of their segment, so
    # cnt_row IS the match count; scatter (lo, cnt) to original probe
    # positions
    probe_tgt = xp.where(ss == _SIDE_PROBE, si, xp.int32(cap_p))
    lo = xp.zeros((cap_p,), xp.int32).at[probe_tgt].set(
        seg_start_excl, mode="drop", unique_indices=True)
    cnt = xp.zeros((cap_p,), xp.int32).at[probe_tgt].set(
        cnt_row, mode="drop", unique_indices=True)
    counts = xp.where(probe_live, cnt, 0).astype(xp.int64)
    return order, lo, counts


def expand_pairs(xp, order, lo, counts, probe_live, out_cap: int,
                 join_type: str = "inner"):
    """Materialize the pair lists at static capacity `out_cap`.

    Returns (probe_idx, build_idx, pair_valid, probe_side_valid,
    build_side_valid, total).  For outer-left, probe rows with zero
    matches emit one pair with build side invalid."""
    outer_left = join_type in ("left", "full")
    eff_counts = xp.maximum(counts, 1) if outer_left else counts
    eff_counts = xp.where(probe_live, eff_counts, 0)
    eff32 = eff_counts.astype(xp.int32)
    offs = xp.concatenate([xp.zeros((1,), xp.int32),
                           cumsum_fast(xp, eff32)])
    total = offs[-1].astype(xp.int64)
    p = xp.arange(out_cap, dtype=xp.int32)
    if xp is np:
        row = np.clip(np.searchsorted(offs[1:], p, side="right"),
                      0, counts.shape[0] - 1).astype(np.int32)
    else:
        # scatter each row's index at its span start, running-max fills
        # the span (replaces a per-position binary search)
        from .scan import fill_rows_from_starts
        row = xp.clip(fill_rows_from_starts(xp, offs[:-1], eff32 > 0,
                                            out_cap),
                      0, counts.shape[0] - 1)
    k = (p - offs[row]).astype(xp.int32)
    pair_valid = p < total
    matched = counts[row] > 0
    build_pos = xp.clip(lo[row] + xp.minimum(k, xp.maximum(
        counts[row].astype(xp.int32) - 1, 0)), 0, order.shape[0] - 1)
    build_idx = order[build_pos]
    build_valid = pair_valid & matched
    probe_idx = row
    probe_valid = pair_valid
    return probe_idx, build_idx, pair_valid, probe_valid, build_valid, total


def build_matched_flags(xp, order, lo, counts, probe_live, build_cap: int):
    """bool[build_cap]: build rows matched by at least one probe row
    (for right/full outer unmatched emission).  Scatter +1 at range starts
    and -1 after range ends over sorted positions, prefix-sum."""
    n = counts.shape[0]
    delta = xp.zeros((build_cap + 1,), dtype=xp.int32)
    starts = xp.clip(lo, 0, build_cap)
    ends = xp.clip(lo + counts.astype(xp.int32), 0, build_cap)
    live = probe_live & (counts > 0)
    if xp is np:
        np.add.at(delta, starts[live], 1)
        np.add.at(delta, ends[live], -1)
    else:
        ones = live.astype(xp.int32)
        delta = delta.at[starts].add(ones)
        delta = delta.at[ends].add(-ones)
    covered = cumsum_fast(xp, delta[:-1]) > 0
    # covered is in sorted-order positions; map back to original rows
    matched = xp.zeros((build_cap,), dtype=bool)
    if xp is np:
        matched[order] = covered
    else:
        matched = matched.at[order].set(covered)
    return matched
