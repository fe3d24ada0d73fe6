"""Row permutations on the device: every sort and every lane move is a
pass of ONE sort signature.

A gather is row-at-a-time on this chip and a sort is not: a 33,554,432-row
XLA gather of one 32-bit lane took 0.93 s (0.15 GB/s, 1/5000 of the HBM
peak; ledger, PR 25) where a sort pass over the same rows takes a tenth
of it, and a pass beat the gather at every size read (1M rows: 1.9 ms
against 9.9; 16.7M: 35.6 against 145; 33.5M: 80 against 490-930;
PERF.md, PR 26).  So every sort-then-permute path in the engine (filter
compaction, sort exec, group-by, window ordering, the joins' probe) moves
its row data with `lax.sort`, never with `x[order]`.  Columns with span
structure (strings, arrays, maps: anything that stores offsets) cannot
ride a row permutation and keep `gather_column`; a fixed-width string
(`columnar/device.DeviceColumn`) stores none and is a lane like any other.

The TPU compiler's time for a `lax.sort` is set by the sort's signature,
not by how often a program repeats it (asked of the v5e compiler at
4,194,304 rows, PR 21: one stable (uint64, int32) sort 87 s, three of
them in one program 90 s; a stable 2-key sort with two more payload
operands 320 s; an unstable 2-key (uint32, int32) sort 23 s; 31 programs
built from many-operand sorts took 1,411.6 s of compile against 479.5 s
from this one).  Every device sort is therefore a pass of that ONE
cheapest signature (`_sort_pass`, the only `lax.sort` here), whose two
operands are both keys and always unique, so that it carries no payload:

  - `_lean_perm` finds a sort's order and its inverse, the rank, from
    passes over (key digit, tie-break); `stable_argsort` is its order;
  - `move_lanes` puts a 32-bit word of row data in its new place with
    one pass keyed by the rank: `sort((rank, x))[1]` is `x[order]`, bit
    for bit.  64-bit lanes are two words, up to 32 bool lanes one;
  - a stable partition (`compact_rows`, a sort by one bool word) has
    its rank in closed form from a prefix sum, and sorts nothing.

That structure is a fact of this module, not a setting: nothing outside
asks how a sort is built.  The numpy engine mirrors the semantics with
fancy indexing per lane, and is the reference the tests compare with.
"""

from __future__ import annotations

import threading
from typing import List, Sequence

import numpy as np

from .. import types as t
from ..columnar.device import DeviceColumn
from .gather import gather_column

# ---------------------------------------------------------------------------
# What a program's build moved, and how (read by obs/compileprof)
# ---------------------------------------------------------------------------

class _MoveCounts(threading.local):
    sorted = 0        # lanes moved by sort passes
    gathered = 0      # lanes of span columns moved by gather_column
    passes = 0        # every `_sort_pass`, the orders' own included
    ungrouped_reduced = 0   # ungrouped aggregates that reduce under a mask
    ungrouped_sorted = 0    # ungrouped aggregates that sort and compact
    grouped_dense = 0       # grouped aggregates that hold the dense arm
    grouped_sorted = 0      # grouped aggregates that are the sort arm alone
    strings_aligned = 0     # string columns moved as row-aligned lanes
    strings_gathered = 0    # string columns moved by offsets and gather
    join_gathered = 0       # columns a join gathered through its pair maps
    join_strings_gathered = 0   # of them, strings (the span repack)
    filters_masked = 0      # filters that hand up their keep flags alone
    filters_compacted = 0   # filters that move the kept rows to the front


_COUNTS = _MoveCounts()


def lane_move_counts() -> dict:
    """Lanes moved by sort pass, lanes moved by gather, sort passes, the
    ungrouped aggregates that moved no row at all (or did), the grouped
    ones that hold the dense arm (or the sort arm alone), and the string
    columns a `sort_rows` moved as row-aligned lanes (fixed-width) or by
    offsets and gather, and the `FilterExec` programs that move no lane
    (or compact), traced on this thread so far, under the names a
    program's build record gives them.  Tracing a program raises them, so
    the difference around a `lower()` is what that program does."""
    return {"lane_moves_sorted": _COUNTS.sorted,
            "lane_moves_gathered": _COUNTS.gathered,
            "sort_passes": _COUNTS.passes,
            "ungrouped_reduced": _COUNTS.ungrouped_reduced,
            "ungrouped_sorted": _COUNTS.ungrouped_sorted,
            "grouped_dense": _COUNTS.grouped_dense,
            "grouped_sorted": _COUNTS.grouped_sorted,
            "string_cols_row_aligned": _COUNTS.strings_aligned,
            "string_cols_gathered": _COUNTS.strings_gathered,
            "join_cols_gathered": _COUNTS.join_gathered,
            "join_string_cols_gathered": _COUNTS.join_strings_gathered,
            "filters_masked": _COUNTS.filters_masked,
            "filters_compacted": _COUNTS.filters_compacted}


def count_ungrouped(reduced: bool) -> None:
    """One ungrouped `exec/aggregate._group_reduce` call, by the arm it
    took: a masked reduction where the rows lie, or the sort arm."""
    if reduced:
        _COUNTS.ungrouped_reduced += 1
    else:
        _COUNTS.ungrouped_sorted += 1


def count_join_gathers(columns: Sequence[DeviceColumn]) -> None:
    """Columns of both sides that a join's expansion gathers row by row
    through its (probe, build) pair maps (`exec/join.HashJoinExec._expand`
    calls `ops/gather.gather_column`, which no other count here sees),
    and how many of them are strings with offsets, whose bytes the gather
    repacks span by span."""
    _COUNTS.join_gathered += len(columns)
    _COUNTS.join_strings_gathered += sum(
        _is_string(c) and c.offsets is not None for c in columns)


def count_filter(masked: bool) -> None:
    """One `exec/basic.FilterExec` program, by what it does with the keep
    flags: hands them up to the aggregate above it and moves no lane, or
    compacts the batch (`exec/filter_common`)."""
    if masked:
        _COUNTS.filters_masked += 1
    else:
        _COUNTS.filters_compacted += 1


def count_grouped(dense: bool) -> None:
    """One grouped `exec/aggregate._group_reduce` call, by what it holds:
    the dense arm (masked reductions of the groups found, with the sort
    arm behind a conditional for more groups than it walks), or the sort
    arm alone."""
    if dense:
        _COUNTS.grouped_dense += 1
    else:
        _COUNTS.grouped_sorted += 1


def _is_string(col: DeviceColumn) -> bool:
    return isinstance(col.dtype, (t.StringType, t.BinaryType))


def _gather_span_column(xp, col: DeviceColumn, order, cap: int):
    import jax
    _COUNTS.gathered += len(jax.tree_util.tree_leaves(col))
    _COUNTS.strings_gathered += _is_string(col)
    return gather_column(xp, col, order, xp.ones((cap,), dtype=bool))


# ---------------------------------------------------------------------------
# The one sort signature, and the moves built from it
# ---------------------------------------------------------------------------

_SIGN32 = np.uint32(0x80000000)


def _sort_pass(a, b):
    """Sort the unique (uint32, int32) pairs lexicographically.  Both are
    keys, so the compiler is asked for no stability and no payload."""
    from jax import lax
    _COUNTS.passes += 1
    return lax.sort((a, b), num_keys=2, is_stable=False)


def _as_i32(x):
    from jax import lax
    return lax.bitcast_convert_type(x, np.int32)


def _inverse(xp, perm, cap: int):
    """inv[perm[i]] = i: an order's rank, a rank's order."""
    return _sort_pass(perm.astype(xp.uint32),
                      xp.arange(cap, dtype=xp.int32))[1]


def _f64_split_words(d):
    """A double as the TPU holds it: the nearest float32 and the float32
    remainder (`segmented._float_split_ordered`)."""
    import jax.numpy as jnp
    hi = d.astype(jnp.float32)
    lo = jnp.where(jnp.isfinite(hi), d - hi.astype(jnp.float64),
                   0.0).astype(jnp.float32)
    return _as_i32(hi), _as_i32(lo)


def _f64_join_split(w0, w1):
    import jax.numpy as jnp
    from jax import lax
    hi = lax.bitcast_convert_type(w0, jnp.float32).astype(jnp.float64)
    lo = lax.bitcast_convert_type(w1, jnp.float32).astype(jnp.float64)
    # -0.0 + 0.0 is +0.0: a head without a remainder is the whole value
    return jnp.where(lo == 0.0, hi, hi + lo)


def _f64_bit_words(d):
    from jax import lax
    w = lax.bitcast_convert_type(d, np.int32)
    return w[:, 0], w[:, 1]


def _f64_join_bits(w0, w1):
    import jax.numpy as jnp
    from jax import lax
    return lax.bitcast_convert_type(jnp.stack([w0, w1], axis=-1),
                                    np.float64)


def _to_words(xp, x):
    """(int32 words, rebuild) of one lane of 32 or 64 bits: the 32-bit
    pieces that move, and the function that makes the lane of them again.
    (Narrower lanes share words: `move_lanes`.)"""
    from jax import lax
    dt = np.dtype(x.dtype)
    if dt.itemsize == 4 and dt.kind in "iuf":
        return [_as_i32(x)], lambda ws: lax.bitcast_convert_type(ws[0], dt)
    if dt.kind in "iu" and dt.itemsize == 8:
        u = x.astype(xp.uint64)
        words = [_as_i32(u.astype(xp.uint32)),
                 _as_i32((u >> np.uint64(32)).astype(xp.uint32))]

        def join(ws):
            lo, hi = (lax.bitcast_convert_type(w, np.uint32).astype(
                xp.uint64) for w in ws)
            return ((hi << np.uint64(32)) | lo).astype(dt)
        return words, join
    if dt == np.float64:
        # the TPU has no bit view of a double (doubles are float32 pairs
        # there); elsewhere the bits move as they are
        words = lax.platform_dependent(x, tpu=_f64_split_words,
                                       default=_f64_bit_words)
        return list(words), lambda ws: lax.platform_dependent(
            ws[0], ws[1], tpu=_f64_join_split, default=_f64_join_bits)
    raise TypeError(f"no sort-pass move for a lane of {dt}")


def _narrow_bits(x) -> int:
    """Bits of a lane that shares a word with others: 1 for a bool, 8 or
    16 for a narrow integer, 0 for a lane that fills words of its own."""
    dt = np.dtype(x.dtype)
    if dt == np.bool_:
        return 1
    return 8 * dt.itemsize if dt.kind in "iu" and dt.itemsize < 4 else 0


def move_lanes(xp, rank, lanes: Sequence) -> List:
    """Every 1-D lane of `lanes` with row r at `rank[r]` (`rank` a
    permutation of 0..cap-1): `x[order]` for the order whose inverse
    `rank` is, bit for bit, without a gather.  One sort pass per 32-bit
    word of distinct lane; bool lanes and 8- and 16-bit integers share
    words."""
    if xp is np:
        out = []
        for x in lanes:
            y = np.empty_like(x)
            y[rank] = x
            out.append(y)
        return out
    if not lanes:
        return []
    slot_of: dict = {}          # the same lane may back several columns
    uniq: List = []
    for x in lanes:
        if id(x) not in slot_of:
            slot_of[id(x)] = len(uniq)
            uniq.append(x)
    _COUNTS.sorted += len(uniq)
    rk = rank.astype(xp.uint32)

    def moved(word):
        return _sort_pass(rk, word)[1]

    out_u: List = [None] * len(uniq)
    # lanes narrower than a word share one: bools a bit each (first, so
    # that a call with no narrow integer packs as it always did), then
    # 8- and 16-bit integers (a fixed-width string's one or two bytes)
    bits_of = [_narrow_bits(x) for x in uniq]
    small = [(i, 1) for i, b in enumerate(bits_of) if b == 1]
    small += [(i, b) for i, b in enumerate(bits_of) if b > 1]
    groups: List[list] = []
    room = 0
    for i, bits in small:
        if bits > room:
            groups.append([])
            room = 32
        groups[-1].append((i, 32 - room, bits))
        room -= bits
    for group in groups:
        packed = None
        for i, shift, bits in group:
            x = uniq[i]
            if bits > 1:    # two's complement bits, without the sign's run
                x = x.astype(np.dtype(f"u{bits // 8}"))
            part = x.astype(xp.uint32)
            if shift:
                part = part << np.uint32(shift)
            packed = part if packed is None else packed | part
        packed = moved(_as_i32(packed))
        for i, shift, bits in group:
            piece = (packed >> np.int32(shift)) & np.int32((1 << bits) - 1)
            if bits == 1:
                out_u[i] = piece != 0
            else:
                dt = np.dtype(uniq[i].dtype)
                out_u[i] = piece.astype(np.dtype(f"u{bits // 8}")) \
                    .astype(dt)
    for i, x in enumerate(uniq):
        if out_u[i] is None:
            words, rebuild = _to_words(xp, x)
            out_u[i] = rebuild([moved(w) for w in words])
    return [out_u[slot_of[id(x)]] for x in lanes]


def compaction_rank(xp, keep, cap: int):
    """Where each row goes in a stable partition, kept rows first
    (int32[cap]), in closed form: a prefix sum and no sort."""
    from .scan import cumsum_fast
    k = keep.astype(xp.int32)
    seen = cumsum_fast(xp, k)           # kept rows up to and with this one
    pos = xp.arange(cap, dtype=xp.int32)
    return xp.where(keep, seen - 1, seen[-1] + pos - seen)


def _u32_pieces(xp, w) -> list:
    """(uint32 lane, bits) pieces of one integer key word, least
    significant first, which order as the word does: one for words of up
    to 32 bits, two for 64."""
    dt = np.dtype(w.dtype)
    if dt == np.bool_:
        return [(w.astype(xp.uint32), 1)]
    if dt.kind not in "iu":
        raise TypeError(f"sort key words are integers, not {dt}")
    bits = 8 * dt.itemsize
    if dt.kind == "i":
        # two's complement orders like unsigned once the sign bit flips
        u = np.dtype(f"u{dt.itemsize}")
        w = w.astype(u) ^ u.type(1 << (bits - 1))
    if bits <= 32:
        return [(w.astype(xp.uint32), bits)]
    return [(w.astype(xp.uint32), 32),
            ((w >> np.uint64(32)).astype(xp.uint32), 32)]


def _digits(xp, key_words, cap: int) -> list:
    """The key as radix digits, least significant first.  A pass sorts 64
    bits and the tie-break needs `pos_bits` of them, so a digit is as
    many adjacent pieces as fit the rest: the subquery's (live flag, null
    flag, int64) key is two digits at 33,554,432 rows, not four."""
    pos_bits = (cap - 1).bit_length()
    digits: list = []
    room = 0
    for w in reversed(list(key_words)):
        for piece in _u32_pieces(xp, w):
            if piece[1] > room:
                digits.append([])
                room = 64 - pos_bits
            digits[-1].append(piece)
            room -= piece[1]
    return digits


def _digit_pass(xp, digit, tie, cap: int):
    """The tie-breaks (int32, a permutation of 0..cap-1, row-aligned with
    the digit) in ascending order of (digit, tie-break)."""
    total = sum(bits for _, bits in digit)
    wide = np.uint32 if total <= 32 else np.uint64
    value, shift = None, 0
    for lane, bits in digit:
        part = lane.astype(wide) << wide(shift)
        value = part if value is None else value | part
        shift += bits
    if total <= 32:
        return _sort_pass(value, tie)[1]
    # more than one operand's worth: the low bits ride above the
    # tie-break in the second operand, biased to order as unsigned
    pos_bits = (cap - 1).bit_length()
    both = (value << np.uint64(pos_bits)) | tie.astype(xp.uint64)
    low = _as_i32(both.astype(xp.uint32) ^ _SIGN32)
    out = _sort_pass((both >> np.uint64(32)).astype(xp.uint32), low)[1]
    return (out ^ np.int32(-2**31)) & np.int32((1 << pos_bits) - 1)


def _lean_perm(xp, key_words, cap: int, want_order: bool = True,
               want_rank: bool = True):
    """(order, rank) of the stable ascending lexicographic sort by integer
    key words, most significant first; one not wanted may be None.
    `order[j]` is the row at place j, `rank[r]` the place of row r.

    A least-significant-digit radix sort in which nothing but the
    permutation moves: each pass sorts (digit, rank so far) with both in
    row order, which is stable because the rank is the tie-break, and
    costs the passes that compose its answer onto the order and the rank
    (two digits: 5 passes for a rank, where gathering digits and order
    between position-keyed passes as sort-pass moves would take 6, and
    four unpacked digits 16)."""
    iota = xp.arange(cap, dtype=xp.int32)
    if len(key_words) == 1 and np.dtype(key_words[0].dtype) == np.bool_:
        rank = compaction_rank(xp, ~key_words[0], cap)
        return (_inverse(xp, rank, cap) if want_order else None), rank
    digits = _digits(xp, key_words, cap)
    if not digits:
        return iota, iota
    order = rank = None
    for k, digit in enumerate(digits):
        last = k == len(digits) - 1
        q = _digit_pass(xp, digit, iota if k == 0 else rank, cap)
        if k == 0:
            order = q
            rank = _inverse(xp, q, cap) if (want_rank or not last) else None
            continue
        # q[j] is the OLD rank of the row now at place j
        inv_q = _inverse(xp, q, cap)
        old_order = order
        # rank[r] = inv_q[old_rank[r]];  order[j] = old_order[q[j]]
        rank = _sort_pass(old_order.astype(xp.uint32), inv_q)[1] \
            if (want_rank or not last) else None
        order = _sort_pass(inv_q.astype(xp.uint32), old_order)[1] \
            if (want_order or not last) else None
    return order, rank


def stable_argsort(xp, key_words, cap: int):
    """Stable ascending lexicographic argsort (int32[cap]) on the device
    by integer key words, most significant first."""
    return _lean_perm(xp, key_words, cap, want_rank=False)[0]


def carriable(col: DeviceColumn) -> bool:
    """True when every lane of the column is row-aligned (no stored
    offsets anywhere in the tree: a fixed-width string stores none), so a
    row permutation is just a lane permute."""
    if col.has_offsets:
        return False
    return all(carriable(c) for c in col.children)


def _permute_col_np(col: DeviceColumn, order) -> DeviceColumn:
    import jax
    return jax.tree_util.tree_map(lambda lane: lane[order], col)


def sort_rows(xp, key_words: Sequence, cols: Sequence[DeviceColumn],
              cap: int, extras: Sequence = (), need_order: bool = True):
    """Stable ascending lexicographic sort by `key_words`; rows of `cols`
    and the 1-D arrays in `extras` travel with the permutation: the rank,
    then a move per lane.

    Returns (order:int32[cap], out_cols, out_extras).  Non-carriable
    columns are gathered by `order` (validity preserved; a permutation
    never invents nulls).  A caller that drops the order says so with
    `need_order=False` and may get None: the order costs a pass of its
    own."""
    import jax
    if xp is np:
        order = np.lexsort(tuple(reversed(list(key_words)))).astype(np.int32)
        out_extras = [e[order] for e in extras]
        out_cols = []
        for c in cols:
            if carriable(c):
                out_cols.append(_permute_col_np(c, order))
            else:
                ones = np.ones((cap,), dtype=bool)
                out_cols.append(gather_column(np, c, order, ones))
        return order, out_cols, out_extras

    flats = [jax.tree_util.tree_flatten(c) if carriable(c) else None
             for c in cols]
    _COUNTS.strings_aligned += sum(
        1 for c, f in zip(cols, flats) if f is not None and _is_string(c))
    lanes = [leaf for f in flats if f is not None for leaf in f[0]]
    lanes += list(extras)
    order, rank = _lean_perm(
        xp, key_words, cap, want_rank=bool(lanes),
        want_order=need_order or any(f is None for f in flats))
    moved = iter(move_lanes(xp, rank, lanes))
    out_cols = []
    for c, f in zip(cols, flats):
        if f is None:
            out_cols.append(_gather_span_column(xp, c, order, cap))
        else:
            out_cols.append(jax.tree_util.tree_unflatten(
                f[1], [next(moved) for _ in f[0]]))
    return order, out_cols, list(moved)


def sort_lanes(xp, key_words: Sequence, lanes: Sequence, cap: int,
               need_order: bool = True):
    """`sort_rows` of lanes alone: returns (order, sorted_lanes)."""
    order, _, out = sort_rows(xp, key_words, (), cap, extras=lanes,
                              need_order=need_order)
    return order, out


def compact_rows(xp, keep, cols: Sequence[DeviceColumn], cap: int,
                 extras: Sequence = (), need_order: bool = False):
    """Stable partition: rows with keep=True move to the front in
    original order.  A sort by the one-bit key `~keep`, whose rank is
    `compaction_rank`'s closed form: only the lanes' own passes run."""
    return sort_rows(xp, [~keep], cols, cap, extras=extras,
                     need_order=need_order)


def mask_validity(xp, col: DeviceColumn, mask) -> DeviceColumn:
    """AND `mask` into the validity of every node of a column tree —
    restores the 'padding rows are invalid' batch contract after a
    carry permutation moved rows past num_rows."""
    validity = mask if col.validity is None else (col.validity & mask)
    if col.fixed_width is not None:
        return col.with_word(col.word, validity)
    # children of span columns are child-cap aligned — only row-aligned
    # (struct) children can take the row mask
    children = col.children if col.offsets is not None else tuple(
        mask_validity(xp, c, mask) for c in col.children)
    return DeviceColumn(col.dtype, data=col.data, validity=validity,
                        offsets=col.offsets, data_hi=col.data_hi,
                        children=children)
