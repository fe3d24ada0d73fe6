"""Carry-sorts: permute whole rows through lax.sort payload operands.

Profiling the chip (round 4) showed a 1M-row gather costs ~20ms (~400MB/s
— XLA TPU gather is row-at-a-time) while adding payload operands to an
existing lax.sort is unmeasurable at the dispatch floor.  So every
sort-then-permute path in the engine (filter compaction, sort exec,
group-by, window ordering) carries its row data THROUGH the sort instead
of gathering afterwards.  Columns with span structure (strings, arrays,
maps — anything with offsets) cannot ride a row permutation and fall back
to gather_column on the carried iota.

The numpy engine mirrors the semantics with fancy indexing per lane.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from ..columnar.device import DeviceColumn
from .gather import gather_column

# ---------------------------------------------------------------------------
# Compile-lean mode
# ---------------------------------------------------------------------------
# The TPU compiler's time for a `lax.sort` is set by the sort's signature,
# not by how often a program repeats it (asked of the v5e compiler at
# 4,194,304 rows, PR 21: one stable (uint64, int32) sort 87 s, three of
# them in one program 90 s; a stable 2-key sort with two more payload
# operands 320 s; an unstable 2-key (uint32, int32) sort 23 s).  In lean
# mode every device sort in the engine is therefore built from passes of
# that ONE cheapest signature: `lean_argsort` below.  Gathers then move
# the payload, so warm cost rises by a gather per lane and per pass; what
# that costs on the chip has not been measured on this code.  The session
# picks the mode from spark.rapids.tpu.sort.compileLean.

_LEAN = True


def set_compile_lean(enabled: bool) -> None:
    global _LEAN
    _LEAN = bool(enabled)


def compile_lean_enabled() -> bool:
    return _LEAN


def _u32_digits(xp, w) -> list:
    """The order-preserving uint32 digits of one integer key word, least
    significant first: one for words of up to 32 bits, two for 64."""
    dt = np.dtype(w.dtype)
    if dt == np.bool_:
        return [w.astype(xp.uint32)]
    if dt.kind not in "iu":
        raise TypeError(f"lean sort key words are integers, not {dt}")
    if dt.kind == "i":
        # two's complement orders like unsigned once the sign bit flips
        u = np.dtype(f"u{dt.itemsize}")
        w = w.astype(u) ^ u.type(1 << (8 * dt.itemsize - 1))
    if dt.itemsize <= 4:
        return [w.astype(xp.uint32)]
    return [w.astype(xp.uint32), (w >> np.uint64(32)).astype(xp.uint32)]


def lean_argsort(xp, key_words, cap: int):
    """Stable ascending lexicographic argsort (int32[cap]) by integer key
    words, most significant first: a least-significant-digit radix sort
    whose every pass is the same 2-operand (uint32 digit, int32 position)
    sort.  The position is the second key, which makes each pass stable
    without asking the compiler for a stable sort."""
    from jax import lax
    iota = xp.arange(cap, dtype=xp.int32)
    order = None
    for w in reversed(list(key_words)):
        for digit in _u32_digits(xp, w):
            kw = digit if order is None else digit[order]
            _, p = lax.sort((kw, iota), num_keys=2, is_stable=False)
            order = p if order is None else order[p]
    return iota if order is None else order


def stable_argsort(xp, key_words, cap: int):
    """Stable ascending lexicographic argsort (int32[cap]) on the device,
    in the session's sort mode: `lean_argsort`, or one multi-operand
    stable `lax.sort`."""
    if _LEAN or not key_words:
        return lean_argsort(xp, key_words, cap)
    from jax import lax
    iota = xp.arange(cap, dtype=xp.int32)
    return lax.sort(tuple(key_words) + (iota,), num_keys=len(key_words),
                    is_stable=True)[-1]


def _sort_rows_lean(xp, key_words, cols, cap, extras):
    """`sort_rows` by `lean_argsort`, then gather everything by the final
    order.  Same results as the carry path, far cheaper to compile."""
    order = lean_argsort(xp, key_words, cap)
    ones = xp.ones((cap,), dtype=bool)
    out_cols = [gather_column(xp, c, order, ones) for c in cols]
    out_extras = [e[order] for e in extras]
    return order, out_cols, out_extras


def carriable(col: DeviceColumn) -> bool:
    """True when every lane of the column is row-aligned (no offsets
    anywhere in the tree), so a row permutation is just a lane permute."""
    if col.offsets is not None:
        return False
    return all(carriable(c) for c in col.children)


def _permute_col_np(col: DeviceColumn, order) -> DeviceColumn:
    import jax
    return jax.tree_util.tree_map(lambda lane: lane[order], col)


def sort_rows(xp, key_words: Sequence, cols: Sequence[DeviceColumn],
              cap: int, extras: Sequence = ()):
    """Stable ascending lexicographic sort by `key_words`; rows of `cols`
    and the 1-D arrays in `extras` travel with the permutation.

    Returns (order:int32[cap], out_cols, out_extras).  Non-carriable
    columns are gathered by `order` (validity preserved; a permutation
    never invents nulls)."""
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

    if _LEAN:
        return _sort_rows_lean(xp, key_words, cols, cap, extras)

    from jax import lax
    iota = xp.arange(cap, dtype=xp.int32)
    operands: List = list(key_words) + [iota]
    # payload slots, deduped by traced-array identity (the same lane may
    # back several logical columns)
    slot_of: dict = {}
    flats: List[Tuple[object, object]] = []  # (treedef, leaf slot indices)
    for c in cols:
        if not carriable(c):
            flats.append((None, None))
            continue
        leaves, treedef = jax.tree_util.tree_flatten(c)
        idxs = []
        for leaf in leaves:
            key = id(leaf)
            if key not in slot_of:
                slot_of[key] = len(operands)
                operands.append(leaf)
            idxs.append(slot_of[key])
        flats.append((treedef, idxs))
    extra_idx = []
    for e in extras:
        key = id(e)
        if key not in slot_of:
            slot_of[key] = len(operands)
            operands.append(e)
        extra_idx.append(slot_of[key])
    res = lax.sort(tuple(operands), num_keys=len(key_words), is_stable=True)
    order = res[len(key_words)]
    out_cols = []
    for c, (treedef, idxs) in zip(cols, flats):
        if treedef is None:
            ones = xp.ones((cap,), dtype=bool)
            out_cols.append(gather_column(xp, c, order, ones))
        else:
            out_cols.append(jax.tree_util.tree_unflatten(
                treedef, [res[i] for i in idxs]))
    out_extras = [res[i] for i in extra_idx]
    return order, out_cols, out_extras


def sort_lanes(xp, key_words: Sequence, lanes: Sequence, cap: int):
    """Lane-only carry-sort: returns (order, sorted_lanes)."""
    order, _, out = sort_rows(xp, key_words, (), cap, extras=lanes)
    return order, out


def compact_rows(xp, keep, cols: Sequence[DeviceColumn], cap: int,
                 extras: Sequence = ()):
    """Stable partition: rows with keep=True move to the front in
    original order (ONE u8-key carry-sort)."""
    key = (~keep).astype(np.uint8 if xp is np else xp.uint8)
    return sort_rows(xp, [key], cols, cap, extras=extras)


def mask_validity(xp, col: DeviceColumn, mask) -> DeviceColumn:
    """AND `mask` into the validity of every node of a column tree —
    restores the 'padding rows are invalid' batch contract after a
    carry permutation moved rows past num_rows."""
    validity = mask if col.validity is None else (col.validity & mask)
    # children of span columns are child-cap aligned — only row-aligned
    # (struct) children can take the row mask
    children = col.children if col.offsets is not None else tuple(
        mask_validity(xp, c, mask) for c in col.children)
    return DeviceColumn(col.dtype, data=col.data, validity=validity,
                        offsets=col.offsets, data_hi=col.data_hi,
                        children=children)
