"""ICI all-to-all shuffle kernel.

The TPU-native replacement for the reference's accelerated shuffle data
path (ref: shuffle-plugin/.../UCX.scala:69 RDMA transport +
GpuPartitioning.scala:50-130 device-side slicing).  Where the reference
moves device buffers peer-to-peer over UCX, a TPU pod slice moves them
over ICI with a single XLA `all_to_all` collective issued inside
`shard_map` — the compiler schedules the transfers, no bounce buffers,
no handshake protocol.

Design (static shapes, one compile per schema):

  1. Each device stably sorts its rows by destination partition id and
     computes per-peer counts/starts — the on-device slicing step.  The
     rows travel with the sort: one rank, then a sort pass per 32-bit word
     of row-aligned lane (ops/carry.py; a gather is row-at-a-time on this
     chip and a pass is not).
  2. In the sorted lane a destination's rows are a run, so its row of
     the ``[n_parts, slot]`` send tensor is a contiguous slice (slot =
     per-peer row budget; default = local capacity so no row can
     overflow).  Columns with offsets are gathered through a
     ``[n_parts, slot]`` source-row index, built only when one is there;
     strings additionally pack their bytes into a
     ``[n_parts, char_slot]`` tensor via a vmapped searchsorted layout.
  3. One ``lax.all_to_all`` per leaf rides the ICI mesh axis.
  4. The receiver packs valid rows to the front, peer by peer: each
     peer's valid rows are a prefix of its row of the receive tensor, so
     they are ``n_parts`` contiguous copies at the running sums of the
     received counts.  Strings, arrays and maps are re-assembled into
     (offsets, children) form through the receive order.

Variable-width nested types (arrays/structs) fall back to the host
shuffle path, mirroring the reference's fallback to the stock Spark
shuffle when the accelerated transport cannot carry a batch
(ref: RapidsShuffleInternalManagerBase.scala:462 proxy fallback).
"""

from __future__ import annotations

import threading
from typing import List, Optional

import jax
import jax.numpy as jnp

from .. import types as t
from ..columnar.device import DeviceBatch, DeviceColumn
from ..ops.carry import carriable, sort_lanes, stable_argsort
from ..ops.scan import cumsum_fast


# ---------------------------------------------------------------------------
# What a program's build puts on the wire (read by obs/compileprof)
# ---------------------------------------------------------------------------

class _WireCounts(threading.local):
    bytes = 0     # bytes that leave a chip, summed over the mesh


_WIRE = _WireCounts()


def wire_byte_counts() -> dict:
    """Bytes the collectives traced on this thread so far hand to the
    interconnect, summed over the mesh, under the name a program's build
    record gives them.  A figure of static shapes: tracing a program
    raises it, so the difference around a `lower()` is what one dispatch
    of that program sends."""
    return {"ici_wire_bytes": _WIRE.bytes}


def wire_all_to_all(x, axis_name: str, n_parts: int):
    """Tiled all_to_all of a per-chip ``[n_parts, ...]`` send tensor: one
    slice stays, the other ``n_parts - 1`` leave, on each of the
    ``n_parts`` chips."""
    _WIRE.bytes += x.size * x.dtype.itemsize * (n_parts - 1)
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)


def wire_all_gather(x, axis_name: str, n_parts: int):
    """Tiled all_gather along axis 0: every chip's ``x`` goes to the
    ``n_parts - 1`` others."""
    _WIRE.bytes += x.size * x.dtype.itemsize * n_parts * (n_parts - 1)
    return jax.lax.all_gather(x, axis_name, axis=0, tiled=True)


def exchange_supported(dtypes) -> Optional[str]:
    """Return a reason string if the ICI path cannot carry these columns.
    Structs of fixed-width fields and arrays/maps of fixed-width
    elements ride the exchange; deeper nesting (string/span elements,
    struct elements) stages via host."""
    def fixed(dt) -> bool:
        return not isinstance(dt, (t.StringType, t.BinaryType,
                                   t.ArrayType, t.MapType, t.StructType))

    def ok(dt) -> bool:
        if isinstance(dt, t.ArrayType):
            return fixed(dt.element_type)
        if isinstance(dt, t.MapType):
            return fixed(dt.key_type) and fixed(dt.value_type)
        if isinstance(dt, t.StructType):
            return all(ok(f.data_type) and
                       not isinstance(f.data_type,
                                      (t.StringType, t.BinaryType))
                       for f in dt.fields)
        return True

    for dt in dtypes:
        if not ok(dt):
            return f"nested type {dt.name} falls back to host shuffle"
    return None


def allgather_supported(dtypes) -> Optional[str]:
    """Return a reason string if ``allgather_batch`` cannot replicate
    these columns.  A strict subset of ``exchange_supported``: the
    gather path has no span receive layout for arrays/maps (they raise
    NotImplementedError at runtime), so any planning gate admitting the
    replicate/allgather branch must check THIS predicate, not just the
    exchange one (the round-5 admit/crash mismatch,
    analysis/capabilities.py ALLGATHER_BATCH)."""
    def ok(dt) -> bool:
        if isinstance(dt, (t.ArrayType, t.MapType)):
            return False
        if isinstance(dt, t.StructType):
            return all(ok(f.data_type) for f in dt.fields)
        return True

    for dt in dtypes:
        if not ok(dt):
            return (f"array/map type {dt.name} rides the host broadcast "
                    f"fallback (no allgather span layout)")
    return None


def _flat_child_lanes(col: DeviceColumn):
    """(lanes, rebuild) for an array/map column of FLAT children: the
    child-aligned 1-D lanes sharing the column's offsets, and a function
    rebuilding the column from exchanged lanes.  (None, None) when a
    child is itself a span/struct (host fallback)."""
    def flat_lanes(c: DeviceColumn):
        if c.offsets is not None or c.children:
            return None
        out = [c.data]
        out.append(c.validity if c.validity is not None else
                   jnp.ones((int(c.data.shape[0]),), bool))
        if c.data_hi is not None:
            out.append(c.data_hi)
        return out

    per_child = [flat_lanes(ch) for ch in col.children]
    if any(x is None for x in per_child):
        return None, None
    lanes = [lane for ls in per_child for lane in ls]

    def rebuild(out_lanes, out_offs, validity):
        it = iter(out_lanes)
        children = []
        for ch, ls in zip(col.children, per_child):
            data = next(it)
            valid = next(it)
            new = DeviceColumn(ch.dtype, data=data, validity=valid)
            if ch.data_hi is not None:
                new.data_hi = next(it)
            children.append(new)
        return DeviceColumn(col.dtype, validity=validity,
                            offsets=out_offs, children=tuple(children))
    return lanes, rebuild


def _counts_starts(pid_key, n_parts: int):
    """Per-destination row counts and exclusive starts after a stable sort."""
    one_hot = pid_key[None, :] == jnp.arange(n_parts, dtype=pid_key.dtype)[:, None]
    counts = jnp.sum(one_hot.astype(jnp.int32), axis=1)
    starts = cumsum_fast(jnp, counts) - counts
    return counts, starts


def _span_send(offs, lanes, src_row, send_valid, n_parts: int, slot: int):
    """Pack a span column's child lanes into fixed-shape send tensors.

    `lanes` are 1-D child-aligned arrays (chars for strings; element
    data/validity lanes for arrays and maps — every lane shares `offs`).
    Returns (list of packed [P, child_slot] tensors, len_send [P, slot])."""
    child_slot = int(lanes[0].shape[0])
    lengths = offs[1:] - offs[:-1]
    row_len = jnp.where(send_valid, lengths[src_row], 0).astype(jnp.int32)
    # per-peer exclusive child starts [P, slot+1]
    child_start = jnp.concatenate(
        [jnp.zeros((n_parts, 1), jnp.int32), cumsum_fast(jnp, row_len, axis=1)],
        axis=1)
    total_children = child_start[:, -1]
    c = jnp.arange(child_slot, dtype=jnp.int32)

    def per_peer_src(cs, srow, tot):
        j = jnp.clip(jnp.searchsorted(cs, c, side="right") - 1, 0, slot - 1)
        within = c - cs[j]
        src_c = offs[srow[j]] + within
        valid_c = c < tot
        return jnp.clip(src_c, 0, child_slot - 1), valid_c

    src_c, valid_c = jax.vmap(per_peer_src)(child_start, src_row,
                                            total_children)
    packed = [jnp.where(valid_c, lane[src_c],
                        jnp.zeros((), lane.dtype))
              for lane in lanes]
    return packed, row_len


def _string_send(col: DeviceColumn, src_row, send_valid, n_parts: int,
                 slot: int):
    """Pack a string column into fixed-shape send tensors.

    Returns (chars_send [P, char_slot], len_send [P, slot])."""
    packed, row_len = _span_send(col.offsets, [col.data], src_row,
                                 send_valid, n_parts, slot)
    return packed[0], row_len


def _span_receive_layout(recv_len, ord2, n_parts: int, slot: int,
                         child_slot: int):
    """Shared re-assembly coordinates for received span lanes: returns
    (out_offs, peer_index, src_child_index, live_child_mask) so every
    child lane of the column gathers through one layout computation."""
    flat_rows = n_parts * slot
    len_flat = recv_len.reshape(flat_rows)
    out_len = len_flat[ord2]
    out_offs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), cumsum_fast(jnp, out_len)]).astype(jnp.int32)
    # per-source-peer exclusive child starts in the receive buffer
    recv_start = jnp.concatenate(
        [jnp.zeros((n_parts, 1), jnp.int32), cumsum_fast(jnp, recv_len, axis=1)],
        axis=1)
    out_child_cap = n_parts * child_slot
    c = jnp.arange(out_child_cap, dtype=jnp.int32)
    r = jnp.clip(jnp.searchsorted(out_offs, c, side="right") - 1,
                 0, flat_rows - 1)
    flat_src = ord2[r]
    p = flat_src // slot
    j = flat_src - p * slot
    src_c = jnp.clip(recv_start[p, j] + (c - out_offs[r]), 0,
                     child_slot - 1)
    live = c < out_offs[-1]
    return out_offs, p, src_c, live


def _string_receive(recv_chars, recv_len, ord2, n_parts: int, slot: int):
    """Re-assemble a received string column into (offsets, chars)."""
    char_slot = int(recv_chars.shape[1])
    out_offs, p, src_c, live = _span_receive_layout(
        recv_len, ord2, n_parts, slot, char_slot)
    out_chars = jnp.where(live, recv_chars[p, src_c], jnp.uint8(0))
    return out_chars, out_offs


def _row_lanes(col: DeviceColumn, out: list) -> list:
    """The row-aligned lanes of a column's tree, appended to `out`: every
    node's validity down to the first node with offsets, and the data
    words of the nodes without."""
    if col.validity is not None:
        out.append(col.validity)
    if col.offsets is None:
        out += [x for x in (col.data, col.data_hi) if x is not None]
        for ch in col.children:
            _row_lanes(ch, out)
    return out


def _send_runs(x, starts, send_valid, slot: int):
    """The ``[n_parts, slot]`` send tensor of a lane whose rows are sorted
    by destination: destination ``p``'s rows are the run that begins at
    ``starts[p]``, so its row of the tensor is a contiguous slice (the
    lane padded by ``slot`` zeros, for a run may begin anywhere), cut to
    the run's length by ``send_valid``."""
    padded = jnp.concatenate([x, jnp.zeros((slot,), x.dtype)])
    runs = [jax.lax.dynamic_slice(padded, (starts[p],), (slot,))
            for p in range(int(starts.shape[0]))]
    return jnp.where(send_valid, jnp.stack(runs), jnp.zeros((), x.dtype))


def _receive_runs(recv, recv_starts, out_live):
    """A received ``[n_parts, slot]`` tensor with its valid rows packed to
    the front, peer by peer.  Each peer's valid rows are a prefix of its
    row of the tensor, so peer ``p``'s row is copied whole to the running
    sum of the counts before it, in rising ``p``: each copy overwrites
    the dead tail of the one before, and ``out_live`` zeroes the last
    one's."""
    out = recv.reshape(-1)          # peer 0's rows are where they belong
    for p in range(1, int(recv.shape[0])):
        out = jax.lax.dynamic_update_slice(out, recv[p], (recv_starts[p],))
    return jnp.where(out_live, out, jnp.zeros((), out.dtype))


def exchange_by_pid(batch: DeviceBatch, pids, n_parts: int, axis_name: str,
                    slot: Optional[int] = None,
                    on_overflow: str = "error"):
    """Redistribute rows so the device at mesh position ``p`` along
    ``axis_name`` receives every row with ``pids == p``, in source-device
    order and, within a source, in source order; padding is zero and
    invalid.

    Must be called inside ``shard_map`` over a mesh with that axis (size
    ``n_parts``).  Returns a batch of capacity ``n_parts * slot``.

    No row-aligned lane is gathered: the rows are sorted by destination
    once (`carry.sort_lanes`: a rank and a sort pass per 32-bit word),
    sliced into the send tensors (`_send_runs`) and packed on arrival by
    contiguous copies (`_receive_runs`).  Only a batch that holds a
    column with offsets builds the ``[n_parts, slot]`` source-row index
    and the receive order its span layouts read.

    The send tensors are ``[n_parts, slot]`` — ``n_parts`` times the
    per-peer budget — so ``slot`` is the exchange's memory knob.  With
    the default ``on_overflow='error'``, ``slot < capacity`` is refused
    up front: a skewed destination would silently drop rows.  With
    ``on_overflow='guard'`` a sub-capacity slot is admitted and the
    return becomes ``(batch, ok)`` where ``ok`` is this shard's
    device-side bool that NO destination overflowed its budget — the
    speculative-sizing pattern (exec/base.py's deferred guards): the
    caller checks every shard's guard after the fetch and re-runs with
    ``slot=capacity`` on a miss, paying hash-shard-balanced joins
    ~``slot/capacity`` of the full exchange footprint."""
    cap = batch.capacity
    guarded = on_overflow == "guard"
    if on_overflow not in ("error", "guard"):
        raise ValueError(f"on_overflow={on_overflow!r}: "
                         f"expected 'error' or 'guard'")
    if slot is not None and slot < cap and not guarded:
        # a per-peer budget below the local capacity can silently drop rows
        # when one destination receives more than `slot` of them; there is
        # no in-graph way to signal that, so refuse up front
        raise ValueError(
            f"slot={slot} < capacity={cap}: a skewed partition could "
            f"overflow the per-peer budget; use slot >= capacity "
            f"(or on_overflow='guard')")
    slot = slot or cap
    live = batch.row_mask()
    pid_key = jnp.where(live, pids.astype(jnp.int32), n_parts)
    counts, starts = _counts_starts(pid_key, n_parts)
    j = jnp.arange(slot, dtype=jnp.int32)
    send_valid = j[None, :] < counts[:, None]                  # [P, slot]

    # rows sorted by destination, stably: one digit of a few bits
    has_spans = any(not carriable(c) for c in batch.columns)
    row_lanes: list = []
    for c in batch.columns:
        _row_lanes(c, row_lanes)
    order, sorted_lanes = sort_lanes(jnp, [pid_key.astype(jnp.uint32)],
                                     row_lanes, cap, need_order=has_spans)
    by_pid = {id(x): y for x, y in zip(row_lanes, sorted_lanes)}

    a2a = lambda x: wire_all_to_all(x, axis_name, n_parts)  # noqa: E731

    recv_valid = a2a(send_valid)
    flat_rows = n_parts * slot
    recv_counts = jnp.sum(recv_valid.astype(jnp.int32), axis=1)
    recv_starts = cumsum_fast(jnp, recv_counts) - recv_counts
    out_total = jnp.sum(recv_counts)
    out_live = jnp.arange(flat_rows, dtype=jnp.int32) < out_total

    def carried(x):
        """Row-aligned lane `x` on its new chip."""
        sent = _send_runs(by_pid[id(x)], starts, send_valid, slot)
        return _receive_runs(a2a(sent), recv_starts, out_live)

    src_row = ord2 = None
    if has_spans:
        # the span layouts index rows: where each send slot's row was,
        # and where each output row lies in the receive tensor
        send_pos = starts[:, None] + j[None, :]
        src_row = order[jnp.clip(send_pos, 0, cap - 1)]        # [P, slot]
        ord2 = stable_argsort(jnp, [~recv_valid.reshape(flat_rows)],
                              flat_rows)

    def move(col: DeviceColumn) -> DeviceColumn:
        # a lane of ones arrives as the rows that arrived
        recv_v = out_live if col.validity is None else carried(col.validity)
        if isinstance(col.dtype, (t.StringType, t.BinaryType)):
            chars_send, len_send = _string_send(col, src_row, send_valid,
                                                n_parts, slot)
            recv_chars = a2a(chars_send)
            recv_len = a2a(len_send)
            out_chars, out_offs = _string_receive(
                recv_chars, recv_len, ord2, n_parts, slot)
            return DeviceColumn(col.dtype, data=out_chars,
                                validity=recv_v, offsets=out_offs)
        if isinstance(col.dtype, t.StructType):
            # struct children are row-aligned: each field rides the same
            # permutation independently
            return DeviceColumn(col.dtype, validity=recv_v,
                                children=tuple(move(ch)
                                               for ch in col.children))
        if isinstance(col.dtype, (t.ArrayType, t.MapType)):
            # array/map of flat elements: every child lane shares the
            # offsets, so they ride one span layout (the string path
            # generalized — elements instead of bytes)
            lanes, rebuild = _flat_child_lanes(col)
            if lanes is None:
                raise NotImplementedError(
                    "nested span elements ride the host shuffle fallback")
            child_slot = int(lanes[0].shape[0])
            packed, row_len = _span_send(col.offsets, lanes, src_row,
                                         send_valid, n_parts, slot)
            recv_lanes = [a2a(x) for x in packed]
            recv_len = a2a(row_len)
            out_offs, p, src_c, live_c = _span_receive_layout(
                recv_len, ord2, n_parts, slot, child_slot)
            out_lanes = [jnp.where(live_c, rl[p, src_c],
                                   jnp.zeros((), rl.dtype))
                         for rl in recv_lanes]
            return rebuild(out_lanes, out_offs, recv_v)
        new_col = DeviceColumn(col.dtype, data=carried(col.data),
                               validity=recv_v)
        if col.data_hi is not None:
            new_col.data_hi = carried(col.data_hi)
        return new_col

    out = DeviceBatch([move(c) for c in batch.columns], out_total,
                      batch.names)
    if guarded:
        # no destination held more rows than its send budget (checked on
        # the send side, where the drop would happen)
        return out, jnp.all(counts <= jnp.int32(slot))
    return out


def allgather_batch(batch: DeviceBatch, axis_name: str,
                    n_parts: int) -> DeviceBatch:
    """Replicate every device's rows onto all devices (the ICI analog of
    the reference's broadcast exchange, ref GpuBroadcastExchangeExec.scala):
    each device ends up with the concatenation of all shards, valid rows
    compacted to the front."""
    cap = batch.capacity
    ag = lambda x: wire_all_gather(x, axis_name, n_parts)  # noqa: E731
    live = batch.row_mask()
    flat_rows = n_parts * cap
    valid_flat = ag(live)
    ord2 = stable_argsort(jnp, [~valid_flat], flat_rows)
    total = jnp.sum(valid_flat.astype(jnp.int32))
    out_live = jnp.arange(flat_rows, dtype=jnp.int32) < total

    def gather_col(col: DeviceColumn) -> DeviceColumn:
        validity = col.validity if col.validity is not None else \
            jnp.ones((cap,), bool)
        recv_v = ag(validity & live)[ord2] & out_live
        if isinstance(col.dtype, (t.StringType, t.BinaryType)):
            char_slot = int(col.data.shape[0])
            lengths = jnp.where(live, col.offsets[1:] - col.offsets[:-1], 0)
            recv_chars = ag(col.data).reshape(n_parts, char_slot)
            recv_len = ag(lengths).reshape(n_parts, cap)
            # source char starts inside each gathered shard = its own offsets
            out_chars, out_offs = _string_receive(
                recv_chars, recv_len, ord2, n_parts, cap)
            return DeviceColumn(col.dtype, data=out_chars,
                                validity=recv_v, offsets=out_offs)
        if isinstance(col.dtype, t.StructType):
            return DeviceColumn(col.dtype, validity=recv_v,
                                children=tuple(gather_col(ch)
                                               for ch in col.children))
        if isinstance(col.dtype, (t.ArrayType, t.MapType)):
            raise NotImplementedError(
                "array/map types ride the host broadcast fallback")
        out_data = ag(col.data)[ord2]
        out_data = jnp.where(out_live, out_data, jnp.zeros_like(out_data))
        new_col = DeviceColumn(col.dtype, data=out_data, validity=recv_v)
        if col.data_hi is not None:
            hi = ag(col.data_hi)[ord2]
            new_col.data_hi = jnp.where(out_live, hi, jnp.zeros_like(hi))
        return new_col

    return DeviceBatch([gather_col(c) for c in batch.columns], total,
                       batch.names)
