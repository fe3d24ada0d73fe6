"""String expressions over (offsets, bytes) tensors.

Ref: org/apache/spark/sql/rapids/stringFunctions.scala (+ GpuOverrides
string rules): Upper, Lower, Length, Substring, Concat, Trim family,
Contains/StartsWith/EndsWith, Like, StringReplace, StringRepeat, Reverse,
Lpad/Rpad, Locate/InStr, SubstringIndex.

All device kernels are O(char_cap)-style vectorized byte ops:
  * substring is UTF-8 character-correct via a global is-char-start prefix
    sum + per-row binary search;
  * literal search (contains/replace/locate) unrolls over the (static)
    needle bytes — one fused compare per needle byte;
  * replace builds the output with a per-input-byte contribution-length
    map (0 = inside a match, 1 = copied, R = match start emits the
    replacement) and a cumsum + searchsorted gather;
  * upper/lower handle ASCII exactly (non-ASCII passes through unchanged —
    gated behind incompatibleOps like the reference's corner-case ops).
"""

from __future__ import annotations

from typing import Optional

import numpy as np
from ..ops.scan import cumsum_fast

from .. import types as t
from ..columnar.device import DEFAULT_CHAR_BUCKETS, DeviceColumn, bucket_for
from ..ops import strings as sops
from .core import (ColumnValue, EvalContext, Expression, Literal,
                   ScalarValue, and_validity, evaluator, make_column,
                   validity_of)


def _string_input(ctx: EvalContext, v, dtype=t.STRING) -> DeviceColumn:
    from .conditional import _as_string_column
    return _as_string_column(ctx, v, dtype).col


def _literal_bytes(e: Expression) -> Optional[bytes]:
    if isinstance(e, Literal) and isinstance(e.dtype, (t.StringType,
                                                       t.BinaryType)):
        v = e.value
        if v is None:
            return None
        return v if isinstance(v, bytes) else str(v).encode()
    return None


def _char_starts(xp, chars):
    """bool per byte: UTF-8 sequence start (not a continuation byte)."""
    return (chars & np.uint8(0xC0)) != np.uint8(0x80)


class StringUnary(Expression):
    def __init__(self, child: Expression):
        self.children = (child,)

    def data_type(self):
        return t.STRING


class Upper(StringUnary):
    pass


class Lower(StringUnary):
    pass


def _case_map(e, ctx: EvalContext, upper: bool):
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    xp = ctx.xp
    c = col.data
    if upper:
        is_lo = (c >= ord("a")) & (c <= ord("z"))
        out = xp.where(is_lo, c - np.uint8(32), c)
    else:
        is_up = (c >= ord("A")) & (c <= ord("Z"))
        out = xp.where(is_up, c + np.uint8(32), c)
    return ColumnValue(DeviceColumn(t.STRING, data=out, offsets=col.offsets,
                                    validity=col.validity))


@evaluator(Upper)
def _eval_upper(e, ctx):
    return _case_map(e, ctx, True)


@evaluator(Lower)
def _eval_lower(e, ctx):
    return _case_map(e, ctx, False)


class Length(StringUnary):
    def data_type(self):
        return t.INT


@evaluator(Length)
def _eval_length(e, ctx: EvalContext):
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    xp = ctx.xp
    # Spark length() counts characters, not bytes
    starts = _char_starts(xp, col.data).astype(xp.int32)
    pre = xp.concatenate([xp.zeros((1,), xp.int32), cumsum_fast(xp, starts,
                                                              dtype=xp.int32)])
    nchars = pre[col.offsets[1:]] - pre[col.offsets[:-1]]
    return make_column(ctx, t.INT, nchars.astype(np.int32), col.validity)


class Ascii(StringUnary):
    """ascii(s): code point of the FIRST character; 0 for empty strings
    (ref stringFunctions.scala GpuAscii).  Full UTF-8 decode of the lead
    sequence (1-4 bytes), matching Spark's behavior on non-ASCII."""

    def data_type(self):
        return t.INT


@evaluator(Ascii)
def _eval_ascii(e, ctx: EvalContext):
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    xp = ctx.xp
    cap = max(int(col.data.shape[0]) - 1, 0)
    starts = col.offsets[:-1]
    lens = col.offsets[1:] - col.offsets[:-1]

    def byte_at(k):
        ok = lens > k
        idx = xp.clip(starts + k, 0, cap)
        return xp.where(ok, col.data[idx],
                        xp.zeros((), col.data.dtype)).astype(np.int32)

    b0, b1, b2, b3 = byte_at(0), byte_at(1), byte_at(2), byte_at(3)
    c1 = b0                                              # 0xxxxxxx
    c2 = ((b0 & 0x1F) << 6) | (b1 & 0x3F)                # 110xxxxx
    c3 = ((b0 & 0x0F) << 12) | ((b1 & 0x3F) << 6) | (b2 & 0x3F)
    c4 = ((b0 & 0x07) << 18) | ((b1 & 0x3F) << 12) |         ((b2 & 0x3F) << 6) | (b3 & 0x3F)
    out = xp.where(b0 < 0x80, c1,
                   xp.where(b0 < 0xE0, c2,
                            xp.where(b0 < 0xF0, c3, c4)))
    out = xp.where(lens == 0, xp.zeros_like(out), out)
    return make_column(ctx, t.INT, out.astype(np.int32), col.validity)


class BitLength(StringUnary):
    def data_type(self):
        return t.INT


@evaluator(BitLength)
def _eval_bitlength(e, ctx):
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    lens = (col.offsets[1:] - col.offsets[:-1]) * 8
    return make_column(ctx, t.INT, lens.astype(np.int32), col.validity)


class Substring(Expression):
    """substring(str, pos, len) — 1-based, character semantics, negative
    pos counts from the end (Spark)."""

    def __init__(self, child, pos, length=None):
        self.children = (child, pos) + ((length,) if length is not None
                                        else ())

    def data_type(self):
        return t.STRING


@evaluator(Substring)
def _eval_substring(e: Substring, ctx: EvalContext):
    xp = ctx.xp
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    pv = e.children[1].eval(ctx)
    from .core import data_of
    pos = data_of(pv, ctx)
    if hasattr(pos, "astype"):
        pos = pos.astype(xp.int64)
    ln = None
    if len(e.children) > 2:
        lv = e.children[2].eval(ctx)
        ln = data_of(lv, ctx)
        if hasattr(ln, "astype"):
            ln = ln.astype(xp.int64)
    starts = _char_starts(xp, col.data).astype(xp.int64)
    pre = xp.concatenate([xp.zeros((1,), xp.int64), cumsum_fast(xp, starts)])
    row_char0 = pre[col.offsets[:-1]]
    nchars = pre[col.offsets[1:]] - row_char0
    # resolve 1-based/negative pos to 0-based char index
    p = pos if hasattr(pos, "shape") and getattr(pos, "shape", ()) else \
        xp.full((ctx.capacity,), np.int64(pos))
    # Spark substringSQL: raw start may be negative; end derives from the
    # RAW start, then both clamp into [0, nchars]
    start_raw = xp.where(p > 0, p - 1, xp.where(p < 0, nchars + p,
                                                xp.zeros_like(nchars)))
    if ln is None:
        end_raw = nchars
    else:
        lnv = ln if hasattr(ln, "shape") and getattr(ln, "shape", ()) else \
            xp.full((ctx.capacity,), np.int64(ln))
        end_raw = start_raw + xp.maximum(lnv, 0)
    start_c = xp.clip(start_raw, 0, nchars)
    end_c = xp.clip(end_raw, start_c, nchars)
    # char index -> byte position: searchsorted over the global char prefix
    def char_to_byte(ci):
        # start byte of (0-based) global char index g: first p with
        # pre[p+1] >= g+1
        tgt = row_char0 + ci
        return xp.searchsorted(pre[1:], tgt + 1,
                               side="left").astype(xp.int32)
    b0 = char_to_byte(start_c)
    b1 = char_to_byte(end_c)
    b0 = xp.clip(b0, col.offsets[:-1], col.offsets[1:])
    b1 = xp.clip(b1, b0, col.offsets[1:])
    # gather spans [b0, b1)
    new_lens = (b1 - b0).astype(xp.int32)
    valid = col.validity if col.validity is not None else \
        xp.ones((ctx.capacity,), dtype=bool)
    new_offs = xp.concatenate([
        xp.zeros((1,), xp.int32),
        cumsum_fast(xp, xp.where(valid, new_lens, 0), dtype=xp.int32)])
    out_cap = int(col.data.shape[0])
    q = xp.arange(out_cap, dtype=xp.int32)
    row = xp.clip(xp.searchsorted(new_offs[1:], q, side="right"),
                  0, ctx.capacity - 1).astype(xp.int32)
    src = xp.clip(b0[row] + (q - new_offs[row]), 0, out_cap - 1)
    chars = xp.where(q < new_offs[-1], col.data[src],
                     xp.zeros((), xp.uint8))
    return ColumnValue(DeviceColumn(t.STRING, data=chars, offsets=new_offs,
                                    validity=valid))


class Concat(Expression):
    def __init__(self, *children):
        self.children = tuple(children)

    def data_type(self):
        return t.STRING


class ConcatWs(Expression):
    def __init__(self, sep, *children):
        self.children = (sep,) + tuple(children)

    def data_type(self):
        return t.STRING

    @property
    def nullable(self):
        return self.children[0].nullable


@evaluator(Concat)
def _eval_concat(e: Concat, ctx: EvalContext):
    xp = ctx.xp
    cols = [_string_input(ctx, c.eval(ctx)) for c in e.children]
    cap = ctx.capacity
    validity = None
    for c in cols:
        cv = c.validity
        validity = cv if validity is None else (validity & cv) \
            if cv is not None else validity
    if validity is None:
        validity = xp.ones((cap,), dtype=bool)
    lens = [c.offsets[1:] - c.offsets[:-1] for c in cols]
    total_len = lens[0]
    for l in lens[1:]:
        total_len = total_len + l
    total_len = xp.where(validity, total_len, 0)
    new_offs = xp.concatenate([xp.zeros((1,), xp.int32),
                               cumsum_fast(xp, total_len, dtype=xp.int32)])
    out_cap = int(sum(int(c.data.shape[0]) for c in cols))
    out_cap = bucket_for(out_cap, DEFAULT_CHAR_BUCKETS)
    q = xp.arange(out_cap, dtype=xp.int32)
    row = xp.clip(xp.searchsorted(new_offs[1:], q, side="right"),
                  0, cap - 1).astype(xp.int32)
    local = q - new_offs[row]
    chars = xp.zeros((out_cap,), dtype=xp.uint8)
    prefix = xp.zeros((cap,), dtype=xp.int32)
    for c, l in zip(cols, lens):
        in_this = (local >= prefix[row]) & (local < (prefix + l)[row])
        src = xp.clip(c.offsets[:-1][row] + (local - prefix[row]), 0,
                      c.data.shape[0] - 1)
        chars = xp.where(in_this, c.data[src], chars)
        prefix = prefix + l
    chars = xp.where(q < new_offs[-1], chars, xp.zeros((), xp.uint8))
    return ColumnValue(DeviceColumn(t.STRING, data=chars, offsets=new_offs,
                                    validity=validity))


class Trim(StringUnary):
    mode = "both"


class TrimLeft(Trim):
    mode = "left"


class TrimRight(Trim):
    mode = "right"


def _trim_impl(e: Trim, ctx: EvalContext):
    xp = ctx.xp
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    cap = ctx.capacity
    is_sp = col.data == np.uint8(32)
    nsp = xp.concatenate([xp.zeros((1,), xp.int64),
                          cumsum_fast(xp, (~is_sp).astype(xp.int64))])
    o0 = col.offsets[:-1].astype(xp.int64)
    o1 = col.offsets[1:].astype(xp.int64)
    if e.mode in ("both", "left"):
        # first nonspace at/after o0
        b0 = xp.searchsorted(nsp, nsp[o0] + 1, side="left") - 1
        b0 = xp.minimum(b0.astype(xp.int32), o1.astype(xp.int32))
    else:
        b0 = o0.astype(xp.int32)
    if e.mode in ("both", "right"):
        # last nonspace before o1: position p with nsp[p+1] == nsp[o1]
        b1 = xp.searchsorted(nsp, nsp[o1], side="left")
        b1 = xp.maximum(b1.astype(xp.int32), b0)
    else:
        b1 = o1.astype(xp.int32)
    empty = nsp[o1] == nsp[o0]  # all spaces
    b0 = xp.where(empty, o0.astype(xp.int32), b0)
    b1 = xp.where(empty, o0.astype(xp.int32), b1)
    valid = col.validity if col.validity is not None else \
        xp.ones((cap,), dtype=bool)
    new_lens = b1 - b0
    new_offs = xp.concatenate([
        xp.zeros((1,), xp.int32),
        cumsum_fast(xp, xp.where(valid, new_lens, 0), dtype=xp.int32)])
    out_cap = int(col.data.shape[0])
    q = xp.arange(out_cap, dtype=xp.int32)
    row = xp.clip(xp.searchsorted(new_offs[1:], q, side="right"),
                  0, cap - 1).astype(xp.int32)
    src = xp.clip(b0[row] + (q - new_offs[row]), 0, out_cap - 1)
    chars = xp.where(q < new_offs[-1], col.data[src], xp.zeros((), xp.uint8))
    return ColumnValue(DeviceColumn(t.STRING, data=chars, offsets=new_offs,
                                    validity=valid))


evaluator(Trim)(_trim_impl)
from .core import _EVALUATORS  # noqa: E402
_EVALUATORS[TrimLeft] = _trim_impl
_EVALUATORS[TrimRight] = _trim_impl


class StringPredicate(Expression):
    def __init__(self, left, right):
        self.children = (left, right)

    def data_type(self):
        return t.BOOLEAN


class Contains(StringPredicate):
    pass


class StartsWith(StringPredicate):
    pass


class EndsWith(StringPredicate):
    pass


def _match_positions(xp, chars, needle: bytes, wildcard: int = -1):
    """bool per byte: needle matches starting at this byte (unrolled over
    the static needle).  Bytes equal to `wildcard` match anything."""
    n = chars.shape[0]
    m = xp.ones((n,), dtype=bool)
    for j, b in enumerate(needle):
        idx = xp.clip(xp.arange(n) + j, 0, n - 1)
        if b == wildcard:
            m = m & (xp.arange(n) + j < n)
        else:
            m = m & (chars[idx] == np.uint8(b)) & (xp.arange(n) + j < n)
    return m


def _contains_impl(e, ctx: EvalContext, kind: str):
    xp = ctx.xp
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    needle = _literal_bytes(e.children[1])
    if needle is None:
        if isinstance(e.children[1], Literal):
            return make_column(ctx, t.BOOLEAN,
                               xp.zeros((ctx.capacity,), bool), False)
        raise NotImplementedError("column needle requires literal")
    val = validity_of(v, ctx)
    o0 = col.offsets[:-1].astype(xp.int64)
    o1 = col.offsets[1:].astype(xp.int64)
    L = len(needle)
    if L == 0:
        return make_column(ctx, t.BOOLEAN,
                           xp.ones((ctx.capacity,), bool), val)
    m = _match_positions(xp, col.data, needle)
    if kind == "starts":
        data = (o1 - o0 >= L) & m[xp.clip(o0, 0, col.data.shape[0] - 1)]
    elif kind == "ends":
        p = xp.clip(o1 - L, 0, col.data.shape[0] - 1)
        data = (o1 - o0 >= L) & m[p]
    else:
        pre = xp.concatenate([xp.zeros((1,), xp.int64),
                              cumsum_fast(xp, m.astype(xp.int64))])
        hi = xp.clip(o1 - L + 1, o0, col.data.shape[0])
        data = (pre[hi] - pre[o0]) > 0
    return make_column(ctx, t.BOOLEAN, data, val)


@evaluator(Contains)
def _eval_contains(e, ctx):
    return _contains_impl(e, ctx, "contains")


@evaluator(StartsWith)
def _eval_startswith(e, ctx):
    return _contains_impl(e, ctx, "starts")


@evaluator(EndsWith)
def _eval_endswith(e, ctx):
    return _contains_impl(e, ctx, "ends")


class Like(Expression):
    """SQL LIKE with % wildcards (and _ only in fixed-length patterns)."""

    def __init__(self, child, pattern: Expression):
        self.children = (child, pattern)

    def data_type(self):
        return t.BOOLEAN

    def pattern_bytes(self):
        return _literal_bytes(self.children[1])


@evaluator(Like)
def _eval_like(e: Like, ctx: EvalContext):
    xp = ctx.xp
    pat = e.pattern_bytes()
    if pat is None:
        raise NotImplementedError("LIKE requires a literal pattern")
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    val = validity_of(v, ctx)
    lens = (col.offsets[1:] - col.offsets[:-1]).astype(xp.int64)
    if b"_" in pat and b"%" not in pat:
        # fixed-length with single-char wildcards (byte-level)
        L = len(pat)
        b, _ = sops.window_bytes(xp, col.offsets, col.data, max(L, 1))
        ok = lens == L
        for j, pb in enumerate(pat):
            if pb != ord("_"):
                ok = ok & (b[:, j] == np.uint8(pb))
        return make_column(ctx, t.BOOLEAN, ok, val)
    wc = ord("_")
    parts = pat.split(b"%")
    first, last = parts[0], parts[-1]
    middles = [p for p in parts[1:-1] if p]
    min_len = sum(len(p) for p in parts)
    data = lens >= min_len
    o0 = col.offsets[:-1].astype(xp.int64)
    o1 = col.offsets[1:].astype(xp.int64)
    cur = o0 + 0
    if first:
        m = _match_positions(xp, col.data, first, wc)
        data = data & (lens >= len(first)) & \
            m[xp.clip(o0, 0, col.data.shape[0] - 1)]
        cur = o0 + len(first)
    # middle tokens must appear in order
    for tok in middles:
        m = _match_positions(xp, col.data, tok, wc)
        pre = xp.concatenate([xp.zeros((1,), xp.int64),
                              cumsum_fast(xp, m.astype(xp.int64))])
        limit = o1 - len(last) - len(tok) + 1
        limit = xp.clip(limit, cur, col.data.shape[0])
        found = (pre[limit] - pre[xp.clip(cur, 0, col.data.shape[0])]) > 0
        # next position after the first occurrence >= cur
        tgt = pre[xp.clip(cur, 0, col.data.shape[0])]
        nxt = xp.searchsorted(pre, tgt + 1, side="left") - 1
        cur = xp.where(found, nxt + len(tok), limit + 1)
        data = data & found
    if last and len(parts) > 1:
        m = _match_positions(xp, col.data, last, wc)
        p = xp.clip(o1 - len(last), 0, col.data.shape[0] - 1)
        data = data & (lens >= len(last)) & m[p] & \
            (o1 - len(last) >= cur)
    elif len(parts) == 1:
        data = data & (lens == len(pat))
    return make_column(ctx, t.BOOLEAN, data, val)


class StringReplace(Expression):
    def __init__(self, child, search, replace):
        self.children = (child, search, replace)

    def data_type(self):
        return t.STRING


def _pattern_self_overlaps(pat: bytes) -> bool:
    """True if the pattern can overlap itself (proper border exists)."""
    for k in range(1, len(pat)):
        if pat[:len(pat) - k] == pat[k:]:
            return True
    return False


@evaluator(StringReplace)
def _eval_replace(e: StringReplace, ctx: EvalContext):
    xp = ctx.xp
    search = _literal_bytes(e.children[1])
    repl = _literal_bytes(e.children[2])
    if search is None or repl is None:
        raise NotImplementedError("replace requires literal search/replace")
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    val = col.validity
    if len(search) == 0:
        return ColumnValue(col)
    if _pattern_self_overlaps(search):
        # greedy non-overlapping selection is sequential; keep off TPU
        raise NotImplementedError(
            "replace with self-overlapping pattern")
    n = int(col.data.shape[0])
    L, R = len(search), len(repl)
    m = _match_positions(xp, col.data, search)
    # constrain matches within one row's span
    q = xp.arange(n, dtype=xp.int32)
    row = xp.clip(xp.searchsorted(col.offsets[1:], q, side="right"),
                  0, ctx.capacity - 1).astype(xp.int32)
    m = m & ((q + L) <= col.offsets[1:][row])
    # contribution length per input byte
    in_match_tail = xp.zeros((n,), dtype=bool)
    for j in range(1, L):
        idx = xp.clip(xp.arange(n) - j, 0, n - 1)
        in_match_tail = in_match_tail | (m[idx] & (xp.arange(n) >= j))
    cl = xp.where(m, np.int32(R), xp.where(in_match_tail, np.int32(0),
                                           np.int32(1)))
    cpre = xp.concatenate([xp.zeros((1,), xp.int32),
                           cumsum_fast(xp, cl, dtype=xp.int32)])
    new_offs = cpre[col.offsets]
    out_cap = bucket_for(max(int(n * max(1, (R + L - 1) // L)), 1),
                         DEFAULT_CHAR_BUCKETS) if R > L else \
        bucket_for(max(n, 1), DEFAULT_CHAR_BUCKETS)
    p = xp.arange(out_cap, dtype=xp.int32)
    src = xp.clip(xp.searchsorted(cpre[1:], p, side="right"), 0,
                  n - 1).astype(xp.int32)
    within = p - cpre[src]
    rbytes = xp.asarray(np.frombuffer(repl.ljust(max(R, 1), b"\0"),
                                      dtype=np.uint8))
    out = xp.where(m[src], rbytes[xp.clip(within, 0, max(R - 1, 0))],
                   col.data[src])
    total = cpre[-1]
    out = xp.where(p < total, out, xp.zeros((), xp.uint8))
    return ColumnValue(DeviceColumn(t.STRING, data=out, offsets=new_offs,
                                    validity=val))


class StringRepeat(Expression):
    def __init__(self, child, times):
        self.children = (child, times)

    def data_type(self):
        return t.STRING


@evaluator(StringRepeat)
def _eval_repeat(e: StringRepeat, ctx: EvalContext):
    xp = ctx.xp
    from .core import data_of
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    tv = e.children[1].eval(ctx)
    times = data_of(tv, ctx)
    cap = ctx.capacity
    if not (hasattr(times, "shape") and getattr(times, "shape", ())):
        times = xp.full((cap,), np.int64(int(times)))
    times = xp.clip(times.astype(xp.int64), 0, 64)
    lens = (col.offsets[1:] - col.offsets[:-1]).astype(xp.int64)
    valid = and_validity(ctx, col.validity, validity_of(tv, ctx))
    if valid is None:
        valid = xp.ones((cap,), dtype=bool)
    elif valid is False:
        valid = xp.zeros((cap,), dtype=bool)
    new_lens = xp.where(valid, lens * times, 0)
    new_offs = xp.concatenate([xp.zeros((1,), xp.int32),
                               cumsum_fast(xp, new_lens, dtype=xp.int64)
                               .astype(xp.int32)])
    out_cap = bucket_for(max(int(col.data.shape[0]) * 4, 1),
                         DEFAULT_CHAR_BUCKETS)
    q = xp.arange(out_cap, dtype=xp.int64)
    row = xp.clip(xp.searchsorted(new_offs[1:], q, side="right"),
                  0, cap - 1).astype(xp.int32)
    local = q - new_offs[row]
    ln = xp.maximum(lens[row], 1)
    src = xp.clip(col.offsets[:-1][row].astype(xp.int64) + local % ln, 0,
                  col.data.shape[0] - 1)
    chars = xp.where(q < new_offs[-1], col.data[src], xp.zeros((), xp.uint8))
    return ColumnValue(DeviceColumn(t.STRING, data=chars, offsets=new_offs,
                                    validity=valid))


class Reverse(StringUnary):
    """Byte-wise reverse (exact for ASCII; gated for multi-byte UTF-8)."""


@evaluator(Reverse)
def _eval_reverse(e, ctx: EvalContext):
    xp = ctx.xp
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    n = int(col.data.shape[0])
    q = xp.arange(n, dtype=xp.int32)
    row = xp.clip(xp.searchsorted(col.offsets[1:], q, side="right"),
                  0, ctx.capacity - 1).astype(xp.int32)
    o0 = col.offsets[:-1][row]
    o1 = col.offsets[1:][row]
    src = xp.clip(o1 - 1 - (q - o0), 0, n - 1)
    in_span = q < col.offsets[-1]
    chars = xp.where(in_span, col.data[src], col.data)
    return ColumnValue(DeviceColumn(t.STRING, data=chars,
                                    offsets=col.offsets,
                                    validity=col.validity))


class StringLocate(Expression):
    """locate(substr, str, start=1): 1-based position, 0 = not found."""

    def __init__(self, substr, child, start=None):
        self.children = (substr, child) + ((start,) if start is not None
                                           else ())

    def data_type(self):
        return t.INT


@evaluator(StringLocate)
def _eval_locate(e: StringLocate, ctx: EvalContext):
    xp = ctx.xp
    needle = _literal_bytes(e.children[0])
    if needle is None:
        raise NotImplementedError("locate requires a literal substring")
    v = e.children[1].eval(ctx)
    col = _string_input(ctx, v)
    val = validity_of(v, ctx)
    o0 = col.offsets[:-1].astype(xp.int64)
    o1 = col.offsets[1:].astype(xp.int64)
    L = len(needle)
    if L == 0:
        return make_column(ctx, t.INT,
                           xp.ones((ctx.capacity,), np.int32), val)
    m = _match_positions(xp, col.data, needle)
    pre = xp.concatenate([xp.zeros((1,), xp.int64),
                          cumsum_fast(xp, m.astype(xp.int64))])
    start_off = o0
    if len(e.children) > 2:
        from .core import data_of
        sv = e.children[2].eval(ctx)
        s = data_of(sv, ctx)
        if not (hasattr(s, "shape") and getattr(s, "shape", ())):
            s = xp.full((ctx.capacity,), np.int64(int(s)))
        start_off = o0 + xp.clip(s.astype(xp.int64) - 1, 0, None)
    # first match position >= start_off
    base = pre[xp.clip(start_off, 0, col.data.shape[0])]
    first = xp.searchsorted(pre, base + 1, side="left") - 1
    limit = o1 - L
    found = (first <= limit) & (first >= start_off) & \
        (pre[xp.clip(o1 - L + 1, 0, col.data.shape[0])] - base > 0)
    posn = xp.where(found, first - o0 + 1, 0).astype(np.int32)
    return make_column(ctx, t.INT, posn, val)


class StringLPad(Expression):
    side = "left"

    def __init__(self, child, length, pad):
        self.children = (child, length, pad)

    def data_type(self):
        return t.STRING


class StringRPad(StringLPad):
    side = "right"


def _pad_impl(e: StringLPad, ctx: EvalContext):
    xp = ctx.xp
    from .core import data_of
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    pad = _literal_bytes(e.children[2]) or b" "
    lv = e.children[1].eval(ctx)
    target = data_of(lv, ctx)
    cap = ctx.capacity
    if not (hasattr(target, "shape") and getattr(target, "shape", ())):
        target = xp.full((cap,), np.int64(int(target)))
    target = xp.clip(target.astype(xp.int64), 0, 1 << 20)
    lens = (col.offsets[1:] - col.offsets[:-1]).astype(xp.int64)
    valid = col.validity if col.validity is not None else \
        xp.ones((cap,), dtype=bool)
    new_lens = xp.where(valid, target, 0)
    new_offs = xp.concatenate([xp.zeros((1,), xp.int32),
                               cumsum_fast(xp, new_lens).astype(xp.int32)])
    out_cap = bucket_for(max(int(col.data.shape[0]) * 2, 1024),
                         DEFAULT_CHAR_BUCKETS)
    q = xp.arange(out_cap, dtype=xp.int64)
    row = xp.clip(xp.searchsorted(new_offs[1:], q, side="right"),
                  0, cap - 1).astype(xp.int32)
    local = q - new_offs[row]
    strlen = xp.minimum(lens[row], target[row])
    padlen = target[row] - strlen
    pb = xp.asarray(np.frombuffer(pad, dtype=np.uint8))
    if e.side == "left":
        in_pad = local < padlen
        src_str = col.offsets[:-1][row].astype(xp.int64) + (local - padlen)
        pad_idx = local % len(pad)
    else:
        in_pad = local >= strlen
        src_str = col.offsets[:-1][row].astype(xp.int64) + local
        pad_idx = (local - strlen) % len(pad)
    src_str = xp.clip(src_str, 0, col.data.shape[0] - 1)
    chars = xp.where(in_pad, pb[xp.clip(pad_idx, 0, len(pad) - 1)],
                     col.data[src_str])
    chars = xp.where(q < new_offs[-1], chars, xp.zeros((), xp.uint8))
    return ColumnValue(DeviceColumn(t.STRING, data=chars, offsets=new_offs,
                                    validity=valid))


evaluator(StringLPad)(_pad_impl)
_EVALUATORS[StringRPad] = _pad_impl


class InitCap(StringUnary):
    """Capitalize the first letter of each word (ASCII)."""


@evaluator(InitCap)
def _eval_initcap(e, ctx: EvalContext):
    xp = ctx.xp
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    c = col.data
    n = c.shape[0]
    from ..ops.scan import shift_right
    prev = shift_right(xp, c, np.uint8(32))
    # word start: previous byte is space, or byte is at a row start
    row_start = xp.zeros((n,), dtype=bool)
    starts = xp.clip(col.offsets[:-1], 0, n - 1)
    if xp is np:
        row_start[starts] = True
    else:
        row_start = row_start.at[starts].set(True)
    word_start = (prev == 32) | row_start
    lo = xp.where((c >= 65) & (c <= 90), c + np.uint8(32), c)
    up = xp.where((c >= 97) & (c <= 122), c - np.uint8(32), c)
    out = xp.where(word_start, up, lo)
    return ColumnValue(DeviceColumn(t.STRING, data=out, offsets=col.offsets,
                                    validity=col.validity))


@evaluator(ConcatWs)
def _eval_concat_ws(e: ConcatWs, ctx: EvalContext):
    """concat_ws(sep, s1, s2, ...): null args are SKIPPED (unlike concat,
    which nulls the whole row); null separator -> null result
    (ref stringFunctions.scala GpuConcatWs semantics).  Host evaluation —
    the variable piece-skipping layout has no fixed-shape device form yet,
    so tagging keeps the projection on CPU like the regex family."""
    from .regex import _host_only, build_string_column, np_string_rows
    _host_only(ctx, "concat_ws")
    cap = ctx.capacity
    cols = [np_string_rows(_string_input(ctx, c.eval(ctx)), cap)
            for c in e.children]
    sep_rows, arg_rows = cols[0], cols[1:]
    out = []
    for i in range(cap):
        sep = sep_rows[i]
        if sep is None:
            out.append(None)
            continue
        out.append(sep.join(r[i] for r in arg_rows if r[i] is not None))
    return build_string_column(ctx, out)


class SubstringIndex(Expression):
    """substring_index(str, delim, count) (ref GpuSubstringIndex).

    Single-byte delimiters lower to a device occurrence scan; multi-byte
    delimiters need non-overlapping forward search (a sequential
    dependency) and stay on the host engine via tagging."""

    def __init__(self, child, delim, count):
        self.children = (child,)
        self.delim = delim
        self.count = int(count)

    def data_type(self):
        return t.STRING

    def delim_bytes(self) -> bytes:
        """The ONE definition of the delimiter's byte form — the tag rule
        and the evaluator both gate on its length, and divergence would
        turn a graceful host fallback into a runtime error."""
        return self.delim.encode() if isinstance(self.delim, str) \
            else bytes(self.delim)

    def sql(self):
        return (f"substring_index({self.children[0].sql()}, "
                f"'{self.delim}', {self.count})")


@evaluator(SubstringIndex)
def _eval_substring_index(e: SubstringIndex, ctx: EvalContext):
    xp = ctx.xp
    v = e.children[0].eval(ctx)
    col = _string_input(ctx, v)
    valid = col.validity if col.validity is not None else \
        xp.ones((ctx.capacity,), dtype=bool)
    delim = e.delim_bytes()
    cnt = e.count
    if xp is np:
        # host engine: python string semantics match Spark's indexOf scan
        out = []
        offs = np.asarray(col.offsets)
        chars = np.asarray(col.data)
        vm = np.asarray(valid)
        d = delim.decode("utf-8", "surrogateescape")
        for i in range(ctx.capacity):
            if not vm[i]:
                out.append("")
                continue
            sv = bytes(chars[offs[i]:offs[i + 1]]).decode(
                "utf-8", "surrogateescape")
            if cnt == 0 or not d:
                out.append("")
            elif cnt > 0:
                out.append(d.join(sv.split(d)[:cnt]))
            else:
                out.append(d.join(sv.split(d)[cnt:]))
        lens = np.array([len(o.encode("utf-8", "surrogateescape"))
                         for o in out], np.int32)
        new_offs = np.concatenate([np.zeros(1, np.int32),
                                   np.cumsum(lens, dtype=np.int32)])
        buf = b"".join(o.encode("utf-8", "surrogateescape") for o in out)
        cap_b = max(int(col.data.shape[0]), 1)
        data = np.zeros((cap_b,), np.uint8)
        data[:len(buf)] = np.frombuffer(buf, np.uint8)
        return ColumnValue(DeviceColumn(t.STRING, data=data,
                                        offsets=new_offs,
                                        validity=valid))
    if len(delim) != 1:
        from .core import EvalError
        raise EvalError("substring_index with multi-byte delimiter runs "
                        "on the host engine (tagging keeps it off the "
                        "device)")
    from ..ops.scan import cumsum_fast as _cs
    from ..ops.scan import fill_rows_from_starts
    char_cap = int(col.data.shape[0])
    cap = ctx.capacity
    b_row0 = col.offsets[:-1]
    b_row1 = col.offsets[1:]
    pos = xp.arange(char_cap, dtype=xp.int32)
    match = (col.data == np.uint8(delim[0])).astype(xp.int32)
    cm = _cs(xp, match)                  # inclusive global match count
    cmp_ = xp.concatenate([xp.zeros((1,), cm.dtype), cm])
    base = cmp_[xp.clip(b_row0, 0, char_cap)]
    total = cmp_[xp.clip(b_row1, 0, char_cap)] - base
    if cnt == 0:
        b0 = b_row0
        b1 = b_row0
    else:
        q = xp.full((cap,), np.int32(cnt)) if cnt > 0 else \
            (total + np.int32(cnt + 1)).astype(xp.int32)
        # char -> row, then per-char occurrence ordinal within its row
        spans = b_row1 - b_row0
        crow = xp.clip(
            fill_rows_from_starts(xp, b_row0.astype(xp.int32), spans > 0,
                                  char_cap), 0, cap - 1)
        occ = cm - base[crow]            # inclusive ordinal at match chars
        want = q[crow]
        hit = (match > 0) & (occ == want) & (pos < b_row1[crow]) & \
            (pos >= b_row0[crow])
        cand = xp.where(hit, pos, np.int32(2**31 - 1))
        import jax
        hitpos = jax.ops.segment_min(
            cand, crow, num_segments=cap)    # int32 scatter (~free)
        found = hitpos < np.int32(2**31 - 1)
        if cnt > 0:
            b0 = b_row0
            b1 = xp.where(found, xp.clip(hitpos, 0, char_cap), b_row1)
            b1 = xp.clip(b1, b_row0, b_row1)
        else:
            # q <= 0 means fewer occurrences than |cnt|: whole string
            b0 = xp.where((q > 0) & found,
                          xp.clip(hitpos + 1, 0, char_cap), b_row0)
            b0 = xp.clip(b0, b_row0, b_row1)
            b1 = b_row1
    new_lens = (b1 - b0).astype(xp.int32)
    new_offs = xp.concatenate([
        xp.zeros((1,), xp.int32),
        _cs(xp, xp.where(valid, new_lens, 0), dtype=xp.int32)])
    q2 = xp.arange(char_cap, dtype=xp.int32)
    row = xp.clip(fill_rows_from_starts(xp, new_offs[:-1].astype(xp.int32),
                                        new_lens > 0, char_cap),
                  0, cap - 1)
    src = xp.clip(b0[row] + (q2 - new_offs[row]), 0, char_cap - 1)
    chars = xp.where(q2 < new_offs[-1], col.data[src],
                     xp.zeros((), xp.uint8))
    return ColumnValue(DeviceColumn(t.STRING, data=chars,
                                    offsets=new_offs, validity=valid))
