"""Device (TPU) window + aggregation plans.

Reference semantics: core:query/processor/stream/window/{Length,Time,
LengthBatch}WindowProcessor.java + core:query/selector/attribute/
aggregator/{Sum,Count,Avg,Min,Max}AttributeAggregator — the reference
updates aggregates event-at-a-time via current/expired event pairs.

TPU-first reformulation: a micro-batch of T events is ONE fused array
program; the per-event "add current, remove expired, read aggregate"
loop becomes closed-form range reductions over the concatenated
[carry | batch] sequence:

  * sliding windows — each event's aggregate is a contiguous-range
    reduction ending at that event.  The left edge is position
    arithmetic for length(L) (the valid entries are one contiguous run:
    `_length_left`) and a vectorized `searchsorted` for time(D);
    sums/counts/avgs read a range of ONE compensated prefix sum (O(T);
    `_range_sum`: exact to the rounding of the range's own contents),
    min/max read a log2 sparse table (O(T log T) build, O(1) per query).
  * group-by — per-group prefixes come from ONE stable sort by the key
    words, which is the (segment, position) order: an entry's rank in it
    is the inverse of the permutation the sort returned (one more sort,
    `_to_arrival`), its window's first rank a search bounded to its own
    segment, both taken for the batch's rows only; no per-group state is
    kept at all for sliding windows.
  * lengthBatch(N) tumbling — per-event running aggregates restart at
    bucket boundaries: a segmented scan keyed by (bucket, group); rows
    emit only when their bucket completes (reference emits the whole
    chunk at batch boundary), so the incomplete bucket's raw events
    ride in the carry.

Carry state is a fixed-capacity device buffer packed at the right edge
(so [carry | batch] keeps global arrival order contiguous).  Every step
reports, in the word its result always had for the overflow flag, HOW MANY
entries it had to keep; a count over the capacity is an overflow, and the
host grows C to the power of two that holds the count (at least double)
and retries: one recompile for a window that needs 16x, not four.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..query import ast
from ..query.ast import AttrType
from .batch import EventBatch
from .expr import (CompiledExpr, ExprError, SingleStreamContext,
                   compile_expression, compute_dtypes, F32_MODE, jnp_dtype)
from .planner import (AGGREGATOR_NAMES, OutputBatch, PlanError, QueryPlan,
                      selector_has_aggregators)
from .schema import StreamSchema, TIMESTAMP_DTYPE, dtype_of
from .telemetry import call_kernel, device_wait, env_nbytes


class DeviceWindowUnsupported(Exception):
    pass


_INCR = {"sum", "count", "avg", "min", "max"}

F64 = jnp.float64
NEG = -jnp.inf
POS = jnp.inf
_TS_PAD = jnp.int64(2 ** 62)


def pow2_at_least(n: int, lo: int = 8) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


# ---------------------------------------------------------------------------
# vectorized building blocks
# ---------------------------------------------------------------------------

# Two forms chosen for what the TPU's compiler makes of them at a carry of
# 2^20 entries (`roomtemp10m`: N = C + T = 1,310,720; compiled for a described
# v5e on the sandbox's CPU, PR 48's builder's readings).  A 1-D
# `associative_scan` is unrolled level by level over the whole length: 150 s
# and 92 MB of code for ONE pair prefix, three of them a step.  A sort's
# compile grows with its operands: `lexsort` of two i64 keys (five u32
# operands) 301 s, `argsort` of an i64 key 183 s, a stable sort of one 32-bit
# key with one payload 26 s.  `_scan` lays the sequence out as rows (1.3 s for
# the same prefix); `_order_by_words` chains one-key sorts: the step compiles
# in 44 s (tests/test_chip_compile.py).  The rows are the faster program at
# `window1k`'s 263,168 entries too: its step 0.176 ms a call for 0.695 on the
# chip, 4.3 s of compiles for 14.3 (PERF.md section 5, PR 49).

_SCAN_ROW = 1024


def _scan(op, elems):
    """`jax.lax.associative_scan(op, elems)` over 1-D arrays of one length,
    laid out as rows of `_SCAN_ROW`: a scan along every row, a scan of the
    row totals, one combine.  (At most 2 log2(row) + 2 log2(rows) + 1
    applications of `op` on the way to any entry: no more than the
    2 log2 N + 1 the bound above `_two_sum` counts on.)  A sequence of
    fewer than two rows is scanned as it is."""
    tmap = jax.tree_util.tree_map
    n = jax.tree_util.tree_leaves(elems)[0].shape[0]
    if n < 2 * _SCAN_ROW:
        return jax.lax.associative_scan(op, elems)
    rows = -(-n // _SCAN_ROW)
    pad = rows * _SCAN_ROW - n      # behind the last entry: no prefix reads it

    def as_rows(a):
        if pad:
            a = jnp.concatenate([a, jnp.zeros(pad, a.dtype)])
        return a.reshape(rows, _SCAN_ROW)
    inner = jax.lax.associative_scan(op, tmap(as_rows, elems), axis=1)
    upto = jax.lax.associative_scan(op, tmap(lambda a: a[:, -1], inner))
    base = tmap(lambda u, a: jnp.broadcast_to(
        jnp.concatenate([u[:1], u[:-1]])[:, None], a.shape), upto, inner)
    first_row = (jnp.arange(rows) == 0)[:, None]
    return tmap(lambda c, a: jnp.where(first_row, a, c).reshape(-1)[:n],
                op(base, inner), inner)


def _words(c: jnp.ndarray) -> list:
    """An integer column as 32-bit words, most significant first: one word
    for a column of 32 bits or fewer, two for a 64-bit one.  Words compare
    as unsigned: an order, and the same for equal values, which is all that
    grouping asks."""
    u32 = lambda w: jax.lax.bitcast_convert_type(w, jnp.uint32)
    if c.dtype.itemsize <= 4:
        return [u32(c.astype(jnp.int32))]
    c = c.astype(jnp.int64)
    return [u32(_w_hi32(c)), u32(_w_lo32(c))]


def _sort_by_words(words: list) -> tuple:
    """(order, lead): arrival order sorted stably by `words` (most
    significant first), what `jnp.lexsort(words[::-1])` gives, as a chain of
    stable sorts of ONE 32-bit key and one payload, least significant word
    first; and `words[0][order]`, the last sort's own key (no gather)."""
    order = jnp.arange(words[0].shape[0], dtype=jnp.int32)
    for i, w in enumerate(reversed(words)):
        lead, order = jax.lax.sort((w if i == 0 else w[order], order),
                                   num_keys=1, is_stable=True)
    return order, lead


def _order_by_words(words: list) -> jnp.ndarray:
    """`_sort_by_words`' order alone."""
    return _sort_by_words(words)[0]


def _floor_log2(x: jnp.ndarray) -> jnp.ndarray:
    """floor(log2(x)) for int64 x >= 1, exact (no float rounding)."""
    res = jnp.zeros_like(x)
    for shift in (32, 16, 8, 4, 2, 1):
        m = x >= (jnp.int64(1) << shift)
        res = jnp.where(m, res + shift, res)
        x = jnp.where(m, x >> shift, x)
    return res


def _sparse_table(v: jnp.ndarray, is_max: bool) -> jnp.ndarray:
    """(J, N) table: row j reduces [i, i + 2^j)."""
    n = v.shape[0]
    neutral = NEG if is_max else POS
    op = jnp.maximum if is_max else jnp.minimum
    rows = [v]
    w = 1
    while w < n:
        prev = rows[-1]
        shifted = jnp.concatenate([prev[w:], jnp.full(w, neutral)])
        rows.append(op(prev, shifted))
        w *= 2
    return jnp.stack(rows)


def _range_reduce(table: jnp.ndarray, l: jnp.ndarray, r: jnp.ndarray,
                  is_max: bool) -> jnp.ndarray:
    """Reduce over inclusive ranges [l, r]; requires r >= l."""
    op = jnp.maximum if is_max else jnp.minimum
    j = _floor_log2(jnp.maximum(r - l + 1, 1))
    j = jnp.minimum(j, table.shape[0] - 1)
    half = jnp.left_shift(jnp.int64(1), j)
    return op(table[j, l], table[j, r - half + 1])


# One primitive serves every windowed, segmented or running SUM of this
# module: inclusive prefix sums carried as unevaluated (hi, lo) pairs of the
# plan's float type, hi + lo holding twice its precision, and a range taken
# as the component-wise difference of two of them.  (A difference of two
# plain f32 prefixes errs by the rounding of the PREFIXES, 2.9e7 and one ulp
# of 2 at 2^18 events of ~110, whatever the size of the window.)
#
# Bound, u = 2^-24 in f32.  `_pair_add` loses at most 2u^2 (|x| + |y|): one
# rounding of lo + lo, one of e + t, both of numbers under u (|x| + |y|);
# the two `_two_sum`s are error-free.  `associative_scan` builds a prefix
# from at most 2 log2 N of them, so a prefix over N <= 2^20 entries is off
# by at most 80 u^2 S = 2^-41.6 S, S the sequence's sum of |v|, and a
# difference of two by 2^-40.6 S.  `_range_sum` adds one rounding of the
# result, u R <= 1 ulp of R, R the RANGE's own sum of |v|.  Hence
#
#     |error| <= 16 ulps of R   wherever S <= 2^20 R
#
# (k = 16: 1 + 2^-40.6 x 2^20 x 2^24 = 1 + 2^3.4 < 16), which holds for
# every range, a single entry included, of up to 2^20 entries of one
# magnitude, wherever in the sequence it lies and whatever T and C are;
# measured on 0.01-step prices: half an ulp, the result's own rounding
# (tests/test_window_exact.py).  On values that are multiples of a step q
# with S under 2^46 q every pair is exact (hi holds the sum's leading 24
# bits, lo the rest, nothing rounds), so a range whose sum is under 2^24 q
# comes out EXACT: quarter-step prices, counts.

def _two_sum(a, b):
    """(s, e) with s = fl(a + b) and s + e == a + b exactly (Knuth)."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _pair_add(x, y):
    (xh, xl), (yh, yl) = x, y
    s, e = _two_sum(xh, yh)
    return _two_sum(s, e + (xl + yl))


def _prefix_pairs(v: jnp.ndarray) -> tuple:
    """Inclusive prefix sums of `v` as (hi, lo) pairs."""
    return _scan(_pair_add, (v, jnp.zeros_like(v)))


def _pair_diff(top: tuple, base: tuple) -> jnp.ndarray:
    """top - base of two (hi, lo) prefix pairs, rounded once."""
    (th, tl), (bh, bl) = top, base
    dh, de = _two_sum(th, -bh)
    return dh + (de + (tl - bl))


def _range_sum(pfx: tuple, lo: jnp.ndarray, hi=None) -> jnp.ndarray:
    """Sum of the scanned values over positions (lo, hi]; lo == -1 takes
    the range from the start.  `hi` None ends each range at its own index:
    `lo` then speaks for the LAST len(lo) positions (all of them, or the
    batch's rows behind the carry), whose prefix is a static slice."""
    ph, pl = pfx
    at = jnp.maximum(lo, 0)
    base = (jnp.where(lo >= 0, ph[at], 0.0), jnp.where(lo >= 0, pl[at], 0.0))
    tail = ph.shape[0] - lo.shape[0]
    return _pair_diff((ph[tail:], pl[tail:]) if hi is None
                      else (ph[hi], pl[hi]), base)


def _trailing_sum(pfx: tuple, L: int) -> jnp.ndarray:
    """Sum of the scanned values over each index's last L positions,
    (i - L, i], 0 < L < n: `_range_sum(pfx, arange(n) - L)` with the base
    pair read as a static shift by L (zeros in front), not a gather."""
    back = lambda p: jnp.concatenate([jnp.zeros(L, p.dtype), p[:-L]])
    return _pair_diff(pfx, (back(pfx[0]), back(pfx[1])))


def _length_left(gpos: jnp.ndarray, first_valid, L: int) -> jnp.ndarray:
    """Left edge of each position's length(L) window where the valid
    entries are ONE contiguous run that starts at `first_valid`: what
    `searchsorted(vcnt, maximum(vcnt - L, 0), side="right")` finds over
    the running count of valid entries, at every position of a run that
    is not empty and before it."""
    return jnp.maximum(gpos - (L - 1), first_valid)


_I32_MAX = 2 ** 31 - 1


def _clock_left(all_ts: jnp.ndarray, D: int, C: int, last) -> jnp.ndarray:
    """Left edge of the time(D) window of each of the batch's rows,
    positions C.. of the monotone clock `all_ts`: the first index whose
    clock lies after `all_ts[g] - D`, what
    `searchsorted(all_ts, all_ts - D, side="right")[C:]` finds, exact at
    every position of `C..last`, the batch's valid entries.  Only the
    batch's rows leave the step, so only they are asked for: T queries into
    the whole clock, not C + T (at `roomtemp10m`'s 2^20 + 2^18 a fifth of
    the gathers a round).  The clock is searched as a 32-bit offset from
    the newest valid entry wherever the oldest of those entries and its
    edge lie within 2^31 ms of it (`lax.cond`, a batch).  A clock as i64 is
    two words, and a binary search gathers both every round; at 1.31 M
    queries that search ran at one of two speeds from process to process,
    820 or 870 ms a step (the step 2,504 or 2,622 ms: 6 of 23 runs the slow
    kind over four leases; PERF.md 7.17), which alone spreads six seeds of
    `roomtemp10m.sat` by 3.9%, over half that cell's bound; on 32-bit
    offsets it took 197 ms for those 1.31 M queries (PR 49).  Older keys,
    the carry's empty slots and the batch's pads clamp to the ends of the
    range, on the side of the edge they lie on."""
    wide = lambda: jnp.searchsorted(all_ts, all_ts[C:] - D, side="right")
    if D > _I32_MAX:
        return wide()
    rel = all_ts - all_ts[last]         # <= 0 at every valid entry

    def narrow():
        as32 = lambda x: jnp.clip(x, -_I32_MAX, _I32_MAX).astype(jnp.int32)
        return jnp.searchsorted(as32(rel), as32(rel[C:] - D), side="right")
    return jax.lax.cond(rel[C] - D >= -_I32_MAX, narrow, wide)


def _run_flags(seg: jnp.ndarray) -> tuple:
    """(is_start, is_last) of each entry in its run of equal `seg`."""
    edge = seg[1:] != seg[:-1]
    one = jnp.array([True])
    return jnp.concatenate([one, edge]), jnp.concatenate([edge, one])


def _segment_start(seg: jnp.ndarray) -> jnp.ndarray:
    """Index of the first entry of each entry's run of equal `seg`."""
    n = seg.shape[0]
    is_start, _ = _run_flags(seg)
    return _scan(jnp.maximum,
                 jnp.where(is_start, jnp.arange(n, dtype=jnp.int32), 0))


def _segment_end(seg: jnp.ndarray) -> jnp.ndarray:
    """Index past the last entry of each entry's run of equal `seg`."""
    n = seg.shape[0]
    _, is_last = _run_flags(seg)
    ends = jnp.where(is_last, jnp.arange(1, n + 1, dtype=jnp.int32), n)
    return _scan(jnp.minimum, ends[::-1])[::-1]


# A group-by sorts arrival order ONCE (`_group_order`: stable, so positions
# ascend inside a group).  Everything that was looked up in that order by a
# binary search over 64-bit seg * n + pos keys is read off the permutation
# itself (PR 50; on the chip a sort of 1.31 M 32-bit keys is ~2 ms, one
# round of gathers of as many 7-24 ns an element, a scatter of as many
# 151 ms):

def _group_order(cols: list, valid: jnp.ndarray) -> tuple:
    """(order, seg_sorted) of a group-by over the key columns `cols`:
    arrival order sorted stably by (invalid, the keys' words), which is the
    (segment, position) order with the invalid entries last, and in it each
    entry's dense segment id (invalid -> n).  A float key groups by value
    (-0.0 with 0.0), as the interpreter's."""
    n = valid.shape[0]
    words = [(~valid).astype(jnp.uint32)]
    for c in cols:
        if c.dtype.kind == "f":
            c = c.astype(jnp.float64)
            c = jnp.where(c == 0.0, 0.0, c).view(jnp.int64)
        words += _words(c)
    order, inval = _sort_by_words(words)
    diff = jnp.zeros(n, dtype=bool)
    for w in words[1:]:
        diff = diff | _run_flags(w[order])[0]
    seg_sorted = jnp.cumsum(diff, dtype=jnp.int32) - 1
    return order, jnp.where(inval == 0, seg_sorted, n)


def _to_arrival(order: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """`x`, given in the sorted order (`x[r]` belongs to arrival index
    `order[r]`), in arrival order: the inverse permutation applied as ONE
    sort of (order, x) by `order` (the keys are unique, so no stability is
    asked for), where a scatter `.at[order].set(x)` was.  `x` = iota gives
    each entry's RANK in the order, what
    `searchsorted(seg[order] * n + order, seg * n + arange(n))` found."""
    return jax.lax.sort((order, x), num_keys=1, is_stable=False)[1]


def _first_at_or_after(order, lo, hi, left) -> jnp.ndarray:
    """Per query the first rank r of [lo, hi) with `order[r] >= left`, `hi`
    where there is none: [lo, hi) a run of `order` whose positions ascend
    (a segment).  A binary search of ONE 32-bit gather a round, for as many
    rounds as the longest run asked about needs (its bit length: 10 for
    `roomtemp10m`'s ~730 members a sensor, not 21 for N)."""
    rounds = 32 - jax.lax.clz(jnp.max(hi - lo, initial=0))

    def halve(_, lh):
        lo, hi = lh
        mid = (lo + hi) >> 1            # lo <= mid < hi wherever lo < hi
        before = order[mid] < left
        return (jnp.where(before & (mid < hi), mid + 1, lo),
                jnp.where(before, hi, mid))
    return jax.lax.fori_loop(0, rounds, halve, (lo, hi))[0]


def _seg_ranges(order, seg_sorted, left, live) -> tuple:
    """(order, first, own) of a grouped sliding window, for the batch's T
    rows (`left` and `live`, its valid rows, are T long): `order` the
    (segment, position) order with `seg_sorted` its segment ids (the
    group-by's own sort: `_group_order`), and in it each row's own rank and
    the rank of its segment's first member at or after `left`, what
    `searchsorted(ks, seg * n + gpos)` and `searchsorted(ks, seg * n +
    left)` found over ks = (seg * n + pos)[order] at every live row.  A
    step takes them ONCE, under a scope of its own
    (`window/segment_rank`): every windowed sum, count, min and max of it
    reads the same ranges."""
    with jax.named_scope("window/segment_rank"):
        n, T = order.shape[0], left.shape[0]
        own = _to_arrival(order, jnp.arange(n, dtype=jnp.int32))[n - T:]
        lo = jnp.where(live, _segment_start(seg_sorted)[own], 0)
        hi = jnp.where(live, _segment_end(seg_sorted)[own], 0)
        first = _first_at_or_after(order, lo, hi, left.astype(jnp.int32))
    return order, first, own


def _seg_window_sum(ranges, v):
    """Per-row sum over its segment's members in positions [left, gpos]:
    a range of the (segment, position) order, from the segment's first
    member at or after `left` to the row's entry itself."""
    order, first, own = ranges
    return _range_sum(_prefix_pairs(v[order]), first - 1, own)


def _seg_window_minmax(ranges, v, is_max):
    """Per-row min/max over its segment's members in positions
    [left, gpos]: a log2 sparse table over the (segment, position) order
    (the grouped analog of the ungrouped range-reduce; v must carry the
    neutral at invalid entries)."""
    order, first, own = ranges
    table = _sparse_table(v[order], is_max)
    return _range_reduce(table, jnp.minimum(first, own), own, is_max)


def _seg_running_sum(seg, v):
    """Per-entry running sum within its segment, arrival order."""
    order = _order_by_words(_words(seg))
    run = _range_sum(_prefix_pairs(v[order]),
                     _segment_start(seg[order]) - 1)
    return _to_arrival(order, run)


def _seg_running_minmax(seg, v, is_max):
    """Per-entry running min/max within its segment, arrival order."""
    order = _order_by_words(_words(seg))
    return _to_arrival(order, _mono_running_minmax(seg[order], v[order],
                                                   is_max))


# monotone-segment variants: when segment ids are nondecreasing in arrival
# order (no group-by, or bucket-only keys) the sort is a no-op — skip it

def _mono_running_sum(seg, v):
    return _range_sum(_prefix_pairs(v), _segment_start(seg) - 1)


def _mono_running_minmax(seg, v, is_max):
    is_start = jnp.concatenate([jnp.array([True]), seg[1:] != seg[:-1]])
    op = jnp.maximum if is_max else jnp.minimum

    def comb(a, b):
        af, av = a
        bf, bv = b
        return (af | bf, jnp.where(bf, bv, op(av, bv)))
    _f, run = _scan(comb, (is_start, v))
    return run


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------

class DeviceWindowAggPlan(QueryPlan):
    """`from S[f]#window.{length|time|lengthBatch}(..) select <aggs>
    [group by ...] [having ...] insert into O` as one fused device step."""

    C_START = 1024          # initial carry capacity for time windows
    L_CAP = 1 << 16         # larger length windows stay on host
    # device state commits only after a successful dispatch, so process()
    # is safe to retry with split batches (degradation ladder)
    retryable_process = True

    def __init__(self, name: str, rt, q: ast.Query,
                 inp: ast.SingleInputStream, target: Optional[str]):
        from ..interp.engine import extract_aggregators
        from ..interp.expr import PyExprContext

        self.name = name
        self.rt = rt
        self.output_target = target
        prec = ast.find_annotation(rt.app.annotations, "app:devicePrecision")
        self.f64 = prec is not None and str(prec.element()).lower() == "f64"
        self._mode = None if self.f64 else F32_MODE
        self.fdt = jnp.float64 if self.f64 else jnp.float32
        if q.rate is not None:
            raise DeviceWindowUnsupported("output rate limiting")
        if getattr(q.output, "events_for", ast.OutputEventsFor.CURRENT) \
                != ast.OutputEventsFor.CURRENT:
            raise DeviceWindowUnsupported("expired-events output")
        self._order_by = list(q.selector.order_by)
        self.limit, self.offset = q.selector.limit, q.selector.offset
        if any(isinstance(h, ast.StreamFunction) for h in inp.handlers):
            raise DeviceWindowUnsupported("stream functions")
        if inp.stream_id in rt.named_windows:
            raise DeviceWindowUnsupported("named-window input")

        schema = rt.schemas[inp.stream_id]
        self.in_schema = schema
        self.input_streams = (inp.stream_id,)
        if any(a.type == AttrType.OBJECT for a in schema.attributes):
            raise DeviceWindowUnsupported("object columns")

        # -- window spec ------------------------------------------------------
        wh = inp.window
        if wh is None:
            raise DeviceWindowUnsupported("no window")
        wname = self._wname = wh.name.lower()
        if wh.namespace is not None:
            raise DeviceWindowUnsupported(f"namespaced window {wname}")

        def _const(i):
            a = wh.args[i]
            if isinstance(a, ast.TimeConstant):
                return a.millis
            if isinstance(a, ast.Constant):
                return a.value
            raise DeviceWindowUnsupported("non-constant window arg")

        self._ext_ts_attr = None
        if wname == "length":
            self.kind = "length"
            self.L = int(_const(0))
            if self.L <= 0 or self.L > self.L_CAP:
                raise DeviceWindowUnsupported(f"length({self.L})")
            self.C = pow2_at_least(self.L)
        elif wname == "time":
            self.kind = "time"
            self.D = int(_const(0))
            self.C = self.C_START
        elif wname == "externaltime":
            # sliding event-time window: same closed-form range reduction
            # as `time`, with the window clock read from the declared
            # timestamp ATTRIBUTE instead of arrival time — no scheduler
            # at all (reference: ExternalTimeWindowProcessor.java expires
            # purely on arriving timestamps; meaningful expiry assumes
            # non-decreasing event time, as in the reference)
            self.kind = "time"
            var = wh.args[0]
            if not isinstance(var, ast.Variable):
                raise DeviceWindowUnsupported(
                    "externalTime timestamp must be an attribute")
            at = schema.type_of(var.attribute) \
                if var.attribute in schema.types else None
            if at not in (AttrType.INT, AttrType.LONG):
                raise DeviceWindowUnsupported(
                    "externalTime timestamp attribute must be int/long")
            self._ext_ts_attr = var.attribute
            self.D = int(_const(1))
            self.C = self.C_START
        elif wname == "externaltimebatch":
            # tumbling over an event-time attribute: lengthBatch's
            # segmented-scan machinery with ts-derived bucket ids
            # (reference: ExternalTimeBatchWindowProcessor.java:520 —
            # bucket boundaries at start + k*duration, flushed when an
            # arriving timestamp crosses the boundary)
            self.kind = "externaltimebatch"
            var = wh.args[0]
            if not isinstance(var, ast.Variable):
                raise DeviceWindowUnsupported(
                    "externalTimeBatch timestamp must be an attribute")
            at = schema.type_of(var.attribute) \
                if var.attribute in schema.types else None
            if at not in (AttrType.INT, AttrType.LONG):
                raise DeviceWindowUnsupported(
                    "externalTimeBatch timestamp attribute must be int/long")
            if len(wh.args) > 2:
                raise DeviceWindowUnsupported(
                    "externalTimeBatch start-time/timeout args")
            self._ext_ts_attr = var.attribute
            self.D = int(_const(1))
            self.C = self.C_START
        elif wname == "lengthbatch":
            self.kind = "lengthbatch"
            self.L = int(_const(0))
            if self.L <= 0 or self.L > self.L_CAP:
                raise DeviceWindowUnsupported(f"lengthBatch({self.L})")
            self.C = pow2_at_least(self.L)
        else:
            raise DeviceWindowUnsupported(f"window {wname}")

        # -- expressions ------------------------------------------------------
        ctx = SingleStreamContext(schema, rt.strings, inp.alias)
        try:
            self._filter = None
            if inp.filters:
                f = inp.filters[0].expr
                for g in inp.filters[1:]:
                    f = ast.And(f, g.expr)
                self._filter = compile_expression(f, ctx)
                if self._filter.type != AttrType.BOOL:
                    raise PlanError(f"filter must be boolean in {name!r}")

            self.group_keys: list[str] = []
            for g in q.selector.group_by:
                key, t = ctx.resolve(g)
                if t == AttrType.OBJECT:
                    raise DeviceWindowUnsupported("object group key")
                self.group_keys.append(key)

            pyctx = PyExprContext({inp.alias: schema, inp.stream_id: schema},
                                  default_ref=inp.alias)
            raw_sites: list = []
            rewritten = []
            sel = q.selector
            if sel.select_all:
                raise DeviceWindowUnsupported("select * with aggregation")
            for oa in sel.attributes:
                rewritten.append(
                    (oa.name, extract_aggregators(oa.expr, raw_sites, pyctx)))
            n_sel_sites = len(raw_sites)
            having_re = None
            if sel.having is not None:
                having_re = extract_aggregators(sel.having, raw_sites, pyctx)
            if not raw_sites:
                raise DeviceWindowUnsupported("no aggregates")

            site_args: list = []
            _collect_site_args([oa.expr for oa in sel.attributes]
                               + ([sel.having] if sel.having is not None
                                  else []), site_args)
            assert len(site_args) == len(raw_sites)
            self.sites = []
            for s, arg_ast in zip(raw_sites, site_args):
                if s.name not in _INCR:
                    raise DeviceWindowUnsupported(f"aggregator {s.name}()")
                arg_ce = (compile_expression(arg_ast, ctx)
                          if arg_ast is not None else None)
                # strings are dictionary codes on device: min()/max() would
                # compare codes, not lexicographic order, and sum()/avg()
                # would aggregate codes — fall back to the host interpreter
                # (advisor r2 HIGH finding)
                if arg_ce is not None and s.name in ("min", "max", "sum", "avg") \
                        and arg_ce.type not in (AttrType.INT, AttrType.LONG,
                                                AttrType.FLOAT, AttrType.DOUBLE):
                    raise DeviceWindowUnsupported(
                        f"{s.name}() over non-numeric ({arg_ce.type.name}) column")
                self.sites.append((s.name, arg_ce, s.out_type))

            extra = {f"__agg{i}": (f"__agg{i}", s.out_type)
                     for i, s in enumerate(raw_sites)}
            octx = SingleStreamContext(schema, rt.strings, inp.alias, extra)
            self.out_fns: list[CompiledExpr] = []
            names, types = [], []
            for nm, expr in rewritten:
                ce = compile_expression(expr, octx)
                self.out_fns.append(ce)
                names.append(nm)
                types.append(ce.type)
            self.having = None
            if having_re is not None:
                hextra = dict(extra)
                hextra.update({n: (n, t) for n, t in zip(names, types)})
                hctx = SingleStreamContext(schema, rt.strings, inp.alias,
                                           hextra)
                self.having = compile_expression(having_re, hctx)
                if self.having.type != AttrType.BOOL:
                    raise PlanError("having must be boolean")
        except ExprError as e:
            raise DeviceWindowUnsupported(str(e))

        self._out_names = names
        for ob in self._order_by:
            if ob.var.attribute not in names:
                raise DeviceWindowUnsupported(
                    f"order by {ob.var.attribute!r}: not an output column")
        self.out_schema = StreamSchema(target or f"#{name}", tuple(
            ast.Attribute(n, t) for n, t in zip(names, types)))

        # event columns the kernel reads
        reads: set = set()
        for ce in self.out_fns:
            reads |= set(ce.reads)
        if self._filter is not None:
            reads |= set(self._filter.reads)
        if self.having is not None:
            # output attribute names are injected into the having env
            reads |= set(self.having.reads) - set(names)
        for _nm, arg, _t in self.sites:
            if arg is not None:
                reads |= set(arg.reads)
        reads |= set(self.group_keys)
        # the sliding length kind never consults time (position-bounded,
        # and slim output rows reconstruct timestamps host-side): skip
        # the ts upload unless some expression reads __timestamp__.
        # lengthBatch still needs it — its non-slim output rows carry
        # device-side timestamps for events carried from prior batches.
        # externalTime reads its clock from an uploaded event COLUMN.
        if self._ext_ts_attr is not None and self.kind == "time" \
                and "__timestamp__" in reads:
            # sliding externalTime: the external column drives the window
            # CLOCK; expressions reading __timestamp__ must see the
            # ARRIVAL time (host parity) — carrying both per event isn't
            # worth it.  (externalTimeBatch carries arrival ts anyway for
            # its non-slim row stamps, so both are available there.)
            raise DeviceWindowUnsupported(
                "externalTime with __timestamp__-reading expressions")
        self._needs_ts = ((self.kind == "externaltimebatch")
                          or (self.kind != "length"
                              and self._ext_ts_attr is None)
                          or "__timestamp__" in reads)
        if self._ext_ts_attr is not None:
            reads.add(self._ext_ts_attr)
        reads.discard("__timestamp__")
        unknown = [k for k in reads
                   if k not in schema.types and not k.startswith("__agg")]
        if unknown:
            raise DeviceWindowUnsupported(f"unresolved columns {unknown}")
        self.cols = sorted(k for k in reads if k in schema.types)

        from .pipeline import DispatchPipeline
        self.pipeline_depth = rt.geometry["pipeline_depth"][0]
        self._pipe = DispatchPipeline(name, self._materialize,
                                      depth=self.pipeline_depth)

        # multi-chip: @app:deviceMesh('always') shards the batch axis T
        # over the mesh — XLA partitions the prefix/segmented scans and
        # inserts the cross-shard collectives (the jax way: annotate
        # shardings, let the partitioner place psum/permute chains).
        # Carry state replicates (it is O(window), not O(batch)).
        from .planner import mesh_for
        self.mesh = mesh_for(rt, "t")

        # what `window` (EXPLAIN / device_metrics) counts: each is a
        # recompile that a steady window of traffic may not hold
        self.counters = {"carry_overflow_reruns": 0, "carry_grows": 0}
        self._T = None              # the last dispatch's padded length
        # valid entries the last pulled step kept for the next (the word
        # that decides an overflow), and the most any has
        self._held = self._held_max = 0
        # the form each indexed pass of the step takes (`window_step` in
        # EXPLAIN / device_metrics): `_build_step_fn` traces what this
        # says, and it says what the query lets the plan see.  A length
        # window's edges and a tumbling kind's buckets are arithmetic on
        # positions, a time window's a search; an ungrouped length sum
        # reads its base prefix L entries back, a grouped one through the
        # (segment, position) order; no filter, nothing to compact.
        self.window_step = {
            "left_edge": "search" if self.kind == "time" else "arithmetic",
            "prefix_read": ("segmented" if self.group_keys else
                            "shift" if self.kind == "length" else "gather"),
            "compaction": "scatter" if self._filter is not None
            else "identity"}
        # beside it, how a group-by's ranks are taken (`window_ranks`; a
        # record of its own because a benchmark cell's test holds
        # `window_step` to its three keys): an entry's rank in the
        # (segment, position) order read off the permutation the group-by's
        # sort returned (`_to_arrival`; a search over 64-bit keys until
        # PR 50), a sliding window's first member searched inside its own
        # segment of that order (`_first_at_or_after`)
        sliding = self.kind in ("length", "time")
        self.window_ranks = {
            "segment_rank": "order" if self.group_keys else None,
            "window_first": "bounded_search"
            if self.group_keys and sliding else None}

        self.state = self._init_state()
        jax.eval_shape(self._step_fn(8, self.C), self.state, self._dummy(8))

    @property
    def window(self) -> dict:
        """The form this plan took: static but for `T` and
        `carry_capacity`, which move with a recompile, and the counters."""
        size = {"length": self.L} if hasattr(self, "L") \
            else {"duration_ms": self.D}
        return {"kind": self._wname, **size,
                "grouped": bool(self.group_keys),
                "sites": [nm for nm, _arg, _t in self.sites],
                "T": self._T, "carry_capacity": int(self.C),
                # sums are ranges of one (hi, lo) pair prefix
                # (`_range_sum`), which never restarts: no block
                "sum_form": "pair_prefix", "block": None,
                **self.counters}

    @property
    def window_carry(self) -> dict:
        """How full the carry is, beside `window`: `held` of `capacity`
        entries after the last step pulled, the most any step held, and
        what growing to it cost (`window`'s two counters)."""
        return {"capacity": int(self.C), "held": self._held,
                "held_max": self._held_max,
                "grows": self.counters["carry_grows"],
                "reruns": self.counters["carry_overflow_reruns"]}

    # -- state ---------------------------------------------------------------

    def _carry_cols(self) -> list:
        """Event columns that must ride in the carry buffer."""
        if self.kind in ("lengthbatch", "externaltimebatch"):
            return list(self.cols)      # rows emit later: full env needed
        need = set(self.group_keys)
        for _nm, arg, _t in self.sites:
            if arg is not None:
                need |= set(arg.reads) & set(self.in_schema.types)
        return sorted(need)

    EXT_START_SENTINEL = -(2 ** 62)

    def _init_state(self) -> dict:
        C = self.C
        st = {"ts": jnp.full(C, -_TS_PAD),
              "valid": jnp.zeros(C, dtype=bool),
              "seen": jnp.int64(0)}
        if self.kind == "externaltimebatch":
            st["start"] = jnp.int64(self.EXT_START_SENTINEL)
        for k in self._carry_cols():
            with compute_dtypes(self._mode):
                st[f"c.{k}"] = jnp.zeros(
                    C, dtype=jnp_dtype(self.in_schema.types[k]))
        return st

    def _dummy(self, T: int) -> dict:
        env = {"__nvalid__": jnp.int32(0)}
        if self._needs_ts:
            env["__ts_off__"] = jnp.zeros(T, jnp.int32)
            env["__ts_base__"] = jnp.int64(0)
        for k in self.cols:
            env[k] = jnp.zeros(T, dtype=jnp_dtype(self.in_schema.types[k]))
        return env

    def _grow(self, new_c: int) -> None:
        old = {k: np.asarray(v) for k, v in self.state.items()}
        self.C = new_c
        fresh = self._init_state()
        st = {}
        for k, f in fresh.items():
            o = old[k]
            if np.ndim(o) == 0:
                st[k] = jnp.asarray(o)
            else:
                pad = np.asarray(f).copy()
                pad[-o.shape[0]:] = o       # keep right-packing
                st[k] = jnp.asarray(pad)
        self.state = st

    # -- kernel --------------------------------------------------------------

    def _step_fn(self, T: int, C: int) -> Callable:
        """Per-instance cache (an lru_cache on the bound method would pin
        the plan instance and its compiled fns forever — advisor r2).
        Offset dtype (i32 vs rare i64 wide batches) needs no cache key:
        jit re-specializes on the __ts_off__ dtype."""
        cache = getattr(self, "_step_cache", None)
        if cache is None:
            cache = self._step_cache = {}
        fn = cache.get((T, C))
        if fn is None:
            fn = cache[(T, C)] = self._build_step_fn(T, C)
        return fn

    def _build_step_fn(self, T: int, C: int) -> Callable:
        kind = self.kind
        sites = self.sites
        group_keys = self.group_keys
        filt = self._filter
        out_fns = self.out_fns
        out_names = self._out_names
        having = self.having
        carry_cols = self._carry_cols()
        cols = self.cols
        ext_ts = self._ext_ts_attr
        L = getattr(self, "L", 0)
        D = getattr(self, "D", 0)
        N = C + T
        FDT = self.fdt
        forms = self.window_step
        out_types = [a.type for a in self.out_schema.attributes]
        # every phase runs under a jax.named_scope, so each device
        # operation of a trace names the phase it belongs to
        scope = jax.named_scope

        def site_vals(env_all, n):
            out = []
            for nm, arg, _t in sites:
                if arg is None or nm == "count":
                    out.append(jnp.ones(n, FDT))
                else:
                    out.append(arg.fn(env_all).astype(FDT))
            return out

        def group_order(env_all, gvalid):
            """`_group_order` of the group-by's key columns: the one sort
            of a grouped step, under `window/group`."""
            with scope("window/group"):
                return _group_order([env_all[g] for g in group_keys], gvalid)

        def bucket_seg(brel, env_all, all_valid):
            """The tumbling kinds' segment id per entry: its bucket, or
            (bucket, group) under a group-by."""
            if not group_keys:
                return brel
            seg = _to_arrival(*group_order(env_all, all_valid))
            return jnp.where(all_valid, brel * (N + 1) + seg,
                             jnp.int64((N + 2) * (N + 1)))

        def finish(env_all, aggs, row_ok):
            """Select + having over an aligned env; returns (outs, ok)."""
            with scope("window/select"):
                env2 = dict(env_all)
                for i, a in enumerate(aggs):
                    _nm, _arg, ot = sites[i]
                    env2[f"__agg{i}"] = _cast_site(a, ot)
                outs = [ce.fn(env2) for ce in out_fns]
                if having is not None:
                    henv = dict(env2)
                    for nm2, col in zip(out_names, outs):
                        henv[nm2] = col
                    row_ok = row_ok & having.fn(henv)
                return outs, row_ok

        def carry(state, seen, all_ts, pending, env_all, k):
            """The next state: the C entries that end at C + k.

            INVARIANT (the sliding kinds' closed forms rest on it): the
            carry is packed RIGHT, its `valid` a suffix.  The batch is
            compacted left (`bvalid` a prefix of k), so the valid entries
            of [carry | batch] are the one run [C - sum(valid), C + k);
            `pending` keeps a suffix of that run, and the slice that ends
            at C + k puts it at the right edge again.  `_grow` pads on the
            left; a snapshot restores the arrays as they were.
            (`all_ts` None: a window that reads no time carries its
            column through untouched.)"""
            sl = lambda a: jax.lax.dynamic_slice(a, (k,), (C,))
            nst = {"seen": seen, "valid": sl(pending),
                   "ts": state["ts"] if all_ts is None else sl(all_ts)}
            for c in carry_cols:
                nst[f"c.{c}"] = sl(env_all[c])
            return nst

        def running(segk, all_valid, vals):
            """The tumbling kinds' per-entry running aggregates within
            (bucket[, group]) segments `segk`.  No group-by: bucket ids are
            nondecreasing over [carry | batch] (the carry holds only the
            lowest incomplete bucket), so the sort inside the segmented
            scans is a no-op and is skipped."""
            if group_keys:
                rsum = lambda v_: _seg_running_sum(segk, v_)
                rmm = lambda v_, mx: _seg_running_minmax(segk, v_, mx)
            else:
                rsum = lambda v_: _mono_running_sum(segk, v_)
                rmm = lambda v_, mx: _mono_running_minmax(segk, v_, mx)
            aggs = []
            for i, (nm, _arg, _ot) in enumerate(sites):
                if nm in ("min", "max"):
                    neutral = NEG if nm == "max" else POS
                    vv = jnp.where(all_valid, vals[i], neutral)
                    with scope("window/minmax"):
                        aggs.append(rmm(vv, nm == "max"))
                    continue
                v = (all_valid.astype(FDT) if nm == "count"
                     else jnp.where(all_valid, vals[i], 0.0))
                with scope("window/sum"):
                    s = rsum(v)
                    if nm == "avg":
                        s = s / jnp.maximum(rsum(all_valid.astype(FDT)), 1.0)
                aggs.append(s)
            return aggs

        def step_sliding(state, bts, bvalid, bcols, k):
            """`bts` None: a length window no expression of which reads
            time (`_needs_ts`) builds, scans and carries no timestamps."""
            all_valid = jnp.concatenate([state["valid"], bvalid])
            env_all = {c: jnp.concatenate([state[f"c.{c}"], bcols[c]])
                       for c in carry_cols}
            all_ts = None
            if bts is not None:
                all_ts = _scan(                         # monotone
                    jnp.maximum, jnp.concatenate([state["ts"], bts]))
                env_all["__timestamp__"] = all_ts
            # only the batch's rows leave the step: every edge, rank and
            # prefix read below is taken for those T positions, C.. of
            # [carry | batch]; the sorts and the scans run over all N
            gpos = jnp.arange(C, N, dtype=jnp.int64)
            with scope("window/left_edge"):
                if forms["left_edge"] == "arithmetic":
                    # the valid run of [carry | batch] (see `carry`)
                    first_valid = C - jnp.sum(state["valid"],
                                              dtype=jnp.int64)
                    left = _length_left(gpos, first_valid, L)
                else:
                    left = _clock_left(all_ts, D, C,
                                       jnp.maximum(C + k - 1, 0))
            ranges = _seg_ranges(*group_order(env_all, all_valid), left,
                                 bvalid) if group_keys else None
            vals = site_vals(env_all, N)

            def wsum(v):
                """Windowed sum over [left, gpos] — per-group via the
                segmented machinery, else a range of arrival order (no
                sort): of a length window the last L entries, invalid
                ones holding zeros."""
                with scope("window/sum"):
                    if forms["prefix_read"] == "segmented":
                        return _seg_window_sum(ranges, v)
                    if forms["prefix_read"] == "shift":
                        return _trailing_sum(_prefix_pairs(v), L)[C:]
                    return _range_sum(_prefix_pairs(v), left - 1)

            def wcount():
                """Valid entries in [left, gpos], in the float type."""
                if forms["prefix_read"] == "shift":
                    # exact: an integer under 2^24
                    return jnp.clip(gpos - first_valid + 1, 0, L).astype(FDT)
                if forms["prefix_read"] == "segmented":
                    # the ranks ARE the count (the invalid entries sort
                    # last): what the range of a prefix of ones reads
                    _order, first, own = ranges
                    return (own - first + 1).astype(FDT)
                return wsum(all_valid.astype(FDT))

            aggs = []
            for i, (nm, _arg, _ot) in enumerate(sites):
                if nm in ("min", "max"):
                    neutral = NEG if nm == "max" else POS
                    vv = jnp.where(all_valid, vals[i], neutral)
                    with scope("window/minmax"):
                        if group_keys:
                            aggs.append(_seg_window_minmax(
                                ranges, vv, nm == "max"))
                            continue
                        table = _sparse_table(vv, nm == "max")
                        aggs.append(_range_reduce(
                            table, jnp.minimum(left, gpos), gpos,
                            nm == "max"))
                    continue
                if nm == "count":
                    aggs.append(wcount())
                    continue
                s = wsum(jnp.where(all_valid, vals[i], 0.0))
                if nm == "avg":
                    s = s / jnp.maximum(wcount(), 1.0)
                aggs.append(s)

            # rows align with the compacted batch part (raw timestamps:
            # the monotonic clamp is internal to expiry math only)
            benv = {c: bcols[c] for c in cols}
            if bts is not None:
                benv["__timestamp__"] = bts
            outs, row_ok = finish(benv, aggs, bvalid)

            # carry = last C entries ending at C+k, minus departed ones
            with scope("window/carry"):
                if forms["left_edge"] == "arithmetic":
                    start_k = jnp.maximum(C + k - L, first_valid)
                else:
                    last_ts = all_ts[jnp.maximum(C + k - 1, 0)]
                    start_k = jnp.searchsorted(all_ts, last_ts - D,
                                               side="right")
                keep = (jnp.arange(N) >= start_k) & all_valid
                nst = carry(state, state["seen"] + k, all_ts, keep, env_all,
                            k)
                kept = jnp.sum(keep, dtype=jnp.int32)
            return nst, outs, row_ok, bts, kept

        def step_lengthbatch(state, bts, bvalid, bcols, k):
            all_ts = jnp.concatenate([state["ts"], bts])
            all_valid = jnp.concatenate([state["valid"], bvalid])
            env_all = {c: jnp.concatenate([state[f"c.{c}"], bcols[c]])
                       for c in carry_cols}
            env_all["__timestamp__"] = all_ts
            # admission index: carried events resume their old positions
            with scope("window/left_edge"):
                base = state["seen"] - jnp.sum(state["valid"])  # multiple of L
                vrank = jnp.cumsum(all_valid.astype(jnp.int64)) - 1
                gidx = base + vrank
                brel = jnp.where(all_valid, (gidx - base) // L, -1)
            segk = bucket_seg(brel, env_all, all_valid)
            aggs = running(segk, all_valid, site_vals(env_all, N))
            total = base + jnp.sum(all_valid)
            completed = (total // L) * L
            emit = all_valid & (gidx < completed)
            outs, row_ok = finish(env_all, aggs, emit)
            with scope("window/carry"):
                pend = all_valid & (gidx >= completed)  # under L: it fits
                nst = carry(state, total, all_ts, pend, env_all, k)
                kept = jnp.sum(pend, dtype=jnp.int32)
            return nst, outs, row_ok, all_ts, kept

        def step_extbatch(state, bts, bvalid, bcols, k):
            """externalTimeBatch: lengthBatch's per-bucket segmented scans
            with bucket ids (ets - start) // D; completed buckets (any
            later-bucket event arrived) emit, the current bucket's raw
            events carry.  Assumes nondecreasing event time, as the
            reference does."""
            SENT = jnp.int64(DeviceWindowAggPlan.EXT_START_SENTINEL)
            all_ts = jnp.concatenate([state["ts"], bts])      # arrival
            all_valid = jnp.concatenate([state["valid"], bvalid])
            env_all = {c: jnp.concatenate([state[f"c.{c}"], bcols[c]])
                       for c in carry_cols}
            env_all["__timestamp__"] = all_ts
            with scope("window/left_edge"):
                ets = env_all[ext_ts].astype(jnp.int64)
                idx0 = jnp.argmax(all_valid)          # first valid entry
                first_e = ets[idx0]
                # latch the bucket anchor only when the block actually
                # holds a valid event: argmax over an all-False mask is 0,
                # and a fully-filtered first micro-batch would otherwise
                # latch a garbage carry-slot timestamp, permanently
                # shifting every bucket boundary vs the host path
                start = jnp.where((state["start"] == SENT)
                                  & jnp.any(all_valid),
                                  first_e, state["start"])
                Dj = jnp.int64(D)
                b = jnp.where(all_valid, (ets - start) // Dj, jnp.int64(-1))
                bfirst = b[idx0]
                brel = jnp.where(all_valid, b - bfirst, jnp.int64(-1))
                blast = jnp.max(b)                    # monotone ts: current
            segk = bucket_seg(brel, env_all, all_valid)
            aggs = running(segk, all_valid, site_vals(env_all, N))
            emit = all_valid & (b < blast)
            outs, row_ok = finish(env_all, aggs, emit)
            with scope("window/carry"):
                pend = all_valid & (b == blast)
                nst = carry(state, state["seen"] + k, all_ts, pend, env_all,
                            k)
                nst["start"] = start
                kept = jnp.sum(pend, dtype=jnp.int32)
            return nst, outs, row_ok, all_ts, kept

        def compact(mask, arr, fill):
            """`arr`'s entries under `mask` moved to the front, `fill`
            behind them.  With no filter the mask is a prefix already."""
            if forms["compaction"] == "identity":
                return jnp.where(mask, arr, fill)
            pos = jnp.cumsum(mask.astype(jnp.int32), dtype=jnp.int32) - mask
            wpos = jnp.where(mask, pos, T)
            return jnp.full((T,), fill, arr.dtype).at[wpos].set(
                arr, mode="drop")

        def step(state, env):
            with compute_dtypes(mode):
                # timestamps travel as offsets from a per-batch i64 base
                # and validity as a prefix count — 5 fewer upload bytes
                # per event than i64 ts + bool valid;
                # length kinds with no ts-reading expression skip ts
                # upload altogether (position-bounded, not time-bounded);
                # sliding externalTime's window clock is the declared
                # event column (externalTimeBatch keeps ARRIVAL time here
                # for its row stamps; its bucket ids read the column
                # inside step_extbatch)
                if ext_ts is not None and kind == "time":
                    ts64 = env[ext_ts].astype(jnp.int64)
                elif "__ts_off__" in env:
                    ts64 = env["__ts_base__"] \
                        + env["__ts_off__"].astype(jnp.int64)
                else:
                    ts64 = None         # sliding length: time is unread
                mask = jnp.arange(T, dtype=jnp.int32) < env["__nvalid__"]
                if filt is not None:
                    fenv = dict(env)
                    if ts64 is not None:
                        fenv["__timestamp__"] = ts64
                    mask = mask & filt.fn(fenv)
                # compact filtered events to the front: one i32 cumsum + one
                # scatter per column (a stable argsort here cost 244s of
                # XLA compile at T=16K and dominated runtime)
                with scope("window/compact"):
                    k = env["__nvalid__"].astype(jnp.int32) \
                        if forms["compaction"] == "identity" \
                        else jnp.sum(mask, dtype=jnp.int32)
                    bvalid = jnp.arange(T, dtype=jnp.int32) < k
                    bts = None if ts64 is None \
                        else compact(mask, ts64, _TS_PAD)
                    bcols = {c: compact(mask, env[c], 0) for c in cols}
                if kind == "lengthbatch":
                    res = step_lengthbatch(state, bts, bvalid, bcols, k)
                elif kind == "externaltimebatch":
                    res = step_extbatch(state, bts, bvalid, bcols, k)
                else:
                    res = step_sliding(state, bts, bvalid, bcols, k)
                with scope("window/pack"):
                    return pack(res, mask, k)

        def bits32(m):
            """(T,) bool -> (ceil(T/32),) i32 word stream, little-bit order."""
            n_ = m.shape[0]
            padded = -(-n_ // 32) * 32
            if padded != n_:
                m = jnp.concatenate([m, jnp.zeros(padded - n_, bool)])
            r = m.reshape(-1, 32).astype(jnp.uint32)
            w = (r << jnp.arange(32, dtype=jnp.uint32)[None, :]) \
                .sum(axis=1).astype(jnp.uint32)   # sum may promote to u64
            return jax.lax.bitcast_convert_type(w, jnp.int32)

        slim = kind not in ("lengthbatch", "externaltimebatch")
        has_filter = filt is not None

        def pack(res, mask, k):
            """Outputs travel in as few bytes as possible — every
            device->host pull pays a fixed cost plus a per-byte cost.
            Sliding kinds are `slim`: row timestamps equal
            the (filter-compacted) input timestamps, which the host already
            holds, so only a small `b` vector ([kept, k] + bit-packed
            masks when needed) plus the out columns travel.  lengthBatch
            rows can emit carried (previous-batch) events, so it keeps the
            full layout: [kept]+ok+ts hi/lo rows ahead of the columns.
            `kept`, the entries the next state has to hold, is the word
            that was the overflow flag: over the capacity, it overflowed
            (`_materialize` grows to it)."""
            nst, outs, row_ok, row_ts, kept = res
            n = row_ok.shape[0]
            irows, frows = [], []
            if slim:
                bparts = [jnp.stack([kept, k]).astype(jnp.int32)]
                if has_filter:
                    bparts.append(bits32(mask))
                if having is not None:
                    bparts.append(bits32(row_ok))
            else:
                meta = jnp.zeros((n,), jnp.int32).at[0].set(kept)
                row_ts = row_ts.astype(jnp.int64)
                irows += [meta, row_ok.astype(jnp.int32),
                          _w_hi32(row_ts), _w_lo32(row_ts)]
            # encode by DECLARED type so the host unpack (which switches on
            # the out schema) always reads the matching rows — the raw
            # device dtype may be widened (e.g. INT aggregates ride i64)
            for colv, t in zip(outs, out_types):
                colv = jnp.asarray(colv)
                if t == AttrType.DOUBLE and FDT == jnp.float64:
                    frows.append(colv.astype(jnp.float64))
                elif t in (AttrType.DOUBLE, AttrType.FLOAT):
                    irows.append(jax.lax.bitcast_convert_type(
                        colv.astype(jnp.float32), jnp.int32))
                elif t == AttrType.LONG:
                    colv = colv.astype(jnp.int64)
                    irows.append(_w_hi32(colv))
                    irows.append(_w_lo32(colv))
                else:
                    irows.append(colv.astype(jnp.int32))
            out = {"nst": nst}
            if irows:       # slim + f64 can route EVERY column to frows
                out["i"] = jnp.stack(irows, axis=0)
            if slim:
                out["b"] = jnp.concatenate(bparts)
            if frows:
                out["f"] = jnp.stack(frows, axis=0)
            return out

        mode = self._mode
        if self.mesh is None:
            return jax.jit(step)
        from jax.sharding import NamedSharding, PartitionSpec
        shard_t = NamedSharding(self.mesh, PartitionSpec("t"))
        repl = NamedSharding(self.mesh, PartitionSpec())
        state_sh = {k: repl for k in self.state}
        env_sh = {"__nvalid__": repl}
        if self._needs_ts:
            env_sh["__ts_off__"] = shard_t
            env_sh["__ts_base__"] = repl
        env_sh.update({c: shard_t for c in cols})
        return jax.jit(step, in_shardings=(state_sh, env_sh))

    # -- QueryPlan interface --------------------------------------------------

    def process(self, stream_id: str, batch: EventBatch) -> list:
        if batch.n == 0:
            return []
        with self.rt.span("host_build", plan=self.name):
            T = pow2_at_least(batch.n)
            if self.mesh is not None:
                # the sharded 't' axis must divide the device count
                T = max(T, self.mesh.devices.size)
            # pads are memoized on the batch (N plans on one stream share
            # ONE pad per column per flush) and backed by the runtime's
            # rotating PadPool, so steady-state flushes stop allocating;
            # depth + 2 slots keep envs of pipelined retries un-aliased
            pool = getattr(self.rt, "_pad_pool", None)
            slots = self.pipeline_depth + 2
            env = {"__nvalid__": np.int32(batch.n)}
            if self._needs_ts:
                off, base = batch.padded_ts_offsets(T, pool=pool,
                                                    min_slots=slots)
                env["__ts_off__"] = off
                env["__ts_base__"] = np.int64(base)
            for c in self.cols:
                dt = None
                if not self.f64 \
                        and batch.columns[c].dtype == np.float64:
                    dt = np.float32              # device DOUBLE policy
                env[c] = batch.padded(c, T, dtype=dt, pool=pool,
                                      min_slots=slots)
        # depth-D pipeline (opt-in @app:devicePipeline): batch i's pull
        # overlaps batch i+1..i+D's upload+compute, hiding the pull's
        # fixed D2H latency; outputs then deliver up to D batches late
        # (the runtime flush barrier drains the tail)
        return self._pipe.push(self._dispatch(env, batch, T))

    def _dispatch(self, env: dict, batch: EventBatch, T: int) -> dict:
        from .pipeline import start_d2h
        # dispatch-boundary fault injection (core/faults.py); state
        # commits only after the call returns, so a raise here leaves the
        # plan retryable (the runtime's degradation ladder re-dispatches
        # with a split batch — half the pad footprint)
        self.rt.inject("dispatch", self.name)
        self._T = T
        pre = self.state
        prof = self.rt.profiler
        if not self.rt.stats.enabled and prof is None:
            res = self._step_fn(T, self.C)(self.state, env)
        else:
            hit = (T, self.C) in getattr(self, "_step_cache", {})
            fn = self._step_fn(T, self.C)
            res = call_kernel(
                self.rt.stats, self.name, fn, (self.state, env),
                cache_hit=hit, nbytes=env_nbytes(env), prof=prof)
        start_d2h(res, keys=("b", "i", "f"))
        self.state = res["nst"]
        return {"pre": pre, "env": env, "batch": batch, "T": T, "res": res}

    def _pull(self, res: dict) -> tuple:
        """The blocking pull of one step's packed outputs: the wait for
        the device (a span of its own while a sink is on), then one D2H
        copy a pack; notes the bytes."""
        span = self.rt.span
        packs = [res.get(k) for k in ("b", "i", "f")]
        with span("transfer", plan=self.name):
            device_wait(span, self.name, packs)
            with span("transfer.copy", plan=self.name):
                packs = [None if p is None else np.asarray(p) for p in packs]
        prof = self.rt.profiler
        if prof is not None:
            prof.note_bytes(self.name, "d2h", sum(
                p.nbytes for p in packs if p is not None))
        return packs

    def _materialize(self, entry: dict) -> list:
        slim = self.kind not in ("lengthbatch", "externaltimebatch")
        while True:
            bpack, ipack, fpack = self._pull(entry["res"])
            kept = int(bpack[0] if slim else ipack[0, 0])
            if kept <= self.C:      # every entry in flight ran at this C
                break
            # carry overflow: grow C to what the step had to keep, at once
            # (never less than double), and replay this entry plus
            # everything dispatched after it (their pre-states are now
            # invalid)
            chain = [entry] + self._pipe.take_all()
            self.state = entry["pre"]
            self._grow(max(2 * self.C, pow2_at_least(kept)))
            self.counters["carry_grows"] += 1
            self.counters["carry_overflow_reruns"] += len(chain)
            redone = [self._dispatch(e["env"], e["batch"], e["T"])
                      for e in chain]
            entry = redone[0]
            self._pipe.requeue(redone[1:])
        self._held = kept
        self._held_max = max(self._held_max, kept)
        with self.rt.span("unpack", plan=self.name, events=entry["batch"].n):
            return self._unpack(entry, slim, bpack, ipack, fpack)

    def _unpack(self, entry: dict, slim: bool, bpack, ipack, fpack) -> list:
        batch = entry["batch"]
        T = entry["T"]
        from .nfa_device import join64_np
        if slim:
            # sliding rows align with the (filter-compacted) input events:
            # timestamps reconstruct host-side, only masks travel as bits
            k = int(bpack[1])
            off = 2
            if self._filter is not None:
                nw = -(-T // 32)
                maskb = _unbits32(bpack[off:off + nw], T)[:batch.n]
                off += nw
                ts_rows = batch.timestamps[maskb]
            else:
                ts_rows = batch.timestamps
            if self.having is not None:
                nw = -(-T // 32)
                valid = _unbits32(bpack[off:off + nw], T)[:k]
            else:
                valid = np.ones(k, dtype=bool)
            if k == 0 or not valid.any():
                return []
            ts_out = ts_rows[:k][valid].astype(TIMESTAMP_DTYPE)
            ii, fi = 0, 0
            take = lambda col: col[:k][valid]
        else:
            ok = ipack[1] != 0
            if not ok.any():
                return []
            ts_out = join64_np(ipack[2], ipack[3])[ok].astype(TIMESTAMP_DTYPE)
            ii, fi = 4, 0
            take = lambda col: col[ok]
        cols = {}
        for a in self.out_schema.attributes:
            dt = np.dtype(jnp_dtype(a.type)) if a.type != AttrType.DOUBLE \
                else np.dtype(np.float64 if self.f64 else np.float32)
            if dt == np.float64:
                col = fpack[fi]; fi += 1
            elif dt == np.float32:
                col = ipack[ii].view(np.float32); ii += 1
            elif dt == np.int64:
                col = join64_np(ipack[ii], ipack[ii + 1]); ii += 2
            else:
                col = ipack[ii]; ii += 1
            v = take(col)
            if a.type == AttrType.BOOL:
                v = v != 0
            cols[a.name] = v.astype(dtype_of(a.type))
        ts_out, cols = self._order_limit(ts_out, cols)
        out = EventBatch(self.out_schema, ts_out, cols, len(ts_out))
        return [OutputBatch(self.output_target, out)]

    def _order_limit(self, ts_out, cols):
        """order-by / offset / limit over one output chunk, host-side
        (device rows are already materialized columns; stable multi-key
        sort mirrors the interp selector's order_limit)."""
        if not (self._order_by or self.limit is not None or self.offset):
            return ts_out, cols
        n = len(ts_out)
        order = np.arange(n)
        for ob in reversed(self._order_by):
            col = cols[ob.var.attribute]
            if self.out_schema.type_of(ob.var.attribute) == AttrType.STRING \
                    and col.dtype.kind in "iu":
                dec = self.rt.strings._to_str
                col = np.array([dec[c] if 0 <= c < len(dec) else ""
                                for c in col.tolist()])
            # rank-inversion covers every dtype exactly (bool, i64 > 2^53,
            # strings lexicographically) and DESC is integer negation of
            # small ranks — no float round-trip (review r5)
            _u, ranks = np.unique(col, return_inverse=True)
            k = ranks[order].astype(np.int64)
            if ob.order == ast.OrderDir.DESC:
                k = -k
            order = order[np.argsort(k, kind="stable")]
        ts_out = ts_out[order]
        cols = {k2: v[order] for k2, v in cols.items()}
        off = self.offset or 0
        if off:
            ts_out = ts_out[off:]
            cols = {k2: v[off:] for k2, v in cols.items()}
        if self.limit is not None:
            ts_out = ts_out[:self.limit]
            cols = {k2: v[:self.limit] for k2, v in cols.items()}
        return ts_out, cols

    # -- snapshot -------------------------------------------------------------

    def device_metrics(self) -> dict:
        """Sampled carry-buffer fill (one D2H pull of the valid mask)."""
        try:
            fill = int(np.asarray(self.state["valid"]).sum())
        except Exception:   # lint: allow-swallow (best-effort metrics
            # sampling — a scrape racing a state swap skips the gauge)
            return {}
        return {"window_capacity": int(self.C), "window_fill": fill,
                "window_fill_ratio": round(fill / max(self.C, 1), 4),
                "window": self.window,
                "window_step": dict(self.window_step),
                "window_ranks": dict(self.window_ranks),
                "window_carry": self.window_carry}

    def state_dict(self) -> dict:
        return {"state": {k: np.asarray(v) for k, v in self.state.items()},
                "C": self.C}

    def load_state_dict(self, d: dict) -> None:
        c = int(d.get("C", self.C))
        if c != self.C:
            self.C = c
        self._pipe.take_all()       # in-flight results predate the restore
        self.state = {k: jnp.asarray(v) for k, v in d["state"].items()}


def _unbits32(words: np.ndarray, n: int) -> np.ndarray:
    """Inverse of the device bits32 pack: i32 words -> (n,) bool."""
    b = ((words.view(np.uint32)[:, None]
          >> np.arange(32, dtype=np.uint32)) & 1).astype(bool)
    return b.reshape(-1)[:n]


from .nfa_device import _hi32 as _w_hi32, _lo32 as _w_lo32  # noqa: E402


def _cast_site(a: jnp.ndarray, t: AttrType) -> jnp.ndarray:
    if t in (AttrType.INT, AttrType.LONG):
        return a.astype(jnp.int64)
    return a


def _collect_site_args(exprs, acc: list) -> None:
    """Aggregator arg ASTs in extract_aggregators traversal order."""
    def walk(e):
        if isinstance(e, ast.FunctionCall) and e.namespace is None \
                and e.name.lower() in AGGREGATOR_NAMES:
            acc.append(e.args[0] if e.args else None)
            return
        if isinstance(e, (ast.Math, ast.Compare, ast.And, ast.Or)):
            walk(e.left)
            walk(e.right)
        elif isinstance(e, ast.Not):
            walk(e.expr)
        elif isinstance(e, ast.FunctionCall):
            for a in e.args:
                walk(a)
    for e in exprs:
        walk(e)
