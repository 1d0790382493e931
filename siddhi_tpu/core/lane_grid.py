"""The partitioned lane grid and the packed result's way back: the host side
of a stateless scan / dfa block.  Three things are known here alone:

  * the grid's layout: a flush as `(rows, F)` grids, cell `(r, i)` at flat
    cell `r * F + i`; ts and seq as i32 offsets from a per-flush base;
    `__nev__` and `__prev_seq__` a row; both axes sticky (F, the
    `_sticky_sixteenth` row count); a lane past `LANE_CUT` cut into rows;
  * tail and dedup: no pattern state lives on the device, so a lane's last
    `within` of events replays in front of its next flush, and completions
    at or before its last delivered seq are dropped on the device;
  * the result's word format: which word of the `i` / `f` pack holds which
    output, the count header in word 0 (`_out_words`, `_Filled`).

Arrows point one way: `pattern_plan` and `multi_query` import this module; it
imports nothing of them, of `runtime` or of `placement`, and is given what it
needs of its plan (window, device count, kernel outputs, `span`) when built.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import numpy as np

from ..query import ast
from .nfa_device import LOCAL_SPAN, pow2_at_least
from .nfa_parallel import DENSE_MAX_F
from .schema import TIMESTAMP_DTYPE, dtype_of

_I32 = np.int32


def _sticky_sixteenth(n: int, held: int, lo: int = 1) -> int:
    """`n` rows of a grid's major axis, padded: up to a granule of a
    sixteenth of n's power of two (never under `lo`), so that a padded row,
    which costs what a real one does, is under an eighth of the count; and
    sticky, since every distinct count is a compile: `held`, the count in
    use, stays while it serves (a count that drifts inside its granule moves
    nothing), grows when n passes it, and is dropped for what n needs once
    it is over four times that."""
    g = max(lo, pow2_at_least(n, lo=1) // 16)
    need = -(-n // g) * g
    return need if held < need or held > 4 * need else held


def _stable_lane_order(part: np.ndarray) -> np.ndarray:
    """The permutation `np.lexsort((seq, part))` returns, for rows that are
    ALREADY in seq order inside every lane (the caller's invariant): a
    stable sort by the lane id alone keeps each lane's rows as they stand.
    A lane id is a small non-negative integer, so the sort is numpy's
    radix pass over 16-bit keys (what `kind="stable"` picks for them):
    one pass while every id is under 2^16, the low half then the high half
    (LSD, each pass stable) above that."""
    if part.size == 0 or int(part.max()) < 1 << 16:
        return np.argsort(part.astype(np.uint16), kind="stable")
    by_low = np.argsort((part & 0xFFFF).astype(np.uint16), kind="stable")
    high = (part >> 16).astype(np.uint16)
    return by_low[np.argsort(high[by_low], kind="stable")]


def _rises_in_lanes(a_l: np.ndarray, run_start: np.ndarray) -> bool:
    """Whether lane-ordered `a_l` is non-decreasing inside every lane
    (`run_start`: the row each lane's run begins at)."""
    rises = a_l[1:] >= a_l[:-1]
    rises[run_start[1:] - 1] = True         # a lane's first row
    return bool(rises.all())


class CutResult(NamedTuple):
    """A cut fused flush's packed result as pulled, (rows, lanes, words, M),
    not yet decoded (`ResultDecoder.cut`): `counts` is its header's match counts
    by (row, lane), a mesh's padding lanes dropped; the bases are the
    flush's own."""
    ipack: np.ndarray
    fpack: Optional[np.ndarray]
    counts: np.ndarray
    ts_base: int
    seq_base: int


class RuleRuns(NamedTuple):
    """A fused flush's matches in the order they are owed: by rule, inside a
    rule by completion seq, same-event ties by head arrival.  Rule
    `lanes[j]` holds the rows from `starts[j]` to the next rule's start
    of every column."""
    tss: np.ndarray
    seqs: np.ndarray
    data: dict
    lanes: np.ndarray
    starts: np.ndarray


class _Scratch:
    """The buffers a plan decodes its results through (index, key, the
    words of one column on their way to the delivered dtype): kept by
    its decoder, grown geometrically, reused flush to flush and NEVER handed
    out.  A fresh page costs the chip machines ~1 ms a MB (PERF.md 7.10),
    so what is not delivered is not allocated a flush; what IS delivered
    is allocated for its batch, always (a callback may keep it)."""

    def __init__(self):
        self._bufs: dict = {}

    def __call__(self, name: str, n: int, dtype) -> np.ndarray:
        """`n` uninitialised items of buffer `name`."""
        buf = self._bufs.get(name)
        if buf is None or len(buf) < n or buf.dtype != dtype:
            buf = self._bufs[name] = np.empty(
                max(n, 2 * (0 if buf is None else len(buf))), dtype)
        return buf[:n]


# `np.take` into scratch: `mode="raise"`, the default, buffers `out`; what
# it would have checked, _Filled checks once a result (`_index`)
_take = partial(np.take, mode="clip")


def _flat_words(a: np.ndarray) -> tuple:
    """(`a`'s memory as ONE flat array, `a`'s strides in items).  A pulled
    result comes in the axis order the device laid it out in (PERF.md
    7.8), so a cell's flat position is read off the strides, not assumed;
    a result that is no permutation of one contiguous block is copied."""
    by_stride = sorted(range(a.ndim), key=lambda i: -a.strides[i])
    if not a.transpose(by_stride).flags.c_contiguous:
        a = np.ascontiguousarray(a)
        by_stride = range(a.ndim)
    return (a.transpose(by_stride).reshape(-1),
            tuple(s // a.itemsize for s in a.strides))


def _ramp(out: np.ndarray, starts, lens, first, step: int) -> np.ndarray:
    """out[k] = first[j] + (k - starts[j]) * step inside run j (the runs
    non-empty, one after the other, covering `out`): a fill, the jumps
    between runs written at the run starts, one running sum in place;
    no temporary of `out`'s length, as `np.repeat` would make."""
    out.fill(step)
    jump = np.array(first, dtype=out.dtype)
    jump[1:] -= jump[:-1] + (lens[:-1] - 1).astype(out.dtype) * step
    out[starts] = jump
    return np.cumsum(out, out=out)


class _Filled:
    """A block's packed result, `(lanes, words, M)` or a cut flush's
    `(rows, lanes, words, M)`, under ONE index over its filled cells,
    built from the count header alone (`counts`: matches by lane, or by
    (row, lane)), lane-major `(lane, [row,] match)`.  Every word is fetched
    once through it into the decoder's scratch (`S`, by its word table
    `words`): no copy and no mask of the capacity, so a decode costs what
    its rows cost, at any fill.  `name`: the plan's, for an error."""

    def __init__(self, S, words, name, counts, ipack, fpack):
        self.S, self.words, self.name = S, words, name
        cnt = counts.T.reshape(-1)              # lane-major cells
        cells = np.flatnonzero(cnt)
        self.lens = cnt[cells].astype(np.intp)
        self.at = np.cumsum(self.lens) - self.lens
        self.n = int(self.lens.sum())
        self.cell = np.unravel_index(cells, counts.shape[::-1])
        self.i = self._index("idx", ipack)
        self.f = None if fpack is None else self._index("fidx", fpack)

    def _index(self, name: str, pack: np.ndarray) -> list:
        """[`pack` flat, its word stride, the cells' places in word 0]."""
        flat, (*s_cell, s_word, s_m) = _flat_words(pack)
        first = sum(c * s for c, s in zip(self.cell, reversed(s_cell)))
        idx = _ramp(self.S(name, self.n, np.intp), self.at, self.lens,
                    first, s_m)
        # the takes clip: every word's cell must lie in the pack
        if idx.min() < 0 or int(idx.max()) \
                + (pack.shape[-2] - 1) * s_word >= len(flat):
            raise IndexError(
                f"{self.name}: decode index past the result "
                f"{pack.shape} (strides {pack.strides})")
        return [flat, s_word, idx]

    def reindex(self, order: np.ndarray) -> None:
        """Compose `order` (a permutation, or the cells kept); once."""
        self.n = len(order)
        for pack, name in ((self.i, "idx.ordered"), (self.f, "fidx.ordered")):
            if pack is not None:
                pack[2] = _take(pack[2], order,
                                out=self.S(name, self.n, np.intp))

    def word(self, nm: str, buf: str = "word", k: int = 0) -> np.ndarray:
        """Word `k` of output `nm` as the pack has it, in scratch `buf`."""
        flat, s_word, idx = self.i
        return _take(flat[(self.words[nm][1] + k) * s_word:], idx,
                     out=self.S(buf, self.n, _I32))

    def column(self, nm: str, t) -> np.ndarray:
        """Output `nm` as the delivered column of type `t`: allocated for
        this batch, written once (f64 from the `f` pack, its own index)."""
        pack, w, dt = self.words[nm]
        if pack == "f":
            flat, s_word, idx = self.f
            src = _take(flat[w * s_word:], idx,
                        out=self.S("f64", self.n, np.float64))
        elif dt == np.int64:                            # join64_np
            src = np.left_shift(self.word(nm), 32, dtype=np.int64,
                                out=self.S("i64", self.n, np.int64))
            src |= self.word(nm, k=1).view(np.uint32)
        elif dt == np.float32:
            src = self.word(nm).view(np.float32)
        else:
            src = self.word(nm)
        col = np.empty(self.n, dtype_of(t))
        if t == ast.AttrType.BOOL:
            np.not_equal(src, 0, out=col)
        else:
            col[...] = src
        return col


def _on_base(off: np.ndarray, base: int, dtype=np.int64) -> np.ndarray:
    """The i32 offsets `off` on their flush's `base`, allocated."""
    return np.add(off, dtype(base), out=np.empty(len(off), dtype))


def _tail_rows(t: dict, rows) -> dict:
    """The rows `rows` (a mask or an index) of a lane tail, or of the
    flush's columns laid out like one."""
    return {"ts": t["ts"][rows], "seq": t["seq"][rows],
            "scode": t["scode"][rows], "part": t["part"][rows],
            "cols": {k: v[rows] for k, v in t["cols"].items()}}


def _offsets32(a: np.ndarray, base: int, lo: int) -> np.ndarray:
    """`a - base` as the i32 offsets the device reads, saturating at
    +-LOCAL_SPAN.  `lo` is a's minimum; a base is never more than
    LOCAL_SPAN under a's maximum, so only the low side can need the clip."""
    off = a - base
    if lo - base < -LOCAL_SPAN:
        np.clip(off, -LOCAL_SPAN, LOCAL_SPAN, out=off)
    return off.astype(_I32)


# The most events one row of the partitioned lane grid holds when a flush
# is cut: a lane longer than this is laid out as several rows, each a
# flush boundary its key never saw (_cut_rows).  Every lane pads to the
# longest row and the block's first-hit queries stay dense only up to
# DENSE_MAX_F events a row, so the cut is at most that.  Half of it, by two
# readings of pattern1k-zipf.sat on the chip (one seed, 30 s; PERF.md
# section 6, PR 35): at DENSE_MAX_F // 2 = 2048 a batch is 1,088 rows, a
# 2048 x 2048 grid, 412,970 events/s; at DENSE_MAX_F = 4096 it is 1,025
# rows, astride the lane axis' power of two, a 2048 x 4096 grid, 178,713
# events/s.  A shorter row serves fewer new events behind its replayed
# window (the top key's 1,294 of 2048), but the grid is rows x cut cells
# and the dense queries cost cut^2 a row.
LANE_CUT = DENSE_MAX_F // 2

# What `lane_fill` counts of a partitioned lane-grid flush, in this order:
# the lanes with new events, the grid rows they were padded to (a cut lane
# is several) and the quiet lanes whose tails were held apart; the new
# events and the events replayed in front of them from the lanes' tails;
# the grid's cells that hold an event and all of them (rows x F); the cells
# of the packed result as pulled (rows x words x M, every pull of a flush)
# and the match rows it carried.
LANE_FILL = ("lanes_active", "lanes_padded", "lanes_held", "events_new",
             "events_replayed", "cells_filled", "cells_total",
             "result_cells", "rows_delivered")



def _cut_rows(counts, run_start, tail_n, tsmono, W: int,
              row_len: Optional[int] = None) -> Optional[tuple]:
    """The grid rows of a flush in which some lane holds more than `row_len`
    events (LANE_CUT unless given: the fused flush's rows are shorter,
    _fused_row_length), as (lane run of each row, its first lane-ordered
    event, its length, its first NEW event), rows of one lane consecutive
    and in order; None when some lane's replay window leaves under a
    quarter of a row for new events: that lane is hotter than the cut can
    serve (the rows it needs grow as the cut over the room left).

    A lane run is [replayed tail | new events] in arrival order
    (`tail_n` of them replayed).  Its first row starts where the run
    starts; every next row starts with the events whose running-max
    timestamp is within W of the last event BEFORE its first new one:
    what the lane's tail would hold had a flush ended there, so the row
    is what that lane's next flush would have been."""
    if row_len is None:
        row_len = LANE_CUT      # read at call time: tests lower it
    cut = np.flatnonzero(counts > row_len)
    room = row_len // 4
    per_lane = []
    for r in cut.tolist():
        a, c = int(run_start[r]), int(counts[r])
        mono = tsmono[a:a + c]
        first, p, s = [], 0, int(tail_n[r])
        while True:
            if row_len - (s - p) < room:
                return None
            e = min(c, p + row_len)
            first.append((a + p, e - p, a + s))
            if e == c:
                break
            s = e
            p = int(np.searchsorted(mono, mono[s - 1] - W, side="left"))
        per_lane.append(first)
    n_rows = np.ones(len(counts), dtype=np.int64)
    n_rows[cut] = [len(f) for f in per_lane]
    row_run = np.repeat(np.arange(len(counts)), n_rows)
    row_at, row_n = run_start[row_run], counts[row_run]
    row_new = row_at + tail_n[row_run]
    at = np.cumsum(n_rows) - n_rows
    for r, first in zip(cut.tolist(), per_lane):
        sl = slice(int(at[r]), int(at[r]) + len(first))
        row_at[sl], row_n[sl], row_new[sl] = np.array(first).T
    return row_run, row_at, row_n, row_new


class LaneGrid:
    """The pack side, one per partitioned stateless plan: the per-lane
    continuity (`tail`, `prev`), the sticky geometry (`F`, `L`) and the
    `lane_cut` / `lane_fill` counts.  `pack_order` is the plan's
    `lane_pack_order`: the lane order's pick is counted into it."""

    def __init__(self, W: int, n_devices: int, multi_stream: bool, span,
                 pack_order: dict):
        self.W, self.n_devices = int(W), n_devices
        self.multi_stream = multi_stream
        self.span, self.pack_order = span, pack_order
        # per-key replay tails + per-key last-emitted completion seq
        self.tail: Optional[dict] = None
        self.prev = np.zeros(0, dtype=np.int64)
        self.F = self.L = 0     # the lane grid in use, sticky
        # the counts behind `cut_record` and `fill_record`
        self.lane_cut = {"flushes_cut": 0, "lanes_cut": 0, "rows_added": 0,
                         "events_replayed": 0, "flushes_uncuttable": 0}
        self.lane_fill = {"flushes": 0, "total": dict.fromkeys(LANE_FILL, 0),
                          "last": None, "grids": {}}

    def mark(self) -> tuple:
        """What `rollback` puts back when a dispatch fails, so that the
        degradation ladder can re-run the flush."""
        return self.tail, self.prev.copy(), self.F, self.L

    def rollback(self, mark: tuple) -> None:
        self.tail, self.prev, self.F, self.L = mark

    def state(self) -> dict:
        """The snapshot's share of the grid, under `state_dict`'s keys."""
        return {"lane_tail": self.tail, "lane_prev": np.asarray(self.prev)}

    def load(self, d: dict) -> None:
        self.tail = d.get("lane_tail")
        if d.get("lane_prev") is not None:
            self.prev = np.asarray(d["lane_prev"], dtype=np.int64)

    def _lane_order(self, part, seq, run_start) -> tuple:
        """(order, seq[order]): the rows by (lane, seq).  `[tail | new]`
        is in seq order inside every lane when (a) the tail keeps each
        lane's rows in seq order (it is a mask over rows this function
        ordered; `held` lanes are disjoint from the active ones), (b)
        every tail seq is below every new seq of its lane (arrival stamps
        only grow) and (c) the new rows are in seq order
        (_finalize_chunks step 2): then one stable radix pass over the
        lane id is the whole sort.  Whether it held is read off the
        result, lane by lane (`run_start`: where each lane's run begins);
        input that breaks it (unstamped batches, whose seqs restart at
        every flush; a restored tail from elsewhere) takes the two-key
        comparison sort."""
        order = _stable_lane_order(part)
        seq_l = seq[order]
        if _rises_in_lanes(seq_l, run_start):
            self.pack_order["radix"] += 1
        else:
            self.pack_order["lexsort"] += 1
            order = np.lexsort((seq, part))
            seq_l = seq[order]
        return order, seq_l

    def _cut_lanes(self, counts, run_start, tail_n, tsmono, W: int,
                   order, seq_l, ts_l, lane_prev) -> Optional[tuple]:
        """The grid rows of a flush whose longest lane is past LANE_CUT
        (_cut_rows), as what the grid is filled from: per cell, in row
        order, the flush row, seq and ts it takes (`order`, `seq_l`, `ts_l`
        gathered: a row's replayed head repeats events of the row before);
        per row, its events, where its cells start, and its dedup bound: a
        lane's first row at the lane's prev seq (`lane_prev`, per lane
        run), a later one at the event before its first new one.  Counted.
        None, and counted, when a lane is hotter than the cut serves: the
        flush then keeps one row a lane, whatever its length."""
        rows = _cut_rows(counts, run_start, tail_n, tsmono, W)
        did = self.lane_cut
        if rows is None:
            did["flushes_uncuttable"] += 1
            return None
        row_run, row_at, row_n, row_new = rows
        g_start = np.cumsum(row_n) - row_n
        src = np.repeat(row_at - g_start, row_n)
        src += np.arange(len(src))
        first = np.r_[True, row_run[1:] != row_run[:-1]]
        did["flushes_cut"] += 1
        did["lanes_cut"] += int(np.count_nonzero(counts > LANE_CUT))
        did["rows_added"] += len(row_n) - len(counts)
        did["events_replayed"] += int((row_new - row_at)[~first].sum())
        g_prev = np.where(first, lane_prev[row_run], seq_l[row_new - 1])
        return order[src], seq_l[src], ts_l[src], row_n, g_start, g_prev

    def pack(self, ts, seq, scode, cols, part, n_keys: int) -> tuple:
        """One flush (rows in arrival order, `part` their lanes of the
        `n_keys` assigned so far) as (the block's `ev`, F, padded lanes, ts
        base, seq base, highest seq, the `fill` record for `note_fill`)."""
        with self.span("host_build"):
            W0 = self.W
            # rows a lane, new then replayed; lane ids are dense in
            # [0, n_keys), so a count is a bincount
            lane_n = np.bincount(part, minlength=n_keys)
            tl = self.tail
            held = tail_n = None
            fill = {"events_new": len(ts), "events_replayed": 0,
                    "lanes_held": 0}
            if tl is not None:
                # only lanes with NEW events this flush replay their
                # tail; a quiet lane cannot produce a new completion
                # (everything it could emit is at or before its prev
                # seq), and letting its old events into the flush would
                # pin the shared i32 ts/seq bases forever (review
                # finding: a long-quiet lane saturated every live
                # lane's offsets at the 2^30 clip)
                with self.span("lane_tail"):
                    active = lane_n[tl["part"]] > 0
                    if not active.all():
                        held = _tail_rows(tl, ~active)
                        tl = _tail_rows(tl, active)
                        # a lane's rows are contiguous in a tail
                        fill["lanes_held"] = 1 + int(np.count_nonzero(
                            held["part"][1:] != held["part"][:-1]))
                    tail_n = np.bincount(tl["part"], minlength=len(lane_n))
                    lane_n = lane_n + tail_n
                    fill["events_replayed"] = len(tl["ts"])
                    ts = np.concatenate([tl["ts"], ts])
                    seq = np.concatenate([tl["seq"], seq])
                    scode = np.concatenate([tl["scode"], scode])
                    part = np.concatenate([tl["part"], part])
                    cols = {k: np.concatenate([tl["cols"][k], v])
                            for k, v in cols.items()}
            N = len(ts)
            # the flush's lanes in ascending id, each one run of the
            # lane-ordered rows
            lane_ids = np.flatnonzero(lane_n)
            counts = lane_n[lane_ids]
            Lr = fill["lanes_active"] = len(lane_ids)
            run_start = np.cumsum(counts) - counts
            run_end = run_start + counts - 1
            order, seq_l = self._lane_order(part, seq, run_start)
            ts_l = ts[order]

            # per-lane running-max ts: feeds the tail-retention bound and
            # the out-of-order `within` widening, exactly like the flat
            # path's global cummax.  Timestamps that rise inside every
            # lane are their own running max; else ONE cummax over all
            # lanes, each lifted clear of the one before (offset trick)
            ts_lo, ts_hi = int(ts.min()), int(ts.max())
            if _rises_in_lanes(ts_l, run_start):
                tsmono, W = ts_l, W0
            else:
                lift = np.repeat(np.arange(Lr, dtype=np.int64)
                                 * (ts_hi - ts_lo + 1), counts)
                tsmono = np.maximum.accumulate(ts_l + lift) - lift
                W = W0 + int(np.max(tsmono - ts_l))

            # lane-grid geometry, both axes sticky so that drift never
            # recompiles.  The lane axis pads to a sixteenth of its power
            # of two (_sticky_sixteenth, never under the 8 sublanes of an
            # uploaded (L, F) grid): hot-adding a key keeps the compiled
            # shape until the count crosses a sixteenth, and a padded row
            # is worked, uploaded and pulled like a real one (the power
            # of two wasted up to half of every grid and result).  F rides
            # a 64-granule bucket: every padded cell multiplies by the
            # lane count
            fm = int(counts.max())
            if len(self.prev) < n_keys:
                grown = np.full(n_keys, -(2 ** 62), dtype=np.int64)
                grown[:len(self.prev)] = self.prev
                self.prev = grown
            # what each grid row holds, in lane order: a lane's run as it
            # stands, unless the flush is cut (rows of g_counts events
            # from g_start on; g_prev: the seq before a row's new events)
            g_order, g_seq, g_ts = order, seq_l, ts_l
            g_counts, g_start = counts, run_start
            g_prev = self.prev[lane_ids]
            cut = None
            if fm > LANE_CUT:
                with self.span("lane_cut"):
                    cut = self._cut_lanes(
                        counts, run_start,
                        np.zeros(Lr, dtype=np.int64) if tail_n is None
                        else tail_n[lane_ids], tsmono, W,
                        order, seq_l, ts_l, g_prev)
            if cut is None:
                f_min = pow2_at_least(fm, lo=16) if fm <= 64 \
                    else (fm // 64 + 2) * 64
                F = max(self.F, f_min)
                if F > 4 * f_min:
                    F = f_min
            else:
                g_order, g_seq, g_ts, g_counts, g_start, g_prev = cut
                Lr, N = len(g_counts), len(g_order)
                F = LANE_CUT        # a cut lane's first row fills it
            self.F = F
            Lpad = self.L = _sticky_sixteenth(max(Lr, 1), self.L, lo=8)
            nd = self.n_devices
            Lpad = -(-Lpad // nd) * nd      # even lane shards
            fill.update(lanes_padded=Lpad, cells_filled=N,
                        cells_total=Lpad * F, F=F)

            # bases anchor at the flush MAX with i32 headroom (like the
            # dense path): a lane resuming after a >2^30 ms / seq gap
            # saturates ITS stale offsets low — which reads as "ancient,
            # expired, already-deduped" on device, the conservative and
            # host-identical outcome — instead of saturating every live
            # lane's offsets high
            budget = LOCAL_SPAN - (1 << 16)
            seq_lo, seq_hi = int(seq.min()), int(seq.max())
            ts_base = max(ts_lo, ts_hi - budget)
            seq_base = max(seq_lo, seq_hi - budget)

            # cell (grid row r, index-within-row i) of the (Lpad, F)
            # grid is flat cell r * F + i: ascending in the row-ordered
            # events, so each column is one in-order scatter
            cell = np.repeat(np.arange(Lr) * F - g_start, g_counts)
            cell += np.arange(N)

            def grid(a):
                g = np.zeros(Lpad * F, dtype=a.dtype)
                g[cell] = a
                return g.reshape(Lpad, F)

            nev = np.zeros(Lpad, _I32)
            nev[:Lr] = g_counts
            prev = np.full(Lpad, -LOCAL_SPAN, _I32)
            prev[:Lr] = np.clip(g_prev - seq_base,
                                -LOCAL_SPAN, LOCAL_SPAN).astype(_I32)
            ev = {"__flat.__ts__": grid(_offsets32(g_ts, ts_base, ts_lo)),
                  "__flat.__seq__": grid(_offsets32(g_seq, seq_base,
                                                    seq_lo)),
                  "__nev__": nev, "__prev_seq__": prev,
                  "__base_ts__": np.int64(ts_base),
                  "__base_seq__": np.int64(seq_base)}
            if self.multi_stream:
                ev["__flat.__scode__"] = grid(scode[g_order])
            for k, v in cols.items():
                ev[f"__flat.{k}"] = grid(v[g_order])

            # per-lane tail: the last `within` window of each lane's
            # events replays at that lane's next flush (lanes quiet this
            # flush keep their stored tail untouched).  Only the kept
            # rows are gathered, in lane order.  By LANE (run_end,
            # counts, lane_ids), never by grid row: a cut lane's tail is
            # its last window whatever rows it was laid out as.
            with self.span("lane_tail"):
                keep = order[tsmono >= np.repeat(tsmono[run_end] - W,
                                                 counts)]
                self.tail = _tail_rows(
                    {"ts": ts, "seq": seq, "scode": scode, "part": part,
                     "cols": cols}, keep)
                if held is not None:
                    # quiet lanes' tails ride along untouched: their lanes
                    # are none of the kept ones, so every lane's rows stay
                    # contiguous and in seq order (_lane_order's invariant)
                    self.tail = {
                        k: (np.concatenate([self.tail[k], held[k]])
                            if k != "cols" else
                            {c: np.concatenate([self.tail["cols"][c],
                                                held["cols"][c]])
                             for c in held["cols"]})
                        for k in self.tail}
            self.prev[lane_ids] = seq_l[run_end]
        return ev, F, Lpad, ts_base, seq_base, seq_hi, fill

    def cut_record(self) -> dict:
        """What the cut of long lanes did (EXPLAIN `lane_cut`):
        `flushes_cut`, and over them the `lanes_cut`, the grid `rows_added`
        to one a lane and the `events_replayed` at the head of those rows;
        `flushes_uncuttable`, flushes with a lane past `cut_length`
        (LANE_CUT) that kept one row a lane because some lane's replay
        window overfills a row."""
        return {**self.lane_cut, "cut_length": LANE_CUT}

    def fill_record(self) -> Optional[dict]:
        """How full the lane grids were (EXPLAIN `lane_fill`), once a
        flush has been materialised: `total`, the sums over `flushes`, and
        `last`, the newest flush alone, of LANE_FILL's counts (`last` also
        has the grid's `F` and the result's `M`); `grids`, flushes by
        `"<rows>x<F>x<M>"`: more than one entry is a geometry that moved
        (each new one a compilation).  `lanes_padded` is `lanes_active`
        (grid rows, once a hot lane is cut) up to a sticky sixteenth of its
        power of two, at least 8, then to the mesh's device count: under
        9/8 of it past 128 rows.  What the ratios say: `events_replayed`
        over `events_new`, the tail replay the host pays for keeping no
        pattern state on the device; `cells_total` over `cells_filled`, the
        padding uploaded; `result_cells` over `rows_delivered`, the capacity
        pulled for every row carried."""
        did = self.lane_fill
        if not did["flushes"]:
            return None
        return {"flushes": did["flushes"], "total": dict(did["total"]),
                "last": dict(did["last"]), "grids": dict(did["grids"])}

    def note_fill(self, fill: dict, M: int, result_cells: int,
                  rows: int) -> None:
        """Count one materialised partitioned flush into `lane_fill`:
        what the pack noted at dispatch (`fill`) with the result's side."""
        did = self.lane_fill
        last = {**dict.fromkeys(LANE_FILL, 0), **fill, "M": M,
                "result_cells": result_cells, "rows_delivered": rows}
        for k in LANE_FILL:
            did["total"][k] += last[k]
        grid = f"{last['lanes_padded']}x{last['F']}x{M}"
        did["grids"][grid] = did["grids"].get(grid, 0) + 1
        did["last"] = last
        did["flushes"] += 1


def _out_words(out_names, out_dtypes, having: bool) -> dict:
    """Where the pack holds each output of the kernel's `out_names`, as
    (pack, first word, word dtype), for _Filled: `i` words from 1
    (word 0 is the block's header), a `having` flag first, as
    `__having__`; f32 bit-cast, i64 a hi / lo pair; f64 in the `f` pack."""
    words, ii, fi = {}, 1, 0
    if having:
        words["__having__"], ii = ("i", 1, np.dtype(_I32)), 2
    for nm in out_names:
        dt = np.dtype(out_dtypes[nm])
        if dt == np.float64:
            words[nm] = ("f", fi, dt)
            fi += 1
        else:
            words[nm] = ("i", ii, dt)
            ii += 2 if dt == np.int64 else 1
    return words


class ResultDecoder:
    """The result side, one per plan: the word table (from what the kernel
    declares; a grown or rebuilt kernel keeps it), the scratch, and the
    counts `result_decode` (results that held rows) and `route_order` (a
    fused plan's flushes by the way `rule_order` ordered them)."""

    def __init__(self, name: str, kernel, names, types, span):
        self.name, self.names, self.types, self.span = name, names, types, span
        self.having = kernel.having is not None
        self.emit_qid = kernel.emit_qid
        self.null_outputs = dict(kernel.null_outputs)
        self.words = _out_words(kernel.out_names, kernel.out_dtypes,
                                self.having)
        self.scratch = _Scratch()
        self.result_decode = {"indexed": 0}
        self.route_order = {"keyed": 0, "lexsort": 0}

    def cut(self, res: CutResult) -> RuleRuns:
        """A cut fused flush's packed result, the flush's rows its alone,
        to host columns in delivery order (_Filled).  `unpack`: the index,
        lane-major (lane, row, match), so that a rule's rows are one run
        of nearly sorted rows; and the two key words, completion and head
        seq, fetched through it.  `route`: the delivery order
        (rule_order), composed into the index.  `scatter`: every
        delivered column written once through the COMPOSED index, not
        lane-major and then through the order: composed reads stay inside
        the lane-row they reorder, so they cost what sequential ones do,
        and the second pass a column is saved (PERF.md section 6, PR 41)."""
        S, span = self.scratch, self.span
        self.result_decode["indexed"] += 1
        with span("unpack"):
            got = _Filled(S, self.words, self.name, res.counts, res.ipack,
                          res.fpack)
            seq = got.word("__seq__", "seq")
            hseq = got.word("__head_seq__", "hseq")
            # a lane IS a rule (`__lane_qid__` is arange(P)): its id is
            # read off the layout, the `__qid__` word never fetched
            lane_n = res.counts.sum(axis=0, dtype=np.intp)
            lanes = np.flatnonzero(lane_n)
            starts = np.cumsum(lane_n[lanes]) - lane_n[lanes]
            lane = _ramp(S("lane", got.n, _I32), starts, lane_n[lanes],
                         lanes, 0)
        with span("route"):
            order = self.rule_order(lane, seq, hseq)
            got.reindex(order)
        with span("scatter"):
            tss = _on_base(got.word("__timestamp__"), res.ts_base,
                           TIMESTAMP_DTYPE)
            # (the completions are on the host already, in lane-major order)
            seqs = _on_base(_take(seq, order, out=S("word", got.n, _I32)),
                            res.seq_base)
            data = {nm: got.column(nm, t)
                    for nm, t in zip(self.names, self.types)}
        return RuleRuns(tss, seqs, data, lanes, starts)

    def lanes(self, ipack, fpack, counts, ts_base, seq_base):
        """A lane block's packed result `(lanes, words, M)`, or a cut one
        that is not its flush's alone, to the match table `(tss, seqs,
        hseqs, data, nulls, qids)`, lane-major; None when it holds no
        row.  `unpack`: the index over the filled cells (_Filled) from
        `counts`, the header as _materialize_par read it; a `having` flag
        thins the INDEX before any other word is read.  `scatter`: every
        word fetched once through it, into columns of the batch's own."""
        if not counts.any():
            return None
        with self.span("unpack"):
            got = _Filled(self.scratch, self.words, self.name, counts,
                          ipack, fpack)
            if self.having:
                got.reindex(np.flatnonzero(got.word("__having__")))
        if not got.n:
            return None
        self.result_decode["indexed"] += 1
        with self.span("scatter"):
            tss = _on_base(got.word("__timestamp__"), ts_base,
                           TIMESTAMP_DTYPE)
            seqs = _on_base(got.word("__seq__"), seq_base)
            hseqs = got.word("__head_seq__").copy()
            qids = got.word("__qid__").copy() if self.emit_qid \
                else None
            data = {nm: got.column(nm, t)
                    for nm, t in zip(self.names, self.types)}
            nulls = {}
            for nm, ref in self.null_outputs.items():
                if f"__present__.{ref}" in got.words:
                    absent = got.word(f"__present__.{ref}") == 0
                    if absent.any():
                        nulls[nm] = absent
        return (tss, seqs, hseqs, data, nulls, qids)

    def rule_order(self, lane, seq, hseq) -> np.ndarray:
        """The permutation `np.lexsort((hseq, seq, lane))` returns, for
        rows that stand as a fused result's do: lane-major, a lane's grid
        rows in rising and disjoint completion ranges (each row's dedup
        bound is the last event before it), a cell's matches in head
        order (the block compacts them by head index).  Then the rows are
        a run a lane of nearly sorted rows, and ONE stable sort of ONE
        key, `lane * span + seq - seq.min()`, orders them.  Whether that
        IS the lexsort is read off the result: rows of equal key must
        come with their head seqs rising.  Where they do not (unstamped
        batches whose seqs restart, a final-count burst that emits a
        head's rows out of head order), or the key has no room (lanes x
        span past 31 bits), the three-key sort serves.  Counted
        (`fused.route_order`)."""
        S, n = self.scratch, len(seq)
        lo = int(seq.min())
        span = int(seq.max()) - lo + 1
        if (int(lane.max()) + 1) * span < 1 << 31:
            key = np.multiply(lane, span, out=S("key", n, _I32),
                              casting="unsafe")
            key += np.subtract(seq, lo, out=S("rel", n, _I32),
                               casting="unsafe")
            order = np.argsort(key, kind="stable")
            keys = _take(key, order, out=S("key.sorted", n, _I32))
            heads = _take(hseq, order, out=S("heads", n, hseq.dtype))
            tie = np.equal(keys[1:], keys[:-1], out=S("tie", n - 1, bool))
            tie &= np.less(heads[1:], heads[:-1],
                           out=S("falls", n - 1, bool))
            if not tie.any():
                self.route_order["keyed"] += 1
                return order
        self.route_order["lexsort"] += 1
        return np.lexsort((hseq, seq, lane))

    def rule_runs(self, table: tuple) -> RuleRuns:
        """A flat fused flush's match table (_multi_table) in delivery
        order: its table is lane-major as a cut one's is (`lanes`),
        a lane one cell."""
        tss, seqs, hseqs, data, qids = table
        order = self.rule_order(qids, seqs, hseqs)
        qids = qids[order]
        starts = np.flatnonzero(np.r_[True, qids[1:] != qids[:-1]])
        return RuleRuns(tss[order].astype(TIMESTAMP_DTYPE, copy=False),
                        seqs[order], {k: v[order] for k, v in data.items()},
                        qids[starts], starts)

