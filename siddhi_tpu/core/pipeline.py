"""Shared async device-dispatch pipeline.

Every device plan follows the same protocol: build a host env, dispatch
the jitted block (async — the call returns once the device owns the
work), kick off the D2H pull with `copy_to_host_async`, and only later
block on `np.asarray(...)` to materialize the result.  This module owns
the in-flight bookkeeping that used to be re-implemented per plan
(pattern chunks, window aggs, joins, filters):

  * `DispatchPipeline` — the depth-D deferred-materialization queue
    behind `@app:devicePipeline`.  `push()` enqueues a dispatched entry
    and materializes whatever exceeds the configured depth; `drain()`
    is the flush barrier.  `hold()`/`collect()` let the runtime dispatch
    EVERY device plan subscribed to a batch before the first blocking
    pull, so N plans overlap on device even at depth 0 (host/device
    decoupling: the host's build+dispatch of plan B hides plan A's
    compute + readback).
  * `start_d2h` — best-effort async D2H prefetch of packed result
    buffers (the repeated try/except `copy_to_host_async` idiom).
  * `PadPool` — rotating zero-padded upload buffers reused across
    flushes, so padding a micro-batch to its pow2 grid stops allocating
    per flush.  Combined with `EventBatch.padded(...)` memoization,
    N plans subscribed to one stream share ONE pad per column per flush.

Telemetry (always on — two clock reads per entry): per-plan dispatch
count, live/max queue depth, and the overlap accounting behind the
`overlap_ratio` gauge: `overlap_s` is host-side time entries spent in
flight while the host moved on to other work, `wait_s` is the blocking
remainder paid at materialization.  `overlap_ratio ~ 1.0` means the
pipeline fully hid device compute + D2H behind host work; `~ 0.0` means
the host serialized against the device (no overlap).  Exposed through
`StatisticsManager.device_report()` as `dispatch_queue_depth`,
`pipeline_max_depth`, `pipeline_dispatches`, `overlap_ratio`.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import numpy as np


def _entry_device_nbytes(entry) -> int:
    """Best-effort D2H payload size of one in-flight entry: sum nbytes
    of device arrays (anything exposing copy_to_host_async) found one
    or two levels into the entry tuple — the packed result buffers the
    materialize below will pull."""
    try:
        total = 0
        items = entry if isinstance(entry, (tuple, list)) else (entry,)
        for it in items:
            vals = it.values() if isinstance(it, dict) else (
                it if isinstance(it, (tuple, list)) else (it,))
            for v in vals:
                if hasattr(v, "copy_to_host_async"):
                    total += int(getattr(v, "nbytes", 0) or 0)
        return total
    except Exception:
        return 0


def start_d2h(out, keys=("i", "f", "b")) -> None:
    """Start async device->host copies for the packed result buffers so
    the pull overlaps remaining device compute (best-effort: some
    backends/array types don't support it)."""
    if isinstance(out, dict):
        arrays = [out[k] for k in keys if k in out]
    else:
        arrays = list(out)
    for a in arrays:
        try:
            a.copy_to_host_async()
        except Exception:
            pass


class DispatchPipeline:
    """Depth-D in-flight entry queue shared by all device plans.

    `materialize(entry)` is the plan's blocking pull + unpack; it must
    return an iterable of results (output batches, or raw chunks for the
    pattern plan).  Entries are materialized strictly in dispatch order
    — device results may complete out of order, but delivery is FIFO so
    output ordering matches the unpipelined path exactly.
    """

    __slots__ = ("plan", "depth", "entries", "_materialize", "_t_disp",
                 "_held", "dispatches", "max_depth", "overlap_s", "wait_s",
                 "origin", "_origins", "inject", "_ready", "prof",
                 "set_trace")

    def __init__(self, plan_name: str, materialize: Callable,
                 depth: int = 0):
        self.plan = plan_name
        self.depth = int(depth)
        self._materialize = materialize
        self.entries: list = []
        self._t_disp: list = []        # dispatch-return time per entry
        self._held = False
        self.dispatches = 0
        self.max_depth = 0
        self.overlap_s = 0.0
        self.wait_s = 0.0
        # fault attribution + injection (core/faults.py): the runtime
        # sets `origin` to the (stream_id, batch) a dispatch round is
        # processing; push() snapshots it per entry so a materialization
        # failure D batches later still names the batch it belongs to
        # (@OnError routing stays exact under pipelining).  `inject` is
        # the "d2h" fault-injection hook, wired by _register_plan.
        self.origin = None
        self._origins: list = []
        self.inject: Optional[Callable] = None
        # device-time profiler (core/profiler.py), wired by
        # runtime._register_plan: the blocking pull below is THE
        # d2h_materialize phase (outermost-wins: inner `transfer`
        # stages inside a plan's materialize are suppressed)
        self.prof = None
        # runtime._set_trace, wired with it: a deferred entry
        # materializes under the trace of the frame it was dispatched for
        self.set_trace = None
        # results materialized but not yet handed to the caller: a later
        # entry failing mid-drain must not discard an earlier entry's
        # already-materialized outputs — they survive here and return on
        # the next collect/drain (zero silent loss under @OnError)
        self._ready: list = []

    def __len__(self) -> int:
        return len(self.entries) + len(self._ready)

    # -- dispatch side ---------------------------------------------------

    def push(self, entry) -> list:
        """Enqueue a dispatched entry; materialize (in FIFO order) any
        entries beyond the configured depth — unless a dispatch round is
        held open, in which case they wait for collect()."""
        self.entries.append(entry)
        self._origins.append(self.origin)
        self._t_disp.append(time.perf_counter())
        self.dispatches += 1
        if len(self.entries) > self.max_depth:
            self.max_depth = len(self.entries)
        if self._held:
            return []
        return self._drain_to(self.depth)

    def hold(self) -> None:
        """Open a dispatch round: push() stops auto-materializing until
        collect() — the runtime holds every subscribed plan, dispatches
        them all, then collects, so plans overlap on device."""
        self._held = True

    def collect(self) -> list:
        """Close a dispatch round: materialize entries beyond depth."""
        self._held = False
        return self._drain_to(self.depth)

    def drain(self) -> list:
        """Flush barrier: materialize EVERYTHING in flight."""
        self._held = False
        return self._drain_to(0)

    def _drain_to(self, target: int) -> list:
        while len(self.entries) > target:
            entry = self.entries.pop(0)
            origin = self._origins.pop(0)
            t_disp = self._t_disp.pop(0)
            t0 = time.perf_counter()
            self.overlap_s += t0 - t_disp
            # frame tracing: a deferred entry still knows the batch it
            # was dispatched for — the plan's transfer/unpack spans
            # (which may land D batches later, on the scheduler thread)
            # record on that frame's tree, and the materialized outputs
            # inherit the handle so sink egress stays connected
            od = None if origin is None \
                else getattr(origin[1], "__dict__", None)
            h = None if od is None or self.set_trace is None \
                else od.get("_trace")
            prev_tr = None if h is None else self.set_trace(h)
            pspan = None
            if self.prof is not None:
                self.prof.note_bytes(self.plan, "d2h",
                                     _entry_device_nbytes(entry))
                pspan = self.prof.phase("d2h_materialize")
                pspan.__enter__()
            try:
                if self.inject is not None:
                    self.inject()       # "d2h" fault-injection point
                res = self._materialize(entry)
                if h is not None:
                    res = list(res)
                    for r in res:
                        b = getattr(r, "batch", None)
                        if b is not None:
                            b.__dict__.setdefault("_trace", h)
                self._ready.extend(res)
            except Exception as e:
                # attribute the failure to the batch this entry was
                # dispatched for; the entry is consumed — later entries
                # stay queued and earlier entries' materialized results
                # stay in _ready, so subsequent collects keep flowing
                if origin is not None \
                        and getattr(e, "fault_origin", None) is None:
                    try:
                        e.fault_origin = origin
                    except Exception:
                        pass
                raise
            finally:
                if pspan is not None:
                    pspan.__exit__(None, None, None)
                if h is not None:
                    self.set_trace(prev_tr)
            self.wait_s += time.perf_counter() - t0
        out, self._ready = self._ready, []
        return out

    # -- retry support (plans that must replay the in-flight chain) ------

    def take_all(self) -> list:
        """Remove and return every queued entry (carry-overflow replay:
        the pre-states of everything dispatched after the failed entry
        are invalid and the whole chain re-dispatches)."""
        entries, self.entries, self._t_disp = self.entries, [], []
        self._origins = []
        return entries

    def requeue(self, entries: list) -> None:
        now = time.perf_counter()
        self.entries.extend(entries)
        # re-dispatched replay entries: origin attribution is lost (they
        # aggregate a replayed chain) — fault routing falls back to
        # propagation for these
        self._origins.extend([None] * len(entries))
        self._t_disp.extend([now] * len(entries))

    # -- telemetry -------------------------------------------------------

    def metrics(self) -> dict:
        m = {"dispatch_queue_depth": len(self.entries),
             "pipeline_depth": self.depth,
             "pipeline_max_depth": self.max_depth,
             "pipeline_dispatches": self.dispatches}
        tot = self.overlap_s + self.wait_s
        if tot > 0.0:
            m["overlap_ratio"] = round(self.overlap_s / tot, 4)
            m["pipeline_overlap_s"] = round(self.overlap_s, 4)
            m["pipeline_wait_s"] = round(self.wait_s, 4)
        return m


class PadPool:
    """Rotating pow2-padded upload buffers, reused across flushes.

    `take(key, n, dtype, min_slots)` returns a zeroed-tail (n,) buffer
    for the caller to fill [:batch_n].  Each key rotates through at
    least `min_slots` buffers so an env retained for a pipelined retry
    (up to depth flushes old) is never aliased by a newer flush —
    callers pass min_slots = pipeline_depth + 2.  jax copies numpy
    arguments to device at dispatch, so a buffer is safe to reuse once
    its slot cycles around.
    """

    def __init__(self):
        self._slots: dict = {}     # key -> [bufs, next_index]

    def reserve(self, key, n: int, dtype, min_slots: int) -> None:
        """Grow a key's rotation to at least min_slots without consuming
        a buffer — called on pad-memo hits so a later plan's deeper
        pipeline still widens the rotation it depends on."""
        ent = self._slots.get(key)
        if ent is None:
            ent = self._slots[key] = [[], 0]
        bufs = ent[0]
        while len(bufs) < max(2, min_slots):
            bufs.append(np.zeros(n, dtype=dtype))

    def take(self, key, n: int, dtype, min_slots: int = 2) -> np.ndarray:
        ent = self._slots.get(key)
        if ent is None:
            ent = self._slots[key] = [[], 0]
        bufs, i = ent
        if len(bufs) < max(2, min_slots):
            # two plans with different depths can share a key: the pool
            # grows to the largest requested rotation
            buf = np.zeros(n, dtype=dtype)
            bufs.append(buf)
            return buf
        buf = bufs[i]
        ent[1] = (i + 1) % len(bufs)
        return buf
