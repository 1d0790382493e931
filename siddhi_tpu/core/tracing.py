"""End-to-end frame tracing — causal cross-thread span trees.

A thread's own clock breaks at every thread hand-off of the serving
path (net reader -> admission park -> WAL append -> dispatch pipeline
-> scheduler-pump materialization -> sink egress).  This module is the
causal plane that survives the hops: one ingested frame yields ONE
trace — a tree of spans linked by explicit (trace_id, span_id,
parent_id) edges, no matter which `siddhi-*` thread recorded each span.
It is one SINK of the engine's span primitive
(`telemetry.StatisticsManager.span`), which records into it whenever
the frame carries a handle.

Pieces:

  * `TraceHandle` — the per-frame carrier.  It rides the `Work` unit
    through admission, the frozen `EventBatch` through dispatch and the
    `DispatchPipeline`, and the sink outbox to egress.  A span parents
    on the handle's current head — the span it is nested in, else the
    stage that finished before it — and becomes the head, so the
    recorded spans form one causal tree (net.decode -> admit ->
    queue_wait -> freeze[wal.append] -> dispatch[host_build -> kernel
    -> transfer -> unpack] -> sink.publish[sink.encode -> sink.send])
    with no orphans.
  * `FrameTracer` — the per-runtime recorder: a bounded always-on ring
    of completed spans (cheap: one deque append per span), sampling
    (`@app:trace(sample='N')` — 1 in N server-assigned frames gets a
    trace; producer-stamped wire trace ids ALWAYS trace), and trace-id
    allocation tagged with host+pid so multi-host dumps merge.
  * the trigger registry — `trigger(kind, detail)` is nonblocking and
    lock-cheap (it only enqueues; safe under engine locks).  A
    triggered kind (`slo_breach`, `breaker_open`, `quarantine`,
    `shed_burst`, `wal_stall`) promotes the ring into a retained dump
    on the `siddhi-trace-export` thread, which also auto-exports Chrome
    `trace_event` JSON (with hostname metadata) to the configured dir.
    Per-kind cooldown bounds dump churn.

Cost (docs/OBSERVABILITY.md has the chip reading): tracing off
(`@app:trace('off')` -> `rt.tracing is None`) or on-but-unsampled, the
hot path is one counter increment and a modulo per frozen frame, and
every other hook is gated on a `None` handle check.
"""
from __future__ import annotations

import itertools
import json
import os
import socket
import threading
import time
from collections import deque
from typing import Callable, Optional

from ..utils.locks import new_lock

# the trigger registry: every kind a dump can cite, with the site that
# fires it (all sites enqueue-only — the promotion/export work runs on
# the siddhi-trace-export thread, never under an engine lock)
TRIGGER_KINDS = (
    "slo_breach",     # slo.SLOController: decision-window p99 > target
    "breaker_open",   # io.Sink: a per-sink circuit breaker opened
    "quarantine",     # runtime: a device plan quarantined onto the interpreter
    "shed_burst",     # net.admission: frames shed by rate limit / watermark
    "wal_stall",      # core.wal: a durability barrier exceeded its budget
    "host_share_breach",  # core.profiler: windowed host-dispatch share
                          # above @app:hostShareAlert — the profile dump
)


class TraceScope(threading.local):
    """`rt._trace_tls`: the frame trace active on this thread (`handle`)
    and the net feed path's deferred-sink depth.  Class defaults, so a
    thread that never set one reads None / 0 at plain attribute cost."""

    handle = None
    defer_sink = 0


class TraceHandle:
    """One frame's trace carrier.  `head` is the span id the NEXT span
    parents on; `mark()` advances it, so sequential stages chain and a
    hand-off to another thread keeps the causal link (the handle object
    itself crosses the thread boundary on the Work/EventBatch/outbox
    entry it rides)."""

    __slots__ = ("tracer", "trace_id", "head")

    def __init__(self, tracer: "FrameTracer", trace_id: str, head: int = 0):
        self.tracer = tracer
        self.trace_id = trace_id
        self.head = head

    def open(self) -> tuple:
        """Start a span: (its id, its parent's).  It is the head while
        open, so spans recorded inside it are its children."""
        sid, parent = next(self.tracer._span_ids), self.head
        self.head = sid
        return sid, parent

    def close(self, sid: int, parent: int, name: str, t0: float,
              dur: float, args: Optional[dict]) -> None:
        """Record the span `open` started (t0 = perf_counter at its
        start); it stays the head: the next stage parents on it."""
        self.tracer._record(self.trace_id, sid, parent, name, t0, dur, args)
        self.head = sid

    def mark(self, name: str, t0: float, dur: float, **args) -> int:
        """Record one already-timed span as a child of the current head
        (the zero-length `frame` root; replayed spans)."""
        sid, parent = self.open()
        self.close(sid, parent, name, t0, dur, args or None)
        return sid

    def ctx(self) -> tuple:
        """(trace_id, head) — the resumable wire/payload form
        (`FrameTracer.resume`)."""
        return (self.trace_id, self.head)


class FrameTracer:
    """Per-runtime span recorder + trigger-promoted flight dumps."""

    def __init__(self, app_name: str, sample_every: int = 16,
                 export_dir: Optional[str] = None,
                 cooldown_s: float = 5.0, capacity: int = 8192,
                 max_dumps: int = 8):
        self.app = app_name
        # 1 in N server-assigned frames gets a trace; 0 disables
        # server-assigned sampling (producer-stamped ids still trace)
        self.sample_every = int(sample_every)
        self.export_dir = export_dir or os.environ.get("SIDDHI_TRACE_DIR")
        self.cooldown_s = float(cooldown_s)
        self.hostname = socket.gethostname()
        self._tag = f"{self.hostname.split('.')[0]}-{os.getpid():x}"
        # completed spans: (trace_id, span_id, parent_id, name, t0_rel,
        # dur, thread_name, args|None).  deque.append is atomic under
        # the GIL — the one hot-path mutation stays lock-free by design
        self._ring: deque = deque(maxlen=int(capacity))
        self._epoch = time.perf_counter()
        self._epoch_wall = time.time()
        self._span_ids = itertools.count(1)
        self._trace_ids = itertools.count(1)
        self._frame_ctr = itertools.count(0)
        self._lock = new_lock("FrameTracer._lock")
        # trigger -> dump machinery (exporter thread owns the slow work)
        self.dumps: deque = deque(maxlen=int(max_dumps))
        self._pending: list = []
        self._last_trigger: dict = {}
        self._wake = threading.Event()
        # a never-started placeholder (is_alive() False): _ensure_exporter
        # swaps in a live one per burst; the constructor assignment also
        # pins the attr's type for the concurrency self-analysis, so
        # `.start()` resolves to threading.Thread, not an engine class
        self._exporter = threading.Thread(name="siddhi-trace-export",
                                          daemon=True)
        self._closed = False
        # gauges (statistics()["tracing"])
        self.traces_started = 0
        self.producer_traces = 0
        self.trigger_counts: dict = {}
        self.triggers_suppressed = 0
        self.exported_files = 0

    # -- recording -----------------------------------------------------------

    def begin_frame(self, stream_id: str, trace_id: Optional[str] = None,
                    parent: int = 0) -> Optional[TraceHandle]:
        """Start a frame trace.  A producer-stamped `trace_id` (wire
        TRACE frame) always traces; otherwise the sampling decision is
        made here — `None` means this frame is unsampled and every
        downstream hook stays on its no-op path.  `parent` is the
        upstream engine's head span id (the TRACE frame's `span`
        field): span ids are only unique per host, so it is recorded as
        the root marker's `remote_parent` annotation — federation
        merges the cross-hop edge via (trace_id, remote_parent) without
        colliding with local span ids."""
        if trace_id is None:
            se = self.sample_every
            if se <= 0 or next(self._frame_ctr) % se:
                return None
            trace_id = f"{self._tag}-{next(self._trace_ids):x}"
            with self._lock:
                self.traces_started += 1
        else:
            with self._lock:
                self.traces_started += 1
                self.producer_traces += 1
        h = TraceHandle(self, str(trace_id))
        # zero-duration root marker: every stage span descends from it
        extra = {"remote_parent": int(parent)} if parent else {}
        h.mark("frame", time.perf_counter(), 0.0, stream=stream_id,
               **extra)
        return h

    def resume(self, trace_id: str, head: int = 0) -> TraceHandle:
        """Re-attach to a trace from its resumable ctx (ErrorStore
        payload replay, cross-hop continuations)."""
        return TraceHandle(self, str(trace_id), int(head))

    def _record(self, trace_id: str, sid: int, parent: int, name: str,
                t0: float, dur: float, args: Optional[dict]) -> None:
        self._ring.append((trace_id, sid, parent, name,
                           t0 - self._epoch, dur,
                           threading.current_thread().name, args))

    # -- read side -----------------------------------------------------------

    def spans(self) -> list:
        """Snapshot of the ring as dicts (tests / the trace endpoint)."""
        return [self._span_dict(s) for s in list(self._ring)]

    @staticmethod
    def _span_dict(s: tuple) -> dict:
        trace_id, sid, parent, name, t0, dur, thread, args = s
        d = {"trace": trace_id, "span": sid, "parent": parent,
             "name": name, "t0_s": round(t0, 6), "dur_s": round(dur, 6),
             "thread": thread}
        if args:
            d["args"] = dict(args)
        return d

    def traces(self) -> dict:
        """{trace_id: [span dicts]} over the current ring."""
        out: dict = {}
        for s in list(self._ring):
            out.setdefault(s[0], []).append(self._span_dict(s))
        return out

    def chrome_events(self, spans: Optional[list] = None,
                      pid: int = 1) -> list:
        """Chrome `trace_event` array for a span snapshot: "X" duration
        events per span plus thread_name metadata, threads mapped to
        stable integer tids."""
        raw = list(self._ring) if spans is None else spans
        tids: dict = {}
        evs = []
        for trace_id, sid, parent, name, t0, dur, thread, args in raw:
            tid = tids.setdefault(thread, len(tids) + 1)
            ev = {"name": name, "cat": "frame", "ph": "X",
                  "ts": round(t0 * 1e6, 1), "dur": round(dur * 1e6, 1),
                  "pid": pid, "tid": tid,
                  "args": {"trace": trace_id, "span": sid,
                           "parent": parent, **(args or {})}}
            evs.append(ev)
        meta = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                 "args": {"name": f"{self.hostname}/{self.app}"}}]
        for thread, tid in sorted(tids.items(), key=lambda kv: kv[1]):
            meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                         "tid": tid, "args": {"name": thread}})
        return meta + evs

    def chrome_dump(self, spans: Optional[list] = None,
                    extra_meta: Optional[dict] = None) -> dict:
        """The exported/HTTP-served object form: {"traceEvents": [...],
        "metadata": {hostname, app, ...}} — hostname rides every dump so
        cross-host federation can merge them."""
        raw = list(self._ring) if spans is None else spans
        slowest = None
        for s in raw:
            if s[3] == "frame":
                continue                    # zero-dur root markers
            if slowest is None or s[5] > slowest[5]:
                slowest = s
        meta = {"hostname": self.hostname, "app": self.app,
                "epoch_unix_s": round(self._epoch_wall, 3),
                "spans": len(raw)}
        if slowest is not None:
            meta["slowest"] = {"name": slowest[3],
                               "dur_ms": round(slowest[5] * 1e3, 4),
                               "trace": slowest[0],
                               **({"args": slowest[7]} if slowest[7]
                                  else {})}
        if extra_meta:
            meta.update(extra_meta)
        return {"traceEvents": self.chrome_events(raw), "metadata": meta}

    # -- triggers ------------------------------------------------------------

    def trigger(self, kind: str, detail: str = "") -> bool:
        """Ask for a retained dump.  NONBLOCKING and safe under engine
        locks: this only enqueues — snapshotting the ring, building the
        dump, and writing the export file all happen on the
        `siddhi-trace-export` thread.  Per-kind cooldown; returns
        whether the trigger was accepted."""
        if self._closed:
            return False
        now = time.monotonic()
        with self._lock:
            last = self._last_trigger.get(kind)
            if last is not None and now - last < self.cooldown_s:
                self.triggers_suppressed += 1
                return False
            self._last_trigger[kind] = now
            self.trigger_counts[kind] = self.trigger_counts.get(kind, 0) + 1
            self._pending.append((kind, str(detail), time.time()))
        self._wake.set()
        self._ensure_exporter()
        return True

    def _ensure_exporter(self) -> None:
        # the thread is CONSTRUCTED and STARTED outside the tracer lock
        # (trigger() may be called under engine locks; a spawn must not
        # widen that hold) — only the reference swap is guarded, and a
        # loser that finds the slot already live never starts its thread
        t = threading.Thread(target=self._export_loop,
                             name="siddhi-trace-export", daemon=True)
        with self._lock:
            if self._exporter.is_alive():
                return
            self._exporter = t
        t.start()

    def _export_loop(self) -> None:
        """Drain pending triggers; self-terminates after a short idle so
        a runtime that never shuts down cleanly cannot leak a live
        thread past the conftest leak gate."""
        while True:
            self._wake.wait(0.5)
            self._wake.clear()
            worked = False
            while True:
                with self._lock:
                    item = self._pending.pop(0) if self._pending else None
                if item is None:
                    break
                worked = True
                try:
                    self._promote(item)
                except Exception:
                    # a failed export must never kill the exporter loop
                    # mid-queue; the dump is simply lost
                    pass
            if self._closed or not worked:
                with self._lock:
                    if not self._pending:
                        # leave self._exporter pointing at THIS (about to
                        # finish) thread: is_alive() goes False and the
                        # next trigger swaps in a fresh one
                        return

    def _promote(self, item: tuple) -> None:
        """One trigger -> retained dump (+ optional file export)."""
        kind, detail, wall_ts = item
        spans = list(self._ring)
        dump = {"reason": kind, "detail": detail,
                "at_unix_s": round(wall_ts, 3), "spans": len(spans),
                "chrome": self.chrome_dump(
                    spans, extra_meta={"reason": kind, "detail": detail})}
        # export BEFORE publication: a dump visible through dumps /
        # dump_summaries / statistics()["tracing"] must never mutate
        # afterwards — the old order set dump["path"] outside the lock
        # on an already-published dict, a torn read for any scraper
        path = None
        if self.export_dir:
            try:
                os.makedirs(self.export_dir, exist_ok=True)
                safe_app = self.app.replace(os.sep, "_") or "_app"
                with self._lock:
                    n = self.exported_files
                path = os.path.join(
                    self.export_dir, f"trace-{safe_app}-{kind}-{n}.json")
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(dump["chrome"], f)
                os.replace(tmp, path)
            except OSError:
                path = None
        with self._lock:
            if path is not None:
                dump["path"] = path
                self.exported_files += 1
            self.dumps.append(dump)

    # -- lifecycle / telemetry ----------------------------------------------

    def close(self, timeout: float = 2.0) -> None:
        """Flush pending triggers and join the exporter (bounded)."""
        self._closed = True
        with self._lock:
            t = self._exporter
        self._wake.set()
        if t.ident is not None:     # never-started placeholder: no join
            t.join(timeout=timeout)

    def reopen(self) -> None:
        """Re-arm a closed tracer (a shutdown()/start() cycle in one
        process — the WAL-reopen analog): triggers enqueue again and the
        exporter respawns on the next one.  The ring and counters carry
        across generations; a no-op on a live tracer."""
        self._closed = False

    def metrics(self) -> dict:
        with self._lock:
            return {"sample_every": self.sample_every,
                    "ring_spans": len(self._ring),
                    "traces_started": self.traces_started,
                    "producer_traces": self.producer_traces,
                    "dumps": len(self.dumps),
                    "triggers": dict(self.trigger_counts),
                    "triggers_suppressed": self.triggers_suppressed,
                    "exported_files": self.exported_files}

    def dump_summaries(self) -> list:
        with self._lock:
            return [{k: v for k, v in d.items() if k != "chrome"}
                    for d in self.dumps]


def tracer_from_annotations(app) -> Optional[FrameTracer]:
    """Build the runtime's tracer from `@app:trace(...)`:

        @app:trace('off')                 -- rt.tracing is None (zero cost)
        @app:trace('all')                 -- every frame traced
        (default / 'sampled')             -- 1 in 16 frames traced
        @app:trace(sample='64')           -- 1 in 64
        @app:trace(dir='/var/traces')     -- triggered-dump export dir
        @app:trace(cooldown='1')          -- per-kind trigger cooldown (s)

    $SIDDHI_TRACE_DIR supplies the export dir when `dir=` is absent;
    $SIDDHI_TRACE_SAMPLE overrides the default sampling for apps
    without the annotation."""
    from ..query import ast as qast
    ann = qast.find_annotation(app.annotations, "app:trace")
    mode = None
    sample = None
    export_dir = None
    cooldown = 5.0
    if ann is not None:
        mode = (ann.element() or "").lower() or None
        for k, v in ann.elements:
            if k is None:
                continue
            kl = k.lower()
            if kl == "sample":
                sample = int(v)
            elif kl == "dir":
                export_dir = v
            elif kl == "cooldown":
                cooldown = float(v)
    if mode == "off":
        return None
    if mode in ("on", "all"):
        sample = 1
    if sample is None:
        env = os.environ.get("SIDDHI_TRACE_SAMPLE")
        sample = int(env) if env else 16
    return FrameTracer(app.name, sample_every=sample,
                       export_dir=export_dir, cooldown_s=cooldown)
