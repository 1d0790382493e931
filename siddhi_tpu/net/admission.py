"""Admission control for the serving plane.

Per-stream token-bucket rate limits plus byte watermarks, declared on
net sources (`@source(type='tcp', rate.limit='50000',
shed.policy='shed', max.pending='4 MB')`) and consulted by every
transport that feeds the stream (TCP/WS connections, the shm ring,
and the service front door share ONE controller per stream, so the
limit is global, not per-connection).

Three shed policies once the bucket is empty:

    block  - the caller waits (`decision.wait_s`); a TCP reader thread
             that waits stops draining its socket, which is kernel-level
             backpressure all the way to the producer, and the server
             withholds CREDIT frames.
    shed   - the NEW frame is dropped into the runtime's ErrorStore
             (decoded to replayable events — zero unaccounted loss;
             `rt.error_store.replay(rt)` re-ingests once load clears).
    oldest - the new frame parks in a bounded pending queue; when the
             queue's byte watermark overflows, the OLDEST pending frame
             sheds to the ErrorStore (freshest-data-wins, the classic
             ticker-plant policy).  `pump()` drains pending frames as
             tokens refill.

The PR-5 SLO controller lowers admission BEFORE latency collapses via
`set_rate_factor` (slo.SLOController.admission_factor): p99 over
target scales every bucket's refill rate down, recovery raises it back
to 1.0.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

from ..utils.locks import new_lock

ADMIT = "admit"
SHED = "shed"
WAIT = "wait"
QUEUED = "queued"


class TokenBucket:
    """Classic token bucket in event units.  `rate` tokens/s refill up
    to `burst`; `None` rate = unlimited.  A monotonic-clock callable
    makes tests deterministic."""

    def __init__(self, rate: Optional[float], burst: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        # `rate=0` means ADMIT NOTHING (a declared quarantine: every
        # frame sheds/blocks, accounted) — only None means unlimited
        self.rate = float(rate) if rate is not None else None
        self.burst = float(burst) if burst is not None else \
            (self.rate if self.rate else 0.0)
        self.factor = 1.0               # SLO admission factor (0 < f <= 1)
        self._tokens = self.burst
        self._clock = clock
        self._t = clock()

    @property
    def effective_rate(self) -> Optional[float]:
        return None if self.rate is None else self.rate * self.factor

    def _refill(self) -> None:
        now = self._clock()
        dt = now - self._t
        self._t = now
        if self.rate is not None and dt > 0:
            self._tokens = min(self.burst, self._tokens
                               + dt * self.rate * self.factor)

    def try_take(self, n: float) -> float:
        """Take `n` tokens if available; returns 0.0 on success, else
        the estimated seconds until `n` tokens exist (never takes a
        partial amount)."""
        if self.rate is None:
            return 0.0
        self._refill()
        if self._tokens >= n:
            self._tokens -= n
            return 0.0
        rate = max(self.rate * self.factor, 1e-9)
        return (n - self._tokens) / rate

    def set_factor(self, f: float) -> None:
        self._refill()                  # settle at the old rate first
        self.factor = min(1.0, max(0.01, float(f)))


@dataclass
class Work:
    """One admitted-or-pending unit: a decoded frame ready to feed.
    `feed` ingests it (already bound to runtime + stream); `rows`
    lazily decodes to [(ts_ms, row_tuple), ...] for ErrorStore
    capture on shed.  `trace` is the frame's TraceHandle
    (core/tracing.py) — it rides the park queue, so a frame drained
    and fed on ANOTHER thread (scheduler pump, a later connection
    tick) still lands its spans on the same tree."""
    n: int
    nbytes: int
    feed: Callable[[], None]
    rows: Callable[[], list]
    stream_id: str = ""
    trace: object = None
    # perf_counter at the end of its `admit` span, when that was timed:
    # where its `queue_wait` span (park + runtime gate) starts
    t_admit: Optional[float] = None


@dataclass
class Decision:
    action: str                         # ADMIT | SHED | WAIT | QUEUED
    wait_s: float = 0.0
    ready: list = field(default_factory=list)   # pending work now admitted


def parse_bytes(text) -> int:
    """'4 MB' / '512 KB' / '65536' -> bytes."""
    if text is None:
        return 0
    s = str(text).strip().lower()
    for suffix, mult in (("gb", 1 << 30), ("mb", 1 << 20), ("kb", 1 << 10),
                         ("g", 1 << 30), ("m", 1 << 20), ("k", 1 << 10),
                         ("b", 1)):
        if s.endswith(suffix):
            return int(float(s[:-len(suffix)].strip()) * mult)
    return int(float(s))


class AdmissionController:
    """Per-stream admission: rate limit + shed policy + pending-byte
    watermark.  Thread-safe — every transport feeding the stream shares
    one instance (registered in `rt.admission[stream_id]`)."""

    POLICIES = ("block", "shed", "oldest")

    def __init__(self, stream_id: str, rate_limit: Optional[float] = None,
                 policy: str = "block", max_pending_bytes: int = 4 << 20,
                 burst: Optional[float] = None, error_store=None,
                 on_fault: Optional[Callable] = None,
                 clock: Callable[[], float] = time.monotonic,
                 now_ms: Optional[Callable[[], int]] = None,
                 on_shed: Optional[Callable[[str, str], None]] = None):
        policy = (policy or "block").lower()
        if policy not in self.POLICIES:
            raise ValueError(f"stream {stream_id!r}: unknown shed.policy "
                             f"{policy!r} (have: block | shed | oldest)")
        self.stream_id = stream_id
        self.policy = policy
        self.bucket = TokenBucket(rate_limit, burst, clock)
        self.max_pending_bytes = int(max_pending_bytes)
        self.error_store = error_store
        self.on_fault = on_fault        # stats.on_fault hook
        # shed-burst trace trigger (core/tracing.py): nonblocking
        # enqueue, safe under this controller's lock; the tracer's
        # per-kind cooldown turns a shed storm into at most one dump.
        # Named after its target (FrameTracer.trigger) like wal's
        # injected `inject`, so the static lock graph composes the
        # AdmissionController._lock -> FrameTracer._lock edge the
        # runtime lock-witness observes
        self.trigger = on_shed
        self.now_ms = now_ms or (lambda: int(time.time() * 1000))
        self._pending: deque = deque()  # Work, oldest first
        self._inflight = 0              # drained-but-not-yet-fed frames
        self._lock = new_lock("AdmissionController._lock")
        # gauges/counters (statistics()["net"] + Prometheus)
        self.frames_in = 0
        self.events_in = 0
        self.bytes_in = 0
        self.admitted_events = 0
        self.shed_frames = 0
        self.shed_events = 0
        self.blocked_s = 0.0
        self.pending_bytes = 0

    # -- core ---------------------------------------------------------------

    def offer(self, work: Work) -> Decision:
        """Admit, queue, shed, or ask the caller to wait.  Admitted
        pending work (oldest policy) rides `Decision.ready` — the caller
        feeds those IN ORDER before `work` itself."""
        return self._decide(work, count=True)

    def submit(self, work: Work, stop: Optional[Callable[[], bool]] = None,
               sleep: Callable[[float], None] = time.sleep) -> Decision:
        """offer() plus the block-policy wait loop: under 'block' this
        call sleeps (in <=50 ms parks, so `stop` — shutdown — stays
        responsive) until tokens refill, which is what stalls a TCP
        reader thread and turns into kernel backpressure.  If `stop`
        fires first the frame sheds to the ErrorStore (accounted, never
        silently dropped)."""
        d = self._decide(work, count=True)
        while d.action == WAIT:
            if stop is not None and stop():
                with self._lock:
                    self._shed_locked(work, "transport stopping")
                return Decision(SHED, ready=d.ready)
            t0 = time.monotonic()
            sleep(min(d.wait_s, 0.05))
            with self._lock:
                self.blocked_s += time.monotonic() - t0
            nxt = self._decide(work, count=False)
            nxt.ready = d.ready + nxt.ready
            d = nxt
        return d

    def _decide(self, work: Work, count: bool) -> Decision:
        with self._lock:
            if count:
                self.frames_in += 1
                self.events_in += work.n
                self.bytes_in += work.nbytes
            # a frame with more events than the bucket can EVER hold
            # would wait forever under 'block' and jam the queue head
            # under 'oldest': shed it loudly (accounted + replayable —
            # replay re-enters via row ingest, which is not bucketed)
            if count and self.bucket.rate is not None \
                    and work.n > self.bucket.burst:
                self._shed_locked(
                    work, f"frame of {work.n} events exceeds the bucket "
                          f"burst ({self.bucket.burst:.0f}); split the "
                          f"batch or raise burst")
                return Decision(SHED, ready=self._drain_locked())
            ready = self._drain_locked()
            if self._pending or self._inflight:
                # order preserved: new work can never jump queued work,
                # including drained frames another thread is still
                # feeding outside this lock (admitting around those
                # would reorder one producer's frames)
                return self._enqueue_locked(work, ready)
            wait = self.bucket.try_take(work.n)
            if wait <= 0.0:
                self.admitted_events += work.n
                return Decision(ADMIT, ready=ready)
            if self.policy == "shed":
                self._shed_locked(work, "rate limit exceeded")
                return Decision(SHED, ready=ready)
            if self.policy == "oldest":
                return self._enqueue_locked(work, ready)
            return Decision(WAIT, wait_s=wait, ready=ready)

    def pump(self) -> list:
        """Admit pending work whose tokens have refilled (oldest
        policy); returns the Work list to feed, in order."""
        with self._lock:
            return self._drain_locked()

    def pending_count(self) -> int:
        """Frames admitted-but-not-yet-fed: parked in the 'oldest'
        queue or drained and still feeding on another thread.  The
        durable-ACK barrier waits on this — an ACK must never cover a
        frame that exists only in memory."""
        with self._lock:
            return len(self._pending) + self._inflight

    def feed_safely(self, work: Work) -> None:
        """Feed one admitted unit, capturing a failure into the
        ErrorStore — admitted work must never vanish.  (The server's
        own Work.feed closures self-capture; this guards feeds whose
        closure does not, e.g. queued REST batches drained by the
        runtime scheduler pump.)"""
        try:
            work.feed()
        except Exception as e:
            if self.error_store is None:
                raise
            if not getattr(e, "_wal_captured", False):
                # (a WAL append failure already captured the frame —
                # a second entry would double-ingest on replay)
                try:
                    rows = work.rows()
                except Exception:
                    rows = []
                self.error_store.add(
                    work.stream_id or self.stream_id, "net.feed", e,
                    self.now_ms(), events=rows)
            if self.on_fault is not None:
                try:
                    self.on_fault(self.stream_id, "net.feed")
                except Exception:
                    pass

    def flush_pending_to_store(self, reason: str = "source stopped") -> int:
        """Teardown: every still-pending frame sheds to the ErrorStore
        so nothing admitted-but-unfed is silently lost."""
        with self._lock:
            n = 0
            while self._pending:
                self._shed_locked(self._pending.popleft(), reason,
                                  from_pending=True)
                n += 1
            self.pending_bytes = 0
            return n

    def _drain_locked(self) -> list:
        if self._inflight:
            # strict FIFO: a previous drain's frames are still being
            # fed on another thread — handing out more now could feed
            # them out of order
            return []
        out = []
        while self._pending:
            head = self._pending[0]
            if self.bucket.try_take(head.n) > 0.0:
                break
            self._pending.popleft()
            self.pending_bytes -= head.nbytes
            self.admitted_events += head.n
            out.append(self._tracked(head))
        self._inflight = len(out)
        return out

    def _tracked(self, work: Work) -> Work:
        """Wrap a drained frame's feed so the in-flight count drops when
        it lands — every consumer (connection threads, the scheduler
        pump, REST handlers) feeds via `Work.feed`, so no call-site
        changes are needed."""
        inner = work.feed

        def feed():
            try:
                inner()
            finally:
                with self._lock:
                    self._inflight -= 1

        return Work(n=work.n, nbytes=work.nbytes, feed=feed,
                    rows=work.rows, stream_id=work.stream_id,
                    trace=work.trace)

    def _enqueue_locked(self, work: Work, ready: list) -> Decision:
        self._pending.append(work)
        self.pending_bytes += work.nbytes
        while self.pending_bytes > self.max_pending_bytes \
                and len(self._pending) > 1:
            oldest = self._pending.popleft()
            self.pending_bytes -= oldest.nbytes
            self._shed_locked(oldest, "pending watermark overflow",
                              from_pending=True)
        if self._pending and self.pending_bytes > self.max_pending_bytes:
            # a single frame larger than the watermark: shed it outright
            lone = self._pending.popleft()
            self.pending_bytes -= lone.nbytes
            self._shed_locked(lone, "frame exceeds pending watermark",
                              from_pending=True)
            if lone is work:
                # the just-offered frame itself was shed — telling the
                # caller QUEUED would promise a feed that never comes
                # (REST maps QUEUED to 202 "queued")
                return Decision(SHED, ready=ready)
        return Decision(QUEUED, ready=ready)

    def _shed_locked(self, work: Work, why: str,
                     from_pending: bool = False) -> None:
        self.shed_frames += 1
        self.shed_events += work.n
        if self.trigger is not None:
            try:
                self.trigger("shed_burst",
                             f"stream {self.stream_id!r}: {why} "
                             f"({self.shed_frames} frames shed)")
            except Exception:
                pass
        if self.on_fault is not None:
            try:
                self.on_fault(self.stream_id, "net.shed")
            except Exception:
                pass
        if self.error_store is not None:
            try:
                rows = work.rows()
            except Exception as e:      # decode failed: account anyway
                rows = []
                why = f"{why}; row decode failed: {e}"
            self.error_store.add(
                work.stream_id or self.stream_id, "net.shed",
                f"admission shed ({self.policy}): {why}",
                self.now_ms(), events=rows)

    # -- SLO hook -----------------------------------------------------------

    def set_rate_factor(self, f: float) -> None:
        """PR-5 SLO controller hook: scale the admitted rate (0..1] so
        overload lowers admission BEFORE engine p99 collapses.  Locked:
        set_factor refills the bucket, which races try_take's own
        read-modify-write on connection threads."""
        with self._lock:
            self.bucket.set_factor(f)

    # -- telemetry ----------------------------------------------------------

    def metrics(self) -> dict:
        with self._lock:
            m = {"policy": self.policy,
                 "frames_in": self.frames_in,
                 "events_in": self.events_in,
                 "bytes_in": self.bytes_in,
                 "admitted_events": self.admitted_events,
                 "shed_frames": self.shed_frames,
                 "shed_events": self.shed_events,
                 "pending_frames": len(self._pending),
                 "pending_bytes": self.pending_bytes,
                 "blocked_seconds": round(self.blocked_s, 6),
                 "rate_factor": self.bucket.factor}
            if self.bucket.rate is not None:
                m["rate_limit_eps"] = self.bucket.rate
            return m


def controller_from_options(stream_id: str, options: dict, rt,
                            clock=time.monotonic) -> AdmissionController:
    """Build a controller from @source annotation options
    (`rate.limit`, `shed.policy`, `max.pending`, `burst`)."""
    rate = options.get("rate.limit")
    tracer = getattr(rt, "tracing", None)
    return AdmissionController(
        stream_id,
        rate_limit=float(rate) if rate is not None else None,
        policy=options.get("shed.policy", "block"),
        max_pending_bytes=parse_bytes(options.get("max.pending")) or (4 << 20),
        burst=float(options["burst"]) if options.get("burst") else None,
        error_store=rt.error_store,
        on_fault=rt.stats.on_fault,
        clock=clock,
        now_ms=rt.now_ms,
        on_shed=None if tracer is None else tracer.trigger)
