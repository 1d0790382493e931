"""Continuous device-time attribution: the per-dispatch phase profiler.

Host dispatch/materialization — not the kernel — often bounds end-to-end
eps.  This module makes that attribution continuous and per-plan in a
*live* engine: every dispatch round (pattern
scan/dfa/chunk/seq, window, join, filter, fused multi-query — plus the
runtime's sink egress) attributes its wall time into six phases:

    h2d_upload        host->device argument upload (timed `device_put`
                      of the numpy leaves, sampled rounds only)
    kernel_compute    device execution (timed `block_until_ready`,
                      sampled rounds only)
    d2h_materialize   blocking result pull + unpack (DispatchPipeline
                      materialize / the `transfer` stage)
    host_pack_unpack  host-side batch build + callback scatter (the
                      `host_build` / `scatter` stages)
    python_dispatch   residual: python plan code, jit call overhead,
                      cache probes — whatever the round spent that no
                      explicit phase claimed
    sink_egress       sink payload delivery (runtime sink outbox flush)

Why sampling: JAX dispatch is async — a jitted call returns once the
device owns the work, so on the steady-state path kernel time is only
*observable* by blocking.  Blocking every round would serialize the
host/device overlap the pipeline exists to create, so kernel + h2d are
measured on a duty cycle (`@app:profile('sample=N')`, default 1-in-32
of the rounds that actually dispatch a warm kernel — collect polls and
scheduler pumps don't consume the cycle) and extrapolated: unsampled
rounds pay two clock reads and a dict merge.
The extrapolated kernel time is *subtracted* from the raw materialize
wall (which absorbs the device wait on unsampled rounds), so the
published shares are an estimate of the true steady-state split, and
always normalize to sum 1.0.

The sampled h2d probe relies on a JAX invariant: `jax.device_put` of a
numpy array yields a device array with the *identical* ShapedArray aval,
so substituting the uploaded leaves into the jit call triggers no
recompile and no second upload.

Surfaces: `rt.profile()` (totals + windowed ring),
`GET /siddhi/artifact/profile`, Prometheus
`siddhi_tpu_phase_seconds_total{plan,phase}` /
`siddhi_tpu_host_dispatch_share{plan}`, and a host-share breach trigger
(`@app:hostShareAlert(0.7)`) that promotes a flight-recorder dump via
the tracing trigger registry (docs/OBSERVABILITY.md).

Threading: dispatch rounds run on whatever thread drives `_drain`
(caller, scheduler pump, ingest worker) — round state is thread-local
and merged into the shared accumulators under `PhaseProfiler._lock`
once per round.  The profiler spawns no threads.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Callable, Optional

from ..utils.locks import new_lock
from .telemetry import NOOP_SPAN, Histogram, Span

PHASES = ("h2d_upload", "kernel_compute", "d2h_materialize",
          "host_pack_unpack", "python_dispatch", "sink_egress")

DEVICE_PHASES = ("h2d_upload", "kernel_compute", "d2h_materialize")
HOST_PHASES = ("host_pack_unpack", "python_dispatch", "sink_egress")

# pseudo-plans: attribution that belongs to the dispatch loop, not a
# device plan ("_runtime" = scatter/emit between rounds, "_sink" = sink
# outbox egress)
PSEUDO_PLANS = ("_runtime", "_sink")


def _no_span(name: str):
    return NOOP_SPAN


class _Acc:
    """Per-plan accumulator (one for the running totals, one per live
    ring window).  Mutated only under the profiler lock."""

    __slots__ = ("rounds", "kernel_rounds", "sampled_rounds", "events",
                 "wall_s", "kernel_wall_s", "sampled_wall_s", "phases",
                 "bytes_h2d", "bytes_d2h", "hist")

    def __init__(self):
        self.rounds = 0
        self.kernel_rounds = 0       # rounds that dispatched a warm kernel
        self.sampled_rounds = 0      # ... of which the probe blocked+timed
        self.events = 0
        self.wall_s = 0.0
        self.kernel_wall_s = 0.0
        self.sampled_wall_s = 0.0
        self.phases: dict = {}
        self.bytes_h2d = 0
        self.bytes_d2h = 0
        self.hist = Histogram()      # round wall -> p99

    def merge_round(self, wall: float, sampled: bool, has_kernel: bool,
                    phases: dict, events: int, bytes_h2d: int,
                    bytes_d2h: int) -> None:
        self.rounds += 1
        self.events += events
        self.wall_s += wall
        if has_kernel:
            self.kernel_rounds += 1
            self.kernel_wall_s += wall
            if sampled:
                self.sampled_rounds += 1
                self.sampled_wall_s += wall
        for k, v in phases.items():
            self.phases[k] = self.phases.get(k, 0.0) + v
        self.bytes_h2d += bytes_h2d
        self.bytes_d2h += bytes_d2h
        self.hist.record(wall)


class _Round:
    """Thread-local state of one open dispatch round.  Lock-free by
    construction: only the owning thread touches it."""

    __slots__ = ("plan", "sampled", "has_kernel", "phases", "attr_total",
                 "cur_phase", "bytes_h2d", "bytes_d2h")

    def __init__(self, plan: str):
        self.plan = plan
        # sampling is decided LAZILY at the first warm kernel call: the
        # dispatch loop opens many kernel-less rounds (collect polls,
        # scheduler pumps), and a duty cycle counted per round would
        # mostly land the probe on rounds with nothing to measure
        self.sampled = None       # None = no kernel seen yet
        self.has_kernel = False
        self.phases: dict = {}
        self.attr_total = 0.0     # explicitly attributed seconds so far
        self.cur_phase = None     # owner of the open phase span, if any
        self.bytes_h2d = 0
        self.bytes_d2h = 0

    def add(self, name: str, dt: float) -> None:
        if dt < 0.0:
            dt = 0.0
        self.phases[name] = self.phases.get(name, 0.0) + dt
        self.attr_total += dt


class _RoundCM(Span):
    __slots__ = ("prof", "plan", "events", "t0", "rd", "nested")

    def __init__(self, prof: "PhaseProfiler", plan: str, events: int):
        self.prof = prof
        self.plan = plan
        self.events = events

    def __enter__(self):
        tls = self.prof._tls
        if getattr(tls, "round", None) is not None:
            # a round within a round (fused plan delegating to its inner
            # plan, a replay loop re-entering): the outer round owns the
            # attribution — this marker is a no-op
            self.nested = True
            return self
        self.nested = False
        self.rd = _Round(self.plan)
        tls.round = self.rd
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.nested:
            return False
        wall = time.perf_counter() - self.t0
        rd = self.rd
        self.prof._tls.round = None
        # residual: round wall no explicit phase claimed — python plan
        # code, jit-call overhead, cache probes, arg packing
        py = wall - rd.attr_total
        if py > 0.0:
            rd.phases["python_dispatch"] = \
                rd.phases.get("python_dispatch", 0.0) + py
        self.prof._merge_round(self.plan, wall, bool(rd.sampled),
                               rd.has_kernel, rd.phases, self.events,
                               rd.bytes_h2d, rd.bytes_d2h)
        return False


class _PhaseSpan(Span):
    """Outermost-wins phase span.  Nested spans mapping into an already
    open phase (the `transfer` stage inside the pipeline's materialize
    wrap) are suppressed; explicit attributions made *inside* the span
    (a sampled kernel re-dispatch during an M-overflow replay) are
    subtracted, so one second of wall is never counted twice."""

    __slots__ = ("prof", "name", "plan", "events", "t0", "rd", "mark",
                 "direct")

    _SUPPRESSED = -1.0

    def __init__(self, prof: "PhaseProfiler", name: str, plan: str,
                 events: int):
        self.prof = prof
        self.name = name
        self.plan = plan            # takes the span outside any round
        self.events = events

    def __enter__(self):
        rd = getattr(self.prof._tls, "round", None)
        self.rd = rd
        if rd is None:
            # outside any round (callback scatter between rounds, sink
            # egress): attribute directly to the span's pseudo-plan
            self.direct = True
            self.mark = 0.0
        else:
            self.direct = False
            if rd.cur_phase is None:
                rd.cur_phase = self.name
                self.mark = rd.attr_total
            else:
                self.mark = self._SUPPRESSED
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.direct:
            self.prof.note(self.plan, self.name, dt, self.events)
        elif self.mark != self._SUPPRESSED:
            rd = self.rd
            inner = rd.attr_total - self.mark
            rd.add(self.name, max(0.0, dt - inner))
            rd.cur_phase = None
        return False


class PhaseProfiler:
    """The per-runtime attribution plane.  `mode` is 'sample' or 'all'
    ('off' never constructs one — `rt.profiler is None`)."""

    def __init__(self, app_name: str, mode: str = "sample",
                 sample_every: int = 32, window_s: float = 5.0,
                 host_share_alert: float = 0.7, ring: int = 120):
        self.app = app_name
        self.mode = mode
        self.sample_every = 1 if mode == "all" else max(1, int(sample_every))
        self.window_s = float(window_s)
        self.host_share_alert = float(host_share_alert)
        # wired by the runtime to the tracing trigger registry
        # (enqueue-only, safe under engine locks)
        self.on_host_share_breach: Optional[Callable] = None
        self._tls = threading.local()
        self._rctr = itertools.count(0)   # round counter (duty cycle)
        self._lock = new_lock("PhaseProfiler._lock")
        # totals + the live window under construction, both per plan
        self._totals: dict = {}           # plan -> _Acc
        self._cur: dict = {}              # plan -> _Acc
        self._batch_wall_s = 0.0          # full dispatch-loop batch wall
        self._batch_events = 0
        self._cur_batch_wall_s = 0.0
        self._cur_batch_events = 0
        self._win_t0 = time.monotonic()
        self._win_wall = time.time()
        self._windows: list = []          # ring of rolled window dicts
        self._ring_cap = int(ring)
        self.probe_failures = 0
        self.breaches = 0

    # -- hot-path hooks ------------------------------------------------------

    def round(self, plan: str, events: int = 0) -> _RoundCM:
        """Wrap one plan dispatch round (process / collect / finalize)."""
        return _RoundCM(self, plan, events)

    def phase(self, name: str, plan: str = "_runtime",
              events: int = 0) -> _PhaseSpan:
        """Wrap a region whose wall belongs to one phase (outermost
        wins; see _PhaseSpan).  Outside any round it is attributed to
        the pseudo-plan `plan`."""
        return _PhaseSpan(self, name, plan, events)

    def run_kernel(self, fn, args: tuple, cache_hit: bool = True,
                   span: Optional[Callable] = None):
        """Invoke a jitted kernel.  On a sampled round: time the numpy
        leaf upload (h2d_upload) and the device execution via
        `block_until_ready` (kernel_compute).  Unsampled rounds and
        compile calls (cache_hit=False — trace+XLA time must not skew
        the kernel estimate) dispatch untouched.

        `span(name)` opens an engine span (telemetry.call_kernel passes
        the runtime's): the upload and the dispatch are its `kernel`
        span on every round, and the probe's wait for the device is a
        `transfer` span of its own, so that the sampled rounds do not
        read as slow dispatches.

        The duty cycle counts KERNEL-carrying rounds, decided here on
        the round's first warm call: collect polls and scheduler pumps
        open rounds with no kernel, and a per-round cycle would burn
        most of its samples on them."""
        rd = getattr(self._tls, "round", None)
        if rd is not None and cache_hit:
            rd.has_kernel = True
            if rd.sampled is None:
                se = self.sample_every
                rd.sampled = se <= 1 or (next(self._rctr) % se == 0)
        if rd is None or not cache_hit or not rd.sampled:
            if span is None:
                return fn(*args)
            with span("kernel"):    # (call_kernel spans a compile itself)
                return fn(*args)
        if span is None:
            span = _no_span
        # the probe owns the open phase: a phase-mapped span inside it
        # (`transfer`) must not count the wait a second time
        owner, rd.cur_phase = rd.cur_phase, "kernel_compute"
        try:
            import jax
            with span("kernel"):
                t0 = time.perf_counter()
                args = tuple(_device_put_leaves(a) for a in args)
                t1 = time.perf_counter()
                out = fn(*args)
            with span("transfer"):
                out = jax.block_until_ready(out)
            t2 = time.perf_counter()
        except Exception:
            with self._lock:
                self.probe_failures += 1
            return fn(*args)
        finally:
            rd.cur_phase = owner
        rd.add("h2d_upload", t1 - t0)
        # t1..t2 = python dispatch + device execution; the dispatch-call
        # overhead is small vs a blocked kernel and is what this phase
        # names anyway
        rd.add("kernel_compute", t2 - t1)
        return out

    def note_bytes(self, plan: str, direction: str, nbytes: int) -> None:
        """H2D/D2H payload bytes for the current round (lock-free: the
        open round is thread-local; merged at round end)."""
        if not nbytes:
            return
        rd = getattr(self._tls, "round", None)
        if rd is None:
            with self._lock:
                acc = self._acc_locked(plan)
                if direction == "h2d":
                    acc[0].bytes_h2d += nbytes
                    acc[1].bytes_h2d += nbytes
                else:
                    acc[0].bytes_d2h += nbytes
                    acc[1].bytes_d2h += nbytes
            return
        if direction == "h2d":
            rd.bytes_h2d += nbytes
        else:
            rd.bytes_d2h += nbytes

    def note(self, plan: str, phase: str, seconds: float,
             events: int = 0) -> None:
        """Attribute an already-measured span outside any round (sink
        egress, scatter between rounds)."""
        ph = {phase: seconds}
        self._merge_round(plan, seconds, False, False, ph, events, 0, 0)

    def note_batch(self, seconds: float, events: int) -> None:
        """One full dispatch-loop batch wall (the coverage denominator)."""
        with self._lock:
            self._batch_wall_s += seconds
            self._batch_events += events
            self._cur_batch_wall_s += seconds
            self._cur_batch_events += events

    def maybe_roll(self, now: Optional[float] = None) -> None:
        """Roll the live window into the ring once window_s elapsed;
        called from the dispatch loop between batches (one clock read
        when nothing to do)."""
        now = time.monotonic() if now is None else now
        # lock-free fast path: a stale _win_t0 read only delays the roll
        # by one batch; the locked re-check below decides
        # lint: allow (unlocked fast-path read; locked re-check decides)
        if now - self._win_t0 < self.window_s:
            return
        breach_detail = None
        with self._lock:
            if now - self._win_t0 < self.window_s:
                return
            dur = now - self._win_t0
            if self._cur:
                snap = self._window_snapshot_locked(dur)
                self._windows.append(snap)
                if len(self._windows) > self._ring_cap:
                    del self._windows[:len(self._windows) - self._ring_cap]
                hs = snap.get("host_dispatch_share")
                if hs is not None and hs > self.host_share_alert:
                    self.breaches += 1
                    breach_detail = (
                        f"host dispatch share {hs:.3f} > alert "
                        f"{self.host_share_alert} over {dur:.1f}s window "
                        f"(eps {snap.get('eps', 0):.0f})")
            self._cur = {}
            self._cur_batch_wall_s = 0.0
            self._cur_batch_events = 0
            self._win_t0 = now
            self._win_wall = time.time()
        if breach_detail is not None and self.on_host_share_breach is not None:
            # outside the profiler lock: the callback enqueues a tracing
            # trigger (itself enqueue-only) — no lock-order edge
            try:
                self.on_host_share_breach(breach_detail)
            except Exception:
                pass

    # -- merge ---------------------------------------------------------------

    def _acc_locked(self, plan: str) -> tuple:
        tot = self._totals.get(plan)
        if tot is None:
            tot = self._totals[plan] = _Acc()
        cur = self._cur.get(plan)
        if cur is None:
            cur = self._cur[plan] = _Acc()
        return tot, cur

    def _merge_round(self, plan, wall, sampled, has_kernel, phases,
                     events, bytes_h2d, bytes_d2h) -> None:
        with self._lock:
            tot, cur = self._acc_locked(plan)
            tot.merge_round(wall, sampled, has_kernel, phases, events,
                            bytes_h2d, bytes_d2h)
            cur.merge_round(wall, sampled, has_kernel, phases, events,
                            bytes_h2d, bytes_d2h)

    # -- views ---------------------------------------------------------------

    @staticmethod
    def _view(acc: _Acc) -> dict:
        """Extrapolate sampled kernel/h2d to the full round population,
        correct the raw materialize/residual walls, and normalize.

        Raw `d2h_materialize` absorbs the device wait on *unsampled*
        rounds (async dispatch: the blocking pull pays for the kernel);
        raw `python_dispatch` absorbs their upload.  The extrapolation
        deltas move that time where it belongs, clamped at zero, and
        shares are normalized over the corrected total so they sum to
        exactly 1.0."""
        ph = acc.phases
        kern = ph.get("kernel_compute", 0.0)
        h2d = ph.get("h2d_upload", 0.0)
        f = 1.0
        # extrapolate over KERNEL-carrying rounds only: collect polls /
        # pump rounds never dispatch, so scaling by total round wall
        # would inflate the estimate by their (kernel-less) time
        if acc.sampled_rounds and acc.sampled_rounds < acc.kernel_rounds:
            f = (acc.kernel_wall_s / acc.sampled_wall_s
                 if acc.sampled_wall_s > 0.0
                 else acc.kernel_rounds / acc.sampled_rounds)
        kern_est = kern * f
        h2d_est = h2d * f
        d2h = max(0.0, ph.get("d2h_materialize", 0.0) - (kern_est - kern))
        py = max(0.0, ph.get("python_dispatch", 0.0) - (h2d_est - h2d))
        est = {"h2d_upload": h2d_est,
               "kernel_compute": kern_est,
               "d2h_materialize": d2h,
               "host_pack_unpack": ph.get("host_pack_unpack", 0.0),
               "python_dispatch": py,
               "sink_egress": ph.get("sink_egress", 0.0)}
        tot = sum(est.values())
        shares = {k: (v / tot if tot > 0.0 else 0.0)
                  for k, v in est.items()}
        host = sum(shares[k] for k in HOST_PHASES)
        v = {"rounds": acc.rounds,
             "kernel_rounds": acc.kernel_rounds,
             "sampled_rounds": acc.sampled_rounds,
             "events": acc.events,
             "wall_s": round(acc.wall_s, 6),
             "phases_s": {k: round(s, 6) for k, s in est.items()},
             "shares": {k: round(s, 4) for k, s in shares.items()},
             "host_dispatch_share": round(host, 4),
             "device_share": round(1.0 - host, 4)}
        if acc.bytes_h2d or acc.bytes_d2h:
            v["bytes"] = {"h2d": acc.bytes_h2d, "d2h": acc.bytes_d2h}
        if acc.hist.count:
            p99 = acc.hist.percentile(99)
            if p99 is not None:
                v["round_p99_ms"] = round(p99 * 1e3, 4)
        if acc.events and kern_est > 0.0:
            v["kernel_eps"] = round(acc.events / kern_est, 1)
        if acc.events and acc.wall_s > 0.0:
            v["end_to_end_eps"] = round(acc.events / acc.wall_s, 1)
        return v

    def _aggregate_locked(self, accs: dict, batch_wall: float,
                          batch_events: int) -> dict:
        agg = _Acc()
        covered = 0.0
        for name, a in accs.items():
            agg.rounds += a.rounds
            agg.kernel_rounds += a.kernel_rounds
            agg.sampled_rounds += a.sampled_rounds
            agg.wall_s += a.wall_s
            agg.kernel_wall_s += a.kernel_wall_s
            agg.sampled_wall_s += a.sampled_wall_s
            for k, s in a.phases.items():
                agg.phases[k] = agg.phases.get(k, 0.0) + s
            agg.bytes_h2d += a.bytes_h2d
            agg.bytes_d2h += a.bytes_d2h
            if name != "_sink":     # sink egress runs outside batch wall
                covered += a.wall_s
        agg.events = batch_events
        out = self._view(agg)
        if batch_wall > 0.0:
            out["coverage"] = round(min(1.0, covered / batch_wall), 4)
            out["batch_wall_s"] = round(batch_wall, 6)
            out["eps"] = round(batch_events / batch_wall, 1)
        return out

    def _window_snapshot_locked(self, dur_s: float) -> dict:
        plans = {n: self._view(a) for n, a in self._cur.items()}
        agg = self._aggregate_locked(self._cur, self._cur_batch_wall_s,
                                     self._cur_batch_events)
        snap = {"t_unix": round(self._win_wall, 3),
                "dur_s": round(dur_s, 3),
                "plans": plans,
                "host_dispatch_share": agg.get("host_dispatch_share"),
                "shares": agg.get("shares"),
                "coverage": agg.get("coverage")}
        if dur_s > 0.0:
            snap["eps"] = round(self._cur_batch_events / dur_s, 1)
            # share of the window the dispatch loop was busy at all
            snap["occupancy"] = round(
                min(1.0, self._cur_batch_wall_s / dur_s), 4)
        return snap

    def metrics(self) -> dict:
        """Compact summary for statistics()/Prometheus: cumulative
        totals per plan, no ring."""
        with self._lock:
            out = {"mode": self.mode,
                   "sample_every": self.sample_every,
                   "window_s": self.window_s,
                   "host_share_alert": self.host_share_alert,
                   "plans": {n: self._view(a)
                             for n, a in self._totals.items()},
                   "windows_rolled": len(self._windows),
                   "breaches": self.breaches}
            agg = self._aggregate_locked(self._totals, self._batch_wall_s,
                                         self._batch_events)
            out["aggregate"] = agg
            if self.probe_failures:
                out["probe_failures"] = self.probe_failures
            return out

    def profile(self, window: Optional[int] = None) -> dict:
        """The full surface behind rt.profile() and the HTTP endpoint:
        metrics() plus the last `window` ring snapshots (all retained
        windows when None)."""
        rep = self.metrics()
        with self._lock:
            wins = list(self._windows)
        if window is not None and window >= 0:
            wins = wins[-window:] if window else []
        rep["windows"] = wins
        return rep

    def reset(self) -> None:
        """Drop all accumulated attribution (bench A/B reuse)."""
        with self._lock:
            self._totals = {}
            self._cur = {}
            self._windows = []
            self._batch_wall_s = 0.0
            self._batch_events = 0
            self._cur_batch_wall_s = 0.0
            self._cur_batch_events = 0
            self._win_t0 = time.monotonic()
            self._win_wall = time.time()


def _device_put_leaves(x):
    """jax.device_put every numpy leaf of a (shallow pytree) kernel
    argument — dict envs, tuples/lists, bare arrays.  jax arrays and
    scalars pass through untouched; the resulting leaves have identical
    avals so the jit call neither recompiles nor re-uploads."""
    import numpy as np
    import jax
    if isinstance(x, np.ndarray):
        return jax.device_put(x)
    if isinstance(x, dict):
        return {k: _device_put_leaves(v) for k, v in x.items()}
    if isinstance(x, tuple):
        return tuple(_device_put_leaves(v) for v in x)
    if isinstance(x, list):
        return [_device_put_leaves(v) for v in x]
    return x


# ---------------------------------------------------------------------------
# annotation parsing
# ---------------------------------------------------------------------------

def profiler_from_annotations(app) -> Optional[PhaseProfiler]:
    """Build the runtime's profiler from `@app:profile(...)`:

        @app:profile('off')            -- rt.profiler is None (zero cost)
        @app:profile('all')            -- every round blocked + timed
        (default / 'sampled')          -- 1 in 32 rounds sampled
        @app:profile('sample=8')       -- 1 in 8 (positional form)
        @app:profile(sample='8')       -- 1 in 8 (keyed form)
        @app:profile(window='2')       -- ring window seconds
        @app:profile(ring='600')       -- retained window count

    `@app:hostShareAlert('0.7')` sets the windowed host-dispatch-share
    threshold above which the profiler fires a `host_share_breach`
    tracing trigger (flight-recorder dump).  $SIDDHI_PROFILE supplies
    the mode for apps without the annotation."""
    from ..query import ast as qast
    ann = qast.find_annotation(app.annotations, "app:profile")
    mode = None
    sample = None
    window_s = 5.0
    ring = 120
    if ann is not None:
        el = (ann.element() or "").lower() or None
        if el is not None:
            if el.startswith("sample=") or el.startswith("sample:"):
                mode = "sample"
                sample = int(el.split("=" if "=" in el else ":", 1)[1])
            else:
                mode = el
        for k, v in ann.elements:
            if k is None:
                continue
            kl = k.lower()
            if kl == "sample":
                mode = mode or "sample"
                sample = int(v)
            elif kl == "window":
                window_s = float(str(v).split()[0])
            elif kl == "ring":
                ring = int(v)
    if mode is None:
        env = (os.environ.get("SIDDHI_PROFILE") or "").lower() or None
        if env is not None:
            if env.startswith("sample="):
                mode, sample = "sample", int(env.split("=", 1)[1])
            else:
                mode = env
    if mode == "off":
        return None
    if mode in (None, "sampled", "sample", "on"):
        mode = "sample"
    elif mode != "all":
        from .planner import PlanError
        raise PlanError(
            f"@app:profile({mode!r}): unknown mode "
            f"(have: off | sample=N | all)")
    alert = 0.7
    aa = qast.find_annotation(app.annotations, "app:hostShareAlert")
    if aa is not None:
        el = aa.element() or next(
            (v for k, v in aa.elements if k and k.lower() == "share"), None)
        if el is not None:
            alert = float(el)
    return PhaseProfiler(app.name, mode=mode,
                         sample_every=sample if sample else 32,
                         window_s=window_s, host_share_alert=alert,
                         ring=ring)
