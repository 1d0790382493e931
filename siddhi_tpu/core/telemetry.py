"""Deep telemetry: pipeline tracing, latency histograms, device metrics,
the statistics/reporter SPI, and Prometheus text exposition.

Folds the former `stats.py` trackers into one observability layer
(reference surface: core:util/statistics/metrics/SiddhiStatisticsManager.java:35-85
— Codahale registry with throughput/latency/memory trackers — plus
core:debugger/SiddhiDebugger.java:36-139).  What the reference cannot see
— and this engine must — are the device-economics quantities that govern
throughput on TPU (SURVEY §3.3; Simultaneous Finite Automata,
arxiv 1405.0562): jit compile count/wall-time, kernel-cache hit rates,
host->device transfer bytes, NFA lane occupancy and state-frontier
width, and window/join carry-buffer fill.

Layout:

  * `Histogram` — HDR-style fixed log-bucket latency histogram (pure
    python, no deps): 16 sub-buckets per octave over 1 µs..~4000 s, so
    p50/p95/p99 carry <= ~4.5 % relative quantile error at O(1)/record.
  * `Tracker` — per-(stream|query|span) counter + histogram.
  * `StatisticsManager.span` — THE span primitive: one `perf_counter`
    pair per span feeds every sink that is on (the `stages` trackers, a
    `jax.profiler.TraceAnnotation` on the profiler's clock, the frame's
    causal `TraceHandle` tree, the phase profiler).  `SPANS` is the
    taxonomy (docs/OBSERVABILITY.md prints it).
  * `StatisticsManager` — hangs off the runtime's batch dispatch loop;
    enabled statistics cost two clock reads, a locked tracker update and
    a TraceAnnotation per span.
  * reporter SPI (`register_stats_reporter`) with console / log /
    prometheus reporters; `render_prometheus` emits the text exposition
    served by `service.py`'s `GET /metrics`.
  * `SiddhiDebugger` — micro-batch-boundary breakpoints (unchanged).

`kernel` is the jitted dispatch call (async: it returns once the device
has the work); `transfer` is the blocking pull of its result.  While a
sink is on, the pattern plans' and the filter's pulls split it into two
children (`device_wait`): `transfer.wait`, the device time still owed
when the host arrives (`block_until_ready`), and `transfer.copy`, what
is left of the D2H copy and the host-side assembly.  With every sink off
the pull makes no call it did not make before the split.  The result
path's spans (`FAULT_SPANS`) also count the process's page faults.
"""
from __future__ import annotations

import gc
import json
import math
import mmap
import os
import threading
import time
from collections import defaultdict
from functools import partial
from typing import Callable, Optional

try:
    import resource
except ImportError:         # no getrusage on this platform: no fault counts
    resource = None

import jax.monitoring
import jax.profiler

from ..utils.locks import new_lock

# every span the engine records, by layer (docs/OBSERVABILITY.md has the
# table: thread, parent, which sinks record it when)
SPANS = (
    "parse", "plan", "compile",                         # build
    "net.wait", "net.decode", "admit", "queue_wait",    # wire
    "ingest", "frame", "freeze", "wal.append",          # ingest
    "dispatch", "host_build", "lane_cut", "lane_tail",  # dispatch
    "kernel",
    "transfer", "transfer.wait", "transfer.copy",
    "unpack", "scatter", "route", "emit",
    "sink.publish", "sink.encode", "sink.send",         # egress
    "gc",                                               # process
)
SPAN_PREFIX = "siddhi:"     # TraceAnnotation names in a jax.profiler trace


def _page_faults() -> tuple:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_minflt, ru.ru_majflt


def _kernel_counts_faults() -> bool:
    """Does a first touch of fresh pages move `ru_minflt` here?  A
    sandboxed kernel (gVisor, `runsc`: the machines the benchmark's chips
    hang on) answers `getrusage` with 0 faults whatever the process
    touches, at 6-14 us a call: there a count of 0 would read as "no
    fresh pages", so no span counts at all."""
    before = _page_faults()[0]
    with mmap.mmap(-1, 16 * mmap.PAGESIZE) as fresh:
        for i in range(16):
            fresh[i * mmap.PAGESIZE] = 1
    return _page_faults()[0] > before


# the result path: where a pulled result lands and is copied again.  With
# statistics on, a PLAN's span of these names reads the PROCESS's page-
# fault counts at open and close (`stages[name]["minor_faults"]`,
# `["major_faults"]`).  Process-wide on purpose: a D2H landing buffer is
# touched by the runtime's transfer thread, not by the python thread that
# waits for it, so RUSAGE_THREAD would miss the copy; faults other
# threads take meanwhile fall in.  A plan's spans only: the runtime's own
# `scatter` (one a delivered batch, 500 a flush of rules1k) lands no
# result, and getrusage walks every thread of the process.  Empty where
# there is no `resource` module or the kernel does not count.
FAULT_SPANS = frozenset(
    ("transfer.copy", "unpack", "scatter", "route")
    if resource is not None and _kernel_counts_faults() else ())


# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------

class Histogram:
    """HDR-style fixed log-bucket histogram over seconds.

    Bucket i covers [MIN * 2^(i/SUB), MIN * 2^((i+1)/SUB)): geometric
    buckets, SUB per octave — the classic HdrHistogram trade of bounded
    relative error for O(1) record and a few hundred ints of memory.
    Values clamp at both ends (1 µs .. ~4000 s)."""

    SUB = 16                       # sub-buckets per octave
    MIN = 1e-6                     # 1 µs resolution floor
    OCTAVES = 32                   # ~4300 s ceiling
    NBUCKETS = SUB * OCTAVES

    __slots__ = ("counts", "count", "sum", "min", "max")

    def __init__(self):
        self.counts = [0] * self.NBUCKETS
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = 0.0

    def record(self, seconds: float) -> None:
        if seconds < 0.0:
            seconds = 0.0
        self.count += 1
        self.sum += seconds
        if seconds < self.min:
            self.min = seconds
        if seconds > self.max:
            self.max = seconds
        if seconds <= self.MIN:
            i = 0
        else:
            i = int(math.log2(seconds / self.MIN) * self.SUB)
            if i >= self.NBUCKETS:
                i = self.NBUCKETS - 1
        self.counts[i] += 1

    @classmethod
    def bucket_hi(cls, i: int) -> float:
        """Upper bound (seconds) of bucket i."""
        return cls.MIN * 2.0 ** ((i + 1) / cls.SUB)

    def percentile(self, p: float) -> Optional[float]:
        """p in [0, 100] -> seconds (bucket upper bound, clamped to the
        observed max so a lone sample reports itself exactly)."""
        if not self.count:
            return None
        target = max(1, math.ceil(self.count * p / 100.0))
        acc = 0
        for i, c in enumerate(self.counts):
            if not c:
                continue
            acc += c
            if acc >= target:
                return min(self.bucket_hi(i), self.max)
        return self.max

    def quantiles(self, ps=(50, 95, 99)) -> dict:
        return {p: self.percentile(p) for p in ps}

    def reset(self) -> None:
        self.counts = [0] * self.NBUCKETS
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = 0.0


# ---------------------------------------------------------------------------
# trackers
# ---------------------------------------------------------------------------

# coarse upper bounds (seconds) for the Prometheus histogram render +
# its trace-id exemplars; the +Inf bucket is implicit
EXEMPLAR_LE = (0.001, 0.005, 0.025, 0.1, 0.5, 2.5)


def _le_label(seconds: float) -> str:
    for le in EXEMPLAR_LE:
        if seconds <= le:
            return str(le)
    return "+Inf"


class Tracker:
    __slots__ = ("events", "batches", "seconds", "hist", "exemplars",
                 "faults", "_lock")

    def __init__(self):
        # spans close on serve threads and the scheduler pump as well
        # as the dispatch thread: observe() is locked
        self._lock = new_lock("Tracker._lock")
        self.events = 0
        self.batches = 0
        self.seconds = 0.0
        self.hist = Histogram()
        # le-label -> (trace_id, observed_seconds, unix_ts): the last
        # TRACED sample per coarse bucket — OpenMetrics exemplars on
        # the /metrics histogram render (docs/OBSERVABILITY.md)
        self.exemplars: Optional[dict] = None
        # [minor, major] page faults over this tracker's spans: a span
        # of FAULT_SPANS only, else None
        self.faults: Optional[list] = None

    def observe(self, seconds: float, events: int = 0,
                trace_id: Optional[str] = None,
                faults: Optional[tuple] = None) -> None:
        """One timed batch; a traced frame's id becomes the bucket
        exemplar linking the latency histogram back to its span tree;
        `faults` is the (minor, major) page faults the span saw."""
        with self._lock:
            self._observe_locked(seconds, events, trace_id)
            if faults is not None:
                if self.faults is None:
                    self.faults = [0, 0]
                self.faults[0] += faults[0]
                self.faults[1] += faults[1]

    def _observe_locked(self, seconds: float, events: int, trace_id) -> None:
        # (the collector hook calls this bare: it is the only writer of
        # its tracker and may hold no lock, see `_on_gc`)
        self.events += events
        self.batches += 1
        self.seconds += seconds
        self.hist.record(seconds)
        if trace_id is not None:
            if self.exemplars is None:
                self.exemplars = {}
            self.exemplars[_le_label(seconds)] = (
                trace_id, seconds, time.time())

    def bucket_counts(self) -> dict:
        """Cumulative sample counts per EXEMPLAR_LE bound (+Inf last),
        aggregated from the fine log buckets — the Prometheus
        histogram render; computed at scrape time, never on the hot
        path."""
        edges = EXEMPLAR_LE
        totals = [0] * (len(edges) + 1)
        for i, c in enumerate(self.hist.counts):
            if not c:
                continue
            hi = self.hist.bucket_hi(i)
            for j, le in enumerate(edges):
                if hi <= le * (1.0 + 1e-9):
                    totals[j] += c
                    break
            else:
                totals[-1] += c
        out = {}
        acc = 0
        for j, le in enumerate(edges):
            acc += totals[j]
            out[str(le)] = acc
        out["+Inf"] = self.hist.count
        return out

    def as_dict(self, buckets: bool = False) -> dict:
        with self._lock:    # a scrape races observe() on other threads
            return self._as_dict_locked(buckets)

    def _as_dict_locked(self, buckets: bool) -> dict:
        d = {"events": self.events, "batches": self.batches}
        if self.faults is not None:
            d["minor_faults"], d["major_faults"] = self.faults
        if self.seconds:
            d["seconds"] = self.seconds
            if self.events:
                d["latency_us_per_event"] = 1e6 * self.seconds / self.events
            # key OMITTED (not None) when seconds is falsy: a consumer
            # summing/dividing report values must not meet nulls
            d["throughput_eps"] = self.events / self.seconds
        if self.hist.count:
            for p in (50, 95, 99):
                v = self.hist.percentile(p)
                if v is not None:
                    d[f"p{p}_ms"] = round(v * 1e3, 4)
            if buckets:
                d["buckets"] = self.bucket_counts()
                if self.exemplars:
                    d["exemplars"] = {k: list(v)
                                      for k, v in self.exemplars.items()}
        return d


# ---------------------------------------------------------------------------
# the span primitive
# ---------------------------------------------------------------------------

class Span:
    """What `StatisticsManager.span` hands back, whichever sinks are on:
    a context manager with `seconds` and `t_end` (perf_counter at close;
    0.0 / None unless the span was timed for statistics or a trace), a
    settable `events` and `note(**args)`.  This base is the no-op."""

    __slots__ = ()
    seconds = 0.0
    t_end = None
    events = property(lambda self: 0, lambda self, n: None)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args) -> None:
        pass


NOOP_SPAN = Span()      # the shared off path: no clock read, no state


class _Span(Span):
    """One open span: ONE `perf_counter` pair feeds every sink that is
    on — the `stages` tracker and a `jax.profiler.TraceAnnotation`
    (statistics enabled), the frame's `TraceHandle` tree (the frame is
    traced), and the piggy-backed profiler phase/round.  Built by
    `StatisticsManager.span` only."""

    __slots__ = ("mgr", "name", "plan", "events", "handle", "args", "t0",
                 "t_end", "seconds", "_pspan", "_ann", "_sid", "_parent",
                 "_faults")

    def __init__(self, mgr, name, plan, events, handle, pspan, t0, args):
        self.mgr = mgr
        self.name = name
        self.plan = plan
        self.events = events
        self.handle = handle
        self.args = args
        self.t0 = t0            # not None: the interval started earlier
        self.t_end = None
        self.seconds = 0.0
        self._pspan = pspan
        self._ann = None
        self._faults = None

    def note(self, **args) -> None:
        """Arguments known only inside the span (a WAL seq, an admission
        verdict): recorded on the frame's tree."""
        self.args.update(args)

    def __enter__(self):
        if self._pspan is not None:
            self._pspan.__enter__()
        h = self.handle
        if h is not None:
            self._sid, self._parent = h.open()
        if self.mgr.enabled:
            kw = dict(self.args)
            if self.plan is not None:
                kw["plan"] = self.plan
            if h is not None:
                kw["trace"] = h.trace_id
            self._ann = jax.profiler.TraceAnnotation(
                SPAN_PREFIX + self.name, **kw)
            self._ann.__enter__()
            if self.plan is not None and self.name in FAULT_SPANS:
                self._faults = _page_faults()
        now = time.perf_counter()
        if self.t0 is None:
            self.t0 = now
        return self

    def __exit__(self, *exc):
        self.t_end = now = time.perf_counter()
        self.seconds = dt = now - self.t0
        if self._ann is not None:
            f0 = self._faults
            if f0 is not None:
                f1 = _page_faults()
                f0 = (f1[0] - f0[0], f1[1] - f0[1])
            self._ann.__exit__(*exc)
            self.mgr.stage_tracker(self.name).observe(dt, self.events,
                                                      faults=f0)
        h = self.handle
        if h is not None:
            args = self.args
            if self.plan is not None:
                args["plan"] = self.plan
            if self.events:
                args["events"] = self.events
            h.close(self._sid, self._parent, self.name, self.t0, dt,
                    args or None)
        if self._pspan is not None:
            self._pspan.__exit__(*exc)
        return False


class _StreamTimer:
    __slots__ = ("mgr", "sid", "n", "start", "trace_id")

    def __init__(self, mgr, sid, n, trace_id=None):
        self.mgr = mgr
        self.sid = sid
        self.n = n
        self.trace_id = trace_id

    def __enter__(self):
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.start
        self.mgr.stream_in[self.sid].observe(dt, self.n,
                                             trace_id=self.trace_id)
        return False


# ---------------------------------------------------------------------------
# the `gc` span: ONE hook per process
# ---------------------------------------------------------------------------

# A collection starts inside whatever allocation tripped it, under any
# lock that thread holds (a Tracker's, while `as_dict` builds its dict).
# So the hook takes no lock: it writes each watching manager's `_gc`
# tracker through `_observe` (collections are serialised by the
# interpreter: one writer) and marks the thread's open frame tree, which
# is a deque append.
_gc_lock = new_lock("telemetry._gc_lock")   # the watcher list; never the hook
_gc_watchers: tuple = ()    # managers with statistics on (replaced whole)
_gc_open = None             # (t0, annotation) of the collection under way


def _watch_gc(mgr, on: bool) -> None:
    global _gc_watchers
    with _gc_lock:
        rest = tuple(m for m in _gc_watchers if m is not mgr)
        _gc_watchers = rest + (mgr,) if on else rest
        hooked = _on_gc in gc.callbacks
        if _gc_watchers and not hooked:
            gc.callbacks.append(_on_gc)
        elif hooked and not _gc_watchers:
            gc.callbacks.remove(_on_gc)


def _on_gc(phase: str, info: dict) -> None:
    # start and stop run back to back on the thread that tripped the
    # collector: one slot is enough
    global _gc_open
    if phase == "start":
        ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + "gc",
                                           generation=info["generation"])
        ann.__enter__()
        _gc_open = (time.perf_counter(), ann)
    elif _gc_open is not None:
        now = time.perf_counter()
        (t0, ann), _gc_open = _gc_open, None
        ann.__exit__(None, None, None)
        for mgr in _gc_watchers:    # each app reports the process's pauses
            mgr._gc._observe_locked(now - t0, 0, None)
            h = mgr.rt._trace_tls.handle
            if h is not None:
                h.mark("gc", t0, now - t0, generation=info["generation"])


# ---------------------------------------------------------------------------
# XLA persistent-cache observation (process-global)
# ---------------------------------------------------------------------------

XLA_CACHE = {"hits": 0, "misses": 0}


def _on_jax_event(event: str, **_kw) -> None:
    """Count the persistent compilation cache's hit/miss events (the
    disk cache placed by `siddhi_tpu._enable_kernel_cache`): JAX records
    `/jax/compilation_cache/cache_hits` and `.../cache_misses`."""
    if event.endswith("/cache_hits"):
        XLA_CACHE["hits"] += 1
    elif event.endswith("/cache_misses"):
        XLA_CACHE["misses"] += 1


jax.monitoring.register_event_listener(_on_jax_event)


# ---------------------------------------------------------------------------
# kernel-call instrumentation helper (shared by the device modules)
# ---------------------------------------------------------------------------

def env_nbytes(env) -> int:
    """Host->device payload size of one kernel argument dict."""
    try:
        return sum(int(getattr(v, "nbytes", 0)) for v in env.values())
    except Exception:
        return 0


def call_kernel(stats, plan: str, fn, args: tuple, *, cache_hit: bool,
                nbytes: int = 0, prof=None):
    """Invoke a jitted kernel `fn(*args)` recording: per-plan fn-cache
    hit/miss, H2D bytes, and a `compile` (fn-cache miss — the call that
    pays trace + XLA compilation) or `kernel` (steady-state dispatch)
    stage span.  Classification rides the caller's cache probe so a
    block compiled while stats were off is never misreported as a
    compile after `enable_stats(True)`.

    `prof` (core/profiler.py PhaseProfiler, or None) routes a warm
    call through the sampled h2d/kernel probe and records H2D bytes into
    the phase plane.  The `kernel` span is the upload and the async
    dispatch on every round; on a *sampled* round the probe's
    block_until_ready (the full device wait) is a `transfer` span of its
    own."""
    if prof is not None and nbytes:
        prof.note_bytes(plan, "h2d", nbytes)
    if stats is None or not (stats.enabled or stats.traced()):
        if prof is not None:
            return prof.run_kernel(fn, args, cache_hit=cache_hit)
        return fn(*args)
    stats.on_kernel_cache(plan, cache_hit)      # (counted only when enabled)
    if nbytes:
        stats.add_transfer_bytes(plan, nbytes)
    if not cache_hit:                           # a compile is never probed
        with stats.span("compile", plan=plan) as sp:
            out = fn(*args)
        stats.on_compile(plan, sp.seconds)
        return out
    if prof is None:
        with stats.span("kernel", plan=plan):
            return fn(*args)
    return prof.run_kernel(fn, args, span=partial(stats.span, plan=plan))


def device_wait(span, plan: str, arrays) -> None:
    """`transfer.wait`, the first child of a pull's `transfer` span: the
    device time still owed when the host arrives, apart from the copy
    that follows (`transfer.copy`).  It is a call the pull does not
    otherwise make, so it exists only while a sink is on: with
    statistics off and no traced frame the span is the no-op and nothing
    blocks here."""
    sp = span("transfer.wait", plan=plan)
    if sp is not NOOP_SPAN:
        with sp:
            jax.block_until_ready(arrays)


# ---------------------------------------------------------------------------
# reporter SPI
# ---------------------------------------------------------------------------

REPORTERS: dict = {}

# latest Prometheus exposition per app, refreshed by the `prometheus`
# reporter (scrape-side consumers can also hit service.py's GET /metrics,
# which renders live instead)
PROM_LATEST: dict = {}

# latest raw report per app — the $SIDDHI_PROM_FILE writer renders ALL
# apps from here so concurrent reporters don't clobber each other's series
_PROM_REPORTS: dict = {}


def register_stats_reporter(name: str, fn, meta=None) -> None:
    """fn(app_name, report_dict) — the reporter SPI (reference:
    SiddhiStatisticsManager.java:35-85 console/JMX reporters).
    Re-registering a name overrides it."""
    from ..extension import register_meta
    register_meta("stats-reporter", meta)
    REPORTERS[name.lower()] = fn


def _console_reporter(app: str, report: dict) -> None:
    import sys
    print(f"[siddhi-stats] {app}: {json.dumps(report, default=str)}",
          file=sys.stderr)


def _log_reporter(app: str, report: dict) -> None:
    import logging
    logging.getLogger("siddhi_tpu.stats").info("%s: %s", app, report)


def _prometheus_reporter(app: str, report: dict) -> None:
    """Render the report as Prometheus text exposition; kept in
    PROM_LATEST[app] and (optionally) written atomically to
    $SIDDHI_PROM_FILE for file-based scrape setups (node_exporter
    textfile collector).  The file always carries EVERY reporting app
    (rendered from the latest report of each), so two runtimes sharing
    one process don't alternate-clobber each other's series."""
    PROM_LATEST[app] = render_prometheus({app: report})
    _PROM_REPORTS[app] = report
    path = os.environ.get("SIDDHI_PROM_FILE")
    if path:
        try:
            text = render_prometheus(dict(sorted(_PROM_REPORTS.items())))
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                f.write(text)
            os.replace(tmp, path)
        except OSError:
            pass


REPORTERS["console"] = _console_reporter
REPORTERS["log"] = _log_reporter
REPORTERS["prometheus"] = _prometheus_reporter


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

_DEV_COUNTERS = {
    "compiles": ("siddhi_tpu_jit_compiles_total",
                 "jit kernel compilations per plan"),
    "compile_seconds": ("siddhi_tpu_jit_compile_seconds_total",
                        "wall time spent in jit compilation per plan"),
    "cache_hits": ("siddhi_tpu_kernel_cache_hits_total",
                   "per-plan jitted-block cache hits"),
    "cache_misses": ("siddhi_tpu_kernel_cache_misses_total",
                     "per-plan jitted-block cache misses"),
    "h2d_bytes": ("siddhi_tpu_h2d_transfer_bytes_total",
                  "host->device payload bytes shipped per plan"),
}


def _esc(v) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n",
                                                                    "\\n")


def _fmt(v) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    f = float(v)
    if f != f or f in (math.inf, -math.inf):
        return "NaN" if f != f else ("+Inf" if f > 0 else "-Inf")
    return repr(f)


class _Prom:
    """Accumulates samples grouped per metric so # HELP / # TYPE render
    exactly once per metric name (the exposition-format requirement).
    `openmetrics=True` attaches exemplars and the `# EOF` terminator —
    exemplar syntax is ONLY legal under the OpenMetrics content type; a
    classic text-format (0.0.4) scrape must never meet one, or a real
    Prometheus parser rejects the whole exposition."""

    def __init__(self, openmetrics: bool = False):
        self.openmetrics = openmetrics
        self.metrics: dict = {}          # name -> (type, help, [samples])

    def add(self, name, mtype, help_, labels: dict, value,
            suffix: str = "", exemplar=None) -> None:
        if value is None:
            return
        ent = self.metrics.setdefault(name, (mtype, help_, []))
        lab = ",".join(f'{k}="{_esc(v)}"' for k, v in labels.items())
        line = (f"{name}{suffix}{{{lab}}} {_fmt(value)}"
                if lab else f"{name}{suffix} {_fmt(value)}")
        if exemplar is not None and self.openmetrics:
            # OpenMetrics exemplar syntax: `# {labels} value timestamp`
            # — the trace id links this bucket back to its span tree
            tid, ev, ets = exemplar
            line += (f' # {{trace_id="{_esc(tid)}"}} '
                     f'{_fmt(float(ev))} {_fmt(float(ets))}')
        ent[2].append(line)

    def render(self) -> str:
        out = []
        for name, (mtype, help_, samples) in self.metrics.items():
            out.append(f"# HELP {name} {help_}")
            out.append(f"# TYPE {name} {mtype}")
            out.extend(samples)
        if self.openmetrics:
            out.append("# EOF")
        return "\n".join(out) + "\n"


def _summary(doc: _Prom, name: str, help_: str, labels: dict, td: dict):
    """One tracker dict -> a Prometheus summary (quantiles + _sum/_count)."""
    for q, key in ((0.5, "p50_ms"), (0.95, "p95_ms"), (0.99, "p99_ms")):
        if key in td:
            doc.add(name, "summary", help_,
                    {**labels, "quantile": str(q)}, td[key] / 1e3)
    doc.add(name, "summary", help_, labels, td.get("seconds", 0.0),
            suffix="_sum")
    doc.add(name, "summary", help_, labels, td.get("batches", 0),
            suffix="_count")


def render_prometheus(reports: dict, openmetrics: bool = False) -> str:
    """reports: {app_name: StatisticsManager.report() dict} ->
    text exposition.  Default: classic Prometheus format 0.0.4 (no
    exemplars).  `openmetrics=True` — served when the scraper's Accept
    header negotiates `application/openmetrics-text` — attaches
    trace-id exemplars to histogram buckets and terminates with
    `# EOF`."""
    doc = _Prom(openmetrics=openmetrics)
    for app, rep in reports.items():
        al = {"app": app}
        doc.add("siddhi_tpu_uptime_seconds", "gauge",
                "runtime uptime", al, rep.get("uptime_s"))
        for sid, td in rep.get("streams", {}).items():
            sl = {**al, "stream": sid}
            doc.add("siddhi_tpu_events_total", "counter",
                    "events ingested per stream", sl, td.get("events", 0))
            doc.add("siddhi_tpu_batches_total", "counter",
                    "micro-batches dispatched per stream", sl,
                    td.get("batches", 0))
            if "p50_ms" in td:
                _summary(doc, "siddhi_tpu_stream_latency_seconds",
                         "per-batch dispatch latency per stream", sl, td)
            bk = td.get("buckets")
            if bk:
                # real histogram render of the same latency data: the
                # bucket lines carry trace-id exemplars for frames the
                # tracing plane sampled (docs/OBSERVABILITY.md)
                hn = "siddhi_tpu_stream_dispatch_latency_seconds"
                hh = ("per-batch dispatch latency histogram per stream; "
                      "buckets carry trace-id exemplars")
                ex = td.get("exemplars") or {}
                for le, c in bk.items():
                    doc.add(hn, "histogram", hh, {**sl, "le": le}, c,
                            suffix="_bucket",
                            exemplar=tuple(ex[le]) if le in ex else None)
                doc.add(hn, "histogram", hh, sl, td.get("seconds", 0.0),
                        suffix="_sum")
                doc.add(hn, "histogram", hh, sl, td.get("batches", 0),
                        suffix="_count")
        for qn, td in rep.get("queries", {}).items():
            ql = {**al, "query": qn}
            doc.add("siddhi_tpu_query_events_total", "counter",
                    "events processed per query", ql, td.get("events", 0))
            _summary(doc, "siddhi_tpu_query_latency_seconds",
                     "per-batch processing latency per query", ql, td)
        for st, td in rep.get("stages", {}).items():
            _summary(doc, "siddhi_tpu_stage_latency_seconds",
                     "per-span latency per pipeline stage",
                     {**al, "stage": st}, td)
            for kind in ("minor", "major"):     # the result path's spans
                doc.add(f"siddhi_tpu_stage_{kind}_faults_total", "counter",
                        f"{kind} page faults of the process while a "
                        "result-path span was open", {**al, "stage": st},
                        td.get(f"{kind}_faults"))
        for plan, m in rep.get("device", {}).items():
            pl = {**al, "plan": plan}
            for key, v in m.items():
                if key in _DEV_COUNTERS:
                    name, help_ = _DEV_COUNTERS[key]
                    doc.add(name, "counter", help_, pl, v)
                elif isinstance(v, (int, float)):
                    doc.add("siddhi_tpu_device", "gauge",
                            "device-side gauges (lane occupancy, frontier "
                            "width, buffer fill, drops)",
                            {**pl, "metric": key}, v)
        # fault-tolerance series (core/faults.py)
        for scope, fd in rep.get("faults", {}).items():
            for action, n in fd.items():
                doc.add("siddhi_tpu_faults_total", "counter",
                        "fault dispositions per stream and action",
                        {**al, "stream": scope, "action": action}, n)
        if "degraded_plans" in rep:
            doc.add("siddhi_tpu_degraded_plans", "gauge",
                    "device plans quarantined onto the interpreter path",
                    al, len(rep["degraded_plans"]))
        # placement plane (core/placement.py): the no-silent-demotions
        # series — every interpreter fallback carries a recorded reason,
        # and this gauge is how a future silent demotion shows up on a
        # dashboard before anyone reads explain()
        pl = rep.get("placement")
        if pl:
            doc.add("siddhi_tpu_interp_demotions", "gauge",
                    "queries demoted off the device path with a recorded "
                    "Demotion reason (rt.explain() has the chain)",
                    al, pl.get("interp_demotions", 0))
            doc.add("siddhi_tpu_placement_queries", "gauge",
                    "query count per chosen execution path",
                    {**al, "path": "device"}, pl.get("device", 0))
            doc.add("siddhi_tpu_placement_queries", "gauge",
                    "query count per chosen execution path",
                    {**al, "path": "interpreter"}, pl.get("interpreter", 0))
            for qn, qd in pl.get("queries", {}).items():
                ql = {**al, "query": qn, "path": qd.get("path", "")}
                if qd.get("family"):
                    ql["family"] = qd["family"]
                doc.add("siddhi_tpu_query_placement", "gauge",
                        "chosen execution path per query (1 = placed)",
                        ql, 1)
        es = rep.get("error_store")
        if es:
            doc.add("siddhi_tpu_error_store_entries", "gauge",
                    "replayable entries captured in the ErrorStore", al,
                    es.get("entries", 0))
            doc.add("siddhi_tpu_error_store_evicted_total", "counter",
                    "ErrorStore entries evicted by the capacity bound", al,
                    es.get("evicted", 0))
        for sid, sd in rep.get("sources", {}).items():
            sl = {**al, "stream": sid}
            doc.add("siddhi_tpu_source_dropped_events_total", "counter",
                    "malformed source messages logged and dropped", sl,
                    sd.get("dropped_events", 0))
            doc.add("siddhi_tpu_source_stored_events_total", "counter",
                    "malformed source messages captured in the ErrorStore",
                    sl, sd.get("stored_events", 0))
        _SINK_COUNTERS = (("published", "siddhi_tpu_sink_published_total",
                           "payloads delivered per sink"),
                          ("retries", "siddhi_tpu_sink_retries_total",
                           "publish retries per sink"),
                          ("failures", "siddhi_tpu_sink_failures_total",
                           "publish attempt failures per sink"),
                          ("stored", "siddhi_tpu_sink_stored_total",
                           "payloads captured in the ErrorStore per sink"),
                          # net egress (siddhi_tpu/net sink.py): batched
                          # columnar frames shipped over the wire
                          ("frames_out", "siddhi_tpu_sink_frames_out_total",
                           "columnar frames shipped by a net sink"),
                          ("bytes_out", "siddhi_tpu_sink_bytes_out_total",
                           "wire bytes shipped by a net sink"))
        for label, m in rep.get("sinks", {}).items():
            kl = {**al, "sink": label}
            for key, name, help_ in _SINK_COUNTERS:
                if m.get(key):
                    doc.add(name, "counter", help_, kl, m[key])
            if "circuit_state" in m:
                doc.add("siddhi_tpu_sink_circuit_state", "gauge",
                        "per-sink circuit breaker state "
                        "(0=closed 1=half-open 2=open)", kl,
                        m["circuit_state"])
                doc.add("siddhi_tpu_sink_circuit_opens_total", "counter",
                        "times the per-sink circuit breaker opened", kl,
                        m.get("circuit_opens", 0))
        # serving-plane series (siddhi_tpu/net): wire ingest + admission
        _NET_COUNTERS = (
            ("frames_in", "siddhi_tpu_net_frames_total",
             "wire frames received per stream"),
            ("events_in", "siddhi_tpu_net_events_total",
             "events received over the serving plane per stream"),
            ("bytes_in", "siddhi_tpu_net_bytes_total",
             "payload bytes received per stream"),
            ("admitted_events", "siddhi_tpu_net_admitted_events_total",
             "events admitted by the rate controller per stream"),
            ("shed_events", "siddhi_tpu_net_shed_events_total",
             "events shed into the ErrorStore per stream"),
            ("shed_frames", "siddhi_tpu_net_shed_frames_total",
             "frames shed into the ErrorStore per stream"),
            ("credit_granted", "siddhi_tpu_net_credit_granted_total",
             "credit frames granted to producers per stream"),
            ("protocol_errors", "siddhi_tpu_net_protocol_errors_total",
             "malformed/checksum-failed frames per stream"))
        _NET_GAUGES = (
            ("pending_frames", "siddhi_tpu_net_pending_frames",
             "frames parked by the 'oldest' admission queue"),
            ("pending_bytes", "siddhi_tpu_net_pending_bytes",
             "bytes parked by the 'oldest' admission queue"),
            ("rate_factor", "siddhi_tpu_net_admission_factor",
             "SLO-driven admission throttle (1.0 = full rate)"),
            ("open_connections", "siddhi_tpu_net_open_connections",
             "live ingest connections per stream"),
            ("ring_occupancy", "siddhi_tpu_net_ring_occupancy",
             "shm-ring frames awaiting the consumer"),
            ("blocked_seconds", "siddhi_tpu_net_blocked_seconds",
             "cumulative block-policy backpressure wait"))
        for sid, m in rep.get("net", {}).items():
            nl = {**al, "stream": sid}
            for key, name, help_ in _NET_COUNTERS:
                if key in m:
                    doc.add(name, "counter", help_, nl, m[key])
            for key, name, help_ in _NET_GAUGES:
                if key in m:
                    doc.add(name, "gauge", help_, nl, m[key])
        # queryable-state series (core/aggregation.py): per-duration
        # bucket/eviction gauges, group cardinality, and the store-query
        # latency histogram (exemplar-carrying, like the stream
        # dispatch histogram above)
        ag = rep.get("aggregation")
        if ag:
            for an, m in (ag.get("aggregations") or {}).items():
                gl = {**al, "aggregation": an}
                doc.add("siddhi_tpu_agg_groups", "gauge",
                        "live group keys per aggregation", gl,
                        m.get("groups"))
                doc.add("siddhi_tpu_agg_device", "gauge",
                        "aggregation lowered to the device plan "
                        "(1 device, 0 host; rt.explain() has the D-AGG "
                        "chain)", gl, 1 if m.get("device") else 0)
                for dn, dd in (m.get("durations") or {}).items():
                    dl = {**gl, "duration": dn}
                    doc.add("siddhi_tpu_agg_buckets", "gauge",
                            "live rollup buckets per aggregation "
                            "duration", dl, dd.get("buckets"))
                    doc.add("siddhi_tpu_agg_evicted_total", "counter",
                            "rollup buckets evicted by @purge retention "
                            "per aggregation duration", dl,
                            dd.get("evicted", 0))
            sq = ag.get("store_query")
            if sq:
                doc.add("siddhi_tpu_agg_store_queries_total", "counter",
                        "on-demand store queries executed (REST + wire "
                        "QUERY frames)", al, sq.get("batches", 0))
                doc.add("siddhi_tpu_agg_store_query_rows_total", "counter",
                        "rows returned by on-demand store queries", al,
                        sq.get("events", 0))
                bk = sq.get("buckets")
                if bk:
                    hn = "siddhi_tpu_agg_store_query_latency_seconds"
                    hh = ("store-query execution latency histogram; "
                          "buckets carry trace-id exemplars")
                    ex = sq.get("exemplars") or {}
                    for le, c in bk.items():
                        doc.add(hn, "histogram", hh, {**al, "le": le}, c,
                                suffix="_bucket",
                                exemplar=tuple(ex[le]) if le in ex
                                else None)
                    doc.add(hn, "histogram", hh, al,
                            sq.get("seconds", 0.0), suffix="_sum")
                    doc.add(hn, "histogram", hh, al,
                            sq.get("batches", 0), suffix="_count")
        # durability series (core/wal.py): WAL volume, fsync latency,
        # segment churn, and the crash-recovery gauges
        dur = rep.get("durability")
        if dur:
            doc.add("siddhi_tpu_wal_enabled", "gauge",
                    "write-ahead log live (0 with @app:durability "
                    "declared means durability silently lost — alert)",
                    al, 1 if dur.get("enabled") else 0)
            _WAL_COUNTERS = (
                ("appended_frames", "siddhi_tpu_wal_appends_total",
                 "admitted frames appended to the WAL"),
                ("appended_events", "siddhi_tpu_wal_events_total",
                 "events covered by WAL records"),
                ("appended_bytes", "siddhi_tpu_wal_bytes_total",
                 "bytes appended to the WAL"),
                ("fsyncs", "siddhi_tpu_wal_fsyncs_total",
                 "WAL fsync calls (per-append under 'fsync', "
                 "barrier-only under 'batch')"),
                ("corrupt_skipped", "siddhi_tpu_wal_corrupt_skipped_total",
                 "torn/corrupt WAL records or segments dropped by "
                 "recovery scans"),
                ("truncated_segments",
                 "siddhi_tpu_wal_truncated_segments_total",
                 "sealed segments deleted behind snapshot barriers"))
            for key, name, help_ in _WAL_COUNTERS:
                if key in dur:
                    doc.add(name, "counter", help_, al, dur[key])
            doc.add("siddhi_tpu_wal_segments", "gauge",
                    "live WAL segments (sealed + open)", al,
                    dur.get("segments"))
            for sid, s in (dur.get("last_seq") or {}).items():
                doc.add("siddhi_tpu_wal_last_seq", "gauge",
                        "last durable frame seq per stream",
                        {**al, "stream": sid}, s)
            fs = dur.get("fsync")
            if fs:
                _summary(doc, "siddhi_tpu_wal_fsync_latency_seconds",
                         "WAL fsync latency", al, fs)
            rec = dur.get("recovery")
            if rec:
                doc.add("siddhi_tpu_wal_recovery_seconds", "gauge",
                        "wall time of the last crash recovery "
                        "(restore + WAL replay)", al, rec.get("recovery_s"))
                doc.add("siddhi_tpu_wal_replayed_frames", "gauge",
                        "frames replayed by the last recovery", al,
                        rec.get("replayed_frames"))
                doc.add("siddhi_tpu_wal_replayed_events", "gauge",
                        "events replayed by the last recovery", al,
                        rec.get("replayed_events"))
        # replication series (core/replication.py): role, lag, volume,
        # fencing rejections — the HA dashboard (docs/OBSERVABILITY.md)
        repl = rep.get("replication")
        if repl:
            doc.add("siddhi_tpu_repl_role", "gauge",
                    "replication role (1 primary, 0 standby)",
                    {**al, "role": str(repl.get("role"))},
                    1 if repl.get("role") == "primary" else 0)
            doc.add("siddhi_tpu_repl_standbys", "gauge",
                    "standby replicas attached to this primary", al,
                    repl.get("standbys", 0))
            doc.add("siddhi_tpu_repl_lag_records", "gauge",
                    "WAL records appended locally but not yet "
                    "acknowledged by a standby", al,
                    repl.get("lag_records", 0))
            doc.add("siddhi_tpu_repl_lag_seconds", "gauge",
                    "seconds since the last standby ack/heartbeat "
                    "(primary) or applied record (standby)", al,
                    repl.get("lag_seconds", 0.0))
            _REPL_COUNTERS = (
                ("shipped_records", "siddhi_tpu_repl_shipped_records_total",
                 "WAL records shipped to standbys"),
                ("shipped_bytes", "siddhi_tpu_repl_shipped_bytes_total",
                 "WAL bytes shipped to standbys"),
                ("shipped_snapshots",
                 "siddhi_tpu_repl_shipped_snapshots_total",
                 "snapshot revisions shipped for catch-up"),
                ("applied_records", "siddhi_tpu_repl_applied_records_total",
                 "replicated WAL records appended to the local log"),
                ("applied_snapshots",
                 "siddhi_tpu_repl_applied_snapshots_total",
                 "shipped snapshot revisions saved locally"),
                ("acks", "siddhi_tpu_repl_acks_total",
                 "standby append-acks received"),
                ("rejected_generation",
                 "siddhi_tpu_repl_rejected_generation_total",
                 "frames/links rejected by the fencing token "
                 "(deposed-primary writes)"),
                ("barrier_timeouts",
                 "siddhi_tpu_repl_barrier_timeouts_total",
                 "semi-sync durable-ACK barriers failed waiting for a "
                 "standby"))
            for key, name, help_ in _REPL_COUNTERS:
                if key in repl:
                    doc.add(name, "counter", help_, al, repl[key])
        # frame-tracing series (core/tracing.py)
        trc = rep.get("tracing")
        if trc:
            doc.add("siddhi_tpu_trace_traces_total", "counter",
                    "frame traces started (sampled + producer-stamped)",
                    al, trc.get("traces_started"))
            doc.add("siddhi_tpu_trace_ring_spans", "gauge",
                    "spans currently retained in the flight ring", al,
                    trc.get("ring_spans"))
            doc.add("siddhi_tpu_trace_dumps", "gauge",
                    "retained trigger-promoted trace dumps", al,
                    trc.get("dumps"))
            for kind, n in (trc.get("triggers") or {}).items():
                doc.add("siddhi_tpu_trace_triggers_total", "counter",
                        "trace-dump triggers by kind",
                        {**al, "kind": kind}, n)
        # device-time attribution series (core/profiler.py)
        prof = rep.get("profile")
        if prof:
            for plan, pd in (prof.get("plans") or {}).items():
                pl2 = {**al, "plan": plan}
                for phase, secs in (pd.get("phases_s") or {}).items():
                    doc.add("siddhi_tpu_phase_seconds_total", "counter",
                            "attributed wall seconds per plan and "
                            "dispatch phase (sampled kernel/h2d "
                            "extrapolated; docs/OBSERVABILITY.md)",
                            {**pl2, "phase": phase}, secs)
                if "host_dispatch_share" in pd:
                    doc.add("siddhi_tpu_host_dispatch_share", "gauge",
                            "share of a plan's dispatch wall spent "
                            "host-side (pack/unpack + python + sink)",
                            pl2, pd["host_dispatch_share"])
            agg = prof.get("aggregate")
            if agg and "host_dispatch_share" in agg:
                doc.add("siddhi_tpu_host_dispatch_share", "gauge",
                        "share of a plan's dispatch wall spent "
                        "host-side (pack/unpack + python + sink)",
                        {**al, "plan": "_aggregate"},
                        agg["host_dispatch_share"])
        slo = rep.get("slo")
        if slo:
            doc.add("siddhi_tpu_slo_target_seconds", "gauge",
                    "@app:latencySLO p99 target", al,
                    (slo["target_ms"] / 1e3) if "target_ms" in slo
                    else None)
            doc.add("siddhi_tpu_slo_window_p99_seconds", "gauge",
                    "SLO controller's last decision-window p99", al,
                    (slo["window_p99_ms"] / 1e3)
                    if "window_p99_ms" in slo else None)
            doc.add("siddhi_tpu_slo_batch_target", "gauge",
                    "SLO controller's current micro-batch target", al,
                    slo.get("batch_target"))
            for action, n in slo.get("decisions", {}).items():
                doc.add("siddhi_tpu_slo_decisions_total", "counter",
                        "AIMD controller decisions by action",
                        {**al, "action": action}, n)
    # process-wide (not per-app): emitted ONCE, unlabeled — an app label
    # would duplicate the same counter N times across a multi-app scrape
    # and N-fold overcount any PromQL sum()
    xc = next((r["xla_cache"] for r in reports.values()
               if r.get("xla_cache")), None)
    if xc:
        doc.add("siddhi_tpu_xla_cache_hits_total", "counter",
                "persistent XLA compilation cache hits (process-wide)",
                {}, xc.get("hits", 0))
        doc.add("siddhi_tpu_xla_cache_misses_total", "counter",
                "persistent XLA compilation cache misses (process-wide)",
                {}, xc.get("misses", 0))
    return doc.render()


# ---------------------------------------------------------------------------
# the statistics manager
# ---------------------------------------------------------------------------

class StatisticsManager:
    """Per-stream throughput + per-query and per-stage latency histograms
    (+ device metrics + flight recorder).
    `@app:statistics(reporter='console', interval='5 sec')` starts a
    periodic reporter thread (reference: @app:statistics reporter/interval,
    SiddhiAppParser.java:108-144)."""

    def __init__(self, rt):
        self.rt = rt
        self.enabled = False
        self.stream_in: dict = defaultdict(Tracker)
        self.query: dict = defaultdict(Tracker)
        self.stages: dict = {}       # span name -> Tracker (stage_tracker)
        self.device: dict = defaultdict(lambda: defaultdict(float))
        # fault dispositions per stream/scope (ALWAYS counted — faults
        # are rare and must be visible even with statistics off)
        self.faults: dict = defaultdict(lambda: defaultdict(int))
        # on-demand (store) query latency — ALWAYS observed (not gated
        # on `enabled`): the queryable-state plane is its own surface
        # (REST + wire QUERY frames) and its p99 is an SLO input
        self.store_query = Tracker()
        self._gc = Tracker()         # the `gc` span (written by `_on_gc`)
        self._t0 = time.perf_counter()
        self.reporter = None
        self.interval_s: float = 5.0
        self._rep_thread = None
        self._rep_stop = None

    # -- reporters -----------------------------------------------------------

    def configure(self, reporter: str, interval_s: float) -> None:
        fn = REPORTERS.get((reporter or "console").lower())
        if fn is None:
            raise ValueError(f"unknown statistics reporter {reporter!r}; "
                             f"have {sorted(REPORTERS)}")
        self.reporter = fn
        self.interval_s = interval_s

    def start_reporting(self) -> None:
        if self.reporter is None or self._rep_thread is not None:
            return
        self._rep_stop = threading.Event()

        def pump():
            while not self._rep_stop.wait(self.interval_s):
                try:
                    self.reporter(self.rt.app.name, self.report())
                except Exception:
                    pass
        self._rep_thread = threading.Thread(
            target=pump, name="siddhi-stats-report", daemon=True)
        self._rep_thread.start()

    def stop_reporting(self) -> None:
        """Runtime shutdown: stop the reporter pump and unhook the
        process-wide gc callback (start() re-arms it via enable())."""
        _watch_gc(self, False)
        if self._rep_stop is not None:
            self._rep_stop.set()
            self._rep_thread.join(timeout=2)
            self._rep_thread = None
            self._rep_stop = None
        # drop this app's cached prometheus series: a shut-down app must
        # not keep exporting frozen metrics through $SIDDHI_PROM_FILE /
        # PROM_LATEST renders triggered by other apps' reporter ticks
        app = getattr(getattr(self.rt, "app", None), "name", None)
        if app is not None:
            _PROM_REPORTS.pop(app, None)
            PROM_LATEST.pop(app, None)

    # -- recording hooks -----------------------------------------------------

    def enable(self, on: bool = True) -> None:
        """The statistics toggle.  On also puts this manager among the
        watchers of the process's one `gc.callbacks` hook (`gc` span: a
        collector pause is a stall no other span can name); off takes it
        out, and the last one out removes the hook."""
        self.enabled = bool(on)
        _watch_gc(self, self.enabled)

    def time_stream(self, sid: str, n: int, trace_id=None):
        """Times one micro-batch's full pass through the dispatch loop
        (callbacks + every subscribed plan); a traced frame's id rides
        into the latency histogram as the bucket exemplar."""
        if not self.enabled:
            return NOOP_SPAN
        return _StreamTimer(self, sid, n, trace_id)

    # spans that map onto the device-time profiler (core/profiler.py):
    # one timer records both planes.  (phase, pseudo-plan that takes the
    # attribution outside any dispatch round)
    _SPAN_PHASE = {"host_build": ("host_pack_unpack", "_runtime"),
                   "unpack": ("host_pack_unpack", "_runtime"),
                   "scatter": ("host_pack_unpack", "_runtime"),
                   "route": ("host_pack_unpack", "_runtime"),
                   "transfer": ("d2h_materialize", "_runtime"),
                   "sink.publish": ("sink_egress", "_sink")}

    def span(self, name: str, *, plan: Optional[str] = None, events: int = 0,
             handle=None, t0: Optional[float] = None, **args):
        """Context manager round one span of `SPANS` — the ONE way the
        engine records time.  Sinks, each fed from the same clock pair:
        statistics enabled -> `report()["stages"][name]` and a
        `siddhi:<name>` TraceAnnotation in whatever jax.profiler trace
        is running (this thread, the device's clock); a traced frame
        (`handle`, else the thread's active trace) -> the frame's causal
        tree; a profiler phase (`_SPAN_PHASE`; `dispatch` opens the
        plan's round) whenever the profiler exists.  With none of them
        on this returns the shared `NOOP_SPAN`: no object, no clock read.
        `t0` backdates the interval (a wait that began on another
        thread); `args` annotate the tree's span."""
        rt = self.rt
        prof = rt.profiler
        pspan = None
        if prof is not None:
            if name == "dispatch":
                pspan = prof.round(plan, events)
            else:
                ph = self._SPAN_PHASE.get(name)
                if ph is not None:
                    pspan = prof.phase(ph[0], ph[1], events)
        if handle is None:
            handle = rt._trace_tls.handle   # (a class default when unset)
        if handle is None and not self.enabled:
            return NOOP_SPAN if pspan is None else pspan
        return _Span(self, name, plan, events, handle, pspan, t0, args)

    def traced(self) -> bool:
        """Is a frame's trace active on this thread?"""
        return self.rt._trace_tls.handle is not None

    def stage_tracker(self, name: str) -> Tracker:
        # setdefault is atomic: two threads closing the first span of a
        # name never end up with a tracker each
        t = self.stages.get(name)
        return t if t is not None else self.stages.setdefault(name, Tracker())

    def note_stage(self, name: str, seconds: float, events: int = 0) -> None:
        """Record an already-measured span (parse time measured before
        the runtime — and its stats manager — existed)."""
        if not self.enabled:
            return
        self.stage_tracker(name).observe(seconds, events)

    def observe_store_query(self, seconds: float, rows: int,
                            trace=None) -> None:
        """One executed store query (runtime.query_with_schema) — rows
        count as the tracker's `events`; a traced caller (the net QUERY
        path under a TRACE-stamped connection) lands a histogram
        exemplar linking the latency bucket to its span tree."""
        tid = getattr(trace, "trace_id", None) if trace is not None else None
        self.store_query.observe(seconds, rows, trace_id=tid)

    def on_fault(self, scope: str, action: str) -> None:
        """One fault disposition (scope = stream or sink label, action =
        the @OnError / on.error disposition taken).  Not gated on
        `enabled`: a dropped batch must never be invisible."""
        self.faults[scope][action] += 1

    def on_kernel_cache(self, plan: str, hit: bool) -> None:
        if self.enabled:
            self.device[plan]["cache_hits" if hit else "cache_misses"] += 1

    def on_compile(self, plan: str, seconds: float) -> None:
        if self.enabled:
            d = self.device[plan]
            d["compiles"] += 1
            d["compile_seconds"] += seconds

    def add_transfer_bytes(self, plan: str, nbytes: int) -> None:
        if self.enabled:
            self.device[plan]["h2d_bytes"] += nbytes

    # -- reporting -----------------------------------------------------------

    def memory_bytes(self) -> int:
        """Approximate retained state size (reference:
        ObjectSizeCalculator.java:66 — we pickle-size the snapshot)."""
        import pickle
        try:
            return len(pickle.dumps(self.rt._snapshot_locked()))
        except Exception:
            return -1

    def device_report(self) -> dict:
        """Per-plan device metrics: the accumulated counters merged with
        each plan's sampled gauges (lane occupancy, frontier width,
        buffer fill) — sampled on demand, one D2H pull per stateful
        plan, so scrapes pay the cost, not the hot path."""
        # snapshot before iterating: the dispatch thread inserts new
        # tracker keys concurrently (first compile of a new shape, a
        # freshly added plan) and a live dict comprehension would raise
        # "dictionary changed size during iteration" on a /metrics scrape
        out = {name: {k: (int(v) if float(v).is_integer() else v)
                      for k, v in list(ctr.items())}
               for name, ctr in list(self.device.items())}
        for p in getattr(self.rt, "_plans", ()):
            dm = getattr(p, "device_metrics", None)
            if dm is not None:
                try:
                    m = dm()
                except Exception:
                    m = None
                if m:
                    out.setdefault(p.name, {}).update(m)
            # dispatch-pipeline gauges (pipeline.py): in-flight queue
            # depth, dispatch count, and the overlap_ratio behind the
            # async host/device decoupling story
            pipe = getattr(p, "_pipe", None)
            if pipe is not None:
                try:
                    out.setdefault(p.name, {}).update(pipe.metrics())
                except Exception:
                    pass
        # degradation-ladder gauges (consecutive dispatch failures,
        # halvings, quarantine flag) — keyed by the original plan name,
        # which survives the interpreter swap
        for name, lad in list(getattr(self.rt, "_ladders", {}).items()):
            out.setdefault(name, {}).update(lad.metrics())
        return out

    def report(self) -> dict:
        up = time.perf_counter() - self._t0
        rep = {
            "uptime_s": up,
            # list() snapshots: scrapes race the dispatch thread's inserts
            # (streams carry histogram buckets + trace-id exemplars for
            # the /metrics histogram render)
            "streams": {k: v.as_dict(buckets=True)
                        for k, v in list(self.stream_in.items())},
            "queries": {k: v.as_dict() for k, v in list(self.query.items())},
            "stages": {k: v.as_dict() for k, v in list(self.stages.items())},
        }
        if self._gc.batches:
            rep["stages"]["gc"] = self._gc.as_dict()
        dev = self.device_report()
        if dev:
            rep["device"] = dev
        if XLA_CACHE["hits"] or XLA_CACHE["misses"]:
            rep["xla_cache"] = dict(XLA_CACHE)
        # fault-tolerance surface (core/faults.py): dispositions taken,
        # quarantined plans, source drop counters, sink retry/breaker
        # gauges, ErrorStore fill — all additive keys, present only when
        # non-empty so fault-free reports keep their shape
        faults = {k: dict(v) for k, v in list(self.faults.items())}
        if faults:
            rep["faults"] = faults
        degraded = list(getattr(self.rt, "_degraded", ()))
        if degraded:
            rep["degraded_plans"] = [d["plan"] for d in degraded]
            rep["degraded_detail"] = degraded
        # placement accounting (core/placement.py): device vs interpreter
        # query counts + the Demotion tally.  ALWAYS present (not gated
        # on `enabled`): a silent demotion must never be invisible —
        # the bench summary and the siddhi_tpu_interp_demotions series
        # both read this block
        if getattr(self.rt, "placement", None) is not None:
            from .placement import summary as _placement_summary
            rep["placement"] = _placement_summary(self.rt)
        es = getattr(self.rt, "error_store", None)
        if es is not None and (len(es) or es.evicted):
            rep["error_store"] = {"entries": len(es), "evicted": es.evicted}
        sources: dict = {}
        for s in getattr(self.rt, "sources", ()):
            if s.dropped_events or s.stored_events:
                d = sources.setdefault(s.stream_id, {"dropped_events": 0,
                                                     "stored_events": 0})
                d["dropped_events"] += s.dropped_events
                d["stored_events"] += s.stored_events
        if sources:
            rep["sources"] = sources
        sinks: dict = {}
        for i, s in enumerate(getattr(self.rt, "sinks", ())):
            try:
                m = s.metrics()
            except Exception:
                continue
            if any(m.values()):
                sinks[f"{s.stream_id}[{i}]"] = m
        if sinks:
            rep["sinks"] = sinks
        # serving plane (siddhi_tpu/net): per-stream admission gauges
        # (frames/events/bytes in, sheds, pending, rate factor) merged
        # with transport-level counters from net sources (connections,
        # credit granted, ring occupancy)
        net: dict = {}
        for sid, ctrl in list(getattr(self.rt, "admission", {}).items()):
            try:
                net[sid] = ctrl.metrics()
            except Exception:
                continue
        for s in getattr(self.rt, "sources", ()):
            nm = getattr(s, "net_metrics", None)
            if nm is None:
                continue
            try:
                m = nm()
            except Exception:
                m = None
            if m:
                net.setdefault(s.stream_id, {}).update(m)
        if net:
            rep["net"] = net
        # queryable-state plane (core/aggregation.py): per-aggregation
        # bucket/group/eviction gauges + the store-query latency
        # histogram.  ALWAYS present when an aggregation exists or a
        # store query ran (not gated on `enabled`) — the agg series on
        # /metrics and the bench matrix both read this block
        agg: dict = {}
        for name, a in list(getattr(self.rt, "aggregations", {}).items()):
            try:
                m = a.metrics()
            except Exception:
                continue
            if m:
                agg[name] = m
        if agg or self.store_query.batches:
            ab: dict = {}
            if agg:
                ab["aggregations"] = agg
            if self.store_query.batches:
                ab["store_query"] = self.store_query.as_dict(buckets=True)
            rep["aggregation"] = ab
        # the SLO controller's state and decision log (core/slo.py)
        slo = getattr(self.rt, "slo", None)
        if slo is not None:
            rep["slo"] = slo.metrics()
        # durability (core/wal.py): the runtime's shared report block —
        # ALWAYS present when @app:durability is declared (not gated on
        # `enabled`): a silently-disabled log must be as loud as a
        # silent demotion would be
        if getattr(self.rt, "durability", "off") != "off":
            rep["durability"] = self.rt.durability_report()
        # replication (core/replication.py): role, peer, lag, shipped/
        # applied volume, fencing rejections — present once the app has
        # a coordinator (annotated, or a standby subscribed)
        coord = getattr(self.rt, "replication", None)
        if coord is not None:
            rep["replication"] = coord.metrics()
        # frame tracing (core/tracing.py): sampling/ring/trigger gauges.
        # ALWAYS present when the tracer exists (not gated on `enabled`)
        # — a triggered dump must be discoverable from any scrape
        tr = getattr(self.rt, "tracing", None)
        if tr is not None:
            rep["tracing"] = tr.metrics()
        # device-time attribution (core/profiler.py): per-plan phase
        # shares + host-dispatch share.  ALWAYS present when the
        # profiler exists (not gated on `enabled`) — the phase plane is
        # its own knob (@app:profile) and feeds its own /metrics series
        prof = getattr(self.rt, "profiler", None)
        if prof is not None:
            rep["profile"] = prof.metrics()
        return rep

    def prometheus(self, openmetrics: bool = False) -> str:
        return render_prometheus({self.rt.app.name: self.report()},
                                 openmetrics=openmetrics)

    def reset(self) -> None:
        self.stream_in.clear()
        self.query.clear()
        self.stages.clear()
        self._gc = Tracker()
        self.device.clear()
        self._t0 = time.perf_counter()


# ---------------------------------------------------------------------------
# debugger (unchanged surface)
# ---------------------------------------------------------------------------

class SiddhiDebugger:
    """Micro-batch-boundary breakpoints (reference: SiddhiDebugger.java:36:
    acquireBreakPoint(query, IN|OUT) + SiddhiDebuggerCallback.debugEvent).

    The callback runs synchronously inside the dispatch loop; inspect live
    state via runtime.snapshot() / runtime.tables etc. from within it."""

    IN = "in"
    OUT = "out"

    def __init__(self, rt):
        self.rt = rt
        self._breakpoints: set = set()       # (query_name, point)
        self._callback: Optional[Callable] = None

    def acquire_breakpoint(self, query_name: str, point: str = IN) -> None:
        if query_name not in self.rt._known_query_names:
            raise KeyError(f"unknown query {query_name!r}")
        self._breakpoints.add((query_name, point))

    def release_breakpoint(self, query_name: str, point: str = IN) -> None:
        self._breakpoints.discard((query_name, point))

    def release_all(self) -> None:
        self._breakpoints.clear()

    def set_callback(self, fn: Callable) -> None:
        """fn(query_name, point, events) — events are decoded host Events."""
        self._callback = fn

    # -- engine hooks --------------------------------------------------------

    def check_in(self, plan, batch) -> None:
        name = getattr(plan, "callback_name", plan.name)
        if self._callback and (name, self.IN) in self._breakpoints:
            self._callback(name, self.IN, self.rt._decode(batch))

    def check_out(self, plan, out_batches: list) -> None:
        name = getattr(plan, "callback_name", plan.name)
        if self._callback and (name, self.OUT) in self._breakpoints:
            for ob in out_batches:
                if ob.batch.n:
                    self._callback(name, self.OUT, self.rt._decode(ob.batch))
