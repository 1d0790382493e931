"""What every driver shares: the clocks of a run, the compile counter, host
spans on the profiler's clock, the device trace, and the result line."""
import contextlib
import glob
import json
import os
import shutil
import sys
import tempfile
import time

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SPAN_PREFIX = "bench:"


class CompileLog:
    """Backend compilations seen by the public jax.monitoring listener (a
    persistent-cache hit still fires the event: it counts compile REQUESTS,
    which is what a steady window must not have).  As chip_smoke.py's."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1
            self.secs += duration


class Spans:
    """Host spans from the benchmark's own driver, around its calls into
    the engine.  Off (`on=False`) a span costs one `if`; on, it is kept in
    memory and written into the profiler's trace as a TraceAnnotation named
    `bench:<name>`, so that idle gaps can be laid against it."""

    def __init__(self, on: bool):
        self.on = on
        self.seconds = {}
        self.count = {}

    @contextlib.contextmanager
    def _span(self, name):
        import jax.profiler
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) \
            + time.perf_counter() - t0
        self.count[name] = self.count.get(name, 0) + 1

    def span(self, name: str):
        return self._span(name) if self.on else contextlib.nullcontext()

    def wrap(self, name: str, fn):
        """`fn` with a span around each call (identity when off)."""
        if not self.on:
            return fn

        def wrapped(*a, **kw):
            with self._span(name):
                return fn(*a, **kw)
        return wrapped

    def reset(self):
        self.seconds, self.count = {}, {}


class DeviceTrace:
    """jax.profiler around a few seconds of a traced run's window.  The
    driver calls `tick(elapsed)` between its calls into the engine."""

    def __init__(self, on: bool, start_after_s: float, length_s: float,
                 keep: str = ""):
        self.on, self.keep = on, keep
        self.start_after_s, self.length_s = start_after_s, length_s
        self.dir = None
        self.state = "idle" if on else "done"
        self.t_start = self.t_stop = None

    def tick(self, elapsed: float) -> None:
        if self.state == "idle" and elapsed >= self.start_after_s:
            import jax.profiler
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans are our own
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_start = time.perf_counter()
            self.state = "tracing"
        elif self.state == "tracing" and \
                time.perf_counter() - self.t_start >= self.length_s:
            self.stop()

    def stop(self) -> None:
        if self.state == "tracing":
            import jax.profiler
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            self.state = "done"

    def summary(self):
        """The reduced trace (benchmark/xplane.py), or None when no trace
        was taken.  The trace directory is removed."""
        if self.dir is None:
            return None
        from benchmark import xplane
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                return None
            if self.keep:       # --keep-trace: the raw file, for a look
                os.makedirs(self.keep, exist_ok=True)
                shutil.copy(files[0], self.keep)
            return xplane.summarize(xplane.load(files[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def device_block(devices) -> dict:
    """The result line's `device`: as JAX reports it, with the peak memory
    of the fullest chip.  Read before the engine is shut down."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def emit(result: dict, checks: list) -> None:
    """The numbers compared, each beside its limit: last on standard error,
    and last in the result line; the result line last on standard output."""
    compared = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                for c in checks}
    sys.stdout.flush()
    print("compared (value <= limit): " + ", ".join(
        f"{k} {v['value']} <= {v['limit']}" for k, v in compared.items()),
        file=sys.stderr, flush=True)
    result["compared"] = compared
    print(json.dumps(result), flush=True)


class Run:
    """One run's settings and instruments, handed to the driver."""

    def __init__(self, cell, seed, seconds, trace_on, devices,
                 keep_trace=""):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace_on, self.devices = trace_on, devices
        self.compiles = CompileLog()
        self.spans = Spans(trace_on)
        t = cell["traffic"].get("trace", {})
        self.trace = DeviceTrace(trace_on, t.get("start_after_s", 3.0),
                                 t.get("length_s", 4.0), keep_trace)

    def close(self, device: dict, window: dict, counted, compiles_in_window,
              samples: dict):
        """Once the window has closed: reduce the trace, add the busy
        seconds (mean over the chips used) and the traced window to
        `device`, and gather what the per-layer readers read.  Returns the
        observations, None for an untraced run."""
        summary = self.trace.summary()
        if summary is not None and summary["devices"]:
            busy = [d["busy_s"] for d in summary["devices"].values()]
            device["busy_s"] = sum(busy) / len(busy)
            device["window_s"] = summary["window_s"]
        if not self.trace_on:
            return None
        return {**window, "stages": counted["stages"],
                "spans": dict(self.spans.seconds),
                "counters": {"h2d_bytes": counted["h2d_bytes"],
                             "d2h_bytes": counted["d2h_bytes"],
                             "lanes": counted["lanes"],
                             "compiles_in_window": compiles_in_window},
                "samples": samples, "trace": summary, "cell": self.cell,
                "device_kind": self.devices[0].device_kind}
