"""The engine's one span source (`telemetry.StatisticsManager.span`, docs/
OBSERVABILITY.md "Span taxonomy"): one clock pair feeds the stage trackers,
a `siddhi:<name>` TraceAnnotation on the profiler's clock, the frame's
causal tree and the phase profiler; off, it is the shared no-op."""
import glob
import os
import re
import sys
import threading
import time

import jax
import jax.profiler
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import telemetry
from siddhi_tpu.core.telemetry import NOOP_SPAN, SPAN_PREFIX, SPANS, Tracker
from siddhi_tpu.net import TcpFrameClient
from siddhi_tpu.net.client import FrameReceiver

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STOCK = "define stream S (sym string, p double, v int);\n"
FILTER = STOCK + ("@info(name='q') from S[p > 100] select sym, p "
                  "insert into Out;\n")
T0_MS = 1_700_000_000_000
PATTERN = ("@app:partitionCapacity(16)\n" + STOCK +
           "partition with (sym of S) begin\n"
           "@info(name='q') from every e1=S[p > 100] -> e2=S[p > e1.p] "
           "within 1 sec select e1.p as a, e2.p as b insert into Out;\n"
           "end;\n")


def _batch(k, n, keys=8):
    """Batch k: keys in turn (every key the same count, so a lane grid
    keeps its shape from batch to batch), seeded prices, 1 ms apart."""
    r = np.random.default_rng(k)
    return ({"sym": np.array([f"K{i % keys}" for i in range(n)]),
             "p": np.round(r.uniform(90, 130, n) * 4) / 4,
             "v": r.integers(1, 100, n).astype(np.int32)},
            T0_MS + np.arange(k * n, (k + 1) * n, dtype=np.int64))


def _host_events(trace_dir):
    """[(name, start_ns, duration_ns, thread line)] of the /host:CPU plane."""
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for pl in jax.profiler.ProfileData.from_file(path).planes:
        if pl.name != "/host:CPU":
            continue
        for ln in pl.lines:
            out += [(e.name, e.start_ns, e.duration_ns, ln.name)
                    for e in ln.events]
    return out


# (a) one clock read, two sinks: the profiler's host plane and the trackers

# the most a runnable thread waits for a core here, with room: six test
# workers and their XLA thread pools share eight cores
QUANTUM_S = 0.025


@pytest.mark.parametrize("app,n,want", [
    (FILTER, 1 << 15,
     ("freeze", "host_build", "kernel", "transfer", "unpack", "scatter")),
    (PATTERN, 1 << 11,
     ("freeze", "host_build", "kernel", "transfer", "scatter")),
], ids=["filter", "partitioned-pattern"])
def test_profiler_trace_holds_the_engine_spans(tmp_path, app, n, want):
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(app)
    rows = [0]
    rt.add_batch_callback("Out", lambda b: rows.__setitem__(0, rows[0] + b.n))
    rt.start()
    h = rt.input_handler("S")
    try:
        for k in range(3):              # every shape compiled beforehand
            h.send_batch(*_batch(k, n))
            rt.flush()
        rt.enable_stats()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("test:window"):
                for k in range(3, 7):
                    h.send_batch(*_batch(k, n))
                    rt.flush()
        finally:
            jax.profiler.stop_trace()
        stages = rt.statistics()["stages"]
    finally:
        mgr.shutdown()
    assert rows[0] > 0
    events = _host_events(str(tmp_path))
    (w0, wd), = [(s, d) for name, s, d, _ln in events
                 if name == "test:window"]
    for name in want:
        mine = [(s, d) for ev, s, d, _ln in events
                if ev == SPAN_PREFIX + name]
        assert mine, (name, sorted({e[0] for e in events}))
        assert all(w0 <= s and s + d <= w0 + wd for s, d in mine), name
        assert len(mine) == stages[name]["batches"], name
        # the annotation is entered just before the first clock read and
        # left just after the second, so it is never the shorter (the two
        # clocks agree to under a microsecond a span), and the longer by a
        # few microseconds a span (some ten where the span reads the page
        # fault counters in between): unless the worker is descheduled or
        # the collector runs between an annotation's edge and its clock
        # read, which under a loaded machine costs a scheduler quantum
        excess = sum(d for _s, d in mine) / 1e9 - stages[name]["seconds"]
        assert -1e-6 * len(mine) <= excess <= QUANTUM_S * len(mine), (
            name, excess, len(mine))
    assert not [e for e in events if e[0] == SPAN_PREFIX + "compile"]


# (b) one producer-stamped frame over loopback TCP: one tree, two threads

def _tcp_out(app, port):
    return app.replace(
        "insert into Out;\n", "insert into Out;\n"
        f"@sink(type='tcp', host='127.0.0.1', port='{port}')\n"
        "define stream Out (sym string, p double);\n")


def _one_tree(spans):
    ids = {s["span"] for s in spans}
    assert [s["name"] for s in spans if s["parent"] == 0] == ["frame"]
    assert not [s for s in spans if s["parent"] and s["parent"] not in ids]
    assert all(s["name"] in SPANS for s in spans)
    return {s["span"]: s for s in spans}


def test_wire_frame_is_one_tree_across_threads():
    """The serve thread decodes, admits and freezes; under @app:async the
    ingest worker dispatches, pulls and publishes: one tree all the same."""
    recv = FrameReceiver()
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:async\n@source(type='tcp', port='0')\n"
        + _tcp_out(FILTER, recv.port))
    rt.enable_stats()
    rt.start()
    try:
        cli = TcpFrameClient("127.0.0.1", rt.sources[0].port, "S",
                             TcpFrameClient.cols_of_schema(rt.schemas["S"]))
        cols, ts = _batch(0, 64)
        cli.send_batch(cols, ts)        # compiles the step
        cli.barrier(timeout=120)
        cli.send_batch(cols, ts + 64, trace_id="one-tree")
        cli.barrier(timeout=120)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not any(
                s["name"] == "sink.publish"
                for s in rt.tracing.traces().get("one-tree", ())):
            time.sleep(0.01)
        spans = rt.tracing.traces()["one-tree"]
        stages = rt.statistics()["stages"]
        cli.close()
    finally:
        rt.shutdown()
        recv.stop()
    by_id = _one_tree(spans)
    order = ["frame", "net.decode", "admit", "queue_wait", "freeze",
             "dispatch", "transfer", "sink.publish"]
    # each stage of the chain is there and starts no earlier than the
    # one before it
    t0 = [min(s["t0_s"] for s in spans if s["name"] == n) for n in order]
    assert t0 == sorted(t0), dict(zip(order, t0))
    # nesting is by parent edge, not by the clock alone
    for child, parent in (("sink.encode", "sink.publish"),
                          ("sink.send", "sink.encode"),
                          ("host_build", "dispatch")):
        sp = next(s for s in spans if s["name"] == child)
        assert by_id[sp["parent"]]["name"] == parent, (child, sp)
    threads = {s["name"]: s["thread"] for s in spans}
    assert threads["net.decode"] != threads["transfer"], threads
    # the serve thread's blocking reads were named too
    assert stages["net.wait"]["batches"] >= 1
    assert stages["queue_wait"]["batches"] >= 2


def test_direct_send_tree_crosses_to_the_ingest_worker():
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime("@app:trace('all')\n@app:async\n" + FILTER)
    rt.add_batch_callback("Out", lambda b: None)
    rt.start()
    try:
        rt.input_handler("S").send_batch(*_batch(0, 64))
        rt.flush()
        (spans,) = rt.tracing.traces().values()
    finally:
        rt.shutdown()
    _one_tree(spans)
    threads = {s["name"]: s["thread"] for s in spans}
    assert threads["freeze"] != threads["dispatch"], threads
    for want in ("freeze", "dispatch", "host_build", "transfer", "unpack",
                 "scatter"):
        assert want in threads, (want, threads)


# (c) the off path

def test_off_path_is_the_noop_singleton(monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("TraceAnnotation built with statistics off")
    monkeypatch.setattr(telemetry.jax.profiler, "TraceAnnotation", boom)
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:trace('off')\n@app:profile('off')\n" + FILTER)
    rt.start()
    try:
        for name in SPANS:
            assert rt.span(name, plan="q", events=3) is NOOP_SPAN, name
        rt.input_handler("S").send_batch(*_batch(0, 256))
        rt.flush()
        assert rt.statistics()["stages"] == {}
    finally:
        mgr.shutdown()


def test_profiler_only_path_builds_no_span_object():
    """The default runtime (profiler on, statistics off, frame unsampled):
    a phase-mapped span is the profiler's own phase object, anything else
    the no-op."""
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime("@app:trace('off')\n" + FILTER)
    try:
        assert rt.span("freeze") is NOOP_SPAN
        assert type(rt.span("transfer")).__name__ == "_PhaseSpan"
        assert type(rt.span("dispatch", plan="q")).__name__ == "_RoundCM"
    finally:
        mgr.shutdown()


@pytest.mark.parametrize("header,stats", [
    ("@app:trace('off')\n@app:profile('off')\n", False),   # the no-op
    ("@app:trace('off')\n", False),         # the profiler's phase / round
    ("@app:trace('all')\n", True)])         # a timed span
def test_every_span_has_one_surface(header, stats):
    """Whatever `span()` hands back, a caller reads `seconds` and
    `t_end`, sets `events` and calls `note()` without asking what it is."""
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(header + FILTER)
    rt.enable_stats(stats)
    try:
        for name in ("ingest", "transfer", "dispatch"):
            with rt.span(name, plan="q") as sp:
                sp.events = 7
                sp.note(action="x")
            assert isinstance(sp, telemetry.Span)
            if stats:
                assert sp.seconds > 0 and sp.t_end is not None
                assert sp.events == 7
            else:
                assert sp.seconds == 0.0 and sp.t_end is None
        assert NOOP_SPAN.events == 0
    finally:
        mgr.shutdown()


# (d) trackers are shared between threads now

def test_tracker_observe_from_four_threads_loses_nothing():
    tr = Tracker()
    n = 20_000

    def work():
        for _ in range(n):
            tr.observe(1e-6, events=2)
    threads = [threading.Thread(target=work, name=f"siddhi-test-{i}")
               for i in range(4)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)     # switch threads mid-observe
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(old)
    assert tr.batches == 4 * n and tr.events == 8 * n
    assert tr.hist.count == 4 * n
    assert tr.seconds == pytest.approx(4 * n * 1e-6)


def test_first_span_of_a_name_from_many_threads_shares_one_tracker():
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(FILTER)
    rt.enable_stats()
    try:
        go = threading.Event()

        def work():
            go.wait()
            for _ in range(200):
                with rt.span("net.decode"):
                    pass
        threads = [threading.Thread(target=work, name=f"siddhi-test-{i}")
                   for i in range(4)]
        for t in threads:
            t.start()
        go.set()
        for t in threads:
            t.join()
        assert rt.statistics()["stages"]["net.decode"]["batches"] == 800
    finally:
        mgr.shutdown()


# (e) the pattern plan counts what it pulls

def test_pattern_plan_notes_d2h_bytes():
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(PATTERN)
    rows = [0]
    rt.add_batch_callback("Out", lambda b: rows.__setitem__(0, rows[0] + b.n))
    rt.start()
    try:
        rt.input_handler("S").send_batch(*_batch(0, 512))
        rt.flush()
        plans = rt.statistics()["profile"]["plans"]
    finally:
        mgr.shutdown()
    assert rows[0] > 0
    assert plans["q"]["bytes"]["d2h"] > 0 and plans["q"]["bytes"]["h2d"] > 0


# (g) the result path under names: `transfer` = wait + copy, `unpack`
# before `scatter`, page faults where the result lands

FLAT = ("@app:devicePatterns('prefer')\n" + STOCK +
        "@info(name='q') from every e1=S[p > 100] -> e2=S[p > e1.p] "
        "{within}select e1.p as a, e2.p as b insert into Out;\n")
FUSED = STOCK + "".join(
    f"@info(name='q{i}') from every e1=S[p > {120 + i}] -> e2=S[p > e1.p] "
    "within 1 sec select e1.p as a, e2.p as b insert into Out;\n"
    for i in range(8))
WINDOW = STOCK + ("@info(name='q') from S#window.length(100) "
                  "select avg(p) as ap insert into Out;\n")
# path -> (app, events a flush, ms an event, the decode it takes (a flat
# block's is the one-lane case of a lane result's), `scatter` spans the
# plan opens a flush: what they were before `unpack` had a span)
# (lane_grid.ResultDecoder's two, and the plan's one-lane call of `lanes`)
DECODES = ("lanes", "cut", "_unpack_block")
RESULT_PATHS = {
    "lane": (PATTERN, 2048, 1, DECODES[:1], 3),
    "fused-row": (FUSED, 512, 50, DECODES[1:2], 1),
    "flat-block": (FLAT.format(within="within 1 sec "), 256, 1,
                   DECODES[::2], 3),
    "seq-block": (FLAT.format(within=""), 256, 1, DECODES[::2], 2),
    "filter": (FILTER, 4096, 1, (), 0),
    "window": (WINDOW, 4096, 1, (), 0),
}
PATTERN_PATHS = [k for k, v in RESULT_PATHS.items() if v[3]]


def _run_result_path(path, monkeypatch, header="@app:trace('all')\n",
                     stats=True):
    """Three send_batch + flush rounds down one result path: (stage
    statistics, the frames' trees, calls of each of DECODES, rows out,
    the runtime's Prometheus text)."""
    from siddhi_tpu.core import lane_grid, pattern_plan
    app, n, dt, _fn, _sc = RESULT_PATHS[path]
    if path == "fused-row":     # rows of 64 events: a short flush is cut
        monkeypatch.setattr(pattern_plan, "FUSED_ROW_WINDOWS", 2)
        monkeypatch.setattr(pattern_plan, "FUSED_ROW_MIN", 16)
    calls = {}
    for fn in DECODES:
        owner = pattern_plan.DevicePatternPlan if fn == "_unpack_block" \
            else lane_grid.ResultDecoder

        def counted(self, *a, _o=getattr(owner, fn), _f=fn, **kw):
            calls[_f] = calls.get(_f, 0) + 1
            return _o(self, *a, **kw)
        monkeypatch.setattr(owner, fn, counted)
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(header + app)
    rows = [0]
    rt.add_batch_callback("Out", lambda b: rows.__setitem__(0, rows[0] + b.n))
    rt.enable_stats(stats)
    rt.start()
    try:
        for k in range(3):
            cols, ts = _batch(k, n)
            rt.input_handler("S").send_batch(cols, T0_MS + dt * (ts - T0_MS))
            rt.flush()
        trees = list(rt.tracing.traces().values()) if rt.tracing else []
        return (rt.statistics()["stages"], trees, calls, rows[0],
                rt.stats.prometheus())
    finally:
        mgr.shutdown()


@pytest.mark.parametrize("path", sorted(RESULT_PATHS))
def test_transfer_is_its_wait_and_its_copy(path, monkeypatch):
    stages, trees, _calls, rows, _prom = _run_result_path(path, monkeypatch)
    assert rows > 0
    wait, copy, whole = (stages[n] for n in
                         ("transfer.wait", "transfer.copy", "transfer"))
    # one of each a pull; the profiler's probe is a `transfer` with neither
    assert 3 <= wait["batches"] == copy["batches"] <= whole["batches"]
    assert wait["seconds"] + copy["seconds"] <= whole["seconds"]
    pulls = 0
    for spans in trees:
        by_id = _one_tree(spans)
        for sp in spans:
            if sp["name"] in ("transfer.wait", "transfer.copy"):
                # the wait hangs below `transfer`, the copy below the wait
                # that closed just before it (as `sink.send` below
                # `sink.encode`), and both lie inside the pull's interval
                parent = by_id[sp["parent"]]
                if sp["name"] == "transfer.copy":
                    assert parent["name"] == "transfer.wait", (sp, parent)
                    parent = by_id[parent["parent"]]
                assert parent["name"] == "transfer", (sp, parent)
                assert sp["t0_s"] >= parent["t0_s"] - 2e-6
                assert sp["t0_s"] + sp["dur_s"] <= \
                    parent["t0_s"] + parent["dur_s"] + 2e-6
                pulls += sp["name"] == "transfer.copy"
    assert pulls == copy["batches"]


@pytest.mark.parametrize("path", PATTERN_PATHS + ["window"])
def test_unpack_closes_before_scatter_opens(path, monkeypatch):
    _app, _n, _dt, fns, plan_scatters = RESULT_PATHS[path]
    stages, trees, calls, rows, _prom = _run_result_path(path, monkeypatch)
    assert rows > 0 and calls == dict.fromkeys(fns, 3), calls
    assert stages["unpack"]["batches"] >= 3
    assert len(trees) == 3
    for spans in trees:
        mine = [s for s in spans if (s.get("args") or {}).get("plan")]
        unpacks = [s for s in mine if s["name"] == "unpack"]
        scatters = [s for s in mine if s["name"] == "scatter"]
        assert unpacks and len(scatters) == plan_scatters, (path, mine)
        for u in unpacks:
            u_end = u["t0_s"] + u["dur_s"]
            for sc in scatters:     # apart: `scatter` keeps its boundaries
                assert u_end <= sc["t0_s"] + 2e-6 \
                    or sc["t0_s"] + sc["dur_s"] <= u["t0_s"] + 2e-6, (u, sc)
        # the flush's last unpack is over before its row decode begins
        # (the window plan decodes no rows: the runtime's own `scatter`,
        # around the callbacks, is what follows its unpack)
        last = max(unpacks, key=lambda s: s["t0_s"])
        after = scatters or [s for s in spans if s["name"] == "scatter"]
        assert any(sc["t0_s"] >= last["t0_s"] + last["dur_s"] - 2e-6
                   for sc in after), (last, after)


@pytest.mark.parametrize("path", PATTERN_PATHS)
def test_the_index_under_unpack_the_columns_under_scatter(path, monkeypatch):
    """What `materialise_ms_per_batch` (span `scatter`) and the idle gap
    `siddhi:unpack` mean: the index over a result's filled cells is built
    while `unpack` is the innermost open span, every delivered column is
    fetched while `scatter` is."""
    from contextlib import contextmanager
    from siddhi_tpu.core import lane_grid, pattern_plan
    app, n, dt, _fns, _sc = RESULT_PATHS[path]
    if path == "fused-row":
        monkeypatch.setattr(pattern_plan, "FUSED_ROW_WINDOWS", 2)
        monkeypatch.setattr(pattern_plan, "FUSED_ROW_MIN", 16)
    open_spans, seen = [], {"_index": [], "column": []}
    for fn in seen:
        def noted(self, *a, _o=getattr(lane_grid._Filled, fn), _f=fn):
            seen[_f].append(open_spans[-1] if open_spans else None)
            return _o(self, *a)
        monkeypatch.setattr(lane_grid._Filled, fn, noted)
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(app)
    span = rt.span

    @contextmanager
    def logged(name, **kw):
        open_spans.append(name)
        try:
            with span(name, **kw) as sp:
                yield sp
        finally:
            open_spans.pop()
    rt.span = logged
    rows = [0]
    rt.add_batch_callback("Out", lambda b: rows.__setitem__(0, rows[0] + b.n))
    rt.start()
    try:
        cols, ts = _batch(0, n)
        rt.input_handler("S").send_batch(cols, T0_MS + dt * (ts - T0_MS))
        rt.flush()
    finally:
        mgr.shutdown()
    assert rows[0] > 0
    assert seen["_index"] and set(seen["_index"]) == {"unpack"}, seen
    assert seen["column"] and set(seen["column"]) == {"scatter"}, seen


def test_fault_counters_ride_the_four_result_spans(monkeypatch):
    from tests.test_tracing import assert_valid_exposition
    stages, _t, _c, _r, prom = _run_result_path("fused-row", monkeypatch)
    counted = {k for k, v in stages.items() if "minor_faults" in v}
    assert counted == set(telemetry.FAULT_SPANS) == {
        "transfer.copy", "unpack", "scatter", "route"}
    assert counted == {k for k, v in stages.items() if "major_faults" in v}
    assert_valid_exposition(prom)
    for kind in ("minor", "major"):
        got = re.findall(rf'^siddhi_tpu_stage_{kind}_faults_total'
                         r'{app="[^"]*",stage="([a-z_.]+)"} \d+$', prom,
                         flags=re.M)
        assert sorted(got) == sorted(counted), (kind, got)


@pytest.mark.parametrize("name,plan", [
    (n, "q") for n in sorted(telemetry.FAULT_SPANS)] + [
    ("transfer.wait", "q"), ("emit", "q"),
    ("scatter", None)])         # the runtime's own: it lands no result
def test_fault_counts_rise_with_a_fresh_buffer(name, plan):
    """64 MB is past the allocator's mmap ceiling, so the buffer is pages
    the process has never touched: 16,384 of them, or 32 huge ones."""
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(FILTER)
    rt.enable_stats()
    try:
        with rt.span(name, plan=plan):
            pass
        before = rt.statistics()["stages"][name]
        with rt.span(name, plan=plan):
            buf = np.empty(64 << 20, np.uint8)
            buf[::4096] = 1
        after = rt.statistics()["stages"][name]
    finally:
        mgr.shutdown()
    if plan is None or name not in telemetry.FAULT_SPANS:
        assert "minor_faults" not in after and "major_faults" not in after
        return
    assert after["minor_faults"] - before["minor_faults"] >= 16
    assert after["major_faults"] >= before["major_faults"] >= 0


@pytest.mark.parametrize("header", [
    "@app:trace('off')\n@app:profile('off')\n",   # every sink off
    "@app:trace('off')\n"])                        # the default profiler
def test_transfer_children_are_noops_with_no_sink_on(header):
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(header + FILTER)
    try:
        for name in ("transfer.wait", "transfer.copy"):
            assert rt.span(name, plan="q") is NOOP_SPAN, name
    finally:
        mgr.shutdown()


@pytest.mark.parametrize("path", ["filter", "lane", "seq-block", "window"])
def test_pull_does_not_block_with_no_sink_on(path, monkeypatch):
    """Statistics off, no traced frame: the pull makes the calls it made
    before `transfer` had children, so no `block_until_ready`."""
    def boom(*a, **kw):
        raise AssertionError("block_until_ready on the off path")
    monkeypatch.setattr(jax, "block_until_ready", boom)
    stages, _t, _calls, rows, _p = _run_result_path(
        path, monkeypatch, stats=False,
        header="@app:trace('off')\n@app:profile('off')\n")
    assert rows > 0 and stages == {}


@pytest.mark.parametrize("counts", [True, False],
                         ids=["linux", "sandboxed-kernel"])
def test_a_kernel_that_counts_no_faults_is_found_out(counts, monkeypatch):
    """gVisor answers getrusage with 0 faults whatever is touched (the
    chip machines, PERF.md 7.10): a count of 0 there would read as "no
    fresh pages", so FAULT_SPANS is empty on such a kernel."""
    if not counts:
        monkeypatch.setattr(telemetry, "_page_faults", lambda: (0, 0))
    elif not telemetry.FAULT_SPANS:
        pytest.skip("this kernel counts no page faults")
    assert telemetry._kernel_counts_faults() is counts


def test_fault_counts_need_the_resource_module(monkeypatch):
    """A platform without `resource`, or whose kernel does not count: no
    span counts, nothing is read."""
    monkeypatch.setattr(telemetry, "FAULT_SPANS", frozenset())
    monkeypatch.setattr(telemetry, "resource", None)
    stages, _t, _c, rows, _p = _run_result_path("filter", monkeypatch)
    assert rows > 0 and "unpack" in stages
    assert not [k for k, v in stages.items() if "minor_faults" in v]


# (f) the taxonomy is the documentation's

def _doc_table():
    with open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")) as f:
        text = f.read()
    sec = text.split("## Span taxonomy", 1)[1].split("\n## ", 1)[0]
    return re.findall(r"^\| `([a-z_.]+)` \|", sec, flags=re.M)


@pytest.mark.parametrize("name", SPANS)
def test_span_is_in_the_documented_taxonomy(name):
    assert name in _doc_table()


def test_documented_taxonomy_names_nothing_else():
    assert sorted(_doc_table()) == sorted(SPANS)
    assert len(set(SPANS)) == len(SPANS)


def test_every_span_the_source_opens_is_in_the_taxonomy():
    """One tuple: a `span("...")` literal anywhere in the package names a
    member of SPANS, and nothing records time another way."""
    pat = re.compile(r"""\bspan\(\s*["']([a-z_.]+)["']""")
    seen = set()
    for path in glob.glob(os.path.join(ROOT, "siddhi_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            src = f.read()
        seen.update(pat.findall(src))
        assert "stats.stage(" not in src and "time_plan" not in src, path
    assert seen and seen <= set(SPANS), seen - set(SPANS)
    # `compile`/`kernel` are chosen at run time, `frame` is the root
    # marker, `gc` is written by the process's lock-free collector hook
    assert set(SPANS) - seen <= {"compile", "kernel", "frame", "parse",
                                 "gc"}, set(SPANS) - seen


# the process span

def test_gc_span_and_its_hook_follow_enable_stats():
    import gc
    hook = telemetry._on_gc
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(FILTER)
    try:
        before = gc.callbacks.count(hook)    # (another app may be watching)
        assert rt.stats not in telemetry._gc_watchers
        rt.enable_stats(True)
        rt.enable_stats(True)
        assert gc.callbacks.count(hook) == 1
        assert telemetry._gc_watchers.count(rt.stats) == 1
        gc.collect()
        st = rt.statistics()["stages"]["gc"]
        assert st["batches"] >= 1 and st["seconds"] > 0
        rt.enable_stats(False)
        assert rt.stats not in telemetry._gc_watchers
        assert gc.callbacks.count(hook) == before
        rt.enable_stats(True)
    finally:
        mgr.shutdown()
    assert rt.stats not in telemetry._gc_watchers
    assert gc.callbacks.count(hook) == before


def test_two_apps_share_one_gc_hook_and_count_a_pause_once_each():
    import gc
    mgr = SiddhiManager()
    rts = [mgr.create_app_runtime(f"@app:name('gc{i}')\n" + FILTER)
           for i in range(2)]
    try:
        for rt in rts:
            rt.enable_stats()
        assert gc.callbacks.count(telemetry._on_gc) == 1
        n0 = [rt.stats._gc.batches for rt in rts]
        was = gc.isenabled()
        gc.disable()            # only the three collections below
        try:
            for _ in range(3):
                gc.collect()
            got = [rt.stats._gc.batches - n for rt, n in zip(rts, n0)]
        finally:
            if was:
                gc.enable()
        assert got == [3, 3]
    finally:
        mgr.shutdown()


_SCRAPE_UNDER_GC = """
import faulthandler, gc, sys, threading, time
faulthandler.dump_traceback_later(40, exit=True)    # a hang prints stacks
sys.path.insert(0, %r)
from siddhi_tpu import SiddhiManager
rt = SiddhiManager().create_app_runtime(%r)
rt.enable_stats()
rt.start()
gc.collect()
gc.set_threshold(1, 1, 1)   # nearly every container allocation collects:
stop = threading.Event()    # the scrape's own dicts trip the hook under
def churn():                # the Tracker locks it holds
    while not stop.is_set():
        [[] for _ in range(50)]
t = threading.Thread(target=churn, daemon=True)
t.start()
end = time.monotonic() + 1.5
n = 0
while time.monotonic() < end:
    assert rt.statistics()["stages"]["gc"]["batches"] > 0
    rt.stats.prometheus()
    n += 1
stop.set()
t.join()
gc.set_threshold(700, 10, 10)
rt.shutdown()
print("scrapes", n)
"""


def test_scrape_while_collections_run_does_not_deadlock():
    """The gc hook runs inside whatever allocation tripped the collector,
    `Tracker.as_dict`'s own under the tracker's lock included: it must
    take no lock (this hung within seconds when `gc` was an ordinary
    span)."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    r = subprocess.run(
        [sys.executable, "-c", _SCRAPE_UNDER_GC % (ROOT, FILTER)],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "scrapes" in r.stdout


def test_backdated_span_counts_the_wait_before_it():
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(FILTER)
    rt.enable_stats()
    try:
        t0 = time.perf_counter()
        time.sleep(0.02)
        with rt.span("queue_wait", t0=t0) as sp:
            pass
        assert sp.seconds >= 0.02
        st = rt.statistics()["stages"]["queue_wait"]
        assert st["seconds"] == pytest.approx(sp.seconds)
    finally:
        mgr.shutdown()
