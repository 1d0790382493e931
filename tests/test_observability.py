"""Statistics, debugger, and extension SPI (stream functions, windows,
aggregators).  Reference test surface: managment/StatisticsTestCase,
debugger/SiddhiDebuggerTestCase, query/extension/*."""
import pytest

from siddhi_tpu import SiddhiManager


@pytest.fixture
def mgr():
    m = SiddhiManager()
    yield m
    m.shutdown()


def collect(rt, sid):
    out = []
    rt.add_callback(sid, lambda evs: out.extend(e.data for e in evs))
    return out


def test_statistics_tracking(mgr):
    rt = mgr.create_app_runtime("""
        @app:statistics('true')
        define stream S (x int);
        @info(name='q1') from S[x > 0] select x insert into O;
    """)
    collect(rt, "O")
    rt.input_handler("S").send([(i,) for i in range(100)])
    rt.flush()
    rep = rt.statistics()
    assert rep["streams"]["S"]["events"] == 100
    assert rep["queries"]["q1"]["events"] == 100
    assert rep["queries"]["q1"]["seconds"] > 0


def test_statistics_runtime_toggle(mgr):
    rt = mgr.create_app_runtime("""
        define stream S (x int);
        from S select x insert into O;
    """)
    collect(rt, "O")
    rt.input_handler("S").send((1,))
    rt.flush()
    assert rt.statistics()["streams"] == {}     # off by default
    rt.enable_stats(True)
    rt.input_handler("S").send((2,))
    rt.flush()
    assert rt.statistics()["streams"]["S"]["events"] == 1


def test_histogram_quantiles():
    from siddhi_tpu.core.telemetry import Histogram
    h = Histogram()
    assert h.percentile(99) is None          # empty -> None, never 0
    for ms in (1, 2, 5, 10, 100):
        h.record(ms / 1e3)
    # log-bucket bound: reported quantile within ~2^(1/16) of exact
    assert 0.001 <= h.percentile(50) <= 0.0055
    assert 0.05 <= h.percentile(99) <= 0.1001
    assert h.percentile(100) == h.max
    one = Histogram()
    one.record(0.25)
    assert one.percentile(99) == 0.25        # lone sample: exact (clamped)
    one.reset()
    assert one.count == 0 and one.percentile(50) is None


def test_tracker_as_dict_guards():
    """No null-valued keys: throughput/latency OMITTED when nothing was
    timed (a consumer summing report values must not meet None)."""
    from siddhi_tpu.core.telemetry import Tracker
    t = Tracker()
    t.events, t.batches = 10, 1              # counted but never timed
    d = t.as_dict()
    assert "throughput_eps" not in d and "latency_us_per_event" not in d
    assert None not in d.values()
    t.observe(0.5, events=10)
    d = t.as_dict()
    assert d["throughput_eps"] == pytest.approx(20 / 0.5)
    assert d["latency_us_per_event"] == pytest.approx(1e6 * 0.5 / 20)
    zero_ev = Tracker()
    zero_ev.observe(0.5, events=0)           # timed but empty batch
    d = zero_ev.as_dict()
    assert "latency_us_per_event" not in d and None not in d.values()


def test_statistics_percentiles(mgr):
    rt = mgr.create_app_runtime("""
        @app:statistics('true')
        define stream S (x int);
        @info(name='q1') from S[x > 0] select x insert into O;
    """)
    collect(rt, "O")
    import numpy as np
    h = rt.input_handler("S")
    for i in range(4):
        h.send_batch({"x": np.arange(1, 6, dtype=np.int32)})
    rt.flush()
    rep = rt.statistics()
    for scope, key in (("streams", "S"), ("queries", "q1"),
                       ("stages", "scatter")):
        td = rep[scope][key]
        assert td["p50_ms"] <= td["p95_ms"] <= td["p99_ms"]
    assert rep["stages"]["ingest"]["events"] == 20   # columnar ingest span
    assert rep["stages"]["plan"]["batches"] == 1     # build-time span


def test_reporter_spi_register_and_override(mgr):
    from siddhi_tpu.core.telemetry import REPORTERS, register_stats_reporter
    calls_a, calls_b = [], []
    register_stats_reporter("spiTest", lambda app, rep: calls_a.append(app))
    assert REPORTERS["spitest"] is not None          # name lowercased
    register_stats_reporter("SPITest",
                            lambda app, rep: calls_b.append(app))  # override
    rt = mgr.create_app_runtime("""
        @app:name('SpiApp')
        @app:statistics(reporter='spiTest', interval='20 milliseconds')
        define stream S (x int);
        from S select x insert into O;
    """)
    assert rt.stats.reporter is REPORTERS["spitest"]
    rt.stats.reporter("SpiApp", rt.statistics())
    assert calls_b == ["SpiApp"] and calls_a == []   # override won
    del REPORTERS["spitest"]


def test_unknown_reporter_rejected(mgr):
    with pytest.raises(Exception, match="unknown statistics reporter"):
        mgr.create_app_runtime("""
            @app:statistics(reporter='nosuch', interval='1 sec')
            define stream S (x int);
            from S select x insert into O;
        """)


def test_periodic_reporting_and_clean_stop(mgr):
    """@app:statistics(reporter=..., interval=...) starts the pump on
    rt.start() and rt.shutdown() leaves no timer thread behind."""
    import threading
    import time as _time
    from siddhi_tpu.core.telemetry import REPORTERS, register_stats_reporter
    got = []
    register_stats_reporter("trap", lambda app, rep: got.append(rep))
    rt = mgr.create_app_runtime("""
        @app:name('PumpApp')
        @app:statistics(reporter='trap', interval='20 milliseconds')
        define stream S (x int);
        from S select x insert into O;
    """)
    collect(rt, "O")
    rt.start()
    rt.input_handler("S").send((1,))
    rt.flush()
    # wait on the report that has seen the event, not on the clock: the
    # 20 ms pump can fire before the first event is counted
    deadline = _time.time() + 5
    while _time.time() < deadline and not (
            got and "S" in got[-1]["streams"]):
        _time.sleep(0.01)
    assert got, "periodic reporter never fired"
    assert "S" in got[-1]["streams"]
    rt.shutdown()
    assert not [t for t in threading.enumerate()
                if t.name == "siddhi-stats-report" and t.is_alive()], \
        "reporter thread leaked past shutdown()"
    n = len(got)
    _time.sleep(0.08)
    assert len(got) == n                     # pump really stopped
    del REPORTERS["trap"]


def test_prometheus_render(mgr):
    from siddhi_tpu.core.telemetry import render_prometheus
    rt = mgr.create_app_runtime("""
        @app:statistics('true')
        define stream S (x int);
        @info(name='q1') from S[x > 0] select x insert into O;
    """)
    collect(rt, "O")
    rt.input_handler("S").send([(i,) for i in range(1, 8)])
    rt.flush()
    text = render_prometheus({"App1": rt.statistics()})
    assert text.endswith("\n")
    assert 'siddhi_tpu_events_total{app="App1",stream="S"} 7' in text
    assert 'quantile="0.99"' in text
    # exposition format: HELP/TYPE exactly once per metric name
    helps = [ln.split()[2] for ln in text.splitlines()
             if ln.startswith("# HELP")]
    assert len(helps) == len(set(helps))
    for ln in text.splitlines():             # every sample line parses
        if ln.startswith("#") or not ln:
            continue
        val = ln.rsplit(" ", 1)[1]
        assert val == "NaN" or float(val) is not None


def test_device_metrics_sampled(mgr):
    """Device gauges (lane occupancy / frontier width) ride the stats
    report for device pattern plans — sampled at scrape, not per batch."""
    rt = mgr.create_app_runtime("""
        @app:statistics('true')
        @app:devicePatterns('always')
        define stream S (sym string, p double);
        partition with (sym of S) begin
          @info(name='q') from every e1=S[p > 10] -> e2=S[p > e1.p]
            within 1 sec
          select e1.p as a, e2.p as b insert into O;
        end;
    """)
    collect(rt, "O")
    h = rt.input_handler("S")
    ts0 = 1_700_000_000_000
    for rnd in range(2):         # identical rounds: round 2 reuses the
        for i in range(8):       # compiled block -> a `kernel` span
            h.send(("K1" if i % 2 else "K2", 11.0 + i),
                   timestamp=ts0 + (rnd * 8 + i) * 10)
        rt.flush()
    rep = rt.statistics()
    dev = rep["device"]["q"]
    assert dev["lanes_total"] >= 1
    assert dev["compiles"] >= 1 and dev["compile_seconds"] > 0
    assert dev["h2d_bytes"] > 0
    assert {"kernel", "transfer"} <= set(rep["stages"])
    # the compile span is attributed separately from steady-state kernel
    assert rep["stages"]["compile"]["seconds"] > 0


def test_debugger_breakpoints(mgr):
    rt = mgr.create_app_runtime("""
        define stream S (x int);
        @info(name='q1') from S[x > 5] select x * 2 as y insert into O;
    """)
    collect(rt, "O")
    dbg = rt.debug()
    hits = []
    dbg.set_callback(lambda q, pt, evs: hits.append((q, pt,
                                                     [e.data for e in evs])))
    dbg.acquire_breakpoint("q1", dbg.IN)
    dbg.acquire_breakpoint("q1", dbg.OUT)
    rt.input_handler("S").send([(3,), (10,)])
    rt.flush()
    assert ("q1", "in", [(3,), (10,)]) in hits
    assert ("q1", "out", [(20,)]) in hits
    dbg.release_all()
    hits.clear()
    rt.input_handler("S").send((7,))
    rt.flush()
    assert hits == []


def test_log_stream_function(mgr, capsys):
    rt = mgr.create_app_runtime("""
        define stream S (x int);
        @info(name='q') from S#log('seen') select x insert into O;
    """)
    out = collect(rt, "O")
    rt.input_handler("S").send((1,))
    rt.flush()
    assert out == [(1,)]
    assert "seen" in capsys.readouterr().out


def test_pol2cart_stream_function(mgr):
    rt = mgr.create_app_runtime("""
        define stream S (theta double, rho double);
        from S#pol2cart(theta, rho) select x, y insert into O;
    """)
    out = collect(rt, "O")
    rt.input_handler("S").send((0.0, 2.0))
    rt.flush()
    x, y = out[0]
    assert abs(x - 2.0) < 1e-9 and abs(y) < 1e-9


def test_custom_stream_function(mgr):
    from siddhi_tpu.interp.engine import register_stream_function

    def explode(args, ctx, in_schema, qname):
        def fn(ev):
            return [ev.data, ev.data]          # duplicate every event
        return in_schema, fn
    register_stream_function("explode", explode, "test")

    rt = mgr.create_app_runtime("""
        define stream S (x int);
        from S#test:explode() select x insert into O;
    """)
    out = collect(rt, "O")
    rt.input_handler("S").send((4,))
    rt.flush()
    assert out == [(4,), (4,)]


@pytest.fixture
def scratch_aggregator():
    """Registers an aggregator for one test and takes it out again: the
    registry is process-wide, and a leftover entry without metadata
    fails test_extension_meta when both files share an xdist worker."""
    from siddhi_tpu.core.planner import AGGREGATOR_NAMES
    from siddhi_tpu.interp.aggregators import (AGGREGATOR_CLASSES,
                                               register_aggregator)
    names = []

    def register(name, cls):
        names.append(name.lower())
        register_aggregator(name, cls)
    yield register
    for name in names:
        AGGREGATOR_CLASSES.pop(name, None)
        AGGREGATOR_NAMES.discard(name)


def test_custom_aggregator(mgr, scratch_aggregator):
    from siddhi_tpu.interp.aggregators import Aggregator
    from siddhi_tpu.query.ast import AttrType

    class ConcatAgg(Aggregator):
        type = AttrType.STRING

        def __init__(self, in_type):
            self.parts = []

        def add(self, v):
            self.parts.append(str(v))

        def remove(self, v):
            if str(v) in self.parts:
                self.parts.remove(str(v))

        def reset(self):
            self.parts = []

        def value(self):
            return "".join(self.parts)

        def state(self):
            return {"parts": list(self.parts)}

        def restore(self, st):
            self.parts = list(st["parts"])

    scratch_aggregator("strConcat", ConcatAgg)
    rt = mgr.create_app_runtime("""
        define stream S (s string);
        from S select strConcat(s) as joined insert into O;
    """)
    out = collect(rt, "O")
    rt.input_handler("S").send([("a",), ("b",)])
    rt.flush()
    assert out == [("a",), ("ab",)]


def test_custom_window_type(mgr):
    from siddhi_tpu.interp.engine import register_window_type
    from siddhi_tpu.interp import windows as W

    def first_n(args, ctx, schema):
        n = int(args[0].value)

        class FirstN(W.Window):
            def __init__(self):
                self.seen = 0

            def process(self, ev, now_ms):
                self.seen += 1
                return [(W.CURRENT, ev)] if self.seen <= n else []

            def state(self):
                return {"seen": self.seen}

            def restore(self, st):
                self.seen = st["seen"]
        return FirstN()
    register_window_type("firstN", first_n)

    rt = mgr.create_app_runtime("""
        define stream S (x int);
        from S#window.firstN(2) select x insert into O;
    """)
    out = collect(rt, "O")
    rt.input_handler("S").send([(1,), (2,), (3,)])
    rt.flush()
    assert out == [(1,), (2,)]
