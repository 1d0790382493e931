"""Parity tail: per-group rate limiters, distributed sinks, ConfigManager
SPI, createSet/sizeOfSet, statistics reporters."""
import time

import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.config import InMemoryConfigManager
from siddhi_tpu.core.io import InMemoryBroker
from siddhi_tpu.core.telemetry import register_stats_reporter


@pytest.fixture
def mgr():
    m = SiddhiManager()
    yield m
    m.shutdown()


def test_group_by_per_event_first_rate(mgr):
    """`output first every 2 events` with group by limits PER GROUP
    (reference: GroupByPerEventOutputRateLimiter)."""
    rt = mgr.create_app_runtime("""
        define stream S (sym string, p double);
        @info(name='q') from S select sym, sum(p) as total group by sym
        output first every 2 events insert into O;
    """)
    out = []
    rt.add_callback("O", lambda evs: out.extend(e.data for e in evs))
    rt.start()
    h = rt.input_handler("S")
    for sym, p in (("A", 1.0), ("B", 10.0), ("A", 2.0), ("B", 20.0),
                   ("A", 3.0), ("B", 30.0)):
        h.send((sym, p))
    rt.flush()
    # first of every 2 PER GROUP: A@1, B@10, A@3(3rd A), B@60(3rd B)
    a_rows = [r for r in out if r[0] == "A"]
    b_rows = [r for r in out if r[0] == "B"]
    assert len(a_rows) == 2 and len(b_rows) == 2, out
    assert a_rows[0] == ("A", 1.0) and b_rows[0] == ("B", 10.0)


def test_group_by_last_rate(mgr):
    rt = mgr.create_app_runtime("""
        define stream S (sym string, p double);
        @info(name='q') from S select sym, p group by sym
        output last every 2 events insert into O;
    """)
    out = []
    rt.add_callback("O", lambda evs: out.extend(e.data for e in evs))
    rt.start()
    h = rt.input_handler("S")
    for sym, p in (("A", 1.0), ("A", 2.0), ("B", 5.0), ("B", 6.0)):
        h.send((sym, p))
    rt.flush()
    assert sorted(out) == [("A", 2.0), ("B", 6.0)]


def _broker_topics(topics):
    got = {t: [] for t in topics}
    subs = []
    for t in topics:
        fn = InMemoryBroker.subscribe(t, lambda m, _t=t: got[_t].append(m))
        subs.append((t, fn))
    return got, subs


def test_distributed_sink_round_robin(mgr):
    got, subs = _broker_topics(["d1", "d2"])
    rt = mgr.create_app_runtime("""
        define stream A (x int);
        @sink(type='inMemory', @map(type='passThrough'),
              @distribution(strategy='roundRobin',
                            @destination(topic='d1'),
                            @destination(topic='d2')))
        define stream B (x int);
        @info(name='q') from A select x insert into B;
    """)
    rt.start()
    h = rt.input_handler("A")
    for i in range(4):
        h.send((i,))
    rt.flush()
    assert got["d1"] == [(0,), (2,)] and got["d2"] == [(1,), (3,)]
    for t, fn in subs:
        InMemoryBroker.unsubscribe(t, fn)


def test_distributed_sink_broadcast_and_partitioned(mgr):
    got, subs = _broker_topics(["b1", "b2", "p1", "p2"])
    rt = mgr.create_app_runtime("""
        define stream A (sym string, x int);
        @sink(type='inMemory', @map(type='passThrough'),
              @distribution(strategy='broadcast',
                            @destination(topic='b1'),
                            @destination(topic='b2')))
        @sink(type='inMemory', @map(type='passThrough'),
              @distribution(strategy='partitioned', partitionKey='sym',
                            @destination(topic='p1'),
                            @destination(topic='p2')))
        define stream B (sym string, x int);
        @info(name='q') from A select sym, x insert into B;
    """)
    rt.start()
    h = rt.input_handler("A")
    for sym, x in (("K1", 1), ("K2", 2), ("K1", 3)):
        h.send((sym, x))
    rt.flush()
    assert got["b1"] == got["b2"] == [("K1", 1), ("K2", 2), ("K1", 3)]
    # partitioned: same key always lands on the same destination
    all_p = got["p1"] + got["p2"]
    assert sorted(all_p) == [("K1", 1), ("K1", 3), ("K2", 2)]
    k1_dest = ["p1" if ("K1", 1) in got["p1"] else "p2"]
    assert (("K1", 3) in got[k1_dest[0]])
    for t, fn in subs:
        InMemoryBroker.unsubscribe(t, fn)


def test_config_manager_spi(mgr):
    mgr.set_config_manager(InMemoryConfigManager({
        "source.inmemory.buffer": "99",
        "global_flag": "on",
        "sink.log.prefix": "XX",
    }))
    rt = mgr.create_app_runtime("""
        @source(type='inMemory', topic='cfg-t', @map(type='passThrough'))
        define stream S (x int);
        @info(name='q') from S select x insert into O;
    """)
    rt.start()
    src = rt.sources[0]
    assert src.config.read("buffer") == "99"
    assert src.config.read("global_flag") == "on"
    assert src.config.read("prefix") is None        # other namespace
    assert src.config.read("missing", "dflt") == "dflt"


def test_create_set_size_of_set(mgr):
    rt = mgr.create_app_runtime("""
        define stream S (sym string, v int);
        @info(name='q') from S#window.lengthBatch(3)
        select sizeOfSet(unionSet(createSet(sym))) as distinct_syms
        insert into O;
    """)
    out = []
    rt.add_callback("O", lambda evs: out.extend(e.data for e in evs))
    rt.start()
    h = rt.input_handler("S")
    for sym in ("A", "B", "A"):
        h.send((sym, 1))
    rt.flush()
    # running union per arriving event: {A}=1, {A,B}=2, {A,B,A}=2
    assert out == [(1,), (2,), (2,)]


def test_statistics_reporter_interval(mgr):
    seen = []
    register_stats_reporter("testrep", lambda app, rep: seen.append(rep))
    rt = mgr.create_app_runtime("""
        @app:statistics(reporter='testrep', interval='50 ms')
        define stream S (x int);
        @info(name='q') from S[x > 0] select x insert into O;
    """)
    rt.start()
    rt.input_handler("S").send((1,))
    rt.flush()
    time.sleep(0.25)
    rt.shutdown()
    assert len(seen) >= 2
    assert any(r["streams"].get("S", {}).get("events") == 1 for r in seen)
