"""End-to-end frame tracing (core/tracing.py + the wire TRACE frame):
cross-thread span trees must stay CONNECTED across every serving-path
hand-off (admission park/drain, WAL append, depth-D pipelined
materialization, sink retry after a breaker), egress frames must carry
the ingress trace id, traced and untraced runs must be byte-identical,
histogram buckets must carry OpenMetrics exemplars, and the whole
/metrics exposition must survive a text-format grammar check even with
hostile label values."""
import json
import os
import re
import tempfile
import time
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.telemetry import SPANS, render_prometheus
from siddhi_tpu.core.tracing import FrameTracer
from siddhi_tpu.net import TcpFrameClient
from siddhi_tpu.net import frame as fp
from siddhi_tpu.net.client import FrameReceiver

STREAM_DEF = "define stream S (sym string, p double);\n"


def _cols(n, seed=0, lo=5.0, hi=15.0):
    r = np.random.default_rng(seed)
    return {"sym": np.array([f"K{i % 3}" for i in range(n)]),
            "p": np.round(r.uniform(lo, hi, n), 2)}


def _tree_check(spans):
    """Assert one connected tree: exactly one root, no orphans."""
    ids = {s["span"] for s in spans}
    roots = [s for s in spans if s["parent"] == 0]
    orphans = [s for s in spans
               if s["parent"] != 0 and s["parent"] not in ids]
    assert len(roots) == 1, f"expected one root, got {roots}"
    assert not orphans, f"orphan spans: {orphans}"
    return [s["name"] for s in spans]


# ---------------------------------------------------------------------------
# tentpole: one TCP-ingested frame on a durable app -> one connected tree
# ---------------------------------------------------------------------------

def test_e2e_tcp_durable_frame_trace_tree(tmp_path):
    recv = FrameReceiver()
    app = (f"@app:name('TraceE2E')\n"
           f"@app:trace('all')\n"
           f"@app:durability('batch', dir='{tmp_path}/wal')\n"
           f"@source(type='tcp', port='0')\n"
           + STREAM_DEF +
           "@info(name='q') from S[p > 10] select sym, p insert into Out;\n"
           f"@sink(type='tcp', host='127.0.0.1', port='{recv.port}')\n"
           "define stream Out (sym string, p double);\n")
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(app)
    rt.enable_stats(True)
    rt.start()
    try:
        cli = TcpFrameClient("127.0.0.1", rt.sources[0].port, "S",
                             TcpFrameClient.cols_of_schema(rt.schemas["S"]))
        cli.send_batch(_cols(8, lo=11.0, hi=20.0),
                       np.arange(8, dtype=np.int64), trace_id="prod-e2e-1")
        cli.barrier(timeout=60)
        rt.flush()
        cli.close()
        traces = rt.tracing.traces()
        assert "prod-e2e-1" in traces, sorted(traces)
        names = _tree_check(traces["prod-e2e-1"])
        # the causal chain: wire decode -> admission -> gate ->
        # freeze (WAL append inside) -> device dispatch -> result pull
        # -> sink egress
        for want in ("frame", "net.decode", "admit", "queue_wait",
                     "wal.append", "freeze", "dispatch", "transfer",
                     "sink.publish", "sink.encode", "sink.send"):
            assert want in names, (want, names)
        by_id = {s["span"]: s for s in traces["prod-e2e-1"]}
        for child, parent in (("wal.append", "freeze"),
                              ("sink.encode", "sink.publish")):
            sp = next(s for s in traces["prod-e2e-1"]
                      if s["name"] == child)
            assert by_id[sp["parent"]]["name"] == parent, sp
        # the wal.append span names the durable frame seq (trace rides
        # the WAL plane's per-stream frame identity)
        wal_span = next(s for s in traces["prod-e2e-1"]
                        if s["name"] == "wal.append")
        assert wal_span["args"]["seq"] == 1
        # the egress DATA frame re-stamped the INGRESS trace id
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and \
                "prod-e2e-1" not in recv.trace_ids:
            time.sleep(0.02)
        assert "prod-e2e-1" in recv.trace_ids
    finally:
        rt.shutdown()
        recv.stop()


def test_traced_vs_untraced_outputs_byte_identical():
    body = (STREAM_DEF +
            "@info(name='q') from S#window.length(6) select sym, "
            "sum(p) as s insert into Out;\n")

    def run(head):
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(head + body)
        rows = []
        rt.add_batch_callback("Out", lambda b: rows.extend(
            map(tuple, b.rows(rt.strings))))
        rt.start()
        h = rt.input_handler("S")
        for k in range(6):
            h.send_batch(_cols(16, seed=k), np.arange(16) + 16 * k)
            rt.flush()
        mgr.shutdown()
        return rows

    base = run("@app:trace('off')\n")
    traced = run("@app:trace('all')\n")
    assert base and traced == base


# ---------------------------------------------------------------------------
# cross-thread reparenting satellites
# ---------------------------------------------------------------------------

def test_depth4_pipelined_window_single_tree():
    """Depth-4 deferred materialization: the transfer span lands up
    to 4 batches later (and on flush) — every frame's tree must still
    be connected, with the transfer parented into ITS frame."""
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:trace('all')\n@app:deviceWindows('always')\n"
        "@app:devicePipeline(4)\n" + STREAM_DEF +
        "from S#window.length(6) select sym, sum(p) as s "
        "group by sym insert into O;\n")
    rt.start()
    h = rt.input_handler("S")
    for k in range(8):
        h.send_batch(_cols(8, seed=k), np.arange(8) + 8 * k)
    rt.flush()
    traces = rt.tracing.traces()
    mgr.shutdown()
    assert len(traces) == 8
    mat_threads = set()
    for tid, spans in traces.items():
        names = _tree_check(spans)
        assert "freeze" in names and "dispatch" in names
        assert "transfer" in names, (tid, names)
        mat_threads.update(s["thread"] for s in spans
                           if s["name"] == "transfer")
    assert mat_threads            # recorded, wherever they ran


def test_oldest_park_drain_lands_on_correct_parent():
    """'oldest'-policy admission: a parked frame drains later — often on
    the scheduler pump thread — and its freeze/dispatch spans must land
    on ITS tree, not the draining frame's."""
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:trace('all')\n"
        "@source(type='tcp', port='0', rate.limit='512', burst='64', "
        "shed.policy='oldest')\n" + STREAM_DEF +
        "@info(name='q') from S[p > 0] select sym, p insert into Out;\n")
    rt.start()
    try:
        cli = TcpFrameClient("127.0.0.1", rt.sources[0].port, "S",
                             TcpFrameClient.cols_of_schema(rt.schemas["S"]))
        for k in range(3):      # 64-event frames: the bucket admits the
            cli.send_batch(_cols(64, seed=k),   # first, the rest park
                           np.arange(64) + 64 * k,
                           trace_id=f"park-{k}")
        # without durability the ACK does not wait for the park: poll
        # until the scheduler pump drained + fed every parked frame
        deadline = time.monotonic() + 20
        traces = {}
        while time.monotonic() < deadline:
            traces = rt.tracing.traces()
            if all("freeze" in [s["name"] for s in traces.get(
                    f"park-{k}", [])] for k in range(3)):
                break
            time.sleep(0.05)
        rt.flush()
        traces = rt.tracing.traces()
        for k in range(3):
            tid = f"park-{k}"
            assert tid in traces, sorted(traces)
            names = _tree_check(traces[tid])
            for want in ("admit", "freeze", "dispatch"):
                assert want in names, (tid, names)
        cli.close()
    finally:
        rt.shutdown()


def test_sink_retry_after_breaker_stays_one_tree():
    """A sink publish that fails into an open breaker sheds the payload
    to the ErrorStore; the later replay re-publishes it.  The replayed
    publish span must resume the ORIGINAL frame's trace (the payload
    carries its resumable ctx) — one tree, no orphans."""
    recv = FrameReceiver(fail_first=2)
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:trace('all')\n" + STREAM_DEF +
        "@info(name='q') from S[p > 10] select sym, p insert into Out;\n"
        f"@sink(type='tcp', host='127.0.0.1', port='{recv.port}', "
        "on.error='store', max.retries='0', breaker.threshold='1', "
        "breaker.reset='50 ms')\n"
        "define stream Out (sym string, p double);\n")
    rt.start()
    try:
        h = rt.input_handler("S")
        h.send_batch(_cols(4, lo=11.0, hi=20.0), np.arange(4))
        rt.flush()
        # the publish failed (refused connection), payload stored
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not len(rt.error_store):
            time.sleep(0.02)
        assert len(rt.error_store) == 1
        time.sleep(0.1)               # breaker reset window
        out = rt.error_store.replay(rt)
        assert out["replayed"] == 1, out
        traces = rt.tracing.traces()
        assert len(traces) == 1
        spans = next(iter(traces.values()))
        names = _tree_check(spans)
        sends = [s for s in spans if s["name"] == "sink.send"]
        # the failed attempt AND the successful replay, same trace
        assert len(sends) >= 2, names
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not recv.rows():
            time.sleep(0.02)
        assert recv.rows()
    finally:
        rt.shutdown()
        recv.stop()


# ---------------------------------------------------------------------------
# triggers + dumps
# ---------------------------------------------------------------------------

def test_slo_breach_trigger_exports_dump(tmp_path):
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        f"@app:trace('all', dir='{tmp_path}', cooldown='0')\n"
        "@app:latencySLO('0.01 ms')\n" + STREAM_DEF +
        "@info(name='q') from S[p > 10] select sym, p insert into Out;\n")
    rt.enable_stats(True)
    rt.start()
    try:
        h = rt.input_handler("S")
        deadline = time.monotonic() + 20
        k = 0
        files = []
        while time.monotonic() < deadline:
            h.send_batch(_cols(64, seed=k), np.arange(64) + 64 * k)
            rt.flush()
            k += 1
            time.sleep(0.01)
            files = [f for f in os.listdir(tmp_path)
                     if f.endswith(".json")]
            if files:
                break
        assert files, rt.tracing.metrics()
        obj = json.load(open(os.path.join(tmp_path, files[0])))
        # the Chrome object contract smoke.sh also lints
        assert "traceEvents" in obj and "metadata" in obj
        md = obj["metadata"]
        assert md["reason"] == "slo_breach"
        assert md["hostname"]                     # federation merge key
        assert md["app"] == rt.app.name
        # the dump's slowest span names the breaching stage
        assert md["slowest"]["name"] in SPANS
        assert rt.tracing.metrics()["triggers"].get("slo_breach")
        # the exporter writes the file BEFORE it publishes the dump
        # (FrameTracer._promote), so the file can be seen a moment early
        while not rt.tracing.dump_summaries() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert rt.tracing.dump_summaries()
    finally:
        rt.shutdown()


def test_trigger_cooldown_and_close():
    tr = FrameTracer("App", sample_every=1, cooldown_s=60.0)
    h = tr.begin_frame("S")
    h.mark("dispatch", time.perf_counter(), 0.001, plan="q")
    assert tr.trigger("quarantine", "plan q")
    assert not tr.trigger("quarantine", "again")      # cooldown
    assert tr.trigger("breaker_open", "other kind ok")
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and len(tr.dumps) < 2:
        time.sleep(0.02)
    assert len(tr.dumps) == 2
    m = tr.metrics()
    assert m["triggers"] == {"quarantine": 1, "breaker_open": 1}
    assert m["triggers_suppressed"] == 1
    tr.close()
    assert not tr.trigger("quarantine", "after close")


def test_unsampled_frames_record_nothing():
    tr = FrameTracer("App", sample_every=0)       # sampling off
    assert tr.begin_frame("S") is None
    assert tr.begin_frame("S", trace_id="forced") is not None
    assert len(tr.traces()) == 1                  # producer id traced
    tr.close()


# ---------------------------------------------------------------------------
# wire TRACE frame
# ---------------------------------------------------------------------------

def test_trace_frame_codec_roundtrip():
    blob = fp.encode_trace("abc-1", 7)
    frames, rest = fp.parse_buffer(blob)
    assert not rest and frames[0][0] == fp.TRACE
    assert fp.decode_trace(frames[0][1]) == ("abc-1", 7)
    with pytest.raises(fp.FrameError):
        fp.decode_trace(b"{}")
    with pytest.raises(fp.FrameError):
        fp.decode_trace(b"not json")


# ---------------------------------------------------------------------------
# exemplars + exposition grammar (satellite: escaping round-trip)
# ---------------------------------------------------------------------------

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:\\.|[^"\\\n])*"'
_VALUE = r"(?:NaN|[+-]Inf|-?[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)"
_EXEMPLAR = rf" # \{{{_LABEL}(?:,{_LABEL})*\}} {_VALUE}(?: {_VALUE})?"
_SAMPLE_RE = re.compile(
    rf"^{_NAME}(?:\{{(?:{_LABEL}(?:,{_LABEL})*)?\}})? {_VALUE}"
    rf"(?:{_EXEMPLAR})?$")


def assert_valid_exposition(text: str) -> None:
    """Validate every line of a text exposition against the
    format grammar (names, escaped label values, numeric samples,
    optional OpenMetrics exemplar suffix)."""
    assert text.endswith("\n")
    for ln in text.splitlines():
        if not ln:
            continue
        if ln.startswith("# HELP ") or ln.startswith("# TYPE ") \
                or ln == "# EOF":
            continue
        assert _SAMPLE_RE.match(ln), f"bad exposition line: {ln!r}"


def _unescape(s: str) -> str:
    out, i = [], 0
    while i < len(s):
        if s[i] == "\\" and i + 1 < len(s):
            out.append({"n": "\n", '"': '"', "\\": "\\"}
                       .get(s[i + 1], "\\" + s[i + 1]))
            i += 2
        else:
            out.append(s[i])
            i += 1
    return "".join(out)


def test_prometheus_label_escaping_roundtrip():
    """Hostile names (backslash, quote, newline) in app/stream/query
    labels AND exemplar trace ids must render as a grammar-valid
    exposition whose unescaped label values round-trip exactly."""
    evil_app = 'A"pp\\Ev\nil'
    evil_stream = 'S"tr\\eam\n1'
    evil_trace = 't"race\\id\n9'
    rep = {"uptime_s": 1.0,
           "streams": {evil_stream: {
               "events": 5, "batches": 2, "seconds": 0.1, "p50_ms": 1.0,
               "p95_ms": 2.0, "p99_ms": 3.0,
               "buckets": {"0.001": 1, "+Inf": 2},
               "exemplars": {"0.001": [evil_trace, 0.0005, 123.0]}}},
           "queries": {'q"u\\ery\n': {"events": 5, "batches": 1,
                                      "seconds": 0.05}},
           "stages": {}}
    text = render_prometheus({evil_app: rep}, openmetrics=True)
    assert_valid_exposition(text)
    # round-trip one sample line's labels back through unescape
    line = next(ln for ln in text.splitlines()
                if ln.startswith("siddhi_tpu_events_total{"))
    labs = dict(re.findall(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:\\.|[^"\\])*)"',
                           line))
    assert _unescape(labs["app"]) == evil_app
    assert _unescape(labs["stream"]) == evil_stream
    ex_line = next(ln for ln in text.splitlines() if " # {" in ln)
    ex_tid = re.search(r'# \{trace_id="((?:\\.|[^"\\])*)"\}', ex_line)
    assert ex_tid and _unescape(ex_tid.group(1)) == evil_trace


def test_live_exposition_grammar_and_exemplars():
    """A real traced runtime's full exposition parses against the
    grammar in BOTH formats; the OpenMetrics form carries a trace-id
    exemplar on at least one bucket, the classic 0.0.4 form carries
    NONE (exemplar syntax is illegal there — a real Prometheus parser
    would reject the whole exposition)."""
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:name('ExpoApp')\n@app:trace('all')\n" + STREAM_DEF +
        "@info(name='q') from S[p > 10] select sym, p insert into Out;\n")
    rt.enable_stats(True)
    rt.start()
    h = rt.input_handler("S")
    for k in range(3):
        h.send_batch(_cols(16, seed=k, lo=8.0, hi=20.0),
                     np.arange(16) + 16 * k)
        rt.flush()
    classic = rt.stats.prometheus()
    text = rt.stats.prometheus(openmetrics=True)
    mgr.shutdown()
    assert_valid_exposition(classic)
    assert_valid_exposition(text)
    # classic format: no exemplars, no EOF terminator
    assert not any(" # {" in ln for ln in classic.splitlines())
    assert "# EOF" not in classic
    assert text.rstrip().endswith("# EOF")
    bucket_lines = [ln for ln in text.splitlines() if ln.startswith(
        "siddhi_tpu_stream_dispatch_latency_seconds_bucket")]
    assert bucket_lines
    assert any(" # {" in ln and "trace_id=" in ln for ln in bucket_lines)
    assert "siddhi_tpu_trace_traces_total" in text
    # histogram invariants: cumulative buckets, +Inf == _count
    inf_line = next(ln for ln in bucket_lines if 'le="+Inf"' in ln)
    count_line = next(ln for ln in text.splitlines() if ln.startswith(
        "siddhi_tpu_stream_dispatch_latency_seconds_count{"))
    assert inf_line.split(" ")[1] == count_line.rsplit(" ", 1)[1]


# ---------------------------------------------------------------------------
# service surface
# ---------------------------------------------------------------------------

def test_service_trace_endpoint():
    from siddhi_tpu.service import SiddhiService
    svc = SiddhiService(port=0, net=True).start()
    try:
        base = f"http://127.0.0.1:{svc.port}"
        app = ("@app:name('TraceSvc')\n@app:trace('all')\n" + STREAM_DEF +
               "@info(name='q') from S[p > 10] select sym, p "
               "insert into Out;\n")
        req = urllib.request.Request(f"{base}/siddhi/artifact/deploy",
                                     data=app.encode(), method="POST")
        urllib.request.urlopen(req).read()
        cli = TcpFrameClient(
            "127.0.0.1", svc.net_port, "S",
            TcpFrameClient.cols_of_schema(svc.runtimes["TraceSvc"]
                                          .schemas["S"]),
            app="TraceSvc")
        cli.send_batch(_cols(4, lo=11.0, hi=20.0), np.arange(4),
                       trace_id="svc-trace-1")
        cli.barrier(timeout=60)
        cli.close()
        obj = json.loads(urllib.request.urlopen(
            f"{base}/siddhi/artifact/trace?siddhiApp=TraceSvc").read())
        assert "traceEvents" in obj and "metadata" in obj
        assert obj["metadata"]["hostname"]
        assert any(ev.get("args", {}).get("trace") == "svc-trace-1"
                   for ev in obj["traceEvents"] if ev.get("ph") == "X")
        # unknown app 404s
        try:
            urllib.request.urlopen(
                f"{base}/siddhi/artifact/trace?siddhiApp=Nope")
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
        # content negotiation: an OpenMetrics scrape carries the
        # producer-stamped exemplar; the default (classic 0.0.4)
        # response must NOT (exemplar syntax is illegal there)
        req = urllib.request.Request(
            f"{base}/metrics",
            headers={"Accept": "application/openmetrics-text; "
                               "version=1.0.0"})
        with urllib.request.urlopen(req) as r:
            assert "openmetrics-text" in r.headers["Content-Type"]
            text = r.read().decode()
        assert 'trace_id="svc-trace-1"' in text
        assert_valid_exposition(text)
        with urllib.request.urlopen(f"{base}/metrics") as r:
            assert "version=0.0.4" in r.headers["Content-Type"]
            classic = r.read().decode()
        assert "trace_id=" not in classic
        assert_valid_exposition(classic)
    finally:
        svc.stop()


def test_tracer_reopens_on_restart_and_annotates_remote_parent():
    """(1) A shutdown()/start() cycle must re-arm the tracer — a closed
    tracer silently dropping every trigger after a restart would be the
    durability-silently-lost failure shape all over again.  (2) A wire
    TRACE frame's `span` field lands as the downstream root's
    `remote_parent` annotation (span ids are host-local)."""
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:trace('all', cooldown='0')\n"
        "@source(type='tcp', port='0')\n" + STREAM_DEF +
        "@info(name='q') from S[p > 0] select sym, p insert into Out;\n")
    rt.start()
    rt.shutdown()
    rt.start()
    try:
        assert rt.tracing.trigger("quarantine", "post-restart"), \
            "tracer stayed closed across shutdown()/start()"
        cli = TcpFrameClient("127.0.0.1", rt.sources[0].port, "S",
                             TcpFrameClient.cols_of_schema(rt.schemas["S"]))
        cli._send(fp.encode_trace("hop-1", 7))   # upstream head span 7
        cli.send_batch(_cols(4), np.arange(4))
        cli.barrier(timeout=60)
        cli.close()
        root = next(s for s in rt.tracing.traces()["hop-1"]
                    if s["name"] == "frame")
        assert root["parent"] == 0               # host-local root
        assert root["args"]["remote_parent"] == 7
    finally:
        rt.shutdown()
