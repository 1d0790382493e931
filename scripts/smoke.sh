#!/usr/bin/env bash
# CI smoke: the tier-1 suite plus a ~5-second end-to-end service check
# (deploy an app over REST, push events, assert /metrics exposes
# nonzero counters).  Exits nonzero on any failure.
#
# This is the pinned-CPU lane: it asserts behaviour and claims no device
# rate.  Several steps below keep two runtimes in two processes alive at
# once (the kill -9 recovery smoke's parent builds a reference runtime and
# then starts service children) — fine on the CPU, impossible on one chip,
# where a chip belongs to one process at a time.  On the chip the program
# runs through `python chip_smoke.py` and `bench.py --chaos`, whose parent
# stays JAX-free (README "Testing").
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
# CPU executables must not land in the in-checkout compile cache the chip
# machine would be offered (siddhi_tpu/__init__.py)
export JAX_ENABLE_COMPILATION_CACHE=false

echo "== compileall =="
# every module must at least parse/compile — a syntax error in a rarely
# imported module must not wait for a request to surface
python -m compileall -q siddhi_tpu

echo "== static analysis: self-lint =="
# the no-silent-demotion CI gate (docs/ANALYSIS.md): an except handler
# on a plan-lowering path that swallows without recording a Demotion
# (SL01), or an unguarded shared-counter mutation in a lock-owning
# class (SL02), fails the build here — exactly the two bug classes
# review rounds keep finding
python -m siddhi_tpu.analysis --self

echo "== static analysis: concurrency (--threads) =="
# the concurrency self-analysis gate (docs/ANALYSIS.md "Concurrency
# self-analysis"): SL03 lockset / inconsistent guard, SL04 lock-order
# inversion, SL05 blocking-call-under-lock, SL06 thread lifecycle over
# the engine's own source.  The baseline pins the justified-suppression
# inventory — a new `# lint: allow (...)` anywhere fails CI until the
# baseline is regenerated in the same commit (--write-baseline)
python -m siddhi_tpu.analysis --threads \
    --baseline scripts/threads_baseline.json

echo "== static analysis: samples corpus =="
# the analyzer over every samples/*.py app string: expected findings are
# PINNED (all info-severity conveniences in the samples); any new rule
# firing — or an expected one disappearing — fails CI
python -m siddhi_tpu.analysis \
    --expect SA07,SA07,SA07,SA07,SA12,SA13,SA13,SA13,SA14,SA15 \
    samples/simple_filter.py samples/time_window.py \
    samples/partitioned_pattern_tpu.py samples/net_serving.py \
    samples/durable_serving.py samples/replicated_failover.py \
    samples/aggregated_dashboard.py

echo "== tier-1 tests =="
python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider

echo "== lock-witness vs static graph =="
# run a fast serving-plane tier-1 subset with every engine lock
# witness-instrumented (utils/locks.py, SIDDHI_LOCK_CHECK=1): the
# ACTUAL acquisition orders the tests exhibit are recorded, then
# cross-checked against the static lock graph.  Any witnessed order
# the model contradicts or does not know fails CI — the SL04 deadlock
# verdicts are only as good as this agreement.  (A dynamic inversion
# additionally raises LockOrderError inside the test run itself.)
WITNESS_OUT="$(mktemp -u /tmp/siddhi_lock_witness.XXXXXX.json)"
SIDDHI_LOCK_CHECK=1 SIDDHI_LOCK_WITNESS_OUT="$WITNESS_OUT" \
    python -m pytest tests/test_net_admission.py tests/test_net_server.py \
    tests/test_wal.py tests/test_service.py tests/test_tracing.py \
    tests/test_replication.py \
    -q -m 'not slow' -p no:cacheprovider
python -m siddhi_tpu.analysis --threads --witness "$WITNESS_OUT"
rm -f "$WITNESS_OUT"

echo "== service /metrics smoke =="
python - <<'EOF'
import json
import sys
import time
import urllib.request

from siddhi_tpu.service import SiddhiService

svc = SiddhiService(port=0).start()
base = f"http://127.0.0.1:{svc.port}"
deadline = time.time() + 5.0
try:
    app = ("@app:name('Smoke')\n"
           "@app:trace('all')\n"       # every frame traced -> exemplars
           "define stream S (sym string, p double);\n"
           "@info(name='q') from S[p > 10] select sym, p insert into Out;\n")
    req = urllib.request.Request(f"{base}/siddhi/artifact/deploy",
                                 data=app.encode(), method="POST")
    assert json.loads(urllib.request.urlopen(req).read())["app"] == "Smoke"
    for i in range(20):
        req = urllib.request.Request(
            f"{base}/siddhi/artifact/event",
            data=json.dumps({"app": "Smoke", "stream": "S",
                             "data": [f"K{i % 4}", 9.0 + i]}).encode(),
            method="POST")
        urllib.request.urlopen(req).read()
    text = ""
    while time.time() < deadline:
        with urllib.request.urlopen(f"{base}/metrics") as r:
            ctype = r.headers["Content-Type"]
            text = r.read().decode()
        if 'siddhi_tpu_events_total{app="Smoke",stream="S"} 20' in text:
            break
        time.sleep(0.2)
    assert "version=0.0.4" in ctype, f"bad content type {ctype!r}"
    assert 'siddhi_tpu_events_total{app="Smoke",stream="S"} 20' in text, \
        "events_total never reached 20:\n" + text[:1500]
    assert "siddhi_tpu_query_latency_seconds" in text
    # classic 0.0.4 response: exemplar syntax is ILLEGAL here — a real
    # Prometheus text parser would reject the whole exposition
    assert " # {trace_id=" not in text
    for ln in text.splitlines():             # exposition parses
        if ln and not ln.startswith("#"):
            float("nan") if ln.rsplit(" ", 1)[1] == "NaN" \
                else float(ln.rsplit(" ", 1)[1])
    # the tracing plane's exemplars ride the Accept-negotiated
    # OpenMetrics form (docs/OBSERVABILITY.md): the dispatch-latency
    # histogram buckets must carry a trace id there
    req = urllib.request.Request(
        f"{base}/metrics",
        headers={"Accept": "application/openmetrics-text; version=1.0.0"})
    with urllib.request.urlopen(req) as r:
        assert "openmetrics-text" in r.headers["Content-Type"]
        om = r.read().decode()
    assert "siddhi_tpu_stream_dispatch_latency_seconds_bucket" in om
    assert any(" # {trace_id=" in ln for ln in om.splitlines()), \
        "no exemplar on the dispatch-latency histogram"
    assert om.rstrip().endswith("# EOF")
    print(f"OK: /metrics valid, nonzero counters; exemplars on the "
          f"OpenMetrics form ({len(text.splitlines())} lines)")
finally:
    svc.stop()
EOF

echo "== service frame-ingest smoke =="
# the front door end-to-end: start the service, deploy a pattern app,
# push ONE columnar frame over localhost TCP to the shared frame port,
# and assert the match arrived and /metrics shows the ingest gauges
python - <<'EOF'
import urllib.request

import numpy as np

from siddhi_tpu.net import TcpFrameClient
from siddhi_tpu.service import SiddhiService

svc = SiddhiService(port=0).start()
base = f"http://127.0.0.1:{svc.port}"
try:
    app = ("@app:name('NetSmoke')\n"
           "define stream S (sym string, p double);\n"
           "@info(name='q') from every e1=S -> e2=S[p > e1.p] "
           "select e1.sym as s1, e2.p as p2 insert into Out;\n")
    req = urllib.request.Request(f"{base}/siddhi/artifact/deploy",
                                 data=app.encode(), method="POST")
    urllib.request.urlopen(req).read()
    rt = svc.runtimes["NetSmoke"]
    matches = []
    rt.add_batch_callback("Out", lambda b: matches.extend(
        map(tuple, b.rows(rt.strings))))
    cli = TcpFrameClient("127.0.0.1", svc.net_port, "S",
                         TcpFrameClient.cols_of_schema(rt.schemas["S"]),
                         app="NetSmoke")
    cli.send_batch({"sym": np.array(["A", "B", "C", "D"]),
                    "p": np.array([10.0, 12.0, 9.0, 11.0])},
                   np.arange(4, dtype=np.int64))
    cli.barrier(timeout=30)
    cli.close()
    assert matches, "no pattern match arrived over the frame plane"
    with urllib.request.urlopen(f"{base}/metrics") as r:
        text = r.read().decode()
    for series in ("siddhi_tpu_net_events_total",
                   "siddhi_tpu_net_admitted_events_total"):
        line = next((ln for ln in text.splitlines()
                     if ln.startswith(series + "{")), None)
        assert line is not None and line.rstrip().endswith(" 4"), \
            f"{series} missing or != 4: {line!r}"
    print(f"OK: {len(matches)} matches via frame plane, ingest gauges live")
finally:
    svc.stop()
EOF

echo "== frame tracing smoke =="
# the causal tracing plane end-to-end (docs/OBSERVABILITY.md "Frame
# tracing"): deploy over REST, send one TCP columnar frame with a
# PRODUCER-stamped trace id, then assert GET /siddhi/artifact/trace
# serves a Chrome trace_event object containing that trace.  The JSON
# is linted on disk with `python -m json.tool` + a required-key check.
TRACE_JSON="$(mktemp -u /tmp/siddhi_trace_smoke.XXXXXX.json)"
python - "$TRACE_JSON" <<'EOF'
import json
import sys
import urllib.request

import numpy as np

from siddhi_tpu.net import TcpFrameClient
from siddhi_tpu.service import SiddhiService

out_path = sys.argv[1]
svc = SiddhiService(port=0).start()
base = f"http://127.0.0.1:{svc.port}"
try:
    app = ("@app:name('TraceSmoke')\n"
           "@app:trace('all')\n"
           "define stream S (sym string, p double);\n"
           "@info(name='q') from S[p > 10] select sym, p insert into Out;\n")
    req = urllib.request.Request(f"{base}/siddhi/artifact/deploy",
                                 data=app.encode(), method="POST")
    urllib.request.urlopen(req).read()
    rt = svc.runtimes["TraceSmoke"]
    cli = TcpFrameClient("127.0.0.1", svc.net_port, "S",
                         TcpFrameClient.cols_of_schema(rt.schemas["S"]),
                         app="TraceSmoke")
    cli.send_batch({"sym": np.array(["A", "B", "C", "D"]),
                    "p": np.array([11.0, 12.0, 13.0, 14.0])},
                   np.arange(4, dtype=np.int64),
                   trace_id="smoke-trace-1")
    cli.barrier(timeout=30)
    cli.close()
    with urllib.request.urlopen(
            f"{base}/siddhi/artifact/trace?siddhiApp=TraceSmoke") as r:
        blob = r.read()
    with open(out_path, "wb") as f:
        f.write(blob)
    obj = json.loads(blob)
    spans = [ev for ev in obj["traceEvents"] if ev.get("ph") == "X"
             and ev.get("args", {}).get("trace") == "smoke-trace-1"]
    names = {ev["name"] for ev in spans}
    for want in ("frame", "admit", "freeze", "dispatch"):
        assert want in names, (want, sorted(names))
    print(f"OK: producer trace id served with {len(spans)} spans "
          f"({sorted(names)})")
finally:
    svc.stop()
EOF
# Chrome trace_event schema lint: valid JSON + the required keys
python -m json.tool "$TRACE_JSON" > /dev/null
python - "$TRACE_JSON" <<'EOF'
import json
import sys
obj = json.load(open(sys.argv[1]))
assert isinstance(obj.get("traceEvents"), list) and obj["traceEvents"]
md = obj.get("metadata")
assert isinstance(md, dict) and md.get("hostname"), md
for ev in obj["traceEvents"]:
    assert ev.get("ph") in ("X", "M") and "name" in ev and "pid" in ev, ev
print("OK: Chrome trace JSON schema valid "
      f"({len(obj['traceEvents'])} events, host {md['hostname']})")
EOF
rm -f "$TRACE_JSON"

echo "== phase-profiler smoke =="
# the device-time attribution plane end-to-end (docs/OBSERVABILITY.md
# "Device-time profiling"): deploy over REST with the profiler in
# 'all' mode, push TCP frames, then assert GET /siddhi/artifact/profile
# serves per-plan phase shares that sum to 1.0 and that /metrics
# exposes the siddhi_tpu_phase_seconds_total series.
python - <<'EOF'
import json
import urllib.request

import numpy as np

from siddhi_tpu.net import TcpFrameClient
from siddhi_tpu.service import SiddhiService

svc = SiddhiService(port=0).start()
base = f"http://127.0.0.1:{svc.port}"
try:
    app = ("@app:name('ProfSmoke')\n"
           "@app:profile('all')\n"
           "define stream S (sym string, p double);\n"
           "@info(name='q') from every e1=S[p > 10] -> e2=S[p > e1.p] "
           "select e1.sym as s1, e2.p as p2 insert into Out;\n")
    req = urllib.request.Request(f"{base}/siddhi/artifact/deploy",
                                 data=app.encode(), method="POST")
    urllib.request.urlopen(req).read()
    rt = svc.runtimes["ProfSmoke"]
    cli = TcpFrameClient("127.0.0.1", svc.net_port, "S",
                         TcpFrameClient.cols_of_schema(rt.schemas["S"]),
                         app="ProfSmoke")
    for k in range(4):
        cli.send_batch({"sym": np.array(["A", "B", "C", "D"]),
                        "p": np.array([11.0, 12.0, 13.0, 14.0])},
                       np.arange(4 * k, 4 * k + 4, dtype=np.int64))
    cli.barrier(timeout=30)
    cli.close()
    with urllib.request.urlopen(
            f"{base}/siddhi/artifact/profile?siddhiApp=ProfSmoke") as r:
        prof = json.loads(r.read())["apps"]["ProfSmoke"]
    assert prof["mode"] == "all", prof.get("mode")
    assert prof["plans"], "no plan accumulated any attribution"
    for name, pv in prof["plans"].items():
        s = sum(pv["shares"].values())
        assert abs(s - 1.0) < 5e-4, (name, pv["shares"])
    agg = prof["aggregate"]
    assert agg["rounds"] > 0 and agg["coverage"] >= 0.9, agg
    with urllib.request.urlopen(f"{base}/metrics") as r:
        text = r.read().decode()
    assert "siddhi_tpu_phase_seconds_total{" in text
    assert "siddhi_tpu_host_dispatch_share{" in text
    print(f"OK: profile plane live ({len(prof['plans'])} plans, "
          f"coverage {agg['coverage']}, "
          f"host share {agg['host_dispatch_share']})")
finally:
    svc.stop()
EOF

echo "== kill -9 recovery smoke =="
# exactly-once durable serving end-to-end (docs/RELIABILITY.md): start a
# service subprocess with @app:durability('batch'), feed N TCP frames
# (ACK'd = durable), SIGKILL the whole service, restart it, redeploy —
# recover-on-redeploy must yield match counts identical to an
# uninterrupted in-process run.  Exits nonzero on any drift.
python - <<'EOF'
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.persistence import FileSystemPersistenceStore
from siddhi_tpu.net import TcpFrameClient

APP = """@app:name('KillSmoke')
@app:durability('batch')
define stream S (sym string, p double);
define table M (s1 string, p2 double);
@info(name='q') from every e1=S[p > 100] -> e2=S[p > e1.p] within 1 sec
select e1.sym as s1, e2.p as p2 insert into M;
"""

CHILD = """
import sys, threading
from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.persistence import FileSystemPersistenceStore
from siddhi_tpu.service import SiddhiService
mgr = SiddhiManager()
mgr.set_persistence_store(FileSystemPersistenceStore(sys.argv[1]))
svc = SiddhiService(port=0, manager=mgr).start()
print(f"READY {svc.port} {svc.net_port}", flush=True)
threading.Event().wait()
"""

rng = np.random.default_rng(11)
ts0 = 1_700_000_000_000
frames = [({"sym": np.array([f"K{i}" for i in rng.integers(0, 4, 256)]),
            "p": np.round(rng.uniform(90, 130, 256), 2)},
           ts0 + np.arange(k * 256, (k + 1) * 256, dtype=np.int64))
          for k in range(6)]

# uninterrupted reference
work = tempfile.mkdtemp(prefix="siddhi_kill9_smoke_")
mgr = SiddhiManager()
mgr.set_persistence_store(FileSystemPersistenceStore(work + "/ref"))
rt = mgr.create_app_runtime(APP)
rt.start()
h = rt.input_handler("S")
for cols, ts in frames:
    h.send_batch(cols, ts)
rt.flush()
want = len(rt.tables["M"].all_rows())
mgr.shutdown()
assert want > 0


def start_service():
    p = subprocess.Popen([sys.executable, "-c", CHILD, work + "/svc"],
                         stdout=subprocess.PIPE, text=True)
    line = p.stdout.readline().split()
    assert line and line[0] == "READY", line
    return p, int(line[1]), int(line[2])


def deploy(port):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/siddhi/artifact/deploy",
        data=APP.encode(), method="POST")
    return json.loads(urllib.request.urlopen(req).read())


def matches(port):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/siddhi/artifact/query",
        data=json.dumps({"app": "KillSmoke",
                         "query": "from M select s1"}).encode(),
        method="POST")
    return len(json.loads(urllib.request.urlopen(req).read())["rows"])

try:
    child, port, net_port = start_service()
    deploy(port)
    cli = TcpFrameClient("127.0.0.1", net_port, "S",
                         [("sym", "string"), ("p", "double")],
                         app="KillSmoke")
    for cols, ts in frames:
        cli.send_batch(cols, ts)
    cli.barrier(timeout=60)        # durable ACK: frames are in the WAL
    os.kill(child.pid, signal.SIGKILL)
    child.wait(timeout=10)
    try:
        cli.close()
    except OSError:
        pass

    child2, port2, _ = start_service()
    deploy(port2)                  # recover-on-redeploy replays the WAL
    got = matches(port2)
    info = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port2}/siddhi/artifact/snapshot"
        f"?siddhiApp=KillSmoke").read())
    rec = info["recovery"]
    assert got == want, f"match drift after kill -9: {got} != {want}"
    assert rec["replayed_frames"] == len(frames), rec
    os.kill(child2.pid, signal.SIGKILL)
    print(f"OK: kill -9 recovery exact ({got} matches, "
          f"{rec['replayed_frames']} frames replayed in "
          f"{rec['recovery_s']}s)")
finally:
    shutil.rmtree(work, ignore_errors=True)
EOF

echo "== HA failover smoke =="
# machine-loss failover end-to-end (docs/RELIABILITY.md "High
# availability"): two service subprocesses — a durable primary and a
# hot standby tailing its WAL over the frame protocol — feed the
# primary N ACK'd frames, wait for the standby's applied watermark to
# converge, SIGKILL the primary, POST /siddhi/artifact/promote to the
# standby, and assert the promoted node serves match counts identical
# to an uninterrupted in-process run.  Exits nonzero on any drift.
python - <<'EOF'
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.persistence import IncrementalFileSystemPersistenceStore
from siddhi_tpu.net import TcpFrameClient

APP = """@app:name('HASmoke')
@app:durability('batch', dir='{wal}', segment.bytes='4096')
{extra}define stream S (sym string, p double);
define table M (s1 string, p2 double);
@info(name='q') from every e1=S[p > 100] -> e2=S[p > e1.p] within 1 sec
select e1.sym as s1, e2.p as p2 insert into M;
"""

CHILD = """
import sys, threading
from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.persistence import IncrementalFileSystemPersistenceStore
from siddhi_tpu.service import SiddhiService
mgr = SiddhiManager()
mgr.set_persistence_store(IncrementalFileSystemPersistenceStore(sys.argv[1]))
svc = SiddhiService(port=0, manager=mgr).start()
print(f"READY {svc.port} {svc.net_port}", flush=True)
threading.Event().wait()
"""

rng = np.random.default_rng(13)
ts0 = 1_700_000_000_000
frames = [({"sym": np.array([f"K{i}" for i in rng.integers(0, 4, 256)]),
            "p": np.round(rng.uniform(90, 130, 256), 2)},
           ts0 + np.arange(k * 256, (k + 1) * 256, dtype=np.int64))
          for k in range(6)]

work = tempfile.mkdtemp(prefix="siddhi_ha_smoke_")

# uninterrupted in-process reference
mgr = SiddhiManager()
mgr.set_persistence_store(
    IncrementalFileSystemPersistenceStore(work + "/ref_store"))
rt = mgr.create_app_runtime(APP.format(wal=work + "/ref_wal", extra=""))
rt.start()
h = rt.input_handler("S")
for cols, ts in frames:
    h.send_batch(cols, ts)
rt.flush()
want = len(rt.tables["M"].all_rows())
mgr.shutdown()
assert want > 0


def start_service(store):
    p = subprocess.Popen([sys.executable, "-c", CHILD, store],
                         stdout=subprocess.PIPE, text=True)
    line = p.stdout.readline().split()
    assert line and line[0] == "READY", line
    return p, int(line[1]), int(line[2])


def post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=body if isinstance(body, bytes) else json.dumps(body).encode(),
        method="POST")
    return json.loads(urllib.request.urlopen(req).read())


def repl_info(port):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/siddhi/artifact/snapshot"
            f"?siddhiApp=HASmoke") as r:
        return json.loads(r.read())


try:
    primary, p_port, p_net = start_service(work + "/p_store")
    post(p_port, "/siddhi/artifact/deploy",
         APP.format(wal=work + "/p_wal", extra="").encode())
    standby, s_port, s_net = start_service(work + "/s_store")
    post(s_port, "/siddhi/artifact/deploy",
         APP.format(wal=work + "/s_wal",
                    extra="@app:replication('async', role='standby', "
                          f"peer='127.0.0.1:{p_net}')\n").encode())

    cli = TcpFrameClient("127.0.0.1", p_net, "S",
                         [("sym", "string"), ("p", "double")],
                         app="HASmoke")
    for cols, ts in frames:
        cli.send_batch(cols, ts)
    cli.barrier(timeout=60)        # durable ACK: frames are in the WAL

    # hot standby converges (async: poll its applied watermark)
    deadline = time.time() + 20
    while time.time() < deadline:
        repl = repl_info(s_port).get("replication", {})
        if repl.get("applied_watermark", {}).get("S", 0) >= len(frames):
            break
        time.sleep(0.1)
    else:
        raise AssertionError(f"standby never converged: {repl}")

    os.kill(primary.pid, signal.SIGKILL)   # machine loss
    primary.wait(timeout=10)
    try:
        cli.close()
    except OSError:
        pass

    rep = post(s_port, "/siddhi/artifact/promote", {"app": "HASmoke"})
    assert rep["promoted"] and rep["generation"] >= 1, rep
    assert rep["recovery"]["replayed_frames"] == len(frames), rep
    got = len(post(s_port, "/siddhi/artifact/query",
                   {"app": "HASmoke",
                    "query": "from M select s1"})["rows"])
    assert got == want, f"match drift after failover: {got} != {want}"
    info = repl_info(s_port)
    assert info["replication"]["role"] == "primary", info["replication"]
    assert info["replication"]["promoted"] is True
    os.kill(standby.pid, signal.SIGKILL)
    print(f"OK: failover exact ({got} matches on the promoted standby, "
          f"{rep['recovery']['replayed_frames']} frames replayed, "
          f"promote {rep['promote_s']}s)")
finally:
    for p in ("primary", "standby"):
        proc = locals().get(p)
        if proc is not None and proc.poll() is None:
            proc.kill()
    shutil.rmtree(work, ignore_errors=True)
EOF

echo "== net serving-plane smoke =="
# bench.py --net --smoke: loopback columnar wire ingest (TCP + shm
# ring) on the config-3 pattern workload, asserted byte-identical to
# in-process send_batch; per-event REST measured as the baseline the
# frame protocol must beat >=5x; paced 2x-overload with
# shed.policy='shed' asserting p99 <= 2x unloaded, zero unaccounted
# loss (every shed event in the ErrorStore) and full replay
python bench.py --net --smoke

echo "== queryable-state smoke =="
# the state plane end-to-end: deploy a `define aggregation` app, ingest
# over the frame plane, then read the SAME rollup three ways — wire
# QUERY frame, REST store query, in-process runtime.query() — and
# assert all three agree byte-for-byte and /metrics carries the
# siddhi_tpu_agg_* series
python - <<'EOF'
import json
import urllib.request

import numpy as np

from siddhi_tpu.net import TcpFrameClient
from siddhi_tpu.service import SiddhiService

svc = SiddhiService(port=0).start()
base = f"http://127.0.0.1:{svc.port}"
try:
    app = ("@app:name('AggSmoke')\n"
           "define stream T (sym string, p double, ts long);\n"
           "define aggregation Roll\n"
           "from T select sym, sum(p) as total, count() as n\n"
           "group by sym aggregate by ts every sec, min;\n")
    req = urllib.request.Request(f"{base}/siddhi/artifact/deploy",
                                 data=app.encode(), method="POST")
    urllib.request.urlopen(req).read()
    rt = svc.runtimes["AggSmoke"]
    ts0 = 1_700_000_000_000
    cli = TcpFrameClient("127.0.0.1", svc.net_port, "T",
                         TcpFrameClient.cols_of_schema(rt.schemas["T"]),
                         app="AggSmoke")
    ts = ts0 + np.arange(256, dtype=np.int64) * 20
    cli.send_batch({"sym": np.array([f"S{i % 5}" for i in range(256)]),
                    "p": np.linspace(1.0, 64.0, 256),
                    "ts": ts}, ts)
    cli.barrier(timeout=30)
    q = (f"from Roll within {ts0}L, {ts0 + 60_000}L per 'sec' "
         f"select sym, total, n")
    assert rt.explain()["aggregations"]["Roll"]["path"] \
        == "device-resident"
    inproc = sorted(rt.query(q))
    wire = sorted(cli.query(q))
    cli.close()
    req = urllib.request.Request(
        f"{base}/siddhi/artifact/query",
        data=json.dumps({"app": "AggSmoke", "query": q}).encode(),
        method="POST")
    with urllib.request.urlopen(req) as r:
        rest = sorted((t, tuple(row)) for t, row in
                      json.loads(r.read())["rows"])
    assert len(inproc) > 0 and wire == inproc and rest == inproc, \
        (len(inproc), len(wire), len(rest))
    with urllib.request.urlopen(f"{base}/metrics") as r:
        text = r.read().decode()
    for series in ("siddhi_tpu_agg_groups", "siddhi_tpu_agg_buckets",
                   "siddhi_tpu_agg_store_queries_total"):
        assert any(ln.startswith(series) for ln in text.splitlines()), \
            f"{series} missing from /metrics"
    print(f"OK: {len(inproc)} rollup rows identical over wire QUERY, "
          f"REST, and in-process; agg series live")
finally:
    svc.stop()
EOF

echo "== queryable-state workload matrix smoke =="
# bench.py --matrix --smoke: shrunk DEBS-style cells (rollup cardinality
# sweep, mixed query/ingest, concurrent wire store queries), each cell
# device-vs-host parity-checked; last line must parse as JSON with
# per-cell eps + store-query p99
python bench.py --matrix --smoke | tee /tmp/_matrix_smoke.out
python - <<'EOF'
import json
d = json.loads(open("/tmp/_matrix_smoke.out")
               .read().strip().splitlines()[-1])
assert d["metric"] == "queryable_state_matrix" and d["value"] == 1, d
assert all(c.get("parity") for c in d["cells"].values()), d["cells"]
print("OK: matrix cells", ", ".join(
    f"{k}={c['eps']} eps" for k, c in d["cells"].items()))
EOF

echo "== seeded chaos smoke =="
# bench.py --chaos: injected dispatch + sink faults under a fixed seed;
# asserts zero event loss and full recovery (ladder halving, interpreter
# quarantine with byte-identical matches, sink retry/ErrorStore replay).
# Exits nonzero if any recovery path loses or duplicates an event.
python bench.py --chaos --seed 7

echo "== plan-family parity smoke =="
# bench.py --family-smoke: one eligible pattern per NFA plan family
# (seq / chunk / scan / dfa), plus the ISSUE-13 count-quantifier and
# partitioned-lanes cells, each run differentially against the host
# interpreter — a lowering regression in any family fails fast here
# instead of surfacing as wrong matches in production
python bench.py --family-smoke

echo "== pipelined-vs-unpipelined bench smoke =="
# bench.py --smoke: short pipelined-vs-unpipelined run over the
# multi-plan overlap config; asserts identical match counts and prints
# the eps delta + overlap_ratio.  The LAST stdout line must round-trip
# through json.loads — the bench driver parses exactly that line, and
# an unparseable tail is the BENCH "parsed": null failure shape
python bench.py --smoke | tee /tmp/_bench_smoke.out
python - <<'EOF'
import json
line = open("/tmp/_bench_smoke.out").read().strip().splitlines()[-1]
parsed = json.loads(line)          # raises -> smoke fails
assert isinstance(parsed, dict) and "metric" in parsed, parsed
print("OK: bench --smoke last line parses:", parsed["metric"])
EOF

echo "smoke: PASS"
