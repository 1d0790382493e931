"""REST control plane + columnar serving data plane.

Reference: modules/siddhi-service (JAX-RS/MSF4J microservice,
`POST /siddhi/artifact/deploy`, `GET /siddhi/artifact/undeploy`,
src/gen/.../api/SiddhiApi.java:31-63).

The HTTP surface is the CONTROL plane (deploy/undeploy/query/stats/
errors/metrics) plus a convenience JSON event endpoint; production
traffic enters through the DATA plane — a NetServer (siddhi_tpu/net)
speaking the columnar frame protocol over TCP and WebSocket on its own
port (`service.net_port`), feeding every deployed app with zero
per-event Python and per-stream admission control (docs/SERVING.md).

Endpoints (JSON unless noted):
  POST /siddhi/artifact/deploy      body = SiddhiQL app text (plain)
  GET  /siddhi/artifact/undeploy?siddhiApp=<name>
  GET  /siddhi/artifact/apps
  POST /siddhi/artifact/event       {"app": ..., "stream": ..., "data": [...],
                                     "timestamp": optional ms}
                                    `data` may be ONE row or a LIST of
                                    rows (batch form, one shared
                                    optional timestamp), or pass
                                    "events": [{"data": [...],
                                    "timestamp": ...}, ...] — all forms
                                    share one validation path; malformed
                                    bodies get a 400 JSON error.  The
                                    batch rides the stream's admission
                                    controller (same quotas/shed
                                    accounting as the frame plane): a
                                    rate-limited stream sheds REST
                                    traffic into the ErrorStore with a
                                    429, or parks it with a 202 under
                                    shed.policy='oldest'
  POST /siddhi/artifact/snapshot    {"app": ..., "incremental": bool?}
                                    persist a revision NOW; returns its
                                    structured descriptor — revision id +
                                    per-stream durable WAL watermark
                                    (persistence.Revision.to_dict())
  GET  /siddhi/artifact/snapshot?siddhiApp=<name>
                                    durability state: sync policy, last
                                    revision descriptor, WAL gauges, and
                                    the last crash-recovery report
  POST /siddhi/artifact/query       {"app": ..., "query": "from T select ..."}
  GET  /siddhi/artifact/stats?siddhiApp=<name>
  GET  /siddhi/artifact/explain?siddhiApp=<name>
                                    the EXPLAIN plane (docs/ANALYSIS.md):
                                    rt.explain() verbatim — per-query
                                    placement (device vs interpreter),
                                    chosen plan family, geometry
                                    provenance, and the full Demotion
                                    reason chain for every rejected
                                    alternative
  GET  /metrics[?siddhiApp=<name>]  Prometheus text exposition (0.0.4) over
                                    every deployed app (or just <name>);
                                    the per-stream dispatch-latency
                                    histogram buckets carry OpenMetrics
                                    trace-id exemplars
  GET  /siddhi/artifact/trace[?siddhiApp=<name>]
                                    the frame-tracing plane
                                    (docs/OBSERVABILITY.md "Frame
                                    tracing"): Chrome trace_event JSON
                                    ({"traceEvents": [...], "metadata":
                                    {hostname, apps, dumps}}) of the
                                    live span ring — load in
                                    chrome://tracing / ui.perfetto.dev;
                                    `metadata.dumps` lists trigger-
                                    promoted retained dumps
  GET  /siddhi/artifact/profile[?siddhiApp=<name>&window=<n>]
                                    the device-time attribution plane
                                    (docs/OBSERVABILITY.md "Device-time
                                    profiling"): per-plan phase shares,
                                    host-dispatch share, windowed ring
                                    (last <n> snapshots)
  GET  /siddhi/net                  data-plane descriptor: frame port +
                                    per-stream admission/transport gauges
  GET  /siddhi/errors?siddhiApp=<name>[&stream=<id>]
                                    list the app's ErrorStore entries
                                    (@OnError(action='store') captures,
                                    exhausted sink publishes, net sheds)
  POST /siddhi/errors               {"app": ..., "action": "replay"|
                                     "discard", "ids": optional [int]}
                                    replay captured events/payloads through
                                    the live runtime, or drop them

Deployed runtimes run with statistics ENABLED (a served engine is meant
to be scraped; one clock read per micro-batch) unless the app itself
says `@app:statistics('false')`.

Run:  python -m siddhi_tpu.service [port]     (or SiddhiService(port).start())
"""
from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

from . import SiddhiManager
from .core.telemetry import render_prometheus
from .query import ast as qast
from .utils.locks import new_lock

PROM_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"
# negotiated via the Accept header: exemplar syntax is only legal in
# OpenMetrics — a classic 0.0.4 parser rejects a line carrying one
OPENMETRICS_CONTENT_TYPE = \
    "application/openmetrics-text; version=1.0.0; charset=utf-8"


class _ControlServer(ThreadingHTTPServer):
    """Handler threads are daemons AND tracked, so `stop()` can join
    them with a bounded timeout — test runs and bench teardown never
    hang on a stuck keep-alive connection."""

    daemon_threads = True
    block_on_close = False      # stdlib would join unbounded; we bound it

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self._handler_threads: list = []
        self._threads_lock = new_lock("_ControlServer._threads_lock")

    def process_request(self, request, client_address):
        t = threading.Thread(target=self.process_request_thread,
                             args=(request, client_address),
                             name="siddhi-http", daemon=True)
        with self._threads_lock:
            self._handler_threads = [th for th in self._handler_threads
                                     if th.is_alive()] + [t]
        t.start()

    def join_handlers(self, timeout: float = 5.0) -> None:
        deadline = time.monotonic() + timeout
        with self._threads_lock:
            threads = list(self._handler_threads)
            self._handler_threads = []
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))


class SiddhiService:
    def __init__(self, port: int = 0, manager: Optional[SiddhiManager] = None,
                 net: bool = True, net_port: int = 0):
        self.manager = manager or SiddhiManager()
        self.runtimes: dict = {}
        self._stopping = False          # unblocks 'block'-policy REST waits
        # serializes deploy/undeploy/stop: the control server handles
        # requests on concurrent threads, and two same-name deploys
        # racing each other used to BOTH start a runtime — the loser
        # leaked alive (scheduler thread and all), never retired, never
        # shut down.  Ops are rare; correctness beats parallel deploys.
        self._ops_lock = new_lock("SiddhiService._ops_lock")
        # ErrorStores of undeployed apps: frames admitted by the data
        # plane before an undeploy land here (never dropped), and stay
        # inspectable until the name is redeployed
        self.retired_errors: dict = {}
        # app name -> static-analysis findings (dicts) from deploy time;
        # the deploy response carries them (docs/ANALYSIS.md)
        self.diagnostics: dict = {}
        service = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):           # quiet
                pass

            def _reply(self, code: int, body: dict) -> None:
                blob = json.dumps(body).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(blob)))
                self.end_headers()
                self.wfile.write(blob)

            def _reply_text(self, code: int, text: str,
                            ctype: str = PROM_CONTENT_TYPE) -> None:
                blob = text.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(blob)))
                self.end_headers()
                self.wfile.write(blob)

            def _body(self) -> bytes:
                n = int(self.headers.get("Content-Length", 0))
                return self.rfile.read(n)

            def do_POST(self):
                path = urlparse(self.path).path
                try:
                    if path == "/siddhi/artifact/deploy":
                        name = service.deploy(self._body().decode())
                        self._reply(200, {
                            "status": "deployed", "app": name,
                            # static-analysis findings for the deployed
                            # app (docs/ANALYSIS.md) — under
                            # @app:strictAnalysis a warn/error finding
                            # fails the deploy instead (400 below)
                            "diagnostics": service.diagnostics.get(name,
                                                                   [])})
                    elif path == "/siddhi/artifact/event":
                        body = self._body()
                        try:
                            req = json.loads(body)
                        except ValueError as e:
                            raise ValueError(f"body is not JSON: {e}") \
                                from None
                        code, out = service.send_events(req,
                                                        nbytes=len(body))
                        self._reply(code, out)
                    elif path == "/siddhi/artifact/snapshot":
                        req = json.loads(self._body())
                        app = req.get("app")
                        if app not in service.runtimes:
                            self._reply(404, {"error":
                                              f"no deployed app {app!r}"})
                        else:
                            self._reply(200, service.snapshot_action(
                                app, bool(req.get("incremental"))))
                    elif path == "/siddhi/artifact/promote":
                        req = json.loads(self._body() or b"{}")
                        app = req.get("app")
                        if app not in service.runtimes:
                            self._reply(404, {"error":
                                              f"no deployed app {app!r}"})
                        else:
                            self._reply(200, service.promote(app))
                    elif path == "/siddhi/artifact/query":
                        req = json.loads(self._body())
                        rows = service.store_query(req["app"], req["query"])
                        self._reply(200, {"rows": rows})
                    elif path == "/siddhi/errors":
                        req = json.loads(self._body())
                        app = req.get("app")
                        if (app not in service.runtimes
                                and app not in service.retired_errors):
                            self._reply(404, {"error":
                                              f"no deployed app {app!r}"})
                        else:
                            self._reply(200, service.errors_action(
                                app, req.get("action", "replay"),
                                req.get("ids")))
                    else:
                        self._reply(404, {"error": f"no route {path}"})
                except Exception as e:
                    # EVERY failure is a 400 JSON error — a malformed
                    # body must never surface as a 500 stack trace.  A
                    # strict-analysis rejection additionally ships the
                    # structured findings so the caller sees rule ids,
                    # not just prose
                    body = {"error": f"{type(e).__name__}: {e}"}
                    findings = getattr(e, "findings", None)
                    if findings is not None:
                        body["diagnostics"] = [f.to_dict()
                                               for f in findings]
                    self._reply(400, body)

            def do_GET(self):
                u = urlparse(self.path)
                q = parse_qs(u.query)
                try:
                    if u.path == "/siddhi/artifact/undeploy":
                        app = q.get("siddhiApp", [None])[0]
                        service.undeploy(app)
                        self._reply(200, {"status": "undeployed", "app": app})
                    elif u.path == "/siddhi/artifact/apps":
                        self._reply(200, {"apps": sorted(service.runtimes)})
                    elif u.path == "/siddhi/artifact/stats":
                        app = q.get("siddhiApp", [None])[0]
                        if app not in service.runtimes:
                            self._reply(404, {"error":
                                              f"no deployed app {app!r}"})
                        else:
                            self._reply(200, service.stats(app))
                    elif u.path == "/siddhi/artifact/explain":
                        app = q.get("siddhiApp", [None])[0]
                        if app not in service.runtimes:
                            self._reply(404, {"error":
                                              f"no deployed app {app!r}"})
                        else:
                            # rt.explain() VERBATIM: the test suite holds
                            # this body byte-for-byte equal to it
                            self._reply(200, service.explain(app))
                    elif u.path == "/siddhi/artifact/snapshot":
                        app = q.get("siddhiApp", [None])[0]
                        if app not in service.runtimes:
                            self._reply(404, {"error":
                                              f"no deployed app {app!r}"})
                        else:
                            self._reply(200, service.snapshot_info(app))
                    elif u.path == "/siddhi/errors":
                        app = q.get("siddhiApp", [None])[0]
                        if (app not in service.runtimes
                                and app not in service.retired_errors):
                            self._reply(404, {"error":
                                              f"no deployed app {app!r}"})
                        else:
                            self._reply(200, service.errors(
                                app, q.get("stream", [None])[0]))
                    elif u.path == "/siddhi/artifact/trace":
                        app = q.get("siddhiApp", [None])[0]
                        if app is not None and app not in service.runtimes:
                            self._reply(404, {"error":
                                              f"no deployed app {app!r}"})
                        else:
                            self._reply(200, service.trace(app))
                    elif u.path == "/siddhi/artifact/profile":
                        app = q.get("siddhiApp", [None])[0]
                        if app is not None and app not in service.runtimes:
                            self._reply(404, {"error":
                                              f"no deployed app {app!r}"})
                        else:
                            w = q.get("window", [None])[0]
                            self._reply(200, service.profile(
                                app, window=None if w is None else int(w)))
                    elif u.path == "/siddhi/net":
                        self._reply(200, service.net_info())
                    elif u.path == "/metrics":
                        app = q.get("siddhiApp", [None])[0]
                        if app is not None and app not in service.runtimes:
                            self._reply(404, {"error":
                                              f"no deployed app {app!r}"})
                        else:
                            # content negotiation: Prometheus asks for
                            # OpenMetrics by default and gets the
                            # exemplar-carrying form; anything else gets
                            # classic 0.0.4 (exemplars stripped — they
                            # are illegal in that format)
                            om = "application/openmetrics-text" in \
                                (self.headers.get("Accept") or "")
                            self._reply_text(
                                200, service.metrics(app, openmetrics=om),
                                ctype=OPENMETRICS_CONTENT_TYPE if om
                                else PROM_CONTENT_TYPE)
                    else:
                        self._reply(404, {"error": f"no route {u.path}"})
                except Exception as e:
                    self._reply(400, {"error": f"{type(e).__name__}: {e}"})

        self.httpd = _ControlServer(("127.0.0.1", port), Handler)
        self.port = self.httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None
        # the data plane: one shared frame server over every deployed
        # app; admission controllers are per (app, stream) and shared
        # with any @source(type='tcp'|'shm') the app itself declares
        self.net = None
        self.net_port = None
        if net:
            from .net.server import NetServer
            self.net = NetServer(self._net_resolve, port=net_port,
                                 name="siddhi-service-net",
                                 repl_resolve=self._repl_resolve,
                                 query_resolve=self._query_resolve)
            self.net_port = self.net.port

    # -- data plane -------------------------------------------------------

    def _net_resolve(self, app: Optional[str], stream: str):
        rt = self.runtimes.get(app or "")
        if rt is None:
            raise KeyError(f"no deployed app {app!r}")
        if rt.is_standby():
            # a replica serves nothing: producers must talk to the
            # primary (or promote this node first) — rejecting at HELLO
            # keeps their retransmit buffers intact
            raise KeyError(
                f"app {app!r} is a standby replica — promote it or "
                f"send to the primary")
        ctrl = rt.admission.get(stream)
        if ctrl is None:
            if stream not in rt.schemas:
                raise KeyError(f"app {app!r} has no stream {stream!r}")
            from .net.admission import controller_from_options
            # default controller: unlimited rate, pure accounting —
            # declare @source(rate.limit=..., shed.policy=...) on the
            # stream to arm real limits (the SAME controller then
            # governs both the app's own port and this front door).
            # setdefault: concurrent HELLOs race this insert — exactly
            # one controller may win or accounting splits across two
            ctrl = rt.admission.setdefault(
                stream, controller_from_options(stream, {}, rt))
        return rt, ctrl

    def _repl_resolve(self, app: str):
        """REPL_SUBSCRIBE resolution for the data plane: the app's
        runtime (the shipper-side checks — durability, standby role —
        live in net/server.py)."""
        rt = self.runtimes.get(app or "")
        if rt is None:
            raise KeyError(f"no deployed app {app!r}")
        return rt

    def _query_resolve(self, app: str):
        """QUERY-frame resolution: store queries naming an app run
        against its deployed runtime — the same compile cache and feed
        gate `POST /siddhi/artifact/query` goes through."""
        rt = self.runtimes.get(app or "")
        if rt is None:
            raise KeyError(f"no deployed app {app!r}")
        return rt

    def net_info(self) -> dict:
        if self.net is None:
            return {"enabled": False}
        streams = {}
        # list() snapshots: connection threads insert controllers at
        # HELLO time, racing this scrape
        for name, rt in list(self.runtimes.items()):
            for sid, ctrl in list(rt.admission.items()):
                streams[f"{name}/{sid}"] = ctrl.metrics()
        return {"enabled": True, "port": self.net.port,
                "server": self.net.metrics(), "streams": streams}

    # -- operations -------------------------------------------------------

    def deploy(self, app_text: str) -> str:
        # the build runs OUTSIDE the ops lock (slow: device lowering);
        # the swap of the live runtime under the name is what must not
        # interleave with another deploy/undeploy of the same name
        rt = self.manager.create_app_runtime(app_text)
        with self._ops_lock:
            # a same-name redeploy shuts the old runtime down (bounded
            # joins) while holding the ops lock: that wait IS the
            # serialization — no other deploy may see the half-swapped name
            # lint: allow (bounded teardown join under the ops lock by design)
            return self._install(rt)

    def _install(self, rt) -> str:
        name = rt.app.name
        # deploy-time lint (docs/ANALYSIS.md): the findings ride the
        # deploy response; @app:strictAnalysis apps never reach here
        # with warn/error findings (the runtime constructor raised)
        from .analysis import analyze_app
        try:
            self.diagnostics[name] = [f.to_dict()
                                      for f in analyze_app(rt.app)]
        except Exception as e:   # lint: allow-swallow (diagnostics are
            # advisory — an analyzer crash must never block a deploy)
            self.diagnostics[name] = [{
                "rule_id": "SA00", "severity": "info",
                "message": f"analyzer failed: {type(e).__name__}: {e}"}]
        # served runtimes default statistics ON (the /metrics scrape is
        # the point of running as a service); an @app:statistics annotation
        # of any flavor was already applied by the runtime constructor
        if qast.find_annotation(rt.app.annotations, "app:statistics") is None:
            rt.enable_stats(True)
        old = self.runtimes.pop(name, None)
        if old is not None:
            if self.net is not None:
                self.net.retire(old)
            self._park_errors(name, old.error_store)
            old.shutdown()
        # recover-on-redeploy (docs/RELIABILITY.md): a durable app
        # restores its newest snapshot and replays the WAL suffix
        # BEFORE serving — a service restart or same-name redeploy
        # resumes exactly where the durable log ends, instead of
        # parking-only.  (The old runtime above shut down first, so
        # its final barrier landed before this replay scans the log.)
        cfg = getattr(rt, "replication_config", None)
        if rt.durability != "off" and not (cfg is not None
                                           and cfg.role == "standby"):
            # standby replicas do NOT recover at deploy: their state
            # materializes at promote() from the replicated log + the
            # shipped revisions (rt.start() enters standby mode)
            rt.recover()
        rt.start()
        self.runtimes[name] = rt
        return name

    def undeploy(self, name: str) -> None:
        with self._ops_lock:
            rt = self.runtimes.pop(name)
            self.diagnostics.pop(name, None)
            # retire FIRST: the data plane serializes this against
            # in-flight feeds, so every admitted frame either reached the
            # live runtime or lands whole in the (parked) ErrorStore —
            # never dropped
            if self.net is not None:
                self.net.retire(rt)
            self._park_errors(name, rt.error_store)
            # lint: allow (bounded teardown join under the ops lock by design)
            rt.shutdown()

    def _park_errors(self, name: str, store) -> None:
        """Park a retiring runtime's ErrorStore under its app name.  A
        PREVIOUS generation's still-unreplayed entries must survive the
        churn ('never dropped'): they merge INTO the retiring store,
        oldest generation first.  The INCOMING store is always the one
        parked — the data plane's retire() pointed in-flight feeds at
        it, so frames admitted before the undeploy but fed after this
        call still land somewhere reachable (merging the other way
        would orphan them in a store nothing lists or replays)."""
        prev = self.retired_errors.get(name)
        self.retired_errors[name] = store
        if prev is None or prev is store or not len(prev):
            return
        newer = store.take(None)
        for e in prev.take(None):       # fresh ids: two generations'
            store.add(e.stream_id, e.point, e.message,    # counters both
                      e.timestamp_ms, events=e.events,    # start at 1
                      payloads=e.payloads, sink=e.sink)
        for e in newer:
            store._readd(e)

    def send_events(self, req: dict, nbytes: int = 0) -> tuple:
        """Shared validation for the single-event AND batch JSON forms;
        raises ValueError (→ 400) on anything malformed.  Returns
        (http_code, body): admitted requests ingest and return
        200 {"status": "ok"}; the batch rides the stream's
        AdmissionController — the SAME quotas, shed accounting, and
        telemetry as the frame plane (docs/SERVING.md) — so under a
        rate limit REST traffic sheds into the replayable ErrorStore
        (429 {"status": "shed"}) or parks ('oldest' policy,
        202 {"status": "queued"}) instead of jumping the line."""
        if not isinstance(req, dict):
            raise ValueError("body must be a JSON object")
        app = req.get("app")
        rt = self.runtimes.get(app)
        if rt is None:
            raise ValueError(f"no deployed app {app!r}")
        stream = req.get("stream")
        if stream not in rt.schemas:
            raise ValueError(f"app {app!r} has no stream {stream!r}")
        attrs = rt.schemas[stream].attributes
        n_attrs = len(attrs)
        events: list = []

        def _row(data, ts, where: str):
            if not isinstance(data, (list, tuple)):
                raise ValueError(f"{where}: 'data' must be a list")
            if len(data) != n_attrs:
                raise ValueError(
                    f"{where}: stream {stream!r} expects {n_attrs} "
                    f"attributes, got {len(data)}")
            for v, a in zip(data, attrs):
                # type-check at the boundary: a bad value admitted here
                # would only surface at flush, inside the engine's
                # batch builder — poisoning the whole runtime, not just
                # this request (malformed input must 400, never 500)
                t = a.type.name
                if t in ("INT", "LONG", "FLOAT", "DOUBLE") and (
                        isinstance(v, bool)
                        or not isinstance(v, (int, float))):
                    raise ValueError(
                        f"{where}: attribute {a.name!r} expects a "
                        f"number ({t.lower()}), got {type(v).__name__}")
                if t == "BOOL" and not isinstance(v, bool):
                    raise ValueError(
                        f"{where}: attribute {a.name!r} expects a bool, "
                        f"got {type(v).__name__}")
            if ts is not None and not isinstance(ts, (int, float)):
                raise ValueError(f"{where}: 'timestamp' must be a number")
            events.append((tuple(data),
                           int(ts) if ts is not None else None))

        if "events" in req:
            evs = req["events"]
            if not isinstance(evs, list):
                raise ValueError("'events' must be a list of objects")
            for i, ev in enumerate(evs):
                if not isinstance(ev, dict) or "data" not in ev:
                    raise ValueError(
                        f"events[{i}] must be an object with 'data'")
                _row(ev["data"], ev.get("timestamp"), f"events[{i}]")
        else:
            data = req.get("data")
            ts = req.get("timestamp")
            if isinstance(data, list) and data \
                    and isinstance(data[0], (list, tuple)):
                for i, row in enumerate(data):       # batch of rows
                    _row(row, ts, f"data[{i}]")
            else:
                _row(data, ts, "event")
        from .net.admission import (ADMIT, QUEUED, SHED, Work,
                                    controller_from_options)
        ctrl = rt.admission.get(stream)
        if ctrl is None:
            ctrl = rt.admission.setdefault(
                stream, controller_from_options(stream, {}, rt))

        def feed():
            for data, ts in events:
                rt.send(stream, data, ts)
            rt.flush()

        def rows():
            now = rt.now_ms()
            return [(ts if ts is not None else now, tuple(data))
                    for data, ts in events]

        work = Work(n=len(events), nbytes=nbytes or len(events) * 64,
                    feed=feed, rows=rows, stream_id=stream)
        # 'block' policy stalls THIS handler thread (the HTTP analogue
        # of a stalled socket reader); shutdown stays responsive
        d = ctrl.submit(work, stop=lambda: self._stopping)
        for w in d.ready:
            # guarded: a failure in OTHER queued work must not 400 this
            # request or vanish — it captures to the app's ErrorStore
            ctrl.feed_safely(w)
        if d.action == ADMIT:
            work.feed()
            return 200, {"status": "ok", "events": len(events)}
        if d.action == QUEUED:
            return 202, {"status": "queued", "events": len(events)}
        assert d.action == SHED
        return 429, {"status": "shed", "events": len(events),
                     "stored": True,
                     "detail": "rate limit exceeded; events captured in "
                               "the ErrorStore (POST /siddhi/errors "
                               "action=replay to re-ingest)"}

    # back-compat embedding surface
    def send_event(self, app: str, stream: str, data: tuple,
                   timestamp=None) -> None:
        self.send_events({"app": app, "stream": stream,
                          "data": list(data), "timestamp": timestamp})

    def store_query(self, app: str, text: str) -> list:
        return [[ts, list(row)] for ts, row in self.runtimes[app].query(text)]

    def stats(self, app: str) -> dict:
        return self.runtimes[app].stats.report()

    def explain(self, app: str) -> dict:
        """rt.explain() verbatim (core/placement.py) — placement +
        demotion reason chains for every query of a deployed app."""
        return self.runtimes[app].explain()

    def _error_stores(self, app: str) -> tuple:
        """(live_store_or_None, parked_store_or_None) for `app` — the
        parked store holds frames admitted before an undeploy (or a
        same-name redeploy) of the name."""
        rt = self.runtimes.get(app)
        live = rt.error_store if rt is not None else None
        parked = self.retired_errors.get(app)
        if live is None and parked is None:
            raise ValueError(f"no deployed app {app!r}")
        return live, parked

    def errors(self, app: str, stream: Optional[str] = None) -> dict:
        """The app's ErrorStore entries (JSON-safe dicts) — live store
        plus anything parked by an undeploy of the same name."""
        live, parked = self._error_stores(app)
        out: list = []
        evicted = 0
        for store, is_parked in ((live, False), (parked, True)):
            if store is None:
                continue
            for e in store.entries(stream):
                d = e.to_dict()
                if is_parked:
                    d["parked"] = True
                out.append(d)
            evicted += store.evicted
        return {"errors": out, "evicted": evicted}

    def errors_action(self, app: str, action: str, ids=None) -> dict:
        """Replay (re-ingest events / re-publish payloads) or discard
        captured failures.  Replay drains the parked store of an
        undeployed-then-redeployed name into the live runtime; an app
        that is not deployed can only be discarded (redeploy to replay).

        The live and parked stores number entries independently, so an
        explicit id could name one entry in EACH: ids resolve against
        the live store first, and only ids the live store does not hold
        reach the parked one — an action aimed at a live entry can
        never also consume an unrelated parked entry (ids=None still
        means everything in both)."""
        live, parked = self._error_stores(app)
        parked_ids = ids
        if ids is not None and live is not None and parked is not None:
            held = {e.id for e in live.entries()}
            parked_ids = [i for i in ids if i not in held]
        if action == "replay":
            rt = self.runtimes.get(app)
            if rt is None:
                raise ValueError(
                    f"app {app!r} is not deployed: redeploy it to replay "
                    f"its parked errors (or action='discard')")
            out = rt.error_store.replay(rt, ids)
            if parked is not None and len(parked):
                for k, v in parked.replay(rt, parked_ids).items():
                    out[k] = out.get(k, 0) + v
            return out
        if action == "discard":
            discarded = remaining = 0
            for store, want in ((live, ids), (parked, parked_ids)):
                if store is None:
                    continue
                discarded += len(store.take(want))
                remaining += len(store)
            return {"discarded": discarded, "remaining": remaining}
        raise ValueError(f"unknown errors action {action!r} "
                         f"(replay | discard)")

    def snapshot_action(self, app: str, incremental: bool = False) -> dict:
        """POST /siddhi/artifact/snapshot: persist a revision NOW and
        return its structured descriptor (revision id + per-stream
        durable watermark — persistence.Revision.to_dict())."""
        rt = self.runtimes[app]
        return rt.persist(incremental=incremental).to_dict()

    def promote(self, app: str) -> dict:
        """POST /siddhi/artifact/promote: fail a standby replica over
        to serving primary (rt.promote() — fence, recover to head,
        start serving).  Serialized with deploy/undeploy: a promote
        racing a redeploy of the same name must see one runtime."""
        with self._ops_lock:
            rt = self.runtimes[app]
            # lint: allow (bounded recovery join under the ops lock by design)
            return rt.promote()

    def snapshot_info(self, app: str) -> dict:
        """GET /siddhi/artifact/snapshot: the durability/recovery state
        of a deployed app — last revision descriptor (this process OR
        the store's newest), WAL gauges, and the last recovery report."""
        rt = self.runtimes[app]
        desc = rt.last_revision_descriptor
        store = rt.manager.persistence_store if rt.manager else None
        out = {
            "app": app,
            "durability": rt.durability,
            "last_revision": desc.to_dict() if desc is not None else None,
            "store_revision": (store.last_revision(app)
                               if store is not None else None),
        }
        if rt.wal is not None:
            out["wal"] = rt.wal.metrics()
        if getattr(rt, "_wal_recovery", None) is not None:
            # the last recover() report (replayed/skipped/failed/
            # corrupt/recovery_s): the post-failover audit trail —
            # also mirrored in rt.explain()["durability"]["recovery"]
            out["recovery"] = rt._wal_recovery
        if getattr(rt, "_promote_report", None) is not None:
            out["promotion"] = rt._promote_report
        coord = getattr(rt, "replication", None)
        if coord is not None:
            out["replication"] = coord.metrics()
        return out

    def trace(self, app: Optional[str] = None) -> dict:
        """GET /siddhi/artifact/trace: the frame-tracing plane as one
        Chrome `trace_event` object (docs/OBSERVABILITY.md).  Spans of
        every deployed app (or just `app`) merge with one pid per app;
        the hostname metadata is what lets cross-host federation merge
        dumps from several engines into one timeline."""
        import socket as _socket
        names = [app] if app is not None else sorted(self.runtimes)
        evs: list = []
        apps_meta: list = []
        dumps: list = []
        for i, name in enumerate(names):
            tr = getattr(self.runtimes[name], "tracing", None)
            if tr is None:
                apps_meta.append({"app": name, "tracing": False})
                continue
            evs.extend(tr.chrome_events(pid=i + 1))
            apps_meta.append({"app": name, "tracing": True,
                              **tr.metrics()})
            dumps.extend({"app": name, **d}
                         for d in tr.dump_summaries())
        return {"traceEvents": evs,
                "metadata": {"hostname": _socket.gethostname(),
                             "apps": apps_meta, "dumps": dumps}}

    def profile(self, app: Optional[str] = None,
                window: Optional[int] = None) -> dict:
        """GET /siddhi/artifact/profile: the device-time attribution
        plane (docs/OBSERVABILITY.md "Device-time profiling") — per-plan
        phase seconds/shares, host-dispatch share and windowed ring, for
        every deployed app (or just `app`).
        `window` limits each app's ring to its last N snapshots."""
        names = [app] if app is not None else sorted(self.runtimes)
        return {"apps": {n: self.runtimes[n].profile(window=window)
                         for n in names}}

    def metrics(self, app: Optional[str] = None,
                openmetrics: bool = False) -> str:
        """Text exposition rendered LIVE from every deployed runtime's
        statistics (or just `app`'s when given); `openmetrics=True` is
        the Accept-negotiated exemplar-carrying form."""
        names = [app] if app is not None else sorted(self.runtimes)
        return render_prometheus(
            {n: self.runtimes[n].stats.report() for n in names},
            openmetrics=openmetrics)

    # -- lifecycle --------------------------------------------------------

    def start(self) -> "SiddhiService":
        # short poll interval: shutdown() waits one poll tick, and the
        # default 0.5 s turns every stop (tests, bench teardown, ops
        # restarts) into a half-second stall
        self._thread = threading.Thread(
            target=lambda: self.httpd.serve_forever(poll_interval=0.05),
            name="siddhi-service", daemon=True)
        self._thread.start()
        if self.net is not None:
            self.net.start()
        return self

    def stop(self) -> None:
        self._stopping = True
        if self.net is not None:
            self.net.stop()
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
        # outstanding handler threads: bounded join, so teardown never
        # wedges a test run behind a stuck keep-alive
        self.httpd.join_handlers(timeout=5.0)
        with self._ops_lock:    # a straggler undeploy must not interleave
            for rt in list(self.runtimes.values()):
                # lint: allow (bounded teardown join under the ops lock)
                rt.shutdown()
            self.runtimes.clear()


if __name__ == "__main__":
    import sys
    port = int(sys.argv[1]) if len(sys.argv) > 1 else 8006
    svc = SiddhiService(port).start()
    print(f"siddhi-tpu service on http://127.0.0.1:{svc.port}"
          + (f" (data plane :{svc.net_port})" if svc.net_port else ""))
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        svc.stop()
