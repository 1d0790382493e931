"""Sources, sinks, and mappers — the transport SPI.

Reference: core:stream/input/source/Source.java:42 (lifecycle +
connectWithRetry), SourceMapper.java:193, core:stream/output/sink/Sink.java,
SinkMapper, InMemorySource.java:115 / InMemorySink over the topic bus
core:util/transport/InMemoryBroker.java:121, exponential backoff
core:util/transport/BackoffRetryCounter.java:24.

Differences by design: mappers translate between wire payloads and columnar
rows (lists of tuples), not pooled event objects; a source delivers a whole
message as one micro-batch.  Extension points are plain registries
(`register_source_type` / `register_sink_type` / `register_*_mapper`) —
the Python analog of `@Extension` classpath scanning.
"""
from __future__ import annotations

import json
import time
import warnings
from collections import defaultdict
from typing import Callable, Optional

from ..query import ast
from .planner import PlanError


# ---------------------------------------------------------------------------
# in-memory topic bus (reference: InMemoryBroker.java:121)
# ---------------------------------------------------------------------------

class Broker:
    """An isolated in-memory topic bus instance.  The reference's
    InMemoryBroker is a process-global static (two apps — even in two
    SiddhiManagers — sharing a topic name cross-talk); construct a
    SiddhiManager with `isolated_broker=True` to scope topics to that
    manager instead."""

    def __init__(self):
        self._subs: dict = defaultdict(list)    # topic -> [subscriber fn]

    def publish(self, topic: str, message) -> None:
        for fn in list(self._subs.get(topic, ())):
            fn(message)

    def subscribe(self, topic: str, fn: Callable) -> Callable:
        self._subs[topic].append(fn)
        return fn

    def unsubscribe(self, topic: str, fn: Callable) -> None:
        try:
            self._subs[topic].remove(fn)
        except ValueError:
            pass

    def reset(self) -> None:
        self._subs.clear()


_DEFAULT_BROKER = Broker()


def broker_for(rt) -> Broker:
    """The bus a runtime's inMemory transports ride: the owning
    manager's isolated broker when configured, else the process-global
    default (reference semantics)."""
    mgr = getattr(rt, "manager", None)
    b = getattr(mgr, "broker", None)
    return b if b is not None else _DEFAULT_BROKER


class InMemoryBroker:
    """Process-global facade (reference: InMemoryBroker.java:121's
    static subscriber table).  Semantics are deliberately global: every
    runtime in the process shares these topics unless its manager opted
    into an isolated broker.  `reset()` clears all topics (tests)."""

    @classmethod
    def publish(cls, topic: str, message) -> None:
        _DEFAULT_BROKER.publish(topic, message)

    @classmethod
    def subscribe(cls, topic: str, fn: Callable) -> Callable:
        return _DEFAULT_BROKER.subscribe(topic, fn)

    @classmethod
    def unsubscribe(cls, topic: str, fn: Callable) -> None:
        _DEFAULT_BROKER.unsubscribe(topic, fn)

    @classmethod
    def reset(cls) -> None:
        _DEFAULT_BROKER.reset()


# ---------------------------------------------------------------------------
# mappers
# ---------------------------------------------------------------------------

class SourceMapper:
    """wire message -> list of (timestamp|None, row_tuple)."""

    def __init__(self, schema, options: dict):
        self.schema = schema
        self.options = options

    def map(self, message) -> list:
        raise NotImplementedError


class PassThroughSourceMapper(SourceMapper):
    """Message is a row tuple, a list of row tuples, or an Event
    (reference: PassThroughSourceMapper.java:80)."""

    def map(self, message) -> list:
        from .runtime import Event
        if isinstance(message, Event):
            return [(message.timestamp, message.data)]
        if isinstance(message, tuple):
            return [(None, message)]
        if isinstance(message, list):
            out = []
            for m in message:
                if isinstance(m, Event):
                    out.append((m.timestamp, m.data))
                else:
                    out.append((None, tuple(m)))
            return out
        raise ValueError(f"passThrough mapper: bad message {message!r}")


class JsonSourceMapper(SourceMapper):
    """`{"event": {attr: value, ...}}` (or a JSON list of such), matching
    the reference json mapper's default template."""

    def map(self, message) -> list:
        if isinstance(message, (str, bytes)):
            message = json.loads(message)
        msgs = message if isinstance(message, list) else [message]
        names = self.schema.names
        out = []
        for m in msgs:
            body = m.get("event", m) if isinstance(m, dict) else m
            out.append((None, tuple(body.get(n) for n in names)))
        return out


class TemplateBuilder:
    """`{{attr}}` payload templating (reference:
    core:util/transport/TemplateBuilder.java — validates placeholders
    against the schema at build time, fills per event at runtime)."""

    import re as _re
    _PH = _re.compile(r"\{\{\s*(\w+)\s*\}\}")

    def __init__(self, schema, template: str):
        self.template = template
        self._parts: list = []      # literal str | attr index
        pos = 0
        for m in self._PH.finditer(template):
            if m.start() > pos:
                self._parts.append(template[pos:m.start()])
            attr = m.group(1)
            if attr not in schema.index_of:
                raise PlanError(
                    f"@payload template references unknown attribute "
                    f"{attr!r}; stream has {list(schema.names)}")
            self._parts.append(schema.index_of[attr])
            pos = m.end()
        if pos < len(template):
            self._parts.append(template[pos:])
        if not any(isinstance(p, int) for p in self._parts):
            raise PlanError(
                f"@payload template has no {{{{attribute}}}} placeholders: "
                f"{template!r}")

    def build(self, data: tuple) -> str:
        return "".join(
            p if isinstance(p, str)
            else ("null" if data[p] is None else str(data[p]))
            for p in self._parts)


class SinkMapper:
    """events -> wire payloads (one per event)."""

    def __init__(self, schema, options: dict):
        self.schema = schema
        self.options = options
        tpl = options.get("_payload")
        self.payload = TemplateBuilder(schema, tpl) if tpl else None

    def map(self, events: list) -> list:
        raise NotImplementedError


class PassThroughSinkMapper(SinkMapper):
    def map(self, events: list) -> list:
        if self.payload is not None:
            return [self.payload.build(e.data) for e in events]
        return [e.data for e in events]


class JsonSinkMapper(SinkMapper):
    """Default `{"event": {...}}` envelope; a @payload template replaces
    it wholesale (reference json sink mapper custom-payload mode)."""

    def map(self, events: list) -> list:
        if self.payload is not None:
            return [self.payload.build(e.data) for e in events]
        names = self.schema.names
        return [json.dumps({"event": dict(zip(names, e.data))}) for e in events]


class TextSinkMapper(SinkMapper):
    """`@map(type='text')` — `attr:"value"` lines per event, or a
    @payload template (reference: siddhi-map-text TextSinkMapper
    default/custom modes).  `delimiter` joins multi-event publishes."""

    def map(self, events: list) -> list:
        names = self.schema.names
        out = []
        for e in events:
            if self.payload is not None:
                out.append(self.payload.build(e.data))
                continue
            parts = []
            for n, v in zip(names, e.data):
                if isinstance(v, str):
                    parts.append(f'{n}:"{v}"')
                elif v is None:
                    parts.append(f"{n}:null")
                else:
                    parts.append(f"{n}:{v}")
            out.append(",\n".join(parts))
        delim = self.options.get("delimiter")
        if delim and out:
            return [delim.join(out)]
        return out


class TextSourceMapper(SourceMapper):
    """`@map(type='text')` inbound: parses `attr:value` lines (quotes
    optional), coercing by schema type; a `delimiter` option splits one
    message into several events (reference: siddhi-map-text
    TextSourceMapper default mapping)."""

    def map(self, message) -> list:
        if isinstance(message, bytes):
            message = message.decode()
        text = str(message)
        delim = self.options.get("delimiter")
        chunks = text.split(delim) if delim else [text]
        out = []
        for chunk in chunks:
            vals: dict = {}
            for line in chunk.splitlines():
                line = line.strip().rstrip(",")
                if not line or ":" not in line:
                    continue
                k, v = line.split(":", 1)
                vals[k.strip()] = v.strip()
            row = []
            for a in self.schema.attributes:
                raw = vals.get(a.name)
                row.append(self._coerce(raw, a.type))
            out.append((None, tuple(row)))
        return out

    @staticmethod
    def _coerce(raw, t):
        from ..query.ast import AttrType
        if raw is None or raw == "null":
            return None
        if raw.startswith('"') and raw.endswith('"'):
            raw = raw[1:-1]
        try:
            if t in (AttrType.INT, AttrType.LONG):
                return int(float(raw))
            if t in (AttrType.FLOAT, AttrType.DOUBLE):
                return float(raw)
            if t == AttrType.BOOL:
                return str(raw).lower() in ("true", "1")
            return raw
        except (TypeError, ValueError):
            return None


SOURCE_MAPPERS: dict = {"passthrough": PassThroughSourceMapper,
                        "json": JsonSourceMapper,
                        "text": TextSourceMapper}
SINK_MAPPERS: dict = {"passthrough": PassThroughSinkMapper,
                      "json": JsonSinkMapper,
                      "text": TextSinkMapper}


def register_source_mapper(name: str, cls, meta=None) -> None:
    from ..extension import register_meta
    register_meta("source-mapper", meta)
    SOURCE_MAPPERS[name.lower()] = cls


def register_sink_mapper(name: str, cls, meta=None) -> None:
    from ..extension import register_meta
    register_meta("sink-mapper", meta)
    SINK_MAPPERS[name.lower()] = cls


# ---------------------------------------------------------------------------
# sources
# ---------------------------------------------------------------------------

class SourceHandler:
    """Interception point between mapper and runtime ingest (reference:
    core:stream/input/source/SourceHandler.java — the HA SPI: an
    active/passive deployment plugs a handler that forwards on the
    active node and records-and-drops on the passive one).  Return the
    (possibly transformed) rows; return None or [] to swallow."""

    def init(self, source: "Source") -> None:
        pass

    def on_rows(self, rows: list) -> Optional[list]:
        return rows

    # snapshot hooks so HA state rides the app snapshot
    def state_dict(self) -> dict:
        return {}

    def load_state_dict(self, d: dict) -> None:
        pass


class SinkHandler:
    """Interception point between the runtime and the sink mapper
    (reference: core:stream/output/sink/SinkHandler.java)."""

    def init(self, sink: "Sink") -> None:
        pass

    def on_events(self, events: list) -> Optional[list]:
        return events

    def state_dict(self) -> dict:
        return {}

    def load_state_dict(self, d: dict) -> None:
        pass


class Source:
    """Transport lifecycle (reference: Source.java:42).  Subclasses
    implement connect/disconnect; incoming payloads go through
    `self.deliver(message)`."""

    def __init__(self, rt, stream_id: str, options: dict,
                 mapper: SourceMapper):
        self.rt = rt
        self.stream_id = stream_id
        self.options = options
        self.mapper = mapper
        self.connected = False
        self.handler: Optional[SourceHandler] = None
        # telemetry: malformed messages silently dropped (logged-only)
        # vs captured into the ErrorStore — surfaced in statistics()
        # and the Prometheus exposition
        self.dropped_events = 0
        self.stored_events = 0

    # -- SPI -----------------------------------------------------------------

    def connect(self) -> None:
        raise NotImplementedError

    def disconnect(self) -> None:
        pass

    # -- runtime glue --------------------------------------------------------

    def deliver(self, message) -> None:
        """Map a wire message and feed it as one micro-batch."""
        try:
            rows = self.mapper.map(message)
        except Exception as e:
            action = self.rt.fault_action(self.stream_id)
            # log/wait (and no action) all DROP a map error — a malformed
            # payload is deterministic, there is nothing to wait out —
            # so telemetry records the true disposition, not the action
            self.rt.stats.on_fault(
                self.stream_id,
                f"source.{action}" if action in ("stream", "store")
                else "source.drop")
            if action == "stream":
                self.rt._route_fault_rows(self.stream_id, [],
                                          f"map error: {e}", raw=message)
            elif action == "store":
                # capture the raw payload for replay through this source
                # (ErrorStore.replay sees .deliver and re-feeds the
                # mapper; a still-broken payload re-captures)
                self.rt.error_store.add(
                    self.stream_id, "source.map", e, self.rt.now_ms(),
                    payloads=[message], sink=self)
                self.stored_events += 1
            else:
                # no routing configured: log and drop the malformed
                # message (reference SourceMapper does the same) instead of
                # raising into the transport and starving co-subscribers —
                # but COUNT it (dropped_events rides statistics() and
                # /metrics, so the drop is no longer invisible)
                self.dropped_events += 1
                hint = ("@OnError(action={a!r}) applies to processing "
                        "faults; map errors drop".format(a=action)
                        if action else
                        "add @OnError(action='stream') to route to a fault "
                        "stream (or 'store' to capture for replay)")
                warnings.warn(
                    f"source on {self.stream_id!r}: dropping unmappable "
                    f"message ({e}); {hint}", RuntimeWarning)
            return
        if self.handler is not None:
            rows = self.handler.on_rows(rows)
            if not rows:
                return
        with self.rt._lock:
            for ts, row in rows:
                self.rt._send_locked(self.stream_id, row, ts)
        self.rt._drain_async_outbox()
        self.rt.flush()      # async: barrier outside the lock

    def connect_with_retry(self, max_tries: int = 5,
                           base_delay_s: float = 0.05) -> None:
        """Exponential-backoff connect (reference:
        Source.connectWithRetry + BackoffRetryCounter) — unified on the
        faults.BackoffPolicy schedule shared with sink publishes."""
        import zlib
        from .faults import BackoffPolicy
        policy = BackoffPolicy(max_tries=max_tries,
                               base_delay_s=base_delay_s,
                               seed=zlib.crc32(self.stream_id.encode()))

        def attempt():
            self.rt.inject("source.connect", self.stream_id)
            self.connect()

        def on_retry(i, e, delay):
            warnings.warn(f"source {type(self).__name__} on "
                          f"{self.stream_id!r}: connect failed ({e}); "
                          f"retrying in {delay:.2f}s", RuntimeWarning)

        policy.run(attempt, on_retry=on_retry)
        self.connected = True


class InMemorySource(Source):
    """@source(type='inMemory', topic='t') (reference: InMemorySource.java:115)."""

    def connect(self) -> None:
        topic = self.options.get("topic")
        if not topic:
            raise PlanError("inMemory source needs a topic")
        self._broker = broker_for(self.rt)
        self._fn = self._broker.subscribe(topic, self.deliver)

    def disconnect(self) -> None:
        if self.connected:
            self._broker.unsubscribe(self.options.get("topic"), self._fn)


class CallbackSource(Source):
    """@source(type='callback'): a programmatic ingress handle —
    `rt.sources_for(stream)[0].deliver(msg)`; useful for tests and
    embedding."""

    def connect(self) -> None:
        pass


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

class Sink:
    """Publish-side transport with optional fault tolerance:
    `@sink(..., on.error='log'|'store'|'stream'|'wait')` arms a
    per-payload retry with exponential backoff + seeded jitter
    (faults.BackoffPolicy — the same schedule as connect_with_retry) and
    a per-sink circuit breaker.  `on.error` names the disposition once
    retries exhaust (or the breaker is open):

      log    - log and drop the payload (reference default)
      store  - capture into the runtime ErrorStore for replay
      stream - route into the "!<stream>" fault stream (falls back to
               the ErrorStore when none is defined)
      wait   - extend retries to a deadline (`retry.timeout`, default
               10 sec), then store

    Knobs: max.retries (3), retry.interval ('50 ms'), retry.max.interval
    ('5 sec'), breaker.threshold (5), breaker.reset ('5 sec').  Without
    on.error the legacy fail-fast path is kept: publish errors propagate
    to the caller."""

    def __init__(self, rt, stream_id: str, options: dict, mapper: SinkMapper):
        self.rt = rt
        self.stream_id = stream_id
        self.options = options
        self.mapper = mapper
        self.connected = False
        self.handler: Optional[SinkHandler] = None
        self.published = 0
        self.retries = 0
        self.failures = 0
        self.stored = 0
        self.on_error = (options.get("on.error") or "").lower() or None
        self.breaker = None
        self.backoff = None
        if self.on_error is not None:
            if self.on_error not in ("log", "store", "stream", "wait"):
                raise PlanError(
                    f"sink on {stream_id!r}: unknown on.error "
                    f"{self.on_error!r} (have: log | store | stream | wait)")
            import zlib
            from .faults import BackoffPolicy, CircuitBreaker
            from .runtime import _parse_interval_s

            def _iv(key, default):
                v = options.get(key)
                return _parse_interval_s(v) if v is not None else default
            deadline = _iv("retry.timeout", 10.0) \
                if self.on_error == "wait" else None
            self.backoff = BackoffPolicy(
                max_tries=(1_000_000 if self.on_error == "wait"
                           else int(options.get("max.retries", 3)) + 1),
                base_delay_s=_iv("retry.interval", 0.05),
                max_delay_s=_iv("retry.max.interval", 5.0),
                deadline_s=deadline,
                seed=zlib.crc32(f"{stream_id}/{options.get('topic', '')}"
                                .encode()))
            self.breaker = CircuitBreaker(
                failure_threshold=int(options.get("breaker.threshold", 5)),
                reset_timeout_s=_iv("breaker.reset", 5.0))

    def connect(self) -> None:
        raise NotImplementedError

    def disconnect(self) -> None:
        pass

    def publish(self, payload) -> None:
        raise NotImplementedError

    def on_events(self, events: list) -> None:
        if self.handler is not None:
            events = self.handler.on_events(events)
            if not events:
                return
        payloads = self.mapper.map(events)
        if self.on_error is None:       # legacy fail-fast path
            for payload in payloads:
                self.publish_attempt(payload)
                self.published += 1
            return
        for payload in payloads:
            self._publish_guarded(payload)

    # -- guarded publish (retry + breaker + on.error) -----------------------

    def publish_attempt(self, payload) -> None:
        """One raw publish attempt through the fault-injection point
        (also the replay entry used by ErrorStore.replay).  Records a
        `sink.send` span on the originating frame's trace: the live
        thread-local scope (set by runtime._flush_sink_outbox, inside
        its `sink.publish`) for in-line publishes, or the resumable ctx
        a stored payload carries — so an ErrorStore replay after a
        breaker shed still lands on the SAME tree, not an orphan."""
        rt = self.rt
        rt.inject("sink.publish", self.stream_id)
        h = rt.current_trace()
        if h is None:
            tc = getattr(payload, "trace_ctx", None)
            if tc is not None and rt.tracing is not None:
                h = rt.tracing.resume(*tc)
        with rt.span("sink.send", handle=h, sink=self.stream_id,
                     transport=getattr(self, "transport",
                                       type(self).__name__)):
            self.publish(payload)

    def _publish_guarded(self, payload) -> None:
        if not self.breaker.allow():
            # open breaker: shed straight to the disposition instead of
            # hammering a dead transport per payload
            self._exhausted(payload, RuntimeError(
                f"circuit breaker open for sink on {self.stream_id!r}"))
            return
        err = None
        delays = self.backoff.delays()
        while True:
            try:
                self.publish_attempt(payload)
            except Exception as e:
                err = e
                self.failures += 1
                self.breaker.on_failure()
                if self.breaker.state == self.breaker.OPEN:
                    tr = getattr(self.rt, "tracing", None)
                    if tr is not None:
                        # enqueue-only (cooldown-throttled): the dump
                        # builds on the siddhi-trace-export thread
                        tr.trigger("breaker_open",
                                   f"sink on {self.stream_id!r}: "
                                   f"{type(e).__name__}: {e}")
                    break
                delay = next(delays, None)
                if delay is None:
                    break
                self.retries += 1
                time.sleep(delay)
                continue
            self.breaker.on_success()
            self.published += 1
            return
        self._exhausted(payload, err)

    def _exhausted(self, payload, err) -> None:
        rt = self.rt
        act = self.on_error
        rt.stats.on_fault(self.stream_id, f"sink.{act}")
        if act == "stream" and ("!" + self.stream_id) in rt.schemas:
            rt._route_fault_rows(self.stream_id, [],
                                 f"sink publish failed: {err}", raw=payload)
            return
        if act in ("store", "stream", "wait"):
            rt.error_store.add(self.stream_id, "sink.publish", err,
                               rt.now_ms(), payloads=[payload], sink=self)
            self.stored += 1
            return
        import logging
        logging.getLogger("siddhi_tpu.faults").error(
            "sink on %r: dropping payload after retries "
            "(@sink on.error='log'): %s: %s",
            self.stream_id, type(err).__name__, err)

    def metrics(self) -> dict:
        m = {"published": self.published, "retries": self.retries,
             "failures": self.failures, "stored": self.stored}
        if self.breaker is not None:
            m.update(self.breaker.metrics())
        return m


class DistributedSink(Sink):
    """Multi-destination fan-out (reference: DistributedTransport +
    Broadcast/RoundRobin/Partitioned DistributionStrategy,
    core:stream/output/sink/distributed/DistributionStrategy.java:107,
    MultiClientDistributedSink): one child sink per @destination, the
    strategy picks destinations per event."""

    def __init__(self, rt, stream_id, options, mapper, children,
                 strategy: str, partition_key=None, schema=None):
        super().__init__(rt, stream_id, options, mapper)
        self.children = children
        self.strategy = strategy
        self._rr = 0
        self._key_idx = None
        if strategy == "partitioned":
            if partition_key is None:
                raise PlanError(
                    f"sink on {stream_id!r}: partitioned distribution "
                    f"needs partitionKey")
            if partition_key not in schema.index_of:
                raise PlanError(
                    f"sink on {stream_id!r}: partitionKey "
                    f"{partition_key!r} not in schema {schema.names}")
            self._key_idx = schema.index_of[partition_key]

    def connect(self) -> None:
        for c in self.children:
            c.connect()
            c.connected = True

    def disconnect(self) -> None:
        for c in self.children:
            if c.connected:
                c.disconnect()
                c.connected = False

    def on_events(self, events: list) -> None:
        n = len(self.children)
        if self.strategy == "broadcast":
            for c in self.children:
                c.on_events(events)
            return
        buckets = [[] for _ in range(n)]
        for ev in events:
            if self.strategy == "roundrobin":
                i = self._rr
                self._rr = (self._rr + 1) % n
            else:
                # stable across processes (builtin hash() is salted for
                # strings): same key -> same destination, always
                import zlib
                i = zlib.crc32(repr(ev.data[self._key_idx]).encode()) % n
            buckets[i].append(ev)
        for c, evs in zip(self.children, buckets):
            if evs:
                c.on_events(evs)


class InMemorySink(Sink):
    def connect(self) -> None:
        if not self.options.get("topic"):
            raise PlanError("inMemory sink needs a topic")
        self._broker = broker_for(self.rt)

    def publish(self, payload) -> None:
        self._broker.publish(self.options["topic"], payload)


class LogSink(Sink):
    """@sink(type='log') — prints events (reference: log sink extension)."""

    def connect(self) -> None:
        pass

    def publish(self, payload) -> None:
        print(f"[{self.options.get('prefix', self.stream_id)}] {payload}")


SOURCE_TYPES: dict = {"inmemory": InMemorySource, "callback": CallbackSource}
SINK_TYPES: dict = {"inmemory": InMemorySink, "log": LogSink}


def register_source_type(name: str, cls, meta=None) -> None:
    from ..extension import register_meta
    register_meta("source", meta)
    SOURCE_TYPES[name.lower()] = cls


def register_sink_type(name: str, cls, meta=None) -> None:
    from ..extension import register_meta
    register_meta("sink", meta)
    SINK_TYPES[name.lower()] = cls


# ---------------------------------------------------------------------------
# wiring from @source/@sink annotations
# (reference: DefinitionParserHelper.addEventSource/addEventSink:309-433)
# ---------------------------------------------------------------------------

def _ann_options(a: ast.Annotation) -> dict:
    return {(k.lower() if k else f"_{i}"): v
            for i, (k, v) in enumerate(a.elements)}


def _load_net_types() -> None:
    """Lazy registration of the serving-plane transports (tcp/ws/shm
    sources, tcp/ws sinks) — importing siddhi_tpu.net registers them.
    Deferred so apps that never network pay no import cost."""
    import importlib
    try:
        importlib.import_module(".net", package=__package__.rsplit(".", 1)[0])
    except ImportError:
        pass


def build_io(rt) -> None:
    """Instantiate sources/sinks declared on stream definitions."""
    from ..query.ast import find_annotation
    for sid, sd in rt.app.stream_definitions.items():
        for a in sd.annotations:
            nm = a.name.lower()
            if nm == "source":
                opts = _ann_options(a)
                typ = opts.get("type", "").lower()
                cls = SOURCE_TYPES.get(typ)
                if cls is None:
                    _load_net_types()
                    cls = SOURCE_TYPES.get(typ)
                if cls is None:
                    raise PlanError(f"unknown source type {typ!r} on "
                                    f"{sid!r}; have {sorted(SOURCE_TYPES)}")
                mapper = _mapper_of(a, rt.schemas[sid], SOURCE_MAPPERS,
                                    PassThroughSourceMapper)
                src = cls(rt, sid, opts, mapper)
                src.config = rt.config_reader("source", typ)
                fac = getattr(rt.manager, "source_handler_factory", None) \
                    if rt.manager else None
                if fac is not None:
                    src.handler = fac()
                    src.handler.init(src)
                rt.sources.append(src)
            elif nm == "sink":
                opts = _ann_options(a)
                typ = opts.get("type", "").lower()
                cls = SINK_TYPES.get(typ)
                if cls is None:
                    _load_net_types()
                    cls = SINK_TYPES.get(typ)
                if cls is None:
                    raise PlanError(f"unknown sink type {typ!r} on "
                                    f"{sid!r}; have {sorted(SINK_TYPES)}")
                mapper = _mapper_of(a, rt.schemas[sid], SINK_MAPPERS,
                                    PassThroughSinkMapper)
                from ..query.ast import find_annotation
                dist = find_annotation(a.annotations, "distribution")
                if dist is not None:
                    # keyed elements only (the lone-positional fallback of
                    # Annotation.element would alias strategy/partitionKey)
                    def _kv(ann, key, default=None):
                        return next((v for k, v in ann.elements if k == key),
                                    default)
                    strategy = (_kv(dist, "strategy") or "roundRobin").lower()
                    if strategy not in ("broadcast", "roundrobin",
                                        "partitioned"):
                        raise PlanError(f"sink on {sid!r}: unknown "
                                        f"distribution strategy {strategy!r}")
                    dests = [d for d in dist.annotations
                             if d.name == "destination"]
                    if not dests:
                        raise PlanError(f"sink on {sid!r}: @distribution "
                                        f"needs @destination entries")
                    children = []
                    for d in dests:
                        child_opts = dict(opts)
                        child_opts.update(_ann_options(d))
                        children.append(cls(rt, sid, child_opts, mapper))
                    sink = DistributedSink(
                        rt, sid, opts, mapper, children, strategy,
                        _kv(dist, "partitionKey"), rt.schemas[sid])
                else:
                    sink = cls(rt, sid, opts, mapper)
                sink.config = rt.config_reader("sink", typ)
                fac = getattr(rt.manager, "sink_handler_factory", None) \
                    if rt.manager else None
                if fac is not None:
                    sink.handler = fac()
                    sink.handler.init(sink)
                rt.sinks.append(sink)
                # stage into the runtime's outbox instead of publishing
                # under the runtime lock (cross-runtime ABBA deadlock —
                # runtime._flush_sink_outbox delivers after release).
                # The active frame-trace handle (scatter runs under the
                # frame's scope) rides the entry so egress spans land on
                # the right tree when the outbox flushes later
                def _stage(events, _sink=sink, _rt=rt):
                    _rt._sink_outbox.append(
                        (_sink.on_events, events, _rt.current_trace()))
                rt._stream_callbacks[sid].append(_stage)


def _mapper_of(a: ast.Annotation, schema, registry: dict, default_cls):
    from ..query.ast import find_annotation
    m = find_annotation(a.annotations, "map")
    if m is None:
        return default_cls(schema, {})
    opts = _ann_options(m)
    # @payload('... {{attr}} ...') nested under @map (reference:
    # AnnotationHelper payload extraction feeding TemplateBuilder)
    pl = find_annotation(m.annotations, "payload")
    if pl is not None:
        opts["_payload"] = pl.element()
    typ = opts.get("type", "passThrough").lower()
    cls = registry.get(typ)
    if cls is None:
        raise PlanError(f"unknown mapper type {typ!r}; have {sorted(registry)}")
    return cls(schema, opts)
