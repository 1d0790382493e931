"""Network sinks — `@sink(type='tcp'|'ws', host=..., port=..., ...)`.

Batched columnar egress: each emitted event batch encodes to ONE
DATA frame (plus any string-table delta), shipped over the same frame
protocol the ingest plane speaks — so a `@sink(type='tcp')` on one
engine can feed a `@source(type='tcp')` on another byte-identically,
and `net/client.py FrameReceiver` is the generic consuming end.

Fault tolerance rides the PR-4 machinery unchanged: `on.error`,
`max.retries`, `retry.interval`, `breaker.threshold`, ... arm the
same BackoffPolicy + CircuitBreaker guarded publish as every other
sink; a publish failure marks the connection dirty and the next
attempt reconnects and replays the FULL string table before data, so
retried frames always decode (the dictionary is connection state).

Payloads handed to the retry path are self-contained `bytes` (delta +
DATA frames concatenated), so an ErrorStore capture/replay round trip
re-publishes the exact wire bytes.
"""
from __future__ import annotations

import socket
from typing import Optional

import numpy as np

from ..core.io import Sink, register_sink_type
from ..utils.locks import new_lock
from ..core.planner import PlanError
from . import frame as fp
from .client import NetClientError, WsFrameClient, _FrameEncoder

# a dead peer can surface as refused/reset (OSError), as a clean EOF
# mid-handshake (EOFError from the frame reader), or as garbage bytes
# where the HELLO_OK should be (FrameError); the ws client wraps its
# handshake/HELLO rejections in NetClientError — all mean reconnect
_CONN_ERRORS = (OSError, ConnectionError, EOFError, NetClientError,
                fp.FrameError)


class _SinkPayload(bytes):
    """A sink payload blob plus the code range its embedded STRINGS
    delta covers [start_code, end_code) — so publish() can tell when
    the payload itself carries the peer forward and skip the catch-up
    delta that would otherwise re-ship every dictionary delta twice.
    Degrades safely: anything that strips the attributes (they do not
    survive pickling) just falls back to catch-up duplication, which
    the server-side remap accepts idempotently.

    `trace_ctx` is the originating frame's resumable (trace_id, head)
    (core/tracing.py): a stored payload replayed from the ErrorStore
    records its publish span on the SAME tree, and the blob itself
    already embeds the wire TRACE frame re-stamping the egress DATA."""
    start_code: Optional[int] = None
    end_code: Optional[int] = None
    trace_ctx: Optional[tuple] = None


class TcpSink(Sink):
    """Columnar frame egress over TCP."""

    transport = "tcp"

    def __init__(self, rt, stream_id, options, mapper):
        super().__init__(rt, stream_id, options, mapper)
        if not options.get("port"):
            raise PlanError(f"sink on {stream_id!r}: "
                            f"@sink(type='{self.transport}') needs a port")
        self.host = options.get("host", "127.0.0.1")
        self.tcp_port = int(options["port"])
        self.sock: Optional[socket.socket] = None
        self.frames_out = 0
        self.bytes_out = 0
        self.reconnects = 0
        schema = rt.schemas[stream_id]
        self._cols = [(a.name, a.type.name.lower())
                      for a in schema.attributes]
        self._schema = schema
        from ..query.ast import AttrType
        str_cols = {a.name for a in schema.attributes
                    if a.type == AttrType.STRING}
        # ONE encoder for the sink's lifetime: payload blobs reference a
        # monotone dictionary; _open replays the full table on every
        # (re)connect and publish() sends a catch-up delta whenever the
        # peer is behind (a shed payload took its STRINGS delta with it)
        # — so queued/ErrorStore payloads always decode
        self.enc = _FrameEncoder(stream_id, self._cols, str_cols)
        self._peer_codes = 1            # peer has mapped codes < this
        self._io_lock = new_lock("TcpSink._io_lock")

    # -- connection management ---------------------------------------------

    def connect(self) -> None:
        # under _io_lock: connect() can race a publish — a replay of
        # stored payloads, or the scheduler flushing the sink outbox,
        # may already be reconnecting on another thread, and _open's
        # negotiation plus the _peer_codes bookkeeping must not
        # interleave (surfaced by the SL03 lockset self-analysis)
        try:
            with self._io_lock:
                self._open_locked()
        except _CONN_ERRORS as e:
            if self.on_error is None:
                raise               # fail-fast sinks surface at start()
            # armed sinks defer: publish() reconnects per attempt, the
            # retry/breaker machinery owns the failure from here
            import warnings
            warnings.warn(
                f"sink on {self.stream_id!r}: peer "
                f"{self.host}:{self.tcp_port} unavailable at start ({e}); "
                f"deferring to per-publish retry", RuntimeWarning)
            with self._io_lock:
                try:
                    if self.sock is not None:
                        self.sock.close()
                except OSError:
                    pass
                self.sock = None

    def _open_locked(self) -> None:
        # blocking connect/negotiate under _io_lock is the sink's design:
        # the lock serializes ALL wire traffic, and a publisher blocked
        # behind a reconnect is exactly the retry/breaker back-off path
        # lint: allow (reconnect-under-io-lock serializes the wire by design)
        self.sock = socket.create_connection((self.host, self.tcp_port),
                                             timeout=5.0)
        try:
            self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._wire_send_locked(fp.encode_hello(self.rt.app.name,
                                            self.stream_id,
                                            self._cols, credit=False))
            ftype, payload = fp.read_frame(fp.reader_for(self.sock))
            if ftype == fp.ERROR:
                import json
                raise ConnectionError(json.loads(payload)["error"])
            if ftype != fp.HELLO_OK:
                raise ConnectionError(
                    f"expected HELLO_OK, got {fp.type_name(ftype)}")
            table = self.enc.strings.all_strings()
            if table:                   # dictionary replay (reconnect)
                self._wire_send_locked(fp.encode_strings(table, start_code=1))
        except BaseException:
            # a half-negotiated socket must not survive: publish() only
            # reconnects when self.sock is None, so leaving it set would
            # ship frames on a connection that never completed HELLO
            try:
                self.sock.close()
            except OSError:
                pass
            self.sock = None
            raise
        self._peer_codes = len(self.enc.strings)
        self.reconnects += 1

    def _wire_send_locked(self, data: bytes) -> None:
        # the socket IS the resource _io_lock serializes: frames must
        # not interleave, and a slow peer backpressures this sink's
        # publisher only (the retry machinery owns longer stalls)
        # lint: allow (wire writes must serialize under _io_lock by design)
        self.sock.sendall(data)

    def disconnect(self) -> None:
        # under _io_lock: a teardown racing an in-flight publish used to
        # interleave the BYE with a half-written DATA frame and null the
        # socket under the publisher's feet
        with self._io_lock:
            if self.sock is not None:
                try:
                    self._wire_send_locked(fp.encode_frame(fp.BYE))
                except OSError:
                    pass
                try:
                    self.sock.close()
                except OSError:
                    pass
                self.sock = None

    # -- egress -------------------------------------------------------------

    def on_events(self, events: list) -> None:
        if self.handler is not None:
            events = self.handler.on_events(events)
            if not events:
                return
        payload = self._encode_events(events)
        if self.on_error is None:       # legacy fail-fast path
            self.publish_attempt(payload)
            with self._io_lock:         # metrics scrapes read cross-thread
                self.published += 1
            return
        self._publish_guarded(payload)

    def _encode_events(self, events: list) -> bytes:
        """Events -> one self-contained frame blob (delta + DATA).
        Columnarizes ONCE per batch — no per-event wire work."""
        with self.rt.span("sink.encode", events=len(events)), \
                self._io_lock:
            return self._encode_events_locked(events)

    def _encode_events_locked(self, events: list) -> bytes:
        n = len(events)
        ts = np.fromiter((e.timestamp for e in events), dtype=np.int64,
                         count=n)
        cols = {}
        for i, (name, tname) in enumerate(self._cols):
            vals = [e.data[i] for e in events]
            if tname == "string":
                cols[name] = np.asarray(
                    ["" if v is None else str(v) for v in vals])
            else:
                from ..core.schema import dtype_of
                dt = dtype_of(self._schema.types[name])
                fill = 0 if np.dtype(dt).kind in "iub" else np.nan
                cols[name] = np.asarray(
                    [fill if v is None else v for v in vals], dtype=dt)
        start = len(self.enc.strings)
        blob = self.enc.encode_batch(cols, ts)
        # wire trace-context re-stamp: the egress DATA frame carries the
        # INGRESS frame's trace id (the batch callback staged us under
        # its scope), so traces compose across engine hops — the
        # downstream engine adopts the id for its own span tree
        h = self.rt.current_trace()
        if h is not None:
            blob = fp.encode_trace(h.trace_id, h.head) + blob
        payload = _SinkPayload(blob)
        payload.start_code = start
        payload.end_code = len(self.enc.strings)
        if h is not None:
            payload.trace_ctx = h.ctx()
        return payload

    def publish(self, payload) -> None:
        with self._io_lock:
            if self.sock is None:       # reconnect + full dictionary replay
                self._open_locked()
            try:
                start = getattr(payload, "start_code", None)
                behind = len(self.enc.strings) - self._peer_codes
                if behind > 0 and (start is None
                                   or self._peer_codes < start):
                    # a shed/stored payload took its STRINGS delta down
                    # with it: catch the peer up before anything newer.
                    # Skipped when THIS payload's embedded delta already
                    # starts at (or before) the peer's mark — otherwise
                    # every dictionary delta would ship twice
                    self._wire_send_locked(fp.encode_strings(
                        self.enc.strings.strings_from(self._peer_codes),
                        start_code=self._peer_codes))
                    self._peer_codes = len(self.enc.strings)
                self._wire_send_locked(payload)
                end = getattr(payload, "end_code", None)
                if end is not None and end > self._peer_codes:
                    # the embedded delta advanced the peer too
                    self._peer_codes = end
                self.frames_out += 1
                self.bytes_out += len(payload)
            except _CONN_ERRORS:
                # dirty connection: the next attempt reconnects fresh
                try:
                    self.sock.close()
                except (OSError, AttributeError):
                    pass
                self.sock = None
                raise

    def metrics(self) -> dict:
        m = super().metrics()
        m.update({"frames_out": self.frames_out,
                  "bytes_out": self.bytes_out,
                  "transport": self.transport})
        return m


class WsSink(TcpSink):
    """Columnar frame egress over a WebSocket connection (the peer is
    a NetServer, which sniffs the upgrade on its one port)."""

    transport = "ws"

    def _open_locked(self) -> None:
        self._ws = WsFrameClient(self.host, self.tcp_port, self.stream_id,
                                 self._cols, app=self.rt.app.name,
                                 credit=False)
        self.sock = self._ws.sock
        try:
            table = self.enc.strings.all_strings()
            if table:
                self._wire_send_locked(fp.encode_strings(table, start_code=1))
        except BaseException:
            try:
                self.sock.close()
            except OSError:
                pass
            self.sock = None
            raise
        self._peer_codes = len(self.enc.strings)
        self.reconnects += 1

    def _wire_send_locked(self, data: bytes) -> None:
        # each protocol frame rides its own ws message; a blob may hold
        # STRINGS + DATA — split on frame boundaries
        frames, rest = fp.parse_buffer(data)
        if rest:
            raise fp.FrameError("sink payload is not whole frames")
        for ftype, payload in frames:
            self._ws._send(fp.encode_frame(ftype, payload))


def register() -> None:
    from ..extension import Example, ExtensionMeta
    register_sink_type("tcp", TcpSink, meta=ExtensionMeta(
        name="tcp", namespace="sink",
        description="batched columnar frame egress over TCP "
                    "(docs/SERVING.md); rides the sink retry/breaker "
                    "machinery",
        examples=(Example(
            "@sink(type='tcp', host='10.0.0.2', port='8008', "
            "on.error='store') define stream Out (sym string, p double);",
            "one DATA frame per emitted batch; exhausted retries "
            "capture the frame for replay"),)))
    register_sink_type("ws", WsSink, meta=ExtensionMeta(
        name="ws", namespace="sink",
        description="batched columnar frame egress over WebSocket",
        examples=(Example(
            "@sink(type='ws', host='10.0.0.2', port='8008') "
            "define stream Out (sym string, p double);",
            "same frames as the tcp sink, wrapped in ws binary "
            "messages"),)))
