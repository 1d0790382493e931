"""Threaded frame server — the serving plane's ingest front door.

One `NetServer` accepts both raw-TCP frame streams and WebSocket
connections on the SAME port (the first bytes are sniffed: an HTTP
`GET ` upgrade request takes the RFC-6455 path, anything else is the
raw frame protocol), and can additionally consume shared-memory rings
(net/ring.py) — all three transports funnel through one per-connection
state machine:

    HELLO       -> resolve (app, stream), validate schema, HELLO_OK
    STRINGS     -> extend the connection's code remap (runtime lock)
    DATA        -> decode to numpy views, remap string codes (one
                   gather), admission-control, rt.send_columnar —
                   zero per-event Python on the admit path
    PING        -> feed+flush everything admitted, reply ACK (barrier)
    BYE / EOF   -> close

Admission decisions come from the per-stream AdmissionController
(net/admission.py) shared across every transport feeding the stream.
A 'block' decision stalls THIS reader thread — the socket stops
draining, which is kernel backpressure to the producer — and the
server stops granting CREDIT until feeding resumes.

Deploy/undeploy racing live ingest: `retire(app)` flips the runtime
into a parked state under the feed gate, so a frame is either fully
fed to the live runtime or captured whole into the app's ErrorStore
('net.undeployed') — never dropped, never half-delivered.
"""
from __future__ import annotations

import base64
import hashlib
import socket
import struct
import threading
import time
from typing import Callable, Optional

import numpy as np

from ..core.telemetry import NOOP_SPAN
from ..utils.locks import new_lock, new_rlock
from . import frame as fp
from .admission import ADMIT, AdmissionController, Work
from .ring import ShmRing

_WS_GUID = "258EAFA5-E914-47DA-95CA-C5AB0DC85B11"


# ---------------------------------------------------------------------------
# byte-stream adapters
# ---------------------------------------------------------------------------

class SockStream:
    """Buffered reader with pushback over a socket, so protocol
    sniffing can un-read the bytes it peeked."""

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self._buf = bytearray()         # append-in-place: O(1) amortized

    def push_back(self, data: bytes) -> None:
        self._buf[:0] = data

    def read_exact(self, n: int) -> bytes:
        while len(self._buf) < n:
            b = self.sock.recv(max(4096, n - len(self._buf)))
            if not b:
                raise EOFError("connection closed mid-frame")
            self._buf += b
        out = bytes(self._buf[:n])
        del self._buf[:n]
        return out

    def read_line(self, limit: int = 8192) -> bytes:
        while b"\n" not in self._buf:
            if len(self._buf) > limit:
                raise fp.FrameError("oversized header line")
            b = self.sock.recv(4096)
            if not b:
                raise EOFError("connection closed in headers")
            self._buf += b
        i = self._buf.index(b"\n")
        line = bytes(self._buf[:i])
        del self._buf[:i + 1]
        return line.rstrip(b"\r")

    def write(self, data: bytes) -> None:
        self.sock.sendall(data)


class TcpWire:
    """Buffer-based frame receive over raw TCP: a read timeout mid-frame
    keeps the partial bytes in the buffer, so a slow producer can NEVER
    desync the stream (the old read_exact-per-frame approach discarded
    an already-consumed header when the payload stalled)."""

    def __init__(self, stream: SockStream):
        self.sock = stream.sock
        self._buf = stream._buf         # adopt any sniffed leftovers
        stream._buf = bytearray()

    def write(self, data: bytes) -> None:
        self.sock.sendall(data)

    def poll(self, wait=NOOP_SPAN) -> list:
        """Complete frames available now (possibly []); raises
        EOFError/OSError when the connection dies.  Blocks at most one
        socket-timeout interval, inside `wait` (the connection's
        `net.wait` span: nothing buffered, waiting for the producer)."""
        frames = fp.parse_buffer_inplace(self._buf)
        if frames:
            return frames
        try:
            with wait:
                b = self.sock.recv(1 << 16)
        except socket.timeout:
            return []
        if not b:
            raise EOFError("connection closed")
        self._buf += b
        return fp.parse_buffer_inplace(self._buf)


class WsWire:
    """RFC-6455 server side, buffer-based like TcpWire: complete ws
    messages are unwrapped into a byte stream, complete protocol frames
    parsed out of it; partial data at any layer just waits in its
    buffer.  Writes wrap each protocol frame in one unmasked binary
    message."""

    def __init__(self, stream: SockStream):
        self.sock = stream.sock
        self._ws_buf = stream._buf      # raw bytes (possibly mid-message)
        stream._buf = bytearray()
        self._stream_buf = bytearray()  # unwrapped protocol bytes

    def write_ws(self, opcode: int, payload: bytes) -> None:
        n = len(payload)
        if n < 126:
            hdr = bytes([0x80 | opcode, n])
        elif n < (1 << 16):
            hdr = bytes([0x80 | opcode, 126]) + struct.pack(">H", n)
        else:
            hdr = bytes([0x80 | opcode, 127]) + struct.pack(">Q", n)
        self.sock.sendall(hdr + payload)

    def write(self, data: bytes) -> None:
        self.write_ws(0x2, data)

    def _unwrap(self) -> None:
        while True:
            got = fp.parse_ws_frame_inplace(self._ws_buf)
            if got is None:
                return
            opcode, body = got
            if opcode == 0x8:                 # close
                raise EOFError("websocket closed")
            if opcode == 0x9:                 # ping -> pong
                self.write_ws(0xA, body)
            elif opcode != 0xA:               # binary/text/continuation
                self._stream_buf += body

    def poll(self, wait=NOOP_SPAN) -> list:
        self._unwrap()
        frames = fp.parse_buffer_inplace(self._stream_buf)
        if frames:
            return frames
        try:
            with wait:
                b = self.sock.recv(1 << 16)
        except socket.timeout:
            return []
        if not b:
            raise EOFError("websocket closed")
        self._ws_buf += b
        self._unwrap()
        return fp.parse_buffer_inplace(self._stream_buf)


def ws_handshake(stream: SockStream, first_line: bytes) -> WsWire:
    """Complete the server side of an RFC-6455 upgrade; `first_line` is
    the already-read request line."""
    key = None
    while True:
        line = stream.read_line()
        if not line:
            break
        k, _, v = line.decode("latin1").partition(":")
        if k.strip().lower() == "sec-websocket-key":
            key = v.strip()
    if key is None:
        raise fp.FrameError("websocket upgrade without Sec-WebSocket-Key")
    accept = base64.b64encode(
        hashlib.sha1((key + _WS_GUID).encode()).digest()).decode()
    stream.write(
        b"HTTP/1.1 101 Switching Protocols\r\n"
        b"Upgrade: websocket\r\nConnection: Upgrade\r\n"
        b"Sec-WebSocket-Accept: " + accept.encode() + b"\r\n\r\n")
    return WsWire(stream)


# ---------------------------------------------------------------------------
# per-connection state machine
# ---------------------------------------------------------------------------

class Connection:
    """One negotiated ingest connection (TCP, WS, or ring)."""

    def __init__(self, server: "NetServer", label: str,
                 send: Optional[Callable[[bytes], None]] = None):
        self.server = server
        self.label = label
        self.send = send                # None: no backchannel (ring)
        # replication link state: once REPL_SUBSCRIBE arrives the
        # connection is repl-dedicated — a WalShipper thread writes
        # records down it while the serve loop keeps reading acks, so
        # every write goes through _wlock
        self._wlock = new_lock("Connection._wlock")
        self.closed = False
        self._shipper = None
        self._repl_coord = None
        self.rt = None
        self.stream_id: Optional[str] = None
        self.schema = None
        self.ctrl: Optional[AdmissionController] = None
        self.remap = fp.StringRemap()
        # store-query egress dictionary (RESULT string columns): the
        # mirror of `remap` — codes WE assign, shipped to the peer as
        # STRINGS deltas ahead of each RESULT.  `_egress_synced` is the
        # first code the peer has NOT mapped yet; it advances only after
        # a successful encode, so a query that failed mid-encode re-ships
        # its orphaned registrations with the next result
        self._egress = fp.WireStringTable()
        self._egress_synced = 1
        self.credit_chunk = 0
        self._since_credit = 0
        self._str_cols: list = []
        self.frames = 0
        self.events = 0
        # producer-stamped trace context (TRACE frame) for the NEXT
        # DATA frame on this connection
        self._next_trace = None

    def wait_span(self):
        """The `net.wait` span of this connection's next blocking read
        (`NOOP_SPAN` before HELLO binds a runtime)."""
        return NOOP_SPAN if self.rt is None else self.rt.span("net.wait")

    # -- frame dispatch -----------------------------------------------------

    def on_frame(self, ftype: int, payload: bytes) -> bool:
        """Handle one frame; returns False when the connection should
        close."""
        if ftype == fp.BYE:
            return False
        if ftype == fp.HELLO:
            self._on_hello(fp.decode_hello(payload))
            return True
        if ftype == fp.REPL_SUBSCRIBE:
            self._on_repl_subscribe(fp.decode_repl_subscribe(payload))
            return True
        if ftype in (fp.REPL_ACK, fp.REPL_HEARTBEAT):
            self._on_repl_status(fp.decode_repl_status(payload), ftype)
            return True
        if ftype == fp.QUERY:
            # dispatched BEFORE the rt-None check: a query-only
            # connection never HELLOs (it names its app in the frame)
            self._on_query(payload)
            return True
        if self.rt is None:
            raise fp.FrameError(
                f"{fp.type_name(ftype)} before HELLO on {self.label}")
        if ftype == fp.STRINGS:
            start, new = fp.decode_strings(payload)
            with self.rt._lock:         # StringTable writes are shared
                self.remap.extend(start, new, self.rt.strings)
            return True
        if ftype == fp.TRACE:
            # wire trace context: adopt the producer's id for the next
            # DATA frame (always traced, bypassing sampling)
            self._next_trace = fp.decode_trace(payload)
            return True
        if ftype == fp.DATA:
            self._on_data(payload)
            return True
        if ftype == fp.PING:
            token = fp.decode_u64(payload)
            self.pump()
            wal0 = getattr(self.rt, "wal", None)
            if wal0 is not None and self.ctrl is not None:
                # durable-ACK: frames parked by the 'oldest' policy (or
                # mid-feed on another thread) are memory-only — acking
                # past them would bound the producer's retransmit
                # buffer below data that can still vanish.  Wait for
                # the park to drain (token refills feed it; sheds land
                # accounted in the ErrorStore); shutdown mid-wait
                # closes WITHOUT acking.
                while self.ctrl.pending_count():
                    if self.server.stopping():
                        return False
                    time.sleep(0.005)
                    self.pump()
            self.rt.flush()
            # durable-ACK contract (docs/SERVING.md): under
            # @app:durability an ACK means every frame before the PING
            # is in the write-ahead log AND fsynced — the producer may
            # discard its retransmit buffer.  ('batch' policy frames
            # are flushed per append; this barrier is the fsync.)
            wal = getattr(self.rt, "wal", None)
            if wal is not None:
                try:
                    wal.barrier()
                except Exception as e:
                    # a failed barrier must NOT ack: the producer would
                    # discard frames the log cannot promise.  Fatal to
                    # the connection (like a desync) — the producer
                    # reconnects and retransmits from its last ACK.
                    raise fp.FrameDesync(
                        f"durability barrier failed: {e}") from e
                # semi-sync replication moves the durable-ACK barrier
                # to "local fsync + standby append-ack": the producer's
                # retransmit buffer may only be discarded once the
                # frames exist on BOTH machines.  A timeout (or no
                # standby, unless degrade='async') fails the barrier —
                # lying here would turn machine loss into silent loss.
                coord = getattr(self.rt, "replication", None)
                if coord is not None and coord.config.mode == "semi-sync" \
                        and coord.role == "primary":
                    if not coord.wait_ack(wal.watermark()):
                        raise fp.FrameDesync(
                            f"semi-sync barrier: no standby append-ack "
                            f"within {coord.config.ack_timeout_s}s "
                            f"({coord.standbys()} standby(s) attached)")
            self._reply(fp.encode_ack(token))
            return True
        raise fp.FrameError(
            f"unexpected {fp.type_name(ftype)} frame on {self.label}")

    def _on_hello(self, hello: dict) -> None:
        try:
            rt, ctrl = self.server.resolve(hello.get("app"), hello["stream"])
        except KeyError as e:
            # unknown app/stream: a protocol-level rejection (ERROR
            # frame + close), not a server-side crash
            raise fp.FrameError(str(e).strip("'\"")) from None
        schema = rt.schemas.get(hello["stream"])
        if schema is None:
            raise fp.FrameError(f"unknown stream {hello['stream']!r}")
        fp.validate_hello_schema(hello, schema)
        if self.rt is not None:
            # re-negotiation: the remap ties THIS connection's string
            # codes to the previously bound runtime's table, so it is
            # stale either way — the peer must re-ship its dictionary
            # (explicit start codes make the replay idempotent; a
            # continuation without one trips the delta-gap check loudly
            # instead of ingesting wrong strings), and credit
            # accounting restarts with the new negotiation
            self.remap = fp.StringRemap()
            self._since_credit = 0
        self.rt, self.schema, self.ctrl = rt, schema, ctrl
        self.stream_id = hello["stream"]
        from ..query.ast import AttrType
        self._str_cols = [a.name for a in schema.attributes
                          if a.type == AttrType.STRING]
        self.credit_chunk = self.server.credit if hello.get("credit") else 0
        self._reply(fp.encode_hello_ok(self.credit_chunk))

    # -- replication link (net/repl.py WalShipper) ---------------------------

    def _on_repl_subscribe(self, sub: dict) -> None:
        if self.send is None:
            raise fp.FrameError(
                "replication needs a duplex transport (not a ring)")
        if self._shipper is not None:
            raise fp.FrameError(
                f"duplicate REPL_SUBSCRIBE on {self.label}")
        try:
            rt = self.server.repl_resolve(sub["app"])
        except KeyError as e:
            raise fp.FrameError(str(e).strip("'\"")) from None
        if getattr(rt, "is_standby", lambda: False)():
            raise fp.FrameError(
                f"app {sub['app']!r} is itself a standby replica — "
                f"subscribe to the primary")
        coord = rt._ensure_replication(default=True)
        if coord is None or getattr(rt, "wal", None) is None:
            raise fp.FrameError(
                f"app {sub['app']!r} has no live WAL to replicate "
                f"(@app:durability required)")
        from .repl import WalShipper
        self.rt = rt                    # repl-dedicated binding
        self._repl_coord = coord
        self._shipper = WalShipper(
            rt, coord, self._reply, sub,
            stop=lambda: self.server.stopping() or self.closed).start()

    def _on_repl_status(self, status: dict, ftype: int) -> None:
        coord = self._repl_coord
        if coord is None:
            raise fp.FrameError(
                f"{fp.type_name(ftype)} before REPL_SUBSCRIBE on "
                f"{self.label}")
        wal = getattr(self.rt, "wal", None)
        if wal is not None and status["generation"] > wal.generation():
            # the standby has been promoted past us: we are deposed —
            # fatal, and every later local append is suspect
            coord.rejected_generation += 1
            raise fp.FrameDesync(
                f"fenced: standby at generation {status['generation']} "
                f"> ours ({wal.generation()}) — this node was deposed")
        if ftype == fp.REPL_ACK:
            coord.on_ack(status["watermark"])
        else:
            coord.on_heartbeat(status["watermark"])

    # -- store queries (QUERY -> STRINGS? + RESULT) ---------------------------

    def _on_query(self, payload: bytes) -> None:
        token, app, text = fp.decode_query(payload)
        if self.send is None:
            raise fp.FrameError(
                "QUERY needs a duplex transport (not a ring)")
        self.server._count(store_queries=1)
        try:
            rt = (self.server.query_resolve(app) if app is not None
                  else self.rt)
            if rt is None:
                raise fp.FrameError(
                    "QUERY names no app and no HELLO bound one")
            # compile (cached per query text in the runtime) + execute
            # under the feed gate — the result is a consistent snapshot
            # against every transport feeding this runtime
            schema, rows = rt.query_with_schema(text)
            blob = self._encode_result(token, schema, rows)
        except Exception as e:
            # compile/execute/resolve failures ride RESULT, not ERROR,
            # so the client correlates them by token — and a bad query
            # never costs the producer its ingest connection
            msg = str(e).strip("'\"") or type(e).__name__
            blob = fp.encode_result(token, {"error": msg})
        self._reply(blob)

    def _encode_result(self, token: int, schema, rows) -> bytes:
        """(optional STRINGS delta +) RESULT frame bytes for one store
        query's out_schema + rows.  Doubles ship float64 (exactness
        beats the ingest plane's f32 compaction here); numeric nulls
        encode NaN/0, string nulls code 0."""
        from ..core.schema import dtype_of
        from ..query.ast import AttrType
        meta_cols = [[a.name, a.type.name.lower()]
                     for a in schema.attributes]
        ts = np.fromiter((r[0] for r in rows), dtype=np.int64,
                         count=len(rows))
        cols = []
        for j, a in enumerate(schema.attributes):
            vals = [r[1][j] for r in rows]
            if a.type == AttrType.STRING:
                codes, _new = self._egress.encode_column(vals)
                cols.append(codes)
                continue
            dt = np.dtype(dtype_of(a.type, float64=True))
            if dt.kind == "O":
                raise fp.FrameError(
                    f"RESULT object column {a.name!r} cannot ride the "
                    f"wire")
            if dt.kind == "f":
                arr = np.array([np.nan if v is None else v for v in vals],
                               dtype=dt)
            else:
                arr = np.array([0 if v is None else v for v in vals],
                               dtype=dt)
            cols.append(arr)
        body = fp.encode_data_payload(ts, cols)
        out = []
        delta = self._egress.strings_from(self._egress_synced)
        if delta:
            out.append(fp.encode_strings(delta,
                                         start_code=self._egress_synced))
        self._egress_synced = len(self._egress)
        out.append(fp.encode_result(token, {"cols": meta_cols}, body))
        # one write: the delta can never arrive after the RESULT that
        # needs it, even with the WalShipper sharing this wire
        return b"".join(out)

    def _on_data(self, payload: bytes) -> None:
        rt = self.rt
        try:
            rt.inject("net.decode", self.stream_id)
        except Exception as e:
            # injected decode fault: connection-fatal like a corrupt
            # frame off the wire (faults.py POINTS) — mapped so the
            # serve loop accounts a protocol error instead of the
            # RuntimeError escaping and killing the thread unhandled
            raise fp.FrameDesync(f"decode fault: {e}") from e
        # frame tracing: a producer-stamped id (TRACE frame) always
        # traces; otherwise the runtime tracer makes the sampling call.
        # The handle rides the Work so a parked ('oldest') frame fed
        # later on another thread keeps its tree.
        tc, self._next_trace = self._next_trace, None
        h = None
        tracer = getattr(rt, "tracing", None)
        if tracer is not None:
            h = tracer.begin_frame(
                self.stream_id, trace_id=None if tc is None else tc[0],
                parent=0 if tc is None else tc[1])
        with rt.span("net.decode", handle=h, bytes=len(payload)):
            ts, cols = fp.decode_data(payload, self.schema)
            for name in self._str_cols:     # one gather per string column
                cols[name] = self.remap.apply(cols[name])
        n = int(ts.shape[0])
        self.frames += 1  # lint: unlocked-ok (single serve-thread writer; _wlock only serializes wire writes)
        self.events += n  # lint: unlocked-ok (single serve-thread writer; _wlock only serializes wire writes)
        work = self.server.make_work(rt, self.stream_id, self.schema,
                                     ts, cols, len(payload), trace=h)
        # the admit span covers the admission decision including any
        # block-policy wait; the park of a queued frame and the wait for
        # the runtime gate are its queue_wait span, which starts here
        with rt.span("admit", handle=h, events=n) as sp:
            d = self.ctrl.submit(work, stop=self.server.stopping)
            sp.note(action=d.action)
        work.t_admit = sp.t_end
        for w in d.ready:
            # guarded: queued work is mixed-provenance (REST batches
            # share the controller and their feeds can raise, e.g. a
            # type-bad value surfacing at flush) — an exception here
            # must capture to the ErrorStore, not kill this connection
            self.ctrl.feed_safely(w)
        if d.action == ADMIT:
            work.feed()                 # our own make_work: self-captures
        self._grant_credit()

    def pump(self) -> None:
        """Feed any pending ('oldest' policy) work whose tokens
        refilled — called between frames and on idle ticks."""
        if self.ctrl is not None:
            for w in self.ctrl.pump():
                self.ctrl.feed_safely(w)

    def _grant_credit(self) -> None:
        # credit is granted AFTER the frame fed (the call site above) —
        # under @app:durability the feed path appended (and, for
        # 'fsync', synced) the WAL first, so credit never outruns the
        # log on the admit path.  The queued ('oldest') path can grant
        # before its park drains; ACK — the PING barrier — is the
        # durability signal producers must trust for retransmit.
        if self.send is None or not self.credit_chunk:
            return
        self._since_credit += 1  # lint: unlocked-ok (single serve-thread writer; _wlock only serializes wire writes)
        if self._since_credit >= max(1, self.credit_chunk // 2):
            self._reply(fp.encode_credit(self._since_credit))
            self.server._count(credit_granted=self._since_credit)
            self._since_credit = 0

    def _reply(self, data: bytes) -> None:
        # locked: on a replication link the WalShipper thread and the
        # serve loop both write to the same wire
        if self.send is not None:
            with self._wlock:
                self.send(data)


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------

class NetServer:
    """Threaded TCP/WS frame listener + shm-ring consumers, feeding one
    or many runtimes through `resolve_fn(app, stream) ->
    (rt, AdmissionController)`."""

    def __init__(self, resolve_fn: Callable, host: str = "127.0.0.1",
                 port: int = 0, credit: int = 64, name: str = "siddhi-net",
                 listen: bool = True,
                 repl_resolve: Optional[Callable] = None,
                 query_resolve: Optional[Callable] = None):
        """`listen=False` builds a listener-less server — no TCP socket
        at all — for transports that only need the connection/feed-gate
        machinery (shm-ring consumers via attach_ring).  `repl_resolve`
        maps an app name to its runtime for REPL_SUBSCRIBE links
        (raising KeyError rejects the subscription); None disables
        replication on this front door.  `query_resolve` maps an app
        name to its runtime for QUERY frames naming an app explicitly
        (the HELLO-bound runtime serves app-less queries either way);
        None restricts store queries to HELLO-bound connections."""
        self._resolve = resolve_fn
        self._repl_resolve = repl_resolve
        self._query_resolve = query_resolve
        self.credit = int(credit)
        self.name = name
        self._sock = None
        self.host, self.port = host, None
        if listen:
            self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            self._sock.bind((host, int(port)))
            self._sock.listen(64)
            # a cross-thread close() does not reliably wake a blocking
            # accept() on Linux: poll with a short timeout instead, so
            # stop() always unblocks the accept loop promptly
            self._sock.settimeout(0.2)
            self.host, self.port = self._sock.getsockname()[:2]
        self._stop = threading.Event()
        self._accept_thread: Optional[threading.Thread] = None
        self._threads: list = []
        self._conn_socks: list = []
        self._rings: list = []          # (ring, thread)
        self._lock = new_lock("NetServer._lock")
        # counters (server-level; per-stream counters live on the
        # AdmissionControllers)
        self.connections = 0
        self.open_connections = 0
        self.ws_connections = 0
        self.frames_in = 0
        self.events_in = 0
        self.bytes_in = 0
        self.credit_granted = 0
        self.protocol_errors = 0
        self.store_queries = 0

    # -- wiring -------------------------------------------------------------

    def resolve(self, app: Optional[str], stream: str):
        return self._resolve(app, stream)

    def repl_resolve(self, app: str):
        if self._repl_resolve is None:
            raise KeyError(
                f"replication is not enabled on this endpoint "
                f"(no repl_resolve for app {app!r})")
        return self._repl_resolve(app)

    def query_resolve(self, app: str):
        if self._query_resolve is None:
            raise KeyError(
                f"named-app store queries are not enabled on this "
                f"endpoint (no query_resolve for app {app!r}) — "
                f"HELLO-bind the connection instead")
        return self._query_resolve(app)

    def stopping(self) -> bool:
        return self._stop.is_set()

    def _gate_of(self, rt) -> threading.RLock:
        """The feed-vs-retire gate for ONE runtime.  It lives ON the
        runtime (like the retired mark) for two reasons: independent
        apps served by one front door must not serialize their ingest
        on a shared lock, and a runtime fed by SEVERAL servers (its own
        @source port plus the service front door) needs retire() to
        serialize against every feeder, not just this one."""
        gate = getattr(rt, "_net_gate", None)
        if gate is None:
            with self._lock:
                gate = getattr(rt, "_net_gate", None)
                if gate is None:
                    gate = rt._net_gate = new_rlock(
                        "SiddhiAppRuntime._net_gate")
        return gate

    def retire(self, rt) -> None:
        """Park a runtime (undeploy/redeploy): frames already admitted
        for THIS runtime land whole in its ErrorStore from now on.  The
        mark lives ON the runtime object (not in an id-keyed map — a
        collected runtime's id() could be recycled by a later deploy and
        silently divert ITS ingest), so a redeploy under the same name
        serves live through the new runtime while old connections'
        frames park instead of feeding the zombie.  Serialized against
        feeds by the runtime's gate — no frame is mid-feed when this
        returns."""
        with self._gate_of(rt):
            rt._net_retired_store = rt.error_store

    def make_work(self, rt, stream_id: str, schema, ts, cols,
                  nbytes: int, trace=None) -> Work:
        from ..core.batch import rows_of_columns
        gate = self._gate_of(rt)

        def _feed_inner(rt=rt, stream_id=stream_id, ts=ts, cols=cols):
            # sink deliveries staged by this feed are deferred past the
            # gate (runtime._flush_sink_outbox honors `defer_sink`): a
            # sink retry backoff sleeping under the gate would stall
            # retire()/undeploy for the whole backoff schedule
            tls = rt._trace_tls
            tls.defer_sink += 1
            try:
                with rt.span("queue_wait", t0=work.t_admit):
                    gate.acquire()
                try:
                    store = getattr(rt, "_net_retired_store", None)
                    if store is not None:
                        store.add(stream_id, "net.undeployed",
                                  "frame admitted before undeploy",
                                  rt.now_ms(),
                                  events=rows_of_columns(schema, ts, cols,
                                                         rt.strings))
                        return
                    try:
                        rt.inject("net.feed", stream_id)
                        rt.send_columnar(stream_id, cols, ts)
                    except Exception as e:
                        # an admitted frame must NEVER vanish: capture
                        # whole — unless the WAL append path already did
                        # (a second entry would double-ingest on replay)
                        if not getattr(e, "_wal_captured", False):
                            rt.error_store.add(
                                stream_id, "net.feed", e, rt.now_ms(),
                                events=rows_of_columns(schema, ts, cols,
                                                       rt.strings))
                        rt.stats.on_fault(stream_id, "net.feed")
                finally:
                    gate.release()
            finally:
                tls.defer_sink -= 1
            rt._flush_sink_outbox()

        if trace is None:
            feed = _feed_inner
        else:
            def feed(rt=rt):
                # install the frame's trace handle on WHICHEVER thread
                # ends up feeding (connection, scheduler pump, another
                # connection's drain): runtime._freeze picks it up so
                # wal.append/freeze/dispatch spans join the same tree
                prev = rt._set_trace(trace)
                try:
                    _feed_inner()
                finally:
                    rt._trace_tls.handle = prev

        work = Work(n=int(ts.shape[0]), nbytes=nbytes, feed=feed,
                    rows=lambda: rows_of_columns(schema, ts, cols,
                                                 rt.strings),
                    stream_id=stream_id, trace=trace)
        return work

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "NetServer":
        if self._sock is not None:
            self._accept_thread = threading.Thread(
                target=self._accept_loop, name=f"{self.name}-accept",
                daemon=True)
            self._accept_thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        with self._lock:
            # snapshot sockets AND threads under the lock: the accept
            # loop rebuilds self._threads concurrently, and a join list
            # read outside the lock could miss the newest connection
            # thread (surfaced by the SL03 lockset self-analysis)
            socks = list(self._conn_socks)
            conn_threads = list(self._threads)
        for s in socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass
        deadline = time.monotonic() + timeout
        threads = ([self._accept_thread] if self._accept_thread else []) \
            + [t for _, t in self._rings] + conn_threads
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        for ring, _ in self._rings:
            ring.close()
            if ring.owner:
                ring.unlink()
        self._rings.clear()

    # -- TCP/WS path --------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                sock, addr = self._sock.accept()
            except socket.timeout:
                continue                # poll the stop flag
            except OSError:
                return                  # listener closed
            try:
                # barrier-critical small frames (durable ACKs, the
                # semi-sync replication handshake) must not sit out a
                # Nagle/delayed-ACK round trip
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass
            t = threading.Thread(
                target=self._serve_conn, args=(sock, addr),
                name=f"{self.name}-conn", daemon=True)
            with self._lock:
                self._conn_socks.append(sock)
                self._threads = [th for th in self._threads
                                 if th.is_alive()] + [t]
            t.start()

    def _count(self, **deltas) -> None:
        """Counter updates from connection/ring threads — locked, so
        concurrent producers never lose increments."""
        with self._lock:
            for key, d in deltas.items():
                setattr(self, key, getattr(self, key) + d)

    def _count_frame(self, ftype: int, payload) -> None:
        if payload is None:             # corrupt frame (CRC rejected)
            self._count(frames_in=1)
            return
        ev = struct.unpack_from("<I", payload, 0)[0] \
            if ftype == fp.DATA and len(payload) >= 4 else 0
        self._count(frames_in=1, bytes_in=len(payload), events_in=ev)

    HANDSHAKE_TIMEOUT_S = 10.0

    def _serve_conn(self, sock: socket.socket, addr) -> None:
        self._count(connections=1, open_connections=1)
        label = f"{addr[0]}:{addr[1]}"
        conn: Optional[Connection] = None
        try:
            # sniff + ws upgrade get a generous deadline (a pooled
            # producer may connect before it has data); the frame loop
            # then drops to short timeouts so idle ticks drive pump()
            sock.settimeout(self.HANDSHAKE_TIMEOUT_S)
            stream = SockStream(sock)
            wire = self._sniff(stream)
            sock.settimeout(0.2)
            conn = Connection(self, label, send=wire.write)
            while not self._stop.is_set():
                frames = wire.poll(conn.wait_span())  # buffer-based: a
                if not frames:      # timeout mid-frame can never desync
                    conn.pump()
                    continue
                for ftype, payload in frames:
                    self._count_frame(ftype, payload)
                    if payload is None:
                        # CRC failure: the frame was consumed whole by
                        # its length prefix, so the stream is still
                        # aligned — reject THIS frame, keep serving
                        self._count(protocol_errors=1)
                        try:
                            wire.write(fp.encode_error(
                                f"checksum mismatch on "
                                f"{fp.type_name(ftype)} frame (rejected)"))
                        except OSError:
                            pass
                        continue
                    try:
                        if not conn.on_frame(ftype, payload):
                            return
                    except fp.FrameDesync:
                        raise
                    except fp.FrameError as e:
                        self._count(protocol_errors=1)
                        try:
                            wire.write(fp.encode_error(str(e)))
                        except OSError:
                            pass
                        if conn.rt is None or ftype == fp.HELLO:
                            # no negotiated binding (or a rejected
                            # re-negotiation): nothing sound can follow
                            return
                        # payload-level error on a live binding
                        # (truncated DATA, bad STRINGS delta, ...):
                        # framing is intact — drop the frame, carry on
        except socket.timeout:
            pass                        # no HELLO within the handshake
        except (EOFError, ConnectionError, OSError):  # deadline
            pass                        # disconnects (mid-frame too) are
        except fp.FrameError:           # normal serving-plane weather
            self._count(protocol_errors=1)
        finally:
            if conn is not None:
                conn.closed = True      # stops a WalShipper on this link
                if conn._shipper is not None:
                    conn._shipper.join(timeout=2.0)
                try:
                    conn.pump()
                except Exception:
                    pass
            self._count(open_connections=-1)
            try:
                sock.close()
            except OSError:
                pass
            with self._lock:
                if sock in self._conn_socks:
                    self._conn_socks.remove(sock)

    def _sniff(self, stream: SockStream):
        head = stream.read_exact(4)
        if head == b"GET ":
            self._count(ws_connections=1)
            first = head + stream.read_line()
            return ws_handshake(stream, first)
        stream.push_back(head)
        return TcpWire(stream)

    # -- shm-ring path ------------------------------------------------------

    def attach_ring(self, ring: ShmRing, label: Optional[str] = None) -> None:
        """Consume a shared-memory ring on a dedicated thread.  The ring
        carries the same frames; there is no backchannel, so credit is
        the ring's own occupancy (a full ring blocks the producer)."""
        conn = Connection(self, label or f"shm:{ring.name}", send=None)

        def loop():
            while not self._stop.is_set():
                with conn.wait_span():
                    data = ring.pop(timeout=0.1)
                if data is None:
                    conn.pump()
                    continue
                try:
                    frames, rest = fp.parse_buffer(data)
                    if rest:
                        raise fp.FrameError(
                            "ring slot holds a truncated frame")
                    for ftype, payload in frames:
                        self._count_frame(ftype, payload)
                        if payload is None:     # CRC-rejected frame
                            self._count(protocol_errors=1)
                            continue
                        if not conn.on_frame(ftype, payload):
                            # BYE ends the PRODUCER, not the ring: the
                            # consumer outlives it so the next producer
                            # attaching to the same ring (it re-HELLOs
                            # to rebind) isn't left pushing into a ring
                            # nobody drains
                            conn.pump()
                except fp.FrameError:
                    self._count(protocol_errors=1)

        t = threading.Thread(target=loop, name=f"{self.name}-ring",
                             daemon=True)
        self._rings.append((ring, t))
        t.start()

    # -- telemetry ----------------------------------------------------------

    def metrics(self) -> dict:
        # wire_* are transport-level totals (control frames included);
        # the per-stream ingest counters live on the AdmissionControllers
        # under their own frames_in/events_in/bytes_in names
        m = {**({"port": self.port} if self.port is not None else {}),
             "connections": self.connections,
             "open_connections": self.open_connections,
             "ws_connections": self.ws_connections,
             "wire_frames": self.frames_in,
             "wire_events": self.events_in,
             "wire_bytes": self.bytes_in,
             "credit_granted": self.credit_granted,
             "protocol_errors": self.protocol_errors,
             "store_queries": self.store_queries}
        if self._rings:
            occ = [r.occupancy() for r, _ in self._rings]
            m["rings"] = len(self._rings)
            m["ring_occupancy"] = sum(u for u, _ in occ)
            m["ring_slots"] = sum(s for _, s in occ)
        return m
