"""The load generator of the wire-paced driver: a process of its own that
never touches the chip (the parent starts it with JAX_PLATFORMS=cpu, so that
importing the client can never take it).  It sends columnar frames to the
app's @source(type='tcp') on a fixed schedule, receives the matches back
from the app's @sink(type='tcp') over the same frame protocol, and times
every match from the instant its last event was DUE, not from when it was
sent: a stall counts against every event behind it.

Talks to its parent in JSON lines: the spec on stdin, then one line each way
per step (see drivers/wire_paced.py).  `Schedule` and `detect_latency` are
plain arithmetic, tested against a fake clock.
"""
import json
import socket
import sys
import threading
import time

import numpy as np


class Schedule:
    """Event j of the window is due at start + j / rate; frame k (events
    k*frame .. (k+1)*frame - 1) is due when its last event is."""

    def __init__(self, rate: float, frame: int, seconds: float):
        self.rate, self.frame = float(rate), int(frame)
        self.n_frames = max(1, int(seconds * rate // frame))

    def frame_due(self, k: int) -> float:
        return ((k + 1) * self.frame - 1) / self.rate

    def event_due(self, j):
        return np.asarray(j, np.float64) / self.rate


def detect_latency(sched: Schedule, e3_in_window, received_at) -> np.ndarray:
    """Seconds from the due time of each match's last event (its position
    in the window's stream) to the receipt of the match, both measured from
    the window's start."""
    return np.asarray(received_at, np.float64) - sched.event_due(e3_in_window)


def pace(sched: Schedule, send, clock=time.monotonic, sleep=time.sleep):
    """Send every frame at its due time (never early; late when `send`
    blocked).  Returns (start, seconds each frame was sent after its due
    time)."""
    start = clock()
    late = np.zeros(sched.n_frames)
    for k in range(sched.n_frames):
        due = start + sched.frame_due(k)
        while True:
            wait = due - clock()
            if wait <= 0:
                break
            sleep(min(wait, 0.002) if wait < 0.004 else wait - 0.002)
        send(k)
        late[k] = clock() - due
    return start, late


class Receiver:
    """The consuming end of @sink(type='tcp'): as net/client.py's
    FrameReceiver, but it keeps the columns as they come off the wire and
    stamps every DATA frame with its receipt time."""

    def __init__(self, out_cols: list):
        from siddhi_tpu.core.schema import StreamSchema
        from siddhi_tpu.query.ast import Attribute, AttrType
        self.schema = StreamSchema("Out", tuple(
            Attribute(n, AttrType[t.upper()]) for n, t in out_cols))
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(4)
        self.port = self.sock.getsockname()[1]
        self.frames = []            # (receipt time, ts, {col: values})
        self.rows = 0               # every row received so far: cumulative,
                                    # as the engine's own count of rows emitted
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._accept, daemon=True)
        self._thread.start()

    def _accept(self):
        while not self._stop.is_set():
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            self._serve(conn)

    def _serve(self, conn):
        from siddhi_tpu.net import frame as fp
        read = fp.reader_for(conn)
        try:
            while not self._stop.is_set():
                ftype, payload = fp.read_frame(read)
                if ftype == fp.DATA:
                    now = time.monotonic()
                    ts, cols = fp.decode_data(payload, self.schema,
                                              float64=True)
                    with self._lock:
                        self.frames.append((now, np.array(ts), {
                            k: np.array(v) for k, v in cols.items()}))
                        self.rows += len(ts)
                elif ftype == fp.HELLO:
                    conn.sendall(fp.encode_hello_ok(0))
                elif ftype == fp.PING:
                    conn.sendall(fp.encode_ack(fp.decode_u64(payload)))
                elif ftype == fp.BYE:
                    return
        except (EOFError, ConnectionError, OSError, fp.FrameError):
            pass
        finally:
            conn.close()

    def wait_rows(self, n: int, timeout: float) -> bool:
        """Until `n` rows have been received since the receiver started
        (`take` does not reset the count)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if self.rows >= n:
                    return True
            time.sleep(0.002)
        return False

    def take(self) -> list:
        with self._lock:
            out, self.frames = self.frames, []
        return out

    def stop(self):
        self._stop.set()
        self.sock.close()


def _say(obj):
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _hear() -> dict:
    line = sys.stdin.readline()
    if not line:
        raise SystemExit("wire_gen: the parent went away")
    return json.loads(line)


def main() -> int:
    from benchmark import engine, manifest
    from siddhi_tpu.net import TcpFrameClient

    spec = _hear()
    cell, tr = spec["cell"], spec["cell"]["traffic"]
    frame, warm = int(tr["batch"]), int(tr["warm_batches"])
    sched = Schedule(tr["rate_events_per_s"], frame, spec["seconds"])
    tape = engine.tape_of(cell, spec["seed"])
    tape_mod = manifest.module("tapes", cell["config"]["tape"])
    names = tape_mod.symbol_names(int(tape.params["keys"]))
    recv = Receiver(cell["config"]["out_cols"])
    _say({"receiver_port": recv.port})

    # every frame of the run, built before the window
    feeds = [tape_mod.feed_columns(tape.batch(i), names)
             for i in range(warm + sched.n_frames)]
    cli = TcpFrameClient("127.0.0.1", _hear()["source_port"],
                         cell["config"]["stream"],
                         [tuple(c) for c in cell["config"]["stream_cols"]])
    try:
        for i in range(warm):
            cli.send_batch(*feeds[i])
        cli.barrier(timeout=600)
        _say({"warm_sent": warm})
        recv.wait_rows(_hear()["rows_emitted"], timeout=60)
        warm_frames = recv.take()

        start, late = pace(sched, lambda k: cli.send_batch(*feeds[warm + k]))
        _say({"window_started": start})     # told after: nothing in the way
        cli.barrier(timeout=tr["drain_timeout_s"])
        _say({"sent_all": sched.n_frames})
        owed = _hear()["rows_emitted"]
        drained = recv.wait_rows(owed, timeout=tr["drain_timeout_s"])
    finally:
        cli.close()
    got = recv.take()
    recv.stop()

    def cat(parts, dtype=np.float64):
        return np.concatenate(parts) if parts else np.zeros(0, dtype)

    everything = warm_frames + got
    at = cat([np.full(len(f[1]), f[0] - start) for f in got])
    cols = {c: cat([f[2][c] for f in everything])
            for c, _t in cell["config"]["out_cols"]}
    ts_all = cat([f[1] for f in everything], np.int64)
    ts_win = cat([f[1] for f in got], np.int64)
    lat = detect_latency(sched, tape.event_index(ts_win) - warm * frame, at)
    np.savez(spec["out"], ts=ts_all, lat=lat, late=late, at=at, **cols)
    tenth = max(1, len(lat) // 10)
    by_time = np.argsort(at, kind="stable")
    # where a stall sat, if there was one: the longest waits between two
    # DATA frames of matches, and the frames that went out latest
    arrivals = np.array([f[0] - start for f in got])
    gaps = np.diff(arrivals) if len(arrivals) > 1 else np.zeros(0)
    longest = np.argsort(gaps)[::-1][:3]
    latest = np.argsort(late)[::-1][:3]
    _say({"done": True, "drained": bool(drained), "rows_in_window": len(lat),
          "window_s": float(at.max()) if len(at) else 0.0,
          "frames": sched.n_frames, "events": sched.n_frames * frame,
          "detect_p50_ms": float(np.percentile(lat, 50)) * 1e3
          if len(lat) else None,
          "detect_p95_ms": float(np.percentile(lat, 95)) * 1e3
          if len(lat) else None,
          "detect_p50_ms_first_tenth": float(np.median(
              lat[by_time[:tenth]])) * 1e3 if len(lat) else None,
          "detect_p50_ms_last_tenth": float(np.median(
              lat[by_time[-tenth:]])) * 1e3 if len(lat) else None,
          "longest_waits_between_match_frames_s_at": [
              [round(float(gaps[i]), 3), round(float(arrivals[i]), 2)]
              for i in longest],
          "latest_frames_late_s_at_frame": [
              [round(float(late[k]), 3), int(k)] for k in latest],
          "late_p95_ms": float(np.percentile(late, 95)) * 1e3,
          "late_last_ms": float(late[-1]) * 1e3})
    return 0


if __name__ == "__main__":
    sys.exit(main())
