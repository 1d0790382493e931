"""The paced generator keeps its schedule, and its lateness and detect
latency arithmetic, against a fake clock."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`

import socket
import threading
import time

import numpy as np
import pytest

from benchmark.drivers.wire_gen import (Receiver, Schedule, detect_latency,
                                        pace)


class FakeClock:
    def __init__(self):
        self.now = 1000.0
        self.slept = []

    def __call__(self):
        return self.now

    def sleep(self, s):
        assert s > 0
        self.slept.append(s)
        self.now += s


def test_schedule_counts_whole_frames_only():
    s = Schedule(rate=1000.0, frame=100, seconds=1.05)
    assert s.n_frames == 10
    assert s.frame_due(0) == 0.099 and s.frame_due(9) == 0.999
    assert list(s.event_due([0, 500])) == [0.0, 0.5]


def test_frames_go_out_at_their_due_time_never_early():
    clock = FakeClock()
    s = Schedule(rate=1000.0, frame=100, seconds=0.5)
    sent = []
    start, late = pace(s, lambda k: sent.append((k, clock.now)),
                       clock=clock, sleep=clock.sleep)
    assert start == 1000.0 and [k for k, _t in sent] == list(range(5))
    for k, t in sent:
        assert t >= start + s.frame_due(k)
        assert t - (start + s.frame_due(k)) < 1e-6
    assert np.all(late >= 0) and late.max() < 1e-6


def test_a_stall_makes_later_frames_late_and_the_schedule_does_not_slip():
    clock = FakeClock()
    s = Schedule(rate=1000.0, frame=100, seconds=0.6)

    def send(k):
        if k == 1:
            clock.now += 0.25      # the server held the connection
    start, late = pace(s, send, clock=clock, sleep=clock.sleep)
    due = [s.frame_due(k) for k in range(6)]
    assert abs(late[1] - 0.25) < 1e-6          # sent on time, returned late
    assert abs(late[2] - (due[1] + 0.25 - due[2])) < 1e-6   # sent at once
    assert abs(late[3] - (due[1] + 0.25 - due[3])) < 1e-6
    assert late[5] < 1e-6                      # caught up: due times fixed


def test_detect_latency_counts_from_the_due_time_of_the_last_event():
    s = Schedule(rate=1000.0, frame=100, seconds=1.0)
    lat = detect_latency(s, e3_in_window=[0, 99, 150], received_at=[0.2, 0.2,
                                                                    0.3])
    assert np.allclose(lat, [0.2, 0.101, 0.15])


@pytest.mark.parametrize("warm_rows,late_rows", [(120, 7), (5, 300)])
def test_the_drain_waits_for_matches_that_arrive_after_sent_all(warm_rows,
                                                                late_rows):
    """A fake sink: warm-up matches, then the window's, whose LAST frame
    arrives only after the generator has said `sent_all` and been told what
    is owed.  The wait is for the cumulative count (what the engine's own
    count of rows emitted is), so it cannot return while up to `warm_rows`
    matches are still in flight, whatever `take()` has handed over."""
    from siddhi_tpu.net import frame as fp
    recv = Receiver([("p1", "double"), ("p2", "double"), ("p3", "double")])
    sink = socket.create_connection(("127.0.0.1", recv.port))

    def emit(n, first_ts):
        ts = np.arange(first_ts, first_ts + n, dtype=np.int64)
        sink.sendall(fp.encode_data(ts, [np.full(n, 101.25)] * 3))
    try:
        emit(warm_rows, 0)
        assert recv.wait_rows(warm_rows, timeout=10)
        assert sum(len(f[1]) for f in recv.take()) == warm_rows
        emit(50, 1000)                          # the window's early matches
        owed = warm_rows + 50 + late_rows       # as the engine counts them
        late = threading.Timer(0.3, emit, (late_rows, 2000))
        late.start()
        t0 = time.monotonic()
        assert recv.wait_rows(owed, timeout=10)
        assert time.monotonic() - t0 >= 0.25    # it did wait for the last
        late.join()
        got = recv.take()
        assert sum(len(f[1]) for f in got) == 50 + late_rows
        assert got[-1][1][-1] == 2000 + late_rows - 1
        assert not recv.wait_rows(owed + 1, timeout=0.05)
    finally:
        sink.close()
        recv.stop()
