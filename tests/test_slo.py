"""The AIMD SLO controller (core/slo.py) and its runtime wiring, plus
the geometry-invariance differentials per device plan family: outputs
must not depend on batch size, pipeline depth, chunk lanes or lane
packing, each reached the way an app reaches it, by annotation."""
import time

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.slo import SLOController


def q4(x):
    return np.round(np.asarray(x) * 4) / 4


def tape(n, keys=8, seed=0, dt_ms=25):
    rng = np.random.default_rng(seed)
    return ({"sym": np.asarray([f"K{i}" for i in
                                rng.integers(0, keys, n)]),
             "p": q4(rng.uniform(90.0, 130.0, n)),
             "v": rng.integers(1, 100, n).astype(np.int32)},
            1_700_000_000_000 + np.arange(n, dtype=np.int64) * dt_ms)


def run_geometry(app, feeds, batch, depth=None, chunk_lanes=None,
                 capacity_switch=None):
    """Feed `feeds` ({stream: (cols, ts)}) in fixed cross-stream quanta,
    sub-chunked at `batch`, with depth/chunk_lanes annotated onto the
    app; returns the full decoded output row/ts sequence.
    `capacity_switch=(at_quantum, new_batch)` exercises a mid-stream
    SLO-controller decision (_apply_batch_target)."""
    head = ""
    if depth is not None:
        head += f"@app:devicePipeline({depth})\n"
    if chunk_lanes is not None:
        head += f"@app:deviceChunkLanes({chunk_lanes})\n"
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(head + app)
    out = []
    rt.add_batch_callback("Out", lambda b: out.extend(
        (int(ts), row) for ts, row in zip(b.timestamps,
                                          b.rows(rt.strings))))
    rt.start()
    handlers = {s: rt.input_handler(s) for s in feeds}
    Q = 128                     # fixed cross-stream interleave quantum
    n = min(len(ts) for _c, ts in feeds.values())
    for qi, q0 in enumerate(range(0, n, Q)):
        if capacity_switch is not None and qi == capacity_switch[0]:
            rt._apply_batch_target(capacity_switch[1])
            batch = capacity_switch[1]
        for s, (cols, ts) in feeds.items():
            hi_q = min(q0 + Q, n)
            for lo in range(q0, hi_q, batch):
                hi = min(lo + batch, hi_q)
                handlers[s].send_batch(
                    {k: v[lo:hi] for k, v in cols.items()}, ts[lo:hi])
    rt.flush()
    mgr.shutdown()
    return out


# ---------------------------------------------------------------------------
# geometry-invariance differentials: same tape, >= 3 geometries per plan
# family -> byte-identical outputs
# ---------------------------------------------------------------------------

FILTER_APP = """
define stream S (sym string, p double, v int);
@info(name='q') from S[p > 100] select sym, p, v * 2 as v2 insert into Out;
"""

WINDOW_APP = """
@app:deviceWindows('auto')
define stream S (sym string, p double, v int);
@info(name='q') from S#window.length(64)
select sym, sum(p) as sp, count() as c group by sym insert into Out;
"""

PATTERN_APP = """
@app:devicePatterns('prefer')
define stream S (sym string, p double, v int);
@info(name='q') from every e1=S[p > 100] -> e2=S[p > e1.p] within 1 sec
select e1.p as p1, e2.p as p2 insert into Out;
"""

JOIN_APP = """
define stream S (sym string, p double, v int);
define stream T (sym string, p double, v int);
@info(name='q') from S#window.length(32) as a join T#window.length(32) as b
on a.sym == b.sym and a.p > b.p
select a.sym as s, a.p as lp, b.p as rp insert into Out;
"""


@pytest.mark.parametrize("app,two_streams,geos", [
    (FILTER_APP, False, [(64, 0, None), (256, 2, None), (1024, 3, None)]),
    (WINDOW_APP, False, [(64, 0, None), (256, 2, None), (512, 3, None)]),
    (PATTERN_APP, False, [(128, 0, 8), (512, 2, 16), (1024, 3, 64)]),
    (JOIN_APP, True, [(32, 0, None), (64, 2, None), (128, 3, None)]),
], ids=["filter", "window", "pattern", "join"])
def test_geometry_invariance(app, two_streams, geos):
    n = 1024 if not two_streams else 512
    feeds = {"S": tape(n, seed=0)}
    if two_streams:
        feeds["T"] = tape(n, seed=1)
    ref = None
    for batch, depth, lanes in geos:
        out = run_geometry(app, feeds, batch, depth=depth,
                           chunk_lanes=lanes)
        assert out, f"geometry ({batch},{depth},{lanes}): no outputs"
        if ref is None:
            ref = out
        else:
            assert out == ref, (
                f"geometry ({batch},{depth},{lanes}) diverged: "
                f"{len(out)} vs {len(ref)} rows")


def test_a_join_with_side_filters_holds_depth_0_whatever_is_annotated():
    """A join with side filters must sync per flush (_can_pipeline is
    False): an annotated depth never overrides that, and the controller's
    batch target still lands on the runtime."""
    app = """
    @app:devicePipeline(3)
    define stream S (sym string, p double, v int);
    define stream T (sym string, p double, v int);
    from S[p > 100]#window.length(8) as a join T#window.length(8) as b
    on a.sym == b.sym select a.sym as s, b.p as bp insert into Out;
    """
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(app)
    plan = next(p for p in rt._plans
                if type(p).__name__ == "DeviceJoinPlan")
    assert rt.geometry["pipeline_depth"] == (3, "annotation")
    assert not plan._can_pipeline
    assert plan.pipeline_depth == 0 and plan._pipe.depth == 0
    rt._apply_batch_target(512)
    assert rt.batch_capacity == 512
    mgr.shutdown()


def test_controller_decision_is_output_invariant():
    """A mid-stream _apply_batch_target (what an SLO decision does at a
    flush boundary) must not change outputs."""
    feeds = {"S": tape(1024, seed=2)}
    ref = run_geometry(FILTER_APP, feeds, 128)
    switched = run_geometry(FILTER_APP, feeds, 128,
                            capacity_switch=(4, 512))
    assert switched == ref


# ---------------------------------------------------------------------------
# the AIMD SLO controller
# ---------------------------------------------------------------------------

def drive(c, rate_eps, seconds, clock):
    """Virtual-clock closed loop: per-batch latency = fixed floor + time
    to fill the controller's batch target at the offered rate."""
    end = clock + seconds
    while clock < end:
        latency = 0.002 + c.batch_target / rate_eps
        clock += latency
        c.observe(latency)
        c.maybe_decide(clock)
    return clock


def test_aimd_convergence_under_rate_step():
    c = SLOController(target_s=0.025, initial_batch=4096, min_batch=32,
                      decide_every_s=0.25, min_samples=4)
    clock = drive(c, 100_000, 30.0, 0.0)
    # at 100k eps the sweet spot is batch ~2300 (0.023s fill): AIMD must
    # sit inside 2x target with a settled batch
    assert c.last_p99_s <= 2 * 0.025
    assert 1000 <= c.batch_target <= 2400
    settled = c.batch_target
    # STEP the offered rate down 5x: the old batch now takes ~115ms to
    # fill -> multiplicative decrease kicks in within a few windows
    clock = drive(c, 20_000, 30.0, clock)
    assert c.batch_target < settled / 2
    assert c.last_p99_s <= 2 * 0.025, \
        f"controller failed to re-converge: p99={c.last_p99_s * 1e3:.1f}ms"
    assert c.counts["decrease"] >= 1 and c.counts["increase"] >= 2
    # hysteresis: the band between target*(1-h) and target produces
    # hold decisions rather than oscillation
    assert c.counts["hold"] >= 1
    # decision log is telemetry-visible and bounded
    m = c.metrics()
    assert m["decision_log"] and m["decisions"]["decrease"] >= 1
    assert all(d["action"] in ("increase", "decrease", "hold")
               for d in m["decision_log"])
    # step back UP: additive increase recovers throughput
    before = c.batch_target
    drive(c, 100_000, 20.0, clock)
    assert c.batch_target > before


def test_controller_bounds_and_window_gating():
    c = SLOController(target_s=0.010, initial_batch=64, min_batch=32,
                      max_batch=128, decide_every_s=1.0, min_samples=4)
    # too few samples / too little elapsed time -> no decision
    c.maybe_decide(0.0)
    c.observe(0.5)
    assert c.maybe_decide(0.5) is None          # window not elapsed
    assert c.maybe_decide(2.0) is None          # min_samples not met
    for _ in range(4):
        c.observe(0.5)
    d = c.maybe_decide(3.0)
    assert d["action"] == "decrease" and c.batch_target == 32
    for _ in range(50):
        for _ in range(4):
            c.observe(0.0001)
        c.maybe_decide(c._last_decide + 2.0)
    assert c.batch_target == 128                # clamped at max_batch


# ---------------------------------------------------------------------------
# runtime wiring: @app:latencySLO + @app:maxBatchLatency fallback
# ---------------------------------------------------------------------------

def test_latency_slo_annotation_wires_controller():
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:latencySLO('25 ms')\n" + FILTER_APP)
    assert rt.slo is not None and rt.slo.adaptive
    assert rt.slo.target_s == pytest.approx(0.025)
    # flush cadence rides the controller: half the target by default
    assert rt.max_batch_latency_s == pytest.approx(0.0125)
    rt.start()
    cols, ts = tape(256, seed=3)
    rt.input_handler("S").send_batch(cols, ts)
    rt.flush()
    rep = rt.statistics()
    assert rep["slo"]["adaptive"] and rep["slo"]["target_ms"] == 25.0
    assert rep["slo"]["observed_batches"] >= 1
    # the controller's series render in the Prometheus exposition
    prom = rt.stats.prometheus()
    assert "siddhi_tpu_slo_batch_target" in prom
    assert "siddhi_tpu_slo_target_seconds" in prom
    mgr.shutdown()


def test_slo_oversize_batch_splits_output_invariant():
    """A columnar send far larger than the SLO batch target is split via
    the PR-4 halving machinery; outputs match the un-SLO'd run."""
    cols, ts = tape(2048, seed=4)

    def run(head):
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(head + FILTER_APP)
        if head:
            rt._apply_batch_target(128)   # force 2048 >> 2 * target
        out = []
        rt.add_batch_callback("Out", lambda b: out.extend(
            (int(t), r) for t, r in zip(b.timestamps,
                                        b.rows(rt.strings))))
        rt.start()
        rt.input_handler("S").send_batch(cols, ts)
        rt.flush()
        mgr.shutdown()
        return out

    plain = run("")
    split = run("@app:latencySLO('25 ms')\n")
    assert split == plain and len(plain) > 0


def test_max_batch_latency_rides_controller_non_adaptive():
    """@app:maxBatchLatency reimplemented on the SLO controller path:
    cadence-only mode, no AIMD, and the auto-flush behavior holds (the
    no-silent-semantics-change fallback)."""
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:maxBatchLatency('40 ms')\n" + FILTER_APP)
    assert rt.slo is not None and not rt.slo.adaptive
    assert rt.slo.target_s is None
    assert rt.max_batch_latency_s == pytest.approx(0.040)
    # an aged-out partial builder still flushes without an explicit
    # flush() — the original annotation behavior
    got = []
    rt.add_callback("Out", lambda evs: got.extend(e.data for e in evs))
    rt.start()
    h = rt.input_handler("S")
    h.send(("K1", 101.0, 1))        # far below batch_capacity
    deadline = time.time() + 5.0
    while not got and time.time() < deadline:
        time.sleep(0.01)
    mgr.shutdown()
    assert got == [("K1", 101.0, 2)]     # v2 = v * 2
    # and no controller decisions ever fire in cadence-only mode
    assert rt.slo.counts == {"increase": 0, "decrease": 0, "hold": 0}


def test_latency_cadence_drains_pipelined_results():
    """A depth-D dispatch pipeline must not hold an
    aged-out micro-batch's results past the flush cadence: the scheduler
    pump drains in-flight entries, so latency targets and pipelining
    compose."""
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:maxBatchLatency('40 ms')\n@app:devicePipeline(2)\n"
        + FILTER_APP)
    assert rt._plans[0].pipeline_depth == 2
    got = []
    rt.add_callback("Out", lambda evs: got.extend(e.data for e in evs))
    rt.start()
    rt.input_handler("S").send(("K1", 101.0, 1))
    deadline = time.time() + 5.0
    while not got and time.time() < deadline:
        time.sleep(0.01)     # NO explicit flush(): the pump must deliver
    mgr.shutdown()
    assert got == [("K1", 101.0, 2)]


# ---------------------------------------------------------------------------
# fused-lane packing (@app:fusedLanes)
# ---------------------------------------------------------------------------

def test_fused_lane_packing_splits_groups():
    nq = 16     # MIN_GROUP is 8: a pack below it can't fuse on its own
    parts = ["@app:playback\n@app:fusedLanes(8)\n"
             "define stream S (sym string, p double);"]
    for i in range(nq):
        parts.append(
            f"@info(name='q{i}') from every e1=S[p > {100 + i}] -> "
            f"e2=S[p > e1.p] within 1 sec "
            f"select e1.p as p1, e2.p as p2 insert into Out{i};")
    app16 = "\n".join(parts)
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(app16)
    fused = [p for p in rt._plans
             if type(p).__name__ == "MultiQueryDevicePatternPlan"]
    assert len(fused) == 2 and all(p.n_queries == 8 for p in fused)
    # unpacked: one kernel carries all 16 lanes
    rt2 = mgr.create_app_runtime(app16.replace("@app:fusedLanes(8)\n", ""))
    fused2 = [p for p in rt2._plans
              if type(p).__name__ == "MultiQueryDevicePatternPlan"]
    assert len(fused2) == 1 and fused2[0].n_queries == nq
    # same matches either way (lane packing is a geometry knob, not a
    # semantics knob)
    def feed(r):
        got = []
        for i in range(nq):
            r.add_callback(f"Out{i}", lambda evs, i=i: got.extend(
                (i, e.data) for e in evs))
        r.start()
        h = r.input_handler("S")
        rng = np.random.default_rng(7)
        ts0 = 1_700_000_000_000
        for k in range(256):
            h.send((f"K{k % 4}", float(q4(rng.uniform(90, 135)))),
                   timestamp=ts0 + k * 25)
        r.flush()
        return sorted(got)
    assert feed(rt) == feed(rt2)
    mgr.shutdown()
