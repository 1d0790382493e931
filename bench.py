#!/usr/bin/env python
"""Benchmark driver — prints ONE JSON line with the headline metric.

Covers all five BASELINE.json configs under MATCHED conditions: device and
host modes process the SAME event tapes with the SAME batch sizes and event
counts (round-1/2 advisor finding).  The headline is config 4 (partitioned
3-state CEP pattern over 1k keys — the north-star workload); `vs_baseline`
is device events/sec over the sequential host interpreter on that config.
p99 detect-latency (event ingest -> match delivery, small batches) is
reported for the pattern configs.

The host interpreter is our measured stand-in for the single-JVM reference
engine (the reference publishes no numbers — BASELINE.md); the JSON also
carries `vs_production_claim` = headline / 300k events/sec, the reference
README's production-deployment claim, so the result can be read against a
real-world anchor.

Config 5 (1k concurrent mixed queries incl. not/within) fuses on device:
structurally identical queries become lanes of one batched kernel
(multi_query.py), so the 1000 matchers run as 4 kernels of 250 lanes.
"""
import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np

PROD_CLAIM_EPS = 300_000     # reference README.md:33-34 (~20B events/day)


def q4(x):
    """Quarter-step rounding: exactly representable in f32 (the device
    computes DOUBLE in f32 by default; keeps device/host tapes comparable)."""
    return np.round(np.asarray(x) * 4) / 4


# ---------------------------------------------------------------------------
# tape + harness
# ---------------------------------------------------------------------------

def make_tape(n_events, batch, keys=8, seed=0, dt_ms=1):
    """Runtime-independent event tape: symbol as key INDEX (encoded to the
    per-runtime string dictionary at feed time so device and host runtimes
    see identical events)."""
    rng = np.random.default_rng(seed)
    tape = []
    ts0 = 1_700_000_000_000
    for start in range(0, n_events, batch):
        n = min(batch, n_events - start)
        tape.append({
            "sym_idx": rng.integers(0, keys, size=n).astype(np.int32),
            "price": q4(rng.uniform(90.0, 130.0, size=n)),
            "volume": rng.integers(1, 1000, size=n).astype(np.int32),
            "ts": ts0 + np.arange(start, start + n, dtype=np.int64) * dt_ms,
            "seqs": np.arange(1 + start, 1 + start + n, dtype=np.int64),
            "n": n,
        })
    return tape


def _columnar(rt, stream, tape, keys):
    """Tape -> list of send_batch argument dicts (symbol pre-encoded to
    this runtime's string-dictionary codes — the public API accepts both
    str arrays and int32 codes)."""
    codes = np.array([rt.strings.encode(f"K{i}") for i in range(keys)],
                     dtype=np.int32)
    return [({"symbol": codes[t["sym_idx"]], "price": t["price"],
              "volume": t["volume"]}, t["ts"]) for t in tape]


def run_tape(app, stream, tape, keys, out_streams=("Out",), warm=1,
             repeats=1, stats_out=None):
    """Feed the tape through a fresh runtime via the PUBLIC columnar
    ingest path (InputHandler.send_batch).  The timed region is split
    into `repeats` equal segments measured independently (state carries
    across segments — a continuous stream); returns
    (median events/sec, matches in segment 1, [per-segment eps]).
    Callers compare segment-1 match counts across engines.
    `stats_out`: dict to fill with the runtime's device gauges (overlap
    ratio, queue depth — pipeline.py telemetry) before shutdown."""
    from siddhi_tpu import SiddhiManager

    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(app)
    counted = [0]
    for s in out_streams:
        rt.add_batch_callback(s, lambda b: counted.__setitem__(0, counted[0] + b.n))
    rt.start()
    h = rt.input_handler(stream)
    batches = _columnar(rt, stream, tape, keys)
    for cols, ts in batches[:warm]:
        h.send_batch(cols, ts)
    rt.flush()                   # pipelined plans: deliver warm leftovers
    warm_matches = counted[0]
    timed = batches[warm:]
    seg_len = max(1, len(timed) // repeats)
    eps_runs, seg1_matches = [], 0
    for r in range(repeats):
        seg = timed[r * seg_len:(r + 1) * seg_len]
        if not seg:
            break
        n_seg = sum(int(t[1].shape[0]) for t in seg)
        t0 = time.perf_counter()
        for cols, ts in seg:
            h.send_batch(cols, ts)
        rt.flush()               # barrier: all outputs delivered in-window
        eps_runs.append(n_seg / (time.perf_counter() - t0))
        if r == 0:
            seg1_matches = counted[0] - warm_matches
    if stats_out is not None:
        stats_out["device"] = rt.statistics().get("device", {})
        stats_out["placement"] = rt.statistics().get("placement", {})
    mgr.shutdown()
    return float(np.median(eps_runs)), seg1_matches, \
        [round(e) for e in eps_runs]


def _placement_summary(stats: dict) -> dict:
    """The per-config placement column (core/placement.py): device vs
    interpreter query counts + recorded interpreter demotions, so any
    future SILENT demotion shows up as a shifted count in the bench
    trajectory instead of only as a quietly slower eps."""
    pl = stats.get("placement") or {}
    if not pl:
        return {}
    return {"placement": {"device": pl.get("device", 0),
                          "interpreter": pl.get("interpreter", 0),
                          "interp_demotions": pl.get("interp_demotions",
                                                     0)}}


def _overlap_summary(stats: dict) -> dict:
    """Pull the pipeline gauges (pipeline.py) out of a stats_out dict:
    the max overlap_ratio across plans plus total dispatch count."""
    dev = stats.get("device", {})
    ratios = [m["overlap_ratio"] for m in dev.values()
              if "overlap_ratio" in m]
    return {
        "overlap_ratio": max(ratios) if ratios else None,
        "plans_with_overlap": len(ratios),
        "dispatches": sum(int(m.get("pipeline_dispatches", 0))
                          for m in dev.values()),
    }


def p99_latency(app, stream, tape, keys, out_stream="Out", warm=10):
    """Per-match detect latency: batch-ingest start -> callback delivery
    through the public path.  Returns p99 in ms (None if no matches).
    Warm batches run (and FLUSH) before the timed window so compiles and
    deferred pipeline deliveries land outside it — the treatment config 6
    got in PR 5; without the post-warm flush the largest frontier points
    could time a compile and report null/outlier p99s."""
    from siddhi_tpu import SiddhiManager

    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(app)
    lat: list = []
    t_start = [0.0]
    rt.add_batch_callback(
        out_stream,
        lambda b: lat.extend([(time.perf_counter() - t_start[0]) * 1e3] * b.n))
    rt.start()
    h = rt.input_handler(stream)
    batches = _columnar(rt, stream, tape, keys)
    for i, (cols, ts) in enumerate(batches):
        if i == warm:
            rt.flush()          # drain warm leftovers OUTSIDE the window
            lat.clear()
        t_start[0] = time.perf_counter()
        h.send_batch(cols, ts)
        if i >= warm:
            # unconditional per-batch flush inside the timed window:
            # every batch's deliveries land while ITS t_start is live,
            # so the histogram can neither attribute a batch's latency
            # to the next batch's clock nor end up empty (the frontier
            # "p99_ms": null failure shape)
            rt.flush()
    rt.flush()                  # deliver anything still in flight
    mgr.shutdown()
    return round(float(np.percentile(lat, 99)), 1) if lat else None


# ---------------------------------------------------------------------------
# the five BASELINE.json configs
# ---------------------------------------------------------------------------

STOCK = "define stream StockStream (symbol string, price double, volume int);\n"

C1 = STOCK + "@info(name='q') from StockStream[price > 100] select * insert into Out;\n"

C2 = STOCK + ("@info(name='q') from StockStream#window.length(1000) "
              "select avg(price) as ap insert into Out;\n")

# extra window-family row (VERDICT r4 #4): event-time tumbling buckets
STOCK_ET = ("define stream StockStream (symbol string, price double, "
            "volume int, et long);\n")
C2B = STOCK_ET + ("@info(name='q') from StockStream"
                  "#window.externalTimeBatch(et, 64) "
                  "select symbol, sum(price) as sp, count() as c "
                  "group by symbol insert into Out;\n")

C3 = STOCK + ("@info(name='q') from every e1=StockStream[price > 100] -> "
              "e2=StockStream[price > e1.price] within 1 sec "
              "select e1.price as p1, e2.price as p2 insert into Out;\n")

# static-transition variant of config 3 (no capture-dependent filter):
# the shape the bit-packed multi-stride "dfa" plan family accepts — used
# for the per-family kernel roofline sweep
C3S = STOCK + ("@info(name='q') from every e1=StockStream[price > 100] -> "
               "e2=StockStream[price < 95] within 1 sec "
               "select e1.price as p1, e2.price as p2 insert into Out;\n")

C4 = STOCK + """
partition with (symbol of StockStream)
begin
  @info(name='q')
  from every e1=StockStream[price > 100] -> e2=StockStream[price > e1.price]
    -> e3=StockStream[price > e2.price] within 10 sec
  select e1.price as p1, e2.price as p2, e3.price as p3 insert into Out;
end;
"""


def c5_app(n_queries=1000):
    """1k concurrent mixed pattern/sequence queries (incl. not/within) over
    one shared input stream.  Thresholds sit in the tape's upper tail so
    per-query pending-match populations stay realistic (the matcher — ours
    AND the reference's — is O(pending x events) on this shape)."""
    parts = ["@app:playback\n" + STOCK]   # historical tape: event-time
    for i in range(n_queries):            # deadlines fire in-scan, not via
        lo = 123 + (i % 6)                # the wall-clock pump
        shape = i % 4
        if shape == 0:
            parts.append(
                f"@info(name='q{i}') from every e1=StockStream[price > {lo}] -> "
                f"e2=StockStream[price > e1.price] within 1 sec "
                f"select e1.price as p1, e2.price as p2 insert into Out{i % 16};")
        elif shape == 1:
            parts.append(
                f"@info(name='q{i}') from e1=StockStream[price > {lo}], "
                f"e2=StockStream[price > e1.price] "
                f"select e1.price as p1, e2.price as p2 insert into Out{i % 16};")
        elif shape == 2:
            parts.append(
                f"@info(name='q{i}') from e1=StockStream[price > {lo + 1}] -> "
                f"not StockStream[price < {lo - 30}] for 500 milliseconds "
                f"select e1.price as p1 insert into Out{i % 16};")
        else:
            parts.append(
                f"@info(name='q{i}') from every e1=StockStream[price > {lo}] -> "
                f"e2=StockStream[price > e1.price] -> "
                f"e3=StockStream[price > e2.price] within 2 sec "
                f"select e1.price as p1, e3.price as p3 insert into Out{i % 16};")
    return "\n".join(parts) + "\n"


DEV = {"filters": "@app:deviceFilters('auto')\n",
       "windows": "@app:deviceWindows('auto')\n",
       "patterns": "@app:devicePatterns('always')\n"}
HOST = {"filters": "@app:deviceFilters('never')\n",
        "windows": "@app:deviceWindows('never')\n",
        "patterns": "@app:devicePatterns('never')\n"}
# throughput mode: overlap batch i's device->host pull with batch i+1..i+3
# (outputs deliver late; the flush barrier inside the timed window drains
# them).  Latency runs do NOT use this — p99 is measured unpipelined.
PIPE = "@app:devicePipeline(3)\n"


STREAM = "StockStream"


def bench_config(name, dev_app, host_app, n, batch, keys=8, dt_ms=1,
                 out_streams=("Out",), warm=1, check_matches=True,
                 latency=False, lat_dev_app=None, repeats=3):
    """Matched-conditions measurement; returns a result dict.
    Device eps = median of `repeats` independently-timed tape segments
    (VERDICT r4 weak #1: repeat-and-median inside the bench, not across
    hand-picked runs).  The host interpreter runs ONE segment (it is the
    slow, low-variance side); zero-false-match compares segment-1 counts
    (both engines consume the identical segment-1 event stream).
    `lat_dev_app` (default dev_app) measures p99 — throughput apps may
    enable output pipelining, which must NOT be active for latency."""
    tape = make_tape(n * repeats + warm * batch, batch, keys=keys,
                     dt_ms=dt_ms)
    dev_stats: dict = {}
    dev_eps, dev_matches, dev_runs = run_tape(
        dev_app, STREAM, tape, keys, out_streams, warm, repeats=repeats,
        stats_out=dev_stats)
    # host consumes exactly the device's segment 1 (seg_len batches), so
    # the zero-false-match counts compare identical event streams
    seg_len = max(1, (len(tape) - warm) // repeats)
    host_tape = tape[:warm + seg_len]
    if host_app == dev_app:        # same engine both modes: one measurement
        host_eps, host_matches = dev_eps, dev_matches
    else:
        host_eps, host_matches, _ = run_tape(host_app, STREAM, host_tape,
                                             keys, out_streams, warm)
    if check_matches:
        assert dev_matches > 0, f"{name}: no matches — kernel broken?"
        assert dev_matches == host_matches, \
            (f"{name}: match-count mismatch device={dev_matches} "
             f"host={host_matches} — zero-false-match check FAILED")
    res = {
        "device_eps": round(dev_eps),
        "device_eps_runs": dev_runs,
        "host_eps": round(host_eps),
        "speedup": round(dev_eps / host_eps, 2),
        "events": n, "batch": batch, "matches": dev_matches,
    }
    res.update({k: v for k, v in _overlap_summary(dev_stats).items()
                if v is not None})
    res.update(_placement_summary(dev_stats))
    if latency:
        lat_tape = make_tape(2048 * 16, 2048, keys=keys, dt_ms=dt_ms)
        lat_app = lat_dev_app or dev_app
        res["p99_detect_ms"] = p99_latency(lat_app, STREAM, lat_tape, keys,
                                           warm=6)
        res["host_p99_detect_ms"] = p99_latency(host_app, STREAM, lat_tape,
                                                keys, warm=6)
    return res


def _wrap_kernel_factory(obj, name, store):
    """Wrap a jitted-block factory so the last (fn, args) pair is kept
    for device-resident re-invocation (kernel-only probes)."""
    orig = getattr(obj, name)

    def factory(*a, **k):
        fn = orig(*a, **k)

        def wrapped(*fa):
            store["fn"], store["args"] = fn, fa
            return fn(*fa)
        return wrapped
    setattr(obj, name, factory)


def _capture_pattern_kernels(plan, store):
    """Instrument EVERY pattern execution family's block factory on one
    plan (sequential NFAKernel, chunked-halo per-K kernels, and the
    scan/dfa parallel kernels) so kernel-only probes capture whichever
    family the plan actually dispatches."""
    _wrap_kernel_factory(plan.kernel, "block_fn", store)
    orig_ck = plan._chunk_kernel

    def chunk_kernel(K):
        kern = orig_ck(K)
        if not getattr(kern, "_bench_wrapped", False):
            _wrap_kernel_factory(kern, "block_fn", store)
            kern._bench_wrapped = True
        return kern
    plan._chunk_kernel = chunk_kernel
    orig_pk = plan._parallel_kernel

    def par_kernel():
        kern = orig_pk()
        if not getattr(kern, "_bench_wrapped", False):
            _wrap_kernel_factory(kern, "block_fn", store)
            kern._bench_wrapped = True
        return kern
    plan._parallel_kernel = par_kernel


def kernel_p99_ms(app, batch, keys=8, dt_ms=1, chains=8, per=16):
    """Kernel-COMPUTE-only detect latency at this micro-batch size: the
    captured jitted NFA block re-runs in `chains` chains of `per` calls on
    device-resident inputs; each chain's per-call mean is one sample
    (amortizes the per-sync round trip), p99 over samples.  This is the
    compute-only latency the chip adds per micro-batch — reported next
    to the end-to-end p99, which also pays transfers and the host."""
    import jax
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.pattern_plan import DevicePatternPlan

    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(app)
    rt.start()
    h = rt.input_handler(STREAM)
    store: dict = {}
    plan = next(p for p in rt._plans if isinstance(p, DevicePatternPlan))
    _capture_pattern_kernels(plan, store)

    tape = make_tape(2 * batch, batch, keys=keys, dt_ms=dt_ms)
    for cols, ts in _columnar(rt, STREAM, tape, keys):
        h.send_batch(cols, ts)
    rt.flush()
    if "fn" not in store:
        mgr.shutdown()
        return None
    fn, args = store["fn"], store["args"]
    jax.block_until_ready(fn(*args))        # warm
    samples = []
    for _ in range(chains):
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(per)])
        samples.append((time.perf_counter() - t0) * 1e3 / per)
    mgr.shutdown()
    return round(float(np.percentile(samples, 99)), 2)


def frontier(dev_app, host_app=None, keys=8, dt_ms=1,
             batches=(2048, 16384), deadline=None):
    """Latency/throughput frontier: micro-batch size vs (end-to-end eps,
    end-to-end p99, kernel-only p99), with the HOST engine measured at
    the SAME operating point for the matched comparison (VERDICT r4 #5).
    Warm batches absorb compiles so the measured window reflects the
    steady state; eps = median of 3 segments.  Points past `deadline`
    are skipped — a partial frontier beats a bench the driver kills
    mid-run."""
    pts = []
    for b in batches:
        if deadline is not None and time.perf_counter() > deadline:
            pts.append({"batch": b, "skipped": "bench time budget"})
            continue
        n_seg = 4 * b
        tape = make_tape(3 * n_seg + 4 * b, b, keys=keys, dt_ms=dt_ms)
        eps, _m, _runs = run_tape(dev_app, STREAM, tape, keys, ("Out",),
                                  warm=4, repeats=3)
        lat_tape = make_tape(b * 16, b, keys=keys, dt_ms=dt_ms)
        p99 = p99_latency(dev_app, STREAM, lat_tape, keys, warm=4)
        kp99 = kernel_p99_ms(dev_app, b, keys=keys, dt_ms=dt_ms)
        pt = {"batch": b, "eps": round(eps), "p99_ms": p99,
              "kernel_p99_ms": kp99}
        if host_app is not None:
            htape = make_tape(2 * b + 4 * b, b, keys=keys, dt_ms=dt_ms)
            heps, _hm, _hr = run_tape(host_app, STREAM, htape, keys,
                                      ("Out",), warm=1)
            hlat = make_tape(b * 8, b, keys=keys, dt_ms=dt_ms)
            pt["host_eps"] = round(heps)
            pt["host_p99_ms"] = p99_latency(host_app, STREAM, hlat, keys,
                                            warm=2)
        pts.append(pt)
    return pts


JOIN_APP = """
define stream L (symbol string, price double, volume int);
define stream R (symbol string, price double, volume int);
@info(name='q') from L#window.length(1024) as a join R#window.length(1024) as b
on a.symbol == b.symbol and a.price > b.price
select a.symbol as s, a.price as lp, b.price as rp insert into Out;
"""


def bench_join(n, batch, keys=1000, repeats=3):
    """Config 6 (extra, VERDICT r4 #2): stream-stream window join.
    Each side receives n/2 events; device = dense probe-grid kernel,
    host = the interp join (per-event probe of the retained window).
    Also measured: the same device engine UNPIPELINED (depth 0), so the
    eps delta attributable to the async dispatch pipeline is explicit
    and cross-checked against the overlap_ratio telemetry."""
    from siddhi_tpu import SiddhiManager

    def run(head, total, measure_repeats, pipe=True, stats_out=None):
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(head + PIPE + JOIN_APP
                                    if "never" not in head and pipe
                                    else head + JOIN_APP)
        counted = [0]
        rt.add_batch_callback(
            "Out", lambda b: counted.__setitem__(0, counted[0] + b.n))
        rt.start()
        hl, hr = rt.input_handler("L"), rt.input_handler("R")
        codes = np.array([rt.strings.encode(f"K{i}") for i in range(keys)],
                         dtype=np.int32)
        rng = np.random.default_rng(0)
        half = batch // 2
        ts0 = 1_700_000_000_000
        eps_runs, seg1 = [], 0
        n_segs = measure_repeats
        per_seg = total // n_segs
        ev_done = 0
        # warm OUTSIDE the timed window: the first timed segment used to
        # pay the probe-grid compiles — identical warm tape for every
        # engine, so the match-count cross-check still compares identical
        # streams
        for _ in range(2):
            for h in (hl, hr):
                h.send_batch(
                    {"symbol": codes[rng.integers(0, keys, half)],
                     "price": q4(rng.uniform(90, 130, half)),
                     "volume": rng.integers(1, 9, half).astype(np.int32)},
                    timestamps=ts0 + np.arange(ev_done, ev_done + half))
                ev_done += half
            rt.flush()
        warm_m = counted[0]
        for s in range(n_segs):
            t0 = time.perf_counter()
            for _ in range(per_seg // batch):
                for h in (hl, hr):
                    h.send_batch(
                        {"symbol": codes[rng.integers(0, keys, half)],
                         "price": q4(rng.uniform(90, 130, half)),
                         "volume": rng.integers(1, 9, half).astype(np.int32)},
                        timestamps=ts0 + np.arange(ev_done,
                                                   ev_done + half))
                    ev_done += half
            rt.flush()      # segment barrier (pipelined plans drain here)
            eps_runs.append(per_seg / (time.perf_counter() - t0))
            if s == 0:
                seg1 = counted[0] - warm_m
        if stats_out is not None:
            stats_out["device"] = rt.statistics().get("device", {})
            stats_out["placement"] = rt.statistics().get("placement", {})
        mgr.shutdown()
        return float(np.median(eps_runs)), seg1, [round(e) for e in eps_runs]

    stats = {}
    dev_eps, dev_m, dev_runs = run("", n * repeats, repeats,
                                   stats_out=stats)
    # same segments + median so compile amortization matches the
    # pipelined run — the delta is overlap, not warm-up accounting
    unp_eps, unp_m, _ = run("", n * repeats, repeats, pipe=False)
    host_eps, host_m, _ = run("@app:deviceJoins('never')\n", n, 1)
    assert dev_m == host_m == unp_m and dev_m > 0, \
        f"join match mismatch device={dev_m} host={host_m} unpiped={unp_m}"
    return {"device_eps": round(dev_eps), "device_eps_runs": dev_runs,
            "host_eps": round(host_eps),
            "speedup": round(dev_eps / host_eps, 2),
            "unpipelined_eps": round(unp_eps),
            "overlap_speedup": round(dev_eps / unp_eps, 2),
            **_overlap_summary(stats),
            "events": n, "batch": batch, "matches": dev_m,
            "note": "stream-stream length-window join, 1024x1024 windows, "
                    "1000 keys, equality + residual condition"}


# ---------------------------------------------------------------------------
# config 8: multi-plan overlap (the unified dispatch pipeline measured
# directly — N device plans share one input stream; runtime._drain
# dispatches all of them before materializing any)
# ---------------------------------------------------------------------------

MULTI_PLAN_APP = (STOCK +
    "@info(name='w1') from StockStream#window.length(512) "
    "select symbol, sum(price) as s group by symbol insert into Out;\n"
    "@info(name='w2') from StockStream#window.length(64) "
    "select max(price) as hi, min(price) as lo insert into Out2;\n"
    "@info(name='w3') from StockStream#window.lengthBatch(256) "
    "select avg(price) as m insert into Out3;\n"
    "@info(name='f1') from StockStream[price > 120] "
    "select symbol, price insert into Out4;\n")
MULTI_PLAN_OUTS = ("Out", "Out2", "Out3", "Out4")


def bench_overlap(n=1 << 16, batch=1 << 13, repeats=3, depth=3):
    """Pipelined (depth-D deferred pulls + cross-plan dispatch rounds)
    vs unpipelined, SAME tape and plans; asserts identical match counts
    and reports the eps delta next to the overlap_ratio telemetry that
    explains it."""
    head = DEV["windows"] + DEV["filters"]
    tape = make_tape(n * repeats + batch, batch)
    unp_eps, unp_m, _ = run_tape(head + MULTI_PLAN_APP, STREAM, tape, 8,
                                 MULTI_PLAN_OUTS, warm=1, repeats=repeats)
    stats = {}
    pip_eps, pip_m, pip_runs = run_tape(
        f"@app:devicePipeline({depth})\n" + head + MULTI_PLAN_APP, STREAM,
        tape, 8, MULTI_PLAN_OUTS, warm=1, repeats=repeats,
        stats_out=stats)
    assert pip_m == unp_m and pip_m > 0, \
        f"overlap config match mismatch piped={pip_m} unpiped={unp_m}"
    return {"device_eps": round(pip_eps), "device_eps_runs": pip_runs,
            "unpipelined_eps": round(unp_eps),
            "host_eps": round(unp_eps),
            "speedup": round(pip_eps / unp_eps, 2),
            "overlap_speedup": round(pip_eps / unp_eps, 2),
            **_overlap_summary(stats),
            "events": n, "batch": batch, "matches": pip_m,
            "note": f"3 device windows + 1 filter on one stream, "
                    f"devicePipeline({depth}) vs depth 0 — speedup here "
                    f"is overlap, not kernel changes"}


def kernel_eps(app, family, batch, keys=8, dt_ms=1, reps=6, info=None):
    """Device-COMPUTE-only events/sec (VERDICT r4 weak #2): feed one real
    batch through the engine to compile + capture the jitted kernel call
    and its device-resident arguments, then re-invoke the kernel `reps`
    times on those arguments and time with block_until_ready.  Host<->
    device transfers, output materialization, and the host engine layer
    are excluded; dispatch overhead is amortized by chaining the calls.
    This is the compute-only ceiling next to the end-to-end numbers,
    which also pay transfers and the host engine."""
    import jax
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.pattern_plan import DevicePatternPlan
    from siddhi_tpu.core.planner import FilterProjectPlan

    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(app)
    rt.start()
    h = rt.input_handler(STREAM)
    store: dict = {}

    plans = rt._plans
    if family == "filter":
        plan = next(p for p in plans if isinstance(p, FilterProjectPlan))
        orig_step = plan._step

        def step(*a):
            store["fn"], store["args"] = orig_step, a
            return orig_step(*a)
        plan._step = step
        count = lambda args: int(next(iter(args[0].values())).shape[0])
    elif family == "window":
        plan = next(p for p in plans
                    if p.__class__.__name__ == "DeviceWindowAggPlan")
        _wrap_kernel_factory(plan, "_step_fn", store)
        count = lambda args: int(np.asarray(args[1]["__nvalid__"]))
    elif family == "pattern":
        plan = next(p for p in plans if isinstance(p, DevicePatternPlan))
        _capture_pattern_kernels(plan, store)
        store["plan_family"] = plan.family

        def count(args):
            ev = args[1]
            if "__nev__" in ev:
                # lane-vmapped blocks carry per-lane counts (L,)
                return int(np.asarray(ev["__nev__"]).sum())
            return int(np.asarray(ev["__valid__"]).sum())
    else:
        raise ValueError(family)

    tape = make_tape(2 * batch, batch, keys=keys, dt_ms=dt_ms)
    for cols, ts in _columnar(rt, STREAM, tape, keys):
        h.send_batch(cols, ts)
    rt.flush()
    if "fn" not in store:
        mgr.shutdown()
        if info is not None and "plan_family" in store:
            info["plan_family"] = store["plan_family"]
        return None
    fn, args = store["fn"], store["args"]
    n_call = count(args)
    threads_state = len(args) == 2 and family in ("window", "pattern")

    def chain(k):
        if family == "window":
            st = args[0]
            outs = []
            for _ in range(k):
                res = fn(st, args[1])
                st = res["nst"]
                outs.append(res)
            return outs
        if family == "pattern" and threads_state and "__nev__" not in args[1]:
            st, outs = args[0], []
            for _ in range(k):
                st, out = fn(st, args[1])
                outs.append(out)
            return outs
        return [fn(*args) for _ in range(k)]

    jax.block_until_ready(chain(2))          # warm (compile cache hit)
    t0 = time.perf_counter()
    jax.block_until_ready(chain(reps))
    dt = time.perf_counter() - t0
    mgr.shutdown()
    if info is not None and "plan_family" in store:
        info["plan_family"] = store["plan_family"]
    return round(n_call * reps / dt)


def latency_demo(dev_app, host_app, target_ms=10, seconds=6.0,
                 keys=8, rate=5_000, capacity=2048):
    """@app:maxBatchLatency demo (VERDICT r4 #5): a producer paced at
    `rate` events/sec; builders auto-flush when the OLDEST buffered
    event has waited target_ms (or at capacity), so micro-batch size
    adapts to the arrival rate.  At a rate ABOVE the host interpreter's
    capacity the host backlog (and its detect latency) grows without
    bound while the device engine holds a steady p99 — the
    latency-under-load story.  Reports achieved events/sec and p99
    detect latency (first-buffered-event -> match delivery) for both
    engines under the identical harness."""
    from siddhi_tpu import SiddhiManager

    def run(app):
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(app)
        rt.batch_capacity = capacity    # both engines: same batch bound
        lat: list = []
        t0_batch = [0.0]
        rt.add_batch_callback(
            "Out", lambda b: lat.extend(
                [(time.perf_counter() - t0_batch[0]) * 1e3] * b.n))
        rt.start()
        h = rt.input_handler(STREAM)
        rng = np.random.default_rng(3)
        syms = rng.integers(0, keys, size=1 << 16)
        prices = q4(rng.uniform(90, 130, size=1 << 16))
        ts0 = 1_700_000_000_000
        i = 0
        t_origin = time.perf_counter()

        def send_one():
            nonlocal i
            while i > (time.perf_counter() - t_origin) * rate:
                pass                            # pace to `rate` events/sec
            j = i % (1 << 16)
            # 25 ms event spacing keeps the within-1s replay tail ~40
            # events, so latency-capped micro-flushes stay small
            h.send((f"K{syms[j]}", float(prices[j]), 1),
                   timestamp=ts0 + i * 25)
            # the runtime tracks first-append time per builder under its
            # lock — read it rather than re-deriving (review r5: a
            # pre-send check races the scheduler's auto-flush)
            t0_batch[0] = rt._builder_t0.get(STREAM, t0_batch[0])
            i += 1

        # prewarm ladder: exercise the flush-size regimes the timed
        # window can produce (shape buckets are sticky, but a compile
        # landing mid-measurement voids the p99), then
        # settle until flushes run compile-free
        for _round in range(2):
            for size in (17, 60, 250, 1000, capacity):
                for _ in range(size):
                    send_one()
                rt.flush()
        settle_end = time.perf_counter() + 20.0
        while time.perf_counter() < settle_end:
            t0f = time.perf_counter()
            for _ in range(17):
                send_one()
            rt.flush()
            if time.perf_counter() - t0f < 0.5:
                break               # flush ran warm: shapes are compiled
        lat.clear()
        t_timed = time.perf_counter()
        sent_at_timed = i
        t_end = t_timed + seconds
        while time.perf_counter() < t_end:
            send_one()
        rt.flush()
        dt = time.perf_counter() - t_timed
        eps = (i - sent_at_timed) / max(dt, 1e-9)
        mgr.shutdown()
        p99 = round(float(np.percentile(lat, 99)), 1) if lat else None
        return round(eps), p99

    lat_head = f"@app:maxBatchLatency('{target_ms} ms')\n"
    dev_eps, dev_p99 = run(lat_head + dev_app)
    host_eps, host_p99 = run(lat_head + host_app)
    return {"target_ms": target_ms, "offered_rate_eps": rate,
            "device_eps": dev_eps, "device_p99_ms": dev_p99,
            "host_eps": host_eps, "host_p99_ms": host_p99,
            "note": "@app:maxBatchLatency adapts micro-batches to the "
                    "arrival rate: p99 detect ~= target + the engine's "
                    "per-flush floor (dispatch + device->host pull); "
                    "the frontier's kernel_p99_ms column is the "
                    "compute-only part of it"}


def _mark(label, t0):
    print(f"[bench {time.perf_counter() - t0:6.1f}s] {label}",
          file=sys.stderr, flush=True)


def _safe(label, fn, default=None):
    """Run one optional bench section; a failure degrades that section to
    `default` instead of killing the run — the final stdout line must
    ALWAYS be the machine-parseable summary (BENCH "parsed": null)."""
    try:
        return fn()
    except Exception as e:
        print(f"[bench] section {label!r} failed: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return default


def harness_info() -> dict:
    """Provenance block recorded with every bench result (BENCH_DETAIL
    + summary): two runs whose harness blocks differ are not comparable
    and a comparison across a config-hash change is not tight."""
    import hashlib
    import os
    import subprocess
    info: dict = {"git_rev": None}
    try:
        r = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        if r.returncode == 0:
            info["git_rev"] = r.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        pass
    # the workload identity: every app text a numbered config runs
    cfg = "\x1e".join([STREAM, PIPE, C1, C2, C2B, C3, C3S, C4, c5_app(8),
                       *(DEV[k] for k in sorted(DEV)),
                       *(HOST[k] for k in sorted(HOST))])
    info["config_hash"] = hashlib.sha256(cfg.encode()).hexdigest()[:12]
    import jax
    info["jax"] = jax.__version__
    info.update(device_info())
    return info


def device_info(_arg=None) -> dict:
    """Where this process runs, as JAX reports it (also the `--chaos-cell
    probe` child: what the JAX-free chaos parent's children will get)."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "device_count": len(devs)}


def require_platform(want: str, who: str, why: str) -> None:
    """Refuse to run on any platform but `want`.  Full-size modes measure
    the chip, so a CPU timing can never be written under `device_eps` /
    `kernel_eps`; chaos children must land where their parent's probe
    did, so none runs quietly on the CPU."""
    d = device_info()
    if d["platform"] != want:
        sys.exit(f"bench.py {who}: jax.devices()[0].platform is "
                 f"{d['platform']!r} ({d['device_kind']!r}), need "
                 f"{want!r}: {why}")


# ---------------------------------------------------------------------------
# native single-core calibration (no JVM in the image: an -O2 C++ run of
# the same matcher algorithms upper-bounds single-JVM single-thread
# throughput on this hardware — see native/bench_native.cpp)
# ---------------------------------------------------------------------------

def native_baseline():
    """Build + run the native harness on tapes matching each config's
    (n + warm, batch, keys) so the event streams are the ones the
    engines consumed; returns {config: {"eps": .., "matches": ..}} or
    {} when unavailable."""
    import os
    import shutil
    import subprocess
    import tempfile
    root = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(root, "native", "bench_native.cpp")
    exe = os.path.join(root, "native", "bench_native")
    # always rebuilt from the committed source: the binary is git-ignored,
    # so one found on disk says nothing about what this checkout measures
    if shutil.which("g++") is None:
        return {}
    r = subprocess.run(["g++", "-O2", "-std=c++17", "-o", exe, src],
                       capture_output=True)
    if r.returncode != 0:
        return {}

    def tape_bin(n, batch, keys, path):
        tape = make_tape(n, batch, keys=keys, dt_ms=1)
        rec = np.dtype([("ts", "<i8"), ("price", "<f4"), ("key", "<i4")])
        rows = np.empty(sum(t["n"] for t in tape), dtype=rec)
        o = 0
        for t in tape:
            sl = slice(o, o + t["n"])
            rows["ts"][sl] = t["ts"]
            rows["price"][sl] = t["price"]
            rows["key"][sl] = t["sym_idx"]
            o += t["n"]
        rows.tofile(path)

    def run_exe(args):
        try:
            r = subprocess.run([exe, *args], capture_output=True,
                               text=True, timeout=120)
            return r.stdout if r.returncode == 0 else ""
        except (OSError, subprocess.SubprocessError):
            return ""

    out = {}
    with tempfile.TemporaryDirectory() as td:
        # config 1's tape (n + 1 warm batch)
        p1 = os.path.join(td, "t1.bin")
        tape_bin((1 << 19) + (1 << 18), 1 << 18, 8, p1)
        text = run_exe([p1, "filter"])
        # configs 2+3 share (n, batch) = (1<<18, 1<<17)
        p2 = os.path.join(td, "t2.bin")
        tape_bin((1 << 18) + (1 << 17), 1 << 17, 8, p2)
        text += run_exe([p2, "window", "sequence"])
        p3 = os.path.join(td, "t3.bin")
        tape_bin((2 << 18) + (1 << 18), 1 << 18, 1000, p3)
        text += run_exe([p3, "partitioned:1000"])
    for line in text.splitlines():
        parts = line.split()
        if len(parts) >= 3:
            out[parts[0]] = {"eps": int(float(parts[1])),
                             "matches": int(parts[2])}
    return out


def _tape_str_batches(tape, keys=8):
    """Tape -> (cols, ts) with symbol as STR arrays — the form both the
    wire clients and the in-process differential feed, so the string
    dictionary builds in the same order on every path."""
    names = np.array([f"K{i}" for i in range(keys)])
    return [({"symbol": names[t["sym_idx"]], "price": t["price"],
              "volume": t["volume"]}, t["ts"]) for t in tape]


def net_bench(smoke=False) -> dict:
    """`--net [--smoke]`: serving-plane bench (docs/SERVING.md) on the
    config-3 pattern workload.

      * per-event REST POSTs (the old front door) vs columnar TCP
        frames vs the shm ring vs in-process `send_batch` — eps each,
        with the wire paths asserted BYTE-IDENTICAL to in-process
        ingest (same matches, same decoded rows, same order)
      * multi-producer TCP fan-in (full mode)
      * overload: 2x the admitted rate under shed.policy='shed' —
        engine p99 must stay within 2x its unloaded value, every shed
        event must be accounted in the ErrorStore, and replay() must
        restore them (zero unaccounted loss)

    --smoke shrinks the tape for CI (scripts/smoke.sh) but keeps every
    assertion."""
    import threading
    import urllib.request
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.net import RingProducer, TcpFrameClient
    from siddhi_tpu.service import SiddhiService

    n = 1 << 12 if smoke else 1 << 16
    batch = 512 if smoke else 4096
    warm = 2
    app_body = DEV["patterns"] + C3
    tape = make_tape(n + warm * batch, batch)
    batches = _tape_str_batches(tape)
    n_timed = sum(t["n"] for t in tape[warm:])

    def run_collect(app, connect_fn):
        """Fresh runtime; connect_fn(rt) -> (send, finish) callables.
        Warm batches (compiles) land outside the timed window; returns
        (eps over the timed region, ALL decoded Out rows)."""
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(app)
        rows = []
        rt.add_batch_callback("Out", lambda b: rows.extend(
            map(tuple, b.rows(rt.strings))))
        rt.start()
        send, finish = connect_fn(rt)
        for cols, ts in batches[:warm]:
            send(cols, ts)
        finish()
        t0 = time.perf_counter()
        for cols, ts in batches[warm:]:
            send(cols, ts)
        finish()
        dt = time.perf_counter() - t0
        mgr.shutdown()
        for key in ("_bench_cli", "_bench_prod"):
            c = rt.__dict__.get(key)
            if c is not None:
                c.close()
        return n_timed / dt, rows

    # 1) in-process columnar (the direct append_columnar path)
    def connect_inproc(rt):
        h = rt.input_handler(STREAM)
        return h.send_batch, rt.flush
    inproc_eps, inproc_rows = run_collect(app_body, connect_inproc)

    # 2) loopback TCP frames through @source(type='tcp')
    def connect_tcp(rt):
        cli = TcpFrameClient("127.0.0.1", rt.sources[0].port, STREAM,
                             TcpFrameClient.cols_of_schema(
                                 rt.schemas[STREAM]))
        rt.__dict__["_bench_cli"] = cli       # keep alive till shutdown
        return cli.send_batch, lambda: cli.barrier(timeout=120)
    tcp_eps, tcp_rows = run_collect(
        "@source(type='tcp', port='0')\n" + app_body, connect_tcp)

    # 3) shm ring
    def connect_shm(rt):
        prod = RingProducer(rt.sources[0].ring_name, STREAM,
                            RingProducer.cols_of_schema(rt.schemas[STREAM]))
        rt.__dict__["_bench_prod"] = prod
        sent = [0]

        def send(cols, ts):
            prod.send_batch(cols, ts)
            sent[0] += len(ts)

        def finish():
            prod.barrier(timeout=120)           # every frame popped
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:  # feed drains async of
                if rt.admission[STREAM].metrics()["admitted_events"] \
                        >= sent[0]:
                    break                       # last pop fed: tight poll
                time.sleep(0.0002)
            rt.flush()
        return send, finish
    ring_slots = "16" if smoke else "64"
    shm_eps, shm_rows = run_collect(
        f"@source(type='shm', slots='{ring_slots}', "
        f"slot.size='1048576')\n" + app_body, connect_shm)

    # 4) per-event REST (the old debug front door) — measured on a
    # slice of the tape, one keep-alive connection, one event per POST
    n_rest = 256 if smoke else 1024
    svc = SiddhiService(port=0, net=False).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{svc.port}/siddhi/artifact/deploy",
            data=("@app:name('RestBench')\n"
                  + app_body).encode(), method="POST")
        urllib.request.urlopen(req).read()
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", svc.port)
        rest_events = []
        for cols, ts in batches:
            for i in range(len(ts)):
                rest_events.append((cols["symbol"][i], cols["price"][i],
                                    int(cols["volume"][i]), int(ts[i])))
                if len(rest_events) >= n_rest:
                    break
            if len(rest_events) >= n_rest:
                break
        t0 = time.perf_counter()
        for sym, p, v, ts_i in rest_events:
            body = json.dumps({"app": "RestBench", "stream": STREAM,
                               "data": [str(sym), float(p), v],
                               "timestamp": ts_i}).encode()
            conn.request("POST", "/siddhi/artifact/event", body=body,
                         headers={"Content-Type": "application/json"})
            conn.getresponse().read()
        rest_eps = n_rest / (time.perf_counter() - t0)
        conn.close()
    finally:
        svc.stop()

    # 5) multi-producer TCP fan-in (full mode): two connections, the
    # tape split between them.  A STATELESS filter app — interleaved
    # producers scramble cross-batch event time, which is a pattern-
    # engine workload question (pending windows stop expiring
    # monotonically), not a transport one; the filter isolates fan-in
    # capacity.  No cross-producer order, so count-only.
    mp_eps = None
    if not smoke:
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(
            "@source(type='tcp', port='0')\n" + DEV["filters"] + C1)
        rt.start()
        port = rt.sources[0].port
        cols_spec = TcpFrameClient.cols_of_schema(rt.schemas[STREAM])
        warm_cli = TcpFrameClient("127.0.0.1", port, STREAM, cols_spec)
        for cols, ts in batches[:warm]:
            warm_cli.send_batch(cols, ts)
        warm_cli.barrier(timeout=120)

        def one(share):
            cli = TcpFrameClient("127.0.0.1", port, STREAM, cols_spec)
            for cols, ts in share:
                cli.send_batch(cols, ts)
            cli.barrier(timeout=120)
            cli.close()
        ths = [threading.Thread(target=one, args=(s,))
               for s in (batches[warm::2], batches[warm + 1::2])]
        t0 = time.perf_counter()
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        mp_eps = n_timed / (time.perf_counter() - t0)
        warm_cli.close()
        mgr.shutdown()

    identical = (tcp_rows == inproc_rows and shm_rows == inproc_rows
                 and len(inproc_rows) > 0)

    # 6) overload: 2x the admitted rate, shed.policy='shed'
    overload = _net_overload(smoke)

    res = {
        "events": n_timed, "batch": batch,
        "transport": {
            "inproc_eps": round(inproc_eps),
            "tcp_eps": round(tcp_eps),
            "shm_eps": round(shm_eps),
            "rest_eps": round(rest_eps, 1),
            **({"tcp_2producer_filter_eps": round(mp_eps)}
               if mp_eps else {}),
        },
        "tcp_vs_rest": round(tcp_eps / rest_eps, 1),
        "shm_vs_tcp": round(shm_eps / tcp_eps, 2),
        "matches": len(inproc_rows),
        "identical": identical,
        "overload": overload,
    }
    res["pass"] = bool(identical and res["tcp_vs_rest"] >= 5.0
                       and overload["pass"])
    return res


def _net_overload(smoke=False) -> dict:
    """Paced 2x-overload against a rate-limited tcp source with
    shed.policy='shed': p99 bound, zero unaccounted loss, replayable."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.net import TcpFrameClient

    rate = 4000.0                   # admitted eps
    burst = 400.0
    pace_batch = 64
    seconds = 1.5 if smoke else 4.0
    app = ("@app:statistics('true')\n"
           f"@source(type='tcp', port='0', rate.limit='{rate}', "
           f"burst='{burst}', shed.policy='shed')\n" + DEV["patterns"] + C3)

    def paced_run(offered_eps):
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(app)
        delivered = [0]
        rt.add_batch_callback(STREAM, lambda b: delivered.__setitem__(
            0, delivered[0] + b.n))
        rt.start()
        cli = TcpFrameClient(
            "127.0.0.1", rt.sources[0].port, STREAM,
            TcpFrameClient.cols_of_schema(rt.schemas[STREAM]))
        rng = np.random.default_rng(11)
        ts0 = 1_700_000_000_000
        sent = 0

        def one_batch():
            nonlocal sent
            cols = {"symbol": np.array(
                        [f"K{i}" for i in rng.integers(0, 8, pace_batch)]),
                    "price": q4(rng.uniform(90, 130, pace_batch)),
                    "volume": rng.integers(1, 100, pace_batch)
                       .astype(np.int32)}
            cli.send_batch(cols, ts0 + np.arange(
                sent, sent + pace_batch, dtype=np.int64))
            sent += pace_batch

        # warm OUTSIDE the measured window: the first batches trigger
        # kernel compiles, which would otherwise backlog the socket and
        # burst-shed on drain (and pollute the p99 histogram)
        for _ in range(4):
            one_batch()
            cli.barrier(timeout=120)
        rt.stats.reset()                # p99 over the paced window only
        ctrl = rt.admission[STREAM]
        m0 = ctrl.metrics()
        sent0, delivered0 = sent, delivered[0]
        interval = pace_batch / offered_eps
        t_end = time.perf_counter() + seconds
        ts_next = time.perf_counter()
        while time.perf_counter() < t_end:
            one_batch()
            ts_next += interval
            lag = ts_next - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
        cli.barrier(timeout=60)
        m = ctrl.metrics()
        stats = rt.statistics()
        p99 = stats["streams"].get(STREAM, {}).get("p99_ms")
        out = {"sent": sent - sent0,
               "delivered": delivered[0] - delivered0,
               "shed": m["shed_events"] - m0["shed_events"],
               "p99_ms": p99,
               "stored_frames": m["shed_frames"] - m0["shed_frames"]}
        # replay restores every shed event (lift the limit first)
        ctrl.bucket.rate = None
        rep = rt.error_store.replay(rt)
        rt.flush()
        out["replayed_ok"] = (rep["remaining"] == 0
                              and delivered[0] == sent)
        cli.close()
        mgr.shutdown()
        return out

    base = paced_run(rate * 0.5)            # unloaded: half the limit
    over = paced_run(rate * 2.0)            # 2x the admitted rate
    p99_ok = (base["p99_ms"] is None or over["p99_ms"] is None
              or over["p99_ms"] <= 2.0 * max(base["p99_ms"], 1.0))
    res = {"rate_limit_eps": rate, "unloaded": base, "overloaded": over,
           "p99_within_2x": p99_ok,
           "zero_loss": bool(over["replayed_ok"] and over["shed"] > 0)}
    res["pass"] = bool(res["p99_within_2x"] and res["zero_loss"]
                       and base["replayed_ok"])
    return res


def chaos_net(seed: int = 7) -> dict:
    """Serving-plane chaos (`--chaos` rides this after the core
    sections): mid-frame disconnects must not poison the server or
    lose admitted frames; a slow consumer on a tiny shm ring must
    backpressure the producer, never drop; injected ingest faults
    capture whole frames for replay."""
    import socket as _socket
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.faults import FaultInjector
    from siddhi_tpu.net import RingProducer, TcpFrameClient
    from siddhi_tpu.net import frame as fp

    APP = ("@source(type='tcp', port='0')\n"
           "define stream S (sym string, p double);\n"
           "@info(name='q') from S select sym, p insert into Out;\n")
    out: dict = {}
    rng = np.random.default_rng(seed)

    # 1) mid-frame disconnects between healthy producers
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(APP)
    delivered = [0]
    rt.add_batch_callback("S", lambda b: delivered.__setitem__(
        0, delivered[0] + b.n))
    rt.start()
    port = rt.sources[0].port
    cols_spec = TcpFrameClient.cols_of_schema(rt.schemas["S"])
    n_sent = 0
    for round_ in range(3):
        cli = TcpFrameClient("127.0.0.1", port, "S", cols_spec)
        for k in range(4):
            cli.send_batch(
                {"sym": np.array([f"K{i}" for i in
                                  rng.integers(0, 4, 32)]),
                 "p": q4(rng.uniform(0, 10, 32))},
                np.arange(n_sent, n_sent + 32, dtype=np.int64))
            n_sent += 32
        cli.barrier()
        cli.close()
        # now a rude client: half a frame, then vanish
        raw = _socket.create_connection(("127.0.0.1", port))
        blob = fp.encode_hello("", "S", cols_spec)
        raw.sendall(blob[:len(blob) // 2 + round_])
        raw.close()
        # and one that sends garbage
        raw = _socket.create_connection(("127.0.0.1", port))
        raw.sendall(bytes(rng.integers(0, 256, 64, dtype=np.uint8)))
        raw.close()
    time.sleep(0.1)
    errors = rt.statistics()["net"]["S"].get("protocol_errors", 0)
    disc_ok = delivered[0] == n_sent
    out["mid_frame_disconnect"] = {
        "sent": n_sent, "delivered": delivered[0],
        "protocol_errors": errors, "pass": disc_ok}
    mgr.shutdown()

    # 2) slow consumer: a 2-slot ring backpressures, loses nothing
    APP_SHM = ("@source(type='shm', slots='2', slot.size='8192')\n"
               "define stream S (sym string, p double);\n"
               "@info(name='q') from S select sym, p insert into Out;\n")
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(APP_SHM)
    delivered2 = [0]
    rt.add_batch_callback("S", lambda b: delivered2.__setitem__(
        0, delivered2[0] + b.n))
    rt.start()
    prod = RingProducer(rt.sources[0].ring_name, "S",
                        RingProducer.cols_of_schema(rt.schemas["S"]),
                        push_timeout=30)
    n2 = 0
    for k in range(64):                     # 64 frames through 2 slots
        prod.send_batch({"sym": np.array(["A", "B"]),
                         "p": np.array([1.0, 2.0])},
                        np.arange(n2, n2 + 2, dtype=np.int64))
        n2 += 2
    prod.barrier(timeout=30)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and delivered2[0] < n2:
        rt.flush()
        time.sleep(0.01)
    slow_ok = delivered2[0] == n2
    out["slow_consumer_ring"] = {"sent": n2, "delivered": delivered2[0],
                                 "pass": slow_ok}
    prod.close()
    mgr.shutdown()

    # 3) injected ingest faults: admitted frames capture whole + replay
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(APP)
    delivered3 = [0]
    rt.add_batch_callback("S", lambda b: delivered3.__setitem__(
        0, delivered3[0] + b.n))
    rt.start()
    rt.fault_injector = FaultInjector(seed=seed, counts={"net.feed": 3})
    cli = TcpFrameClient("127.0.0.1", rt.sources[0].port, "S",
                         TcpFrameClient.cols_of_schema(rt.schemas["S"]))
    n3 = 0
    for k in range(8):
        cli.send_batch({"sym": np.array(["X"] * 16),
                        "p": q4(rng.uniform(0, 10, 16))},
                       np.arange(n3, n3 + 16, dtype=np.int64))
        n3 += 16
    cli.barrier()
    stored = len(rt.error_store)
    rt.fault_injector = None
    rep = rt.error_store.replay(rt)
    rt.flush()
    feed_ok = (stored == 3 and rep["remaining"] == 0
               and delivered3[0] == n3)
    out["injected_feed_faults"] = {
        "sent": n3, "stored_then_replayed": stored,
        "delivered_after_replay": delivered3[0], "pass": feed_ok}
    cli.close()
    mgr.shutdown()

    out["pass"] = disc_ok and slow_ok and feed_ok
    return out


# ---------------------------------------------------------------------------
# kill-9 durability chaos (`--chaos`): SIGKILL at a fault-injected point,
# recover, prove exactly-once (docs/RELIABILITY.md)
# ---------------------------------------------------------------------------

_K9_HEAD = ("@app:name('K9')\n"
            "@app:durability('batch')\n")

K9_PATTERN = _K9_HEAD + """
@app:devicePatterns('prefer')
@source(type='tcp', port='0')
define stream S (sym string, p double);
define table OutT (s1 string, s2 string);
@info(name='q') from every a=S[p > 120] -> b=S[p < 80] within 1 sec
select a.sym as s1, b.sym as s2 insert into OutT;
"""

K9_WINDOW = _K9_HEAD + """
@source(type='tcp', port='0')
define stream S (sym string, p double);
define table OutT (sym string, s double, c long);
@info(name='q') from S#window.length(64)
select sym, sum(p) as s, count() as c group by sym insert into OutT;
"""

K9_JOIN = _K9_HEAD + """
@source(type='tcp', port='0')
define stream S (sym string, p double);
@source(type='tcp', port='0')
define stream T (sym string, p double);
define table OutT (sym string, pa double, pb double);
@info(name='q') from S#window.length(32) as a join T#window.length(32) as b
    on a.sym == b.sym
select a.sym as sym, a.p as pa, b.p as pb insert into OutT;
"""

K9_CONFIGS = {"pattern": (K9_PATTERN, ["S"]),
              "window": (K9_WINDOW, ["S"]),
              "join": (K9_JOIN, ["S", "T"])}


def _k9_tape(seed, streams, rounds=10, batch=128, keys=6,
             with_ts=False):
    """Deterministic per-round frame tape, regenerated identically by
    the parent (clean run + resume) and the to-be-killed child.
    `with_ts` adds the event-time column aggregations fold by."""
    rng = np.random.default_rng(seed)
    ts0 = 1_700_000_000_000
    out = []
    for k in range(rounds):
        rd = {}
        for sid in streams:
            ts = ts0 + np.arange(k * batch, (k + 1) * batch,
                                 dtype=np.int64) * 2
            cols = {"sym": np.array([f"K{i}" for i in
                                     rng.integers(0, keys, batch)]),
                    "p": q4(rng.uniform(60.0, 140.0, batch))}
            if with_ts:
                cols["ts"] = ts
            rd[sid] = (cols, ts)
        out.append(rd)
    return out


def chaos_kill9_child(spec_path: str) -> None:
    """Hidden `--chaos-child <spec.json>` mode: build the durable app,
    feed the deterministic tape over loopback TCP (per-frame ACK
    barriers, so every acked frame is a durability promise), persist at
    the scripted round, and SIGKILL OURSELVES at the armed injection
    point — mid-`wal.append` leaves a torn record on disk, exactly the
    crash shape recovery must absorb.  Exits 3 if the kill never fired
    (the parent treats any exit other than SIGKILL as a failure)."""
    import json as _json
    import os
    import signal
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.persistence import FileSystemPersistenceStore
    from siddhi_tpu.net import TcpFrameClient

    with open(spec_path) as f:
        spec = _json.load(f)
    _require_probed(spec["platform"], "--chaos-child")

    class _Kill9:
        """FaultInjector-shaped: SIGKILL (not an exception) at the Nth
        check of one point — the process vanishes mid-operation."""

        def __init__(self, point, at):
            self.point, self.at, self.n = point, at, 0

        def check(self, point, detail=""):
            if point == self.point:
                self.n += 1
                if self.n >= self.at:
                    os.kill(os.getpid(), signal.SIGKILL)

    mgr = SiddhiManager()
    mgr.set_persistence_store(FileSystemPersistenceStore(spec["snap_dir"]))
    rt = mgr.create_app_runtime(spec["app"])
    rt.start()
    rt.fault_injector = _Kill9(spec["kill_point"], spec["kill_at"])
    ports = {s.stream_id: s.port for s in rt.sources}
    clis = {sid: TcpFrameClient("127.0.0.1", ports[sid], sid,
                                TcpFrameClient.cols_of_schema(
                                    rt.schemas[sid]))
            for sid in spec["streams"]}
    tape = _k9_tape(spec["seed"], spec["streams"], spec["rounds"],
                    spec["batch"], spec["keys"],
                    with_ts=spec.get("with_ts", False))
    for k, rd in enumerate(tape):
        if k == spec["snapshot_at"]:
            rt.persist()
        for sid in spec["streams"]:
            cols, ts = rd[sid]
            clis[sid].send_batch(cols, ts)
            # serialize streams per round: append order (and thus the
            # clean-run differential) stays deterministic
            clis[sid].barrier(timeout=60)
    os._exit(3)


# ---------------------------------------------------------------------------
# --chaos orchestration: ONE process per chip.  A chip belongs to one
# process at a time, so the process that spawns chip-needing children
# never touches JAX itself: `bench.py --chaos` is a JAX-free orchestrator,
# and every runtime — reference runs, armed children, recoveries — lives
# in a child, ONE alive at a time.  The machine-loss cell alone keeps two
# alive (primary + standby); each gets its own chip through the child's
# environment, or the cell refuses.  Every child checks that it landed on
# the platform the parent's probe saw: a child quietly on the CPU is a
# failure, not a slower pass.
# ---------------------------------------------------------------------------

def _require_probed(platform: str, who: str) -> None:
    require_platform(platform, who, "the platform the --chaos parent's "
                     "probe saw")


def _spawn(args: list, **popen_kw):
    """Start `bench.py <args>` as a chip-needing child of the JAX-free
    `--chaos` parent."""
    import os
    import subprocess
    if "jax" in sys.modules:
        raise RuntimeError(
            "the --chaos parent imported jax: it would hold the chip its "
            "children need (one process per chip)")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), *args], **popen_kw)


def _spawn_cell(name: str, arg, platform: str, env=None):
    """Start `bench.py --chaos-cell <name> <platform> <arg>`."""
    import subprocess
    return _spawn(["--chaos-cell", name, platform, str(arg)],
                  stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                  env=env)


def _reap_cell(proc, name: str, timeout_s: float = 900) -> dict:
    """A cell's result is the JSON object on its last stdout line."""
    try:
        out, err = proc.communicate(timeout=timeout_s)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"chaos cell {name!r} exited {proc.returncode}: "
                           f"{err[-800:]}")
    return json.loads(out.strip().splitlines()[-1])


def _run_cell(name: str, arg, platform: str, timeout_s: float = 900) -> dict:
    return _reap_cell(_spawn_cell(name, arg, platform), name, timeout_s)


def _kill_then_verify(spec: dict, work: str, verify_cell: str) -> dict:
    """Run the armed child to its SIGKILL; then — the chip free again —
    the child that recovers from what it left on disk and compares with
    an uninterrupted run."""
    import json as _json
    import os
    import subprocess
    spec_path = os.path.join(work, "spec.json")
    with open(spec_path, "w") as f:
        _json.dump(spec, f)
    proc = _spawn(["--chaos-child", spec_path],
                  stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
    try:
        _out, err = proc.communicate(timeout=600)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != -9:
        return {"killed": False, "pass": False,
                "child_rc": proc.returncode,
                "child_tail": err.decode(errors="replace")[-500:]}
    return _run_cell(verify_cell, spec_path, spec["platform"])


def chaos_kill9(seed: int, platform: str, only=None) -> dict:
    """`--chaos` kill-9-and-recover section: for each of the pattern /
    window / join configs, a child feeds N TCP frames into a
    `@app:durability('batch')` app and is SIGKILLED at a fault-injected
    point (mid-`wal.append` with a snapshot behind it; mid-snapshot
    with only the log).  A second child then recovers — restore newest
    loadable snapshot + replay the WAL suffix past the watermark — and
    resumes the unacked tape tail exactly as a real producer would
    (`_k9_verify`).

    Asserted per config and kill point:
      * byte-identical outputs to an uninterrupted run (zero duplicate,
        zero lost admitted events — the exactly-once invariant)
      * events_in == applied + shed over the recovered pipeline
      * zero ErrorStore captures (nothing was quietly parked)"""
    import os
    import shutil
    import tempfile

    rounds, batch, keys = 10, 128, 6
    out = {"seed": seed, "configs": {}, "pass": True}
    for name, (app, streams) in K9_CONFIGS.items():
        if only is not None and name != only:
            continue
        cfg = {"events_in": rounds * batch * len(streams)}
        snapshot_at = 4
        pre_appends = snapshot_at * len(streams)
        for kname, point, at in (
                ("mid_wal_append", "wal.append",
                 pre_appends + 2 * len(streams) + 1),
                ("mid_snapshot", "persist.save", 1)):
            work = tempfile.mkdtemp(prefix=f"siddhi_k9_{name}_")
            try:
                cfg[kname] = _kill_then_verify({
                    "clean_app": app,
                    "app": app.replace(
                        "@app:durability('batch')",
                        f"@app:durability('batch', dir='{work}/wal')"),
                    "streams": streams,
                    "snap_dir": os.path.join(work, "snap"),
                    "seed": seed, "rounds": rounds, "batch": batch,
                    "keys": keys, "snapshot_at": snapshot_at,
                    "kill_point": point, "kill_at": at,
                    "platform": platform}, work, "k9-verify")
            finally:
                shutil.rmtree(work, ignore_errors=True)
        cfg["pass"] = all(cfg[k]["pass"] for k in
                          ("mid_wal_append", "mid_snapshot"))
        out["pass"] = out["pass"] and cfg["pass"]
        out["configs"][name] = cfg
    return out


def _k9_verify(spec_path: str) -> dict:
    """`--chaos-cell k9-verify <spec.json>`: the armed child is dead.
    Run the uninterrupted reference, recover from the dead child's WAL
    and snapshots, resume the unacked tail, compare."""
    import json as _json
    import shutil
    import tempfile
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.persistence import FileSystemPersistenceStore

    with open(spec_path) as f:
        spec = _json.load(f)
    streams, batch = spec["streams"], spec["batch"]
    tape = _k9_tape(spec["seed"], streams, spec["rounds"], batch,
                    spec["keys"])
    events_in = spec["rounds"] * batch * len(streams)

    # uninterrupted reference run (in-process feed; wire-vs-inproc
    # byte-identity is net_bench's standing assertion)
    clean_dir = tempfile.mkdtemp(prefix="siddhi_k9_clean_")
    mgr = SiddhiManager()
    mgr.set_persistence_store(FileSystemPersistenceStore(clean_dir))
    rt = mgr.create_app_runtime(spec["clean_app"])
    hs = {sid: rt.input_handler(sid) for sid in streams}
    for rd in tape:
        for sid in streams:
            cols, ts = rd[sid]
            hs[sid].send_batch(cols, ts)
    rt.flush()
    want = sorted(map(tuple, rt.tables["OutT"].all_rows()))
    mgr.shutdown()
    shutil.rmtree(clean_dir, ignore_errors=True)

    m2 = SiddhiManager()
    m2.set_persistence_store(FileSystemPersistenceStore(spec["snap_dir"]))
    rt2 = m2.create_app_runtime(spec["app"])
    rep = rt2.recover()
    durable = dict(rt2.wal.seqs)
    h2 = {sid: rt2.input_handler(sid) for sid in streams}
    resumed_events = 0
    for k, rd in enumerate(tape):
        for sid in streams:
            if k + 1 > durable.get(sid, 0):
                cols, ts = rd[sid]   # the unacked tail: a
                h2[sid].send_batch(cols, ts)  # producer
                resumed_events += batch       # retransmits
    rt2.flush()
    got = sorted(map(tuple, rt2.tables["OutT"].all_rows()))
    shed = sum(len(e.events or ()) for e in rt2.error_store.entries())
    wm_events = sum(rep["watermark"].values()) * batch
    applied = wm_events + rep["replayed_events"] + resumed_events
    m2.shutdown()
    return {
        "killed": True, "clean_rows": len(want),
        "restored_revision": rep.get("restored_revision"),
        "watermark": rep.get("watermark"),
        "replayed_frames": rep.get("replayed_frames"),
        "corrupt_skipped": rep.get("corrupt_skipped"),
        "recovery_s": rep.get("recovery_s"),
        "resumed_events": resumed_events,
        "applied": applied, "shed": shed,
        "identical": got == want,
        "pass": (got == want and shed == 0
                 and applied + shed == events_in),
    }


K9_AGG = _K9_HEAD + """
@source(type='tcp', port='0')
define stream S (sym string, p double, ts long);
define aggregation Roll
from S
select sym, sum(p) as total, avg(p) as mean, count() as n
group by sym
aggregate by ts every sec, min;
"""

K9_AGG_QUERY = ("from Roll within 1699999000000L, 1700001000000L "
                "per 'sec' select sym, total, mean, n")


def chaos_agg_kill9(seed: int, platform: str) -> dict:
    """`--chaos` queryable-state section: the kill-9 harness pointed at
    a `define aggregation` app.  A child feeds TCP frames into the
    durable rollup and is SIGKILLED mid-`wal.append` (snapshot behind
    it) and mid-snapshot; a second child recovers and resumes the
    unacked tail (`_aggk9_verify`).  Asserted per kill point, against
    an uninterrupted run:

      * store-query rows byte-identical (the exactly-once invariant on
        the aggregation plane — no bucket double-merge, none lost)
      * the device-resident bucket store itself byte-identical
        (`state_dict()` compares raw f64 bases, not rendered rows)
      * zero ErrorStore captures"""
    import os
    import shutil
    import tempfile

    out = {"seed": seed, "kills": {}, "pass": True}
    snapshot_at = 4
    for kname, point, at in (
            ("mid_wal_append", "wal.append", snapshot_at + 3),
            ("mid_snapshot", "persist.save", 1)):
        work = tempfile.mkdtemp(prefix="siddhi_k9agg_")
        try:
            cell = _kill_then_verify({
                "app": K9_AGG.replace(
                    "@app:durability('batch')",
                    f"@app:durability('batch', dir='{work}/wal')"),
                "streams": ["S"], "snap_dir": os.path.join(work, "snap"),
                "seed": seed, "rounds": 10, "batch": 128, "keys": 6,
                "snapshot_at": snapshot_at, "with_ts": True,
                "kill_point": point, "kill_at": at,
                "platform": platform}, work, "aggk9-verify")
        finally:
            shutil.rmtree(work, ignore_errors=True)
        out["kills"][kname] = cell
        out["pass"] = out["pass"] and cell["pass"]
    return out


def _aggk9_verify(spec_path: str) -> dict:
    """`--chaos-cell aggk9-verify <spec.json>`: reference, recovery and
    comparison for one aggregation kill point."""
    import json as _json
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.persistence import FileSystemPersistenceStore

    with open(spec_path) as f:
        spec = _json.load(f)
    batch = spec["batch"]
    tape = _k9_tape(spec["seed"], ["S"], spec["rounds"], batch,
                    spec["keys"], with_ts=True)

    # uninterrupted reference (in-proc feed, same tape; durability off
    # -- the reference run needs no WAL and must not warn about one)
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        K9_AGG.replace("@app:durability('batch')\n", ""))
    rt.start()
    h = rt.input_handler("S")
    for rd in tape:
        cols, ts = rd["S"]
        h.send_batch(cols, ts)
    rt.flush()
    want_rows = rt.query(K9_AGG_QUERY)
    want_state = rt.aggregations["Roll"].state_dict()
    dev_path = rt.explain()["aggregations"]["Roll"]["path"]
    mgr.shutdown()

    m2 = SiddhiManager()
    m2.set_persistence_store(FileSystemPersistenceStore(spec["snap_dir"]))
    rt2 = m2.create_app_runtime(spec["app"])
    rep = rt2.recover()
    durable = dict(rt2.wal.seqs)
    h2 = rt2.input_handler("S")
    resumed = 0
    for k, rd in enumerate(tape):
        if k + 1 > durable.get("S", 0):
            cols, ts = rd["S"]
            h2.send_batch(cols, ts)
            resumed += batch
    rt2.flush()
    rows_ok = rt2.query(K9_AGG_QUERY) == want_rows
    state_ok = rt2.aggregations["Roll"].state_dict() == want_state
    shed = sum(len(e.events or ()) for e in rt2.error_store.entries())
    m2.shutdown()
    return {
        "killed": True, "path": dev_path, "clean_rows": len(want_rows),
        "restored_revision": rep.get("restored_revision"),
        "replayed_frames": rep.get("replayed_frames"),
        "resumed_events": resumed, "shed": shed,
        "rows_identical": rows_ok,
        "bucket_state_identical": state_ok,
        "pass": (rows_ok and state_ok and shed == 0
                 and dev_path != "host")}


# ---------------------------------------------------------------------------
# machine-loss chaos (`--chaos`): SIGKILL the PRIMARY PROCESS, promote the
# hot standby, resume the producer — the whole machine is gone, so only
# what replication shipped survives (docs/RELIABILITY.md "High
# availability & failover")
# ---------------------------------------------------------------------------

REPL_APP = """@app:name('HARepl')
@source(type='tcp', port='0')
define stream S (sym string, p double);
define table OutT (sym string, s double, c long);
@info(name='q') from S#window.length(64)
select sym, sum(p) as s, count() as c group by sym insert into OutT;
"""


def chaos_repl_child(spec_path: str) -> None:
    """Hidden `--chaos-repl-child <spec.json>` mode: run the PRIMARY of
    the machine-loss cell — a durable app plus a replication front door
    (NetServer with repl_resolve) — and SIGKILL OURSELVES at the armed
    injection point.  Two feed modes: 'parent' (the standby cell is
    the producer over loopback TCP; we die mid-`wal.append`, a frame
    the producer was never acked for) and 'self' (we feed our own tape,
    persist full+incremental snapshots that TRUNCATE the log, then
    idle; we die mid-`repl.ship snapshot:` — the standby's catch-up
    chain cut off halfway).  Exits 3 if the kill never fired."""
    import json as _json
    import os
    import signal
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.persistence import (
        IncrementalFileSystemPersistenceStore)
    from siddhi_tpu.net import TcpFrameClient
    from siddhi_tpu.net.server import NetServer

    with open(spec_path) as f:
        spec = _json.load(f)
    _require_probed(spec["platform"], "--chaos-repl-child")

    class _Kill9:
        """SIGKILL at the Nth check of one point (optionally only when
        the detail starts with a prefix — 'snapshot:' selects the
        catch-up frames of repl.ship)."""

        def __init__(self, point, at, prefix=""):
            self.point, self.at, self.prefix = point, at, prefix
            self.n = 0

        def check(self, point, detail=""):
            if point == self.point and \
                    str(detail).startswith(self.prefix):
                self.n += 1
                if self.n >= self.at:
                    os.kill(os.getpid(), signal.SIGKILL)

    mgr = SiddhiManager()
    mgr.set_persistence_store(
        IncrementalFileSystemPersistenceStore(spec["snap_dir"]))
    rt = mgr.create_app_runtime(spec["app"])
    rt.start()
    rt.fault_injector = _Kill9(spec["kill_point"], spec["kill_at"],
                               spec.get("kill_prefix", ""))
    srv = NetServer(lambda a, s: (_ for _ in ()).throw(KeyError(s)),
                    port=0, repl_resolve=lambda app: rt).start()
    ports = {"repl": srv.port, "source": rt.sources[0].port}
    tmp_ports = spec["ports_path"] + ".tmp"
    with open(tmp_ports, "w") as f:
        _json.dump(ports, f)
    os.replace(tmp_ports, spec["ports_path"])
    if spec["feed"] == "self":
        cli = TcpFrameClient("127.0.0.1", rt.sources[0].port, "S",
                             TcpFrameClient.cols_of_schema(
                                 rt.schemas["S"]))
        tape = _k9_tape(spec["seed"], ["S"], spec["rounds"],
                        spec["batch"], spec["keys"])
        for k, rd in enumerate(tape):
            cols, ts = rd["S"]
            cli.send_batch(cols, ts)
            cli.barrier(timeout=60)
            if k == spec["full_at"]:
                # first incremental persist = F- full (oplog activation);
                # its snapshot barrier truncates sealed segments
                rt.persist(incremental=True)
            elif k == spec["incr_at"]:
                rt.persist(incremental=True)    # I- delta -> 2-rev chain
        with open(spec["fed_path"], "w") as f:
            f.write("done")
    # serve (and, armed, die) until the parent's cell is over
    import time as _time
    deadline = _time.monotonic() + 600
    while _time.monotonic() < deadline:
        _time.sleep(0.05)
    os._exit(3)


def _repl_standby(peer_port: int, wal_dir: str, store_dir: str):
    """The hot standby of the machine-loss and split-brain cells."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.persistence import (
        IncrementalFileSystemPersistenceStore)
    mgr = SiddhiManager()
    # shipped F-/I- revisions land verbatim: the standby's store must
    # reassemble the chain at promote time
    mgr.set_persistence_store(
        IncrementalFileSystemPersistenceStore(store_dir))
    rt = mgr.create_app_runtime(
        "@app:durability('batch', dir='" + wal_dir + "', "
        "segment.bytes='2048')\n"
        "@app:replication('async', role='standby', "
        f"peer='127.0.0.1:{peer_port}')\n" + REPL_APP)
    rt.start()
    return mgr, rt


class ChaosRefused(RuntimeError):
    """A cell that cannot run on the visible devices."""


def _two_process_envs(device: dict) -> tuple:
    """Environments for two chip-holding processes alive at once (the
    machine-loss cell's primary and standby).  On the CPU there is
    nothing to bind.  On TPUs each process is bound to its own chip by
    libtpu's process-topology variables, set here by the JAX-free parent
    before either child starts; with fewer than two chips the cell
    cannot run at all."""
    import os
    base = dict(os.environ)
    if device["platform"] != "tpu":
        return base, base
    if device["device_count"] < 2:
        raise ChaosRefused(
            f"the machine-loss cell keeps a primary and a standby alive "
            f"at once and a chip belongs to one process at a time: it "
            f"needs 2 TPU chips, {device['device_count']} visible "
            f"({device['device_kind']})")

    def bound_to(chip: int) -> dict:
        # a one-chip, one-process topology of its own: the host-wide
        # bounds the machine exports (older spelling of the same
        # variables) must not contradict it
        env = {k: v for k, v in base.items()
               if k not in ("TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS")}
        port = 8476 + chip
        env.update({"TPU_VISIBLE_CHIPS": str(chip),
                    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                    "TPU_PROCESS_BOUNDS": "1,1,1",
                    "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
                    "TPU_PROCESS_PORT": str(port),
                    "TPU_RUNTIME_METRICS_PORTS": str(8431 + chip)})
        return env
    return bound_to(0), bound_to(1)


def chaos_machine_loss(seed: int, device: dict) -> dict:
    """`--chaos` machine-loss cell: the primary RUNS IN A CHILD PROCESS
    and is SIGKILLED — its disk is treated as gone; a second child holds
    the hot standby, promotes it, and resumes the producer from the
    standby's durable watermark (exactly a real producer's retransmit
    contract) — `_ml_standby`.  Two kill shapes:

      * mid_frame: killed inside `wal.append` of a frame the producer
        was never acked for — the standby replays its replicated log
        and the producer retransmits the tail
      * mid_snapshot_ship: killed halfway through shipping the
        snapshot catch-up chain (the standby subscribed AFTER
        truncation) — the standby promotes from the partial chain's
        newest full revision and the producer retransmits the rest

    Asserted per shape: outputs byte-identical to an uninterrupted run,
    `events_in == applied + shed` (shed == 0 — nothing quietly parked),
    and the pre-kill happy path left ZERO ErrorStore captures."""
    import json as _json
    import os
    import shutil
    import subprocess
    import tempfile
    import time as _time

    env_primary, env_standby = _two_process_envs(device)
    rounds, batch, keys = 10, 128, 6
    out = {"seed": seed, "events_in": rounds * batch, "pass": True}
    shapes = (
        ("mid_frame", {"feed": "parent", "kill_point": "wal.append",
                       "kill_at": 7}),
        ("mid_snapshot_ship", {"feed": "self", "kill_point": "repl.ship",
                               "kill_prefix": "snapshot:", "kill_at": 2,
                               "full_at": 3, "incr_at": 6}),
    )
    for name, kill in shapes:
        work = tempfile.mkdtemp(prefix=f"siddhi_ml_{name}_")
        spec = {"app": ("@app:durability('batch', dir='" + work
                        + "/pwal', segment.bytes='2048')\n" + REPL_APP),
                "work": work,
                "snap_dir": os.path.join(work, "psnap"),
                "ports_path": os.path.join(work, "ports.json"),
                "fed_path": os.path.join(work, "fed"),
                "rc_path": os.path.join(work, "primary_rc"),
                "seed": seed, "rounds": rounds, "batch": batch,
                "keys": keys, "platform": device["platform"], **kill}
        spec_path = os.path.join(work, "spec.json")
        with open(spec_path, "w") as f:
            _json.dump(spec, f)
        proc = _spawn(["--chaos-repl-child", spec_path],
                      stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                      env=env_primary)
        standby = None
        try:
            if not _wait_file(spec["ports_path"], alive=proc):
                raise RuntimeError("primary never published its ports")
            if spec["feed"] == "self" and \
                    not _wait_file(spec["fed_path"], alive=proc):
                # the child feeds + snapshots ITSELF (truncating its
                # log); the standby subscribes only after, so its very
                # first poll is the catch-up gap
                raise RuntimeError("primary never finished feeding")
            standby = _spawn_cell("ml-standby", spec_path,
                                  device["platform"], env=env_standby)
            # the standby cell drives the primary to its armed point;
            # the kill fired when the primary is gone
            deadline = _time.monotonic() + 180
            while proc.poll() is None and standby.poll() is None:
                if _time.monotonic() > deadline:
                    raise RuntimeError("the armed primary never died")
                _time.sleep(0.05)
            if proc.poll() is not None:
                tmp = spec["rc_path"] + ".tmp"
                with open(tmp, "w") as f:
                    f.write(str(proc.returncode))
                os.replace(tmp, spec["rc_path"])
            cell = _reap_cell(standby, "ml-standby", timeout_s=300)
        except Exception as e:
            cell = {"pass": False, "error": f"{type(e).__name__}: {e}"}
        finally:
            for p in (proc, standby):
                if p is not None and p.poll() is None:
                    p.kill()
                    p.wait(timeout=30)
            if not cell.get("killed"):
                cell["child_tail"] = (proc.stderr.read() or b"") \
                    .decode(errors="replace")[-500:]
            shutil.rmtree(work, ignore_errors=True)
        out[name] = cell
        out["pass"] = out["pass"] and bool(cell.get("pass"))
    return out


def _wait_file(path: str, timeout_s: float = 120.0, alive=None) -> bool:
    """Poll for a file another process publishes (atomically); gives up
    early when the publishing process `alive` has exited."""
    import os
    import time as _time
    deadline = _time.monotonic() + timeout_s
    while _time.monotonic() < deadline:
        if os.path.exists(path):
            return True
        if alive is not None and alive.poll() is not None:
            return os.path.exists(path)
        _time.sleep(0.02)
    return False


def _ml_standby(spec_path: str) -> dict:
    """`--chaos-cell ml-standby <spec.json>`: the surviving machine of
    the machine-loss cell.  Runs the uninterrupted reference, starts the
    hot standby against the live primary, plays the producer ('parent'
    feed), waits for the parent to report the primary's death, promotes,
    resumes the producer's unacked tail and compares."""
    import json as _json
    import os
    import shutil
    import signal
    import tempfile
    import time as _time
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.persistence import FileSystemPersistenceStore
    from siddhi_tpu.net import TcpFrameClient

    with open(spec_path) as f:
        spec = _json.load(f)
    work, batch = spec["work"], spec["batch"]
    events_in = spec["rounds"] * batch
    tape = _k9_tape(spec["seed"], ["S"], spec["rounds"], batch,
                    spec["keys"])

    # uninterrupted reference
    clean_dir = tempfile.mkdtemp(prefix="siddhi_ml_clean_")
    mgr = SiddhiManager()
    mgr.set_persistence_store(FileSystemPersistenceStore(clean_dir))
    rt = mgr.create_app_runtime(REPL_APP)
    h = rt.input_handler("S")
    for rd in tape:
        cols, ts = rd["S"]
        h.send_batch(cols, ts)
    rt.flush()
    want = sorted(map(tuple, rt.tables["OutT"].all_rows()))
    mgr.shutdown()
    shutil.rmtree(clean_dir, ignore_errors=True)

    with open(spec["ports_path"]) as f:
        ports = _json.load(f)
    cell = {"clean_rows": len(want)}
    mgr_s, rt_s = _repl_standby(ports["repl"], os.path.join(work, "swal"),
                                os.path.join(work, "ssnap"))
    try:
        sent = 0
        if spec["feed"] == "parent":
            cli = TcpFrameClient(
                "127.0.0.1", ports["source"], "S",
                TcpFrameClient.cols_of_schema(rt_s.schemas["S"]))
            try:
                for rd in tape:
                    cols, ts = rd["S"]
                    cli.send_batch(cols, ts)
                    cli.barrier(timeout=60)
                    sent += 1
                    if sent == 3:
                        # pre-kill happy path: NOTHING was parked
                        cell["pre_kill_captures"] = len(rt_s.error_store)
            except Exception:
                pass                # the machine just died mid-frame
            finally:
                try:
                    cli.close()
                except Exception:
                    pass
        # the kill fired (anything else is a failed cell)
        if not _wait_file(spec["rc_path"], timeout_s=180):
            raise RuntimeError("the parent never reported the primary's "
                               "exit")
        with open(spec["rc_path"]) as f:
            killed = int(f.read()) == -signal.SIGKILL
        cell["killed"] = killed
        if spec["feed"] == "self":
            # let the receiver land whatever the chain shipped
            deadline = _time.monotonic() + 10
            while _time.monotonic() < deadline and \
                    rt_s.statistics()["replication"] \
                    .get("applied_snapshots", 0) < 1:
                _time.sleep(0.05)
        # post-kill `repl.receive` link errors are the EXPECTED loud
        # capture of a dead machine; any OTHER point captured means
        # the happy path quietly parked something
        cell["happy_path_captures"] = len(
            [e for e in rt_s.error_store.entries()
             if e.point != "repl.receive"])
        report = rt_s.promote()
        durable = dict(rt_s.wal.seqs)
        h2 = rt_s.input_handler("S")
        resumed_events = 0
        for k, rd in enumerate(tape):
            if k + 1 > durable.get("S", 0):
                cols, ts = rd["S"]
                h2.send_batch(cols, ts)     # producer retransmit
                resumed_events += batch
        rt_s.flush()
        got = sorted(map(tuple, rt_s.tables["OutT"].all_rows()))
        shed = sum(len(e.events or ())
                   for e in rt_s.error_store.entries())
        wm_events = sum(report["recovery"]["watermark"].values()) * batch
        applied = (wm_events + report["recovery"]["replayed_events"]
                   + resumed_events)
        ok = (killed and got == want and shed == 0
              and applied + shed == events_in
              and cell.get("happy_path_captures", 1) == 0
              and cell.get("pre_kill_captures", 0) == 0)
        cell.update({
            "promote_s": report["promote_s"],
            "generation": report["generation"],
            "restored_revision": report["recovery"]["restored_revision"],
            "replayed_frames": report["recovery"]["replayed_frames"],
            "resumed_events": resumed_events,
            "applied": applied, "shed": shed,
            "identical": got == want, "pass": ok})
    finally:
        mgr_s.shutdown()
    return cell


def chaos_split_brain(seed: int = 7) -> dict:
    """`--chaos` split-brain cell: after the standby promotes (fencing
    ABOVE every generation it saw), the deposed primary is still alive
    and still believes it serves.  Point the promoted node's receiver
    back at it — the operator misconfiguration that makes split-brain
    dangerous — and prove the fence rejects the stale timeline LOUDLY
    on both sides: the deposed primary refuses the from-the-future
    subscriber (`rejected_generation`, ERROR frame), and the promoted
    node captures the refusal in its ErrorStore instead of silently
    rewinding onto the dead branch."""
    import shutil
    import tempfile
    import time as _time
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.persistence import FileSystemPersistenceStore
    from siddhi_tpu.net.repl import WalReceiver
    from siddhi_tpu.net.server import NetServer

    work = tempfile.mkdtemp(prefix="siddhi_sb_")
    out = {"seed": seed, "pass": False}
    mgr_a = mgr_b = srv = None
    try:
        mgr_a = SiddhiManager()
        mgr_a.set_persistence_store(
            FileSystemPersistenceStore(work + "/asnap"))
        rt_a = mgr_a.create_app_runtime(
            "@app:durability('batch', dir='" + work + "/awal')\n"
            + REPL_APP)
        rt_a.start()
        srv = NetServer(lambda a, s: (_ for _ in ()).throw(KeyError(s)),
                        port=0, repl_resolve=lambda app: rt_a).start()
        mgr_b, rt_b = _repl_standby(srv.port, work + "/bwal",
                                    work + "/bsnap")
        tape = _k9_tape(seed, ["S"], 4, 64, 6)
        h = rt_a.input_handler("S")
        for rd in tape:
            cols, ts = rd["S"]
            h.send_batch(cols, ts)
        rt_a.flush()
        wm = rt_a.wal.watermark()
        deadline = _time.monotonic() + 20
        while _time.monotonic() < deadline and \
                rt_b.replication.applied_watermark() != wm:
            _time.sleep(0.02)
        report = rt_b.promote()         # A is now DEPOSED — but alive
        out["generation"] = report["generation"]
        # the misconfigured resubscribe: promoted B tails deposed A
        recv = WalReceiver(rt_b, rt_b.replication,
                           f"127.0.0.1:{srv.port}").start()
        try:
            deadline = _time.monotonic() + 20
            while _time.monotonic() < deadline and (
                    rt_a.replication is None
                    or rt_a.replication.rejected_generation < 1):
                _time.sleep(0.02)
        finally:
            recv.stop()
        a_rejected = (rt_a.replication is not None
                      and rt_a.replication.rejected_generation >= 1)
        b_captures = [e for e in rt_b.error_store.entries("_replication")
                      if "rejected" in e.message or "deposed" in e.message]
        # and B's own timeline was never rewound: its log still serves
        h2 = rt_b.input_handler("S")
        cols, ts = tape[0]["S"]
        h2.send_batch(cols, ts)
        rt_b.flush()
        out.update({
            "deposed_rejected_subscriber": a_rejected,
            "promoted_captured_refusal": len(b_captures),
            "promoted_still_serving":
                rt_b.wal.watermark()["S"] > wm["S"],
            "pass": bool(a_rejected and b_captures
                         and rt_b.wal.watermark()["S"] > wm["S"])})
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        if srv is not None:
            srv.stop()
        for m in (mgr_a, mgr_b):
            if m is not None:
                m.shutdown()
        shutil.rmtree(work, ignore_errors=True)
    return out


def durability_bench(smoke=True) -> dict:
    """The measured durability-overhead column: config-3 TCP-ingest eps
    per sync policy.  `'batch'` must cost <= 15% vs `'off'` (the bench
    `durability` field the acceptance criteria pin); `'fsync'` is
    reported for the honesty of the trade.  `'semi-sync'` is batch PLUS
    a live in-process hot standby whose append-ack the durable barrier
    waits on (@app:replication('semi-sync')) — it must cost <= 25% vs
    `'batch'` alone, measured at the same barrier cadence."""
    import shutil
    import tempfile
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.net import TcpFrameClient
    from siddhi_tpu.net.server import NetServer

    n = 1 << 12 if smoke else 1 << 15
    batch = 512 if smoke else 2048
    warm = 2
    tape = make_tape(n + warm * batch, batch)
    batches = _tape_str_batches(tape)
    n_timed = sum(t["n"] for t in tape[warm:])
    eps, matches = {}, {}
    tmp = tempfile.mkdtemp(prefix="siddhi_dur_bench_")
    try:
        for policy in ("off", "batch", "fsync", "semi-sync"):
            head = "@source(type='tcp', port='0')\n"
            if policy == "semi-sync":
                head = (f"@app:durability('batch', "
                        f"dir='{tmp}/wal_semi')\n"
                        f"@app:replication('semi-sync', "
                        f"ack.timeout='30 sec', heartbeat='25 ms')\n"
                        ) + head
            elif policy != "off":
                head = (f"@app:durability('{policy}', "
                        f"dir='{tmp}/wal_{policy}')\n") + head
            mgr = SiddhiManager()
            rt = mgr.create_app_runtime(head + DEV["patterns"] + C3)
            rows = []
            rt.add_batch_callback("Out", lambda b, rows=rows: rows.extend(
                map(tuple, b.rows(rt.strings))))
            rt.start()
            srv = mgr_s = None
            if policy == "semi-sync":
                # the hot standby the barrier waits on, in-process: a
                # replication front door on the primary + a standby
                # runtime tailing it (net/repl.py)
                srv = NetServer(
                    lambda a, s: (_ for _ in ()).throw(KeyError(s)),
                    port=0, repl_resolve=lambda app: rt).start()
                mgr_s = SiddhiManager()
                rt_s = mgr_s.create_app_runtime(
                    f"@app:name('DurStandby')\n"
                    f"@app:durability('batch', dir='{tmp}/wal_sb')\n"
                    f"@app:replication('async', role='standby', "
                    f"peer='127.0.0.1:{srv.port}')\n"
                    "define stream StockStream "
                    "(symbol string, price double, volume int);\n")
                rt_s.start()
            cli = TcpFrameClient("127.0.0.1", rt.sources[0].port, STREAM,
                                 TcpFrameClient.cols_of_schema(
                                     rt.schemas[STREAM]))
            for cols, ts in batches[:warm]:
                cli.send_batch(cols, ts)
            cli.barrier(timeout=120)
            t0 = time.perf_counter()
            for cols, ts in batches[warm:]:
                cli.send_batch(cols, ts)
            cli.barrier(timeout=120)
            eps[policy] = round(n_timed / (time.perf_counter() - t0))
            matches[policy] = len(rows)
            cli.close()
            if srv is not None:
                srv.stop()
            if mgr_s is not None:
                mgr_s.shutdown()
            mgr.shutdown()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    overhead = {p: round(100.0 * (1.0 - eps[p] / eps["off"]), 1)
                for p in ("batch", "fsync")}
    # the semi-sync premium is measured against 'batch' ALONE — the
    # replication cost on top of the same local sync policy
    overhead["semi-sync_vs_batch"] = round(
        100.0 * (1.0 - eps["semi-sync"] / eps["batch"]), 1)
    identical = len(set(matches.values())) == 1
    return {"policy": "batch", "tcp_eps": eps,
            "overhead_pct": overhead, "events": n_timed,
            "batch": batch, "identical_matches": identical,
            "pass": bool(overhead["batch"] <= 15.0 and identical
                         and overhead["semi-sync_vs_batch"] <= 25.0)}


def chaos_inproc(seed: int = 7) -> dict:
    """`--chaos-cell inproc`: every chaos section whose runtimes share
    ONE process.  Runs the pattern, window, and join configs clean and
    then under injected faults (core/faults.py FaultInjector), asserting
    ZERO event loss and full recovery:

      * transient dispatch resource faults  -> ladder halves the work and
        retries; outputs byte-identical to the clean run
      * persistent dispatch resource faults -> plan quarantined onto the
        interpreter path; outputs byte-identical to the clean run
      * sink publish faults -> retried with backoff; payloads that
        exhaust retries are captured in the ErrorStore and REPLAYED once
        the transport recovers — every payload delivered exactly once

    then the serving-plane chaos (`chaos_net`), the split-brain cell and
    the durability-overhead column.  Deterministic under a fixed seed:
    the injector's schedule and the backoff jitter both derive from it."""
    import warnings
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.faults import FaultInjector
    from siddhi_tpu.core.io import InMemoryBroker

    PATTERN = """
        @app:devicePatterns('prefer')
        @OnError(action='store')
        define stream S (sym string, p double);
        from every a=S[p > 120] -> b=S[p < 80] within 1 sec
        select a.sym as s1, b.sym as s2 insert into Out;
    """
    WINDOW = """
        @OnError(action='store')
        define stream S (sym string, p double);
        from S#window.length(64) select sym, sum(p) as s, count() as c
            group by sym insert into Out;
    """
    JOIN = """
        @OnError(action='store')
        define stream S (sym string, p double);
        define stream T (sym string, p double);
        from S#window.length(32) as a join T#window.length(32) as b
            on a.sym == b.sym
        select a.sym as sym, a.p as pa, b.p as pb insert into Out;
    """

    def feed(rt, streams, n_batches=8, batch=256, keys=8):
        rng = np.random.default_rng(seed)
        ts0 = 1_700_000_000_000
        rows = []
        rt.add_callback("Out", lambda evs: rows.extend(e.data for e in evs))
        handlers = [rt.input_handler(s) for s in streams]
        for k in range(n_batches):
            for h in handlers:
                h.send_batch(
                    {"sym": [f"K{i % keys}" for i in range(batch)],
                     "p": q4(rng.uniform(60.0, 140.0, batch))},
                    ts0 + np.arange(k * batch, (k + 1) * batch,
                                    dtype=np.int64) * 2)
            rt.flush()
        return sorted(map(tuple, rows))

    def run(app, streams, injector=None):
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(app)
        rt.fault_injector = injector
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                rows = feed(rt, streams)
            lad = next(iter(rt._ladders.values()), None)
            return rows, {
                "halvings": lad.halvings if lad else 0,
                "quarantined": bool(rt.statistics().get("degraded_plans")),
                "injected": (rt.fault_injector.stats()["fired"]
                             if rt.fault_injector else {})}
        finally:
            mgr.shutdown()

    out = {"seed": seed, "configs": {}, "pass": True}
    for name, app, streams in (("pattern", PATTERN, ["S"]),
                               ("window", WINDOW, ["S"]),
                               ("join", JOIN, ["S", "T"])):
        clean, _ = run(app, streams)
        halved, info_h = run(app, streams,
                             FaultInjector(seed=seed,
                                           counts={"dispatch": 2}))
        quar, info_q = run(app, streams,
                           FaultInjector(seed=seed,
                                         counts={"dispatch": 10 ** 6}))
        cfg = {"matches": len(clean),
               "halving": {"identical": halved == clean, **info_h},
               "quarantine": {"identical": quar == clean, **info_q}}
        ok = (halved == clean and quar == clean and len(clean) > 0
              and info_h["halvings"] >= 1 and not info_h["quarantined"]
              and info_q["quarantined"])
        cfg["pass"] = ok
        out["configs"][name] = cfg
        out["pass"] = out["pass"] and ok

    # sink delivery under publish faults: retry, capture, replay
    SINK = """
        define stream S (x int);
        @sink(type='inMemory', topic='chaos_out', on.error='store',
              max.retries='2', retry.interval='1 ms',
              breaker.threshold='4', breaker.reset='50 ms')
        define stream Out (x int);
        from S select x insert into Out;
    """
    got = []
    InMemoryBroker.reset()
    InMemoryBroker.subscribe("chaos_out", lambda m: got.append(m[0]))
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(SINK)
    rt.fault_injector = FaultInjector(seed=seed,
                                      rates={"sink.publish": 0.4})
    rt.start()
    h = rt.input_handler("S")
    n_sink = 64
    for i in range(n_sink):
        h.send((i,))
        rt.flush()
    stored = len(rt.error_store)
    rt.fault_injector = None            # transport recovers
    replay = rt.error_store.replay(rt)
    sink = rt.sinks[0]
    sink_ok = (sorted(got) == list(range(n_sink))
               and replay["remaining"] == 0)
    out["sink"] = {"delivered": len(got), "expected": n_sink,
                   "retries": sink.retries, "stored_then_replayed": stored,
                   "breaker_opens": sink.metrics().get("circuit_opens", 0),
                   "pass": sink_ok}
    out["pass"] = out["pass"] and sink_ok
    mgr.shutdown()

    # serving-plane chaos: mid-frame disconnects, slow shm consumer,
    # injected ingest faults (zero admitted-frame loss throughout)
    net = _safe("chaos net", lambda: chaos_net(seed), {"pass": False})
    out["net"] = net
    out["pass"] = out["pass"] and bool(net.get("pass"))

    # split-brain: the deposed primary is alive; fencing rejects its
    # timeline loudly on both sides
    sb = _safe("chaos split brain", lambda: chaos_split_brain(seed),
               {"pass": False})
    out["split_brain"] = sb
    out["pass"] = out["pass"] and bool(sb.get("pass"))

    # measured durability overhead per sync policy ('batch' <= 15%)
    dur = _safe("durability overhead", lambda: durability_bench(smoke=True),
                {"pass": False})
    out["durability"] = dur
    out["pass"] = out["pass"] and bool(dur.get("pass"))
    return out


CHAOS_CELLS = ("inproc", "kill9", "agg_kill9", "machine_loss")


def chaos_bench(seed: int = 7, cell=None) -> dict:
    """Seeded chaos harness (`--chaos [--seed N] [--cell C]`), the
    JAX-free orchestrator (see "--chaos orchestration" above): probes
    the devices through a child, then runs each cell's children one at
    a time.  `--cell` selects one of CHAOS_CELLS; `kill9:<config>` one
    kill-9 config (pattern / window / join)."""
    name, _, sub = (cell or "").partition(":")
    if cell is not None and (name not in CHAOS_CELLS
                             or (sub and (name != "kill9"
                                          or sub not in K9_CONFIGS))):
        sys.exit(f"bench.py --chaos --cell {cell!r}: expected one of "
                 f"{CHAOS_CELLS} or kill9:<{'|'.join(K9_CONFIGS)}>")
    want = CHAOS_CELLS if cell is None else (name,)
    device = _run_cell("probe", "-", "any")
    platform = device["platform"]
    out = {"seed": seed, "device": device, "pass": True}
    if "inproc" in want:
        # injected dispatch/sink/net faults, split-brain, durability
        # overhead: runtimes that share one process
        out.update(_run_cell("inproc", seed, platform, timeout_s=1800))
    if "kill9" in want:
        # durability chaos: SIGKILL at fault-injected points
        # (mid-wal.append, mid-snapshot), recover, prove exactly-once
        out["kill9"] = chaos_kill9(seed, platform, only=sub or None)
    if "agg_kill9" in want:
        # queryable-state chaos: SIGKILL mid-flush on a durable
        # aggregation, recover, prove the bucket store byte-identical
        out["agg_kill9"] = chaos_agg_kill9(seed, platform)
    if "machine_loss" in want:
        # machine-loss chaos: SIGKILL the primary PROCESS (its disk is
        # gone), promote the hot standby, resume the producer — lossless
        try:
            out["machine_loss"] = chaos_machine_loss(seed, device)
        except ChaosRefused as e:
            print(f"[bench] machine-loss cell refused: {e}",
                  file=sys.stderr, flush=True)
            out["refused"] = {"machine_loss": str(e)}
    for c in ("kill9", "agg_kill9", "machine_loss"):
        if c in out:
            out["pass"] = out["pass"] and bool(out[c].get("pass"))
    if cell == "machine_loss" and "refused" in out:
        out["pass"] = False         # asked for exactly this, got nothing
    out["parent_jax_free"] = "jax" not in sys.modules
    return out


CHAOS_CELL_FNS = {"probe": device_info,
                  "inproc": lambda seed: chaos_inproc(int(seed)),
                  "k9-verify": _k9_verify,
                  "aggk9-verify": _aggk9_verify,
                  "ml-standby": _ml_standby}


def _print_summary(summary: dict, cap: int = 2048) -> None:
    """Emit the machine-parseable summary as the FINAL stdout line,
    bounded to `cap` bytes: drivers keep only a stdout tail and parse
    its last line, so an oversized line truncates into garbage (the
    BENCH "parsed": null failure shape).  Oversize degrades by dropping
    detail keys — never by emitting an unparseable line.  The bound is
    HARD: if dropping detail keys still leaves the line over cap (or a
    value fails to serialize), a minimal headline line prints instead,
    so the last stdout line ALWAYS round-trips through json.loads
    (pinned by scripts/smoke.sh and tests/test_bench_summary.py)."""
    drop_order = ("configs", "roofline", "transport", "harness",
                  "durability", "placement")
    try:
        line = json.dumps(summary)
        for key in drop_order:
            if len(line) <= cap:
                break
            summary.pop(key, None)
            line = json.dumps(summary)
    except (TypeError, ValueError):        # non-serializable value crept in
        line = None
    if line is None or len(line) > cap:
        line = json.dumps({k: summary.get(k) for k in
                           ("metric", "value", "unit", "vs_baseline",
                            "detail")
                           if isinstance(summary.get(k),
                                         (str, int, float, type(None)))})
    sys.stderr.flush()
    print(line, flush=True)


def pattern_families_smoke() -> dict:
    """`bench.py --family-smoke` (scripts/smoke.sh): one eligible pattern
    per plan family, run differentially against the host interpreter —
    a lowering regression in any family fails fast, in CI time budget.
    Includes the ISSUE-13 lowerings: a count-quantifier cell (rank/
    select chase) and a partitioned-lanes parity cell (the lane-vmapped
    flat block vs per-key host clones)."""
    from siddhi_tpu import SiddhiManager

    C_COUNT = STOCK + (
        "@info(name='q') from every e1=StockStream[price > 110]<1:3> -> "
        "e2=StockStream[price < 95] within 1 sec "
        "select e1[0].price as a, e1[last].price as b, e2.price as c "
        "insert into Out;\n")

    CASES = {
        # family -> (annotation head, query): each query is eligible for
        # the family it exercises (asserted below via plan.family)
        "seq": ("@app:patternFamily('seq')\n", C3),
        "chunk": ("@app:patternFamily('chunk')\n", C3),
        "scan": ("@app:patternFamily('scan')\n", C3),
        "dfa": ("@app:patternFamily('dfa')\n", C3S),
        "scan_count": ("@app:patternFamily('scan')\n", C_COUNT),
        "dfa_count": ("@app:patternFamily('dfa')\n", C_COUNT),
    }

    def run(app, n=1024, batch=256, keys=8, sort=False):
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(app)
        rows = []
        rt.add_batch_callback("Out", lambda b: rows.extend(
            map(tuple, b.rows(rt.strings))))
        rt.start()
        h = rt.input_handler(STREAM)
        from siddhi_tpu.core.pattern_plan import DevicePatternPlan
        fam = next((p.family for p in rt._plans
                    if isinstance(p, DevicePatternPlan)), None)
        tape = make_tape(n, batch, keys=keys)
        for cols, ts in _columnar(rt, STREAM, tape, keys):
            h.send_batch(cols, ts)
        rt.flush()
        mgr.shutdown()
        return fam, sorted(rows) if sort else rows

    out = {"families": {}, "pass": True}
    for cell, (ann, q) in CASES.items():
        fam = cell.split("_")[0]
        used, dev = run(ann + DEV["patterns"] + q)
        _u, host = run(HOST["patterns"] + q)
        ok = used == fam and dev == host and len(dev) > 0
        out["families"][cell] = {"engaged": used, "matches": len(dev),
                                 "host_matches": len(host),
                                 "identical": dev == host, "pass": ok}
        out["pass"] = out["pass"] and ok

    # partitioned-lanes parity: config 4's shape at smoke scale, default
    # family selection (must be a parallel one), per-key host clones as
    # the oracle; cross-key delivery order is not defined -> sorted
    used, dev = run("@app:partitionCapacity(64)\n" + C4,
                    keys=48, sort=True)
    _u, host = run(HOST["patterns"] + C4, keys=48, sort=True)
    ok = used in ("scan", "dfa") and dev == host and len(dev) > 0
    out["families"]["partitioned_lanes"] = {
        "engaged": used, "matches": len(dev), "host_matches": len(host),
        "identical": dev == host, "pass": ok}
    out["pass"] = out["pass"] and ok
    return out


# ---------------------------------------------------------------------------
# queryable-state workload matrix (`--matrix`): DEBS-style rollup shapes
# over the aggregation plane, every cell asserting device-vs-host parity
# (docs/AGGREGATION.md)
# ---------------------------------------------------------------------------

MATRIX_TS0 = 1_700_000_000_000


def _matrix_app(head=""):
    return (head +
            "define stream Trades "
            "(sym string, p double, v double, ts long);\n"
            "define aggregation Roll\n"
            "from Trades\n"
            "select sym, sum(p * v) as turnover, avg(p) as mean, "
            "min(p) as lo, max(p) as hi, count() as n\n"
            "group by sym\n"
            "aggregate by ts every sec, min, hour;\n")


def _matrix_tape(n_batches, batch, keys, seed=13):
    rng = np.random.default_rng(seed)
    out = []
    for k in range(n_batches):
        ts = (MATRIX_TS0 + k * 1500
              + np.sort(rng.integers(0, 1500, batch)))
        out.append((
            {"sym": np.array([f"G{i}" for i in
                              rng.integers(0, keys, batch)]),
             "p": rng.uniform(10, 500, batch),
             "v": rng.uniform(1, 50, batch),
             "ts": ts.astype(np.int64)},
            ts.astype(np.int64)))
    return out


def _matrix_query(per="min"):
    return (f"from Roll within {MATRIX_TS0 - 3_600_000}L, "
            f"{MATRIX_TS0 + 86_400_000}L per {per!r} "
            f"select sym, turnover, mean, lo, hi, n")


def matrix_bench(smoke=False) -> dict:
    """Queryable-state workload matrix (`--matrix`): DEBS-grand-challenge
    shaped cells over `define aggregation`:

      * rollup_kN — ingest-only rollup sweep across group-by
        cardinalities; per-cell differential against the forced-host
        path (`@app:deviceAggregations('off')`) across EVERY duration
      * mixed     — interleaved ingest + store queries on one thread
        (the dashboard-refresh shape); in-process store-query p99
      * wire      — paced TCP producer thread + a second connection
        issuing concurrent wire store queries; client-observed p99 and
        final wire-vs-inproc row parity

    Per-cell summary (eps + store_query_p99_ms + parity) lands in
    BENCH_DETAIL.json; the final stdout line is machine-parseable."""
    import threading
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.net import TcpFrameClient

    n_batches = 8 if smoke else 24
    batch = 512 if smoke else 4096
    key_sweep = (8, 64) if smoke else (8, 128, 1024)
    pers = ("sec", "min", "hour")

    def run_inproc(head, keys, query_every=0):
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(_matrix_app(head))
        rt.start()
        h = rt.input_handler("Trades")
        tape = _matrix_tape(n_batches, batch, keys)
        qlat = []
        t0 = time.perf_counter()
        for i, (cols, ts) in enumerate(tape):
            h.send_batch(cols, ts)
            if query_every and (i + 1) % query_every == 0:
                tq = time.perf_counter()
                rt.query(_matrix_query())
                qlat.append((time.perf_counter() - tq) * 1e3)
        rt.flush()
        elapsed = time.perf_counter() - t0
        rows = {per: sorted(rt.query(_matrix_query(per)))
                for per in pers}
        path = rt.explain()["aggregations"]["Roll"]["path"]
        sq = (rt.statistics().get("aggregation", {})
              .get("store_query", {}))
        mgr.shutdown()
        return rows, elapsed, path, qlat, sq

    out = {"smoke": smoke, "events_per_cell": n_batches * batch,
           "cells": {}, "pass": True}

    # rollup cardinality sweep: device vs forced-host differential
    host_rows = {}
    for keys in key_sweep:
        dev_rows, el, path, _, _ = run_inproc("", keys)
        hrows, _, hpath, _, _ = run_inproc(
            "@app:deviceAggregations('off')\n", keys)
        host_rows[keys] = hrows
        parity = dev_rows == hrows
        ok = (parity and path == "device-resident" and hpath == "host"
              and all(len(v) > 0 for v in dev_rows.values()))
        out["cells"][f"rollup_k{keys}"] = {
            "keys": keys, "eps": round(n_batches * batch / el),
            "path": path, "parity": parity,
            "rows": {per: len(v) for per, v in dev_rows.items()},
            "pass": ok}
        out["pass"] = out["pass"] and ok

    # mixed ingest + store-query load on one thread
    mkeys = key_sweep[-1]
    mrows, mel, mpath, qlat, msq = run_inproc("", mkeys, query_every=1)
    mok = (mrows == host_rows[mkeys] and mpath == "device-resident"
           and len(qlat) == n_batches)
    out["cells"]["mixed"] = {
        "keys": mkeys, "eps": round(n_batches * batch / mel),
        "store_queries": len(qlat),
        "store_query_p99_ms": round(float(np.percentile(qlat, 99)), 3),
        "tracker_p99_ms": msq.get("p99_ms"),
        "parity": mrows == host_rows[mkeys], "pass": mok}
    out["pass"] = out["pass"] and mok

    # concurrent wire store queries under paced TCP ingest
    wkeys = key_sweep[0]
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        _matrix_app("@source(type='tcp', port='0')\n"))
    rt.start()
    port = rt.sources[0].port
    cols_spec = TcpFrameClient.cols_of_schema(rt.schemas["Trades"])
    tape = _matrix_tape(n_batches, batch, wkeys)
    stop = threading.Event()
    feed_err = []

    def feed():
        cli = TcpFrameClient("127.0.0.1", port, "Trades", cols_spec)
        try:
            for cols, ts in tape:
                cli.send_batch(cols, ts)
                time.sleep(0.001)      # paced: leave room for queries
            cli.barrier(timeout=300)
        except Exception as e:          # surfaced in the cell result
            feed_err.append(repr(e))
        finally:
            stop.set()
            cli.close()

    qcli = TcpFrameClient("127.0.0.1", port, "Trades", cols_spec)
    th = threading.Thread(target=feed)
    t0 = time.perf_counter()
    th.start()
    wlat = []
    while not stop.is_set() or not wlat:
        tq = time.perf_counter()
        qcli.query(_matrix_query(), timeout=120)
        wlat.append((time.perf_counter() - tq) * 1e3)
    th.join()
    elapsed = time.perf_counter() - t0
    wire_rows = sorted(qcli.query(_matrix_query(), timeout=120))
    inproc_rows = sorted(rt.query(_matrix_query()))
    qcli.close()
    wsq = rt.statistics().get("aggregation", {}).get("store_query", {})
    mgr.shutdown()
    wok = (not feed_err and wire_rows == inproc_rows
           and len(wire_rows) > 0)
    out["cells"]["wire"] = {
        "keys": wkeys, "eps": round(n_batches * batch / elapsed),
        "store_queries": len(wlat),
        "store_query_p99_ms": round(float(np.percentile(wlat, 99)), 3),
        "tracker_p99_ms": wsq.get("p99_ms"),
        "parity": wire_rows == inproc_rows,
        "feed_errors": feed_err, "pass": wok}
    out["pass"] = out["pass"] and wok
    return out


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if "--chaos-child" in argv:
        # hidden subprocess mode for the kill-9 durability chaos: feeds
        # the scripted tape and SIGKILLs itself at the armed point
        chaos_kill9_child(argv[argv.index("--chaos-child") + 1])
        return
    if "--chaos-repl-child" in argv:
        # hidden subprocess mode for the machine-loss chaos: runs the
        # PRIMARY (durable app + replication front door) and SIGKILLs
        # itself at the armed point
        chaos_repl_child(argv[argv.index("--chaos-repl-child") + 1])
        return
    if "--chaos-cell" in argv:
        # hidden subprocess mode: one chaos cell's runtimes, in a child
        # of the JAX-free `--chaos` parent; result = last stdout line
        name, platform, arg = argv[argv.index("--chaos-cell") + 1:][:3]
        if name != "probe":
            _require_probed(platform, f"--chaos-cell {name}")
        print(json.dumps(CHAOS_CELL_FNS[name](arg), default=str))
        return
    # full-size modes report device rates: TPU or nothing.  The `--smoke`
    # sizes and the parity/chaos modes assert behaviour and are the CPU
    # lane's (scripts/smoke.sh) — their output names the platform too.
    if not any(f in argv for f in ("--smoke", "--family-smoke", "--chaos")):
        require_platform("tpu", " ".join(argv) or "(full run)",
                         "this mode reports device rates")
    if "--family-smoke" in argv:
        res = pattern_families_smoke()
        print(json.dumps({"metric": "plan_family_parity",
                          "value": 1 if res["pass"] else 0,
                          "unit": "all_families_match_interpreter", **res}))
        if not res["pass"]:
            sys.exit(1)
        return
    if "--net" in argv:
        # serving-plane bench (docs/SERVING.md): REST vs TCP vs shm vs
        # in-process on config 3, byte-identical differential, paced 2x
        # overload with shed accounting + replay; --smoke shrinks for CI
        res = net_bench(smoke="--smoke" in argv)
        print(json.dumps({"metric": "net_serving_plane",
                          "value": res["tcp_vs_rest"],
                          "unit": "tcp_frame_eps_over_per_event_rest",
                          **res}))
        if not res["pass"]:
            sys.exit(1)
        return
    if "--matrix" in argv:
        # queryable-state workload matrix (docs/AGGREGATION.md): rollup
        # cardinality sweep + mixed query/ingest + concurrent wire
        # store queries, each cell device-vs-host parity-checked;
        # --smoke shrinks it for scripts/smoke.sh
        res = matrix_bench(smoke="--smoke" in argv)
        detail = {"harness": harness_info(), "matrix": res}
        with open("BENCH_DETAIL.json", "w") as f:
            json.dump(detail, f, indent=1, default=str)
        print(json.dumps({
            "metric": "queryable_state_matrix",
            "value": 1 if res["pass"] else 0,
            "unit": "all_cells_device_host_parity",
            "cells": {k: {"eps": c.get("eps"),
                          "store_query_p99_ms":
                              c.get("store_query_p99_ms"),
                          "parity": c.get("parity", c.get("pass"))}
                      for k, c in res["cells"].items()},
            "detail": "BENCH_DETAIL.json"}))
        if not res["pass"]:
            sys.exit(1)
        return
    if "--chaos" in argv:
        seed = 7
        if "--seed" in argv:
            seed = int(argv[argv.index("--seed") + 1])
        res = chaos_bench(seed, argv[argv.index("--cell") + 1]
                          if "--cell" in argv else None)
        print(json.dumps({"metric": "chaos_recovery",
                          "value": 1 if res["pass"] else 0,
                          "unit": "all_recovery_paths_lossless", **res}))
        if not res["pass"]:
            sys.exit(1)
        return
    if "--smoke" in argv:
        # CI sanity (scripts/smoke.sh): a short pipelined-vs-unpipelined
        # run over the multi-plan config — asserts identical match
        # counts (inside bench_overlap) and prints the eps delta, so
        # overlap regressions surface in tier-1 time budget
        res = bench_overlap(n=1 << 12, batch=1 << 10, repeats=1, depth=2)
        print(json.dumps({
            "metric": "pipelined_vs_unpipelined_smoke",
            "value": res["overlap_speedup"],
            "unit": "eps_ratio",
            "eps_pipelined": res["device_eps"],
            "eps_unpipelined": res["unpipelined_eps"],
            "overlap_ratio": res["overlap_ratio"],
            "matches": res["matches"],
        }))
        return
    t0 = time.perf_counter()
    configs = {}

    configs["1_filter"] = bench_config(
        "filter", PIPE + DEV["filters"] + C1, HOST["filters"] + C1,
        n=1 << 19, batch=1 << 18, repeats=5)
    configs["1_filter"]["kernel_eps"] = kernel_eps(
        DEV["filters"] + C1, "filter", batch=1 << 18)
    _mark("config 1 done", t0)

    configs["2_window_agg"] = bench_config(
        "window", PIPE + DEV["windows"] + C2, HOST["windows"] + C2,
        n=1 << 18, batch=1 << 17, repeats=5)
    configs["2_window_agg"]["kernel_eps"] = kernel_eps(
        DEV["windows"] + C2, "window", batch=1 << 17)
    _mark("config 2 done", t0)

    configs["3_sequence"] = bench_config(
        "sequence", PIPE + DEV["patterns"] + C3, HOST["patterns"] + C3,
        n=1 << 18, batch=1 << 17, latency=True,
        lat_dev_app=DEV["patterns"] + C3)
    info3: dict = {}
    configs["3_sequence"]["kernel_eps"] = kernel_eps(
        DEV["patterns"] + C3, "pattern", batch=1 << 17, info=info3)
    configs["3_sequence"]["plan_family"] = info3.get("plan_family")
    # per-family kernel roofline sweep (the plan-family axis): same tape,
    # same batch, each family forced via @app:patternFamily; the "dfa"
    # family needs a static transition, so it sweeps the C3S variant
    # next to "scan" on the same tape for a like-for-like column

    def _fam_eps(fam, app):
        # a forced-but-ineligible family falls back with a warning; the
        # roofline must never mislabel the fallback's throughput, so the
        # ENGAGED family is checked and mismatches are reported as such
        inf: dict = {}
        eps = kernel_eps(app, "pattern", batch=1 << 17, info=inf)
        used = inf.get("plan_family")
        if used != fam:
            return {"eps": eps, "engaged": used, "requested": fam}
        return eps

    configs["3_sequence"]["kernel_eps_by_family"] = {
        fam: _safe(f"kernel_eps family {fam}", lambda fam=fam: _fam_eps(
            fam, f"@app:patternFamily('{fam}')\n" + DEV["patterns"] + C3))
        for fam in ("seq", "chunk", "scan")}
    configs["3_sequence"]["kernel_eps_static_by_family"] = {
        fam: _safe(f"kernel_eps static family {fam}",
                   lambda fam=fam: _fam_eps(
                       fam, f"@app:patternFamily('{fam}')\n"
                       + DEV["patterns"] + C3S))
        for fam in ("scan", "dfa")}
    _mark("config 3 done", t0)

    # latency/throughput frontier for the CEP sequence config (the
    # micro-batch size is the knob, VERDICT r3 #3) — measured HERE, before
    # the expensive configs 4/5, so a slow run degrades those first
    c3 = configs["3_sequence"]
    # the largest frontier point reuses config 3's measured eps but gets
    # a REAL p99 (it used to report null): measured unpipelined, like
    # every other frontier point
    big = c3["batch"]
    # the largest frontier point gets a REAL measured p99 like every
    # other point: warmed (and flushed) before timing — the same
    # treatment config 6 got in PR 5
    c3["frontier"] = _safe("frontier", lambda: frontier(
        DEV["patterns"] + C3, HOST["patterns"] + C3,
        deadline=t0 + 420), []) + [
        {"batch": big, "eps": c3["device_eps"],
         "p99_ms": _safe("big-point p99", lambda: p99_latency(
             DEV["patterns"] + C3, STREAM,
             make_tape(big * 8, big), 8, warm=4))}]
    c3["latency_demo"] = _safe("latency_demo", lambda: latency_demo(
        DEV["patterns"] + C3, HOST["patterns"] + C3))
    _mark("frontier + latency demo done", t0)

    head = ("@app:partitionCapacity(1000)\n@app:deviceSlots(32)\n")
    configs["4_partitioned_1k"] = bench_config(
        "partitioned", head + C4, HOST["patterns"] + C4,
        n=2 << 18, batch=1 << 18, keys=1000, latency=True, repeats=5)
    info4: dict = {}
    configs["4_partitioned_1k"]["kernel_eps"] = kernel_eps(
        head + C4, "pattern", batch=1 << 18, keys=1000, info=info4)
    configs["4_partitioned_1k"]["plan_family"] = info4.get("plan_family")

    c5 = c5_app(1000)
    c5_outs = tuple(f"Out{i}" for i in range(16))
    configs["5_1k_mixed_queries"] = bench_config(
        "1k-queries", c5, HOST["patterns"] + c5,
        n=1 << 11, batch=1 << 10, dt_ms=50, warm=2,
        out_streams=c5_outs, check_matches=True)
    configs["5_1k_mixed_queries"]["note"] = \
        ("device = 4 fused multi-query kernels (250 lanes each), median of "
         "3 x 2048-event segments; host = 1000 sequential matchers")

    configs["6_join"] = bench_join(n=1 << 15, batch=4096)

    configs["8_multi_plan_overlap"] = bench_overlap()

    # externalTimeBatch window row (device kind added r5): same tape but
    # with an event-time column driving the tumbling buckets
    def et_tape_cols(rt, tape):
        codes = np.array([rt.strings.encode(f"K{i}") for i in range(8)],
                         dtype=np.int32)
        return [({"symbol": codes[t["sym_idx"]], "price": t["price"],
                  "volume": t["volume"], "et": t["ts"]}, t["ts"])
                for t in tape]

    def run_etb(app, tape, repeats):
        from siddhi_tpu import SiddhiManager
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(app)
        counted = [0]
        rt.add_batch_callback("Out", lambda b: counted.__setitem__(
            0, counted[0] + b.n))
        rt.start()
        h = rt.input_handler(STREAM)
        batches = et_tape_cols(rt, tape)
        for cols, ts in batches[:1]:
            h.send_batch(cols, ts)
        rt.flush()
        warm_m = counted[0]
        timed = batches[1:]
        seg = max(1, len(timed) // repeats)
        eps_runs, m1 = [], 0
        for r in range(repeats):
            part = timed[r * seg:(r + 1) * seg]
            if not part:
                break
            n_seg = sum(int(t[1].shape[0]) for t in part)
            tt = time.perf_counter()
            for cols, ts in part:
                h.send_batch(cols, ts)
            rt.flush()
            eps_runs.append(n_seg / (time.perf_counter() - tt))
            if r == 0:
                m1 = counted[0] - warm_m
        mgr.shutdown()
        return float(np.median(eps_runs)), m1, [round(e) for e in eps_runs]

    etb_tape = make_tape((1 << 17) * 3 + (1 << 16), 1 << 16)
    d_eps, d_m, d_runs = run_etb(
        PIPE + DEV["windows"] + C2B, etb_tape, 3)
    h_eps, h_m, _ = run_etb(HOST["windows"] + C2B,
                            etb_tape[:1 + (1 << 17) // (1 << 16)], 1)
    assert d_m == h_m and d_m > 0, (d_m, h_m)
    configs["7_external_time_batch"] = {
        "device_eps": round(d_eps), "device_eps_runs": d_runs,
        "host_eps": round(h_eps), "speedup": round(d_eps / h_eps, 2),
        "events": 1 << 17, "batch": 1 << 16, "matches": d_m,
        "note": "grouped externalTimeBatch(et, 64ms) tumbling buckets"}
    _mark("configs 4+5+6+7 done", t0)

    # non-Python calibration column (VERDICT r3 #9): no JVM exists in
    # this image, so an -O2 C++ run of the same matcher algorithms on
    # the same tape distribution stands in as a conservative UPPER bound
    # for single-JVM single-thread throughput on this hardware
    nat = _safe("native baseline", native_baseline, {})
    nat_of = {"1_filter": "filter", "2_window_agg": "window",
              "3_sequence": "sequence", "4_partitioned_1k": "partitioned"}
    for cfg, key in nat_of.items():
        if key in nat:
            configs[cfg]["native_cpp_eps"] = nat[key]["eps"]
            configs[cfg]["vs_native_cpp"] = round(
                configs[cfg]["device_eps"] / nat[key]["eps"], 2)
    _mark("native baseline done", t0)

    # roofline block (ROADMAP item 2 trajectory): per-config device
    # KERNEL eps vs the single-thread native C++ roofline, for the
    # WINNING plan family — the gap this PR's parallel-in-time families
    # exist to close, tracked per run
    roofline = {}
    for cfg in ("3_sequence", "4_partitioned_1k"):
        c = configs.get(cfg, {})
        ke, ne = c.get("kernel_eps"), c.get("native_cpp_eps")
        roofline[cfg] = {
            "plan_family": c.get("plan_family"),
            "kernel_eps": ke,
            "native_cpp_eps": ne,
            "vs_native_cpp": round(ke / ne, 4) if ke and ne else None,
        }
    roofline["3_sequence"]["kernel_eps_by_family"] = \
        configs["3_sequence"].get("kernel_eps_by_family")
    roofline["3_sequence"]["kernel_eps_static_by_family"] = \
        configs["3_sequence"].get("kernel_eps_static_by_family")

    # serving-plane transport column (ROADMAP item 3): a smoke-scale
    # net bench so every full run reports wire vs in-process ingest
    net_res = _safe("net transport smoke",
                    lambda: net_bench(smoke=True), {})
    _mark("net transport smoke done", t0)

    # durability-overhead column (ROADMAP item 5): TCP ingest eps per
    # sync policy on the config-3 schema — 'batch' must stay within 15%
    # of 'off' for durable serving to be the production default
    dur_res = _safe("durability overhead",
                    lambda: durability_bench(smoke=True), {})
    _mark("durability overhead done", t0)

    # transport-vs-host-vs-kernel breakdown per config: the
    # "transport-bound" calibration note as a MEASURED column.  For each
    # config: the kernel-only ceiling, the end-to-end in-process engine
    # rate (kernel + host dispatch), and the wire ceiling (loopback TCP
    # frames, measured on the config-3 schema at smoke scale — the
    # schema every numbered config shares).  `bound` names the limiter:
    # the wire when it is slower than the engine, else host dispatch
    # when >half the end-to-end time is outside the kernel, else the
    # kernel itself.
    wire_eps = (net_res.get("transport") or {}).get("tcp_eps")
    breakdown = {}
    for cfg, c in sorted(configs.items()):
        de, ke = c.get("device_eps"), c.get("kernel_eps")
        if not de:
            continue
        row = {"engine_eps": de}
        if ke:
            row["kernel_eps"] = ke
            row["host_share"] = round(max(0.0, 1.0 - de / ke), 3)
        if wire_eps:
            row["wire_tcp_eps"] = wire_eps
            row["wire_vs_engine"] = round(wire_eps / de, 2)
        if wire_eps and wire_eps < de:
            row["bound"] = "transport"
        elif ke and de / ke < 0.5:
            row["bound"] = "host"
        elif ke:
            row["bound"] = "kernel"
        breakdown[cfg] = row

    h = configs["4_partitioned_1k"]
    detail = {
        "harness": _safe("harness", harness_info, {}),
        "metric": "partitioned_pattern_throughput_1k_keys",
        "value": h["device_eps"],
        "unit": "events/sec",
        "vs_baseline": h["speedup"],
        "vs_production_claim": round(h["device_eps"] / PROD_CLAIM_EPS, 2),
        "p99_detect_ms": h.get("p99_detect_ms"),
        "calibration": {
            "host_eps": "single-threaded python interpreter (measured, "
                        "same tapes) — the matched-conditions baseline",
            "vs_production_claim": "device headline over the reference "
                                   "README's ~300k eps production anchor "
                                   "(engine-level comparison)",
            "native_cpp_eps": "-O2 C++ of the same matcher algorithm, no "
                              "engine around it (no event model, dispatch, "
                              "or output materialization) — an upper bound "
                              "for any single-thread CPU engine incl. a "
                              "JVM; the reference engine's own production "
                              "anchor sits ~1000x below this roofline",
            "transport": "device numbers come from the locally "
                         "attached chip named in harness.device_kind; "
                         "transport_breakdown says per config whether "
                         "the wire, the host or the kernel bounds it",
        },
        "roofline": roofline,
        "transport": net_res,
        "durability": dur_res,
        "transport_breakdown": breakdown,
        "configs": configs,
    }
    def _write_detail():
        with open("BENCH_DETAIL.json", "w") as f:
            json.dump(detail, f, indent=1)
    _safe("detail file", _write_detail)
    # ONE short stdout line: drivers keep only the stdout TAIL, so the
    # full per-config detail (which blew past their capture window —
    # BENCH "parsed": null) goes to BENCH_DETAIL.json and the parseable
    # summary stays bounded; _print_summary degrades the payload rather
    # than ever emitting an oversized/unparseable final line
    summary = {
        "metric": detail["metric"], "value": detail["value"],
        "unit": detail["unit"], "vs_baseline": detail["vs_baseline"],
        "vs_production_claim": detail["vs_production_claim"],
        "p99_detect_ms": detail["p99_detect_ms"],
        "harness": detail["harness"] or None,
        "roofline": {k: {kk: v.get(kk) for kk in
                         ("plan_family", "kernel_eps", "vs_native_cpp")}
                     for k, v in roofline.items()},
        # the serving-plane transport column: wire ingest eps by
        # transport (net_bench smoke scale) + the REST multiple
        "transport": ({**net_res.get("transport", {}),
                       "tcp_vs_rest": net_res.get("tcp_vs_rest"),
                       "identical": net_res.get("identical")}
                      if net_res else None),
        "configs": {k: {"eps": v["device_eps"], "speedup": v["speedup"],
                        **({"p99_ms": v["p99_detect_ms"]}
                           if v.get("p99_detect_ms") is not None else {}),
                        **({"bound": breakdown[k]["bound"]}
                           if breakdown.get(k, {}).get("bound") else {})}
                    for k, v in configs.items()},
        # durability column (sync policy + measured overhead vs 'off'):
        # LAST in the oversize drop_order, like placement, so the
        # exactly-once serving trade survives into the final line unless
        # nothing else is left to drop (a parseable line always wins)
        "durability": ({"policy": dur_res.get("policy"),
                        "overhead_pct": dur_res.get("overhead_pct"),
                        "tcp_eps": (dur_res.get("tcp_eps") or {}).get(
                            "batch")}
                       if dur_res else None),
        # device/interpreter query counts per config (placement plane,
        # docs/ANALYSIS.md): a future silent demotion shifts these
        # numbers in the bench trajectory — dropped only as the final
        # resort before the minimal-headline fallback
        "placement": {k: "{}d/{}i/{}dem".format(
                          v["placement"].get("device", 0),
                          v["placement"].get("interpreter", 0),
                          v["placement"].get("interp_demotions", 0))
                      for k, v in configs.items() if v.get("placement")},
        "detail": "BENCH_DETAIL.json",
    }
    _print_summary(summary)


if __name__ == "__main__":
    main()
