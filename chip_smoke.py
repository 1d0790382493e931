#!/usr/bin/env python
"""Chip smoke: the quickest proof that the engine's main path still starts
and answers correctly on a locally attached TPU.

    python chip_smoke.py              # default: what the driver runs
    python chip_smoke.py --all        # + one pass per remaining plan kind
    python chip_smoke.py --chips 4    # C4 sharded over a four-chip mesh
    python chip_smoke.py --dry-run-cpu [--all] [--chips 4]   # tiny, CPU

The main path is the north-star workload of BASELINE.json: the partitioned
`every e1 -> e2 -> e3 within 10 sec` pattern (bench.C4) over 1000 keys,
driven through SiddhiManager.create_app_runtime -> add_batch_callback ->
start -> InputHandler.send_batch -> flush in 2^18-event micro-batches,
beside the filter (bench.C1) and length-window average (bench.C2) spine a
pattern app sits on, then the same C4 app once more through the TCP
serving plane (docs/SERVING.md).

Everything runs in ONE process (a chip belongs to one process at a time).
The run refuses to start unless `jax.devices()[0].platform == "tpu"`;
`--dry-run-cpu` is the only other way in and is never inferred.  Every
phase proves placement (EXPLAIN path/kind/family, no demotion, no fault-
ladder activity, state leaves on the expected devices), proves answers
against the host interpreter, and fails on any backend compilation after
its warm-up batches.  Exceptions propagate: a failed phase is a non-zero
exit and no result line.

The rates printed per phase are smoke output — a sanity reading that the
timed region ended in a flush — not benchmark metrics.

The last stdout line is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
The full per-phase report goes to <out>/chip_smoke.json.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# The device computes DOUBLE in f32 (core/schema.py dtype policy) and TPU
# f64 is emulated.  Tapes use quarter-step prices (bench.q4), so captured
# and compared values are exact in f32 and pattern/filter/join outputs are
# held to EQUALITY, as are integer outputs (counts, dictionary codes).
# Window SUMS are exact on these tapes too: a windowed sum errs by the
# rounding of the window's own contents (core/window_device.py `_range_sum`),
# and a window of quarter steps sums to under 2^24 of them, so `sum` columns
# are held to equality.  An `avg` is that sum through ONE f32 division,
# which the TPU does not round correctly: up to 2.26 ulps from the exact
# quotient over every (sum, count) a length(1000) window of these prices
# can hold (my chip run, PR 44), so 3 ulps of the largest mean, 130, one
# of which is 2^-16.  The aggregation rings fold non-quarter-step p*v
# products in emulated f64.
WINDOW_AVG_ATOL = 3 * 2.0 ** -16
AGG_F64_RTOL = 1e-9

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TS0 = 1_700_000_000_000          # bench.make_tape's first timestamp


FLOATS = ("DOUBLE", "FLOAT")


class SmokeFailure(AssertionError):
    """A smoke check that did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileLog:
    """Backend compilations seen by the public jax.monitoring duration
    listener (a persistent-cache hit still fires the event: it counts
    compile REQUESTS, which is what a steady-state window must not have)."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1
            self.secs += duration

    def mark(self):
        return (self.n, self.secs)


def truncate(sends, n_events):
    """The first `n_events` events of a send list (last batch sliced)."""
    out, left = [], n_events
    for stream, cols, ts in sends:
        if left <= 0:
            break
        k = min(left, len(ts))
        out.append((stream, {c: v[:k] for c, v in cols.items()}, ts[:k]))
        left -= k
    return out


def rows_of(batches):
    """Collected output batches -> one (n, 1 + n_cols) f64 table, rows
    sorted lexicographically (cross-key delivery order is not defined for
    partitioned queries).  Every output column here is numeric: doubles,
    ints, longs and string dictionary codes, all exact in f64."""
    import numpy as np
    if not batches:
        return np.zeros((0, 0))
    tab = np.concatenate(batches, axis=0)
    return tab[np.lexsort(tab.T[::-1])]


class Smoke:
    def __init__(self, args, platform):
        self.args = args
        self.platform = platform
        self.compiles = CompileLog()
        self.phases = {}
        self._oracles = {}      # (host app, sends, prefix) -> rows

    # -- one app, one run ----------------------------------------------------

    def drive(self, app, sends_fn, outs, warm, tcp=False):
        """Build `app`, feed `sends_fn(rt)` (warm-up batches first, then a
        timed region that ends in flush), return (rows per out stream,
        run stats, rt, mgr).  The caller inspects `rt` and shuts down."""
        import numpy as np
        from siddhi_tpu import SiddhiManager

        c0 = self.compiles.mark()       # the build's compiles are set-up
        mgr = SiddhiManager()
        try:
            rt = mgr.create_app_runtime(app)
            got = {s: [] for s in outs}

            def collect(stream):
                def cb(b):
                    if b.n:
                        got[stream].append(np.column_stack(
                            [b.timestamps.astype(np.float64)]
                            + [np.asarray(b.columns[a.name], np.float64)
                               for a in b.schema.attributes]))
                return cb
            for s in outs:
                rt.add_batch_callback(s, collect(s))
            rt.start()
            sends = sends_fn(rt)
            if tcp:
                from siddhi_tpu.net import TcpFrameClient
                ports = {s.stream_id: s.port for s in rt.sources}
                clients = {}

                def send(stream, cols, ts):
                    cli = clients.get(stream)
                    if cli is None:
                        cli = clients[stream] = TcpFrameClient(
                            "127.0.0.1", ports[stream], stream,
                            TcpFrameClient.cols_of_schema(rt.schemas[stream]))
                    cli.send_batch(cols, ts)

                def barrier():
                    for cli in clients.values():
                        cli.barrier(timeout=300)
                    rt.flush()
            else:
                handlers = {}

                def send(stream, cols, ts):
                    h = handlers.get(stream)
                    if h is None:
                        h = handlers[stream] = rt.input_handler(stream)
                    h.send_batch(cols, ts)
                barrier = rt.flush

            for stream, cols, ts in sends[:warm]:
                send(stream, cols, ts)
            barrier()
            c1 = self.compiles.mark()
            t0 = time.perf_counter()
            for stream, cols, ts in sends[warm:]:
                send(stream, cols, ts)
            barrier()                       # every output delivered in-window
            wall = time.perf_counter() - t0
            c2 = self.compiles.mark()
            if tcp:
                for cli in clients.values():
                    cli.close()
            n_timed = sum(len(ts) for _s, _c, ts in sends[warm:])
            stats = {
                "events": n_timed, "wall_s": wall,
                "events_per_s": n_timed / wall,
                "warmup_events": sum(len(ts) for _s, _c, ts in sends[:warm]),
                "setup_compilations": c1[0] - c0[0],
                "setup_compile_s": c1[1] - c0[1],
                "steady_compilations": c2[0] - c1[0],
            }
            return {s: rows_of(b) for s, b in got.items()}, stats, rt, mgr
        except BaseException:
            mgr.shutdown()      # no engine thread may outlive a failed phase
            raise

    # -- placement -----------------------------------------------------------

    def placement(self, rt, kind, family, sharded):
        """Prove where the app ran; returns the facts for the report."""
        ex = rt.explain()
        check(ex["queries"], "no query in EXPLAIN")
        for q, ent in ex["queries"].items():
            check(ent["path"] == "device" and ent["kind"] == kind,
                  f"query {q!r} placed {ent['path']}/{ent['kind']}, "
                  f"expected device/{kind}: {ent}")
            if family is not None:
                check(ent.get("family") == family,
                      f"query {q!r} runs family {ent.get('family')!r}, "
                      f"expected {family!r}: {ent.get('rejected')}")
        check(not ex["demotions"], f"demotions: {ex['demotions']}")
        ladders = {n: lad.metrics() for n, lad in rt._ladders.items()}
        for name, m in ladders.items():
            check(not (m["dispatch_failures"] or m["dispatch_halvings"]
                       or m["quarantined"]),
                  f"degradation ladder fired on {name!r}: {m} "
                  f"({rt._ladders[name].last_error})")
        st = rt.statistics()
        check(not st.get("degraded_plans"),
              f"plans quarantined: {st.get('degraded_detail')}")
        check(len(rt.error_store) == 0,
              f"ErrorStore holds {len(rt.error_store)} captures")
        leaves = {}
        for plan in rt._plans:
            state = getattr(plan, "state", None)
            if not isinstance(state, dict):
                continue
            for key, leaf in state.items():
                devs = leaf.devices()
                check(all(d.platform == self.platform for d in devs),
                      f"{plan.name}.state[{key!r}] lives on {devs}, "
                      f"expected {self.platform}")
                leaves[f"{plan.name}.{key}"] = len(devs)
            mesh = getattr(plan, "mesh", "absent")
            if sharded:
                check(mesh is not None, f"{plan.name}: no mesh")
                n = len(state["occ"].sharding.device_set)
                check(n == sharded, f"{plan.name}.state['occ'] sharded "
                      f"over {n} devices, expected {sharded}")
            elif mesh != "absent":
                check(mesh is None, f"{plan.name}: unexpected mesh {mesh}")
        return {"queries": {q: {k: e.get(k) for k in
                                ("path", "kind", "family")}
                            for q, e in ex["queries"].items()},
                "ladders": ladders, "state_leaves": leaves}

    # -- one phase = device run + oracle + checks ----------------------------

    def phase(self, name, dev_app, host_app, sends_fn, outs, warm, kind,
              family=None, oracle_events=None, dt_ms=1, atol=0.0, tcp=False,
              sharded=0, expect_rows=None):
        import numpy as np
        t_phase = time.perf_counter()
        rows, stats, rt, mgr = self.drive(dev_app, sends_fn, outs, warm,
                                          tcp=tcp)
        try:
            place = self.placement(rt, kind, family, sharded)
        finally:
            mgr.shutdown()
        check(stats["steady_compilations"] == 0,
              f"{name}: {stats['steady_compilations']} backend "
              f"compilation(s) after the warm-up batches")
        n_rows = sum(len(r) for r in rows.values())
        check(n_rows > 0, f"{name}: the device produced no output")

        if expect_rows is not None:     # equality with an earlier run
            want, cutoff = expect_rows, None
        else:
            def oracle_sends(rt2):
                s = sends_fn(rt2)
                return s if oracle_events is None \
                    else truncate(s, oracle_events)
            # one interpreter run serves every device run of the same
            # query on the same tape (the C3 families)
            key = (host_app, sends_fn, oracle_events)
            if key not in self._oracles:
                want, _st, _rt, mgr2 = self.drive(host_app, oracle_sends,
                                                  outs, warm=0)
                mgr2.shutdown()
                self._oracles[key] = want
            want = self._oracles[key]
            cutoff = None if oracle_events is None \
                else TS0 + oracle_events * dt_ms
        compared, max_err = 0, 0.0
        for s in outs:
            got = rows[s]
            if cutoff is not None and len(got):
                got = got[got[:, 0] < cutoff]
            check(got.shape == want[s].shape,
                  f"{name}/{s}: device {got.shape} vs reference "
                  f"{want[s].shape} rows")
            if not len(got):
                continue                # an out stream nothing reached
            # timestamps and integer-typed columns are always exact;
            # `atol` only loosens DOUBLE/FLOAT columns
            exact = [0] + [1 + i for i, a in enumerate(
                rt.schemas[s].attributes) if a.type.name not in FLOATS]
            check(np.array_equal(got[:, exact], want[s][:, exact]),
                  f"{name}/{s}: output differs from the reference in its "
                  f"timestamps or integer columns")
            err = float(np.abs(got - want[s]).max())
            check(err <= atol, f"{name}/{s}: differs from the reference: "
                  f"max abs err {err} > atol {atol}")
            max_err = max(max_err, err)
            compared += len(got)
        check(compared > 0, f"{name}: nothing to compare on the checked "
              f"prefix")
        rep = {**stats, "rows": n_rows, "rows_compared": compared,
               "reference": "in-process run" if expect_rows is not None
               else "host interpreter",
               "atol": atol, "max_abs_err": max_err, **place,
               "phase_s": time.perf_counter() - t_phase}
        self.phases[name] = rep
        print(f"[{name}] {rep['events']} events in {rep['wall_s']:.3f} s "
              f"= {rep['events_per_s']:.0f} ev/s (smoke output); "
              f"set-up {rep['setup_compilations']} compilations "
              f"{rep['setup_compile_s']:.1f} s, steady 0; "
              f"{n_rows} rows, {compared} compared "
              + (f"~ (max abs err {max_err:.3g} <= {atol:g}) " if atol
                 else "== ")
              + f"{rep['reference']}; "
              f"{place['queries']}", flush=True)
        return rows


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def stock_sends(bench, tape, keys, et=False):
    """bench tape -> send list with the symbol pre-encoded to this
    runtime's dictionary codes (bench._columnar's feed form)."""
    def fn(rt):
        out = []
        for cols, ts in bench._columnar(rt, bench.STREAM, tape, keys):
            if et:
                cols = {**cols, "et": ts}
            out.append((bench.STREAM, cols, ts))
        return out
    return fn


def stock_str_sends(bench, tape, keys):
    """The same tape with the symbol as strings (what a wire client
    sends: the frame codec carries its own dictionary)."""
    def fn(_rt):
        return [(bench.STREAM, cols, ts)
                for cols, ts in bench._tape_str_batches(tape, keys)]
    return fn


HOST_HEAD = ("@app:devicePatterns('never')\n@app:deviceFilters('never')\n"
             "@app:deviceWindows('never')\n")


# C4 warm-up: the lane grid's flat capacity F is a sticky 64-granule bucket
# (core/lane_grid.py LaneGrid.pack) that grows whenever the
# busiest key's event count crosses a multiple of 64.  At 2^18 events over
# 1000 keys that count sits at 313..332 per flush — astride 320 — so F
# settles (384 -> 448, one recompile) at the first flush that crosses,
# usually the third.  Four warm-up batches put that growth in set-up.
C4_WARM, C4_TIMED = 4, 8


def c4_tape(bench, sz, seed):
    return bench.make_tape((C4_WARM + C4_TIMED) * sz["pattern_batch"],
                           sz["pattern_batch"], keys=sz["keys"],
                           seed=seed + 2, dt_ms=sz["pattern_dt_ms"])


def main_path(sm, bench, sz):
    seed, mesh = sm.args.seed, "@app:deviceMesh('never')\n"
    f_tape = bench.make_tape(4 * sz["filter_batch"], sz["filter_batch"],
                             seed=seed)
    sm.phase("c1_filter", mesh + bench.PIPE + bench.DEV["filters"]
             + bench.C1, HOST_HEAD + bench.C1,
             stock_sends(bench, f_tape, 8), ("Out",), warm=1,
             kind="filter", oracle_events=sz["oracle"])
    w_tape = bench.make_tape(4 * sz["window_batch"], sz["window_batch"],
                             seed=seed + 1)
    sm.phase("c2_window_avg", mesh + bench.PIPE + bench.DEV["windows"]
             + bench.C2, HOST_HEAD + bench.C2,
             stock_sends(bench, w_tape, 8), ("Out",), warm=1,
             kind="window", oracle_events=sz["oracle"],
             atol=WINDOW_AVG_ATOL)              # avg over length(1000)
    head = "@app:partitionCapacity(1000)\n@app:deviceSlots(32)\n"
    p_tape = c4_tape(bench, sz, seed)
    rows = sm.phase("c4_partitioned", mesh + head + bench.C4,
                    HOST_HEAD + bench.C4,
                    stock_sends(bench, p_tape, sz["keys"]), ("Out",),
                    warm=C4_WARM, kind="pattern", family="scan",
                    oracle_events=sz["oracle"], dt_ms=sz["pattern_dt_ms"])
    sm.phase("c4_partitioned_tcp",
             mesh + head + "@source(type='tcp', port='0')\n" + bench.C4,
             None, stock_str_sends(bench, p_tape, sz["keys"]), ("Out",),
             warm=C4_WARM, kind="pattern", family="scan", tcp=True,
             expect_rows=rows)


def four_chips(sm, bench, sz):
    head = ("@app:deviceMesh('always')\n@app:partitionCapacity(1000)\n"
            "@app:deviceSlots(32)\n")
    p_tape = c4_tape(bench, sz, sm.args.seed)
    sm.phase("c4_partitioned_4chips", head + bench.C4,
             HOST_HEAD + bench.C4, stock_sends(bench, p_tape, sz["keys"]),
             ("Out",), warm=C4_WARM, kind="pattern", family="scan",
             oracle_events=sz["oracle"], dt_ms=sz["pattern_dt_ms"],
             sharded=4)


def all_kinds(sm, bench, sz):
    """One pass per remaining device plan kind, so the TPU compiler sees
    each at least once.  Sizes follow bench.py's rows for each config."""
    import numpy as np
    seed, mesh = sm.args.seed, "@app:deviceMesh('never')\n"
    prefer = "@app:devicePatterns('prefer')\n"
    # two warm-up flushes: the replay tail (and with it the chunk
    # family's explicit-seq block variant) first appears at flush 2
    s_tape = bench.make_tape(4 * sz["seq_batch"], sz["seq_batch"],
                             seed=seed + 3)
    s_sends = stock_sends(bench, s_tape, 8)
    for fam, query in (("seq", bench.C3), ("chunk", bench.C3),
                       ("scan", bench.C3), ("dfa", bench.C3S)):
        sm.phase(f"c3_{fam}", mesh + prefer
                 + f"@app:patternFamily('{fam}')\n" + query,
                 HOST_HEAD + query, s_sends, ("Out",), warm=2,
                 kind="pattern", family=fam, oracle_events=sz["oracle"])

    c5 = bench.c5_app(sz["queries"])
    c5_outs = tuple(f"Out{i}" for i in range(min(16, sz["queries"])))
    q_tape = bench.make_tape(4 * sz["mq_batch"], sz["mq_batch"],
                             seed=seed + 4, dt_ms=50)
    sm.phase("c5_fused_queries", mesh + c5, HOST_HEAD + c5,
             stock_sends(bench, q_tape, 8), c5_outs, warm=2,
             kind="multi_query")

    def join_sends(rt):
        rng = np.random.default_rng(seed + 5)
        codes = np.array([rt.strings.encode(f"K{i}") for i in range(1000)],
                         dtype=np.int32)
        half, out, done = sz["join_batch"] // 2, [], 0
        for _ in range(6):
            for stream in ("L", "R"):
                out.append((stream, {
                    "symbol": codes[rng.integers(0, 1000, half)],
                    "price": bench.q4(rng.uniform(90, 130, half)),
                    "volume": rng.integers(1, 9, half).astype(np.int32)},
                    TS0 + np.arange(done, done + half, dtype=np.int64)))
                done += half
        return out
    sm.phase("c6_join", mesh + bench.PIPE + bench.JOIN_APP,
             "@app:deviceJoins('never')\n" + bench.JOIN_APP, join_sends,
             ("Out",), warm=4, kind="join")

    e_tape = bench.make_tape(4 * sz["etb_batch"], sz["etb_batch"],
                             seed=seed + 6)
    sm.phase("c7_external_time_batch", mesh + bench.PIPE
             + bench.DEV["windows"] + bench.C2B, HOST_HEAD + bench.C2B,
             stock_sends(bench, e_tape, 8, et=True), ("Out",), warm=1,
             kind="window")                     # sums: exact
    aggregation(sm, bench, sz)


def aggregation(sm, bench, sz):
    """`define aggregation` with device-resident rings
    (core/agg_device.py — the donated-buffer step runs only off-CPU)."""
    import numpy as np
    from siddhi_tpu import SiddhiManager
    t_phase = time.perf_counter()
    tape = bench._matrix_tape(6, sz["agg_batch"], 64, seed=sm.args.seed + 7)

    def run(head):
        mgr = SiddhiManager()
        try:
            rt = mgr.create_app_runtime(bench._matrix_app(
                "@app:deviceMesh('never')\n" + head))
            rt.start()
            h = rt.input_handler("Trades")
            c0 = sm.compiles.mark()
            for cols, ts in tape[:2]:
                h.send_batch(cols, ts)
            rt.flush()
            c1 = sm.compiles.mark()
            t0 = time.perf_counter()
            for cols, ts in tape[2:]:
                h.send_batch(cols, ts)
            rt.flush()
            rows = {per: rt.query(bench._matrix_query(per))
                    for per in ("sec", "min", "hour")}
            wall = time.perf_counter() - t0
            c2 = sm.compiles.mark()
            path = rt.explain()["aggregations"]["Roll"]["path"]
            leaves = {}
            plan = rt.aggregations["Roll"].device_plan
            if plan is not None:
                for d, ring in plan.rings.items():
                    devs = ring.bases.devices()
                    check(all(x.platform == sm.platform for x in devs),
                          f"Roll ring {d.name} lives on {devs}")
                    leaves[d.name] = len(devs)
            check(len(rt.error_store) == 0, "ErrorStore holds captures")
            return rows, path, leaves, (c0, c1, c2), wall
        finally:
            mgr.shutdown()

    got, path, leaves, (c0, c1, c2), wall = run("")
    want, _p, _l, _c, _w = run("@app:deviceAggregations('off')\n")
    check(path == "device-resident", f"aggregation Roll placed {path!r}")
    check(c2[0] == c1[0], f"aggregation: {c2[0] - c1[0]} backend "
          f"compilation(s) after the warm-up batches")
    compared = 0
    for per in got:
        # store-query rows are (bucket ts, (sym, turnover, mean, lo, hi, n))
        a = sorted((ts, *row) for ts, row in got[per])
        b = sorted((ts, *row) for ts, row in want[per])
        check(len(a) == len(b) and len(a) > 0,
              f"aggregation per {per}: {len(a)} rows vs host {len(b)}")
        for ra, rb in zip(a, b):
            check(ra[:2] == rb[:2] and np.allclose(
                np.array(ra[2:], float), np.array(rb[2:], float),
                rtol=AGG_F64_RTOL, atol=0.0),
                f"aggregation per {per}: {ra} vs host {rb}")
        compared += len(a)
    n = sum(len(ts) for _c, ts in tape[2:])
    sm.phases["c8_aggregation"] = {
        "events": n, "wall_s": wall, "events_per_s": n / wall,
        "setup_compilations": c1[0] - c0[0],
        "setup_compile_s": c1[1] - c0[1], "steady_compilations": 0,
        "rows_compared": compared, "reference": "host reduce path",
        "rtol": AGG_F64_RTOL, "path": path, "state_leaves": leaves,
        "phase_s": time.perf_counter() - t_phase}
    print(f"[c8_aggregation] {n} events + 3 store queries in {wall:.3f} s "
          f"(smoke output); {compared} rows ~ host reduce path; {path}; "
          f"rings {leaves}", flush=True)


REAL = {"filter_batch": 1 << 18, "window_batch": 1 << 17,
        "pattern_batch": 1 << 18, "pattern_dt_ms": 1, "keys": 1000,
        "oracle": 1 << 16,
        "seq_batch": 1 << 17, "queries": 1000, "mq_batch": 1 << 10,
        "join_batch": 4096, "etb_batch": 1 << 16, "agg_batch": 1 << 14}
TINY = {"filter_batch": 1 << 11, "window_batch": 1 << 11,
        "pattern_batch": 1 << 12, "pattern_dt_ms": 64, "keys": 64,
        "oracle": 1 << 11,
        "seq_batch": 1 << 11, "queries": 32, "mq_batch": 1 << 8,
        "join_batch": 2048, "etb_batch": 1 << 10, "agg_batch": 1 << 9}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--all", action="store_true",
                    help="add one pass per remaining device plan kind")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the C4 phase sharded over four chips")
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="tiny sizes on the CPU backend (tests, and "
                         "before each chip call); never inferred")
    ap.add_argument("--out", help="report directory (default "
                    "chiprun_out/chip_smoke, or .../chip_smoke_dry_run)")
    args = ap.parse_args(argv)
    if args.out is None:            # a dry run never overwrites a chip report
        args.out = os.path.join(ROOT, "chiprun_out", "chip_smoke_dry_run"
                                if args.dry_run_cpu else "chip_smoke")

    if args.dry_run_cpu and args.chips > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.chips}").strip()
    import jax
    if args.dry_run_cpu:
        jax.config.update("jax_platforms", "cpu")
        # CPU executables must never land in the in-checkout cache the
        # chip machine would then load (siddhi_tpu/__init__.py)
        jax.config.update("jax_enable_compilation_cache", False)
    devs = jax.devices()
    platform = devs[0].platform
    want = "cpu" if args.dry_run_cpu else "tpu"
    if platform != want:
        print(f"chip_smoke: jax.devices()[0].platform is {platform!r} "
              f"({devs[0].device_kind!r} x{len(devs)}), need {want!r}; "
              f"nothing was run (--dry-run-cpu is the explicit CPU lane)",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} not run: {len(devs)} "
              f"devices visible", file=sys.stderr)
        return 2
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    print(f"platform: {platform}  device_kind: {device['kind']}  "
          f"devices: {len(devs)}  jax: {jax.__version__}  "
          f"libtpu: {libtpu_version}", flush=True)

    os.makedirs(args.out, exist_ok=True)

    import bench
    import siddhi_tpu  # noqa: F401  (fails here outside a checkout)
    from siddhi_tpu.core.telemetry import XLA_CACHE

    t0 = time.perf_counter()
    sm = Smoke(args, platform)
    sz = TINY if args.dry_run_cpu else REAL
    if args.chips == 4:
        four_chips(sm, bench, sz)
    else:
        main_path(sm, bench, sz)
        if args.all:
            all_kinds(sm, bench, sz)

    report = {
        "ok": True, "device": device, "jax": jax.__version__,
        "libtpu": libtpu_version, "seed": args.seed,
        "mode": ("dry-run-cpu" if args.dry_run_cpu else "chip")
                + (" --all" if args.all else "")
                + (f" --chips {args.chips}" if args.chips > 1 else ""),
        "total_s": time.perf_counter() - t0,
        "compile": {"backend_compilations": sm.compiles.n,
                    "backend_compile_s": sm.compiles.secs,
                    "cache_dir": jax.config.jax_compilation_cache_dir,
                    "persistent_cache": dict(XLA_CACHE)},
        "phases": sm.phases,
    }
    with open(os.path.join(args.out, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    print(f"compile: {sm.compiles.n} backend compilations, "
          f"{sm.compiles.secs:.1f} s; persistent cache "
          f"{report['compile']['cache_dir']}: {XLA_CACHE['hits']} hits, "
          f"{XLA_CACHE['misses']} misses; total {report['total_s']:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
