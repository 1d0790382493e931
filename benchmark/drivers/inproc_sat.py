"""Closed loop, in process: SiddhiManager -> InputHandler.send_batch ->
batch callback on the out stream, batches back to back for --seconds, then
flush().  See traffic/sat-2p18-inproc.json."""
import time

import numpy as np

from benchmark import compare, engine, harness, manifest


def run(job: "harness.Run") -> dict:
    from siddhi_tpu import SiddhiManager

    cell, cfg, tr = job.cell, job.cell["config"], job.cell["traffic"]
    batch, warm = int(tr["batch"]), int(tr["warm_batches"])
    tape = engine.tape_of(cell, job.seed)
    judge = manifest.module("reference", cfg["reference"]).Judge(
        cfg, tape, job.seed)

    mgr = SiddhiManager()
    try:
        rt = mgr.create_app_runtime(engine.app_text(cell))
        if job.trace_on:
            rt.enable_stats()       # stage seconds, for the traced run only
        if hasattr(judge, "bind"):
            judge.bind(rt)
        rt.add_batch_callback(cfg["out_stream"],
                              job.spans.wrap("callback", judge.on_batch))
        rt.start()
        handler = rt.input_handler(cfg["stream"])
        tape_mod = manifest.module("tapes", cfg["tape"])
        names = tape_mod.symbol_names(int(tape.params["keys"]))
        codes = np.array([rt.strings.encode(str(s)) for s in names], np.int32)

        # the tape, built ahead of the window
        if tape.ring:
            n_built = tape.ring
        else:
            n_built = warm + int(np.ceil(
                job.seconds * cfg["prebuild_events_per_s"] / batch))
        feeds = [tape_mod.feed_columns(tape.batch(i), codes)
                 for i in range(n_built)]
        built_late = 0

        def feed(i):
            nonlocal built_late
            if tape.ring:
                cols, ts = feeds[i % tape.ring]
                if i >= tape.ring:          # in place: one lap later
                    ts += tape.lap_ms
                    for c in tape_mod.EVENT_TIME_COLUMNS:
                        cols[c] += tape.lap_ms
                return cols, ts
            if i >= len(feeds):
                built_late += 1
                return tape_mod.feed_columns(tape.batch(i), codes)
            return feeds[i]

        for i in range(warm):
            handler.send_batch(*feed(i))
        rt.flush()
        placement = engine.check_placement(rt, cell,
                                           job.devices[0].platform)
        job.spans.reset()
        rows_warm = judge.rows
        before = engine.counters(rt) if job.trace_on else None
        c_setup = (job.compiles.n, job.compiles.secs)

        # ---- the timed window ------------------------------------------
        sent = warm
        marks = []                  # when each batch of the window began
        t0 = time.perf_counter()
        while True:
            elapsed = time.perf_counter() - t0
            if elapsed >= job.seconds:
                break
            marks.append(elapsed)
            job.trace.tick(elapsed)
            with job.spans.span("feed"):    # the generator's own time
                cols, ts = feed(sent)
            with job.spans.span("send_batch"):
                handler.send_batch(cols, ts)
            sent += 1
        with job.spans.span("flush"):
            rt.flush()              # every output delivered inside the window
        t1 = time.perf_counter()
        # ------------------------------------------------------------------
        job.trace.stop()
        compiles_in_window = job.compiles.n - c_setup[0]
        counted = engine.delta(engine.counters(rt), before) \
            if job.trace_on else None
        device = harness.device_block(job.devices)
        rows_in_window = judge.rows - rows_warm
    finally:
        mgr.shutdown()

    t_check = time.perf_counter()
    checks = judge.judge(sent)
    check_s = time.perf_counter() - t_check
    n_batches = sent - warm
    events = n_batches * batch
    window_s = t1 - t0
    periods = np.diff(np.array(marks + [window_s]))
    obs = job.close(device, {"events": events, "batches": n_batches,
                             "window_s": window_s, "batch": batch,
                             "rows_delivered": rows_in_window},
                    counted, compiles_in_window, samples={})
    return {"t_window0": t0, "window_s": window_s, "events": events,
            "end_to_end": {"events_per_s": events / window_s},
            "attempted": n_batches, "failed": 0,
            "correct": compare.verdict(checks), "checks": checks,
            "compiles_in_window": compiles_in_window,
            "compiles_in_setup": c_setup[0], "compile_s_in_setup": c_setup[1],
            "check_s": check_s, "device": device, "obs": obs,
            "counts": {"events": events, "batches": n_batches,
                       "rows_delivered": judge.rows, **judge.detail},
            "notes": {"placement": placement, "judge": judge.detail,
                      "tape_batches_built_in_window": built_late,
                      "batch_period_ms": _periods(periods)}}


def _periods(periods: np.ndarray) -> dict:
    """How evenly the window's batches followed one another (the last
    one's period holds the closing flush): a printed note, not a metric.
    `stalled_s` is the time batches took beyond twice the median period,
    what a host that stood still now and then cost the window."""
    if not len(periods):
        return {}
    med = float(np.median(periods))
    q = np.quantile(periods, [0.05, 0.95])
    thirds = [float(np.mean(t)) for t in np.array_split(periods, 3)
              if len(t)]
    return {"median": round(1e3 * med, 4), "p05": round(1e3 * float(q[0]), 4),
            "p95": round(1e3 * float(q[1]), 4),
            "max": round(1e3 * float(periods.max()), 4),
            "mean_by_third": [round(1e3 * t, 4) for t in thirds],
            "stalled_s": round(float(np.sum(np.maximum(
                periods - 2 * med, 0.0))), 4)}
