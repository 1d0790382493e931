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
        before = engine.counters(rt) if job.trace_on else None
        c_setup = (job.compiles.n, job.compiles.secs)

        # ---- the timed window ------------------------------------------
        sent = warm
        t0 = time.perf_counter()
        while True:
            elapsed = time.perf_counter() - t0
            if elapsed >= job.seconds:
                break
            job.trace.tick(elapsed)
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
    finally:
        mgr.shutdown()

    t_check = time.perf_counter()
    checks = judge.judge(sent)
    check_s = time.perf_counter() - t_check
    n_batches = sent - warm
    events = n_batches * batch
    window_s = t1 - t0
    obs = job.close(device, {"events": events, "batches": n_batches,
                             "window_s": window_s},
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
                      "tape_batches_built_in_window": built_late}}
