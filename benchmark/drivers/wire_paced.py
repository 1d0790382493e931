"""Open loop over the wire: a generator process of its own
(drivers/wire_gen.py) sends frames to the app's @source(type='tcp') on a
fixed schedule and receives the matches back from its @sink(type='tcp'):
the served path of docs/SERVING.md, from the client's side.  This process
holds the chip and the engine; during the window its main thread only
waits.  See traffic/paced-2p14-tcp.json."""
import json
import os
import select
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmark import compare, engine, harness, manifest


class _Gen:
    """The generator process and the JSON lines to and from it."""

    def __init__(self, spec: dict):
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": manifest.ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", "")}
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmark.drivers.wire_gen"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            cwd=manifest.ROOT, text=True, bufsize=1)
        self.say(spec)

    def say(self, obj: dict) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def hear(self, key: str, timeout: float, idle=None) -> dict:
        """The next line, which has to carry `key`; `idle()` is called
        about every 50 ms while waiting."""
        deadline = time.monotonic() + timeout
        while True:
            ready, _, _ = select.select([self.proc.stdout], [], [], 0.05)
            if ready:
                line = self.proc.stdout.readline()
                if not line:
                    raise RuntimeError(
                        f"generator exited ({self.proc.poll()}) before "
                        f"{key!r}")
                msg = json.loads(line)
                if key not in msg:
                    raise RuntimeError(f"generator said {msg}, expected "
                                       f"{key!r}")
                return msg
            if idle is not None:
                idle()
            if time.monotonic() > deadline:
                raise RuntimeError(f"generator silent for {timeout} s "
                                   f"waiting for {key!r}")

    def close(self) -> None:
        for pipe in (self.proc.stdin, self.proc.stdout):
            try:
                pipe.close()
            except OSError:
                pass
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


def run(job: "harness.Run") -> dict:
    from siddhi_tpu import SiddhiManager

    cell, cfg, tr = job.cell, job.cell["config"], job.cell["traffic"]
    tape = engine.tape_of(cell, job.seed)
    judge = manifest.module("reference", cfg["reference"]).Judge(
        cfg, tape, job.seed)
    work = tempfile.mkdtemp(prefix="bench_wire_")
    out_file = os.path.join(work, "received.npz")
    wire_cell = {k: cell[k] for k in ("name", "chips", "config", "traffic")}
    gen = _Gen({"cell": wire_cell, "seed": job.seed, "seconds": job.seconds,
                "out": out_file})
    emitted = [0]
    mgr = SiddhiManager()
    try:
        port = gen.hear("receiver_port", 120)["receiver_port"]
        rt = mgr.create_app_runtime(engine.app_text(
            cell, source=tr["source"] + "\n",
            sink=tr["sink"].replace("{port}", str(port)) + "\n"))
        if job.trace_on:
            rt.enable_stats()

        def count(b):
            emitted[0] += b.n
        rt.add_batch_callback(cfg["out_stream"],
                              job.spans.wrap("callback", count))
        rt.start()
        gen.say({"source_port": rt.sources[0].port})
        gen.hear("warm_sent", 900)
        rt.flush()
        placement = engine.check_placement(rt, cell,
                                           job.devices[0].platform)
        job.spans.reset()
        before = engine.counters(rt) if job.trace_on else None
        c_setup = (job.compiles.n, job.compiles.secs)
        gen.say({"rows_emitted": emitted[0]})

        # ---- the timed window: the generator's --------------------------
        t_go = time.perf_counter()
        start = gen.hear(
            "window_started", job.seconds + tr["drain_timeout_s"] + 60,
            idle=lambda: job.trace.tick(time.perf_counter() - t_go)
        )["window_started"]
        gen.hear("sent_all", tr["drain_timeout_s"] + 60)
        rt.flush()
        gen.say({"rows_emitted": emitted[0]})
        done = gen.hear("done", tr["drain_timeout_s"] + 120)
        # ------------------------------------------------------------------
        job.trace.stop()
        t0 = time.perf_counter() - (time.monotonic() - start)
        compiles_in_window = job.compiles.n - c_setup[0]
        counted = engine.delta(engine.counters(rt), before) \
            if job.trace_on else None
        device = harness.device_block(job.devices)
    finally:
        gen.close()
        mgr.shutdown()

    t_check = time.perf_counter()
    with np.load(out_file) as got:
        judge.add_rows(got["ts"], *(got[c] for c in judge.columns))
        lat, late = got["lat"], got["late"]
    os.remove(out_file)
    os.rmdir(work)
    warm = int(tr["warm_batches"])
    checks = judge.judge(warm + done["frames"])
    undelivered = emitted[0] - judge.rows
    checks.append(compare.check("matches_not_delivered_to_the_client",
                                max(0, undelivered)))
    check_s = time.perf_counter() - t_check
    obs = job.close(device, {"events": done["events"],
                             "batches": done["frames"],
                             "window_s": done["window_s"]},
                    counted, compiles_in_window,
                    samples={"gen_late_s": late, "detect_s": lat})
    return {"t_window0": t0, "window_s": done["window_s"],
            "events": done["events"],
            "end_to_end": {"detect_p95_ms": done["detect_p95_ms"],
                           "detect_p50_ms": done["detect_p50_ms"]},
            "attempted": emitted[0], "failed": max(0, undelivered),
            "correct": compare.verdict(checks), "checks": checks,
            "compiles_in_window": compiles_in_window,
            "compiles_in_setup": c_setup[0], "compile_s_in_setup": c_setup[1],
            "check_s": check_s, "device": device, "obs": obs,
            "counts": {"events": done["events"], "batches": done["frames"],
                       "rows_delivered": judge.rows, **judge.detail},
            "notes": {"placement": placement, "judge": judge.detail,
                      "generator": done}}
