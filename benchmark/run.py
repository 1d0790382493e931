"""Run one cell of BENCHMARK.json once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process per run: attach to the chip, set up (parse and plan the app,
build the tape, load or compile the XLA programs, warm up every shape), then
measure for --seconds, check what the timed path delivered against the plain
reference, print one JSON line last on standard output, exit.  A run that
finds no TPU, or fewer chips than the cell asks for, exits non-zero and
prints no result: it never falls back to the CPU.  `--rehearse-cpu` is the
CPU lane for tests and for rehearsing a chip call at a tiny size; it is
never inferred, and it prints counts and the verdict but no metric.
"""
import argparse
import os
import sys
import time

T_PROCESS = time.perf_counter()


def _attach(chips: int, rehearse: bool):
    """Bring up the device runtime; returns (devices, attach seconds).  The
    seconds before `jax.devices()` returns are the platform's (PERF.md
    section 2): they are printed as attach_s and are not a metric."""
    if rehearse:
        flags = os.environ.get("XLA_FLAGS", "")
        if chips > 1 and "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips}"
            ).strip()
    import jax
    from benchmark.manifest import ROOT
    if rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        # one fixed directory inside the checkout (the path is part of the
        # cache's key), and every program kept, however quick its compile
        cache = os.path.join(ROOT, ".jax_cache")
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    attach_s = time.perf_counter() - T_PROCESS
    want = "cpu" if rehearse else "tpu"
    if devices[0].platform != want or len(devices) < chips:
        print(f"benchmark.run: found {len(devices)} x "
              f"{devices[0].device_kind!r} ({devices[0].platform}); the cell "
              f"needs {chips} x {want}. Nothing was run.", file=sys.stderr)
        return None, attach_s
    return devices, attach_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU backend; prints no metric")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="override a number of the traffic file, or with "
                    "tape.K one of the configuration's tape_params, as in "
                    "--set rate_events_per_s=200000 or --set "
                    "tape.price_step=0.01: for a sweep or an experiment; "
                    "the driver's check never passes it")
    ap.add_argument("--keep-trace", default="", metavar="DIR",
                    help="copy the traced run's raw .xplane.pb into DIR "
                    "(inside the checkout), for a look by hand; the "
                    "driver's check never passes it")
    args = ap.parse_args(argv)

    from benchmark import harness, manifest
    mf = manifest.Manifest()
    cell = mf.cell(args.workload)
    if args.rehearse_cpu:
        cell["config"] = manifest.rehearsed(cell["config"])
        cell["traffic"] = manifest.rehearsed(cell["traffic"])

    for item in args.set:
        key, _, value = item.partition("=")
        where = cell["traffic"]
        if key.startswith("tape."):
            where, key = cell["config"]["tape_params"], key[5:]
        if key not in where:
            raise SystemExit(f"--set {item}: no such key to override")
        where[key] = type(where[key])(float(value))

    devices, attach_s = _attach(cell["chips"], args.rehearse_cpu)
    if devices is None:
        return 2
    t_attached = time.perf_counter()
    print(f"attach_s {attach_s:.3f} (platform's; not in setup_s)  "
          f"devices {len(devices)} x {devices[0].device_kind!r}", flush=True)

    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace_on=bool(args.trace),
                      devices=devices[:cell["chips"]],
                      keep_trace=args.keep_trace)
    driver = manifest.module("drivers", cell["traffic"]["driver"])
    out = driver.run(run)

    setup_s = out["t_window0"] - t_attached
    print(f"setup_s {setup_s:.3f}  window_s {out['window_s']:.3f}  "
          f"events {out['events']}  compiles_in_window "
          f"{out['compiles_in_window']}  backend compilations in set-up "
          f"{out['compiles_in_setup']} ({out['compile_s_in_setup']:.1f} s)  "
          f"check_s {out['check_s']:.1f}", flush=True)
    for k, v in out.get("notes", {}).items():
        print(f"{k} {v}", flush=True)

    metrics = {}
    if args.trace:
        obs = out["obs"]
        if obs["batches"]:      # the driver's own spans, whole window
            print("driver_spans_ms_per_batch", {
                k: round(1e3 * v / obs["batches"], 4)
                for k, v in obs["spans"].items()}, flush=True)
        for m in mf.metrics_of(cell["name"], "per_layer"):
            spec = mf.metric_spec(m["name"])
            value = manifest.module("readers", spec["reader"]).read(spec, obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {**out["end_to_end"], "setup_s": setup_s}
        for m in mf.metrics_of(cell["name"], "end_to_end"):
            value = values[manifest.quantity_of(m["name"], values)]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": out["device"]}
    if args.rehearse_cpu:           # a CPU run yields no device metric:
        result["rehearsal"] = True  # which metrics it would print, no more
        result["counts"] = out["counts"]
        result["metrics_found"] = sorted(metrics)
        result["metrics"] = {}
    if args.trace and out["obs"].get("trace"):
        result["breakdown"] = out["obs"]["trace"]["breakdown"]
        # false: the trace reader knew no operation's name-scope, and the
        # names in device_ops are XLA's own
        result["scopes"] = out["obs"]["trace"]["scopes"]
    harness.emit(result, out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
