"""A kernel's share of its HBM roofline: the bytes its calls must move
(benchmark/kernels.py, from the call's shapes) over the device seconds of
its XLA module in the trace, against the published peak of the device kind
(benchmark/peaks/).  The kernel's module is the one that took most device
time on the busiest device; its shapes are read through the engine's
counters: lanes from the plan's gauges, capacity from the upload bytes of
one call."""
from benchmark import kernels, peaks


def read(spec: dict, obs: dict):
    t = obs.get("trace")
    cfg = obs["cell"]["config"]
    if not t or not t.get("devices") or cfg.get("kernel") != spec["kernel"]:
        return None
    dev = t["devices"][t["busiest"]]
    if not dev["module_seconds"] or not obs["batches"]:
        return None
    module = max(dev["module_seconds"], key=dev["module_seconds"].get)
    seconds, runs = dev["module_seconds"][module], dev["module_runs"][module]
    lanes = obs["counters"].get("lanes")
    h2d = obs["counters"].get("h2d_bytes")
    if not (seconds and runs and lanes and h2d):
        return None
    in_cols = int(spec["in_cols"])
    capacity = max(1, round(h2d / obs["batches"] / (4 * in_cols * lanes)))
    per_call = getattr(kernels, spec["bytes_fn"])(
        lanes, capacity, in_cols, int(spec["out_rows"]))
    # a sharded call moves its bytes on that many devices at once
    least_s = per_call * runs / (cfg["expect"]["sharded_over"] or 1) \
        / peaks.peaks_of(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
