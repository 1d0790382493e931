"""A span's SELF time in the trace: its duration less the part its child
spans cover (benchmark/xplane.py `nest`), summed over the traced interval
and divided by the count of the span named `per` in the same interval
(`bench:send_batch`: the batches the interval holds), times `scale` (1000:
milliseconds).  Names carry their prefix (`siddhi:` the engine's, `bench:`
the driver's).  No trace, or no such span in it: nothing to read."""


def read(spec: dict, obs: dict):
    t = obs.get("trace")
    if not t:
        return None
    seconds = t.get("span_self_seconds", {}).get(spec["span"])
    per = t.get("span_counts", {}).get(spec["per"])
    if seconds is None or not per:
        return None
    return spec.get("scale", 1000.0) * seconds / per
