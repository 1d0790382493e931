"""Per-layer metric readers, found by the `reader` name in a metric's file
(benchmark/metrics/<metric>.json).  A reader is `read(spec, obs)`: `spec` is
the metric's file, `obs` what the traced run observed (events, batches,
window_s, stages, spans, counters, samples, trace, cell, device_kind).  A
reader that finds nothing to read returns None and the metric is left out
of the result line."""


def reduce(spec: dict, total, obs: dict):
    """One of the small set of reductions a metric file may name."""
    kind = spec["reduce"]
    scale = spec.get("scale", 1.0)
    if total is None:
        return None
    if kind == "per_batch":
        return scale * total / obs["batches"] if obs["batches"] else None
    if kind == "per_event":
        return scale * total / obs["events"] if obs["events"] else None
    if kind == "share_of_window":
        return 100.0 * total / obs["window_s"] if obs["window_s"] else None
    if kind == "count":
        return scale * total
    raise ValueError(f"unknown reduction {kind!r}")
