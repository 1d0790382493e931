"""Seconds of the engine's own stages (`rt.statistics()["stages"]`) and of
the benchmark driver's spans, summed over the window."""
from benchmark.readers import reduce


def read(spec: dict, obs: dict):
    parts = [obs["stages"].get(s) for s in spec.get("stages", [])] \
        + [obs["spans"].get(s) for s in spec.get("spans", [])]
    found = [p for p in parts if p is not None]
    if not found:
        return None
    return reduce(spec, sum(found), obs)
