"""A counter of the window: transfer bytes as the engine counts them, or
backend compilations as jax.monitoring reports them."""
from benchmark.readers import reduce


def read(spec: dict, obs: dict):
    value = obs["counters"].get(spec["counter"])
    if value is None or (value == 0 and spec.get("zero_is_nothing")):
        return None
    return reduce(spec, value, obs)
