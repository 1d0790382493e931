"""A percentile of a sample the driver kept (latencies, lateness), in the
sample's own unit times `scale`."""
import numpy as np


def read(spec: dict, obs: dict):
    sample = obs["samples"].get(spec["sample"])
    if sample is None or len(sample) == 0:
        return None
    return float(np.percentile(sample, spec["percentile"])) \
        * spec.get("scale", 1.0)
