"""Published peaks of the chips the benchmark runs on: one file per JAX
`device_kind` under benchmark/peaks/ (spaces written as `_`), each with its
source.  A device that has no file is an error, never a default.  Nothing
here reads scripts/perf_baseline.json or the phase profiler's native-C++
"roofline" (a CPU rate from another machine)."""
import json
import os


def peaks_of(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks",
                        device_kind.replace(" ", "_") + ".json")
    if not os.path.exists(path):
        raise KeyError(f"no published peaks for device kind {device_kind!r}: "
                       f"add {path} with its source")
    with open(path) as f:
        return json.load(f)
