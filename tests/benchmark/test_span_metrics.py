"""The per-layer metrics that read the engine's own spans (PR 26): each new
entry of BENCHMARK.json resolves to a metric file that agrees with it, and a
traced rehearsal of every cell finds something to read for each entry that
lists the cell.  (Readings come from the chip; a rehearsal only says which
metrics it would print.)"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`
from benchmark.manifest import Manifest
from siddhi_tpu.core.telemetry import SPANS
from test_rehearsal import last_line, run_cell

QUANTITIES = {
    "freeze_ms_per_batch": ["freeze"],
    "kernel_dispatch_ms_per_batch": ["kernel"],
    "device_wait_ms_per_batch": ["transfer"],
    "unpack_ms_per_batch": ["unpack"],
    "wire_decode_ms_per_batch": ["net.decode"],
    "queue_wait_ms_per_batch": ["admit", "queue_wait"],
    "sink_publish_ms_per_batch": ["sink.publish"],
}
MF = Manifest()
NEW = [m for m in MF.data["per_layer"]
       if m["name"].rsplit(".", 1)[0] in QUANTITIES
       or m["name"].split(".")[0] in QUANTITIES
       or m["name"] in ("d2h_bytes_per_event", "d2h_bytes_per_event.paced")]


def test_the_new_entries_are_the_fifteen_of_the_issue():
    assert len(NEW) == 15
    # still in `per_layer` in their order (later PRs append after them)
    rest = iter(MF.data["per_layer"])
    assert all(any(m is entry for m in rest) for entry in NEW)


@pytest.mark.parametrize("entry", NEW, ids=[m["name"] for m in NEW])
def test_entry_resolves_to_a_file_that_agrees_with_it(entry):
    spec = MF.metric_spec(entry["name"])
    for key in ("layer", "unit", "source"):
        assert spec[key] == entry[key], key
    suffix = {"events_per_s": "", "events_per_s.host": ".host",
              "detect_p95_ms": ".paced"}[entry["moves"]]
    assert entry["name"].endswith(suffix)
    quantity = entry["name"][:len(entry["name"]) - len(suffix)] \
        if suffix else entry["name"]
    assert os.path.exists(MF.path("metrics", quantity + ".json"))
    if quantity in QUANTITIES:
        assert spec["reader"] == "stage" and spec["reduce"] == "per_batch"
        assert spec["scale"] == 1000.0
        assert spec["stages"] == QUANTITIES[quantity]
        assert set(spec["stages"]) <= set(SPANS)
    else:
        assert spec["reader"] == "counter" and spec["counter"] == "d2h_bytes"
    # every cell listed reports the end-to-end metric the entry moves
    moved = next(m for m in MF.data["end_to_end"]
                 if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])


@pytest.mark.parametrize("cell", [w["name"] for w in MF.data["workloads"]])
def test_traced_rehearsal_reads_every_new_metric_of_the_cell(cell):
    want = sorted(m["name"] for m in NEW if cell in m["workloads"])
    assert want
    out = last_line(run_cell(["--workload", cell, "--seed", str(2**31 + 26),
                              "--seconds", "1.5", "--trace", "1",
                              "--rehearse-cpu"]))
    assert out["correct"] is True, out["compared"]
    missing = [n for n in want if n not in out["metrics_found"]]
    assert not missing, (missing, out["metrics_found"])
