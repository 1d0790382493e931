"""A later PR adds a configuration, a traffic mix and a per-layer metric as
NEW files and new manifest entries, edits no file that was there, and the
harness runs them: proved on a temporary copy, on the CPU rehearsal lane."""
import hashlib
import json
import os
import shutil

from test_rehearsal import ROOT, last_line, run_cell


def _digests(root):
    out = {}
    for d, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_cell_and_metric_are_files_and_entries_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "siddhi_tpu"),
               os.path.join(root, "siddhi_tpu"))
    before = _digests(root)

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "pattern1k.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "throwaway"
    cfg["rehearsal"]["tape_params"] = {"keys": 32, "dt_ms": 128}
    with open(os.path.join(root, "benchmark", "configs",
                           "throwaway.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "sat-2p18-inproc.json")) as f:
        traffic = json.load(f)
    traffic["rehearsal"]["batch"] = 2048
    with open(os.path.join(root, "benchmark", "traffic",
                           "sat-throwaway.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "benchmark", "metrics",
                           "flush_ms_per_batch.json"), "w") as f:
        json.dump({"layer": "materialise + sink", "unit": "ms",
                   "source": "program_span",
                   "reader": "stage", "spans": ["flush"],
                   "reduce": "per_batch", "scale": 1000.0}, f)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        mf = json.load(f)
    mf["configs"].append({"name": "throwaway", "source": "a test",
                          "file": "benchmark/configs/throwaway.json",
                          "reduced": cfg["reduced"], "why": "a test"})
    mf["workloads"].append({"name": "throwaway.sat", "config": "throwaway",
                            "traffic": "sat-throwaway", "chips": 1,
                            "why": "a test"})
    for m in mf["end_to_end"]:
        if m["name"] == "events_per_s":
            m["workloads"].append("throwaway.sat")
    mf["per_layer"].append({"name": "flush_ms_per_batch", "unit": "ms",
                            "better": "lower", "source": "program_span",
                            "layer": "materialise + sink",
                            "moves": "events_per_s",
                            "workloads": ["throwaway.sat"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(mf, f)

    out = last_line(run_cell(["--workload", "throwaway.sat", "--seed", "9",
                              "--seconds", "1", "--trace", "1",
                              "--rehearse-cpu"], root=root))
    assert out["correct"] is True and out["counts"]["keys_compared"] == 32
    assert out["metrics_found"] == ["flush_ms_per_batch"]
    after = _digests(root)
    assert {k: after[k] for k in before} == before      # nothing was edited
    assert set(after) - set(before) == {
        "benchmark/configs/throwaway.json",
        "benchmark/traffic/sat-throwaway.json",
        "benchmark/metrics/flush_ms_per_batch.json"}
