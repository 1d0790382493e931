"""The CPU rehearsal lane drives every cell's code path end to end at a
tiny size (the mesh cell on four virtual devices), holds its output to the
reference, and prints no metric; without --rehearse-cpu a run that finds no
TPU fails and prints no result.  Subprocesses: platform, device count and
the one-process-per-chip rule are per-process facts."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_cell(args, root=ROOT, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-m", "benchmark.run", *args],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=timeout)


def last_line(r):
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [(w["name"], w["chips"]) for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("cell,chips", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_end_to_end_on_the_cpu(cell, chips, trace):
    r = run_cell(["--workload", cell, "--seed", str(2**31 + 77 + trace),
                  "--seconds", "1.5", "--trace", str(trace),
                  "--rehearse-cpu"])
    out = last_line(r)
    assert out["correct"] is True, (out["compared"], r.stdout[-2000:])
    assert out["rehearsal"] is True and out["metrics"] == {}
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == chips
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["counts"]["rows_delivered"] > 0
    assert list(out)[-1] == "compared" and out["compared"]
    assert all(c["value"] <= c["limit"] for c in out["compared"].values())
    # the numbers compared are also the last line on standard error
    assert r.stderr.strip().splitlines()[-1].startswith("compared ")
    assert "attach_s" in r.stdout and "compiles_in_window" in r.stdout
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        mf = json.load(f)
    if not trace:       # every end-to-end metric the manifest gives the cell
        assert out["metrics_found"] == sorted(
            m["name"] for m in mf["end_to_end"]
            if cell in m.get("workloads", [cell]))
    if trace:
        assert any(n.startswith("compiles_in_window")
                   for n in out["metrics_found"])
        # nothing ran on a TPU: no reader may report a device share
        assert not any("share" in n or "roofline" in n
                       for n in out["metrics_found"])
        assert "busy_s" not in out["device"]


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    r = run_cell(["--workload", CELLS[0][0], "--seed", "1", "--seconds", "1",
                  "--trace", "0"], timeout=120)
    assert r.returncode != 0
    assert "Nothing was run" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_an_unknown_workload_fails():
    r = run_cell(["--workload", "no.such", "--seed", "1", "--seconds", "1",
                  "--trace", "0", "--rehearse-cpu"], timeout=120)
    assert r.returncode != 0 and "no workload" in r.stderr
