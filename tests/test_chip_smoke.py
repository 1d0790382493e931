"""Bring-up contract (CPU lane): chip_smoke.py's explicit dry run and its
refusal without it, where the package places the persistent compile cache,
that cache keys do not move between processes, and that the `--chaos`
parent stays JAX-free.  Everything runs in subprocesses: platform choice,
cache placement and `'jax' in sys.modules` are per-process facts."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env=None, timeout=600, unset=()):
    # XLA_FLAGS: conftest's 8 virtual devices are this process's, not the
    # children's (chip_smoke --chips 4 asks for its own four)
    e = {k: v for k, v in os.environ.items()
         if k not in ("XLA_FLAGS", *unset)}
    e.update(env or {})
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=e,
                          capture_output=True, text=True, timeout=timeout)


def _smoke(tmp_path, *flags):
    r = _run(["chip_smoke.py", "--dry-run-cpu", "--out", str(tmp_path),
              *flags])
    assert r.returncode == 0, r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"      # labelled, never "tpu"
    assert set(last["device"]) == {"platform", "kind", "count"}
    with open(tmp_path / "chip_smoke.json") as f:
        rep = json.load(f)
    assert rep["device"] == last["device"]
    assert rep["mode"].startswith("dry-run-cpu")
    assert rep["jax"] and "backend_compilations" in rep["compile"]
    assert set(rep["compile"]["persistent_cache"]) == {"hits", "misses"}
    for name, ph in rep["phases"].items():
        assert ph["steady_compilations"] == 0, name
        assert ph["events"] > 0 and ph["wall_s"] > 0, name
        assert ph["rows_compared"] > 0, name
    return rep


def test_dry_run_all_kinds(tmp_path):
    rep = _smoke(tmp_path, "--all")
    ph = rep["phases"]
    assert {"c1_filter", "c2_window_avg", "c4_partitioned",
            "c4_partitioned_tcp", "c3_seq", "c3_chunk", "c3_scan",
            "c3_dfa", "c5_fused_queries", "c6_join",
            "c7_external_time_batch", "c8_aggregation"} <= set(ph)
    c4 = ph["c4_partitioned"]
    assert c4["queries"]["q"] == {"path": "device", "kind": "pattern",
                                  "family": "scan"}
    assert c4["state_leaves"]["q.occ"] == 1 and not c4["ladders"]
    assert ph["c4_partitioned_tcp"]["reference"] == "in-process run"
    assert ph["c4_partitioned_tcp"]["rows_compared"] == c4["rows"]
    for fam in ("seq", "chunk", "scan", "dfa"):
        assert ph[f"c3_{fam}"]["queries"]["q"]["family"] == fam
    assert ph["c8_aggregation"]["path"] == "device-resident"


def test_dry_run_four_chips(tmp_path):
    rep = _smoke(tmp_path, "--chips", "4")
    assert rep["device"]["count"] == 4
    c4 = rep["phases"]["c4_partitioned_4chips"]
    assert c4["state_leaves"]["q.occ"] == 4
    assert c4["queries"]["q"]["family"] == "scan"


def test_refuses_any_platform_but_tpu(tmp_path):
    r = _run(["chip_smoke.py", "--out", str(tmp_path)],
             env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "platform" in r.stderr
    assert '"ok"' not in r.stdout                   # no result line
    assert not (tmp_path / "chip_smoke.json").exists()


_PLACEMENT = """
import os, sys, jax
calls = []
orig = jax.config.update
def spy(k, v):
    calls.append(k)
    return orig(k, v)
jax.config.update = spy
import siddhi_tpu
from siddhi_tpu import SiddhiManager
if os.environ.get("JAX_COMPILATION_CACHE_DIR") is None:
    good, siddhi_tpu.CACHE_DIR = siddhi_tpu.CACHE_DIR, "/proc/version/x"
    try:
        SiddhiManager()
    except OSError:
        print("uncreatable cache dir raised")
    siddhi_tpu.CACHE_DIR = good
SiddhiManager().shutdown()
print("cache_dir_updates", calls.count("jax_compilation_cache_dir"))
print("cache_dir", jax.config.jax_compilation_cache_dir)
"""


def test_cache_dir_from_environment_is_left_alone(tmp_path):
    r = _run(["-c", _PLACEMENT],
             env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "cache_dir_updates 0" in r.stdout
    assert f"cache_dir {tmp_path}" in r.stdout


def test_cache_dir_defaults_to_fixed_path_in_checkout():
    r = _run(["-c", _PLACEMENT], unset=("JAX_COMPILATION_CACHE_DIR",))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "uncreatable cache dir raised" in r.stdout
    assert "cache_dir_updates 1" in r.stdout
    assert f"cache_dir {os.path.join(ROOT, '.jax_cache')}" in r.stdout


_LANE_BLOCK_HLO = """
import hashlib, jax
import bench
from siddhi_tpu import SiddhiManager
mgr = SiddhiManager()
rt = mgr.create_app_runtime(
    "@app:deviceMesh('never')\\n@app:partitionCapacity(64)\\n" + bench.C4)
plan = rt._plans[0]
fn = plan._parallel_kernel().block_fn((8, 64), 64)
text = fn.lower({}, plan._flat_dummy(64, L=8)).as_text()
print("hlo", hashlib.sha1(text.encode()).hexdigest())
mgr.shutdown()
"""


def test_lane_block_hlo_is_stable_across_hash_seeds():
    """The persistent compile cache is keyed by the HLO text: a set of
    strings iterated while tracing moves the key with PYTHONHASHSEED and
    every new process misses (found on the chip: the C4 lane block)."""
    digests = set()
    for seed in ("1", "2", "3"):
        r = _run(["-c", _LANE_BLOCK_HLO], env={"PYTHONHASHSEED": seed})
        assert r.returncode == 0, r.stderr[-2000:]
        digests.add(r.stdout.strip().splitlines()[-1])
    assert len(digests) == 1, digests


def test_chaos_parent_never_imports_jax():
    """One process per chip: `bench.py --chaos` spawns every runtime as a
    child and must not hold a backend itself (`_spawn_cell` refuses if
    jax is imported; the parent reports it again at the end)."""
    r = _run(["bench.py", "--chaos", "--cell", "kill9:pattern",
              "--seed", "7"])
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["parent_jax_free"] is True
    assert res["device"]["platform"] == "cpu"
    cell = res["kill9"]["configs"]["pattern"]
    assert cell["pass"] and cell["mid_wal_append"]["killed"] \
        and cell["mid_snapshot"]["identical"]
