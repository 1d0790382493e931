"""BENCHMARK.json names only files that exist, and only names, units and
sizes that the benchmark's contract allows."""
import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`
from benchmark.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def mf():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes(mf):
    assert set(mf) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(mf["run_seconds"], int) and 1 <= mf["run_seconds"] <= 51
    # a full check of 24 cells has to fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (mf["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert 1 <= len(mf["command"]) <= 32 and all(map(_line, mf["command"]))
    assert all(not w.startswith("/") and ".." not in w for w in mf["command"])
    assert 1 <= len(mf["paths"]) <= 16
    for p in mf["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        assert os.path.isdir(os.path.join(ROOT, p))


def test_configs_name_files_under_paths(mf):
    files = set()
    assert 1 <= len(mf["configs"]) <= 24
    for c in mf["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in mf["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) and k in body for k in c["reduced"])
        assert body["guarantees"] and body["assumed"] and _line(body["source"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "apps", body["app"] + ".siddhi"))
        for kind, key in (("tapes", "tape"), ("reference", "reference")):
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", kind, body[key] + ".py"))
    used = {w["config"] for w in mf["workloads"]}
    assert used == {c["name"] for c in mf["configs"]}


def test_workloads(mf):
    assert 1 <= len(mf["workloads"]) <= 24
    pairs, names = set(), set()
    for w in mf["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        names.add(w["name"])
        path = os.path.join(ROOT, "benchmark", "traffic",
                            w["traffic"] + ".json")
        with open(path) as f:
            traffic = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "drivers", traffic["driver"] + ".py"))
    assert len(names) == len(mf["workloads"])
    four = sum(1 for w in mf["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(mf["workloads"]) // 2)


def test_metrics(mf):
    cells = {w["name"] for w in mf["workloads"]}
    e2e = {m["name"]: m for m in mf["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert 1 <= len(mf["end_to_end"]) <= 16 and len(e2e) == len(mf["end_to_end"])
    for m in mf["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    names = set(e2e)
    assert 1 <= len(mf["per_layer"]) <= 128
    for m in mf["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["name"] not in names
        names.add(m["name"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert _line(m["layer"]) and m["moves"] in e2e
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", moved)) <= moved
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        spec = Manifest().metric_spec(m["name"])
        assert "moves" not in spec      # the entry's, so a file serves twins
        for k in ("layer", "unit", "source"):
            assert spec[k] == m[k], (m["name"], k)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    # every cell reports setup_s, another end-to-end metric and a layer metric
    for cell in cells:
        assert any(cell in m.get("workloads", cells)
                   for m in mf["end_to_end"] if m["name"] != "setup_s")
        assert any(cell in m.get("workloads", cells) for m in mf["per_layer"])


def test_every_file_under_paths_is_legally_named(mf):
    for p in mf["paths"]:
        for d, _dirs, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_a_quantity_split_by_what_it_moves_has_one_file(mf):
    m = Manifest()
    split = [x["name"] for x in mf["per_layer"] if "." in x["name"]]
    assert {n.rsplit(".", 1)[1] for n in split} == {"paced", "host"}
    shared = 0
    for name in split:
        own = os.path.exists(m.path("metrics", name + ".json"))
        stem = name.rsplit(".", 1)[0]
        shared += not own
        assert own or m.metric_spec(name) == m.metric_spec(stem)
    assert shared >= 15         # the wire and filter cells' twins: no copies
    # a split name with a file of its own keeps it
    assert m.metric_spec("gen_late_p95_ms.paced")["sample"] == "gen_late_s"


def test_a_split_name_is_the_quantity_the_driver_reports():
    from benchmark.manifest import quantity_of
    reported = {"events_per_s": 1.0, "setup_s": 2.0}
    assert quantity_of("events_per_s.host", reported) == "events_per_s"
    assert quantity_of("events_per_s", reported) == "events_per_s"
    assert quantity_of("setup_s", reported) == "setup_s"
    assert quantity_of("a.b", {"a.b", "a"}) == "a.b"


def test_cells_whose_spreads_differ_tenfold_do_not_share_a_bound(mf):
    """PERF.md section 2: `pattern1k.sat` spreads 0.2-0.6%, `filter1q.sat`
    1.7-2.8%; one bound over both guards the north star at the filter's."""
    by_name = {m["name"]: m for m in mf["end_to_end"]}
    assert "filter1q.sat" not in by_name["events_per_s"]["workloads"]
    assert by_name["events_per_s.host"]["workloads"] == ["filter1q.sat"]
    assert by_name["events_per_s"]["bound"] < by_name["events_per_s.host"][
        "bound"]
