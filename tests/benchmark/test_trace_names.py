"""The trace read under the engine's own names (PR 39): spans of both
prefixes nested by interval, self time, idle gaps laid piece by piece
against the spans, device seconds by `jax.named_scope`, the wire reader of
the trace file and its fallback, the five per-layer entries that read them,
and the recorded chip trace of the current program."""
import gzip
import os
import shutil
import struct
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`

import pytest

from benchmark import kernels_fused, xplane, xproto
from benchmark.manifest import Manifest
from benchmark.readers import roofline_fused, span_self, stage
from siddhi_tpu.core.telemetry import SPANS
from test_rehearsal import last_line, run_cell
from test_xplane import _trace as old_trace

MS = 1e6    # ns
MF = Manifest()
OWED = ["route_ms_per_batch", "lane_cut_ms_per_batch", "fused_block_roofline",
        "dispatch_self_ms_per_batch", "feed_ms_per_batch"]
NEW = MF.data["per_layer"][-5:]
SAT4 = ["pattern1k.sat", "pattern1k-mesh4.sat", "pattern1k-zipf.sat",
        "rules1k.sat"]


def _op(name, start_ms, dur_ms, scope=None):
    return (name, start_ms * MS, dur_ms * MS, scope)


def _span(name, start_ms, dur_ms):
    return (name, start_ms * MS, dur_ms * MS)


def _trace(extra_lines=()):
    """Device busy 0-12 and 40-52 ms of a 100 ms trace; one driver thread
    with two batches' worth of engine spans; optional other threads."""
    lane = "jit(lane_block)/vmap("
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                _span("jit_lane_block(7)", 0, 12),
                _span("jit_lane_block(7)", 40, 12)]},
            {"name": "XLA Ops", "events": [
                _op("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop",
                    0, 8, lane + "hop1)/within_kill/reduce_min:"),
                _op("%fusion.2 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop",
                    8, 4, lane + "compact)/reduce:"),
                _op("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop",
                    40, 8, lane + "hop1)/within_kill/reduce_min:"),
                _op("%copy-done.1 = s32[8]{0} copy-done(%c)", 48, 4, None),
            ]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                _span("bench:feed", 0, 5),
                _span("bench:send_batch", 5, 90),
                _span("siddhi:dispatch", 10, 10),
                _span("siddhi:host_build", 11, 3),
                _span("siddhi:transfer", 15, 2),
                _span("siddhi:dispatch", 30, 10),
                _span("siddhi:scatter", 32, 4),       # inside dispatch
                _span("siddhi:scatter", 50, 10),      # around the callbacks
                _span("bench:callback", 52, 3),
                _span("np.asarray(jax.Array)", 15, 2),    # not a span of ours
                _span("bench:flush", 97, 3)]},
            *extra_lines]},
    ]


def test_self_time_is_a_span_less_what_its_children_cover():
    s = xplane.summarize(_trace())
    assert s["span_counts"]["siddhi:dispatch"] == 2
    assert s["span_seconds"]["siddhi:dispatch"] == pytest.approx(0.020)
    # 10 holding host_build 3 and transfer 2 -> 5; 10 holding scatter 4 -> 6
    assert s["span_self_seconds"]["siddhi:dispatch"] == pytest.approx(0.011)
    assert s["span_self_seconds"]["siddhi:host_build"] == pytest.approx(0.003)
    # scatter inside dispatch and around the callbacks: kept apart by parent
    assert s["span_children"]["siddhi:dispatch"]["siddhi:scatter"] == \
        pytest.approx(0.004)
    assert s["span_children"]["bench:send_batch"]["siddhi:scatter"] == \
        pytest.approx(0.010)
    assert s["span_seconds"]["siddhi:scatter"] == pytest.approx(0.014)
    assert s["span_self_seconds"]["siddhi:scatter"] == pytest.approx(0.011)
    assert s["span_children"]["siddhi:scatter"] == {
        "bench:callback": pytest.approx(0.003)}
    # send_batch 90 less dispatch 20 and the outer scatter 10
    assert s["span_self_seconds"]["bench:send_batch"] == pytest.approx(0.060)
    assert s["span_children"][""] == {
        "bench:feed": pytest.approx(0.005),
        "bench:send_batch": pytest.approx(0.090),
        "bench:flush": pytest.approx(0.003)}
    # the driver's spans under their bare names, as before PR 39
    assert s["host_spans"] == {"feed": pytest.approx(0.005),
                               "send_batch": pytest.approx(0.090),
                               "callback": pytest.approx(0.003),
                               "flush": pytest.approx(0.003)}


def test_a_child_that_outlasts_its_parent_is_clipped_to_it():
    n = xplane.nest([("a", 0.0, 10 * MS, "t"), ("b", 8 * MS, 5 * MS, "t"),
                     ("a", 0.0, 10 * MS, "u")])     # another thread's
    assert n["self_seconds"]["a"] == pytest.approx(0.018)
    assert n["children"]["a"] == {"b": pytest.approx(0.005)}
    assert n["count"] == {"a": 2, "b": 1}


def test_idle_gaps_are_cut_at_span_boundaries_and_named_by_the_innermost():
    s = xplane.summarize(_trace())
    assert s["window_s"] == pytest.approx(0.100)
    assert s["devices"]["/device:TPU:0"]["busy_s"] == pytest.approx(0.024)
    gaps = dict(map(tuple, s["breakdown"]["idle_gaps"]))
    # gap 12-40: host_build 12-14, dispatch 14-15, transfer 15-17, dispatch
    # 17-20, send_batch alone 20-30, dispatch 30-32, scatter 32-36,
    # dispatch 36-40; gap 52-100: callback 52-55, scatter 55-60,
    # send_batch alone 60-95, nothing 95-97, flush 97-100
    assert gaps == {
        "siddhi:host_build": pytest.approx(0.002),
        "siddhi:dispatch": pytest.approx(0.010),
        "siddhi:transfer": pytest.approx(0.002),
        "siddhi:scatter": pytest.approx(0.009),
        "bench:callback": pytest.approx(0.003),
        "bench:send_batch" + xplane.NO_ENGINE: pytest.approx(0.045),
        "bench:flush": pytest.approx(0.003),    # holds no engine span here
        xplane.BETWEEN: pytest.approx(0.002)}
    assert sum(gaps.values()) == pytest.approx(0.076)
    assert all(n.startswith(("siddhi:", "bench:")) or n == xplane.BETWEEN
               for n in gaps)


def test_a_span_of_another_thread_that_began_later_is_the_innermost():
    s = xplane.summarize(_trace([{"name": "siddhi-sink", "events": [
        _span("siddhi:sink.publish", 62, 8)]}]))
    gaps = dict(map(tuple, s["breakdown"]["idle_gaps"]))
    assert gaps["siddhi:sink.publish"] == pytest.approx(0.008)
    assert gaps["bench:send_batch" + xplane.NO_ENGINE] == pytest.approx(0.037)


def test_a_span_that_only_waits_names_a_piece_only_when_alone():
    waits = [{"name": "siddhi-net", "events": [
        _span("siddhi:net.wait", 0, 100),
        _span("siddhi:queue_wait", 96, 1)]}]
    s = xplane.summarize(_trace(waits))
    gaps = dict(map(tuple, s["breakdown"]["idle_gaps"]))
    # 95-97 was between the driver's calls: now the waits', the one that
    # began last first; every other piece keeps its name
    assert gaps["siddhi:net.wait"] == pytest.approx(0.001)
    assert gaps["siddhi:queue_wait"] == pytest.approx(0.001)
    assert xplane.BETWEEN not in gaps
    assert gaps["siddhi:dispatch"] == pytest.approx(0.010)
    assert gaps["bench:send_batch" + xplane.NO_ENGINE] == pytest.approx(0.045)


@pytest.mark.parametrize("path,scope", [
    ("jit(lane_block)/vmap(hop1)/within_kill/reduce_min:", "hop1/within_kill"),
    ("jit(row_lane_block)/vmap(vmap(compact))/jit(_where)/select_n:",
     "compact"),
    ("jit(lane_block)/vmap(select)/jit(clip)/min:", "select"),
    ("jit(step)/compare/lt:", "compare"),
    ("jit(f)/vmap(a/b)/c/while/body/add:", "a/b/c"),
    ("jit(lane_block)/vmap()/while/body/closed_call/gather:", ""),
    ("reduce_window_sum:", ""), ("", ""), (None, "")])
def test_scope_of_a_name_scope_path(path, scope):
    assert xplane.scope_of(path) == scope


def test_device_seconds_by_scope_and_scoped_operation_names():
    s = xplane.summarize(_trace())
    assert s["scopes"] is True
    d = s["devices"]["/device:TPU:0"]
    assert d["scope_seconds"] == {
        "hop1/within_kill": pytest.approx(0.016),
        "compact": pytest.approx(0.004),
        xplane.NO_SCOPE: pytest.approx(0.004)}
    assert s["breakdown"]["device_ops"] == [
        ["hop1/within_kill/%fusion.1 fusion", pytest.approx(0.016)],
        ["compact/%fusion.2 fusion", pytest.approx(0.004)],
        ["%copy-done.1 copy-done", pytest.approx(0.004)]]
    assert s["breakdown"]["modules"] == [
        ["jit_lane_block(7) x2", pytest.approx(0.024)]]
    assert d["module_runs"] == {"jit_lane_block(7)": 2}


def test_engine_spans_change_nothing_of_what_was_read_before():
    """`summarize` on a trace with no `siddhi:` span gives PR 38's
    `busy_s`, `op_seconds`, `module_seconds`, and adding engine spans to it
    moves none of them."""
    plain = xplane.summarize(old_trace())
    d0 = plain["devices"]["/device:TPU:0"]
    assert d0["busy_s"] == pytest.approx(0.100)
    assert d0["op_seconds"] == {"while.1": pytest.approx(0.090),
                                "fusion.2": pytest.approx(0.020)}
    assert d0["module_seconds"] == {"jit_block(1)": pytest.approx(0.100),
                                    "jit_small(2)": pytest.approx(0.001)}
    spanned = old_trace()
    spanned[2]["lines"][0]["events"] += [
        _span("siddhi:dispatch", 1, 80), _span("siddhi:transfer", 2, 40)]
    after = xplane.summarize(spanned)
    assert after["window_s"] == plain["window_s"]
    assert after["host_spans"] == plain["host_spans"]
    for dev, d in plain["devices"].items():
        for key in ("busy_s", "op_seconds", "module_seconds", "module_runs"):
            assert after["devices"][dev][key] == d[key]
    assert after["breakdown"]["device_ops"] == plain["breakdown"]["device_ops"]
    assert dict(map(tuple, after["breakdown"]["idle_gaps"]))[
        "siddhi:dispatch"] == pytest.approx(0.005)     # 50-55 ms


# -- the wire reader ---------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _xspace():
    """One device plane, by hand: two operations, one with a `tf_op`."""
    stat_meta = _field(5, _field(1, 9) + _field(2, _field(1, 9)
                                                + _field(2, "tf_op")))
    other = _field(5, _field(1, 4) + _field(2, _field(1, 4)
                                            + _field(2, "flops")))
    meta1 = _field(1, 1) + _field(2, "%fusion.1 = s32[8] fusion(%p)") \
        + _field(5, _field(1, 4) + _field(4, 77)) \
        + _field(5, _field(1, 9) + _field(5, "jit(f)/vmap(pack)/scatter:"))
    meta2 = _field(1, 2) + _field(2, "%copy.2 = s32[8] copy(%p)") \
        + _field(5, _field(1, 4) + _field(2, 1.5))
    events = _field(4, _field(1, 1) + _field(2, 5_000_999) + _field(3, 2_000_500)
                    + _field(4, _field(1, 4) + _field(3, 1))) \
        + _field(4, _field(1, 2) + _field(2, 9_000_000) + _field(3, 1_000_000))
    line = _field(1, 3) + _field(2, "XLA Ops") + _field(3, 1000) + events
    steps = _field(2, "Steps") + _field(3, 1000) \
        + _field(4, _field(1, 1) + _field(2, 0) + _field(3, 4_000_000))
    plane = _field(1, 0) + _field(2, "/device:TPU:0") + _field(3, line) \
        + _field(3, steps) + _field(4, _field(1, 1) + _field(2, meta1)) \
        + _field(4, _field(1, 2) + _field(2, meta2)) + stat_meta + other
    return _field(1, plane) + _field(4, "a-host-name")


def test_the_wire_reader_on_a_trace_file_written_by_hand(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_xspace())
    planes = xplane.load(str(path))
    assert planes == [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            # whole nanoseconds from the line's start, as ProfileData
            ("%fusion.1 = s32[8] fusion(%p)", 6000.0, 2000.0,
             "jit(f)/vmap(pack)/scatter:"),
            ("%copy.2 = s32[8] copy(%p)", 10000.0, 1000.0, None)]},
        {"name": "Steps", "events": [      # no scope asked of this line
            ("%fusion.1 = s32[8] fusion(%p)", 1000.0, 4000.0)]}]}]
    s = xplane.summarize(planes)
    assert s["scopes"] is True
    assert s["breakdown"]["device_ops"][0] == [
        "pack/%fusion.1 fusion", pytest.approx(2e-6)]
    with pytest.raises(ValueError):
        xproto.planes_of(_xspace()[:-3])        # a file cut short


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "pattern1k_sat_v5e_pr39.xplane.pb.gz")
OLD_RECORDED = os.path.join(os.path.dirname(RECORDED),
                            "pattern1k_sat_v5e.xplane.pb.gz")


def _unzipped(src, tmp_path):
    raw = tmp_path / "recorded.xplane.pb"
    with gzip.open(src, "rb") as f, open(raw, "wb") as dst:
        shutil.copyfileobj(f, dst)
    return str(raw)


def test_recorded_chip_trace_of_the_current_program_by_hand(tmp_path):
    """The first twelve batches of a traced pattern1k.sat run on a TPU v5e
    (PR 39, chip run, seed 2147484001; the device plane and the driver's
    thread, trimmed and re-written with TensorFlow's `xplane_pb2`).  The
    numbers were read from the file with that module and loops of their
    own (a scope's seconds as the operations whose `tf_op` holds
    `vmap(select)`, self time as each dispatch less the spans directly
    inside it, idle pieces by brute force over every boundary), not with
    the code under test."""
    assert os.path.getsize(RECORDED) < 400_000
    s = xplane.summarize(xplane.load(_unzipped(RECORDED, tmp_path)))
    assert list(s["devices"]) == ["/device:TPU:0"] and s["scopes"] is True
    d = s["devices"]["/device:TPU:0"]
    assert s["window_s"] == pytest.approx(0.631438475, rel=1e-9)
    assert d["busy_s"] == pytest.approx(0.028050568, rel=1e-9)
    assert d["scope_seconds"]["select"] == pytest.approx(0.006053935, rel=1e-9)
    assert d["scope_seconds"]["capture"] == pytest.approx(0.003133771,
                                                         rel=1e-9)
    assert sum(d["scope_seconds"].values()) == pytest.approx(0.028050568,
                                                             rel=1e-9)
    assert d["module_runs"] == {"jit_lane_block(758326944370535799)": 12}
    assert s["breakdown"]["modules"] == [
        ["jit_lane_block(758326944370535799) x12",
         pytest.approx(0.028055889, rel=1e-9)]]     # 2.338 ms a call
    assert s["span_counts"] == {
        "bench:feed": 12, "bench:send_batch": 12, "siddhi:ingest": 12,
        "siddhi:freeze": 12, "siddhi:dispatch": 72, "siddhi:host_build": 36,
        "siddhi:kernel": 12, "siddhi:scatter": 60, "siddhi:transfer": 13,
        "siddhi:emit": 12, "bench:callback": 12}
    assert s["span_seconds"]["siddhi:dispatch"] == pytest.approx(
        0.568081175, rel=1e-9)
    assert s["span_self_seconds"]["siddhi:dispatch"] == pytest.approx(
        0.191531391, rel=1e-9)                      # 15.96 ms a batch
    # the judge's callback runs INSIDE the engine's scatter span
    assert list(s["span_children"]["siddhi:scatter"]) == ["bench:callback"]
    gaps = dict(map(tuple, s["breakdown"]["idle_gaps"]))
    assert gaps == {
        "siddhi:dispatch": pytest.approx(0.188813653, rel=1e-9),
        "siddhi:scatter": pytest.approx(0.173074406, rel=1e-9),
        "siddhi:host_build": pytest.approx(0.1305956, rel=1e-9),
        "bench:feed": pytest.approx(0.053826468, rel=1e-9),
        "siddhi:transfer": pytest.approx(0.045533688, rel=1e-9),
        "bench:send_batch" + xplane.NO_ENGINE:
            pytest.approx(0.003731943, rel=1e-9),
        "siddhi:kernel": pytest.approx(0.002652041, rel=1e-9),
        "bench:callback": pytest.approx(0.002229089, rel=1e-9),
        "siddhi:freeze": pytest.approx(0.001631611, rel=1e-9),
        "siddhi:emit": pytest.approx(0.000646788, rel=1e-9)}
    assert s["breakdown"]["device_ops"][0] == [
        "hop1/within_kill/%fusion.10 fusion",
        pytest.approx(0.004740932, rel=1e-9)]
    spec = MF.metric_spec("dispatch_self_ms_per_batch")
    assert span_self.read(spec, {"trace": s}) == pytest.approx(
        15.96094925, rel=1e-9)


@pytest.mark.parametrize("recorded", [RECORDED, OLD_RECORDED],
                         ids=["pr39", "pr25"])
def test_both_readers_give_the_same_events_and_the_fallback_no_scope(
        recorded, tmp_path, monkeypatch, capsys):
    raw = _unzipped(recorded, tmp_path)
    wire, plain = xplane._load_wire(raw), xplane._load_profile_data(raw)
    assert [p["name"] for p in wire] == [p["name"] for p in plain]
    for a, b in zip(wire, plain):
        assert [ln["name"] for ln in a["lines"]] == \
            [ln["name"] for ln in b["lines"]]
        for la, lb in zip(a["lines"], b["lines"]):
            assert [e[:3] for e in la["events"]] == lb["events"]

    def no_reader(path):
        raise ImportError("no module that reads a trace's metadata")
    monkeypatch.setattr(xplane, "READERS",
                        (no_reader, xplane._load_profile_data))
    full, fallen = xplane.summarize(wire), xplane.summarize(xplane.load(raw))
    assert "no_reader could not read" in capsys.readouterr().err
    assert fallen["scopes"] is False
    for key in ("window_s", "devices", "span_self_seconds", "span_counts"):
        if key == "devices":
            for dev, d in full["devices"].items():
                for k in ("busy_s", "op_seconds", "module_seconds"):
                    assert fallen["devices"][dev][k] == d[k]
        else:
            assert fallen[key] == full[key]
    assert fallen["breakdown"]["idle_gaps"] == full["breakdown"]["idle_gaps"]
    # today's names: XLA's own, no scope before them
    assert all("/" not in n for n, _s in fallen["breakdown"]["device_ops"])
    assert sorted(s for _n, s in fallen["breakdown"]["device_ops"]) == \
        sorted(s for _n, s in full["breakdown"]["device_ops"])


# -- the metrics that read it --------------------------------------------------

def test_the_five_entries_are_appended_in_the_issues_order():
    assert [m["name"] for m in NEW] == OWED
    assert len(MF.data["per_layer"]) == 44


@pytest.mark.parametrize("entry", NEW, ids=[m["name"] for m in NEW])
def test_new_entry_resolves_to_a_file_that_agrees_with_it(entry):
    spec = MF.metric_spec(entry["name"])
    for key in ("layer", "unit", "source"):
        assert spec[key] == entry[key], key
    assert os.path.exists(MF.path("readers", spec["reader"] + ".py"))
    assert set(spec.get("stages", [])) <= set(SPANS)
    assert entry["moves"] == "events_per_s"
    moved = next(m for m in MF.data["end_to_end"]
                 if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    want = {"route_ms_per_batch": ["rules1k.sat"],
            "lane_cut_ms_per_batch": ["rules1k.sat", "pattern1k-zipf.sat"],
            "fused_block_roofline": ["rules1k.sat"]}
    assert entry["workloads"] == want.get(entry["name"], SAT4)
    layers = {m["layer"] for m in MF.data["per_layer"][:-5]}
    assert entry["layer"] in layers         # a layer the benchmark names


def test_materialise_no_longer_adds_the_callback_that_scatter_holds():
    spec = MF.metric_spec("materialise_ms_per_batch")
    assert spec["stages"] == ["scatter"] and "spans" not in spec
    obs = {"stages": {"scatter": 3.0}, "spans": {"callback": 1.0},
           "batches": 2}
    assert stage.read(spec, obs) == pytest.approx(1500.0)


@pytest.mark.parametrize("cell", SAT4)
def test_traced_rehearsal_finds_the_span_metrics_of_the_cell(cell, tmp_path):
    want = sorted(m["name"] for m in NEW if cell in m["workloads"]
                  and "roofline" not in m["name"])
    assert {"dispatch_self_ms_per_batch", "feed_ms_per_batch"} <= set(want)
    # the trace starts between two batches: a rules1k batch takes the CPU
    # over a second (several on a loaded machine: a first batch that outlasts
    # the window leaves no trace), so its window has to hold a few
    seconds = "8" if cell == "rules1k.sat" else "1.5"
    r = run_cell(["--workload", cell, "--seed", str(2**31 + 39),
                  "--seconds", seconds, "--trace", "1", "--rehearse-cpu",
                  "--keep-trace", str(tmp_path)])
    out = last_line(r)
    assert out["correct"] is True, out["compared"]
    # the raw trace is kept where asked, and reads back: the engine's
    # dispatch rounds nest inside the driver's send_batch
    (kept,) = os.listdir(tmp_path)
    s = xplane.summarize(xplane.load(str(tmp_path / kept)))
    assert s["span_children"]["bench:send_batch"]["siddhi:dispatch"] > 0
    assert s["span_counts"]["bench:feed"] >= s["span_counts"][
        "bench:send_batch"] >= 1
    missing = [n for n in want if n not in out["metrics_found"]]
    assert not missing, (missing, out["metrics_found"])
    assert out["scopes"] is False and out["breakdown"]["device_ops"] == []
    assert "tape_batches_built_in_window" in r.stdout
    assert "batch_period_ms" in r.stdout
    assert "driver_spans_ms_per_batch" in r.stdout


def test_batch_periods_by_hand():
    """Ten batches of 10 ms and one that stood still for 50: the note says
    so, and counts only what lies beyond twice the median as stalled."""
    import numpy as np
    from benchmark.drivers import inproc_sat
    got = inproc_sat._periods(np.array([0.01] * 5 + [0.05] + [0.01] * 5))
    assert got["median"] == 10.0 and got["max"] == 50.0
    assert got["stalled_s"] == pytest.approx(0.03)
    assert len(got["mean_by_third"]) == 3
    assert inproc_sat._periods(np.array([])) == {}


def test_fused_block_bytes_on_shapes_worked_by_hand():
    # one 2^16-event batch, 26 rows an event: three 4-byte columns read
    # once, seven 4-byte words a row written once
    assert kernels_fused.fused_block_bytes(65536, 26 * 65536) == \
        4 * (3 * 65536 + 7 * 1703936) == 48_496_640
    assert kernels_fused.fused_block_bytes(10, 0) == 120
    assert kernels_fused.fused_block_bytes(8, 2.5, in_cols=1, out_words=2) \
        == 4 * (8 + 5)
    for bad in ((0, 1), (4, -1)):
        with pytest.raises(ValueError):
            kernels_fused.fused_block_bytes(*bad)


def test_fused_roofline_from_events_rows_busy_seconds_and_the_peak():
    s = xplane.summarize(_trace())      # one send_batch, busy 24 ms
    spec = MF.metric_spec("fused_block_roofline")
    obs = {"trace": s, "device_kind": "TPU v5 lite", "batch": 65536,
           "events": 10 * 65536, "rows_delivered": 260 * 65536,
           "cell": {"config": {"kernel": "fused_lane_block"}}}
    want = 100.0 * (48_496_640 / 819e9) / 0.024
    assert roofline_fused.read(spec, obs) == pytest.approx(want)
    assert 0 < want < 1
    # the same work laid out over any grid, capacity or upload reads the
    # same: nothing of the layout is in `obs`
    assert roofline_fused.read(spec, {**obs, "counters": {
        "lanes": 36000, "h2d_bytes": 1 << 30}}) == pytest.approx(want)
    for hole in ({"trace": None}, {"rows_delivered": None},
                 {"cell": {"config": {"kernel": "lane_block"}}},
                 {"trace": xplane.summarize(
                     [p for p in _trace() if p["name"] == "/host:CPU"])}):
        assert roofline_fused.read(spec, {**obs, **hole}) is None


def test_span_self_reader_divides_by_the_batches_of_the_traced_interval():
    s = xplane.summarize(_trace())
    spec = {"span": "siddhi:dispatch", "per": "bench:send_batch"}
    assert span_self.read(spec, {"trace": s}) == pytest.approx(11.0)
    assert span_self.read({**spec, "span": "siddhi:route"},
                          {"trace": s}) is None
    assert span_self.read({**spec, "per": "bench:frame"},
                          {"trace": s}) is None
    assert span_self.read(spec, {"trace": None}) is None
    assert span_self.read(spec, {}) is None
