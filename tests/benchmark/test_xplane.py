"""The trace reduction gives known busy, idle, kernel and gap numbers on a
small hand-made trace, and reads the recorded chip trace kept beside it."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`

import pytest

from benchmark import xplane
from benchmark.readers import roofline, trace

MS = 1e6    # ns


def _trace():
    # device 0: ops busy 0-40, 30-50 (overlap), 100-150 ms; device 1: 0-10
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_block(1)", 0.0, 50 * MS), ("jit_block(1)", 100 * MS,
                                                 50 * MS),
                ("jit_small(2)", 60 * MS, 1 * MS)]},
            {"name": "XLA Ops", "events": [
                ("while.1", 0.0, 40 * MS), ("fusion.2", 30 * MS, 20 * MS),
                ("while.1", 100 * MS, 50 * MS)]},
            {"name": "Steps", "events": [("0", 0.0, 200 * MS)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [("while.1", 0.0, 10 * MS)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ("bench:send_batch", 0.0, 90 * MS),
                ("bench:send_batch", 95 * MS, 65 * MS),
                ("bench:flush", 160 * MS, 40 * MS),
                ("something else", 0.0, 500 * MS)]},
            {"name": "siddhi-scatter", "events": [
                ("bench:callback", 55 * MS, 30 * MS)]}]},
    ]


def test_busy_is_the_union_of_op_intervals_per_device():
    s = xplane.summarize(_trace())
    assert s["window_s"] == pytest.approx(0.200)
    assert s["devices"]["/device:TPU:0"]["busy_s"] == pytest.approx(0.100)
    assert s["devices"]["/device:TPU:1"]["busy_s"] == pytest.approx(0.010)
    assert s["busiest"] == "/device:TPU:0"
    d0 = s["devices"]["/device:TPU:0"]
    assert d0["op_seconds"]["while.1"] == pytest.approx(0.090)
    assert d0["module_seconds"]["jit_block(1)"] == pytest.approx(0.100)
    assert d0["module_runs"] == {"jit_block(1)": 2, "jit_small(2)": 1}
    assert s["host_spans"]["send_batch"] == pytest.approx(0.155)


def test_idle_gaps_go_piece_by_piece_to_the_span_that_covers_them():
    s = xplane.summarize(_trace())
    gaps = dict(map(tuple, s["breakdown"]["idle_gaps"]))
    # 50-100 ms is cut at the span boundaries: 50-55 and 85-90 send_batch,
    # 55-85 callback (another thread's, begun later: the innermost), 90-95
    # between the two calls, 95-100 send_batch; 150-200 ms: 150-160
    # send_batch, 160-200 flush.  (Before PR 39 a gap went whole to the
    # span over its midpoint: callback 50, flush 50.)
    assert gaps == {"bench:callback": pytest.approx(0.030),
                    "bench:send_batch": pytest.approx(0.025),
                    "bench:flush": pytest.approx(0.040),
                    xplane.BETWEEN: pytest.approx(0.005)}
    assert s["breakdown"]["device_ops"][0] == ["while.1",
                                               pytest.approx(0.090)]
    assert s["scopes"] is False     # hand-made events carry no name-scope
    assert s["breakdown"]["modules"] == [
        ["jit_block(1) x2", pytest.approx(0.100)],
        ["jit_small(2) x1", pytest.approx(0.001)]]
    assert xplane.short_name(
        "%while.73 = (u32[]{:T(128)}, s32[1024,640]{1,0:T(8,128)}) "
        "while((u32[]{:T(128)}) %tuple.1), condition=%c") == "%while.73 while"
    assert xplane.short_name(
        "%custom-call.1 = f32[262144]{0:T(1024)S(1)} custom-call(f64[26]"
        " %p)") == "%custom-call.1 custom-call"
    assert len(s["breakdown"]["device_ops"]) <= 10


def test_trace_readers_give_shares_and_nothing_without_a_device_plane():
    s = xplane.summarize(_trace())
    obs = {"trace": s}
    assert trace.read({"quantity": "busy_share"}, obs) == pytest.approx(50.0)
    assert trace.read({"quantity": "idle_share"}, obs) == pytest.approx(50.0)
    host_only = xplane.summarize([p for p in _trace()
                                  if p["name"] == "/host:CPU"])
    assert trace.read({"quantity": "idle_share"}, {"trace": host_only}) is None
    assert trace.read({"quantity": "idle_share"}, {"trace": None}) is None


def test_roofline_share_from_shapes_module_time_and_the_peak():
    s = xplane.summarize(_trace())
    spec = {"kernel": "lane_block", "bytes_fn": "lane_block_bytes",
            "in_cols": 3, "out_rows": 7}
    obs = {"trace": s, "batches": 2,
           "device_kind": "TPU v5 lite",
           "cell": {"config": {"kernel": "lane_block",
                               "expect": {"sharded_over": 0}}},
           "counters": {"lanes": 1024, "h2d_bytes": 2 * 12 * 1024 * 448}}
    per_call = 4 * (10 * 1024 * 448 + 2 * 1024)
    want = 100.0 * (2 * per_call / 819e9) / 0.100
    assert roofline.read(spec, obs) == pytest.approx(want)
    assert 0 < want < 100
    obs["cell"]["config"]["expect"]["sharded_over"] = 4   # a sharded call
    assert roofline.read(spec, obs) == pytest.approx(want / 4)
    obs["cell"] = {"config": {}}          # a cell without the kernel
    assert roofline.read(spec, obs) is None


def test_merge_joins_touching_and_nested_intervals():
    assert xplane.merge([[5, 6], [0, 2], [1, 3], [3, 4], [0.5, 1]]) == \
        [[0, 4], [5, 6]]


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "pattern1k_sat_v5e.xplane.pb.gz")


def test_recorded_chip_trace_reduces_to_the_numbers_read_by_hand(tmp_path):
    """One and a half seconds of a pattern1k.sat traced run on a TPU v5e
    (PR 25, chip run): two calls of the lane block, the device busy 1.139 s
    of a 1.295 s window, every idle gap inside the driver's send_batch."""
    import gzip
    import shutil
    raw = tmp_path / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(raw, "wb") as dst:
        shutil.copyfileobj(src, dst)
    s = xplane.summarize(xplane.load(str(raw)))
    assert list(s["devices"]) == ["/device:TPU:0"]
    d = s["devices"]["/device:TPU:0"]
    assert s["window_s"] == pytest.approx(1.29487575, rel=1e-6)
    assert d["busy_s"] == pytest.approx(1.139033706, rel=1e-6)
    assert list(d["module_runs"].values()) == [2]
    assert next(iter(d["module_runs"])).startswith("jit_lane_block(")
    assert sum(d["module_seconds"].values()) == pytest.approx(d["busy_s"],
                                                              rel=0.01)
    assert s["breakdown"]["device_ops"][0][0] == "%while.73 while"
    # (names carry their prefix since PR 39; the trace predates the
    # engine's `siddhi:` spans, and the callback and the closing flush hold
    # a third of a millisecond each beside send_batch's 155)
    assert [g[0] for g in s["breakdown"]["idle_gaps"]][:1] == [
        "bench:send_batch"]
    assert s["breakdown"]["idle_gaps"][0][1] == pytest.approx(
        s["window_s"] - d["busy_s"], rel=0.01)
    assert s["scopes"] is False     # PR 25's block had no named scope
    assert trace.read({"quantity": "idle_share"}, {"trace": s}) == \
        pytest.approx(100 * (1 - 1.139033706 / 1.29487575), rel=1e-6)
