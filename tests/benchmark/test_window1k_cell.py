"""The cell `window1k.sat`: its files (the app is bench.py's C2, the
configuration as ISSUE 44 gives it), its plain reference against the host
interpreter, its judge (every batch counted, batch 0 and a seeded one in 16
compared by value; what turns `correct` false and what does not), its
control, the roofline metric's data file, and that the CPU rehearsal is
`correct`, finds the cell's metrics and reports the plan's `window` record.
The cell joins test_rehearsal.py, test_span_metrics.py and test_manifest.py
by being in the manifest."""
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`

import numpy as np
import pytest

import bench
from benchmark import compare, control, engine, kernels_fused, manifest
from benchmark.readers import roofline_fused
from benchmark.reference import window_avg
from test_rehearsal import last_line, run_cell

CELL = "window1k.sat"
PER_LAYER = ["ingest_ms_per_batch", "freeze_ms_per_batch",
             "host_pack_ms_per_batch", "kernel_dispatch_ms_per_batch",
             "device_wait_ms_per_batch", "materialise_ms_per_batch",
             "h2d_bytes_per_event", "d2h_bytes_per_event",
             "kernel_busy_share", "device_idle_share", "compiles_in_window"]


def _cell(rehearse=True):
    cell = manifest.Manifest().cell(CELL)
    if rehearse:
        cell["config"] = manifest.rehearsed(cell["config"])
        cell["traffic"] = manifest.rehearsed(cell["traffic"])
    return cell


def _by(checks):
    return {c["name"]: c["value"] for c in checks}


# -- the files ----------------------------------------------------------------------

def test_the_app_is_bench_c2_apart_from_the_placeholders():
    text = manifest.Manifest().cell(CELL)["app_text"]
    assert text.replace("{source}", "").replace("{sink}", "") == bench.C2
    assert text.count("{source}") == text.count("{sink}") == 1
    assert text.startswith("{source}define stream StockStream")


def test_the_configuration_is_as_the_issue_gives_it():
    cfg = manifest.Manifest().cell(CELL)["config"]
    assert cfg["source"] == (
        "BASELINE.json configs[1] 'length/time window aggregation'; Siddhi "
        "4.x docs 'Window' length(): from StockStream#window.length(1000) "
        "select avg(price); as bench.py C2")
    assert len(cfg["source"]) <= 200
    # the default spelled out; no devicePipeline, no geometry annotation
    assert cfg["annotations"] == ["@app:deviceMesh('never')",
                                  "@app:deviceWindows('auto')"]
    assert (cfg["stream"], cfg["out_stream"]) == ("StockStream", "Out")
    assert cfg["out_cols"] == [["ap", "double"]]
    assert cfg["stateful"] is True and cfg["tape"] == "stock"
    assert cfg["tape_params"] == {"keys": 8, "dt_ms": 1, "price_lo": 90.0,
                                  "price_hi": 130.0, "price_step": 0.25}
    assert cfg["query"] == {"length": 1000}
    assert cfg["reference"] == "window_avg"
    assert cfg["compare_one_batch_in"] == 16
    assert cfg["kernel"] == "window_block"
    assert cfg["expect"] == {"path": "device", "kind": "window",
                             "family": None, "sharded_over": 0}
    assert cfg["reduced"] == ["stream_events"] and "stream_events" in cfg
    assert set(cfg["guarantees"]) == {"rows", "values", "state", "delivery",
                                      "device_precision"}
    assert "ONE f32 division" in cfg["guarantees"]["values"]
    assert f"within {window_avg.VALUE_ULPS} f32 ulps" in \
        cfg["guarantees"]["values"]
    assert 2 <= window_avg.VALUE_ULPS <= 4      # never past 4 (ISSUE 44)
    # the prebuilt tape: a multiple of 0.5 M events/s under the 4 GB cap
    rate = cfg["prebuild_events_per_s"]
    assert rate % 500_000 == 0 and 30 * rate * 24 <= 4e9
    told = " ".join(cfg["assumed"])
    for said in ("8 symbols", "quarter steps", "1 ms apart",
                 "2^18-event columnar batches", "prebuild_events_per_s",
                 "compare_one_batch_in 16"):
        assert said in told, said
    # a full window's sum is exact in f32 on this tape
    tp = cfg["tape_params"]
    assert cfg["query"]["length"] * tp["price_hi"] / tp["price_step"] < 2 ** 24
    # the skew stanza of the traffic file does not apply: 8 keys
    tape = engine.tape_of(manifest.Manifest().cell(CELL), 3)
    assert "skew" not in tape.params and tape.ring == 0


def test_the_manifest_gains_the_cell_and_nothing_moves(manifest_cut_after):
    data = manifest.Manifest().data
    assert len(data["configs"]) == 7 and len(data["workloads"]) == 8
    assert sum(w["chips"] == 4 for w in data["workloads"]) == 3
    cell = next(w for w in data["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "window1k", "sat-2p18-inproc", 1)
    assert len(cell["why"]) <= 200
    assert [m["name"] for m in data["end_to_end"]
            if CELL in m.get("workloads", ())] == ["events_per_s"]
    assert sorted(m["name"] for m in data["per_layer"]
                  if CELL in m["workloads"]) == sorted(PER_LAYER)
    assert len(data["per_layer"]) == 44         # no entry appended (ISSUE 44)
    # the cell's entries come after everything that was there: without
    # them the manifest is what its parent's was (tests/benchmark/conftest.py)
    before = manifest_cut_after(data, "pattern200k.sat", "pattern200k")
    cells = [w["name"] for w in data["workloads"]]
    assert cells.index(CELL) == cells.index("pattern200k.sat") + 1
    assert [w["name"] for w in before["workloads"]] + [CELL] == cells
    assert [c["name"] for c in before["configs"]] + ["window1k"] == \
        [c["name"] for c in data["configs"]]
    for old, new in zip(before["per_layer"] + before["end_to_end"],
                        data["per_layer"] + data["end_to_end"]):
        if CELL in new.get("workloads", ()):
            assert new["workloads"] == old["workloads"] + [CELL]
        else:
            assert new == old


# -- the reference ------------------------------------------------------------------

def test_window_mean_by_hand():
    got = window_avg.window_mean([4.0, 8.0, 6.0, 2.0], 3, [])
    assert got.tolist() == [4.0, 6.0, 6.0, 16.0 / 3.0]
    # `before`: only its last length - 1 prices are read
    got = window_avg.window_mean([6.0, 2.0], 3, [100.0, 4.0, 8.0])
    assert got.tolist() == [6.0, 16.0 / 3.0]
    assert window_avg.window_mean([5.0], 1, [7.0]).tolist() == [5.0]


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_the_reference_agrees_with_the_host_interpreter(seed):
    from siddhi_tpu import SiddhiManager
    cell = _cell()
    cell["config"]["annotations"] = ["@app:deviceWindows('never')"]
    tape = engine.tape_of(cell, seed)
    mgr = SiddhiManager()
    try:
        rt = mgr.create_app_runtime(engine.app_text(cell))
        assert rt.explain()["queries"]["q"]["path"] == "interpreter"
        got = []
        rt.add_batch_callback("Out", lambda b: got.append(
            (np.array(b.timestamps), np.array(b.columns["ap"]))))
        rt.start()
        codes = np.array([rt.strings.encode(f"K{i}") for i in range(8)],
                         np.int32)
        made = [tape.batch(i) for i in range(2)]
        for b in made:
            cut = slice(0, 1500)    # the interpreter goes event by event
            rt.input_handler("StockStream").send_batch(
                {"symbol": codes[b["sym_idx"][cut]], "price": b["price"][cut],
                 "volume": b["volume"][cut]}, b["ts"][cut])
        rt.flush()
    finally:
        mgr.shutdown()
    price = np.concatenate([b["price"][:1500] for b in made])
    want = window_avg.window_mean(price, 1000, [])
    ts = np.concatenate([t for t, _a in got])
    ap = np.concatenate([a for _t, a in got])
    assert np.array_equal(ts, np.concatenate([b["ts"][:1500] for b in made]))
    assert np.abs(ap - want).max() < 1e-9
    assert window_avg.values_off(ap, want) == 0


# -- the judge ----------------------------------------------------------------------

def _judged(tamper=None, n_batches=20, seed=5):
    """The checks had the program delivered what the reference owes for
    the rehearsal's tape, `tamper(i, ts, ap)`ed with on the way."""
    cell = _cell()
    tape = engine.tape_of(cell, seed)
    judge = window_avg.Judge(cell["config"], tape, seed)
    before = np.zeros(0)
    for i in range(n_batches):
        b = tape.batch(i)
        out = (b["ts"], window_avg.window_mean(b["price"], 1000, before))
        before = np.concatenate([before, b["price"]])[-999:]
        if tamper is not None:
            out = tamper(i, *out)
        if out is not None:
            judge.on_batch(SimpleNamespace(n=len(out[0]), timestamps=out[0],
                                           columns={"ap": out[1]}))
    return judge.judge(n_batches), judge


def _ulps_off(row, k):
    def tamper(i, ts, ap):
        if i == 0:
            ap = ap.copy()
            ap[row] += k * float(np.spacing(np.float32(ap[row])))
        return ts, ap
    return tamper


def _swapped(i, ts, ap):
    if i == 0:
        ts, ap = ts.copy(), ap.copy()
        ts[[10, 11]], ap[[10, 11]] = ts[[11, 10]], ap[[11, 10]]
    return ts, ap


@pytest.mark.parametrize("tamper,want", [
    (None, {}),
    (lambda i, ts, ap: None if i == 7 else (ts, ap),
     {"batches_with_wrong_row_count": 1}),
    (lambda i, ts, ap: (ts[:-1], ap[:-1]) if i == 0 else (ts, ap),
     {"batches_with_wrong_row_count": 1, "sampled_values_off": 4096}),
    (_ulps_off(2000, 8), {"sampled_values_off": 1}),
    (_ulps_off(2000, -8), {"sampled_values_off": 1}),
    (_ulps_off(2000, 1), {}),
    (_ulps_off(3, 1), {}),
    (_swapped, {"sampled_values_off": 2, "sampled_rows_out_of_order": 2}),
], ids=["sound", "a_dropped_batch", "a_row_short", "8_ulps_up", "8_ulps_down",
        "1_ulp", "1_ulp_while_the_window_fills", "two_rows_swapped"])
def test_what_turns_correct_false(tamper, want):
    checks, judge = _judged(tamper)
    assert [c["name"] for c in checks] == [
        "batches_with_wrong_row_count", "sampled_values_off",
        "sampled_rows_out_of_order", "nothing_to_compare"]
    assert all(c["limit"] == 0 for c in checks)
    assert {k: v for k, v in _by(checks).items() if v} == want
    assert compare.verdict(checks) == (not want)
    assert judge.detail["batches_counted"] == 20


def test_batch_0_and_a_seeded_one_in_sixteen_are_compared_whole():
    checks, judge = _judged(n_batches=40, seed=9)
    assert compare.verdict(checks)
    kept = sorted(judge._kept)
    assert kept[0] == 0 and len(kept) in (3, 4)     # 0, p, p + 16[, p + 32]
    assert all(i == 0 or i % 16 == judge._phase for i in kept)
    assert judge.detail["batches_compared_by_value"] == len(kept)
    assert judge.detail["rows_compared_by_value"] == len(kept) * 4096
    other = window_avg.Judge(judge.config, judge.tape, 10)
    again = window_avg.Judge(judge.config, judge.tape, 9)
    assert again._phase == judge._phase
    assert {other._phase, judge._phase} <= set(range(16))
    # nothing delivered at all: nothing to compare is its own check
    empty = window_avg.Judge(judge.config, judge.tape, 9)
    assert _by(empty.judge(0))["nothing_to_compare"] == 1


def test_the_old_prefix_difference_is_what_the_comparison_was_built_for():
    """A sum taken as the difference of two f32 prefixes over a whole
    2^18-event batch, in the program's place, is not correct."""
    cell = _cell(rehearse=False)
    tape = engine.tape_of(cell, 7)
    judge = window_avg.Judge(cell["config"], tape, 7)
    b = tape.batch(0)
    prefix = np.cumsum(b["price"].astype(np.float32), dtype=np.float32)
    g = np.arange(b["n"])
    left = np.maximum(g - 999, 0)
    total = prefix - np.where(left > 0, prefix[np.maximum(left - 1, 0)],
                              np.float32(0))
    ap = (total / np.minimum(g + 1, 1000).astype(np.float32)).astype(
        np.float64)
    judge.on_batch(SimpleNamespace(n=b["n"], timestamps=b["ts"],
                                   columns={"ap": ap}))
    checks = judge.judge(1)
    assert not compare.verdict(checks)
    assert _by(checks)["sampled_values_off"] > 10_000
    assert judge.detail["worst_value_ulps"] > 100


# -- the control --------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_lower_precision_in_the_programs_place_fails(seed):
    cell = _cell()
    sound = control.stand_in(cell, seed, 24, lower=False)
    assert compare.verdict(sound), sound
    lowered = control.stand_in(cell, seed, 24, lower=True)
    assert not compare.verdict(lowered)
    assert _by(lowered)["sampled_values_off"] > 4000
    assert _by(lowered)["batches_with_wrong_row_count"] == 0


# -- the roofline metric's file -----------------------------------------------------

def test_the_roofline_file_counts_the_work_by_the_reader_that_is_there():
    mf = manifest.Manifest()
    spec = mf.metric_spec("window_block_roofline")
    assert spec == {"layer": "kernel", "unit": "%", "source": "device_trace",
                    "reader": "roofline_fused", "kernel": "window_block",
                    "per": "bench:send_batch", "in_cols": 1, "out_words": 1}
    obs = {"cell": mf.cell(CELL), "device_kind": "TPU v5 lite",
           "events": 50 * 262144, "rows_delivered": 50 * 262144,
           "batch": 262144,
           "trace": {"busiest": "/device:TPU:0",
                     "devices": {"/device:TPU:0": {"busy_s": 2.0}},
                     "span_counts": {"bench:send_batch": 20}}}
    sent = 20 * 262144          # the traced interval's events; a row each
    assert kernels_fused.fused_block_bytes(sent, sent, 1, 1) == 4 * 2 * sent
    assert roofline_fused.read(spec, obs) == pytest.approx(
        100.0 * 4 * (sent + sent) / 819e9 / 2.0)
    # every other configuration's block is another metric's
    for w in mf.data["workloads"]:
        if w["config"] != "window1k":
            assert roofline_fused.read(
                spec, {**obs, "cell": mf.cell(w["name"])}) is None
    # and this cell is not the fused blocks'
    assert roofline_fused.read(mf.metric_spec("fused_block_roofline"),
                               obs) is None
    assert roofline_fused.read(spec, {**obs, "trace": None}) is None


# -- the rehearsal ------------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_is_correct_and_finds_the_cells_metrics(trace):
    r = run_cell(["--workload", CELL, "--seed", str(2 ** 31 + 44 + trace),
                  "--seconds", "1.5", "--trace", str(trace),
                  "--rehearse-cpu"])
    out = last_line(r)
    assert out["correct"] is True, out["compared"]
    assert set(out["compared"]) == {
        "batches_with_wrong_row_count", "sampled_values_off",
        "sampled_rows_out_of_order", "nothing_to_compare"}
    assert all(v == {"value": 0, "limit": 0} for v in out["compared"].values())
    assert "compiles_in_window 0 " in r.stdout
    assert "('device', 'window', None)" in r.stdout
    counts = out["counts"]
    assert counts["rows_delivered"] == counts["batches_counted"] * 4096
    assert counts["batches_compared_by_value"] >= 2
    assert counts["worst_value_ulps"] <= 1.0    # the CPU divides correctly
    assert counts["window"] == {
        "kind": "length", "length": 1000, "grouped": False,
        "sites": ["avg"], "T": 4096, "carry_capacity": 1024,
        "sum_form": "pair_prefix", "block": None,
        "carry_overflow_reruns": 0, "carry_grows": 0}
    if trace:       # every listed metric but the two device shares
        assert out["metrics_found"] == sorted(
            n for n in PER_LAYER if "share" not in n)
    else:
        assert out["metrics_found"] == ["events_per_s", "setup_s"]
