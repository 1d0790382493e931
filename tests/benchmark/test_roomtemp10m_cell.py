"""The cell `roomtemp10m.sat`: its files (the Query Guide's grouped sliding
time window, the configuration as ISSUE 49 gives it), its sensor tape, its
plain reference by hand and against the host interpreter, its judge (every
batch counted; batch 0, the first with a whole window ahead of it and a
seeded one in 4 compared by value; what turns
`correct` false and what does not), its control, the device path held to
the reference while the carry grows by count, the roofline metric's data
file read off a rehearsal's record, and that the CPU rehearsal is `correct`,
finds the cell's metrics and reports the plan's records.  The cell joins
test_rehearsal.py, test_span_metrics.py and test_manifest.py by being in
the manifest.  (The file's light cases come first: the tier-1 run hands the
files with the most cases out first.)"""
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`

import numpy as np
import pytest

from benchmark import compare, control, engine, kernels_fused, manifest
from benchmark.readers import roofline_fused
from benchmark.reference import window_group_avg as ref
from benchmark.tapes import temp
from test_rehearsal import last_line, run_cell

CELL, CONFIG = "roomtemp10m.sat", "roomtemp10m"
PER_LAYER = ["ingest_ms_per_batch", "freeze_ms_per_batch",
             "host_pack_ms_per_batch", "kernel_dispatch_ms_per_batch",
             "device_wait_ms_per_batch", "materialise_ms_per_batch",
             "h2d_bytes_per_event", "d2h_bytes_per_event",
             "kernel_busy_share", "device_idle_share", "compiles_in_window"]
CHECKS = ["batches_with_wrong_row_count", "sampled_values_off",
          "sampled_keys_off", "sampled_rows_out_of_order",
          "nothing_to_compare", "state_held_off"]
QUERY = ("from TempStream#window.time(10 min) select avg(temp) as avgTemp, "
         "roomNo, deviceID group by roomNo, deviceID insert into "
         "AvgTempStream")


def _cell(rehearse=True, **tape_params):
    cell = manifest.Manifest().cell(CELL)
    if rehearse:
        cell["config"] = manifest.rehearsed(cell["config"])
        cell["traffic"] = manifest.rehearsed(cell["traffic"])
    cell["config"]["tape_params"].update(tape_params)
    return cell


def _by(checks):
    return {c["name"]: c["value"] for c in checks}


# -- the files ----------------------------------------------------------------------

def test_the_app_is_the_guides_query_letter_for_letter():
    text = manifest.Manifest().cell(CELL)["app_text"]
    assert text.count("{source}") == text.count("{sink}") == 1
    assert text.replace("{source}", "").replace("{sink}", "") == (
        "define stream TempStream (deviceID long, roomNo int, temp double);\n"
        "@info(name='q') " + QUERY + ";\n")
    assert text.startswith("{source}define stream TempStream")


def test_the_configuration_is_as_the_issue_gives_it():
    cfg = manifest.Manifest().cell(CELL)["config"]
    assert cfg["source"] == "Siddhi 4.x Query Guide, 'Group By': " + QUERY
    assert len(cfg["source"]) <= 200
    # as window1k: the default spelled out; no devicePipeline, no geometry
    # and no capacity annotation
    assert cfg["annotations"] == ["@app:deviceMesh('never')",
                                  "@app:deviceWindows('auto')"]
    assert (cfg["stream"], cfg["out_stream"]) == ("TempStream",
                                                  "AvgTempStream")
    assert cfg["stream_cols"] == [["deviceID", "long"], ["roomNo", "int"],
                                  ["temp", "double"]]
    assert cfg["out_cols"] == [["avgTemp", "double"], ["roomNo", "int"],
                               ["deviceID", "long"]]
    assert cfg["stateful"] is True and cfg["tape"] == "temp"
    assert cfg["tape_params"] == {"keys": 2000, "rooms": 500, "dt_ms": 1,
                                  "temp_lo": 15.0, "temp_hi": 35.0,
                                  "temp_step": 0.25}
    assert cfg["query"] == {"duration_ms": 600_000,
                            "group_by": ["roomNo", "deviceID"]}
    assert cfg["reference"] == "window_group_avg"
    assert cfg["compare_one_batch_in"] == 4
    assert cfg["kernel"] == "window_group_block"
    assert cfg["expect"] == {"path": "device", "kind": "window",
                             "family": None, "sharded_over": 0}
    # no key of the file says what program may run: the judge holds the
    # `state` guarantee to the plan's own record, and says so there
    assert "requires" not in cfg
    assert "window_carry" in cfg["guarantees"]["state"]
    assert "state_held_off" in cfg["guarantees"]["state"]
    assert cfg["reduced"] == ["stream_events"] and "stream_events" in cfg
    assert set(cfg["guarantees"]) == {"rows", "values", "keys", "state",
                                      "delivery", "device_precision"}
    assert "ONE f32 division" in cfg["guarantees"]["values"]
    assert f"within {ref.VALUE_ULPS} f32 ulps" in cfg["guarantees"]["values"]
    assert ref.VALUE_ULPS == 3              # window_avg's limit and reason
    assert "~600,000" in cfg["guarantees"]["state"]
    rate = cfg["prebuild_events_per_s"]
    assert rate % 500_000 == 0 and 30 * rate * 28 <= 4e9
    told = " ".join(cfg["assumed"])
    for said in ("as recalled", "2,000 sensors in 500 rooms",
                 "1,000 events a second", "drawn uniformly",
                 "quarter degrees", "2^18-event columnar batches",
                 "skew stanza", "NOT applied", "prebuild_events_per_s",
                 "compare_one_batch_in 4 "):
        assert said in told, said
    # the window at the stated rate: 600,000 events, in a 2^20-entry carry
    tp = cfg["tape_params"]
    held = cfg["query"]["duration_ms"] // tp["dt_ms"]
    assert held == 600_000 and 2 ** 19 < held <= 2 ** 20
    # a group's window sum is exact in f32 on this tape, at any fill
    assert 4 * held / tp["keys"] * tp["temp_hi"] / tp["temp_step"] < 2 ** 24
    # the rehearsal thins the STREAM, never the query: a 4,096-event batch
    # spans more than the window
    small = manifest.rehearsed(cfg)
    assert small["query"] == cfg["query"]
    assert {k: v for k, v in small["tape_params"].items() if k != "dt_ms"} \
        == {k: v for k, v in tp.items() if k != "dt_ms"}
    assert 4096 * small["tape_params"]["dt_ms"] > cfg["query"]["duration_ms"]


def test_the_manifest_gains_the_cell_and_nothing_before_it_moves(
        manifest_cut_after):
    """Written so that the NEXT cell does not break it: positions by
    `index`, the manifest cut after this cell's own entries."""
    data = manifest.Manifest().data
    cells = [w["name"] for w in data["workloads"]]
    configs = [c["name"] for c in data["configs"]]
    cell = data["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "sat-2p18-inproc", 1)
    assert len(cell["why"]) <= 200
    entry = data["configs"][configs.index(CONFIG)]
    assert entry["file"] == "benchmark/configs/roomtemp10m.json"
    assert entry["reduced"] == ["stream_events"] and len(entry["why"]) <= 200
    assert entry["source"] == "Siddhi 4.x Query Guide, 'Group By': " + QUERY
    # one cell of this configuration, and four-chip cells stay three
    assert [w["name"] for w in data["workloads"]
            if w["config"] == CONFIG] == [CELL]
    here = manifest_cut_after(data, CELL, CONFIG)
    assert sum(w["chips"] == 4 for w in here["workloads"]) == 3
    assert [m["name"] for m in data["end_to_end"]
            if CELL in m.get("workloads", ())] == ["events_per_s"]
    assert sorted(m["name"] for m in data["per_layer"]
                  if CELL in m.get("workloads", ())) == sorted(PER_LAYER)
    # the lists window1k.sat is on, and no entry of its own (ISSUE 49: the
    # roofline entry is owed to the next `benchmark` issue)
    assert sorted(m["name"] for m in data["per_layer"]
                  if "window1k.sat" in m.get("workloads", ())) == \
        sorted(PER_LAYER)
    assert not any("window_group" in m["name"] for m in data["per_layer"])
    # appended after window1k.sat, and nothing before it moved: without
    # this cell's entries the manifest is what its parent's was
    assert cells.index(CELL) == cells.index("window1k.sat") + 1
    assert configs.index(CONFIG) == configs.index("window1k") + 1
    before = manifest_cut_after(data, "window1k.sat", "window1k")
    assert [w["name"] for w in before["workloads"]] + [CELL] == \
        [w["name"] for w in here["workloads"]]
    assert [c["name"] for c in before["configs"]] + [CONFIG] == \
        [c["name"] for c in here["configs"]]
    assert before["workloads"] == here["workloads"][:-1]
    assert before["configs"] == here["configs"][:-1]
    for old, new in zip(before["per_layer"] + before["end_to_end"],
                        here["per_layer"] + here["end_to_end"]):
        if CELL in new.get("workloads", ()):
            assert new["workloads"] == old["workloads"] + [CELL]
        else:
            assert new == old
    assert len(before["per_layer"]) == len(here["per_layer"])


# -- the tape -----------------------------------------------------------------------

def test_the_tape_is_the_fleet_the_configuration_states():
    cell = _cell(rehearse=False)
    tape = engine.tape_of(cell, 2 ** 31 + 5)
    # the traffic file hands the skew stanza to a tape of 2,000 keys; it
    # is accepted and not applied: no sensor is raised to 352 events
    assert tape.params["skew"]["events"] == 352 and tape.ring == 0
    b = tape.batch(0)
    assert b["n"] == 262144 and set(b) == {"dev_idx", "deviceID", "roomNo",
                                           "temp", "ts", "n"}
    assert b["deviceID"].dtype == np.int64 and b["roomNo"].dtype == np.int32
    assert b["temp"].dtype == np.float64 and b["ts"].dtype == np.int64
    assert np.array_equal(b["deviceID"], 100_000 + b["dev_idx"])
    assert np.array_equal(b["roomNo"], b["dev_idx"] // 4)
    assert (b["dev_idx"].min(), b["dev_idx"].max()) == (0, 1999)
    assert (b["roomNo"].min(), b["roomNo"].max()) == (0, 499)
    counts = np.bincount(b["dev_idx"], minlength=2000)
    assert counts.max() < 200 and counts.min() > 75     # ~131 each: uniform
    assert np.array_equal(b["temp"] * 4, np.rint(b["temp"] * 4))
    assert (b["temp"].min(), b["temp"].max()) == (15.0, 35.0)
    assert np.array_equal(b["temp"], b["temp"].astype(np.float32))
    # a timestamp names its event; any batch can be made alone
    assert np.array_equal(b["ts"], temp.TS0 + np.arange(262144))
    later = tape.batch(3)
    assert later["ts"][0] == temp.TS0 + 3 * 262144
    assert np.array_equal(tape.event_index(later["ts"][:3]),
                          3 * 262144 + np.arange(3))
    again = engine.tape_of(cell, 2 ** 31 + 5).batch(3)
    assert all(np.array_equal(later[k], again[k]) for k in later)
    other = engine.tape_of(cell, 2 ** 31 + 6).batch(3)
    assert not np.array_equal(later["dev_idx"], other["dev_idx"])


def test_the_tape_module_gives_the_five_names_without_a_string_key():
    assert temp.EVENT_TIME_COLUMNS == ()
    names = temp.symbol_names(2000)
    assert len(names) == 2000 and names[7] == 7
    tape = temp.Tape({"keys": 8, "rooms": 2, "batch": 16, "dt_ms": 5,
                      "temp_lo": 15.0, "temp_hi": 35.0, "temp_step": 0.25},
                     3)
    b = tape.batch(1)
    cols, ts = temp.feed_columns(b, np.arange(8, dtype=np.int32) + 100)
    assert list(cols) == ["deviceID", "roomNo", "temp"] and ts is b["ts"]
    assert np.array_equal(cols["deviceID"], b["deviceID"])    # codes unread
    kept = temp.rows(b, np.array([1, 3]), None)
    assert np.array_equal(kept["roomNo"], b["roomNo"][[1, 3]])
    assert set(b["roomNo"]) <= {0, 1} and b["ts"][0] == temp.TS0 + 16 * 5
    with pytest.raises(ValueError, match="no ring"):
        temp.Tape({**tape.params, "ring": 4}, 3)
    with pytest.raises(ValueError, match="multiple of rooms"):
        temp.Tape({**tape.params, "rooms": 3}, 3)
    assert "What a tape module gives" in temp.__doc__


# -- the reference ------------------------------------------------------------------

def test_group_window_mean_by_hand():
    """A dozen events of three devices in two rooms, a 10 ms window."""
    #       j:   0    1    2    3    4    5    6    7    8    9   10   11
    room = [1,   1,   2,   1,   2,   1,   1,   2,   1,   1,   2,   1]
    dev = [7,    8,   9,   7,   9,   8,   7,   9,   7,   8,   9,   7]
    t = [0,      2,   3,   4,   9,  12,  13,  13,  14,  22,  23,  30]
    v = [20.0, 30.0, 16.0, 22.0, 18.0, 32.0, 24.0, 20.0, 26.0, 28.0, 17.0,
         15.0]
    got = ref.group_window_mean([room, dev], v, t, 10)
    want = [20.0,       # (1, 7): event 0 alone
            30.0,       # (1, 8): 1
            16.0,       # (2, 9): 2
            21.0,       # (1, 7): 0, 3
            17.0,       # (2, 9): 2, 4
            32.0,       # (1, 8): 5; event 1 left at 12 (2 + 10 <= 12)
            23.0,       # (1, 7): 3, 6; event 0 left at 10
            19.0,       # (2, 9): 4, 7; event 2 left at 13 (3 + 10 <= 13)
            25.0,       # (1, 7): 6, 8; event 3 left at 14 (4 + 10 <= 14)
            28.0,       # (1, 8): 9; event 5 left at 22
            17.0,       # (2, 9): 10; event 7 left at 23
            15.0]       # (1, 7): 11
    assert got.tolist() == want
    # only the last n rows, the events ahead handed in with them
    assert ref.group_window_mean([room, dev], v, t, 10, 4).tolist() == \
        want[-4:]
    # grouped by BOTH keys: the same deviceID in another room is another group
    both = ref.group_window_mean([[1, 2, 1], [7, 7, 7]], [10.0, 20.0, 30.0],
                                 [0, 1, 2], 10)
    assert both.tolist() == [10.0, 20.0, 20.0]
    one = ref.group_window_mean([[7, 7, 7]], [10.0, 20.0, 30.0], [0, 1, 2], 10)
    assert one.tolist() == [10.0, 15.0, 20.0]
    # ties in time: a window ends at its own event, whatever arrives later
    tied = ref.group_window_mean([[7, 7, 7]], [10.0, 20.0, 60.0], [5, 5, 5],
                                 10)
    assert tied.tolist() == [10.0, 15.0, 30.0]
    assert ref.group_window_mean([[], []], [], [], 10).tolist() == []


def test_values_off_counts_past_three_f32_ulps():
    want = np.array([20.0, 25.125, 33.3333333])
    ulp = np.spacing(want.astype(np.float32)).astype(np.float64)
    assert ref.values_off(want, want) == 0
    assert ref.values_off(want + 3 * ulp, want) == 0
    assert ref.values_off(want - 3 * ulp, want) == 0
    assert ref.values_off(want + 4 * ulp, want) == 3
    assert ref.values_off(want + [0, 4 * ulp[1], 0], want) == 1
    assert ref.values_off([np.nan, 25.125, 33.3333333], want) == 1


# -- the judge ----------------------------------------------------------------------

def _owed(cell, tape, n_batches):
    """(ts, avgTemp, roomNo, deviceID) the query owes, batch by batch."""
    q = cell["config"]["query"]
    made = [tape.batch(i) for i in range(n_batches)]
    keys, temps, ts = ref._joined(made, q["group_by"])
    mean = ref.group_window_mean(keys, temps, ts, q["duration_ms"])
    n = made[0]["n"]
    return [(b["ts"], mean[i * n:(i + 1) * n], b["roomNo"], b["deviceID"])
            for i, b in enumerate(made)]


def _judged(tamper=None, n_batches=20, seed=5):
    """The checks had the program delivered what the reference owes for
    the rehearsal's tape, `tamper(i, ts, avg, room, dev)`ed with on the
    way."""
    cell = _cell()
    tape = engine.tape_of(cell, seed)
    judge = ref.Judge(cell["config"], tape, seed)
    for i, out in enumerate(_owed(cell, tape, n_batches)):
        if tamper is not None:
            out = tamper(i, *out)
        if out is not None:
            judge.on_batch(SimpleNamespace(
                n=len(out[0]), timestamps=out[0],
                columns={"avgTemp": out[1], "roomNo": out[2],
                         "deviceID": out[3]}))
    return judge.judge(n_batches), judge


def _ulps_off(row, k):
    def tamper(i, ts, avg, room, dev):
        if i == 0:
            avg = avg.copy()
            avg[row] += k * float(np.spacing(np.float32(avg[row])))
        return ts, avg, room, dev
    return tamper


def _rows_swapped(i, ts, avg, room, dev):
    if i == 0:
        ts, avg, room, dev = (a.copy() for a in (ts, avg, room, dev))
        for a in (ts, avg, room, dev):
            a[[10, 11]] = a[[11, 10]]
    return ts, avg, room, dev


def _key_swapped(i, ts, avg, room, dev):
    """Row 10 of batch 0 says another sensor of its own room."""
    if i == 0:
        dev = dev.copy()
        dev[10] += 1 if (dev[10] - temp.DEVICE_ID0) % 4 < 3 else -1
    return ts, avg, room, dev


def _room_off(i, ts, avg, room, dev):
    if i == 0:
        room = room.copy()
        room[10] += 1
    return ts, avg, room, dev


def _batches_out_of_order(i, ts, avg, room, dev):
    """Batch 0's rows under batch 1's timestamps and the other way."""
    return (ts + 4096 * 200 * (1 if i == 0 else -1 if i == 1 else 0),
            avg, room, dev)


@pytest.mark.parametrize("tamper,want", [
    (None, {}),
    (lambda i, *out: None if i == 7 else out,
     {"batches_with_wrong_row_count": 1}),
    (lambda i, *out: tuple(a[:-1] for a in out) if i == 0 else out,
     {"batches_with_wrong_row_count": 1, "sampled_values_off": 4096}),
    (_ulps_off(2000, 4), {"sampled_values_off": 1}),
    (_ulps_off(2000, -4), {"sampled_values_off": 1}),
    (_ulps_off(2000, 3), {}),
    (_ulps_off(3, 1), {}),
    (_key_swapped, {"sampled_keys_off": 1}),
    (_room_off, {"sampled_keys_off": 1}),
    (_rows_swapped, {"sampled_rows_out_of_order": 2}),
    (_batches_out_of_order, {"sampled_keys_off": 4000,
                             "sampled_values_off": 4000}),
], ids=["sound", "a_dropped_batch", "a_dropped_row", "4_ulps_up",
        "4_ulps_down", "3_ulps", "1_ulp_while_the_window_fills",
        "a_swapped_key", "a_room_off", "two_rows_swapped",
        "two_batches_out_of_order"])
def test_what_turns_correct_false(tamper, want):
    checks, judge = _judged(tamper)
    assert [c["name"] for c in checks] == CHECKS
    assert all(c["limit"] == 0 for c in checks)
    got = {k: v for k, v in _by(checks).items() if v}
    if tamper in (_rows_swapped, _batches_out_of_order):
        # out of order shows by time or by key, and by value besides: at
        # least what `want` says
        assert all(got.get(k, 0) >= v for k, v in want.items()), got
        assert not compare.verdict(checks)
    else:
        assert got == want
        assert compare.verdict(checks) == (not want)
    assert judge.detail["batches_counted"] == 20


def _bound(records, seed=1):
    """A judge of the rehearsal's tape bound to a runtime whose one query
    explains itself with `records` beside `window`."""
    cell = _cell()
    entry = {"path": "device", "kind": "window",
             "window": {"kind": "time"}, "window_step": {}}
    judge = ref.Judge(cell["config"], engine.tape_of(cell, seed), seed)
    judge.bind(SimpleNamespace(
        explain=lambda: {"queries": {"q": {**entry, **records}}}))
    return judge


def test_a_plan_that_says_nothing_of_its_carry_is_not_run():
    """The `state` guarantee is held to the plan's own record: a program
    that gives none (PR 47 and before, whose carry would double eleven
    times at this size) is turned away before anything is sent, with a
    non-zero exit and the reason."""
    with pytest.raises(SystemExit) as refused:
        _bound({})
    said = str(refused.value)
    assert "'window_carry'" in said and "nothing was run" in said
    assert "last 600000 ms on the device" in said   # what it cannot hold
    assert refused.value.code != 0


@pytest.mark.parametrize("held,n_batches,off", [
    (3000, 5, 0), (3000, 1, 0), (2999, 5, 1), (3001, 5, 1), (4096, 5, 1096),
    (0, 5, 3000), (0, 0, 0), (7, 0, 7),
])
def test_the_state_guarantee_is_held_to_the_carrys_own_count(held, n_batches,
                                                             off):
    """The rehearsal's 4,096-event batch spans more than the window: 3,000
    events are in it once any batch is in.  What the plan says it held
    after the last step misses that by `off` entries."""
    carry = {"capacity": 4096, "held": held, "held_max": max(held, 3000),
             "grows": 1, "reruns": 1}
    judge = _bound({"window_carry": carry})
    assert judge.held(n_batches) == (3000 if n_batches else 0)
    assert judge.state_off(n_batches) == off
    checks = _by(judge.judge(n_batches))
    assert checks["state_held_off"] == off
    assert judge.detail["window_carry"] == carry
    assert judge.detail["window"] == {"kind": "time"}
    assert judge.detail["events_in_window"] == judge.held(n_batches)


def test_the_window_fills_over_its_first_batches_at_the_cells_pace():
    """At the cell's own pace (dt_ms 1, 2^18-event batches, not made here:
    the count is of timestamps alone) a window holds what has arrived
    until 600,000 have: 2.3 batches in."""
    cell = _cell(rehearse=False)
    judge = ref.Judge(cell["config"], SimpleNamespace(
        params={**cell["config"]["tape_params"], "batch": 2 ** 18},
        batch=lambda i: {"ts": temp.TS0 + np.arange(
            i * 2 ** 18, (i + 1) * 2 ** 18, dtype=np.int64)}), 1)
    assert [judge.held(n) for n in (0, 1, 2, 3, 4, 16)] == [
        0, 262_144, 524_288, 600_000, 600_000, 600_000]
    assert judge.state_off(16) == 0         # nothing bound: nothing held to


def test_batch_0_the_first_full_window_and_a_seeded_one_in_four():
    checks, judge = _judged(n_batches=40, seed=9)
    assert compare.verdict(checks)
    kept = sorted(judge._kept)
    # a rehearsal's batch spans more than the window: batch 1 is the first
    # with a whole window of stream ahead of it
    assert judge.batches_ahead == 1 and kept[:2] == [0, 1]
    assert len(kept) in (11, 12)            # 0, 1 and p, p + 4, ..., p + 36
    assert all(i in (0, 1) or i % 4 == judge._phase for i in kept)
    assert judge.detail["batches_compared_by_value"] == len(kept)
    assert judge.detail["rows_compared_by_value"] == len(kept) * 4096
    assert ref.Judge(judge.config, judge.tape, 9)._phase == judge._phase
    assert {ref.Judge(judge.config, judge.tape, 10)._phase,
            judge._phase} <= set(range(4))
    # nothing delivered at all: nothing to compare is its own check
    empty = ref.Judge(judge.config, judge.tape, 9)
    assert _by(empty.judge(0))["nothing_to_compare"] == 1


@pytest.mark.parametrize("seed", range(2 ** 31, 2 ** 31 + 16))
def test_every_seed_compares_a_batch_whose_windows_have_lost_events(seed):
    """At the cell's own pace (2^18-event batches of 262 s, a window of
    600) the window fills through batches 0-2 and batch 3 is the first
    whose EVERY row has a left edge that events have left: whatever the
    seed's phase, and however few batches a run's window takes after the
    four of warm-up, a batch at or after it is compared by value, so the
    clock's search and the rank of a group's first member are."""
    full = _cell(rehearse=False)["config"]
    judge = ref.Judge(full, SimpleNamespace(
        params={**full["tape_params"], "batch": 262144}), seed)
    assert judge.batches_ahead == 3
    span_ms = 262144 * full["tape_params"]["dt_ms"]
    for n_batches in (5, 8, 16):            # warm-up's four and 1, 4, 12
        sampled = [i for i in range(n_batches) if judge.sampled(i)]
        assert sampled[0] == 0 and 3 in sampled
        # the first event of a batch at or after 3 is later than a window
        assert all(i * span_ms >= full["query"]["duration_ms"]
                   for i in sampled if i >= 3)


def test_a_sampled_batch_is_held_to_the_whole_window_ahead_of_it():
    """At the cell's own pace the window spans 2.3 batches: the judge makes
    the three batches ahead of a sampled one again from the tape."""
    full = _cell(rehearse=False)["config"]
    big = ref.Judge(full, SimpleNamespace(
        params={**full["tape_params"], "batch": 262144}), 1)
    assert big.batches_ahead == 3
    # at a small size: dt_ms 50 on 4,096-event batches, 2.9 batches a window
    cell = _cell(dt_ms=50)
    tape = engine.tape_of(cell, 11)
    judge = ref.Judge(cell["config"], tape, 11)
    assert judge.batches_ahead == 3
    owed = _owed(cell, tape, 6)
    assert np.array_equal(judge.owed(5), owed[5][1])
    assert np.array_equal(judge.owed(1), owed[1][1])
    # and the window really reaches that far back: without the batches
    # ahead the means differ
    alone = ref.group_window_mean(
        [tape.batch(5)[k] for k in ("roomNo", "deviceID")],
        tape.batch(5)["temp"], tape.batch(5)["ts"], 600_000)
    assert np.count_nonzero(alone != owed[5][1]) > 2000


# -- the control --------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_lower_precision_in_the_programs_place_fails(seed):
    cell = _cell()
    sound = control.stand_in(cell, seed, 24, lower=False)
    assert compare.verdict(sound), sound
    lowered = control.stand_in(cell, seed, 24, lower=True)
    assert not compare.verdict(lowered)
    # a rehearsal's window holds ~1.5 events a sensor, and the mean of ONE
    # quarter degree is exact in bfloat16: a quarter of the rows are off
    # there (at the cell's own size, ~300 a sensor, nearly every row)
    assert _by(lowered)["sampled_values_off"] > 1000
    assert {k: v for k, v in _by(lowered).items()
            if k != "sampled_values_off"} == {
        "batches_with_wrong_row_count": 0, "sampled_keys_off": 0,
        "sampled_rows_out_of_order": 0, "nothing_to_compare": 0,
        "state_held_off": 0}            # no program bound: no state to hold


def test_the_tapes_quarter_degrees_are_exact_in_bfloat16():
    """Why `stand_in` lowers the mean and not the inputs alone."""
    from ml_dtypes import bfloat16
    grid = np.arange(15.0, 35.25, 0.25)
    assert np.array_equal(grid.astype(bfloat16).astype(np.float64), grid)


# -- the roofline metric's file -----------------------------------------------------

SPEC = {"layer": "kernel", "unit": "%", "source": "device_trace",
        "reader": "roofline_fused", "kernel": "window_group_block",
        "per": "bench:send_batch", "in_cols": 5, "out_words": 4}


def test_the_roofline_file_counts_the_work_by_the_reader_that_is_there():
    mf = manifest.Manifest()
    spec = mf.metric_spec("window_group_block_roofline")
    assert spec == SPEC
    # temp, roomNo, the two words of deviceID, the timestamp offset in;
    # avgTemp, roomNo, the two words of deviceID out
    cfg = mf.cell(CELL)["config"]
    words = {"long": 2, "int": 1, "double": 1}      # DOUBLE travels as f32
    assert spec["in_cols"] == sum(words[t] for _n, t in cfg["stream_cols"]) + 1
    assert spec["out_words"] == sum(words[t] for _n, t in cfg["out_cols"])
    obs = {"cell": mf.cell(CELL), "device_kind": "TPU v5 lite",
           "events": 20 * 262144, "rows_delivered": 20 * 262144,
           "batch": 262144,
           "trace": {"busiest": "/device:TPU:0",
                     "devices": {"/device:TPU:0": {"busy_s": 3.8}},
                     "span_counts": {"bench:send_batch": 3}}}
    sent = 3 * 262144           # the traced interval's events; a row each
    assert kernels_fused.fused_block_bytes(sent, sent, 5, 4) == 4 * 9 * sent
    assert roofline_fused.read(spec, obs) == pytest.approx(
        100.0 * 4 * 9 * sent / 819e9 / 3.8)
    # every other configuration's block is another metric's
    for w in mf.data["workloads"]:
        if w["config"] != CONFIG:
            assert roofline_fused.read(
                spec, {**obs, "cell": mf.cell(w["name"])}) is None
    assert roofline_fused.read(mf.metric_spec("window_block_roofline"),
                               obs) is None
    assert roofline_fused.read(spec, {**obs, "trace": None}) is None


# -- the device path, the carry growing by count ------------------------------------

def _drive(cell, seed, n_batches, annotations=None):
    """The app through SiddhiManager -> send_batch -> batch callback, as
    the driver feeds it; returns (checks, judge, explain entry)."""
    from siddhi_tpu import SiddhiManager
    if annotations is not None:
        cell["config"]["annotations"] = annotations
    tape = engine.tape_of(cell, seed)
    judge = ref.Judge(cell["config"], tape, seed)
    mgr = SiddhiManager()
    try:
        rt = mgr.create_app_runtime(engine.app_text(cell))
        if annotations is None:     # the interpreter has no device records
            judge.bind(rt)
        rt.add_batch_callback("AvgTempStream", judge.on_batch)
        rt.start()
        handler = rt.input_handler("TempStream")
        for i in range(n_batches):
            handler.send_batch(*temp.feed_columns(tape.batch(i)))
        rt.flush()
        entry = rt.explain()["queries"]["q"]
        placement = engine.check_placement(rt, cell, "cpu") \
            if annotations is None else None
        checks = judge.judge(n_batches)
    finally:
        mgr.shutdown()
    return checks, judge, entry, placement


def test_the_device_path_equals_the_reference_while_the_carry_grows():
    """dt_ms 50: the window holds 12,000 events, 2.9 batches of 4,096.  The
    carry goes 1,024 -> 4,096 -> 8,192 -> 16,384 as the steps of batches 0,
    1 and 2 say 4,096, 8,192 and 12,000: three overflows, each grown to the
    count the step's word carried, each a re-run (the cell's own three, at
    1/64 of its size), and every sampled row is held to the reference
    across the grows."""
    grows = reruns = 3
    cell = _cell(dt_ms=50)
    cell["config"]["compare_one_batch_in"] = 2      # batches 1 or 2 sampled
    checks, judge, entry, placement = _drive(cell, 2 ** 31 + 48, 8)
    assert compare.verdict(checks), checks
    assert placement["queries"] == {"q": ("device", "window", None)}
    assert judge.detail["batches_compared_by_value"] >= 4
    assert judge.detail["rows_compared_by_value"] >= 4 * 4096
    assert judge.detail["worst_value_ulps"] <= 1.0  # the CPU divides correctly
    assert entry["window"]["carry_capacity"] == 16384
    assert entry["window"]["carry_grows"] == grows
    assert entry["window"]["carry_overflow_reruns"] == reruns
    assert entry["window_carry"] == {
        "capacity": 16384, "held": 12000, "held_max": 12000, "grows": grows,
        "reruns": reruns}
    assert judge.detail["window_carry"] == entry["window_carry"]
    assert entry["window_step"] == {"left_edge": "search",
                                    "prefix_read": "segmented",
                                    "compaction": "identity"}


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_the_reference_agrees_with_the_host_interpreter(seed):
    """`@app:deviceWindows('never')` and the playback clock (the events'
    own timestamps, as the device plan reads a time window): the host
    interpreter, event by event, owes what the reference does.  dt_ms 2000:
    300 events a window, ~1 a group in it at 2,000 sensors, so 40 sensors."""
    cell = _cell(dt_ms=2000, keys=40, rooms=10)
    cell["traffic"]["batch"] = 700
    cell["config"]["compare_one_batch_in"] = 1
    checks, judge, entry, _p = _drive(
        cell, seed, 2, ["@app:deviceWindows('never')", "@app:playback"])
    assert entry["path"] == "interpreter"
    assert compare.verdict(checks), checks
    assert judge.detail["rows_compared_by_value"] == 1400
    assert judge.detail["worst_value_ulps"] < 1e-6      # float64 both


# -- the rehearsal ------------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_is_correct_and_finds_the_cells_metrics(trace):
    r = run_cell(["--workload", CELL, "--seed", str(2 ** 31 + 48 + trace),
                  "--seconds", "1.5", "--trace", str(trace),
                  "--rehearse-cpu"])
    out = last_line(r)
    assert out["correct"] is True, out["compared"]
    assert list(out["compared"]) == CHECKS
    assert all(v == {"value": 0, "limit": 0} for v in out["compared"].values())
    assert "compiles_in_window 0 " in r.stdout
    assert "('device', 'window', None)" in r.stdout
    assert "tape_batches_built_in_window 0" in r.stdout
    counts = out["counts"]
    assert counts["rows_delivered"] == counts["batches_counted"] * 4096
    assert counts["batches_compared_by_value"] >= 2
    assert counts["worst_value_ulps"] <= 1.0    # the CPU divides correctly
    # a 4,096-event batch spans more than the window: 3,000 events held,
    # the carry grown ONCE, 1,024 -> 4,096, by the count of batch 0's step
    assert counts["window"] == {
        "kind": "time", "duration_ms": 600_000, "grouped": True,
        "sites": ["avg"], "T": 4096, "carry_capacity": 4096,
        "sum_form": "pair_prefix", "block": None,
        "carry_overflow_reruns": 1, "carry_grows": 1}
    assert counts["window_carry"] == {
        "capacity": 4096, "held": 3000, "held_max": 3000, "grows": 1,
        "reruns": 1}
    if trace:       # every listed metric but the two device shares
        assert out["metrics_found"] == sorted(
            n for n in PER_LAYER if "share" not in n)
        # and the roofline file reads off the record the traced run leaves:
        # on the CPU there is no device plane, so nothing to read
        assert "window_group_block_roofline" not in out["metrics_found"]
    else:
        assert out["metrics_found"] == ["events_per_s", "setup_s"]
