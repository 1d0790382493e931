"""Plain reference for the Siddhi Query Guide's grouped sliding window,

    from TempStream#window.time(D) select avg(temp) as avgTemp, roomNo,
    deviceID group by roomNo, deviceID insert into AvgTempStream

one row an event, in arrival order, stamped with its event's timestamp:
`avgTemp` the mean of the temperatures of the event's own (roomNo, deviceID)
group over the events that arrived with a timestamp in (t - D, t], up to and
including the event itself (an event leaves the window when `ts + D <= now`),
`roomNo` and `deviceID` the event's own.  Float64 numpy, independent of
`siddhi_tpu`: per group one prefix sum and one `searchsorted` over the
group's own timestamps.  `temp` may be handed in any float type: the control
(benchmark/control.py) runs this same code in bfloat16."""
from types import SimpleNamespace

import numpy as np

from benchmark import compare
# How far a delivered mean may lie from the exact one, in f32 ulps of the
# exact one: `window_avg`'s limit, 3, for its reason, and its count of the
# rows beyond it.  The tape's temperatures sit on a quarter-degree grid in
# [15, 35]: a group's window holds ~300 events (a few hundred at most) of at
# most 140 quarter steps, far under 2^24, so a sum taken over the WINDOW is
# exact in f32 (the configuration's guarantee) and only the one division
# rounds; a TPU v5e's f32 division lies at most 2.26 ulps from the exact
# quotient (my chip run, PR 44; PERF.md section 6).
from benchmark.reference.window_avg import VALUE_ULPS, values_off
from benchmark.tapes.temp import TS0

# Where the program says what its carry holds on the device, in
# `rt.explain()["queries"][q]`: {"capacity", "held" (the entries the last
# step kept), "held_max", ...}.  The configuration's `state` guarantee is
# held to it (`Judge.bind`, `Judge.state_off`).
STATE_RECORD = "window_carry"


def group_window_mean(keys, temp, ts, duration_ms: int, n_last: int = None):
    """The mean, for each of the last `n_last` events (all, if None), of
    the temperatures of its own group's events with a timestamp in
    (t - duration_ms, t] up to and including it.  `keys` (the group-by
    columns, first key first), `temp`, `ts`: one entry an event, in arrival
    order, timestamps nondecreasing.  A stable sort by the keys keeps
    arrival order inside a group (so an event's window ends at the event
    itself, whatever arrives later at the same instant); per group one
    float64 prefix sum (exact on the tape's grid) and one searchsorted."""
    keys = [np.asarray(k) for k in keys]
    temp = np.asarray(temp).astype(np.float64)
    ts = np.asarray(ts, np.int64)
    n = len(ts)
    out = np.zeros(n)
    order = np.lexsort(keys[::-1])
    new_group = np.zeros(max(n - 1, 0), bool)
    for k in keys:
        new_group |= np.diff(k[order]) != 0
    for idx in np.split(order, np.flatnonzero(new_group) + 1):
        t = ts[idx]
        prefix = np.concatenate([[0.0], np.cumsum(temp[idx])])
        first = np.searchsorted(t, t - duration_ms, side="right")
        end = np.arange(len(idx)) + 1
        out[idx] = (prefix[end] - prefix[first]) / (end - first)
    return out if n_last is None else out[n - n_last:]


def _joined(batches: list, group_by: list) -> tuple:
    """(keys, temp, ts) of `batches` end to end."""
    col = lambda k: np.concatenate([b[k] for b in batches])
    return [col(k) for k in group_by], col("temp"), col("ts")


def stand_in(judge: "Judge", batches: list, cast) -> None:
    """Put this reference in the program's place: hand `judge` what the
    query owes for `batches`, computed in `cast`'s type.  The tape's
    quarter degrees in [15, 35] are at most 140 quarter steps, which the 8
    bits of a bfloat16 hold exactly, so lowering the INPUTS alone would
    change nothing; what the lower precision rounds is what is computed
    from them, so the mean is delivered as `cast` leaves it."""
    query = judge.config["query"]
    ahead = judge.batches_ahead
    for i, b in enumerate(batches):
        keys, temp, ts = _joined(batches[max(0, i - ahead):i + 1],
                                 query["group_by"])
        temp = np.asarray(cast(temp)).astype(np.float64)
        mean = group_window_mean(keys, temp, ts, int(query["duration_ms"]),
                                 b["n"])
        judge.on_batch(SimpleNamespace(
            n=b["n"], timestamps=b["ts"],
            columns={"avgTemp": np.asarray(cast(mean)).astype(np.float64),
                     "roomNo": b["roomNo"], "deviceID": b["deviceID"]}))


class Judge:
    """Counts the rows delivered for EVERY input batch of the window (one
    row an event is owed), keeps three kinds of batch whole: batch 0 (where
    the window fills), the first batch with `duration_ms` of stream ahead of
    it (every row of it has a left edge where events have LEFT, so the
    clock's search and the rank of a group's first member are held by
    value on every seed, however short the run), and a seeded one batch in
    `compare_one_batch_in`; and once the window has closed holds the kept
    rows to `group_window_mean` over the same events: the sampled batch and
    the `duration_ms` of stream ahead of it, made again from the tape.  (A
    window carries millions of rows: every row cannot be kept, every batch
    can be counted.)  And the `state` guarantee, every event of the last
    `duration_ms` on the device: what the program says its carry held after
    the run's last step (`STATE_RECORD`) is held to the count the tape
    gives (`state_held_off`).

    `bind` does not run a plan that reports no such record.  Said plainly:
    that refusal is ALSO what ends, in seconds and with exit code 1, the
    run of this cell by a program from before the record existed (PR 47's
    tree, the parent of the PR that brought the cell).  Such a program
    doubles a `time` window's carry from 1,024 entries: eleven capacities
    to 2^20, each a compile of about two minutes at this size, over 21
    minutes of set-up; it neither fails nor hangs, it compiles, and the
    driver's check, which must see a new cell's parent give a result or
    FAIL CLEANLY, stopped it at 1,200 s and refused PR 48 for it.  Do not
    take the refusal out in review without something that does the same:
    a set-up limit in the harness would (a `benchmark` issue's: PERF.md
    section 7), and `state_held_off` can stay when `bind`'s refusal
    goes."""

    def __init__(self, config: dict, tape, seed: int):
        self.config, self.tape, self.seed = config, tape, int(seed)
        tp = tape.params
        self._span_ms = int(tp["batch"]) * int(tp["dt_ms"])
        # whole batches that can hold an event of a batch's first window
        self.batches_ahead = -(-int(config["query"]["duration_ms"])
                               // self._span_ms)
        self._every = max(1, int(config["compare_one_batch_in"]))
        self._phase = int(np.random.default_rng(
            [self.seed, 0xC0FFEE]).integers(0, self._every))
        self._counts = {}
        self._kept = {}
        self._rt = None
        self.rows = 0
        self.detail = {}

    def sampled(self, i: int) -> bool:
        return i in (0, self.batches_ahead) \
            or i % self._every == self._phase

    def bind(self, rt) -> None:
        """The driver hands over the runtime, before anything is sent.  The
        plan's records are printed with the run, and `STATE_RECORD` is what
        the `state` guarantee is held to.  A plan that gives no such record
        cannot be held to that guarantee: its run is void before it starts,
        as a run whose placement cannot be proven is
        (`engine.check_placement`).  So ends, cleanly, the run of a program
        that says nothing of its carry and doubles it from 1,024 entries:
        eleven compiles of two minutes each at this deployment's size, a
        set-up that no run's time holds (the class's docstring; PERF.md 7.17)."""
        self._rt = rt
        silent = [q for q, entry in rt.explain()["queries"].items()
                  if STATE_RECORD not in entry]
        if silent:
            raise SystemExit(
                f"window_group_avg: the plan of {silent} reports no "
                f"{STATE_RECORD!r} in rt.explain(), so the configuration's "
                f"`state` guarantee (every event of the last "
                f"{self.config['query']['duration_ms']} ms on the device) "
                f"cannot be held to anything: nothing was run")

    def on_batch(self, b) -> None:
        """Batch callback of the engine (in the timed window)."""
        if not b.n:
            return
        self.rows += b.n
        first = (int(b.timestamps[0]) - TS0) // self._span_ms
        last = (int(b.timestamps[-1]) - TS0) // self._span_ms
        if first != last:           # an output batch astride two inputs
            self._counts[-1] = self._counts.get(-1, 0) + b.n
            return
        self._counts[first] = self._counts.get(first, 0) + b.n
        if self.sampled(first):
            self._kept.setdefault(first, []).append(tuple(
                np.array(c, t) for c, t in (
                    (b.timestamps, np.int64),
                    (b.columns["avgTemp"], np.float64),
                    (b.columns["roomNo"], np.int64),
                    (b.columns["deviceID"], np.int64))))

    def owed(self, i: int) -> np.ndarray:
        """What the query owes for batch `i` of the tape: its `avgTemp`."""
        made = [self.tape.batch(j)
                for j in range(max(0, i - self.batches_ahead), i + 1)]
        query = self.config["query"]
        return group_window_mean(*_joined(made, query["group_by"]),
                                 int(query["duration_ms"]), made[-1]["n"])

    def held(self, n_batches: int) -> int:
        """How many events a window holds once batch `n_batches - 1` is in:
        those of the tape with a timestamp in (t - duration_ms, t], t the
        last event's."""
        if not n_batches:
            return 0
        ts = np.concatenate([self.tape.batch(j)["ts"] for j in range(
            max(0, n_batches - 1 - self.batches_ahead), n_batches)])
        return int(np.count_nonzero(
            ts > ts[-1] - int(self.config["query"]["duration_ms"])))

    def state_off(self, n_batches: int) -> int:
        """By how many entries what the program says its carry held after
        the last step misses the window's own count; 0 where no program is
        bound (the control, the host interpreter)."""
        if self._rt is None:
            return 0
        want = self.held(n_batches)
        return sum(abs(int(entry[STATE_RECORD]["held"]) - want)
                   for entry in self._rt.explain()["queries"].values())

    def judge(self, n_batches: int) -> list:
        owed = {i: int(self.tape.params["batch"]) for i in range(n_batches)}
        wrong_counts = sum(1 for i in set(owed) | set(self._counts)
                           if self._counts.get(i, 0) != owed.get(i, 0))
        off = keys_off = out_of_order = compared = 0
        worst = 0.0
        sampled = [i for i in range(n_batches) if self.sampled(i)]
        for i in sampled:
            b = self.tape.batch(i)
            parts = self._kept.get(i, [])
            ts, avg, room, dev = (
                np.concatenate([p[c] for p in parts]) if parts
                else np.zeros(0, np.int64) for c in range(4))
            compared += b["n"]
            if len(ts) != b["n"]:
                off += max(len(ts), b["n"])
                continue
            want = self.owed(i)
            out_of_order += int(np.count_nonzero(ts != b["ts"]))
            keys_off += int(np.count_nonzero(
                (room != b["roomNo"]) | (dev != b["deviceID"])))
            off += values_off(avg, want)
            worst = max(worst, float(np.max(
                np.abs(avg - want) / np.spacing(want.astype(np.float32)))))
        self.detail = {"batches_counted": n_batches,
                       "batches_compared_by_value": len(sampled),
                       "rows_delivered": self.rows,
                       "rows_compared_by_value": compared,
                       "worst_value_ulps": round(worst, 4)}
        if self._rt is not None:
            self.detail["events_in_window"] = self.held(n_batches)
            for entry in self._rt.explain()["queries"].values():
                for record in ("window", STATE_RECORD):
                    if entry.get(record):
                        self.detail[record] = entry[record]
        return [compare.check("batches_with_wrong_row_count", wrong_counts),
                compare.check("sampled_values_off", off),
                compare.check("sampled_keys_off", keys_off),
                compare.check("sampled_rows_out_of_order", out_of_order),
                compare.check("nothing_to_compare", int(compared == 0)),
                compare.check("state_held_off", self.state_off(n_batches))]
