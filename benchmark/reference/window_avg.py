"""Plain reference for `from S#window.length(L) select avg(price) as ap
insert into Out`: one row an event, in arrival order, stamped with its
event's timestamp, `ap` the mean of the last min(n, L) prices up to and
including the event.  Float64 numpy, independent of `siddhi_tpu`.  `price`
may be handed in any float type: the control (benchmark/control.py) runs
this same code on bfloat16 prices."""
from types import SimpleNamespace

import numpy as np

from benchmark import compare

# How far a delivered mean may lie from the exact one, in f32 ulps of the
# exact one.  The tape's prices sit on a quarter-step grid in [90, 130]: a
# window of 1000 holds at most 520,000 quarter steps, under 2^24, so a sum
# taken over the WINDOW is exact in f32 (the configuration's guarantee) and
# only the one division rounds.  The device's f32 division is not promised
# correctly rounded: over all 80,081,000 (sum, count) pairs these windows
# can hold, ONE division on a TPU v5e lies at most 2.26 ulps from the exact
# quotient (past 2 for 0.12% of the pairs of 951..1000 prices) and within 2
# of the correctly rounded one (my chip run, PR 44; PERF.md section 6), so
# the limit is 3: a division's reading and 0.74 ulp of room.  (What this
# comparison was built to see, a sum that errs by the rounding of a
# 2^18-event prefix, reads ~600-900; bfloat16 prices read thousands.)
VALUE_ULPS = 3


def window_mean(price, length: int, before) -> np.ndarray:
    """The mean, for every event of `price`, of the last min(n, length)
    prices up to and including it; `before` holds the prices that came
    ahead of this batch (its last length - 1 are read).  One float64 prefix
    sum over [before | price]: exact on the tape's grid."""
    seq = np.concatenate([np.asarray(before, np.float64)[-(length - 1):],
                          np.asarray(price, np.float64)])
    prefix = np.concatenate([[0.0], np.cumsum(seq)])
    end = np.arange(len(seq) - len(price), len(seq)) + 1
    start = np.maximum(end - length, 0)
    return (prefix[end] - prefix[start]) / (end - start)


def values_off(got, want) -> int:
    want = np.asarray(want, np.float64)
    room = VALUE_ULPS * np.spacing(want.astype(np.float32)).astype(np.float64)
    return int(np.count_nonzero(
        ~(np.abs(np.asarray(got, np.float64) - want) <= room)))


def stand_in(judge: "Judge", batches: list, cast) -> None:
    """Put this reference in the program's place: hand `judge` what the
    query owes for `batches`, computed on `cast(price)`."""
    length = int(judge.config["query"]["length"])
    before = np.zeros(0)
    for b in batches:
        price = np.asarray(cast(b["price"])).astype(np.float64)
        judge.on_batch(SimpleNamespace(
            n=b["n"], timestamps=b["ts"],
            columns={"ap": window_mean(price, length, before)}))
        before = np.concatenate([before, price])[-(length - 1):]


class Judge:
    """Counts the rows delivered for EVERY input batch of the window (one
    row an event is owed), keeps batch 0 (where the window fills: means
    over 1..999 prices) and a seeded one batch in `compare_one_batch_in`
    whole, and once the window has closed holds the kept rows to
    `window_mean` over the same events.  (A window carries about 10^8 rows:
    every row cannot be kept, every batch can be counted.)"""

    def __init__(self, config: dict, tape, seed: int):
        self.config, self.tape, self.seed = config, tape, int(seed)
        tp = tape.params
        self._span_ms = int(tp["batch"]) * int(tp["dt_ms"])
        self._every = max(1, int(config["compare_one_batch_in"]))
        self._phase = int(np.random.default_rng(
            [self.seed, 0xC0FFEE]).integers(0, self._every))
        self._counts = {}
        self._kept = {}
        self._rt = None
        self.rows = 0
        self.detail = {}

    def sampled(self, i: int) -> bool:
        return i == 0 or i % self._every == self._phase

    def bind(self, rt) -> None:
        """The driver hands over the runtime: its `window` record (what
        form the plan took, the carry's re-runs) is printed with the run."""
        self._rt = rt

    def on_batch(self, b) -> None:
        """Batch callback of the engine (in the timed window)."""
        if not b.n:
            return
        from benchmark.tapes.stock import TS0
        self.rows += b.n
        first = (int(b.timestamps[0]) - TS0) // self._span_ms
        last = (int(b.timestamps[-1]) - TS0) // self._span_ms
        if first != last:           # an output batch astride two inputs
            self._counts[-1] = self._counts.get(-1, 0) + b.n
            return
        self._counts[first] = self._counts.get(first, 0) + b.n
        if self.sampled(first):
            self._kept.setdefault(first, []).append(
                (np.array(b.timestamps, np.int64),
                 np.array(b.columns["ap"], np.float64)))

    def _before(self, i: int, length: int) -> np.ndarray:
        """The tape's last length - 1 prices ahead of batch `i`."""
        parts, have = [], 0
        while i > 0 and have < length - 1:
            i -= 1
            parts.insert(0, self.tape.batch(i)["price"])
            have += len(parts[0])
        return np.concatenate(parts) if parts else np.zeros(0)

    def judge(self, n_batches: int) -> list:
        length = int(self.config["query"]["length"])
        owed = {i: int(self.tape.params["batch"]) for i in range(n_batches)}
        wrong_counts = sum(1 for i in set(owed) | set(self._counts)
                           if self._counts.get(i, 0) != owed.get(i, 0))
        off = out_of_order = compared = 0
        worst = 0.0
        sampled = [i for i in range(n_batches) if self.sampled(i)]
        for i in sampled:
            b = self.tape.batch(i)
            want = window_mean(b["price"], length, self._before(i, length))
            parts = self._kept.get(i, [])
            ts = np.concatenate([p[0] for p in parts]) if parts \
                else np.zeros(0, np.int64)
            ap = np.concatenate([p[1] for p in parts]) if parts \
                else np.zeros(0)
            compared += b["n"]
            if len(ts) != b["n"]:
                off += max(len(ts), b["n"])
                continue
            out_of_order += int(np.count_nonzero(ts != b["ts"]))
            off += values_off(ap, want)
            worst = max(worst, float(np.max(
                np.abs(ap - want) / np.spacing(want.astype(np.float32)))))
        self.detail = {"batches_counted": n_batches,
                       "batches_compared_by_value": len(sampled),
                       "rows_delivered": self.rows,
                       "rows_compared_by_value": compared,
                       "worst_value_ulps": round(worst, 4)}
        if self._rt is not None:    # a program older than the record: none
            for entry in self._rt.explain()["queries"].values():
                if entry.get("window"):
                    self.detail["window"] = entry["window"]
        return [compare.check("batches_with_wrong_row_count", wrong_counts),
                compare.check("sampled_values_off", off),
                compare.check("sampled_rows_out_of_order", out_of_order),
                compare.check("nothing_to_compare", int(compared == 0))]
