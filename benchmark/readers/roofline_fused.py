"""The fused multi-query blocks' share of their HBM roofline, from the work
done and not from the layout: the bytes that the events sent and the rows
delivered must move (benchmark/kernels_fused.py) over the busy seconds of
the busiest device, against the published peak of the device kind
(benchmark/peaks/).

The busy seconds are those of the traced interval, so the events and rows
are that interval's: the events are its count of `per` spans
(`bench:send_batch`) times the batch; the rows are those events times the
WINDOW's rows an event (the judge's count of rows delivered in the window
over the events sent in it), since nobody counts rows by interval.  Every
device operation of the interval is in the divisor, the blocks of all
groups and whatever else ran: the share is of the chip's busy time, and
cannot rise by work moved out of one module into another."""
from benchmark import kernels_fused, peaks


def read(spec: dict, obs: dict):
    t = obs.get("trace")
    cfg = obs["cell"]["config"]
    if not t or not t.get("devices") or cfg.get("kernel") != spec["kernel"]:
        return None
    busy_s = t["devices"][t["busiest"]]["busy_s"]
    sends = t.get("span_counts", {}).get(spec["per"])
    events, rows = obs.get("events"), obs.get("rows_delivered")
    if not (busy_s and sends and events and obs.get("batch")) or rows is None:
        return None
    sent = sends * obs["batch"]
    least_s = kernels_fused.fused_block_bytes(
        sent, sent * rows / events, int(spec["in_cols"]),
        int(spec["out_words"])) \
        / peaks.peaks_of(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / busy_s
