"""Bench driver-interface pins (ISSUE 13 satellites):

  * the final stdout line of every bench mode must round-trip through
    json.loads within the driver's tail-capture bound — _print_summary
    degrades by dropping detail keys and falls back to a minimal
    headline line rather than EVER printing an oversized/unparseable
    final line (the BENCH "parsed": null failure shape);
  * every latency/throughput frontier point gets a MEASURED p99 —
    p99_latency flushes unconditionally per timed batch, so a batch's
    deliveries land while its own clock is live and the histogram can
    never come back empty (the frontier "p99_ms": null shape, r05).
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def _last_line(capsys) -> str:
    out = capsys.readouterr().out.strip()
    return out.splitlines()[-1]


def test_print_summary_small_passes_through(capsys):
    s = {"metric": "m", "value": 1, "unit": "u", "vs_baseline": 2.0}
    bench._print_summary(dict(s))
    assert json.loads(_last_line(capsys)) == s


def test_print_summary_oversize_degrades_to_parseable(capsys):
    s = {"metric": "m", "value": 1, "unit": "u", "vs_baseline": 2.0,
         "detail": "BENCH_DETAIL.json",
         "configs": {f"c{i}": {"eps": i, "note": "x" * 50}
                     for i in range(100)},
         "roofline": {"a": list(range(200))},
         "transport": {"b": "y" * 500},
         "placement": {"c": "z" * 300},
         "durability": {"d": "w" * 300}}
    bench._print_summary(dict(s), cap=512)
    line = _last_line(capsys)
    assert len(line) <= 512
    parsed = json.loads(line)
    assert parsed["metric"] == "m" and parsed["value"] == 1


def test_print_summary_oversize_beyond_drops_still_parses(capsys):
    # even the headline keys blow the cap: the minimal fallback line
    # must still print and parse (hard bound, never garbage)
    s = {"metric": "m" * 4000, "value": 1, "unit": "u",
         "vs_baseline": 2.0, "detail": "BENCH_DETAIL.json"}
    bench._print_summary(dict(s), cap=256)
    parsed = json.loads(_last_line(capsys))
    assert parsed["value"] == 1


def test_print_summary_nonserializable_falls_back(capsys):
    s = {"metric": "m", "value": 1, "unit": "u", "vs_baseline": 2.0,
         "detail": "BENCH_DETAIL.json", "configs": {"bad": object()}}
    bench._print_summary(dict(s))
    parsed = json.loads(_last_line(capsys))
    assert parsed["metric"] == "m" and parsed["value"] == 1


def test_p99_latency_always_measured():
    """The per-batch flush guarantees a measured histogram whenever the
    tape produces matches at all — no silent None."""
    tape = bench.make_tape(256 * 6, 256)
    p99 = bench.p99_latency(bench.DEV["patterns"] + bench.C3,
                            bench.STREAM, tape, 8, warm=2)
    assert isinstance(p99, float) and p99 >= 0.0


def test_frontier_every_point_has_measured_p99():
    pts = bench.frontier(bench.DEV["patterns"] + bench.C3,
                         host_app=None, batches=(256,))
    assert pts, "frontier returned no points"
    for pt in pts:
        assert "skipped" in pt or pt.get("p99_ms") is not None, pt
