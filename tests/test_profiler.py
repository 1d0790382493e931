"""Continuous device-time attribution (core/profiler.py, ISSUE 17):
the phase profiler must be output-invariant across every plan family,
publish shares that sum to exactly 1.0 with >= 0.9 coverage of the
dispatch wall, honor the kernel-round duty cycle, serve
/siddhi/artifact/profile, render grammar-valid Prometheus phase
series, and fire the host-share breach trigger through the tracing
registry."""
import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.profiler import HOST_PHASES, PHASES, PhaseProfiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STOCK = "define stream S (sym string, p double, v int);\n"

FAMILIES = {
    "filter": "@info(name='q') from S[p > 10] select sym, p "
              "insert into Out;\n",
    "window": "@info(name='q') from S#window.length(64) select sym, "
              "sum(p) as s insert into Out;\n",
    "pattern": "@info(name='q') from every e1=S[p > 10] -> e2=S[p > e1.p] "
               "select e1.sym as s1, e2.p as p2 insert into Out;\n",
    "join": "define stream T (sym string, q double);\n"
            "@info(name='q') from S#window.length(32) as a join "
            "T#window.length(32) as b on a.sym == b.sym "
            "select a.sym as sym, a.p as p, b.q as q insert into Out;\n",
}


def _cols(n, seed=0):
    r = np.random.default_rng(seed)
    return {"sym": np.array([f"K{i % 4}" for i in range(n)]),
            "p": np.round(r.uniform(5.0, 20.0, n), 2),
            "v": r.integers(1, 100, n).astype(np.int32)}


# devicePatterns defaults to 'auto', which routes unpartitioned patterns
# to the host matcher — force the device NFA so the pattern family
# actually exercises kernel-round accounting
PREFER = "@app:devicePatterns('prefer')\n"


def _run_family(head, family, batches=6, n=64):
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(head + PREFER + STOCK + FAMILIES[family])
    rows = []
    rt.add_callback("Out", lambda evs: rows.extend(repr(e) for e in evs))
    rt.start()
    h = rt.input_handler("S")
    hj = rt.input_handler("T") if family == "join" else None
    for k in range(batches):
        h.send_batch(_cols(n, seed=k), np.arange(n) + n * k)
        if hj is not None:
            c = _cols(n, seed=100 + k)
            hj.send_batch({"sym": c["sym"], "q": c["p"]},
                          np.arange(n) + n * k)
        rt.flush()
    prof = rt.profiler.metrics() if rt.profiler is not None else None
    mgr.shutdown()
    return rows, prof


# ---------------------------------------------------------------------------
# tentpole: output invariance + attribution invariants, all plan families
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_profiler_output_invariant_per_family(family):
    """off / all / sample=2 must be byte-identical: observation must
    never change what the engine computes."""
    base, _ = _run_family("@app:profile('off')\n", family)
    assert base, f"{family}: no output rows at all"
    for head in ("@app:profile('all')\n", "@app:profile('sample=2')\n"):
        got, prof = _run_family(head, family)
        assert got == base, f"{family} {head.strip()}: outputs diverged"
        assert prof is not None and prof["plans"]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_shares_sum_to_one_and_coverage(family):
    """Per-plan and aggregate shares sum to exactly 1.0 (normalized
    over the corrected total) and phase attribution covers >= 0.9 of
    the dispatch wall — the ISSUE 17 acceptance bar."""
    _, prof = _run_family("@app:profile('all')\n", family)
    for name, pv in prof["plans"].items():
        s = sum(pv["shares"].values())
        assert abs(s - 1.0) < 5e-4, (name, pv["shares"])
        assert set(pv["shares"]) == set(PHASES)
        host = sum(pv["shares"][k] for k in HOST_PHASES)
        assert abs(host - pv["host_dispatch_share"]) < 1e-3
    agg = prof["aggregate"]
    assert abs(sum(agg["shares"].values()) - 1.0) < 5e-4
    assert agg["coverage"] >= 0.9, agg
    assert agg["rounds"] > 0 and agg["events"] > 0


PARTITIONED = ("partition with (sym of S) begin\n@info(name='q') "
               "from every e1=S[p > 10] -> e2=S[p > e1.p] "
               "within 8 milliseconds select e1.p as a, e2.p as b "
               "insert into Out;\nend;\n")


@pytest.mark.parametrize("app,phase", [
    # the seq family pulls and unpacks inside finalize(): the span's own
    # phase takes the time
    (FAMILIES["pattern"], "host_pack_unpack"),
    # the lane path materialises under the pipeline's d2h_materialize
    # wrap, the outermost phase, which holds its unpack as it always has
    (PARTITIONED, "d2h_materialize")], ids=["seq", "lane"])
def test_unpack_span_time_is_not_python_dispatch(app, phase):
    """A pattern plan's `unpack` span maps to `host_pack_unpack`: its time
    leaves the `python_dispatch` residual, and the shares still sum to 1."""
    import time
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:profile('all')\n@app:partitionCapacity(8)\n"
        + PREFER + STOCK + app)
    assert rt.stats._SPAN_PHASE["unpack"][0] == "host_pack_unpack"
    assert "transfer.wait" not in rt.stats._SPAN_PHASE
    assert "transfer.copy" not in rt.stats._SPAN_PHASE
    real, slept = rt.span, [0]

    class Slow:                 # the real span, 10 ms of work inside it
        def __init__(self, inner):
            self.inner = inner

        def __enter__(self):
            self.inner.__enter__()
            time.sleep(0.01)
            slept[0] += 1

        def __exit__(self, *exc):
            return self.inner.__exit__(*exc)

    rt.start()
    for k in range(8):
        if k == 4:              # compiled: from here on the spans count
            rt.profiler.reset()
            rt.span = lambda name, **kw: Slow(real(name, **kw)) \
                if name == "unpack" else real(name, **kw)
        rt.input_handler("S").send_batch(_cols(64, seed=k),
                                         np.arange(64) + 64 * k)
        rt.flush()
    prof = rt.profiler.metrics()
    mgr.shutdown()
    pv = prof["plans"]["q"]
    assert slept[0] >= 4
    assert pv["phases_s"][phase] >= 0.01 * slept[0]
    assert pv["phases_s"].get("python_dispatch", 0.0) < 0.01 * slept[0]
    assert abs(sum(pv["shares"].values()) - 1.0) < 5e-4
    assert abs(sum(prof["aggregate"]["shares"].values()) - 1.0) < 5e-4


def test_duty_cycle_counts_kernel_rounds():
    """sample=N probes ~1 in N KERNEL-carrying rounds: collect polls
    and scheduler pumps open kernel-less rounds and must not consume
    the cycle (the bug that zeroed kernel shares on the TCP path)."""
    _, prof = _run_family("@app:profile('sample=3')\n", "pattern",
                          batches=12)
    agg = prof["aggregate"]
    kr, sr = agg["kernel_rounds"], agg["sampled_rounds"]
    assert kr >= 6, agg
    # ceil(kr / 3) sampled, +-1 for the counter being shared app-wide
    want = -(-kr // 3)
    assert abs(sr - want) <= 1, (kr, sr, want)
    # the probe actually measured device time on those rounds
    assert agg["phases_s"]["kernel_compute"] > 0.0


def test_all_mode_does_not_extrapolate():
    """mode='all' blocks every kernel round: sampled == kernel rounds,
    so the extrapolation factor must stay 1 (kernel seconds reported
    exactly as measured, not scaled by kernel-less round wall)."""
    _, prof = _run_family("@app:profile('all')\n", "pattern")
    for pv in prof["plans"].values():
        if pv["kernel_rounds"]:
            assert pv["sampled_rounds"] == pv["kernel_rounds"], pv


def test_statistics_report_always_carries_profile():
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:profile('all')\n" + STOCK + FAMILIES["filter"])
    rt.start()
    h = rt.input_handler("S")
    h.send_batch(_cols(32), np.arange(32))
    rt.flush()
    rep = rt.statistics()
    assert rep["profile"]["mode"] == "all"
    assert rep["profile"]["plans"]
    prof = rt.profile()
    assert "windows" in prof
    assert [name for name in prof["plans"] if not name.startswith("_")]
    mgr.shutdown()


def test_profile_off_is_absent():
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:profile('off')\n" + STOCK + FAMILIES["filter"])
    rt.start()
    assert rt.profiler is None
    assert rt.profile() == {"mode": "off"}
    assert "profile" not in rt.statistics()
    mgr.shutdown()


def test_unknown_mode_rejected():
    from siddhi_tpu.core.planner import PlanError
    with pytest.raises(PlanError):
        SiddhiManager().create_app_runtime(
            "@app:profile('sometimes')\n" + STOCK + FAMILIES["filter"])


# ---------------------------------------------------------------------------
# breach trigger through the tracing registry
# ---------------------------------------------------------------------------

def test_host_share_breach_fires_tracing_trigger(tmp_path):
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:profile(window='0.05')\n@app:hostShareAlert('0.01')\n"
        f"@app:trace('all', export='{tmp_path}')\n"
        + STOCK + FAMILIES["filter"])
    rt.start()
    h = rt.input_handler("S")
    import time
    deadline = time.time() + 10.0
    k = 0
    while time.time() < deadline:
        h.send_batch(_cols(64, seed=k), np.arange(64) + 64 * k)
        rt.flush()
        k += 1
        if rt.profiler.breaches:
            break
        time.sleep(0.02)
    assert rt.profiler.breaches > 0, "window never breached a 1% alert"
    tm = rt.tracing.metrics()
    deadline = time.time() + 5.0
    while time.time() < deadline and not tm["triggers"].get(
            "host_share_breach"):
        time.sleep(0.05)
        tm = rt.tracing.metrics()
    assert tm["triggers"].get("host_share_breach", 0) > 0, tm
    mgr.shutdown()


# ---------------------------------------------------------------------------
# service endpoint + Prometheus grammar
# ---------------------------------------------------------------------------

def test_service_profile_endpoint_and_prometheus():
    from siddhi_tpu.service import SiddhiService
    from tests.test_tracing import assert_valid_exposition
    svc = SiddhiService(port=0).start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        app = ("@app:name('ProfEp')\n@app:profile('all')\n"
               + PREFER + STOCK + FAMILIES["pattern"])
        req = urllib.request.Request(f"{base}/siddhi/artifact/deploy",
                                     data=app.encode(), method="POST")
        urllib.request.urlopen(req).read()
        rt = svc.runtimes["ProfEp"]
        h = rt.input_handler("S")
        for k in range(4):
            h.send_batch(_cols(64, seed=k), np.arange(64) + 64 * k)
        rt.flush()
        with urllib.request.urlopen(
                f"{base}/siddhi/artifact/profile?siddhiApp=ProfEp") as r:
            assert r.status == 200
            prof = json.loads(r.read())["apps"]["ProfEp"]
        assert prof["mode"] == "all" and prof["plans"]
        for pv in prof["plans"].values():
            assert abs(sum(pv["shares"].values()) - 1.0) < 5e-4
        # windowed slice: ?window=0 -> no ring entries, still 200
        with urllib.request.urlopen(
                f"{base}/siddhi/artifact/profile?siddhiApp=ProfEp"
                f"&window=0") as r:
            assert json.loads(r.read())["apps"]["ProfEp"]["windows"] == []
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"{base}/siddhi/artifact/profile?siddhiApp=NoSuchApp")
        assert ei.value.code == 404
        with urllib.request.urlopen(f"{base}/metrics") as r:
            text = r.read().decode()
        assert_valid_exposition(text)
        phase_lines = [ln for ln in text.splitlines()
                       if ln.startswith("siddhi_tpu_phase_seconds_total{")]
        assert phase_lines
        assert any('phase="kernel_compute"' in ln for ln in phase_lines)
        assert any(ln.startswith("siddhi_tpu_host_dispatch_share{")
                   for ln in text.splitlines())
    finally:
        svc.stop()


def test_profiler_spawns_no_threads():
    import threading
    before = {t.name for t in threading.enumerate()}
    _, prof = _run_family("@app:profile('all')\n", "filter", batches=2)
    assert prof["plans"]
    after = {t.name for t in threading.enumerate()} - before
    assert not any(n.startswith("siddhi-profile") for n in after), after
