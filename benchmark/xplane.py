"""Reduction of a profiler trace (.xplane.pb) to the numbers the per-layer
metrics read: per device the union of the intervals in which an operation
ran (busy), per operation name its device seconds, per XLA module its runs
and seconds, and the idle gaps of the busiest device laid against the
benchmark's own host spans (`bench:<name>` TraceAnnotations).

    python -m benchmark.xplane <file.xplane.pb>     # what is in a trace

`load` needs JAX only for `jax.profiler.ProfileData`; `summarize` works on
plain lists, so the tests feed it a hand-made trace with known numbers.
"""
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
BETWEEN = "between the driver's calls"


def load(path: str) -> list:
    """[{"name": plane, "lines": [{"name": line, "events":
    [(name, start_ns, duration_ns), ...]}]}] of one trace file."""
    from jax.profiler import ProfileData
    planes = []
    for pl in ProfileData.from_file(path).planes:
        lines = []
        for ln in pl.lines:
            lines.append({"name": ln.name, "events": [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in ln.events]})
        planes.append({"name": pl.name, "lines": lines})
    return planes


_OPCODE = re.compile(r" = .*? ([a-z][a-z0-9\-]*)\(")


def short_name(hlo: str) -> str:
    """'%while.73 = (u32[]...) while(...)' -> '%while.73 while': device
    operations carry XLA's whole instruction text today."""
    head = hlo.split(" = ", 1)[0]
    m = _OPCODE.search(hlo)
    return f"{head} {m.group(1)}" if m and len(hlo) > len(head) else head[:80]


def merge(intervals: list) -> list:
    """Sorted, disjoint [start, end] intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _covering_span(spans: list, t: float) -> str:
    """The shortest host span that covers instant `t`, else BETWEEN."""
    best, best_len = BETWEEN, None
    for name, s, d in spans:
        if s <= t <= s + d and (best_len is None or d < best_len):
            best, best_len = name, d
    return best


def summarize(planes: list) -> dict:
    devices, spans = {}, []
    for pl in planes:
        m = DEVICE_PLANE.match(pl["name"])
        if m:
            ops = [e for ln in pl["lines"] if ln["name"] == OPS_LINE
                   for e in ln["events"] if e[2] > 0]
            mods = [e for ln in pl["lines"] if ln["name"] == MODULES_LINE
                    for e in ln["events"]]
            devices[m.group(0)] = {"ops": ops, "modules": mods}
        elif pl["name"] == HOST_PLANE:
            spans += [(e[0][len(SPAN_PREFIX):], e[1], e[2])
                      for ln in pl["lines"] for e in ln["events"]
                      if e[0].startswith(SPAN_PREFIX)]
    marks = [e[1] for d in devices.values() for e in d["ops"]] \
        + [s for _n, s, _d in spans]
    ends = [e[1] + e[2] for d in devices.values() for e in d["ops"]] \
        + [s + d for _n, s, d in spans]
    if not marks:
        return {"window_s": 0.0, "devices": {}, "host_spans": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    t0, t1 = min(marks), max(ends)
    out = {"window_s": (t1 - t0) / 1e9, "devices": {}, "host_spans": {}}
    for name, s, d in spans:
        out["host_spans"][name] = out["host_spans"].get(name, 0.0) + d / 1e9
    busiest, busiest_merged = None, []
    for dev, d in devices.items():
        merged = merge([[e[1], e[1] + e[2]] for e in d["ops"]])
        busy = sum(e - s for s, e in merged) / 1e9
        op_s, mod_s, mod_n = {}, {}, {}
        for n, _s, dur in d["ops"]:
            op_s[n] = op_s.get(n, 0.0) + dur / 1e9
        for n, _s, dur in d["modules"]:
            mod_s[n] = mod_s.get(n, 0.0) + dur / 1e9
            mod_n[n] = mod_n.get(n, 0) + 1
        out["devices"][dev] = {"busy_s": busy, "op_seconds": op_s,
                               "module_seconds": mod_s, "module_runs": mod_n}
        if busiest is None or busy > out["devices"][busiest]["busy_s"]:
            busiest, busiest_merged = dev, merged
    out["busiest"] = busiest
    gaps = {}
    if busiest is not None:
        edges = [t0] + [x for iv in busiest_merged for x in iv] + [t1]
        for i in range(0, len(edges), 2):
            s, e = edges[i], edges[i + 1]
            if e > s:
                who = _covering_span(spans, (s + e) / 2)
                gaps[who] = gaps.get(who, 0.0) + (e - s) / 1e9
    top = sorted(out["devices"][busiest]["op_seconds"].items(),
                 key=lambda kv: -kv[1])[:10] if busiest else []
    out["breakdown"] = {
        "device_ops": [[short_name(n), s] for n, s in top],
        "idle_gaps": [[n, s] for n, s in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]]}
    return out


def main(argv) -> int:
    planes = load(argv[0])
    for pl in planes:
        print("PLANE", pl["name"])
        for ln in pl["lines"]:
            names = {}
            for n, _s, d in ln["events"]:
                c = names.setdefault(n, [0, 0.0])
                c[0] += 1
                c[1] += d / 1e9
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:6]
            print(f"  LINE {ln['name']!r}: {len(ln['events'])} events; "
                  + "; ".join(f"{n[:60]} x{c} {s:.4f}s" for n, (c, s) in top))
    s = summarize(planes)
    print({k: v for k, v in s.items() if k not in ("devices",)})
    for dev, d in s["devices"].items():
        print(dev, "busy_s", d["busy_s"], "modules", d["module_runs"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
