"""Reduction of a profiler trace (.xplane.pb) to the numbers the per-layer
metrics read: per device the union of the intervals in which an operation
ran (busy), per operation name its device seconds, per `jax.named_scope`
of the engine its device seconds, per XLA module its runs and seconds; the
host spans of the driver (`bench:<name>`) and of the engine (`siddhi:<name>`)
nested by interval on each thread, with each name's self time; and the idle
gaps of the busiest device cut at the span boundaries, each piece under the
name of the innermost span that covers it.

    python -m benchmark.xplane <file.xplane.pb>     # what is in a trace

`load` reads the file's protobuf wire format itself (benchmark/xproto.py),
because the name-scope of a device operation is a stat of the event's
METADATA, which `jax.profiler.ProfileData` does not show; where that reader
cannot follow a file it falls back to `ProfileData`, and the names are
XLA's own (`scopes` false).  `summarize` works on plain lists, so the tests
feed it a hand-made trace with known numbers.
"""
import heapq
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BENCH, ENGINE = "bench:", "siddhi:"
# spans in which a thread only waits: they name a piece of idle time only
# where no other span covers it
WAITS = (ENGINE + "net.wait", ENGINE + "queue_wait")
BETWEEN = "between the driver's calls"
NO_ENGINE = " (no engine span)"
NO_SCOPE = "(no scope)"


def _load_wire(path: str) -> list:
    from benchmark import xproto
    return xproto.planes(path, scoped=lambda plane, line:
                         bool(DEVICE_PLANE.match(plane)) and line == OPS_LINE)


def _load_profile_data(path: str) -> list:
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


# the ways to read a trace file, tried in this order; the first knows each
# device operation's name-scope, the second does not
READERS = (_load_wire, _load_profile_data)


def load(path: str) -> list:
    """[{"name": plane, "lines": [{"name": line, "events":
    [(name, start_ns, duration_ns) or (name, start_ns, duration_ns,
    op_name), ...]}]}] of one trace file.  `op_name` is the `tf_op` stat
    of a device operation's metadata: its JAX name-scope path."""
    error = None
    for reader in READERS:
        try:
            return reader(path)
        except Exception as e:      # the next reader; never a failure here
            error = e
            print(f"benchmark.xplane: {reader.__name__} could not read "
                  f"{path}: {type(e).__name__}: {e}", file=sys.stderr)
    raise error


_OPCODE = re.compile(r" = .*? ([a-z][a-z0-9\-]*)\(")


def short_name(hlo: str) -> str:
    """'%while.73 = (u32[]...) while(...)' -> '%while.73 while': device
    operations carry XLA's whole instruction text today."""
    head = hlo.split(" = ", 1)[0]
    m = _OPCODE.search(hlo)
    return f"{head} {m.group(1)}" if m and len(hlo) > len(head) else head[:80]


# A name-scope path as JAX writes it: "jit(lane_block)/vmap(hop1)/
# within_kill/reduce_min:".  A transform wraps the scopes it was applied
# under in its parentheses ("vmap(hop1)", "vmap(vmap(compact))"); `jit` and
# `pjit` hold the name of the jitted FUNCTION, which is no scope; control
# flow adds components of its own; the last component is the primitive.
_WRAPPED = re.compile(r"^([A-Za-z_][\w.\-]*)\((.*)\)$")
_FUNCTION_WRAPPERS = ("jit", "pjit")
_CONTROL_FLOW = re.compile(r"^(while|body|cond|body_fun|cond_fun|"
                           r"closed_call|checkpoint|remat|core_call|"
                           r"custom_jvp_call|custom_vjp_call|"
                           r"branch_\d+_fun)$")


def _split(path: str) -> list:
    """`path` cut at the slashes outside every parenthesis."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return out


def _scopes(parts: list) -> list:
    """The `jax.named_scope`s among a path's components, outermost first."""
    names = []
    for part in parts:
        m = _WRAPPED.match(part)
        if m:
            if m.group(1) not in _FUNCTION_WRAPPERS:
                names += _scopes(_split(m.group(2)))
        elif part and not _CONTROL_FLOW.match(part):
            names.append(part)
    return names


def scope_of(op_name) -> str:
    """'jit(lane_block)/vmap(hop1)/within_kill/reduce_min:' ->
    'hop1/within_kill': the `jax.named_scope`s the program wrote around an
    operation, outermost first (the innermost is the last); '' where the
    path has none, or there is no path."""
    if not op_name:
        return ""
    return "/".join(_scopes(_split(op_name.split(":", 1)[0])[:-1]))


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


def nest(spans: list) -> dict:
    """Spans `(name, start, duration, thread)` nested by interval on each
    thread.  Per name: `seconds`, `count`, and `self_seconds`, a span's
    duration less the part its children cover; `children[parent][name]`,
    the seconds of `name` as the immediate child of `parent` ('' = the top
    of its thread), which keeps a name opened in two places apart."""
    seconds, self_s, count, children = {}, {}, {}, {}
    by_thread = {}
    for name, s, d, thread in spans:
        by_thread.setdefault(thread, []).append((s, -d, name))
    for rows in by_thread.values():
        rows.sort()
        stack = []      # [name, end] of the spans still open
        for s, neg_d, name in rows:
            d = -neg_d
            while stack and stack[-1][1] <= s:
                stack.pop()
            # a child that outlasts its parent (clock jitter) is clipped
            inside = d if not stack else max(0.0, min(s + d, stack[-1][1]) - s)
            parent = stack[-1][0] if stack else ""
            if stack:
                self_s[parent] -= inside / 1e9
            kids = children.setdefault(parent, {})
            kids[name] = kids.get(name, 0.0) + d / 1e9
            seconds[name] = seconds.get(name, 0.0) + d / 1e9
            self_s[name] = self_s.get(name, 0.0) + d / 1e9
            count[name] = count.get(name, 0) + 1
            stack.append([name, s + d])
    return {"seconds": seconds, "self_seconds": self_s, "count": count,
            "children": children}


def _calls_into_the_engine(children: dict) -> set:
    """The driver's spans that hold an engine span: its calls into the
    engine (`bench:send_batch`, not `bench:feed` or `bench:callback`)."""
    return {parent for parent, kids in children.items()
            if parent.startswith(BENCH)
            and any(k.startswith(ENGINE) for k in kids)}


def segments(spans: list) -> list:
    """Disjoint, sorted `(start, end, name)`: every stretch between two
    span boundaries that some span covers, under the name of the innermost
    one: of the spans of any thread that cover it, the one that began last
    (the shorter of two that began together); a span of WAITS only where
    nothing else covers the stretch."""
    marks = sorted({t for _n, s, d, _th in spans if d > 0
                    for t in (s, s + d)})
    starting = {}
    for name, s, d, _th in spans:
        if d > 0:
            starting.setdefault(s, []).append((-s, d, name))
    working, waiting, out = [], [], []
    for i, t in enumerate(marks[:-1]):
        for item in starting.get(t, ()):
            heapq.heappush(waiting if item[2] in WAITS else working, item)
        name = None
        for heap in (working, waiting):
            while heap and -heap[0][0] + heap[0][1] <= t:
                heapq.heappop(heap)     # ended
            if heap and name is None:
                name = heap[0][2]
        if name is not None:
            if out and out[-1][2] == name and out[-1][1] == t:
                out[-1][1] = marks[i + 1]
            else:
                out.append([t, marks[i + 1], name])
    return out


def lay(gaps: list, segs: list, suffix=()) -> dict:
    """Seconds of the sorted disjoint `gaps` by the name of the segment
    each piece falls in; a piece in no segment is BETWEEN; a name in
    `suffix` gets NO_ENGINE appended."""
    out, j = {}, 0
    for s, e in gaps:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k, at = j, s
        while at < e:
            if k < len(segs) and segs[k][0] <= at:
                upto, name = min(e, segs[k][1]), segs[k][2]
                if name in suffix:
                    name += NO_ENGINE
                k += 1
            else:
                upto = min(e, segs[k][0]) if k < len(segs) else e
                name = BETWEEN
            out[name] = out.get(name, 0.0) + (upto - at) / 1e9
            at = upto
    return out


def _top(seconds: dict, n: int = 10) -> list:
    return [[k, v] for k, v in
            sorted(seconds.items(), key=lambda kv: -kv[1])[:n]]


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
            spans += [(e[0], e[1], e[2], ln["name"])
                      for ln in pl["lines"] for e in ln["events"]
                      if e[0].startswith((BENCH, ENGINE))]
    nested = nest(spans)
    out = {"window_s": 0.0, "devices": {}, "busiest": None, "scopes": False,
           # the driver's spans under their bare names, as before PR 39
           "host_spans": {n[len(BENCH):]: s
                          for n, s in nested["seconds"].items()
                          if n.startswith(BENCH)},
           "span_seconds": nested["seconds"],
           "span_self_seconds": nested["self_seconds"],
           "span_counts": nested["count"],
           "span_children": nested["children"],
           "breakdown": {"device_ops": [], "idle_gaps": [], "modules": []}}
    marks = [e[1] for d in devices.values() for e in d["ops"]] \
        + [s for _n, s, _d, _th in spans]
    ends = [e[1] + e[2] for d in devices.values() for e in d["ops"]] \
        + [s + d for _n, s, d, _th in spans]
    if not marks:
        return out
    t0, t1 = min(marks), max(ends)
    out["window_s"] = (t1 - t0) / 1e9
    busiest, busiest_merged = None, []
    for dev, d in devices.items():
        merged = merge([[e[1], e[1] + e[2]] for e in d["ops"]])
        busy = sum(e - s for s, e in merged) / 1e9
        op_s, by_path, scope_s, shown_s, mod_s, mod_n = {}, {}, {}, {}, {}, {}
        for e in d["ops"]:
            op_s[e[0]] = op_s.get(e[0], 0.0) + e[2] / 1e9
            key = (e[0], e[3] if len(e) > 3 else None)
            by_path[key] = by_path.get(key, 0.0) + e[2] / 1e9
        for (n, path), secs in by_path.items():     # once a distinct name
            scope = scope_of(path)
            key = scope or NO_SCOPE
            scope_s[key] = scope_s.get(key, 0.0) + secs
            key = f"{scope}/{short_name(n)}" if scope else short_name(n)
            shown_s[key] = shown_s.get(key, 0.0) + secs
        for n, _s, dur in d["modules"]:
            mod_s[n] = mod_s.get(n, 0.0) + dur / 1e9
            mod_n[n] = mod_n.get(n, 0) + 1
        out["devices"][dev] = {"busy_s": busy, "op_seconds": op_s,
                               "scope_seconds": scope_s, "shown_seconds":
                               shown_s, "module_seconds": mod_s,
                               "module_runs": mod_n}
        if busiest is None or busy > out["devices"][busiest]["busy_s"]:
            busiest, busiest_merged = dev, merged
    out["busiest"] = busiest
    if busiest is None:
        return out
    dev = out["devices"][busiest]
    # scopes were read if any operation of the busiest device has one
    out["scopes"] = any(k != NO_SCOPE for k in dev["scope_seconds"])
    edges = [t0] + [x for iv in busiest_merged for x in iv] + [t1]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out["breakdown"] = {
        "device_ops": _top(dev["shown_seconds"]),
        "idle_gaps": _top(lay(idle, segments(spans),
                              _calls_into_the_engine(nested["children"]))),
        # a module's runs ride in its name, so that every list of the
        # breakdown is [[name, seconds], ...]: seconds / runs is a call
        "modules": _top({f"{n} x{dev['module_runs'][n]}": s
                         for n, s in dev["module_seconds"].items()})}
    return out


def main(argv) -> int:
    planes = load(argv[0])
    for pl in planes:
        print("PLANE", pl["name"])
        for ln in pl["lines"]:
            names = {}
            for e in ln["events"]:
                c = names.setdefault(e[0], [0, 0.0])
                c[0] += 1
                c[1] += e[2] / 1e9
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:6]
            print(f"  LINE {ln['name']!r}: {len(ln['events'])} events; "
                  + "; ".join(f"{n[:60]} x{c} {s:.4f}s" for n, (c, s) in top))
    s = summarize(planes)
    print({k: v for k, v in s.items() if k not in ("devices",)})
    for dev, d in s["devices"].items():
        print(dev, "busy_s", d["busy_s"], "modules", d["module_runs"],
              "scopes", _top(d["scope_seconds"], 20))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
