"""A reader of the profiler's .xplane.pb in its protobuf wire format: the
seven messages of tsl/profiler/protobuf/xplane.proto (XSpace, XPlane, XLine,
XEvent, XStat, XEventMetadata, XStatMetadata) and of each only the fields
the benchmark reads.  It is here because a device operation's name-scope
(`tf_op`) is a stat of the event's METADATA, which `jax.profiler.ProfileData`
does not show, and because the one `xplane_pb2` module this installation
has lives inside TensorFlow: importing it loads all of TensorFlow (6 s and
its runtime) into the process that holds the chip.  Nothing here imports
anything; an event's name, start and duration come out as `ProfileData`
gives them (the tests hold the two readers equal on a recorded trace).
"""

SCOPE_STAT = "tf_op"


def fields(buf, pos: int, end: int):
    """(field number, wire type, value) of one message: a varint's value,
    the (start, end) of a length-delimited field, or the raw bytes of a
    fixed one."""
    while pos < end:
        key = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0 or wire == 2:
            v = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            if wire == 0:
                yield key >> 3, 0, v
            else:
                yield key >> 3, 2, (pos, pos + v)
                pos += v
        elif wire == 1:
            yield key >> 3, 1, buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            yield key >> 3, 5, buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
    if pos != end:
        raise ValueError(f"a message ends at byte {pos}, not {end}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """The value message of one map<int64, Message> entry."""
    for num, _w, v in fields(buf, *span):
        if num == 2:
            return v
    return None


def _stat_metadata(buf, span) -> tuple:
    ident, name = 0, ""
    for num, _w, v in fields(buf, *span):
        if num == 1:
            ident = v
        elif num == 2:
            name = _text(buf, v)
    return ident, name


def _event_metadata(buf, span) -> tuple:
    """(id, name, [(stat metadata id, text or None), ...])."""
    ident, name, stats = 0, "", []
    for num, _w, v in fields(buf, *span):
        if num == 1:
            ident = v
        elif num == 2:
            name = _text(buf, v)
        elif num == 5:
            sid, text = 0, None
            for n2, _w2, v2 in fields(buf, *v):
                if n2 == 1:
                    sid = v2
                elif n2 == 5:       # str_value
                    text = v2
            stats.append((sid, text))
    return ident, name, stats


def _plane(buf, span, scoped) -> dict:
    name, lines, event_meta, stat_names = "", [], [], {}
    for num, _w, v in fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            event_meta.append(v)
        elif num == 5:
            entry = _map_entry(buf, v)
            if entry:
                ident, text = _stat_metadata(buf, entry)
                stat_names[ident] = text
    names, scopes = {}, {}
    for entry in event_meta:
        meta = _map_entry(buf, entry)
        if meta is None:
            continue
        ident, text, stats = _event_metadata(buf, meta)
        names[ident] = text
        for sid, where in stats:
            if where is not None and stat_names.get(sid) == SCOPE_STAT:
                scopes[ident] = _text(buf, where)
    out = []
    for span_ in lines:
        line_name, t_line, events = "", 0, []
        for num, _w, v in fields(buf, *span_):
            if num == 2:
                line_name = _text(buf, v)
            elif num == 3:
                t_line = v
            elif num == 4:
                events.append(v)
        with_scope = scoped(name, line_name)
        rows = []
        for ev in events:
            meta = offset_ps = duration_ps = 0
            for num, _w, v in fields(buf, *ev):
                if num == 1:
                    meta = v
                elif num == 2:
                    offset_ps = v
                elif num == 3:
                    duration_ps = v
            # whole nanoseconds, as ProfileData gives them
            start = float(t_line + offset_ps // 1000)
            duration = float(duration_ps // 1000)
            if with_scope:
                rows.append((names.get(meta, ""), start, duration,
                             scopes.get(meta)))
            else:
                rows.append((names.get(meta, ""), start, duration))
        out.append({"name": line_name, "events": rows})
    return {"name": name, "lines": out}


def planes_of(data: bytes, scoped=lambda plane, line: False) -> list:
    buf = memoryview(data)
    return [_plane(buf, v, scoped) for num, wire, v in
            fields(buf, 0, len(buf)) if num == 1 and wire == 2]


def planes(path: str, scoped=lambda plane, line: False) -> list:
    """The planes of one trace file, in benchmark/xplane.py's `load` form;
    events of the lines that `scoped(plane name, line name)` picks carry
    their metadata's `tf_op` (None where it has none) as a fourth item."""
    with open(path, "rb") as f:
        return planes_of(f.read(), scoped)
