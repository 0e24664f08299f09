"""From a profiler trace (``.xplane.pb``, or its text form) to numbers:
device busy and idle time, time by op class, collective time and its exposed
part, and the idle gaps attributed to what the host was doing.

Which planes are devices, which line holds the ops, how an op is classed
and which host events name a gap are data: ``trace_rules.json`` beside this
file.  Nothing here imports the program, and nothing at import loads JAX.

A device's ops sit on one line and nest (a ``while`` encloses its body's
ops), so an op's *self* time is its duration less its children's; busy time
is the union of the intervals; a class's time is the sum of the self times
of its ops.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import struct

RULES_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "trace_rules.json")


def load_rules(path: str = RULES_PATH) -> dict:
    with open(path) as f:
        return json.load(f)


# -- reading an XSpace ---------------------------------------------------------
# ``jax.profiler.ProfileData`` hides the stats XLA attaches to an op's metadata
# (``hlo_category``, ``tf_op`` with the flax module path, ``flops``), and the
# class rules need them, so the few messages of xplane.proto that matter are
# decoded here from the wire format: XSpace.planes=1; XPlane name=2 lines=3
# event_metadata=4 stat_metadata=5; XLine name=2 timestamp_ns=3 events=4;
# XEvent metadata_id=1 offset_ps=2 duration_ps=3; XEventMetadata id=1 name=2
# display_name=4 stats=5; XStatMetadata id=1 name=2; XStat metadata_id=1
# double=2 uint64=3 int64=4 str=5 bytes=6 ref=7.

def _fields(buf):
    """(field number, value) of one message: ints for varints and fixed
    widths, a memoryview for anything length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0 or wire == 2:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            if wire == 2:
                v, i = buf[i:i + v], i + v
        elif wire == 1:
            v, i = struct.unpack_from("<d", buf, i)[0], i + 8
        elif wire == 5:
            v, i = struct.unpack_from("<f", buf, i)[0], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, v


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


@dataclasses.dataclass
class Event:
    name: str  # the metadata's display name, or its name
    start: float  # ns
    end: float
    stats: dict  # the metadata's stats, by name


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def parse_xspace(data: bytes) -> list:
    planes = []
    for no, plane in _fields(memoryview(data)):
        if no != 1:
            continue
        name, raw_lines, raw_meta, stat_names = "", [], [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 3:
                raw_lines.append(v)
            elif f == 4:
                raw_meta += [vv for ff, vv in _fields(v) if ff == 2]
            elif f == 5:
                for ff, vv in _fields(v):
                    if ff == 2:
                        d = dict(_fields(vv))
                        stat_names[d.get(1, 0)] = _text(d.get(2, b""))
        meta = {}
        for m in raw_meta:
            mid, mname, display, stats = 0, "", "", {}
            for f, v in _fields(m):
                if f == 1:
                    mid = v
                elif f == 2:
                    mname = _text(v)
                elif f == 4:
                    display = _text(v)
                elif f == 5:
                    d = dict(_fields(v))
                    key = stat_names.get(d.get(1, 0), "")
                    if 5 in d:
                        stats[key] = _text(d[5])
                    elif 7 in d:
                        stats[key] = stat_names.get(d[7], "")
                    else:
                        stats[key] = next((d[k] for k in (2, 3, 4) if k in d),
                                          None)
            meta[mid] = (display or mname, stats)
        lines = []
        for raw in raw_lines:
            lname, t0, events = "", 0, []
            for f, v in _fields(raw):
                if f == 2:
                    lname = _text(v)
                elif f == 3:
                    t0 = v
                elif f == 4:
                    d = dict((ff, vv) for ff, vv in _fields(v) if ff < 4)
                    ename, stats = meta.get(d.get(1, 0), ("", {}))
                    start = d.get(2, 0) / 1000.0
                    events.append((ename, start, start + d.get(3, 0) / 1000.0,
                                   stats))
            lines.append(Line(lname, [Event(n, t0 + s, t0 + e, st)
                                      for n, s, e, st in events]))
        planes.append(Plane(name, lines))
    return planes


def load_profile(path: str) -> list:
    """The planes of an ``.xplane.pb``, or of its text form (test data)."""
    if path.endswith(".textproto"):
        from jax.profiler import ProfileData

        with open(path) as f:
            return parse_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    with open(path, "rb") as f:
        return parse_xspace(f.read())


@dataclasses.dataclass
class Op:
    name: str
    start: float  # ns
    end: float
    self_ns: float = 0.0
    klass: str = "other"
    leaf: bool = True  # encloses no other op
    stats: dict = dataclasses.field(default_factory=dict, repr=False)


def classify(event: Event, rules: dict) -> str:
    for rule in rules["op_classes"]:  # first match wins
        text = (event.name if rule["field"] == "name"
                else str(event.stats.get(rule["field"], "")))
        if re.search(rule["pattern"], text):
            return rule["class"]
    return "other"


def self_times(ops: list) -> None:
    """Set ``self_ns`` of every op of one line: duration less the time its
    direct children (the ops it wholly encloses) cover.  ``ops`` sorted by
    (start, -end)."""
    stack = []
    for op in ops:
        # an op that only overlaps the one before it is its sibling
        while stack and stack[-1].end < op.end:
            stack.pop()
        op.self_ns = op.end - op.start
        if stack:
            stack[-1].self_ns -= op.end - op.start
            stack[-1].leaf = False
        stack.append(op)


def union(intervals: list) -> list:
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """The parts of merged intervals ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


@dataclasses.dataclass
class DeviceSummary:
    plane: str
    busy_ns: float
    class_ns: dict  # op class -> self time
    collective_ns: float
    collective_exposed_ns: float
    busy: list  # merged [start ns, end ns] in which an op ran
    ops: list = dataclasses.field(default_factory=list, repr=False)


@dataclasses.dataclass
class Summary:
    window_ns: float  # the traced calls, first start to last end
    devices: list  # DeviceSummary, one a device plane
    host_gaps: list  # [what the host was doing, seconds], longest first
    calls: int  # ``bench.call`` annotations found

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices used."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def idle_share(self) -> float:
        """Of the window, on the device that idles most."""
        return 1.0 - min(d.busy_ns for d in self.devices) / self.window_ns

    def all_idle_share(self) -> float:
        """Of the window, the part in which no device runs an op: the host
        holds every chip back, whatever the chips wait for among themselves."""
        any_busy = union([iv for d in self.devices for iv in d.busy])
        return 1.0 - covered(any_busy) / self.window_ns

    def class_share(self, klass: str) -> float:
        """Share of device busy time in ops of ``klass``, over all devices."""
        busy = sum(d.busy_ns for d in self.devices)
        return sum(d.class_ns.get(klass, 0.0) for d in self.devices) / busy

    def seconds_where(self, keep) -> float:
        """Mean over the devices of the self seconds of the ops ``keep(op)``
        accepts: for a per-layer metric that brings its own pattern."""
        return sum(o.self_ns for d in self.devices for o in d.ops
                   if keep(o)) / len(self.devices) / 1e9

    def breakdown(self) -> dict:
        """The ten ops with most self time (mean seconds a device) and the
        ten longest idle gaps by host activity."""
        by_name = {}
        for d in self.devices:
            for o in d.ops:
                by_name[o.name] = by_name.get(o.name, 0.0) + o.self_ns
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, ns / len(self.devices) / 1e9]
                               for n, ns in top],
                "idle_gaps": self.host_gaps[:10]}


def host_events(profile, rules: dict) -> list:
    """(name, start, end) of the host events the rules know, on the thread
    that made the ``bench.call`` annotations, outermost first."""
    patterns = [re.compile(p) for p in rules["host_spans"]]
    best = []
    for plane in profile:
        if not re.search(rules["host_plane"], plane.name):
            continue
        for line in plane.lines:
            evs = [(host_label(e.name), e.start, e.end)
                   for e in line.events
                   if any(p.search(e.name) for p in patterns)]
            if (any(n == rules["call_annotation"] for n, _, _ in evs)
                    and len(evs) > len(best)):
                best = evs
    return sorted(best, key=lambda e: (e[1], -e[2]))


def host_label(name: str) -> str:
    """``$file.py:12 function`` (the Python tracer's form) -> ``function``."""
    return re.sub(r"^\$\S+ ", "", name)


def attribute_gap(gap, events, rules) -> str:
    """The innermost known host event that covers the gap's middle."""
    mid = (gap[0] + gap[1]) / 2
    name = rules["outside_calls"]
    for n, s, e in events:  # sorted outermost first
        if s <= mid < e:
            name = n
    return name


def reduce_profile(profile, rules: dict | None = None) -> Summary:
    rules = rules or load_rules()
    events = host_events(profile, rules)
    calls = [(s, e) for n, s, e in events if n == rules["call_annotation"]]
    if not calls:
        raise ValueError(f"no {rules['call_annotation']!r} annotation in the "
                         "trace: nothing marks the window")
    w0, w1 = min(s for s, _ in calls), max(e for _, e in calls)
    devices, gap_seconds = [], {}
    for plane in profile:
        if not re.search(rules["device_plane"], plane.name):
            continue
        ops, async_collectives = [], []
        for line in plane.lines:
            on_op_line = re.search(rules["op_line"], line.name)
            if not on_op_line and not re.search(rules["async_line"],
                                                line.name):
                continue
            for e in line.events:
                if e.end <= w0 or e.start >= w1:
                    continue
                klass = classify(e, rules)
                span = [max(e.start, w0), min(e.end, w1)]
                if not on_op_line:
                    if klass == "collective":
                        async_collectives.append(span)
                    continue
                # one name for the copies of an op XLA deduplicated
                name = e.stats.get("deduplicated_name") or e.name
                where = "/".join(str(e.stats.get("tf_op", "")).split("/")[-3:])
                ops.append(Op(
                    f"{name} [{e.stats.get('hlo_category', '')}] {where}",
                    span[0], span[1], klass=klass, stats=e.stats))
        if not ops:
            continue
        ops.sort(key=lambda o: (o.start, -o.end))
        self_times(ops)
        busy = union([[o.start, o.end] for o in ops])
        class_ns = {}
        for o in ops:
            class_ns[o.klass] = class_ns.get(o.klass, 0.0) + o.self_ns
        coll = union(async_collectives + [[o.start, o.end] for o in ops
                                          if o.klass == "collective"])
        compute = union([[o.start, o.end] for o in ops
                         if o.leaf and o.klass != "collective"])
        gaps = subtract([[w0, w1]], busy)
        for g in gaps:
            what = attribute_gap(g, events, rules)
            gap_seconds[what] = gap_seconds.get(what, 0.0) + (g[1] - g[0]) / 1e9
        devices.append(DeviceSummary(
            plane=plane.name, busy_ns=covered(busy), class_ns=class_ns,
            collective_ns=covered(coll),
            collective_exposed_ns=covered(subtract(coll, compute)),
            busy=busy, ops=ops))
    if not devices:
        raise ValueError("no device plane with ops inside the traced calls")
    n = len(devices)
    host_gaps = sorted(([k, v / n] for k, v in gap_seconds.items()),
                       key=lambda kv: -kv[1])
    return Summary(window_ns=w1 - w0, devices=devices, host_gaps=host_gaps,
                   calls=len(calls))


def reduce_trace(path: str) -> Summary:
    return reduce_profile(load_profile(path))


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader is handed."""

    summary: Summary
    cell: object
    session: object
    calls: list  # (start, end, rounds, metrics) of the traced calls
    device_kind: str
