"""Cut a recorded ``.xplane.pb`` down to a small text-form trace that can be
checked in as test data: only what ``trace_reduce`` reads (the device op
lines with the class fields, the host line with the call annotations and
the known host spans), clipped to ``[start, start + length)`` milliseconds
after the first ``bench.call`` begins.

    python3 benchmark/tools/crop_trace.py <in.xplane.pb> <out.textproto> <start_ms> <length_ms>
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def quote(text: str) -> str:
    return json.dumps(str(text))


def main() -> None:
    from benchmark import trace_reduce

    src, dst, start_ms, length_ms = sys.argv[1:5]
    rules = trace_reduce.load_rules()
    profile = trace_reduce.load_profile(src)
    events = trace_reduce.host_events(profile, rules)
    t0 = min(s for n, s, _ in events if n == rules["call_annotation"])
    lo = t0 + float(start_ms) * 1e6
    hi = lo + float(length_ms) * 1e6
    host = [re.compile(p) for p in rules["host_spans"]]
    keep = ("hlo_category", "tf_op", "deduplicated_name")
    out = []
    for pid, plane in enumerate(profile, 1):
        device = re.search(rules["device_plane"], plane.name)
        if not device and not re.search(rules["host_plane"], plane.name):
            continue
        metas, lines = {}, []  # event name -> (id, its kept stats)
        for lid, line in enumerate(plane.lines, 1):
            if device and not re.search(
                    rules["op_line"] + "|" + rules["async_line"], line.name):
                continue
            evs, has_call = [], False
            for e in line.events:
                if e.end <= lo or e.start >= hi:
                    continue
                if not device and not any(p.search(e.name) for p in host):
                    continue
                s, t = max(e.start, lo), min(e.end, hi)
                has_call |= e.name == rules["call_annotation"]
                mid, _ = metas.setdefault(e.name, (len(metas) + 1, e.stats))
                evs.append(f"    events {{ metadata_id: {mid} offset_ps: "
                           f"{int((s - lo) * 1000)} duration_ps: "
                           f"{int((t - s) * 1000)} }}")
            if evs and (device or has_call):
                lines.append(f"  lines {{ id: {lid} name: {quote(line.name)} "
                             f"timestamp_ns: 0\n" + "\n".join(evs) + "\n  }")
        if not lines:
            continue
        meta = []
        for name, (mid, stats) in metas.items():
            kept = "".join(f" stats {{ metadata_id: {keep.index(k) + 1} "
                           f"str_value: {quote(stats[k])} }}"
                           for k in keep if stats.get(k))
            meta.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                        f"name: {quote(name)}{kept} }} }}")
        meta += [f"  stat_metadata {{ key: {i} value {{ id: {i} name: "
                 f"{quote(k)} }} }}" for i, k in enumerate(keep, 1)]
        out.append(f"planes {{\n  id: {pid}\n  name: {quote(plane.name)}\n"
                   + "\n".join(lines + meta) + "\n}")
    with open(dst, "w") as f:
        f.write("\n".join(out) + "\n")
    print(f"{dst}: {os.path.getsize(dst)} bytes, window "
          f"{(hi - lo) / 1e6:.1f} ms")


if __name__ == "__main__":
    main()
