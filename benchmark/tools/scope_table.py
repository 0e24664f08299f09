"""Device time of a trace by stage of the federated round: seconds and share
of busy time by innermost ``fed.*`` scope x op class, forward and backward
apart, then the ops that carry no scope, by name (or, with a scope as the
third argument, the ops of that scope: which op a stage's time is).  It shows
what no metric reads yet (``fed.codec``, ``fed.agg_transform``, a scope a
later PR adds).

    python3 benchmark/tools/scope_table.py <trace dir, .xplane.pb or .textproto> [top [scope]]

The trace's calls are marked by the benchmark's ``bench.call`` annotation or
by the ``fed.traced_round`` of ``fedml_tpu.obs.jax_hooks.trace_rounds``.
"""

from __future__ import annotations

import glob
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

ROUND_ANNOTATION = "fed.traced_round"  # what ``trace_rounds`` wraps a round in


def rules_for(profile) -> dict:
    """The rules as loaded, or a copy that takes ``trace_rounds``'s
    annotation for the call's where the trace has no ``bench.call``."""
    from benchmark import trace_reduce

    rules = trace_reduce.load_rules()
    if trace_reduce.host_events(profile, rules):
        return rules
    return {**rules, "call_annotation": ROUND_ANNOTATION,
            "host_spans": rules["host_spans"] + [r"^fed\.traced_round$"]}


def table(summary, listed=None) -> tuple:
    """({(scope, "bwd" or "", op class): seconds a device}, {name of an op
    whose innermost scope is ``listed``: seconds a device}); the scope None
    is that of the ops without one, which are in no row."""
    from benchmark import fed_scopes

    rows, names, n = {}, {}, len(summary.devices)
    for dev in summary.devices:
        for op in dev.ops:
            scope, seconds = fed_scopes.innermost(op), op.self_ns / n / 1e9
            if scope == listed:
                names[op.name] = names.get(op.name, 0.0) + seconds
            if scope is None:
                continue
            key = (scope, "bwd" if fed_scopes.is_backward(op) else "",
                   op.klass)
            rows[key] = rows.get(key, 0.0) + seconds
    return rows, names


def main() -> None:
    from benchmark import trace_reduce

    path = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    listed = sys.argv[3] if len(sys.argv) > 3 else None
    if os.path.isdir(path):
        (path,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True)
    profile = trace_reduce.load_profile(path)
    summary = trace_reduce.reduce_profile(profile, rules_for(profile))
    rows, names = table(summary, listed)
    busy = summary.busy_s
    print(f"{summary.calls} call(s), {len(summary.devices)} device(s): window "
          f"{summary.window_s:.6f} s, busy {busy:.6f} s a device, in scopes "
          f"{100 * sum(rows.values()) / busy:.3f} %")
    print(f"{'scope':<18} {'':<3} {'class':<10} {'seconds':>10} {'% busy':>8}")
    by_scope = {}
    for (scope, _, _), s in rows.items():
        by_scope[scope] = by_scope.get(scope, 0.0) + s
    for key in sorted(rows, key=lambda k: (-by_scope[k[0]], k[0], -rows[k])):
        print(f"{key[0]:<18} {key[1]:<3} {key[2]:<10} {rows[key]:>10.6f} "
              f"{100 * rows[key] / busy:>8.3f}")
    rest = sum(names.values())
    print(f"{listed or 'no scope'}: {rest:.6f} s, {100 * rest / busy:.3f} % of "
          f"busy time; the {min(top, len(names))} longest of {len(names)} names")
    for name in sorted(names, key=lambda n: -names[n])[:top]:
        print(f"  {names[name]:>10.6f} {100 * names[name] / busy:>8.3f}  {name}")


if __name__ == "__main__":
    main()
