"""Device time of a cell's trace by part of the model step x op class x
direction (ROADMAP A4's table): for each ``model.*`` scope that holds ops of
the class asked for, forward and backward apart, the seconds a device; under
each row its longest ops with their full ``tf_op``; last the seconds under no
``model.*`` scope, a row a stage of the round in brackets (``(fed.model)``:
what ``model_scope_coverage_pct`` finds no part for), so that the rows sum to
the class (or, for ``--class all``, to the device's busy time).  The class is ``matmul`` unless
``--class`` names another of ``trace_rules.json`` or ``all``, which is how
the scopes no metric reads are read: ``model.norm``, ``model.attn_full``, a
group's seconds outside its products, the embedding's scatter-add.

With ``--result``, a file whose last line is the result line the traced run
printed (``run.py --trace 1``), a ``matmul`` row also gets the FLOPs
``dense_groups.py`` credits the group for the tokens computed and the share
of its roofline.  Tokens and peaks are the metrics' own: the line says the
cell, the seed, the rounds and the device kind, and the tool hands
``computed_units`` a ``Context`` over the cell's resident block
(``traffic.resident_block``, what the accepted drivers pack).  A trace alone
says neither what was computed nor on which chip, so without the line the
table is seconds.  A scope the configuration credits no dense product
(``model.kda_scan``: the chunked scan's inner dots) has seconds and no
roofline.  ``F`` or ``B`` says which side bounds the group's least time,
FLOPs or bytes.  A ``B`` row's share is of its FLOP side alone: the bytes are
``train_bytes_per_unit``'s rule, an input read once a product, good to say
which side bounds and too many where one fusion reads an input for several
products (the linear-attention gates' three read 139-174 by it).  The
metrics' groups are ``F`` rows.

    python3 benchmark/tools/matmul_table.py <trace dir or .xplane.pb> [top] [--class matmul|all|<class>] [--result <file>]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

NO_STAGE = "(no fed.* scope)"
ALL = "all"


def table(summary, klass: str = "matmul") -> dict:
    """{(scope, op class, backward): (seconds a device, {full tf_op and op
    name: seconds})} of the ops of ``klass``, or of every op; the scope of
    an op with no ``model.*`` one is its stage in brackets, or NO_STAGE."""
    from benchmark import fed_scopes, model_scopes

    rows, n = {}, len(summary.devices)
    for dev in summary.devices:
        for op in dev.ops:
            if klass not in (ALL, op.klass):
                continue
            stage = fed_scopes.innermost(op)
            key = (model_scopes.innermost(op) or (
                f"({stage})" if stage else NO_STAGE), op.klass,
                fed_scopes.is_backward(op))
            seconds, names = rows.get(key, (0.0, {}))
            name = f"{fed_scopes.tf_op(op)}  <{op.name.split(' ')[0]}>"
            names[name] = names.get(name, 0.0) + op.self_ns / n / 1e9
            rows[key] = (seconds + op.self_ns / n / 1e9, names)
    return rows


def context_of(result: dict, summary):
    """The ``Context`` a reader of the run that printed ``result`` was
    handed, as far as ``computed_units`` and the peaks ask: the cell, its
    resident block, the traced calls' rounds, the device kind."""
    from benchmark import cells, trace_reduce, traffic
    from benchmark.drivers.base import BaseSession

    detail = result["detail"]
    cell = cells.load_cell(detail["cell"])
    session = BaseSession()
    session.block = traffic.resident_block(cell.config, cell.geometry,
                                           detail["seed"])
    rounds, calls = detail["rounds"], detail["calls"]
    if calls != summary.calls or rounds % calls:
        raise SystemExit(f"the line counts {calls} calls of {rounds} rounds "
                         f"and the trace {summary.calls}: another run's?")
    return trace_reduce.Context(
        summary=summary, cell=cell, session=session,
        calls=[(0.0, 0.0, rounds // calls, {})] * calls,
        device_kind=result["device"]["kind"])


def main() -> None:
    from benchmark import dense_groups, peaks, trace_reduce, traffic
    from benchmark.layer_metrics.step_mfu_pct import computed_units

    p = argparse.ArgumentParser()
    p.add_argument("path")
    p.add_argument("top", nargs="?", type=int, default=3)
    p.add_argument("--class", dest="klass", default="matmul")
    p.add_argument("--result")
    args = p.parse_args()
    path = args.path
    if os.path.isdir(path):
        (path,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True)
    summary = trace_reduce.reduce_trace(path)
    n = len(summary.devices)
    rows = table(summary, args.klass)
    whole = summary.busy_s if args.klass == ALL else summary.seconds_where(
        lambda op: op.klass == args.klass)
    head = (f"{summary.calls} call(s), {n} device(s); busy "
            f"{summary.busy_s:.6f} s a device, class {args.klass} "
            f"{whole:.6f} s ({100 * whole / summary.busy_s:.2f} %)")
    credited = {}
    if args.result:
        with open(args.result) as f:
            result = json.loads(f.read().strip().splitlines()[-1])
        ctx = context_of(result.get("result", result), summary)
        config, units = ctx.cell.config, computed_units(ctx)
        batch_units = (ctx.cell.geometry["batch"]
                       * traffic.units_per_sample(config))
        pk = peaks.peaks(ctx.device_kind)
        credited = dense_groups.fwd_flops(config)
        head = (f"{ctx.cell.name} on {ctx.device_kind}: {units:.0f} tokens "
                f"computed; " + head)
    print(head)
    print(f"{'scope':<20} {'class':<14} {'':<3} {'seconds':>10} "
          f"{'% class':>8} {'TFLOP':>9} {'roofline %':>10}")
    by_scope = {}
    for (scope, _, _), (s, _) in rows.items():
        by_scope[scope] = by_scope.get(scope, 0.0) + s
    order = sorted(by_scope, key=lambda s: (s[0] == "(", -by_scope[s]))

    def line(scope, klass, tag, seconds, passes):
        group = scope[len(dense_groups.PREFIX):]
        work = f"{'-':>9} {'-':>10}"
        if klass == "matmul" and credited.get(group) and seconds:
            least = dense_groups.least_seconds(config, group, units,
                                               batch_units, pk, passes)
            tflop = passes * credited[group] * units / 1e12
            by_flops = tflop * 1e12 / pk["bf16_flops_per_s"]
            work = (f"{tflop:>9.3f} {100 * by_flops / (seconds * n):>8.2f} "
                    f"{'F' if least <= by_flops else 'B'}")
        print(f"{scope:<20} {klass:<14} {tag:<3} {seconds:>10.6f} "
              f"{100 * seconds / whole:>8.2f} {work}")

    def both(scope, klass):
        return [rows.get((scope, klass, b), (0.0, {})) for b in (False, True)]

    for scope in order:
        if args.klass == ALL:
            line(scope, ALL, "", by_scope[scope], 3)
        classes = {k for s, k, _ in rows if s == scope}
        for klass in sorted(classes, key=lambda k: -sum(
                s for s, _ in both(scope, k))):
            fwd, bwd = both(scope, klass)
            line(scope, klass, "", fwd[0] + bwd[0], 3)
            for tag, (seconds, names), passes in (("fwd", fwd, 1),
                                                  ("bwd", bwd, 2)):
                if not seconds:
                    continue
                line(scope, klass, tag, seconds, passes)
                for name in sorted(names, key=lambda k: -names[k])[:args.top]:
                    print(f"      {names[name]:>10.6f}  {name}")
    total = sum(by_scope.values())
    print(f"sum of the rows {total:.6f} s = the class's {whole:.6f} s"
          if abs(total - whole) < 1e-9 * max(whole, 1.0) else
          f"THE ROWS SUM TO {total:.6f} s, THE CLASS TO {whole:.6f} s")


if __name__ == "__main__":
    main()
