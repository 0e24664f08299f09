"""How much of the linear-attention scan runs in Pallas kernels: 100 x the
device seconds of the Pallas custom calls under ``model.kda_scan`` (as
``latent_attn_roofline.in_kernel`` picks them under its scope: the pair sums'
and the recurrence's kernels of ``ops/linear_attention.py``, forward and
backward) over the seconds of every op under ``model.kda_scan``.  The rest is
what is still lax ops around the kernels: the triangular solve and its
transpose, pads and layout copies.  Nothing where no op carries the scope;
0 where the scan runs as lax ops alone."""

from benchmark import fed_scopes
from benchmark.layer_metrics.linear_attn_roofline import in_scan


def in_kernel(op) -> bool:
    return (in_scan(op) and "pallas_call" in fed_scopes.tf_op(op)
            and op.stats.get("hlo_category") == "custom-call")


def read(ctx):
    seconds = ctx.summary.seconds_where(in_scan)
    if not seconds:
        return None
    return 100.0 * ctx.summary.seconds_where(in_kernel) / seconds
