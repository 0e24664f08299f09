"""How much of the state-space scan runs in Pallas kernels: 100 x the device
seconds of the Pallas custom calls under ``model.ssm_scan`` (the forward and
backward kernels of ``ops/ssd.py``) over the seconds of every op under
``model.ssm_scan``.  The rest is what is still lax ops around the kernels:
pads, the decays' product ``dt A`` and layout copies.  Nothing where no op
carries the scope; 0 where the scan runs as lax ops alone."""

from benchmark import fed_scopes
from benchmark.layer_metrics.ssm_scan_roofline import in_scan


def in_kernel(op) -> bool:
    return (in_scan(op) and "pallas_call" in fed_scopes.tf_op(op)
            and op.stats.get("hlo_category") == "custom-call")


def read(ctx):
    seconds = ctx.summary.seconds_where(in_scan)
    if not seconds:
        return None
    return 100.0 * ctx.summary.seconds_where(in_kernel) / seconds
