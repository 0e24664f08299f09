"""The dense matmuls' share of their roofline: the least time the chip
could take for their FLOPs and bytes (``flops.py``, ``peaks.py``) over the
device time the trace gives the class.  At these shapes FLOPs bound it."""

from benchmark import flops, peaks, traffic
from benchmark.layer_metrics.step_mfu_pct import computed_units


def read(ctx):
    seconds = ctx.summary.seconds_where(
        lambda op: op.klass == "matmul") * len(ctx.summary.devices)
    if not seconds:
        return None
    pk = peaks.peaks(ctx.device_kind)
    units = computed_units(ctx)
    batch_units = (ctx.cell.geometry["batch"]
                   * traffic.units_per_sample(ctx.cell.config))
    f = flops.train_flops_per_unit(ctx.cell.config)["matmul"] * units
    b = flops.train_bytes_per_unit(ctx.cell.config,
                                   batch_units).get("matmul", 0.0) * units
    least = max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds
