"""The flash attention kernels' share of their roofline: the least time the
chip could take for the FLOPs of the (query, key) pairs the masks need
(exact causal and window pair counts; a pair costs a q head 4 x head_dim
FLOPs forward and 10 x head_dim backward: two and five matmuls) over the
kernels' device time.  Skipped blocks earn nothing and the masked half of an
edge block reads as loss.  Nothing where attention ran as lax ops, or for a
family that does not count its pairs."""

from benchmark import cells, peaks, traffic
from benchmark.layer_metrics.attention_kernel_pct import in_kernel
from benchmark.layer_metrics.step_mfu_pct import computed_units


def read(ctx):
    seconds = ctx.summary.seconds_where(in_kernel) * len(ctx.summary.devices)
    family = cells.load_family(ctx.cell.config)
    if not seconds or not hasattr(family, "attention_pairs_per_sample"):
        return None
    samples = computed_units(ctx) / traffic.units_per_sample(ctx.cell.config)
    flops = (family.attention_pairs_per_sample(ctx.cell.config) * samples
             * (4 + 10) * ctx.cell.config["head_dim"]
             * ctx.cell.config["num_attention_heads"])
    peak = peaks.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / peak / seconds
