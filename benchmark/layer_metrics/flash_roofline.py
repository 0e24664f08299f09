"""The flash attention kernels' share of their roofline: the least time the
chip could take for the FLOPs of the (query, key) pairs the masks need
(exact causal and window pair counts; a pair costs a q head 4 x head size
FLOPs forward and 10 x head size backward: two and five matmuls) over the
kernels' device time (``fedml_tpu/ops/flash_attention.py``: the Pallas
custom calls ``flash_fwd`` and ``flash_bwd``).  Skipped blocks earn nothing
and the masked half of an edge block reads as loss.  Nothing where attention
ran as lax ops, or for a family that does not count its pairs.  The family
says what it counts: ``attention_pairs_per_sample(config)`` and
``attention_heads(config)`` = (q heads, head size)."""

from benchmark import cells, fed_scopes, peaks, traffic
from benchmark.layer_metrics.step_mfu_pct import computed_units


def in_kernel(op) -> bool:
    return (op.klass == "attention"
            and op.stats.get("hlo_category") == "custom-call"
            and "pallas_call" in fed_scopes.tf_op(op))


def read(ctx):
    seconds = ctx.summary.seconds_where(in_kernel) * len(ctx.summary.devices)
    family = cells.load_family(ctx.cell.config)
    if not seconds or not hasattr(family, "attention_pairs_per_sample"):
        return None
    samples = computed_units(ctx) / traffic.units_per_sample(ctx.cell.config)
    heads, head_size = family.attention_heads(ctx.cell.config)
    flops = (family.attention_pairs_per_sample(ctx.cell.config) * samples
             * (4 + 10) * head_size * heads)
    peak = peaks.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / peak / seconds
