"""The attention kernels' share of their roofline in a layer whose keys are
chosen: as ``flash_roofline``, the least time the chip could take for the
FLOPs of the **chosen** (query, key) pairs (the family's
``attention_pairs_per_sample``; a pair costs a q head 4 x head size FLOPs
forward and 10 x backward, ``attention_heads``) over the device time of the
Pallas custom calls under ``model.attn_sparse`` (``flash_fwd`` and
``flash_bwd`` with a choice).  A masked pair earns nothing, so the share reads
the same work whatever computes it and only low: while no tile can be skipped
a kernel computes every causal pair of a tile for the chosen ones' credit.
Nothing where the layer ran as lax ops, or for a family that does not count
its pairs."""

from benchmark import cells, fed_scopes, peaks, traffic
from benchmark.layer_metrics.step_mfu_pct import computed_units

SPARSE = "model.attn_sparse"


def in_kernel(op) -> bool:
    name = fed_scopes.tf_op(op)
    return (SPARSE in name and "pallas_call" in name
            and op.stats.get("hlo_category") == "custom-call")


def read(ctx):
    seconds = ctx.summary.seconds_where(in_kernel) * len(ctx.summary.devices)
    family = cells.load_family(ctx.cell.config)
    if not seconds or not hasattr(family, "attention_pairs_per_sample"):
        return None
    config = ctx.cell.config
    samples = computed_units(ctx) / traffic.units_per_sample(config)
    heads, head_size = family.attention_heads(config)
    flops = (family.attention_pairs_per_sample(config) * samples
             * (4 + 10) * head_size * heads)
    peak = peaks.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / peak / seconds
