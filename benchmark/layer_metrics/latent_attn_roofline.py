"""The latent-attention layer's flash kernels' share of their roofline: the
least time the chip could take for the FLOPs of the causal (query, key) pairs
(the family's ``attention_pairs_per_sample``; a pair costs a q head what
``latent_pair_flops`` says, forward and backward: scores over the q/k head
size, values over the v head size) over the device time of the Pallas custom
calls under ``model.attn_latent`` (``flash_fwd`` and ``flash_bwd`` with a v
head size of its own).  Skipped blocks earn nothing, the masked half of an
edge block and the lanes of the other head in a column block read as loss.
Nothing where the layer ran as lax ops, or for a family that does not count
its pairs."""

from benchmark import cells, fed_scopes, peaks, traffic
from benchmark.layer_metrics.step_mfu_pct import computed_units

LATENT = "model.attn_latent"


def in_kernel(op) -> bool:
    name = fed_scopes.tf_op(op)
    return (LATENT in name and "pallas_call" in name
            and op.stats.get("hlo_category") == "custom-call")


def read(ctx):
    seconds = ctx.summary.seconds_where(in_kernel) * len(ctx.summary.devices)
    family = cells.load_family(ctx.cell.config)
    if not seconds or not hasattr(family, "latent_pair_flops"):
        return None
    config = ctx.cell.config
    samples = computed_units(ctx) / traffic.units_per_sample(config)
    flops = (family.attention_pairs_per_sample(config) * samples
             * sum(family.latent_pair_flops(config))
             * config["num_attention_heads"])
    peak = peaks.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / peak / seconds
