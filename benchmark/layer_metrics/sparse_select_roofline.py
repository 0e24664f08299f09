"""The choice's share of its roofline: the least time the chip could take for
the index scores of the causal pairs (the family's ``index_flops_per_pair`` x
``causal_pairs_per_sample``, forward once, at the bf16 peak) and for reading
``qI``, ``kI`` and ``w`` and writing the choice once
(``index_bytes_per_token`` a token and layer), over the device seconds of the
ops under ``model.attn_select``.  The choosing itself earns nothing: the share
reads the same work under a sort, a bisection or a kernel, and only low.
Nothing where no op carries the scope or the family does not count."""

from benchmark import cells, model_scopes, peaks, traffic
from benchmark.layer_metrics.step_mfu_pct import computed_units

SCOPE = "model.attn_select"


def read(ctx):
    under = model_scopes.seconds(ctx, SCOPE)
    family = cells.load_family(ctx.cell.config)
    if not under or not hasattr(family, "index_flops_per_pair"):
        return None
    config = ctx.cell.config
    units = computed_units(ctx)
    samples = units / traffic.units_per_sample(config)
    pk = peaks.peaks(ctx.device_kind)
    f = (family.index_flops_per_pair(config)
         * family.causal_pairs_per_sample(config) * samples)
    b = family.index_bytes_per_token(config) * units * config["n_layer"]
    least = max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / (under * len(ctx.summary.devices))
