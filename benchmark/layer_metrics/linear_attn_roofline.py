"""The linear-attention scan's share of its roofline: the least time the chip
could take for **the recurrence's own** FLOPs and bytes over the device time
of every op under ``model.kda_scan`` (``ops/linear_attention.py``'s chunked
scan: cumulative sums, pair sums, the triangular solve, the scan over chunks,
forward and backward).  The family counts the work: a head's three
matrix-vector products with its state a token
(``linear_attn_flops_per_token``), q, k, v and g in and o out once a pass
(``linear_attn_bytes_per_token``), three passes a training step.  What the
chunked form computes besides earns nothing, so the share reads the same work
whatever implements the scan, lax ops or a kernel, and can only read low.
Nothing where no op carries the scope or the family does not count."""

from benchmark import cells, fed_scopes, peaks
from benchmark.layer_metrics.step_mfu_pct import computed_units

SCAN = "model.kda_scan"


def in_scan(op) -> bool:
    return SCAN in fed_scopes.tf_op(op)


def read(ctx):
    seconds = ctx.summary.seconds_where(in_scan) * len(ctx.summary.devices)
    family = cells.load_family(ctx.cell.config)
    if not seconds or not hasattr(family, "linear_attn_flops_per_token"):
        return None
    config = ctx.cell.config
    token_layers = computed_units(ctx) * family.layer_counts(config)["kda"]
    pk = peaks.peaks(ctx.device_kind)
    f = 3 * family.linear_attn_flops_per_token(config) * token_layers
    b = 3 * family.linear_attn_bytes_per_token(config) * token_layers
    least = max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds
