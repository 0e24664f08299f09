"""The experts' grouped matrix products' share of their roofline: the least
time the chip could take for their FLOPs and bytes over the device time of
the grouped-product kernels (the custom calls under ``model.moe_experts``).
The FLOPs are those of the token-expert assignments the traced calls really
made (the program's ``moe_assignments_held`` counter), forward + backward,
not of an expectation; rows of the buffer past them earn nothing."""

from benchmark import cells, fed_scopes, peaks
from benchmark.layer_metrics.expert_pct import EXPERTS

COUNTER = "moe_assignments_held"


def in_kernel(op) -> bool:
    return (EXPERTS in fed_scopes.tf_op(op)
            and op.stats.get("hlo_category") == "custom-call")


def assignments(ctx):
    """Held assignments of the traced calls, or None without the counter."""
    if not all(COUNTER in c[3] for c in ctx.calls):
        return None
    return float(sum(c[3][COUNTER].sum() for c in ctx.calls))


def read(ctx):
    seconds = ctx.summary.seconds_where(in_kernel) * len(ctx.summary.devices)
    made = assignments(ctx)
    if not seconds or not made:
        return None
    family = cells.load_family(ctx.cell.config)
    pk = peaks.peaks(ctx.device_kind)
    layer_steps = (sum(c[2] for c in ctx.calls) * ctx.cell.config["n_layer"]
                   * ctx.session.padded_samples_per_round()
                   / ctx.cell.geometry["batch"])
    f = 3 * family.expert_flops_per_assignment(ctx.cell.config) * made
    b = family.expert_train_bytes(ctx.cell.config, made, layer_steps)
    least = max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds
