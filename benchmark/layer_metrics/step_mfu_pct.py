"""Model FLOPs of the traced calls (forward + backward of every sample
computed, padding included, recomputation not) / device BUSY seconds /
bf16 peak.  With ``device_idle_pct`` it splits end-to-end utilisation into
slow-while-busy and not-busy."""

from benchmark import flops, peaks, traffic


def computed_units(ctx) -> float:
    return (sum(c[2] for c in ctx.calls)
            * ctx.session.padded_samples_per_round()
            * traffic.units_per_sample(ctx.cell.config))


def read(ctx):
    total = sum(flops.train_flops_per_unit(ctx.cell.config).values())
    peak = peaks.peaks(ctx.device_kind)["bf16_flops_per_s"]
    busy = ctx.summary.busy_s * len(ctx.summary.devices)
    return 100.0 * total * computed_units(ctx) / busy / peak
