"""How many of a sequence's tiles a sparse-attention layer's choice scored:
``100 x select_tiles_scored`` of the traced calls ``/`` (layer-steps x
sequences a step x every tile of a sequence, ``n x n`` at ``n`` tiles of 512 a
side).  The counter is each layer's count of tiles whose index scores were
computed, summed over layers, steps and clients.  It says which form of the
choice the cell ran, which the seconds alone would not: the kernel scores a
row block against its causal key tiles only, ``n (n + 1) / 2`` (53.125 at
8192 positions), the lax form every tile and masks afterwards (100).  Nothing
where the program has no such counter."""

COUNTER = "select_tiles_scored"
TILE = 512


def read(ctx):
    if not ctx.calls or not all(COUNTER in c[3] for c in ctx.calls):
        return None
    config = ctx.cell.config
    length = config["n_positions"]
    side = length // TILE if length % TILE == 0 else 1
    scored = float(sum(c[3][COUNTER].sum() for c in ctx.calls))
    sequences = (sum(c[2] for c in ctx.calls)
                 * ctx.session.padded_samples_per_round())
    return 100.0 * scored / (sequences * config["n_layer"] * side * side)
