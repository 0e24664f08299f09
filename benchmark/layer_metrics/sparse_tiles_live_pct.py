"""How many of a sparse-attention layer's causal tiles hold a chosen pair:
``100 x attn_tiles_live`` of the traced calls ``/`` (layer-steps x sequences a
step x the causal tiles of a sequence).  The counter is each layer's count of
512 x 512 tiles that hold a chosen pair, summed over layers, steps and
clients; a sequence of ``n`` tiles a side has ``n (n + 1) / 2`` causal ones
(136 at 8192 positions).  It says which regime the kernels were timed in:
near 100 under a fresh indexer, whose picks are scattered through every tile,
lower under a trained one, whose empty tiles a kernel steps over.  Nothing
where the program has no such counter."""

COUNTER = "attn_tiles_live"
TILE = 512


def read(ctx):
    if not ctx.calls or not all(COUNTER in c[3] for c in ctx.calls):
        return None
    config = ctx.cell.config
    length = config["n_positions"]
    side = length // TILE if length % TILE == 0 else 1
    live = float(sum(c[3][COUNTER].sum() for c in ctx.calls))
    sequences = (sum(c[2] for c in ctx.calls)
                 * ctx.session.padded_samples_per_round())
    causal_tiles = side * (side + 1) // 2
    return 100.0 * live / (sequences * config["n_layer"] * causal_tiles)
