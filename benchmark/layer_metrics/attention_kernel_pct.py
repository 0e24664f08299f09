"""Share of the attention class's device seconds spent in the fused flash
kernels (``fedml_tpu/ops/flash_attention.py``: the Pallas custom calls of
the forward, dQ and dK/dV): whether the model's attention policy sent the
cell's shape to them.  0 where attention runs as lax ops; the rest of the
class is what stays outside the kernels (``delta = sum(o * do)``, the
slices of the fused qkv projection, layout copies).  Nothing where the
trace has no attention class."""

from benchmark import fed_scopes


def in_kernel(op) -> bool:
    return (op.klass == "attention"
            and op.stats.get("hlo_category") == "custom-call"
            and "pallas_call" in fed_scopes.tf_op(op))


def read(ctx):
    s = ctx.summary
    attention = s.seconds_where(lambda op: op.klass == "attention")
    if not attention:
        return None
    return 100.0 * s.seconds_where(in_kernel) / attention
