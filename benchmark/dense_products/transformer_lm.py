"""``transformer_lm``'s dense products: the weight-tied head, a layer's fused
q/k/v and output products, its MLP's two.  By ``dense_groups.pass_bytes``
these read 4 % under the family's ``train_bytes_per_unit``, which counts one
width more a layer than the four products have; FLOPs bound every group by a
factor of three or more, so no share moves."""


def products(c: dict) -> dict:
    d = c["n_embd"]
    inner = c.get("n_inner") or 4 * d
    return {"head": [(d, c["vocab_size"])],
            "attn_proj": c["n_layer"] * [(d, 3 * d), (d, d)],
            "mlp_dense": c["n_layer"] * [(d, inner), (inner, d)]}
