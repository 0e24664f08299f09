"""``mellum_moe``'s dense products: the head, a layer's fused q/k/v (grouped
k/v heads) and output products, its router.  The experts' products are the
``expert_matmul`` class's, not this one's."""


def products(c: dict) -> dict:
    h, d = c["hidden_size"], c["head_dim"]
    H, G = c["num_attention_heads"], c["num_key_value_heads"]
    return {"head": [(h, c["vocab_size"])],
            "attn_proj": c["n_layer"] * [(h, (H + 2 * G) * d), (H * d, h)],
            "moe_router": c["n_layer"] * [(h, c["num_experts_routed"])]}
