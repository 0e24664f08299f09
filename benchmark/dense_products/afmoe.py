"""``afmoe``'s dense products by the part of the model step that has them
(``families/afmoe.layer_counts``): the head; every layer's fused q/k/v and
output products (``attn_proj``) and, a group of its own under its own scope,
the gate's projection (``attn_gate``); the leading layers' dense MLP; a sparse
layer's router and shared expert.  The experts' products are the
``expert_matmul`` class's, not this one's."""

from benchmark.families import afmoe


def products(c: dict) -> dict:
    n = afmoe.layer_counts(c)
    h, d = c["hidden_size"], c["head_dim"]
    H, G = c["num_attention_heads"], c["num_key_value_heads"]
    f = c["moe_intermediate_size"] * c["num_shared_experts"]
    wide = c["intermediate_size"]
    return {
        "head": [(h, c["vocab_size"])],
        "attn_proj": c["n_layer"] * [(h, (H + 2 * G) * d), (H * d, h)],
        "attn_gate": c["n_layer"] * [(h, H * d)],
        "mlp_dense": n["dense"] * [(h, wide), (h, wide), (wide, h)],
        "moe_router": n["sparse"] * [(h, c["num_experts_routed"])],
        "moe_shared": n["sparse"] * [(h, f), (h, f), (f, h)],
    }
