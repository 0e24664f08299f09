"""``kimi_linear``'s dense products by the kind of layer that has them
(``families/kimi_linear.layer_counts``): the head; a latent layer's q/k/v
(``mla_proj``) and its output product (``attn_proj``); a linear-attention
layer's projections, gates and output; the first layers' dense MLP; a sparse
layer's router and shared expert."""

from benchmark.families import kimi_linear, kimi_linear_plain


def products(c: dict) -> dict:
    n = kimi_linear.layer_counts(c)
    h, H = c["hidden_size"], c["num_attention_heads"]
    heads = kimi_linear_plain.linear_heads(c)
    d = c["linear_attn_config"]["head_dim"]
    rank, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, v = c["qk_nope_head_dim"], c["v_head_dim"]
    f = c["moe_intermediate_size"] * c["num_shared_experts"]
    wide = c["intermediate_size"]
    return {
        "head": [(h, c["vocab_size"])],
        "attn_proj": n["mla"] * [(H * v, h)],
        "mlp_dense": n["dense"] * [(h, wide), (h, wide), (wide, h)],
        "mla_proj": n["mla"] * [(h, H * (nope + rope)), (h, rank + rope),
                                (rank, H * (nope + v))],
        "kda_proj": n["kda"] * 3 * [(h, heads * d)],
        # the decay's and the output gate's low-rank pairs, and beta
        "kda_gates": n["kda"] * (2 * [(h, d), (d, heads * d)] + [(h, heads)]),
        "kda_out": n["kda"] * [(heads * d, h)],
        "moe_router": n["sparse"] * [(h, c["num_experts_routed"])],
        "moe_shared": n["sparse"] * [(h, f), (h, f), (f, h)],
    }
