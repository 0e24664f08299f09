"""``nemotron_h``'s dense products by the part of the model step that has
them (``families/nemotron_h.layer_counts``): the head; a Mamba layer's
in_proj (``ssm_proj``) and out_proj (``ssm_out``); an attention layer's fused
q/k/v and output (``attn_proj``); an expert layer's router, its latent down-
and up-projections (``moe_latent``) and its shared expert; a dense layer's
MLP.  The experts' products are the ``expert_matmul`` class's, and the
chunked scan's dots earn nothing here."""

from benchmark.families import nemotron_h


def products(c: dict) -> dict:
    n = nemotron_h.layer_counts(c)
    h = c["hidden_size"]
    H, P = c["mamba_num_heads"], c["mamba_head_dim"]
    G, N = c["n_groups"], c["ssm_state_size"]
    Hq, Hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    latent = c["moe_latent_size"]
    shared = c["moe_shared_expert_intermediate_size"]
    wide = c["intermediate_size"]
    return {
        "head": [(h, c["vocab_size"])],
        "ssm_proj": n["mamba"] * [(h, 2 * H * P + 2 * G * N + H)],
        "ssm_out": n["mamba"] * [(H * P, h)],
        "attn_proj": n["attention"] * [(h, (Hq + 2 * Hkv) * d), (Hq * d, h)],
        "moe_router": n["sparse"] * [(h, c["n_routed_experts"])],
        "moe_latent": n["sparse"] * [(h, latent), (latent, h)],
        "moe_shared": n["sparse"] * [(h, shared), (shared, h)],
        "mlp_dense": n["dense"] * [(h, wide), (wide, h)],
    }
