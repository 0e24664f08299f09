"""The ``jax.named_scope`` names of a federated round's stages.

A scope is a path segment in an op's ``op_name`` (the profiler's ``tf_op``):
debug info, not part of the program, so it costs nothing at run time and
the compile cache's key does not hold it.  Inside ``value_and_grad`` JAX
wraps the name: ``jvp(fed.model)`` forward, ``transpose(jvp(fed.model))``
backward.  Read by ``benchmark/fed_scopes.py`` and
``benchmark/tools/scope_table.py``.
"""

ROUNDS = "fed.rounds"  # make_multi_round_fn: the scan over fused rounds
ROUND = "fed.round"  # round_fn: the whole round, key folding included
CLIENTS = "fed.clients"  # the client loop around the updates (and the [K, ...] stack, where kept)
LOCAL_UPDATE = "fed.local_update"  # one client's local_update call
CODEC = "fed.codec"  # the lossy-uplink round trip and its error feedback
AGG_TRANSFORM = "fed.agg_transform"  # aggregate_transform with its keys
AGGREGATE = "fed.aggregate"  # weights, weighted sum (in the client loop: a multiply-add a client), psums, guarded divide
SERVER_UPDATE = "fed.server_update"  # server_update(old, agg, opt_state)
METRICS = "fed.metrics"  # the train_metrics sums and their psum
SHUFFLE = "fed.shuffle"  # an epoch's permutation gather and augment_fn
STEP = "fed.step"  # one optimizer step: the whole step_body
CAST = "fed.cast"  # masters and inputs to compute_dtype, new state back
MODEL = "fed.model"  # bundle.apply_train
LOSS = "fed.loss"  # loss_fn and the proximal term
OPTIMIZER = "fed.optimizer"  # optimizer.update, apply_updates, has_real blend

SCOPES = (ROUNDS, ROUND, CLIENTS, LOCAL_UPDATE, CODEC, AGG_TRANSFORM,
          AGGREGATE, SERVER_UPDATE, METRICS, SHUFFLE, STEP, CAST, MODEL,
          LOSS, OPTIMIZER)

# Inside ``fed.model``: the parts of a decoder that differ from layer to
# layer (``models/decoder.py``) and the dense parts every model shares (both
# model files).  They must not match ``fed\.[a-z_]+``: a stage reader takes
# an op's last such segment as its stage, and these ops stay the model's.
# None goes around a whole block or mixer: ``benchmark/model_scopes.py``
# takes an op's last ``model\.[a-z_]+`` segment as its part.
ROPE = "model.rope"  # rotary tables and the rotation of q and k
ATTN_SLIDING = "model.attn_sliding"  # the attention function of a windowed layer
ATTN_FULL = "model.attn_full"  # the attention function of a full layer (and of TransformerLM's Block)
MOE_ROUTER = "model.moe_router"  # router logits, softmax, top-k, weights
MOE_DISPATCH = "model.moe_dispatch"  # grouping by expert and the gather of rows
MOE_EXPERTS = "model.moe_experts"  # the grouped matrix products and the gate
MOE_COMBINE = "model.moe_combine"  # rows back to tokens, summed over the k
MOE_SHARED = "model.moe_shared"  # the shared expert, beside the routed sum
MLP_DENSE = "model.mlp_dense"  # the MLP of a layer without experts, gated or not
ATTN_LATENT = "model.attn_latent"  # the attention function of a latent-attention layer
MLA_PROJ = "model.mla_proj"  # q, the latent down- and up-projections, the latent's norm
KDA_PROJ = "model.kda_proj"  # linear attention: q/k/v projections, convolutions, SiLU, L2 norms
KDA_GATES = "model.kda_gates"  # decay, beta and output-gate projections, softplus, sigmoids
KDA_SCAN = "model.kda_scan"  # ops.linear_attention.gated_delta_rule and nothing else
KDA_OUT = "model.kda_out"  # the gated norm a head and the output projection
EMBED = "model.embed"  # the token embedding lookup (and wpe and its add); its backward is the scatter-add
ATTN_PROJ = "model.attn_proj"  # MultiHeadAttention's fused q/k/v Dense with its split, and its output Dense
NORM = "model.norm"  # the norms before (and, where a block has them, after) the mixer and the MLP and the final norm, at their call sites
HEAD = "model.head"  # the vocabulary head's product (tok.attend, lm_head) and nothing else
ATTN_INDEXER = "model.attn_indexer"  # a sparse layer's three index projections, the index key's LayerNorm, the rotation of qI and kI
ATTN_SELECT = "model.attn_select"  # index scores, the choice of keys, the tile table, and nothing else
ATTN_SPARSE = "model.attn_sparse"  # the attention function of a layer whose keys are chosen: kernels or lax
ATTN_GATE = "model.attn_gate"  # a gated attention's fourth projection, its sigmoid and the multiply of the attention output, and nothing else
SSM_PROJ = "model.ssm_proj"  # a Mamba-2 mixer's in_proj, convolution, SiLU, splits and dt's softplus
SSM_SCAN = "model.ssm_scan"  # ops.ssd.ssd_chunked and nothing else
SSM_OUT = "model.ssm_out"  # the D skip, the gated group norm and out_proj
MOE_LATENT = "model.moe_latent"  # the routed experts' down-projection to their latent width and the sum's up-projection

MODEL_SCOPES = (ROPE, ATTN_SLIDING, ATTN_FULL, MOE_ROUTER, MOE_DISPATCH,
                MOE_EXPERTS, MOE_COMBINE, MOE_SHARED, MLP_DENSE, ATTN_LATENT,
                MLA_PROJ, KDA_PROJ, KDA_GATES, KDA_SCAN, KDA_OUT, EMBED,
                ATTN_PROJ, NORM, HEAD, ATTN_INDEXER, ATTN_SELECT, ATTN_SPARSE,
                ATTN_GATE, SSM_PROJ, SSM_SCAN, SSM_OUT, MOE_LATENT)
