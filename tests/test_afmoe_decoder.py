"""The decoder's gated-attention family (``models/decoder.py``: a sigmoid gate
on the attention output, rotary positions on windowed layers and none on full
ones, a norm after each sublayer, scaled embeddings, a sigmoid router beside a
shared expert whose choice a selection bias shifts) against the plain
reference that lives with the benchmark (``benchmark/families/
afmoe_plain.py``): float32, seeded random weights, toy sizes."""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import lowered_programs  # noqa: E402
from test_decoder_kinds import round_of  # noqa: E402
from benchmark.families import afmoe_plain as plain  # noqa: E402
from fedml_tpu.models import decoder  # noqa: E402
from fedml_tpu.models.base import COUNTERS  # noqa: E402
from fedml_tpu.models.decoder import (  # noqa: E402
    ASSIGNMENTS_HELD, DENSE, FULL, SLIDING, SPARSE, TOKENS_BIAS_MOVED,
    DecoderConfig, ExpertLayer, decoder_lm,
)

PINNED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                      "lowered_parent_pr40.json")


def toy_config(**over):
    """The leading dense layer and one period after it (dense + sliding,
    MoE + sliding, MoE + sliding, MoE + full, MoE + sliding) at toy widths, 8
    q heads to a k/v head, in the published file's key names and this
    repository's own for what no published key states."""
    return {
        "vocab_size": 64, "hidden_size": 32, "n_layer": 5, "head_dim": 8,
        "num_attention_heads": 8, "num_key_value_heads": 1,
        "layer_types": 3 * ["sliding_attention"] + ["full_attention"],
        "sliding_window": 8, "rope_theta": 10000, "rope_scaling": None,
        "rms_norm_eps": 1e-5, "intermediate_size": 48,
        "moe_intermediate_size": 24, "num_dense_layers": 1,
        "num_experts": 8, "num_experts_routed": 8,
        "experts_held": list(range(8)), "num_experts_per_tok": 2,
        "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
        "route_scale": 2.826, "n_group": 1, "topk_group": 1,
        "mup_enabled": True, "n_positions": 32,
        "qk_norm": True, "attention_gate": True, "post_norm": True,
        "rope_layer_types": ["sliding_attention"],
        "selection_bias_init_std": 0.05, **over}


WHOLE = toy_config()
SHARE = toy_config(num_experts=4, experts_held=[1, 4, 6, 7])  # 4 of 8 held


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# -- the configuration ----------------------------------------------------------

def test_from_dict_reads_the_afmoe_keys():
    cfg = DecoderConfig.from_dict(WHOLE)
    assert cfg.layer_types == (SLIDING, SLIDING, SLIDING, FULL, SLIDING)
    assert cfg.mlp_types == (DENSE,) + 4 * (SPARSE,)  # num_dense_layers
    assert cfg.router_activation == "sigmoid"  # score_func
    assert cfg.norm_topk_prob  # route_norm
    assert cfg.routed_scaling_factor == 2.826  # route_scale
    assert cfg.embed_scale == math.sqrt(32)  # mup_enabled
    assert cfg.shared_expert_size == 24 and cfg.intermediate_size == 48
    assert cfg.attn_gate and cfg.qk_norm
    assert cfg.post_norm is True
    assert cfg.selection_bias == 0.05
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.sliding_window) == (8, 1, 8)
    # positions by layer kind: a full layer has no entry, so no rotation
    assert dict(cfg.rope) == {SLIDING: (("rope_theta", 10000),
                                        ("rope_type", "default"))}


def test_every_new_field_is_off_unless_a_key_states_it():
    from test_decoder import WHOLE as MELLUM

    cfg = DecoderConfig.from_dict(MELLUM)
    assert not cfg.attn_gate and not cfg.post_norm
    assert cfg.embed_scale == 1.0 and cfg.selection_bias is None
    # a top-level rope_theta with no list of kinds rotates every softmax kind
    both = DecoderConfig.from_dict({
        k: v for k, v in WHOLE.items() if k != "rope_layer_types"})
    assert set(dict(both.rope)) == {SLIDING, FULL, decoder.SELECTED}


@pytest.mark.parametrize("key", ["n_group", "topk_group"])
def test_experts_in_groups_are_refused_by_name(key):
    with pytest.raises(ValueError, match="n_group"):
        DecoderConfig.from_dict(toy_config(**{key: 2}))


# -- the decoder against the plain reference -----------------------------------------

@pytest.fixture(scope="module", params=[WHOLE, SHARE], ids=["whole", "share"])
def model(request):
    cfg = request.param
    bundle = decoder_lm(cfg)
    variables = bundle.init(jax.random.PRNGKey(0))
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    return cfg, bundle, variables, x


def test_init_holds_the_new_leaves_and_parameters_only(model):
    cfg, _, variables, _ = model
    assert set(variables) == {"params"}
    params = variables["params"]
    dense, sparse = params["Block_0"], params["Block_1"]
    for block in (dense, sparse):
        assert {"RMSNorm_0", "RMSNorm_1", "post_attn_norm",
                "post_mlp_norm"} <= set(block)
        attn = block["MultiHeadAttention_0"]
        assert set(attn) == {"Dense_0", "Dense_1", "gate", "q_norm", "k_norm"}
        assert attn["gate"]["kernel"].shape == (32, 8 * 8)
        assert attn["Dense_0"]["kernel"].shape == (32, (8 + 2) * 8)
    assert set(dense) - set(sparse) == {"mlp"}
    experts = sparse["ExpertLayer_0"]
    assert experts["selection_bias"].shape == (8,)  # the router's width
    assert experts["gate"].shape == (cfg["num_experts"], 32, 24)
    assert sparse["shared_expert"]["gate"]["kernel"].shape == (32, 24)
    assert "wpe" not in params


def test_the_embedding_the_bias_and_the_post_norms_start_at_the_stated_scales():
    wide = toy_config(hidden_size=64, vocab_size=512, num_experts_routed=512,
                      num_experts=2, experts_held=[0, 1], n_layer=2)
    params = decoder_lm(wide).init(jax.random.PRNGKey(2))["params"]
    table = params["wte"]["embedding"]
    # std 1 / sqrt(h): the scaled output has unit variance
    assert float(table.std()) * math.sqrt(64) == pytest.approx(1.0, rel=0.02)
    bias = params["Block_1"]["ExpertLayer_0"]["selection_bias"]
    assert float(bias.std()) == pytest.approx(0.05, rel=0.1)
    assert float(jnp.abs(bias).max()) > 0
    # the norms after the sublayers start at ``POST_NORM_INIT``, every other
    # norm at 1
    block = params["Block_1"]
    for name in ("post_attn_norm", "post_mlp_norm"):
        np.testing.assert_array_equal(block[name]["scale"],
                                      np.full((64,), decoder.POST_NORM_INIT, np.float32))
    for norm in (block["RMSNorm_0"], block["RMSNorm_1"], params["norm_f"],
                 block["MultiHeadAttention_0"]["q_norm"]):
        np.testing.assert_array_equal(norm["scale"], 1.0)


def test_forward_matches_the_plain_reference(model):
    cfg, bundle, variables, x = model
    logits, _ = bundle.apply_train(variables, x)
    assert rel(logits, plain.forward(cfg, variables["params"], x)) < 1e-5
    assert rel(bundle.apply_eval(variables, x), logits) < 1e-6


def grads_of(forward, params, x):
    y = jnp.roll(x, -1, axis=-1)

    def loss(params):
        logp = jax.nn.log_softmax(forward(params))
        return -jnp.take_along_axis(logp, y[..., None], -1).mean()

    return jax.value_and_grad(loss)(params)


def test_loss_and_every_gradient_match_and_the_bias_gets_exactly_zero(model):
    cfg, bundle, variables, x = model
    params = variables["params"]
    ours, g_ours = grads_of(
        lambda p: bundle.apply_train({"params": p}, x)[0], params, x)
    theirs, g_theirs = grads_of(lambda p: plain.forward(cfg, p, x), params, x)
    assert abs(float(ours - theirs)) / float(theirs) < 1e-5
    flat_ours = jax.tree_util.tree_leaves_with_path(g_ours)
    flat_theirs = jax.tree_util.tree_leaves(g_theirs)
    assert len(flat_ours) == len(flat_theirs)
    for (path, a), b in zip(flat_ours, flat_theirs):
        name = jax.tree_util.keystr(path)
        if "selection_bias" in name:
            # outside the gradient: exactly zero, so SGD leaves the leaf
            assert float(jnp.abs(a).max()) == 0.0 == float(jnp.abs(b).max())
        else:
            assert rel(a, b) < 1e-5, name
            assert float(jnp.abs(a).max()) > 0, name
    # the router learns through the weights of the chosen
    assert float(jnp.abs(
        g_ours["Block_1"]["ExpertLayer_0"]["router"]).max()) > 0
    assert float(jnp.abs(
        g_ours["Block_1"]["MultiHeadAttention_0"]["gate"]["kernel"]).max()) > 0


# -- positions by layer kind ------------------------------------------------------

def one_layer(kind, **over):
    """One dense layer of ``kind`` whose window, where it has one, spans the
    whole sequence: a sliding and a full layer then differ by positions
    alone."""
    return toy_config(n_layer=1, num_dense_layers=1, layer_types=[kind],
                      sliding_window=32, **over)


def test_a_full_layer_applies_no_rotation_and_a_sliding_layer_does():
    x = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, 64)
    full, sliding = one_layer(FULL), one_layer(SLIDING)
    bundle = decoder_lm(full)
    params = bundle.init(jax.random.PRNGKey(4))["params"]
    got = bundle.apply_eval({"params": params}, x)
    # agrees with a reference that has no positions; disagrees with one that
    # rotates (the same weights as a sliding layer whose window is everything)
    assert rel(got, plain.forward(full, params, x)) < 1e-5
    assert rel(got, plain.forward(sliding, params, x)) > 1e-2
    # the sliding layer rotates: it agrees with the reference that does, and
    # its output changes when the rotation is dropped
    rotated = decoder_lm(sliding).apply_eval({"params": params}, x)
    assert rel(rotated, plain.forward(sliding, params, x)) < 1e-5
    dropped = decoder_lm({**sliding, "rope_layer_types": []}).apply_eval(
        {"params": params}, x)
    assert rel(dropped, rotated) > 1e-2
    assert rel(dropped, got) < 1e-6  # no positions: the full layer's output


# -- the embedding's factor -------------------------------------------------------

def test_the_embeddings_factor_is_part_of_the_function():
    """``x0 = E[token] * sqrt(h)``: the same logits as a table ``sqrt(h) E``
    used as looked up, and ``E``'s gradient carries the factor."""
    scaled = one_layer(SLIDING)
    plain_table = {k: v for k, v in scaled.items() if k != "mup_enabled"}
    x = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0, 64)
    params = decoder_lm(scaled).init(jax.random.PRNGKey(6))["params"]
    factor = math.sqrt(32)
    grown = {**params, "wte": {"embedding": params["wte"]["embedding"]
                               * factor}}
    ours, g_ours = grads_of(lambda p: decoder_lm(scaled).apply_eval(
        {"params": p}, x), params, x)
    theirs, g_theirs = grads_of(lambda p: decoder_lm(plain_table).apply_eval(
        {"params": p}, x), grown, x)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-6)
    assert rel(g_ours["wte"]["embedding"],
               factor * g_theirs["wte"]["embedding"]) < 1e-5
    assert rel(g_ours["lm_head"]["kernel"], g_theirs["lm_head"]["kernel"]) \
        < 1e-5


# -- the selection bias ----------------------------------------------------------

ROUTED, TOP_K, SCALE = 16, 4, 2.826


def expert_params(key, h=32, f=24, routed=ROUTED):
    ks = jax.random.split(key, 5)
    return {"router": jax.random.normal(ks[0], (h, routed)) / math.sqrt(h),
            "gate": jax.random.normal(ks[1], (routed, h, f)) / math.sqrt(h),
            "up": jax.random.normal(ks[2], (routed, h, f)) / math.sqrt(h),
            "down": jax.random.normal(ks[3], (routed, f, h)) / math.sqrt(f),
            "selection_bias": 0.05 * jax.random.normal(ks[4], (routed,))}


def share_of(params, held):
    ids = jnp.asarray(held)
    return {**params, **{k: params[k][ids] for k in ("gate", "up", "down")}}


def run_layer(params, x, held, intermediates=False):
    layer = ExpertLayer(ROUTED, tuple(held), TOP_K, 24, True, "sigmoid",
                        SCALE, 0.05)
    variables = {"params": share_of(params, held)}
    if intermediates:
        return layer.apply(variables, x, mutable=["intermediates"])
    return layer.apply(variables, x)


ROUTER_CONFIG = {"num_experts": ROUTED, "num_experts_per_tok": TOP_K,
                 "route_norm": True, "route_scale": SCALE}


def test_a_large_bias_on_one_expert_is_chosen_by_all_and_weighs_by_its_score():
    params = expert_params(jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, 32))
    pushed = {**params,
              "selection_bias": jnp.zeros((ROUTED,)).at[5].set(100.0)}
    (y, _), mutated = run_layer(pushed, x, range(ROUTED), intermediates=True)
    (top_e,) = mutated["intermediates"]["top_e"]
    assert bool((top_e == 5).any(axis=-1).all())  # every token chose it
    # by hand: expert 5 and the three largest of the others, weighed by the
    # scores WITHOUT the bias (its own is under 1, never 100 and something)
    tokens = x.reshape(-1, 32)
    score = np.asarray(jax.nn.sigmoid(tokens @ params["router"]))
    want = np.zeros_like(tokens)
    for t, s in enumerate(score):
        others = [e for e in np.argsort(-s, kind="stable") if e != 5][:3]
        chosen = [5] + others
        total = sum(s[e] for e in chosen) + 1e-20
        for e in chosen:
            f = (jax.nn.silu(tokens[t] @ params["gate"][e])
                 * (tokens[t] @ params["up"][e])) @ params["down"][e]
            want[t] += SCALE * s[e] / total * np.asarray(f)
    assert rel(y.reshape(-1, 32), want) < 1e-5
    # and the plain reference's own choice agrees
    theirs, chosen = plain.expert_layer(ROUTER_CONFIG, tokens, pushed)
    assert bool((chosen == 5).any(axis=-1).all())
    assert rel(y.reshape(-1, 32), theirs) < 1e-5


@pytest.mark.parametrize("std", [0.0, 0.02, 0.3])
def test_tokens_moved_is_zero_at_zero_bias_and_a_brute_force_count_else(std):
    params = expert_params(jax.random.PRNGKey(9))
    bias = std * jax.random.normal(jax.random.PRNGKey(10), (ROUTED,))
    x = jax.random.normal(jax.random.PRNGKey(11), (4, 32, 32))
    _, counters = run_layer({**params, "selection_bias": bias}, x,
                            range(ROUTED))
    score = np.asarray(jax.nn.sigmoid(x.reshape(-1, 32) @ params["router"]))
    plain_sets = np.sort(np.argsort(-score, axis=1)[:, :TOP_K], axis=1)
    moved_sets = np.sort(np.argsort(-(score + np.asarray(bias)), axis=1)[
        :, :TOP_K], axis=1)
    want = int((plain_sets != moved_sets).any(axis=1).sum())
    assert float(counters[TOKENS_BIAS_MOVED]) == want
    assert (want == 0) == (std == 0.0)
    assert counters[TOKENS_BIAS_MOVED].dtype == jnp.float32
    assert counters[TOKENS_BIAS_MOVED].shape == ()


def test_a_layer_without_a_bias_has_no_leaf_and_no_counter():
    layer = ExpertLayer(ROUTED, tuple(range(ROUTED)), TOP_K, 24, True,
                        "sigmoid", SCALE)
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 8, 32))
    variables = layer.init(jax.random.PRNGKey(13), x)
    assert set(variables["params"]) == {"router", "gate", "up", "down"}
    _, counters = layer.apply(variables, x)
    assert TOKENS_BIAS_MOVED not in counters


def test_the_16_shares_add_up_with_the_shared_expert_counted_once():
    params = expert_params(jax.random.PRNGKey(14), routed=32)
    ks = jax.random.split(jax.random.PRNGKey(15), 3)
    shared = {"gate": {"kernel": jax.random.normal(ks[0], (32, 24)) / 6},
              "up": {"kernel": jax.random.normal(ks[1], (32, 24)) / 6},
              "down": {"kernel": jax.random.normal(ks[2], (24, 32)) / 5}}
    x = jax.random.normal(jax.random.PRNGKey(16), (2, 24, 32))
    cfg = {**ROUTER_CONFIG, "num_experts": 32}
    whole, _ = plain.expert_layer(cfg, x.reshape(-1, 32), params, shared)
    # 16 shares of 2 experts, as 16 chips would hold them; what every chip
    # computes alike (the shared expert) enters the sum once
    total = decoder.GatedMLP(24).apply({"params": shared}, x)
    assigned = 0.0
    for s in range(16):
        held = [2 * s, 2 * s + 1]
        layer = ExpertLayer(32, tuple(held), TOP_K, 24, True, "sigmoid",
                            SCALE, 0.05)
        y, counters = layer.apply({"params": share_of(params, held)}, x)
        total, assigned = total + y, assigned + counters[ASSIGNMENTS_HELD]
    assert rel(total.reshape(-1, 32), whole) < 1e-5
    assert float(assigned) == 2 * 24 * TOP_K  # every assignment on one share


# -- the round path ---------------------------------------------------------------

def test_a_round_leaves_the_bias_where_it_was_and_counts_the_tokens_moved():
    bundle, state, block, (new_state, metrics) = round_of(SHARE)
    assert set(new_state.variables) == {"params"}
    assert COUNTERS not in metrics
    moved = float(metrics[TOKENS_BIAS_MOVED][0])
    # 2 clients x 2 steps x 4 expert layers x 64 tokens, some of them moved
    assert 0 < moved < 2 * 2 * 4 * 64 and moved == int(moved)
    old, new = state.variables["params"], new_state.variables["params"]
    for i in range(1, 5):
        layer = f"Block_{i}"
        np.testing.assert_array_equal(
            new[layer]["ExpertLayer_0"]["selection_bias"],
            old[layer]["ExpertLayer_0"]["selection_bias"])
        assert rel(new[layer]["ExpertLayer_0"]["router"],
                   old[layer]["ExpertLayer_0"]["router"]) > 0
    # one forward of the model reports what the reference's own choice gives
    _, new_vars = bundle.apply_train(state.variables, block[0][0, 0])
    _, chosen = plain.forward(SHARE, old, block[0][0, 0], with_selection=True)
    assert float(new_vars[COUNTERS][ASSIGNMENTS_HELD]) == sum(
        np.isin(np.asarray(c), SHARE["experts_held"]).sum() for c in chosen)


def test_one_round_agrees_with_the_benchmark_reference_through_the_driver():
    from benchmark import cells
    from benchmark import run as bench_run

    cell = cells.load_cell("trinitymini_silo_chat8k", rehearsal=True)
    session = cells.load_driver(cell.workload["driver"]).Session(
        cell, 11, jax.devices()[:1])
    assert isinstance(session.bundle, plain.PlainBundle)
    rounds, metrics = session.call()
    assert rounds == 1 and bench_run.call_ok(metrics, session.cohort)
    assert TOKENS_BIAS_MOVED in metrics and ASSIGNMENTS_HELD in metrics
    assert float(metrics[TOKENS_BIAS_MOVED][0]) > 0
    agreement = bench_run.check_reference(cell, session, 11)
    assert agreement["ok"], agreement
    assert agreement["delta_rel_l2"] < 0.01 and agreement["loss_rel"] < 1e-5


def test_it_is_reachable_by_name_from_the_experiment_entry_point(tmp_path):
    from fedml_tpu.experiments.run import ExperimentConfig, run_experiment

    path = tmp_path / "decoder.json"
    path.write_text(json.dumps(SHARE))
    out = run_experiment(ExperimentConfig(
        algorithm="fedllm", model="decoder_lm", model_config=str(path),
        dataset="fed_shakespeare", client_num_in_total=2,
        client_num_per_round=2, comm_round=1, batch_size=4, lr=0.01,
        max_samples_per_client=8, max_test_samples=8), log_fn=None)
    assert np.isfinite(out["final"]["test_loss"])
    assert out["history"][-1][TOKENS_BIAS_MOVED] >= 0


# -- the accepted configurations are what they were ---------------------------------

@pytest.fixture(scope="module")
def pinned():
    with open(PINNED) as f:
        return json.load(f)


@pytest.mark.parametrize("family", lowered_programs.FAMILIES)
def test_an_accepted_familys_tree_and_round_program_are_the_parents(
        family, pinned):
    """With the new fields off (no accepted configuration states one) the
    parameter tree, by path and shape, and the lowered round program of a toy
    of each accepted configuration's kinds are those of the commit before
    this family came: the copy in ``testdata/`` was taken from it
    (``lowered_programs.py`` says how)."""
    got = lowered_programs.program_of(family)
    assert got["tree"] == pinned[family]["tree"]
    assert got["round_sha256"] == pinned[family]["round_sha256"], (
        f"the {family} toy's round no longer lowers to the parent's program: "
        "the compile cache's key moves for its cells")
