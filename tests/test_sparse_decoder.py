"""The decoder's ``sparse_attention`` layers (``models/decoder.py``: a learned
indexer picks the keys a query sees) against the plain reference that lives
with the benchmark (``benchmark/families/keye_sparse_plain.py``): float32,
seeded random weights, toy sizes at which the choice bites (8 keys of 32)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import keye_sparse_plain as plain  # noqa: E402
from fedml_tpu.models import decoder  # noqa: E402
from fedml_tpu.models.base import COUNTERS  # noqa: E402
from fedml_tpu.models.decoder import (  # noqa: E402
    ATTN_TILES_LIVE, SELECT_TILES_SCORED, SELECTED, DecoderBlock,
    DecoderConfig, decoder_lm,
)
from fedml_tpu.ops import sparse_select  # noqa: E402

SA = {"indexer_head_dim": 4, "indexer_num_heads": 2, "indexer_num_kv_heads": 1,
      "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 8}


def toy_config(**over):
    """Two layers at toy widths, in the published file's key names."""
    return {
        "vocab_size": 64, "hidden_size": 32, "n_layer": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "sa_config": SA, "qk_norm": True, "rope_theta": 10000000,
        "rope_scaling": {"mrope_section": [1, 1, 2], "rope_type": "default",
                         "type": "default"},
        "sliding_window": None, "use_sliding_window": False,
        "rms_norm_eps": 1e-6, "moe_intermediate_size": 24, "num_experts": 8,
        "num_experts_routed": 8, "experts_held": list(range(8)),
        "num_experts_per_tok": 2, "norm_topk_prob": True, "n_positions": 32,
        **over}


WHOLE = toy_config()
SHARE = toy_config(num_experts=3, experts_held=[1, 4, 6])


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


@pytest.fixture(scope="module", params=[WHOLE, SHARE], ids=["whole", "share"])
def model(request):
    cfg = request.param
    bundle = decoder_lm(cfg)
    variables = bundle.init(jax.random.PRNGKey(0))
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    return cfg, bundle, variables, x


# -- the configuration ----------------------------------------------------------

def test_sa_config_makes_every_layer_a_sparse_one():
    cfg = DecoderConfig.from_dict(WHOLE)
    assert cfg.layer_types == (SELECTED, SELECTED)
    assert (cfg.indexer_heads, cfg.indexer_head_dim, cfg.index_topk) == (2, 4, 8)
    assert cfg.qk_norm
    # the top-level rope keys, an mrope_section read as plain RoPE
    assert dict(dict(cfg.rope)[SELECTED]) == {"rope_type": "default",
                                             "rope_theta": 10000000}


def test_another_top_level_rope_type_is_refused_by_name():
    with pytest.raises(ValueError, match="'yarn'"):
        DecoderConfig.from_dict(toy_config(
            rope_scaling={"rope_type": "yarn", "factor": 4}))


def test_a_configuration_that_says_no_positions_keeps_none():
    cfg = DecoderConfig.from_dict(toy_config(mla_use_nope=True))
    assert cfg.rope == ()


def test_init_holds_parameters_only(model):
    cfg, _, variables, _ = model
    assert set(variables) == {"params"}
    attn = variables["params"]["Block_0"]["MultiHeadAttention_0"]
    shapes = jax.tree_util.tree_map(jnp.shape, attn)
    assert shapes == {
        "Dense_0": {"kernel": (32, (4 + 2 * 2) * 8)},
        "Dense_1": {"kernel": (32, 32)},
        "q_norm": {"scale": (8,)}, "k_norm": {"scale": (8,)},
        "indexer": {"q": {"kernel": (32, 2 * 4)}, "k": {"kernel": (32, 4)},
                    "k_norm": {"scale": (4,), "bias": (4,)},
                    "w": {"kernel": (32, 2)}}}


# -- against the plain reference ------------------------------------------------

def test_forward_matches_the_plain_reference(model):
    cfg, bundle, variables, x = model
    logits, _ = bundle.apply_train(variables, x)
    assert rel(logits, plain.forward(cfg, variables["params"], x)) < 1e-5
    assert rel(bundle.apply_eval(variables, x), logits) < 1e-6


def test_the_choice_is_the_plain_references_own(model):
    cfg, bundle, variables, x = model
    _, chosen = plain.forward(cfg, variables["params"], x,
                              with_selection=True)
    _, mutated = bundle.module.apply(
        variables, x, train=True, mutable=["intermediates"],
        capture_intermediates=lambda m, _: m.name == "indexer")
    for i, theirs in enumerate(chosen):
        index = mutated["intermediates"][f"Block_{i}"][
            "MultiHeadAttention_0"]["indexer"]["__call__"][0]
        ours = jax.vmap(lambda *t: sparse_select.select_topk(*t, 8)[0])(*index)
        assert (np.asarray(ours) != 0).tolist() == np.asarray(theirs).tolist()
        # the choice bites: 8 keys of up to 32
        assert int(np.asarray(theirs)[0].sum()) == 36 + 24 * 8


def test_loss_and_gradients_match_and_the_indexer_gets_exactly_zero(model):
    cfg, bundle, variables, x = model
    y = jnp.roll(x, -1, axis=-1)

    def loss_of(forward):
        def loss(params):
            logp = jax.nn.log_softmax(forward(params))
            return -jnp.take_along_axis(logp, y[..., None], -1).mean()
        return jax.value_and_grad(loss)(variables["params"])

    ours, g_ours = loss_of(
        lambda p: bundle.apply_train({"params": p}, x)[0])
    theirs, g_theirs = loss_of(lambda p: plain.forward(cfg, p, x))
    assert abs(float(ours - theirs)) / float(theirs) < 1e-5
    flat_ours = jax.tree_util.tree_leaves_with_path(g_ours)
    flat_theirs = jax.tree_util.tree_leaves(g_theirs)
    assert len(flat_ours) == len(flat_theirs)
    indexer_leaves = 0
    for (path, a), b in zip(flat_ours, flat_theirs):
        name = jax.tree_util.keystr(path)
        if "indexer" in name:  # the choice is discrete
            indexer_leaves += 1
            assert not np.asarray(a).any() and not np.asarray(b).any(), name
        else:
            assert np.asarray(a).any(), name
            assert rel(a, b) < 1e-5, name
    assert indexer_leaves == 2 * 5  # q, k, w and the LayerNorm's two, a layer


def test_a_choice_of_every_causal_key_is_the_full_layer():
    """``topk >= L``: the sparse layer keeps every causal pair, and the model
    is the accepted full-attention one over the same weights."""
    sparse = toy_config(sa_config={**SA, "topk": 32})
    full = {k: v for k, v in sparse.items() if k != "sa_config"}
    full["layer_types"] = ["full_attention"]
    bundle, accepted = decoder_lm(sparse), decoder_lm(full)
    variables = bundle.init(jax.random.PRNGKey(4))
    x = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0, 64)
    params = jax.tree_util.tree_map(lambda a: a, variables["params"])
    for i in range(2):
        params[f"Block_{i}"]["MultiHeadAttention_0"] = {
            k: v for k, v in
            params[f"Block_{i}"]["MultiHeadAttention_0"].items()
            if k != "indexer"}
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        accepted.init(jax.random.PRNGKey(4))["params"])
    assert rel(bundle.apply_eval(variables, x),
               accepted.apply_eval({"params": params}, x)) < 1e-6


def test_the_shares_add_up_to_the_uncut_reference():
    """Over all 8 expert shares of a toy layer, the parts the shares' experts
    add sum to what the uncut reference's layer adds; attention, whole on
    every share, is counted once."""
    cfg = toy_config(n_layer=1, num_experts=16, num_experts_routed=16,
                     experts_held=list(range(16)), num_experts_per_tok=4)
    whole = decoder_lm(cfg)
    params = whole.init(jax.random.PRNGKey(6))["params"]
    ids = jax.random.randint(jax.random.PRNGKey(7), (2, 32), 0, 64)
    x = params["wte"]["embedding"][ids]
    want = plain.hidden(cfg, params, ids)[0]

    def block_of(held, experts):
        share = {**cfg, "num_experts": len(held), "experts_held": held}
        layer = {**params["Block_0"], "ExpertLayer_0": experts}
        return DecoderBlock(DecoderConfig.from_dict(share), SELECTED).apply(
            {"params": layer}, x)[0]

    experts = params["Block_0"]["ExpertLayer_0"]
    idle = {**experts, "down": jnp.zeros_like(experts["down"])}
    base = block_of(list(range(16)), idle)  # x + attention, no expert
    total = base
    for s in range(8):  # 8 shares of 2 experts, as 8 chips would hold them
        held = [2 * s, 2 * s + 1]
        part = {"router": experts["router"],
                **{k: experts[k][jnp.asarray(held)]
                   for k in ("gate", "up", "down")}}
        total = total + (block_of(held, part) - base)
    assert rel(total, want) < 1e-5


# -- the counter and the round -----------------------------------------------------

def test_tiles_live_counts_the_tiles_that_hold_a_chosen_pair():
    """At 1024 positions a sequence has three causal 512-tiles a layer; with
    16 keys a query and index keys that make the early positions win, a tile
    can go empty: counted by hand from the plain reference's own choice."""
    cfg = toy_config(n_positions=1024, sa_config={**SA, "topk": 16})
    bundle = decoder_lm(cfg)
    variables = bundle.init(jax.random.PRNGKey(8))
    x = jax.random.randint(jax.random.PRNGKey(9), (2, 1024), 0, 64)
    _, new_vars = bundle.apply_train(variables, x)
    _, chosen = plain.forward(cfg, variables["params"], x, with_selection=True)
    by_hand = sum(int(np.asarray(c).reshape(2, 2, 512, 2, 512).any(
        axis=(2, 4)).sum()) for c in chosen)
    assert 2 * 2 * 2 <= by_hand <= 2 * 2 * 3
    assert float(new_vars[COUNTERS][ATTN_TILES_LIVE]) == by_hand
    # the lax form scored every tile: 2 sequences x 2 layers x 2 x 2
    assert float(new_vars[COUNTERS][SELECT_TILES_SCORED]) == 2 * 2 * 4
    assert set(variables) == {"params"}


@pytest.mark.parametrize("in_kernel, scored", [(True, 3), (False, 4)])
def test_tiles_scored_says_which_form_of_the_choice_ran(monkeypatch,
                                                        in_kernel, scored):
    """``chosen_keys`` counts the tiles ``select_topk`` scores for its
    operands: the causal ones where the shape test sends the call to the
    kernel, all of them as lax ops; summed over sequences like the live
    tiles."""
    monkeypatch.setattr(sparse_select, "kernel_tiles",
                        lambda *a, **k: in_kernel)
    monkeypatch.setattr(
        sparse_select, "select_in_kernel",
        lambda *i: sparse_select.select_in_lax(*i))
    calls = []

    def attn(q, k, v, causal, keep, tiles):
        calls.append(tiles.shape)
        return v

    index = (jnp.ones((3, 1024, 2, 4)), jnp.ones((3, 1024, 4)),
             jnp.ones((3, 1024, 2)))
    v = jnp.zeros((3, 1024, 1, 4))
    _, scalars = jax.vmap(
        lambda v, i: decoder.chosen_keys(attn, 8)(v, v, v, True, i)
    )(v, index)
    assert calls == [(2, 2)]
    assert scalars[SELECT_TILES_SCORED].dtype == jnp.float32
    assert float(scalars[SELECT_TILES_SCORED].sum()) == 3 * scored
    # equal scores everywhere: a row's 8 keys are the first 8 positions
    assert float(scalars[ATTN_TILES_LIVE].sum()) == 3 * 2


def round_of(cfg):
    from fedml_tpu.algorithms.fedavg import ServerState, make_multi_round_fn
    from fedml_tpu.core.client import make_client_optimizer, make_local_update

    bundle = decoder_lm(cfg)
    local_update = make_local_update(
        bundle, make_client_optimizer("sgd", 0.01), epochs=1)
    key = jax.random.PRNGKey(0)
    state = ServerState(variables=bundle.init(key), opt_state=(),
                        round_idx=jnp.zeros((), jnp.int32), key=key)
    k, s, b = 2, 2, 2
    x = jax.random.randint(jax.random.PRNGKey(1), (k, s, b, 32), 0, 64)
    block = (x, jnp.roll(x, -1, axis=-1), jnp.ones((k, s, b)),
             jnp.full((k,), float(s * b)), jnp.ones((k,)), jnp.arange(k))
    return state, jax.jit(make_multi_round_fn(local_update, 1))(state, *block)


def test_the_counter_leaves_with_the_metrics_and_the_indexer_stays_put():
    state, (new_state, metrics) = round_of(SHARE)
    assert set(new_state.variables) == {"params"}
    assert COUNTERS not in metrics
    # one tile a sequence and layer at 32 positions:
    # 2 clients x 2 steps x 2 sequences x 2 layers
    assert float(metrics[ATTN_TILES_LIVE][0]) == 2 * 2 * 2 * 2
    assert float(metrics[SELECT_TILES_SCORED][0]) == 2 * 2 * 2 * 2
    assert float(metrics["count"][0]) == 2 * 2 * 2 * 32
    old = state.variables["params"]["Block_0"]["MultiHeadAttention_0"]
    new = new_state.variables["params"]["Block_0"]["MultiHeadAttention_0"]
    for a, b in zip(jax.tree_util.tree_leaves(old["indexer"]),
                    jax.tree_util.tree_leaves(new["indexer"])):
        assert (np.asarray(a) == np.asarray(b)).all()  # a gradient of zero
    assert (np.asarray(old["Dense_0"]["kernel"])
            != np.asarray(new["Dense_0"]["kernel"])).any()


def test_one_round_agrees_with_the_benchmark_reference_through_the_driver():
    """``drivers/fused_plain.py`` hands ``run.py:check_reference`` the plain
    forward pass; the toy cell's round agrees with ``benchmark/reference.py``."""
    from benchmark import cells
    from benchmark import run as bench_run

    cell = cells.load_cell("keyevl2_silo_text8k", rehearsal=True)
    session = cells.load_driver(cell.workload["driver"]).Session(
        cell, 11, jax.devices()[:1])
    assert isinstance(session.bundle, plain.PlainBundle)
    rounds, metrics = session.call()
    assert rounds == 1 and bench_run.call_ok(metrics, session.cohort)
    assert ATTN_TILES_LIVE in metrics and SELECT_TILES_SCORED in metrics
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
    assert out["history"][-1][ATTN_TILES_LIVE] > 0


def test_the_mixer_kinds_are_one_dispatch():
    assert decoder.SELECTED == "sparse_attention"
    kinds = DecoderConfig.from_dict(toy_config(
        layer_types=["full_attention", "sparse_attention"])).layer_types
    assert kinds == ("full_attention", "sparse_attention")


def test_the_three_scopes_are_the_models_and_name_their_ops():
    """``model.attn_indexer``, ``model.attn_select`` and ``model.attn_sparse``
    are in the lowered program's op names, none matches a stage's pattern, no
    op carries two ``model.*`` segments, and the selection runs inside the
    auto-named ``MultiHeadAttention``'s ``vmap`` (the trace's attention
    class) while the index projections run outside it."""
    import re

    from fedml_tpu.obs import scopes

    new = {scopes.ATTN_INDEXER, scopes.ATTN_SELECT, scopes.ATTN_SPARSE}
    assert new <= set(scopes.MODEL_SCOPES) and not new & set(scopes.SCOPES)
    assert not [s for s in new if re.search(r"fed\.[a-z_]+", s)]
    bundle = decoder_lm(SHARE)
    variables = bundle.init(jax.random.PRNGKey(0))
    x = jnp.zeros((1, 32), jnp.int32)
    text = jax.jit(jax.grad(lambda p: bundle.apply_train(
        {"params": p}, x)[0].sum())).lower(variables["params"]).as_text(
        debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    for name in new:
        assert any(name in n for n in names), name
    assert all(len(set(re.findall(r"model\.[a-z_]+", n))) <= 1 for n in names)
    inside = re.compile(r"MultiHeadAttention_\d+/vmap")
    for n in names:
        if scopes.ATTN_SELECT in n or scopes.ATTN_SPARSE in n:
            assert inside.search(n), n
        if scopes.ATTN_INDEXER in n:
            assert not inside.search(n), n
    # the attention over the chosen keys has a backward, the choice has none
    assert any(scopes.ATTN_SPARSE in n and "transpose(" in n for n in names)
    assert not any(scopes.ATTN_SELECT in n and "transpose(" in n
                   for n in names)
