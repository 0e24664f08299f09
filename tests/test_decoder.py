"""The configuration-driven decoder (``models/decoder.py``) against the plain
reference that lives with the benchmark (``benchmark/families/
mellum_moe_plain.py``): float32, seeded random weights, toy sizes."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import mellum_moe_plain as plain  # noqa: E402
from fedml_tpu.models import decoder  # noqa: E402
from fedml_tpu.models.base import COUNTERS  # noqa: E402
from fedml_tpu.models.decoder import (  # noqa: E402
    ASSIGNMENTS_HELD, EXPERT_TOKENS_MAX, ROWS_BUFFERED, ROWS_COMPUTED,
    ExpertLayer, buffer_capacities, decoder_lm,
)

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
PLAIN_ROPE = {"rope_type": "default", "rope_theta": 500000}


def toy_config(**over):
    """One whole period of 3 sliding + 1 full layers at toy widths."""
    return {
        "vocab_size": 64, "hidden_size": 32, "n_layer": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "sliding_window": 8,
        "rope_parameters": {"full_attention": YARN,
                            "sliding_attention": PLAIN_ROPE},
        "rms_norm_eps": 1e-6, "moe_intermediate_size": 24, "num_experts": 8,
        "num_experts_routed": 8, "experts_held": list(range(8)),
        "num_experts_per_tok": 2, "norm_topk_prob": True, "n_positions": 32,
        **over}


WHOLE = toy_config()
SHARE = toy_config(num_experts=3, experts_held=[1, 4, 6])


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# -- rotary positions -----------------------------------------------------------

def test_yarn_ramps_between_pairs_18_and_35_at_the_published_numbers():
    assert decoder.yarn_correction_range(128, 500000, 8192, 32, 1) == (18, 35)
    i = np.arange(64)
    base = 500000.0 ** (-2 * i / 128)
    m = 1 - np.clip((i - 18) / 17, 0, 1)
    want = base * m + base / 16 * (1 - m)
    got = decoder.yarn_inv_freq(128, 500000, 16, 8192, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(got[:19], base[:19], rtol=1e-12)  # kept
    np.testing.assert_allclose(got[35:], base[35:] / 16, rtol=1e-12)
    assert 0.1 * math.log(16) + 1 == pytest.approx(1.2772588722239782)
    # the reference computes its own, from the same published formulas
    ref, factor = plain.inv_freq(YARN, 128)
    np.testing.assert_allclose(ref, want, rtol=1e-12)
    assert factor == 1.2772588722239782


def test_plain_inv_freq_is_theta_to_the_minus_2i_over_d():
    want = [500000.0 ** (-2 * i / 128) for i in range(64)]
    np.testing.assert_allclose(decoder.rope_inv_freq(128, 500000), want,
                               rtol=1e-12)
    ref, factor = plain.inv_freq(PLAIN_ROPE, 128)
    np.testing.assert_allclose(ref, want, rtol=1e-12)
    assert factor == 1.0


def test_rope_rotates_pairs_i_and_i_plus_half():
    rope = decoder.make_rope_fn(PLAIN_ROPE, 8)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    got = rope(x)
    freq = decoder.rope_inv_freq(8, 500000)
    for pos in range(5):
        for i in range(4):
            c, s = math.cos(pos * freq[i]), math.sin(pos * freq[i])
            a, b = x[0, pos, :, i], x[0, pos, :, i + 4]
            np.testing.assert_allclose(got[0, pos, :, i], a * c - b * s,
                                       atol=1e-6)
            np.testing.assert_allclose(got[0, pos, :, i + 4], b * c + a * s,
                                       atol=1e-6)


# -- the decoder against the plain reference ----------------------------------------

@pytest.fixture(scope="module", params=[WHOLE, SHARE], ids=["whole", "share"])
def model(request):
    cfg = request.param
    bundle = decoder_lm(cfg)
    variables = bundle.init(jax.random.PRNGKey(0))
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    return cfg, bundle, variables, x


def test_init_holds_parameters_only(model):
    cfg, _, variables, _ = model
    assert set(variables) == {"params"}
    layer = variables["params"]["Block_0"]
    assert layer["ExpertLayer_0"]["router"].shape == (32, 8)
    assert layer["ExpertLayer_0"]["gate"].shape == (cfg["num_experts"], 32, 24)
    # one fused projection: 4 q heads + 2 x 2 k/v heads of 16
    assert layer["MultiHeadAttention_0"]["Dense_0"]["kernel"].shape == (32, 128)
    assert "wpe" not in variables["params"]
    assert variables["params"]["lm_head"]["kernel"].shape == (32, 64)


def test_forward_matches_the_plain_reference(model):
    cfg, bundle, variables, x = model
    logits, _ = bundle.apply_train(variables, x)
    assert rel(logits, plain.forward(cfg, variables["params"], x)) < 1e-5
    assert rel(bundle.apply_eval(variables, x), logits) < 1e-6


def test_loss_and_gradients_match_the_plain_reference(model):
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
    for (path, a), b in zip(flat_ours, flat_theirs):
        assert rel(a, b) < 1e-5, jax.tree_util.keystr(path)


# -- the expert layer -------------------------------------------------------------

def expert_params(key, h=32, f=24, routed=16, router_scale=1.0):
    ks = jax.random.split(key, 4)
    return {"router": jax.random.normal(ks[0], (h, routed)) * router_scale
            / math.sqrt(h),
            "gate": jax.random.normal(ks[1], (routed, h, f)) / math.sqrt(h),
            "up": jax.random.normal(ks[2], (routed, h, f)) / math.sqrt(h),
            "down": jax.random.normal(ks[3], (routed, f, h)) / math.sqrt(f)}


def share_of(params, held):
    ids = jnp.asarray(held)
    return {"router": params["router"],
            **{k: params[k][ids] for k in ("gate", "up", "down")}}


def run_layer(params, x, held, routed=16, top_k=4):
    layer = ExpertLayer(routed, tuple(held), top_k, 24)
    return layer.apply({"params": share_of(params, held)}, x)


def test_the_shares_add_up_to_the_uncut_reference():
    params = expert_params(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 32))
    cfg = {"num_experts": 16, "num_experts_per_tok": 4}
    whole, _ = plain.expert_layer(cfg, x.reshape(-1, 32), params)
    total, assigned = 0.0, 0.0
    for s in range(8):  # 8 shares of 2 experts, as 8 chips would hold them
        y, counters = run_layer(params, x, [2 * s, 2 * s + 1])
        total, assigned = total + y, assigned + counters[ASSIGNMENTS_HELD]
    assert rel(total.reshape(-1, 32), whole) < 1e-5
    assert float(assigned) == 2 * 24 * 4  # every assignment on one share


@pytest.mark.parametrize("router_scale, skewed", [(1.0, False), (1.0, True)],
                         ids=["uniform", "skewed"])
def test_no_token_is_lost_and_the_counters_count(router_scale, skewed):
    """Every token's whole top-k lands on held experts (all are held); under
    the skewed router one expert is in every token's top-k."""
    params = expert_params(jax.random.PRNGKey(4), router_scale=router_scale)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32))
    if skewed:  # |x . 1| dominates: expert 0 wins whatever the token
        x = x + 3.0
        params["router"] = params["router"].at[:, 0].set(1.0)
    held = list(range(16))
    y, counters = run_layer(params, x, held)
    cfg = {"num_experts": 16, "num_experts_per_tok": 4}
    want, chosen = plain.expert_layer(cfg, x.reshape(-1, 32), params)
    assert rel(y.reshape(-1, 32), want) < 1e-5
    by_hand = np.bincount(np.asarray(chosen).reshape(-1), minlength=16)
    assert float(counters[ASSIGNMENTS_HELD]) == 48 * 4 == by_hand.sum()
    assert float(counters[EXPERT_TOKENS_MAX]) == by_hand.max()
    if skewed:
        assert by_hand.max() == 48


def test_a_share_counts_only_its_own_assignments():
    params = expert_params(jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 40, 32))
    held = [3, 9, 12]
    y, counters = run_layer(params, x, held)
    cfg = {"num_experts": 3, "num_experts_routed": 16, "experts_held": held,
           "num_experts_per_tok": 4}
    want, chosen = plain.expert_layer(cfg, x.reshape(-1, 32),
                                      share_of(params, held))
    by_hand = np.bincount(np.asarray(chosen).reshape(-1), minlength=16)[held]
    assert rel(y.reshape(-1, 32), want) < 1e-5
    assert float(counters[ASSIGNMENTS_HELD]) == by_hand.sum()
    assert float(counters[EXPERT_TOKENS_MAX]) == by_hand.max()


def test_expert_layer_gradients_match_the_reference_on_a_share():
    params = share_of(expert_params(jax.random.PRNGKey(8)), [0, 5, 7, 11])
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 40, 32))
    cfg = {"num_experts": 4, "num_experts_routed": 16,
           "experts_held": [0, 5, 7, 11], "num_experts_per_tok": 4}
    layer = ExpertLayer(16, (0, 5, 7, 11), 4, 24)
    probe = jax.random.normal(jax.random.PRNGKey(10), (40, 32))
    ours = jax.grad(lambda p, x: (layer.apply({"params": p}, x)[0].reshape(
        -1, 32) * probe).sum(), argnums=(0, 1))(params, x)
    theirs = jax.grad(lambda p, x: (plain.expert_layer(
        cfg, x.reshape(-1, 32), p)[0] * probe).sum(), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        assert rel(a, b) < 1e-5


# -- the row buffer follows the rows routed ------------------------------------------

BRANCH_HELD, BRANCH_T = [3, 9], 1024  # 2 of 16 held, top 4: short 1024, worst 2048


def branch_case(kind):
    """(params of the share, x [1, 1024, 32]) whose router is level, sends
    every token's whole top-k share to the held experts, or puts expert 3 in
    every token's top-k and expert 9 in none (``routed == short`` exactly)."""
    params = expert_params(jax.random.PRNGKey(12))
    x = jax.random.normal(jax.random.PRNGKey(13), (1, BRANCH_T, 32))
    if kind != "level":  # |x . 1| dominates the logits of the columns set
        x = x + 3.0
        router = params["router"].at[:, 3].set(1.0)
        params["router"] = router.at[:, 9].set(1.0 if kind == "all_held"
                                               else -1.0)
    return share_of(params, BRANCH_HELD), x


@pytest.mark.parametrize("kind, capacity", [
    ("level", 1024), ("all_held", 2048), ("exactly_short", 1024)])
def test_the_row_buffer_follows_the_rows_routed(kind, capacity):
    assert buffer_capacities(BRANCH_T, 4, 2, 16) == (1024, 2048)
    params, x = branch_case(kind)
    cfg = {"num_experts": 2, "num_experts_routed": 16,
           "experts_held": BRANCH_HELD, "num_experts_per_tok": 4}
    layer = ExpertLayer(16, tuple(BRANCH_HELD), 4, 24)
    probe = jax.random.normal(jax.random.PRNGKey(14), (BRANCH_T, 32))

    def ours(p, x):
        y, counters = layer.apply({"params": p}, x)
        return (y.reshape(-1, 32) * probe).sum(), (y, counters)

    def theirs(p, x):
        y, chosen = plain.expert_layer(cfg, x.reshape(-1, 32), p)
        return (y * probe).sum(), (y, chosen)

    g_ours, (y, counters) = jax.grad(ours, argnums=(0, 1), has_aux=True)(
        params, x)
    g_theirs, (want, chosen) = jax.grad(theirs, argnums=(0, 1), has_aux=True)(
        params, x)
    assert rel(y.reshape(-1, 32), want) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_ours),
                            jax.tree_util.tree_leaves(g_theirs)):
        assert rel(a, b) < 1e-5, jax.tree_util.keystr(path)
    by_hand = np.bincount(np.asarray(chosen).reshape(-1),
                          minlength=16)[BRANCH_HELD]
    assert float(counters[ROWS_BUFFERED]) == capacity
    assert float(counters[ASSIGNMENTS_HELD]) == by_hand.sum()
    assert float(counters[EXPERT_TOKENS_MAX]) == by_hand.max()
    if kind == "all_held":
        assert by_hand.tolist() == [BRANCH_T, BRANCH_T]
    elif kind == "exactly_short":
        assert by_hand.tolist() == [BRANCH_T, 0]
    else:
        assert 256 < by_hand.sum() < 1024


def rehearsal_config():
    from benchmark import cells

    return dict(cells.load_cell("mellum2_silo_code8k", rehearsal=True).config)


@pytest.mark.parametrize("config", [WHOLE, SHARE, rehearsal_config],
                         ids=["whole", "share", "rehearsal"])
def test_a_share_no_shorter_than_the_worst_case_has_no_branch(config):
    """Where twice the level share is no shorter than the worst case the
    layer is one path: the lowered model holds no conditional."""
    cfg = config() if callable(config) else config
    bundle = decoder_lm({**cfg, "n_positions": 32})
    x = jnp.zeros((2, 32), jnp.int32)
    variables = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    text = jax.jit(jax.grad(lambda v: bundle.apply_train(v, x)[0].sum())
                   ).lower(variables).as_text()
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert "conditional" not in text


def conds_of(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from conds_of(sub)


def test_the_fallback_hands_the_backward_no_buffer_of_its_own():
    """Where the branch exists, the gradient holds two ``cond``s (forward and
    backward) and neither returns an array of the worst case's length: what
    the worst case needs again it computes again."""
    params, x = branch_case("level")
    layer = ExpertLayer(16, tuple(BRANCH_HELD), 4, 24)
    short, worst = buffer_capacities(BRANCH_T, 4, 2, 16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, x: layer.apply({"params": p}, x)[0].sum(),
        argnums=(0, 1)))(params, x)
    conds = list(conds_of(jaxpr.jaxpr))
    assert len(conds) == 2
    shapes = [v.aval.shape for eqn in conds for v in eqn.outvars]
    assert all(s[:1] != (worst,) for s in shapes), shapes
    # the forward returns y and the short path's five buffers, nothing else
    assert [v.aval.shape for v in conds[0].outvars] == [
        (BRANCH_T, 32), (short, 32), (short,), (short, 24), (short, 24),
        (short, 24)]
    text = jax.jit(layer.apply).lower({"params": params}, x).as_text()
    assert "stablehlo.case" in text or "stablehlo.if" in text


# -- the row transfers between tokens and buffer ------------------------------------

from fedml_tpu.ops import expert_rows  # noqa: E402


def route_of(key, tokens, k, routed, held, capacity, skew=0.0, tile=1):
    """The ``Route`` the expert layer builds for a router with random scores
    (``skew`` added to expert ``held[0]``'s), through ``capacity`` rows, each
    group from a multiple of ``tile``."""
    scores = jax.random.normal(key, (tokens, routed)).at[:, held[0]].add(skew)
    _, top_e = jax.lax.top_k(scores, k)
    local_of = np.full((routed,), len(held), np.int32)
    local_of[list(held)] = np.arange(len(held))
    return route_from(jnp.asarray(local_of)[top_e], len(held), capacity, tile)


def route_from(local, held, capacity, tile=1):
    """The ``Route`` of the assignments ``local`` [T, k] (``held``: not
    here), laid out as the expert layer lays them."""
    sizes = (local.reshape(-1, 1) == jnp.arange(held)).sum(
        axis=0, dtype=jnp.int32)
    rows = -(-sizes // tile) * tile
    order, rank = expert_rows.sort_assignments(local, rows - sizes, tile - 1)
    assert int(rows.sum()) <= capacity
    return expert_rows.sorted_route(local, order, rank, sizes, capacity, rows)


def crafted_local(tile):
    """[64, 2] slots of 3 held experts: expert 0 holds exactly ``tile`` rows,
    expert 1 none, expert 2 ``tile + 5``; every other slot is not here."""
    local = np.full((64, 2), 3, np.int32)
    local[:tile, 0] = 0
    local[:tile + 5, 1] = 2
    return jnp.asarray(local)


# (tokens, k, experts routed, held, buffer rows): a share through a short
# buffer with dead rows past the routed count; tokens with none, one and all k
# of their slots here; every expert held, C = T x k and no dead row
PAIRS = [
    pytest.param(64, 4, 16, (3, 9), 128, 1, id="share_with_dead_rows"),
    pytest.param(96, 2, 5, (0, 2, 4), 192, 1, id="none_one_and_k_slots_here"),
    pytest.param(48, 4, 8, tuple(range(8)), 192, 1, id="every_expert_held"),
    pytest.param(64, 4, 16, (3, 9), 128, 16, id="groups_on_row_tiles"),
    pytest.param(64, 2, None, 3, 64, 16,
                 id="row_tiles_with_an_empty_and_a_whole_tile_group"),
]


@pytest.mark.parametrize("tokens, k, routed, held, capacity, tile", PAIRS)
def test_the_two_row_transfers_are_each_others_transpose(tokens, k, routed,
                                                         held, capacity, tile):
    """``to_buffer`` and ``from_buffer`` against the [C, T] matrix of ones
    they stand for, and each one's vjp against the other and against what JAX
    derives from the plain gather; groups one after another, or each from a
    row tile with pad rows behind it."""
    if routed is None:  # crafted: sizes 16, 0 and 21
        route = route_from(crafted_local(tile), held, capacity, tile)
        assert route.group_sizes.tolist() == [tile, 0, tile + 5]
        assert route.starts.tolist() == [0, tile, tile]
    else:
        route = route_of(jax.random.PRNGKey(20), tokens, k, routed, held,
                         capacity, tile=tile)
    here = np.asarray(route.here).sum(axis=1)
    live = int(route.group_sizes.sum())
    if routed is None:
        assert live < capacity
    elif len(held) == routed:
        assert live == capacity == tokens * k and (here == k).all()
    else:
        assert live < capacity and {0, 1}.issubset(here)
    if capacity == 192 and routed == 5:
        assert k in here
    assert int(np.asarray(route.row_live).sum()) == live
    ones = (np.asarray(route.row_live)[:, None]
            & (np.asarray(route.tok)[:, None] == np.arange(tokens)))
    ones = jnp.asarray(ones, jnp.float32)  # [C, T]
    x = jax.random.normal(jax.random.PRNGKey(21), (tokens, 32))
    buf = jax.random.normal(jax.random.PRNGKey(22), (capacity, 32))
    # rows past the routed count may hold anything: nothing reads them
    poisoned = jnp.where(route.row_live[:, None], buf, jnp.nan)
    hi = jax.lax.Precision.HIGHEST
    assert rel(expert_rows.to_buffer(x, route),
               jnp.dot(ones, x, precision=hi)) < 1e-6
    assert rel(expert_rows.from_buffer(poisoned, route),
               jnp.dot(ones.T, buf, precision=hi)) < 1e-6
    (d_x,) = jax.vjp(lambda x: expert_rows.to_buffer(x, route), x)[1](poisoned)
    (d_buf,) = jax.vjp(lambda b: expert_rows.from_buffer(b, route), buf)[1](x)
    assert rel(d_x, expert_rows.from_buffer(poisoned, route)) == 0
    assert rel(d_buf, expert_rows.to_buffer(x, route)) == 0
    (by_jax,) = jax.vjp(lambda x: jnp.where(
        route.row_live[:, None], x[route.tok], 0), x)[1](buf)
    assert rel(d_x, by_jax) < 1e-6
    (by_jax,) = jax.vjp(lambda b: jnp.where(
        route.here[..., None], b[route.rank], 0).sum(axis=1), buf)[1](x)
    assert rel(d_buf, by_jax) < 1e-6


# (tokens, k, experts routed, experts held, buffer rows, h, dtype, skew): the
# two decoder cells' buffers at their tokens and top-k (8 of 64 and of 256
# held, narrow rows); float32 rows; a router that sends every token to one
# held expert, whose range of a tile is four windows long
KERNEL_SHAPES = [
    pytest.param(8192, 8, 64, 8, 16384, 128, jnp.bfloat16, 0.0, 1,
                 id="mellum2_silo_code8k_buffer"),
    pytest.param(8192, 8, 256, 8, 4096, 128, jnp.bfloat16, 0.0, 1,
                 id="kimilin_silo_doc8k_buffer"),
    pytest.param(512, 4, 16, 4, 1024, 256, jnp.float32, 0.0, 1, id="float32"),
    pytest.param(512, 2, 8, 2, 1024, 128, jnp.float32, 9.0, 1,
                 id="a_range_of_four_windows"),
    pytest.param(8192, 8, 256, 8, 4096, 128, jnp.bfloat16, 0.0, 256,
                 id="kimilin_silo_doc8k_buffer_on_row_tiles"),
    pytest.param(512, 2, 8, 2, 1024, 128, jnp.float32, 9.0, 512,
                 id="a_whole_tile_group_of_four_windows_on_row_tiles"),
]


@pytest.mark.parametrize(
    "tokens, k, routed, held, capacity, h, dtype, skew, tile", KERNEL_SHAPES)
def test_the_from_buffer_kernel_matches_the_lax_form(tokens, k, routed, held,
                                                     capacity, h, dtype, skew,
                                                     tile):
    """The Pallas kernel in interpret mode against the gather, select and
    sum, with NaN in the buffer's rows past the groups and zeros in their pad
    rows (what the grouped products leave there)."""
    route = route_of(jax.random.PRNGKey(23), tokens, k, routed,
                     tuple(range(held)), capacity, skew, tile)
    if skew:
        assert int(route.group_sizes[0]) == tokens
    past = jnp.arange(capacity)[:, None] >= route.group_rows.sum()
    buf = jnp.where(route.row_live[:, None], jax.random.normal(
        jax.random.PRNGKey(24), (capacity, h)), jnp.where(past, jnp.nan, 0)
    ).astype(dtype)
    # the window the layer would pick; 64 rows where a range must outgrow it
    window = 64 if skew else expert_rows.window_rows(
        tokens, capacity, h, held, buf.dtype.itemsize)
    got = expert_rows.from_buffer_windows(buf, route, window, interpret=True)
    want = expert_rows.from_buffer_lax(buf, route)
    assert got.dtype == want.dtype == dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    # the same rows, added in float32 by expert where the lax form adds by slot
    assert rel(got.astype(jnp.float32), want.astype(jnp.float32)) < (
        1e-6 if dtype == jnp.float32 else 4e-3)


@pytest.mark.parametrize("tokens, capacity, h, held, itemsize, window", [
    (8192, 16384, 2304, 8, 2, 64),  # mellum2_silo_code8k
    (8192, 4096, 2304, 8, 2, 64),  # kimilin_silo_doc8k
    (8192, 65536, 2304, 8, 2, None),  # a share's worst case: windows past VMEM
    (8200, 16384, 2304, 8, 2, None),  # tokens that are no whole tiles
    (8192, 16384, 2300, 8, 2, None),  # rows that are no whole lanes
    (256, 32, 128, 2, 4, None),  # a buffer shorter than a window
    (8192, 65536, 2304, 64, 2, None),  # every expert held: the same
], ids=["mellum", "kimi", "worst_case", "ragged_tokens", "ragged_lanes",
        "short_buffer", "every_expert"])
def test_the_kernel_takes_the_shapes_it_tiles(tokens, capacity, h, held,
                                              itemsize, window):
    assert expert_rows.window_rows(tokens, capacity, h, held,
                                   itemsize) == window


def test_the_kernel_refuses_tokens_that_are_no_whole_tiles_by_name():
    route = route_of(jax.random.PRNGKey(25), 300, 2, 8, (0, 1), 256)
    with pytest.raises(ValueError, match="300 tokens in tiles of 256"):
        expert_rows.from_buffer_windows(jnp.zeros((256, 128)), route, 64,
                                        interpret=True)


# -- each group from a row tile of the grouped products ---------------------------

@pytest.mark.parametrize("case, tile", [("random", 16), ("crafted", 16),
                                        ("random", 1)],
                         ids=["random_tile_16", "empty_and_whole_tile_groups",
                              "no_pads"])
def test_every_group_starts_on_a_row_tile_and_keeps_its_rows_by_token(case,
                                                                      tile):
    """Each held expert's group starts on a multiple of ``tile`` and takes
    whole tiles; its first ``group_sizes[e]`` rows are its assignments by
    token, and a slot's rank names its row; the rest of the group is pad
    rows, which are not live.  With ``tile`` 1 it is the sort by expert."""
    if case == "crafted":
        local, held = crafted_local(tile), 3
    else:
        held = 2
        scores = jax.random.normal(jax.random.PRNGKey(26), (64, 16))
        local_of = np.full((16,), held, np.int32)
        local_of[[3, 9]] = np.arange(held)
        local = jnp.asarray(local_of)[jax.lax.top_k(scores, 4)[1]]
    route = route_from(local, held, 128, tile)
    sizes, rows = np.asarray(route.group_sizes), np.asarray(route.group_rows)
    starts, live = np.asarray(route.starts), np.asarray(route.row_live)
    assert (starts % tile == 0).all() and (rows % tile == 0).all()
    assert (rows - sizes < tile).all() and (rows[sizes == 0] == 0).all()
    tok, rank, loc = (np.asarray(a) for a in (route.tok, route.rank, local))
    for e in range(held):
        group = slice(starts[e], starts[e] + sizes[e])
        want = np.nonzero((loc == e).any(axis=1))[0]  # its tokens, in order
        assert tok[group].tolist() == want.tolist()
        assert live[group].all()
        assert not live[starts[e] + sizes[e]:starts[e] + rows[e]].any()
        t, j = np.nonzero(loc == e)
        assert (tok[rank[t, j]] == t).all()
        assert ((rank[t, j] >= starts[e])
                & (rank[t, j] < starts[e] + sizes[e])).all()
    assert not live[rows.sum():].any() and live.sum() == sizes.sum()
    if tile == 1:
        order = jnp.argsort(local.reshape(-1), stable=True)
        assert tok.tolist() == (np.asarray(order[:128]) // 4).tolist()


WIDE = dict(h=128, f=128)  # widths the grouped products tile


def wide_layer(held=(3, 9)):
    return ExpertLayer(16, held, 4, WIDE["f"])


def wide_case(key, sizes=None):
    """(params of the share of experts 3 and 9, x [1, 1024, 128]): a level
    router, or one that sends ``sizes`` tokens to experts 3 and 9 (the first
    ``sizes[0]`` to 3, the next ``sizes[1]`` to 9, none to either else)."""
    params = expert_params(key, h=WIDE["h"], f=WIDE["f"])
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, BRANCH_T, WIDE["h"]))
    if sizes is not None:
        t = jnp.arange(BRANCH_T)
        x = x.at[0, :, 0].set(jnp.where(t < sizes[0], 1.0, -1.0))
        x = x.at[0, :, 1].set(jnp.where(
            (t >= sizes[0]) & (t < sizes[0] + sizes[1]), 1.0, -1.0))
        one_hot = jnp.zeros((WIDE["h"],))
        params["router"] = params["router"].at[:, 3].set(
            one_hot.at[0].set(40.0)).at[:, 9].set(one_hot.at[1].set(40.0))
    return share_of(params, BRANCH_HELD), x


def layer_and_grads(layer, params, x, probe):
    def loss(p, x):
        y, counters = layer.apply({"params": p}, x)
        return (y.reshape(-1, WIDE["h"]) * probe).sum(), (y, counters)

    return jax.grad(loss, argnums=(0, 1), has_aux=True)(params, x)


def reference_and_grads(params, x, probe):
    cfg = {"num_experts": 2, "num_experts_routed": 16,
           "experts_held": BRANCH_HELD, "num_experts_per_tok": 4}

    def loss(p, x):
        y, chosen = plain.expert_layer(cfg, x.reshape(-1, WIDE["h"]), p)
        return (y * probe).sum(), (y, chosen)

    return jax.grad(loss, argnums=(0, 1), has_aux=True)(params, x)


def test_groups_on_row_tiles_give_the_layer_and_its_gradients_unmoved(
        monkeypatch):
    """At widths the grouped products tile (256-row tiles in a 1024-row short
    buffer) the layer lays its groups out from row tiles, and its output and
    every gradient match the family's plain reference and the same layer
    with its groups one after another (no row tile to align to)."""
    assert decoder.gmm_tiling(1024, WIDE["h"], WIDE["f"])[0] == 512
    assert decoder.gmm_tiling(1024, WIDE["h"], WIDE["f"], True)[0] == 256
    params, x = wide_case(jax.random.PRNGKey(27))
    probe = jax.random.normal(jax.random.PRNGKey(28), (BRANCH_T, WIDE["h"]))
    g_ours, (y, counters) = layer_and_grads(wide_layer(), params, x, probe)
    g_ref, (want, chosen) = reference_and_grads(params, x, probe)
    by_hand = np.bincount(np.asarray(chosen).reshape(-1),
                          minlength=16)[BRANCH_HELD]
    assert (0 < by_hand).all() and (by_hand <= 512).all()
    assert float(counters[ROWS_BUFFERED]) == 1024  # the short buffer ran
    # whole tiles, none shared
    assert float(counters[ROWS_COMPUTED]) == (-(-by_hand // 256) * 256).sum()
    assert float(counters[ASSIGNMENTS_HELD]) == by_hand.sum()
    monkeypatch.setattr(decoder, "gmm_tiling", lambda *shape: None)
    g_flat, (y_flat, c_flat) = layer_and_grads(wide_layer(), params, x, probe)
    assert float(c_flat[ROWS_COMPUTED]) == by_hand.sum()  # no row tile
    assert rel(y.reshape(-1, WIDE["h"]), want) < 1e-5
    assert rel(y, y_flat) < 1e-6
    for (path, a), b, c in zip(jax.tree_util.tree_leaves_with_path(g_ours),
                               jax.tree_util.tree_leaves(g_ref),
                               jax.tree_util.tree_leaves(g_flat)):
        assert rel(a, b) < 1e-5, jax.tree_util.keystr(path)
        assert rel(a, c) < 1e-6, jax.tree_util.keystr(path)


def test_rows_that_fit_but_whose_pads_do_not_take_the_fallback(monkeypatch):
    """800 + 100 routed rows fit the 1024-row short buffer, but from 256-row
    tiles they take 1024 + 256: the layer runs every held expert over every
    token, and says so in both counters; its output and gradients still
    match the reference.  Without a row tile the same rows take the buffer."""
    params, x = wide_case(jax.random.PRNGKey(29), sizes=(800, 100))
    probe = jax.random.normal(jax.random.PRNGKey(30), (BRANCH_T, WIDE["h"]))
    g_ours, (y, counters) = layer_and_grads(wide_layer(), params, x, probe)
    g_ref, (want, chosen) = reference_and_grads(params, x, probe)
    by_hand = np.bincount(np.asarray(chosen).reshape(-1),
                          minlength=16)[BRANCH_HELD]
    assert by_hand.tolist() == [800, 100]
    assert float(counters[ASSIGNMENTS_HELD]) == 900  # fits 1024 unpadded
    assert float(counters[ROWS_BUFFERED]) == BRANCH_T * 2  # the fallback ran
    assert float(counters[ROWS_COMPUTED]) == BRANCH_T * 2
    assert rel(y.reshape(-1, WIDE["h"]), want) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_ours),
                            jax.tree_util.tree_leaves(g_ref)):
        assert rel(a, b) < 1e-5, jax.tree_util.keystr(path)
    monkeypatch.setattr(decoder, "gmm_tiling", lambda *shape: None)
    _, counters = wide_layer().apply({"params": params}, x)
    assert float(counters[ROWS_BUFFERED]) == 1024
    assert float(counters[ROWS_COMPUTED]) == 900


@pytest.mark.parametrize("sizes, tile", [
    ([1024, 1031, 998, 1010, 1040, 1019, 1002, 990], 512),
    ([256, 270, 0, 243, 251, 512, 0, 260], 512),
    ([256, 270, 0, 243, 251, 512, 0, 260], 256),
    ([0, 0, 17, 0], 128),
    ([5, 600, 3], 1),
], ids=["mellum2_level", "kimilin_with_empty_groups", "kimilin_tile_256",
        "one_group_in_empties", "no_row_tile"])
@pytest.mark.parametrize("aligned", [False, True], ids=["one_after_another",
                                                        "on_row_tiles"])
def test_rows_computed_is_the_kernels_tiles_times_the_tile(sizes, tile,
                                                           aligned):
    """``rows_computed`` against ``megablox``'s own ``make_group_metadata``
    (the grid's row tiles) for the sizes handed to the products: the
    routed counts, or those rounded up to the tile (then their sum)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )

    given = np.asarray(sizes, np.int32)
    if aligned:
        given = -(-given // tile) * tile
    m = -(-max(int(given.sum()), 1) // tile) * tile
    _, tiles = make_group_metadata(
        group_sizes=jnp.asarray(given), m=m, tm=tile,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=len(sizes),
        visit_empty_groups=False)
    got = int(decoder.rows_computed(jnp.asarray(given), tile))
    assert got == int(tiles) * tile
    if aligned:
        assert got == int(given.sum())
    assert got >= sum(sizes)


@pytest.mark.parametrize("counters, want", [
    ({ASSIGNMENTS_HELD: [8192.0, 8200.0], ROWS_COMPUTED: [10240.0, 10752.0]},
     100 * 16392 / 20992),
    ({ASSIGNMENTS_HELD: [8192.0, 20000.0], ROWS_COMPUTED: [10240.0, 65536.0]},
     100 * 28192 / 75776),  # a fallback computes every token for each expert
    ({ASSIGNMENTS_HELD: [8192.0, 8200.0]}, None),  # the parent's program
    ({}, None),
], ids=["on_row_tiles", "one_fallback", "no_rows_computed", "no_counter"])
def test_tile_fill_is_held_assignments_over_rows_computed(counters, want):
    """``benchmark/layer_metrics/expert_tile_fill_pct.py`` on a made-up
    context: two traced calls' metrics, one entry a round."""
    import types

    from benchmark import cells

    calls = [(0.0, 0.1, 1, {"count": np.array([8192.0]),
                            **{k: np.array([v[i]]) for k, v in counters.items()}})
             for i in range(2)]
    metric = cells.load_layer_metric("expert_tile_fill_pct")
    assert metric.COUNTER == ROWS_COMPUTED
    got = metric.read(types.SimpleNamespace(calls=calls))
    assert got == (want if want is None else pytest.approx(want))
    entry = {m["name"]: m for m in cells.manifest()["per_layer"]}[
        "expert_tile_fill_pct"]
    assert entry["workloads"] == ["mellum2_silo_code8k", "kimilin_silo_doc8k",
                                  "keyevl2_silo_text8k",
                                  "trinitymini_silo_chat8k"]
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == ("program_counter", "kernels", "tokens_per_s",
                                 "%", "higher")


# -- through the round path ---------------------------------------------------------

def round_of(cfg, client_axis_impl="map"):
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
    fn = jax.jit(make_multi_round_fn(local_update, 1,
                                     client_axis_impl=client_axis_impl))
    return bundle, state, block, fn(state, *block)


def test_the_vmapped_client_axis_is_refused_by_name():
    """``lax.ragged_dot`` has no batching rule for stacked expert weights:
    the default client loop is a scan, and ``vmap`` says what it lacks."""
    with pytest.raises(NotImplementedError, match="ragged_dot vmap"):
        round_of(SHARE, "vmap")


def test_counters_leave_with_the_metrics_and_never_enter_variables():
    bundle, state, block, (new_state, metrics) = round_of(SHARE)
    assert set(new_state.variables) == {"params"}
    assert COUNTERS not in metrics
    # by hand: the reference's own selection of the first step of client 0
    # holds as many assignments as one forward of the model reports
    _, new_vars = bundle.apply_train(state.variables, block[0][0, 0])
    one = float(new_vars[COUNTERS][ASSIGNMENTS_HELD])
    _, chosen = plain.forward(SHARE, state.variables["params"],
                              block[0][0, 0], with_selection=True)
    assert one == sum(np.isin(np.asarray(c), SHARE["experts_held"]).sum()
                      for c in chosen)
    held = float(metrics[ASSIGNMENTS_HELD][0])
    fullest = float(metrics[EXPERT_TOKENS_MAX][0])
    # 2 clients x 2 steps x 4 layers x 64 tokens x top 2, 3 of 8 experts held
    assert 0 < held < 2 * 2 * 4 * 64 * 2 and held == int(held)
    assert held / 3 <= fullest <= held
    # one path at these shapes: every layer-step buffers its worst case, and
    # widths the grouped products do not tile compute the rows routed
    assert float(metrics[ROWS_BUFFERED][0]) == 2 * 2 * 4 * 64 * 2
    assert float(metrics[ROWS_COMPUTED][0]) == held
    assert float(metrics["count"][0]) == 2 * 2 * 2 * 32


@pytest.mark.parametrize("counters, want", [
    ({ASSIGNMENTS_HELD: [8192.0, 8200.0], ROWS_BUFFERED: [16384.0, 16384.0]},
     100 * 16392 / 32768),
    ({ASSIGNMENTS_HELD: [8192.0, 20000.0], ROWS_BUFFERED: [16384.0, 65536.0]},
     100 * 28192 / 81920),  # a fallback pulls the fill down
    ({ASSIGNMENTS_HELD: [8192.0, 8200.0]}, None),  # the parent's program
    ({}, None),
], ids=["short", "one_fallback", "no_rows_counter", "no_counter"])
def test_buffer_fill_is_held_assignments_over_rows_buffered(counters, want):
    """``benchmark/layer_metrics/expert_buffer_fill_pct.py`` on a made-up
    context: two traced calls' metrics, one entry a round."""
    import types

    from benchmark import cells

    calls = [(0.0, 0.1, 1, {"count": np.array([8192.0]),
                            **{k: np.array([v[i]]) for k, v in counters.items()}})
             for i in range(2)]
    got = cells.load_layer_metric("expert_buffer_fill_pct").read(
        types.SimpleNamespace(calls=calls))
    assert got == (want if want is None else pytest.approx(want))
    entry = {m["name"]: m for m in cells.manifest()["per_layer"]}[
        "expert_buffer_fill_pct"]
    assert entry["workloads"] == ["mellum2_silo_code8k"]
    assert (entry["source"], entry["moves"]) == ("program_counter",
                                                 "tokens_per_s")


def test_a_model_without_counters_reports_none():
    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.models.transformer import transformer_lm

    bundle = transformer_lm(vocab_size=64, embed_dim=32, num_heads=2,
                            num_layers=1, seq_len=16)
    fn = make_local_update(bundle, make_client_optimizer("sgd", 0.01), 1)
    x = jax.random.randint(jax.random.PRNGKey(0), (2, 2, 16), 0, 64)
    _, metrics = fn(bundle.init(jax.random.PRNGKey(1)), x, x,
                    jnp.ones((2, 2)), jax.random.PRNGKey(2))
    assert set(metrics) == {"loss_sum", "correct", "count", "steps"}


def test_one_round_agrees_with_the_benchmark_reference_through_the_driver():
    """``drivers/fused_plain.py`` hands ``run.py:check_reference`` the plain
    forward pass; the toy cell's round agrees with ``benchmark/reference.py``."""
    from benchmark import cells
    from benchmark import run as bench_run

    cell = cells.load_cell("mellum2_silo_code8k", rehearsal=True)
    session = cells.load_driver(cell.workload["driver"]).Session(
        cell, 11, jax.devices()[:1])
    assert isinstance(session.bundle, plain.PlainBundle)
    rounds, metrics = session.call()
    assert rounds == 1 and bench_run.call_ok(metrics, session.cohort)
    assert ASSIGNMENTS_HELD in metrics and EXPERT_TOKENS_MAX in metrics
    agreement = bench_run.check_reference(cell, session, 11)
    assert agreement["ok"], agreement
    assert agreement["delta_rel_l2"] < 0.01 and agreement["loss_rel"] < 1e-5


def test_it_is_reachable_by_name_from_the_experiment_entry_point(tmp_path):
    import json

    from fedml_tpu.experiments.run import ExperimentConfig, run_experiment

    path = tmp_path / "decoder.json"
    path.write_text(json.dumps(SHARE))
    out = run_experiment(ExperimentConfig(
        algorithm="fedllm", model="decoder_lm", model_config=str(path),
        dataset="fed_shakespeare", client_num_in_total=2,
        client_num_per_round=2, comm_round=1, batch_size=4, lr=0.01,
        max_samples_per_client=8, max_test_samples=8), log_fn=None)
    assert np.isfinite(out["final"]["test_loss"])
    assert out["history"][-1][ASSIGNMENTS_HELD] > 0
    with pytest.raises(ValueError, match="decoder_lm"):
        run_experiment(ExperimentConfig(
            algorithm="fedllm", model="decoder_lm", model_config=str(path),
            dataset="fed_shakespeare", client_num_in_total=4,
            client_num_per_round=4, comm_round=1, batch_size=4, mesh="4,2",
            max_samples_per_client=8, max_test_samples=8), log_fn=None)
