"""The model step's dense parts named on the device (``model.embed``,
``model.attn_proj``, ``model.norm``, ``model.head``, ``model.mlp_dense`` in
both model files) and the benchmark's readers of them.  Lowering only: no
program here is compiled or run."""

import contextlib
import importlib
import json
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import (  # noqa: E402
    cells, dense_groups, flops, model_scopes, trace_reduce,
)
from fedml_tpu.obs import scopes  # noqa: E402

from test_afmoe_decoder import SHARE as AFMOE_TOY  # noqa: E402
from test_decoder import SHARE as MELLUM_TOY  # noqa: E402
from test_decoder_kinds import SHARE as KIMI_TOY  # noqa: E402

NEW = {scopes.EMBED, scopes.ATTN_PROJ, scopes.NORM, scopes.HEAD}
CONFIGS = ["gpt2-large", "mellum2-12b-a2.5b", "kimi-linear-48b-a3b",
           "trinity-mini"]
GPT2L = ["gpt2l_silo_fused", "gpt2l_silo_spmd4"]
MELLUM, KIMI = "mellum2_silo_code8k", "kimilin_silo_doc8k"
# metric: (the scope its reader names, layer, better, cells whose traces hold
# something for it, cells left out because theirs do not)
METRICS = {
    "head_pct": (scopes.HEAD, "model step", "lower", GPT2L + [MELLUM, KIMI],
                 []),
    "embed_pct": (scopes.EMBED, "model step", "lower",
                  GPT2L + [MELLUM, KIMI], []),
    "head_roofline": (scopes.HEAD, "kernels", "higher",
                      GPT2L + [MELLUM, KIMI], []),
    "attn_proj_roofline": (scopes.ATTN_PROJ, "kernels", "higher",
                           GPT2L + [MELLUM], [KIMI]),
    "mlp_roofline": (scopes.MLP_DENSE, "kernels", "higher", GPT2L + [KIMI],
                     [MELLUM]),
    "model_scope_coverage_pct": (None, "device", "higher",
                                 GPT2L + [MELLUM, KIMI], []),
}
MODEL_SCOPE = re.compile(r"model\.[a-z_]+")


def toy_bundle(family):
    if family == "transformer_lm":
        from fedml_tpu.models.transformer import transformer_lm

        return transformer_lm(vocab_size=64, embed_dim=32, num_heads=4,
                              num_layers=2, seq_len=32)
    from fedml_tpu.models.decoder import decoder_lm

    return decoder_lm({"mellum_moe": MELLUM_TOY, "kimi_linear": KIMI_TOY,
                       "afmoe": AFMOE_TOY}[family])


def lower_round(bundle):
    """A toy round (2 clients x 2 steps x batch 2 x 32 tokens) of the fused
    round path over ``bundle``, lowered from shapes alone."""
    from fedml_tpu.algorithms.fedavg import ServerState, make_multi_round_fn
    from fedml_tpu.core.client import make_client_optimizer, make_local_update

    fn = jax.jit(make_multi_round_fn(make_local_update(
        bundle, make_client_optimizer("sgd", 0.01), epochs=1,
        compute_dtype=jnp.bfloat16), 1))
    key = jax.random.PRNGKey(0)
    variables = jax.eval_shape(bundle.init, key)
    state = ServerState(variables, (), jnp.zeros((), jnp.int32), key)
    shape = jax.ShapeDtypeStruct
    block = (shape((2, 2, 2, 32), jnp.int32), shape((2, 2, 2, 32), jnp.int32),
             shape((2, 2, 2), jnp.float32), shape((2,), jnp.float32),
             shape((2,), jnp.float32), shape((2,), jnp.int32))
    return variables, fn.lower(state, *block)


# what a layer of each kind holds, as the parent commit's trees have it
LAYER_NORMS = ["RMSNorm_0/scale 32", "RMSNorm_1/scale 32"]
EXPERTS = ["ExpertLayer_0/down 3x24x32", "ExpertLayer_0/gate 3x32x24",
           "ExpertLayer_0/router 32x8", "ExpertLayer_0/up 3x32x24"]
KDA = [f"LinearAttention_0/{leaf}" for leaf in (
    "A_log 2", "b_proj/kernel 32x2", "dt_bias 16", "f_a/kernel 32x8",
    "f_b/kernel 8x16", "g_a/kernel 32x8", "g_b/kernel 8x16", "k_conv 4x16",
    "k_proj/kernel 32x16", "o_norm/scale 8", "o_proj/kernel 16x32",
    "q_conv 4x16", "q_proj/kernel 32x16", "v_conv 4x16",
    "v_proj/kernel 32x16")]
MLA = [f"MultiHeadAttention_0/{leaf}" for leaf in (
    "Dense_0/kernel 16x32", "qkv/kv_a/kernel 32x20", "qkv/kv_b/kernel 16x32",
    "qkv/kv_norm/scale 16", "qkv/q/kernel 32x24")]
SHARED = [f"shared_expert/{m}/kernel {s}" for m, s in (
    ("down", "24x32"), ("gate", "32x24"), ("up", "32x24"))]
DENSE_MLP = [f"mlp/{m}/kernel {s}" for m, s in (
    ("down", "48x32"), ("gate", "32x48"), ("up", "32x48"))]
GPT_BLOCK = [
    "Dense_0/bias 128", "Dense_0/kernel 32x128", "Dense_1/bias 32",
    "Dense_1/kernel 128x32", "LayerNorm_0/bias 32", "LayerNorm_0/scale 32",
    "LayerNorm_1/bias 32", "LayerNorm_1/scale 32",
    "MultiHeadAttention_0/Dense_0/kernel 32x96",
    "MultiHeadAttention_0/Dense_1/kernel 32x32"]
MELLUM_BLOCK = EXPERTS + ["MultiHeadAttention_0/Dense_0/kernel 32x128",
                          "MultiHeadAttention_0/Dense_1/kernel 64x32"
                          ] + LAYER_NORMS
DECODER_TOP = ["lm_head/kernel 32x64", "norm_f/scale 32",
               "wte/embedding 64x32"]
# PR 41's family: a gate beside the fused q/k/v, q/k head norms, a norm after
# each sublayer, a selection bias the router's width, 4 of 8 experts held
GATED = [f"MultiHeadAttention_0/{leaf}" for leaf in (
    "Dense_0/kernel 32x80", "Dense_1/kernel 64x32", "gate/kernel 32x64",
    "k_norm/scale 8", "q_norm/scale 8")]
POST_NORMS = ["post_attn_norm/scale 32", "post_mlp_norm/scale 32"]
BIASED_EXPERTS = ["ExpertLayer_0/down 4x24x32", "ExpertLayer_0/gate 4x32x24",
                  "ExpertLayer_0/router 32x8",
                  "ExpertLayer_0/selection_bias 8",
                  "ExpertLayer_0/up 4x32x24"]
PINNED = {
    "transformer_lm": (
        2 * [GPT_BLOCK], ["ln_f/bias 32", "ln_f/scale 32",
                          "wpe/embedding 32x32", "wte/embedding 64x32"]),
    "mellum_moe": (4 * [MELLUM_BLOCK], DECODER_TOP),
    "kimi_linear": (
        [KDA + LAYER_NORMS + DENSE_MLP]
        + 2 * [EXPERTS + KDA + LAYER_NORMS + SHARED]
        + [EXPERTS + MLA + LAYER_NORMS + SHARED]
        + [EXPERTS + KDA + LAYER_NORMS + SHARED], DECODER_TOP),
    "afmoe": (
        [GATED + LAYER_NORMS + DENSE_MLP + POST_NORMS]
        + 4 * [BIASED_EXPERTS + GATED + LAYER_NORMS + POST_NORMS + SHARED],
        DECODER_TOP),
}
# the parts each toy model has: Mellum has no dense MLP
PARTS = {"transformer_lm": NEW | {scopes.MLP_DENSE, scopes.ATTN_FULL},
         "mellum_moe": NEW | {scopes.ATTN_FULL, scopes.ATTN_SLIDING},
         "kimi_linear": NEW | {scopes.MLP_DENSE},
         "afmoe": NEW | {scopes.MLP_DENSE, scopes.ATTN_GATE,
                         scopes.ATTN_FULL, scopes.ATTN_SLIDING,
                         scopes.MOE_SHARED}}
HEAD_MODULE = {"transformer_lm": "wte.attend", "mellum_moe": "lm_head",
               "kimi_linear": "lm_head", "afmoe": "lm_head"}


@pytest.mark.parametrize("family", sorted(PINNED))
def test_the_dense_parts_are_named_and_the_program_is_the_parents(
        family, monkeypatch):
    variables, lowered = lower_round(toy_bundle(family))
    # an op's name is a path through the scopes; an argument's is not
    names = {n for n in re.findall(r'loc\("([^"]*)"', lowered.as_text(
        debug_info=True)) if "fed." in n}
    in_model = [n for n in names if "fed.model" in n]
    for part in PARTS[family]:
        assert any("transpose(" not in n and part in n
                   for n in in_model), f"{part} forward"
        assert any("transpose(jvp(fed.model))" in n and part in n
                   for n in in_model), f"{part} backward"
    # the head's module sits under ``model.head``, and nothing else does
    module = HEAD_MODULE[family]
    heads = [n for n in names if module in n]
    assert heads and all(f"{scopes.HEAD}/{module}" in n for n in heads)
    assert all(module in n for n in names if scopes.HEAD in n)
    # no scope goes around another: an op has one ``model.*`` segment at
    # most, so the older names keep every op they had, and only under
    # ``fed.model``
    assert all(len(set(MODEL_SCOPE.findall(n))) <= 1 for n in names)
    assert all("fed.model" in n for n in names if MODEL_SCOPE.search(n))
    assert set(m for n in names for m in MODEL_SCOPE.findall(n)) <= set(
        scopes.MODEL_SCOPES)
    # a scope is debug info: the program the compile cache keys is the one
    # the same code lowers to with no scope at all
    text = lowered.as_text()
    assert "model." not in text and "fed." not in text
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert lower_round(toy_bundle(family))[1].as_text() == text
    # and the parameter tree is the parent's
    blocks, top = PINNED[family]
    want = sorted([f"Block_{i}/{leaf}" for i, block in enumerate(blocks)
                   for leaf in block] + top)
    got = ["/".join(k.key for k in path) + " " + "x".join(map(str, a.shape))
           for path, a in jax.tree_util.tree_flatten_with_path(
               variables["params"])[0]]
    assert got == want


def test_the_vocabulary_and_its_readers_agree():
    # (the count of ``MODEL_SCOPES`` is pinned once, in test_decoder_kinds)
    assert NEW <= set(scopes.MODEL_SCOPES) and not NEW & set(scopes.SCOPES)
    assert len(set(scopes.MODEL_SCOPES)) == len(scopes.MODEL_SCOPES)
    assert not [s for s in scopes.MODEL_SCOPES
                if re.search(r"fed\.[a-z_]+", s)
                or not MODEL_SCOPE.fullmatch(s)]
    assert cells.load_layer_metric(
        "model_scope_coverage_pct").STAGE == scopes.MODEL
    for name, (scope, _, _, _, _) in METRICS.items():
        if scope is not None:
            assert cells.load_layer_metric(name).SCOPE == scope
    # a group is its scope without the prefix
    for config in CONFIGS:
        groups = dense_groups.products(cells.read_json(
            "configs", f"{config}.json"))
        assert {dense_groups.PREFIX + g for g in groups} <= set(
            scopes.MODEL_SCOPES)


@pytest.mark.parametrize("config", CONFIGS)
def test_the_groups_sum_to_the_familys_matmul_class(config):
    config = cells.read_json("configs", f"{config}.json")
    family = cells.load_family(config)
    groups = dense_groups.fwd_flops(config)
    assert groups["head"] == (2 * config["vocab_size"] * config.get(
        "hidden_size", config.get("n_embd")))
    assert sum(groups.values()) == family.fwd_flops_per_unit(config)["matmul"]
    assert 3 * sum(groups.values()) == flops.train_flops_per_unit(config)[
        "matmul"]
    batch_units = 8192
    ours = 3 * sum(dense_groups.pass_bytes(config, batch_units).values())
    theirs = family.train_bytes_per_unit(config, batch_units)["matmul"]
    # ``transformer_lm`` counts one width more a layer than its products
    # have, 4 % of its bytes; FLOPs bound every group but the router
    assert ours == pytest.approx(
        theirs, rel=5e-2 if config["family"] == "transformer_lm" else 1e-12)
    assert ours <= theirs * (1 + 1e-12)


LATER = "a_later_family"


@pytest.fixture
def later_family(tmp_path, monkeypatch):
    """Writes the files a later PR would add for a family ``LATER``:
    ``families/<name>.py`` whose ``matmul`` class counts ``matmul`` forward
    FLOPs a token and, given ``products`` (source text of the function's
    result, or of the whole file), ``dense_products/<name>.py``."""
    import benchmark.dense_products
    import benchmark.families

    for package in (benchmark.families, benchmark.dense_products):
        leaf = tmp_path / package.__name__.split(".")[-1]
        leaf.mkdir()
        monkeypatch.setattr(package, "__path__",
                            list(package.__path__) + [str(leaf)])

    def write(matmul, products=None):
        (tmp_path / "families" / f"{LATER}.py").write_text(
            f"def fwd_flops_per_unit(config):\n"
            f"    return {{'matmul': {matmul}}}\n"
            f"def units_per_sample(config):\n    return 1024\n")
        if products is not None:
            (tmp_path / "dense_products" / f"{LATER}.py").write_text(
                products if "import" in products else
                f"def products(config):\n    return {products}\n")
        importlib.invalidate_caches()

    yield write
    for package in ("families", "dense_products"):
        sys.modules.pop(f"benchmark.{package}.{LATER}", None)


@pytest.mark.parametrize("case", ["with a file", "without", "disagrees",
                                  "broken file"])
def test_a_familys_products_are_found_by_its_name_and_held_to_it(
        case, later_family):
    """No reader knows a family by name: a family a later PR adds gets the
    group rooflines by adding ``dense_products/<family>.py``, nothing without
    one, and a refusal where its file and its family disagree."""
    config = {"family": LATER}
    # wide enough for FLOPs to bound them, as the cells' products are
    groups = ("{'head': [(1024, 32768)], "
              "'attn_proj': 2 * [(1024, 3072), (1024, 1024)]}")
    head, attn = 2 * 1024 * 32768, 2 * 2 * 1024 * 4096
    ctx = context(SCOPED)
    ctx.cell.config = config
    if case == "with a file":
        later_family(head + attn, groups)
        assert dense_groups.fwd_flops(config) == {"head": head,
                                                  "attn_proj": attn}
        assert dense_groups.roofline(ctx, "head") == pytest.approx(
            100 * (3 * head * TOKENS / PEAK) / 0.75, rel=1e-12)
        assert dense_groups.roofline(ctx, "mlp_dense") is None
    elif case == "without":
        later_family(head + attn)
        assert dense_groups.products(config) is None
        assert dense_groups.fwd_flops(config) == {}
        assert [dense_groups.roofline(ctx, g)
                for g in ("head", "attn_proj", "mlp_dense")] == 3 * [None]
        # and the readers that need no products read on
        assert cells.load_layer_metric("head_pct").read(ctx) == 40.0
    elif case == "disagrees":
        later_family(head, groups)
        with pytest.raises(ValueError, match=LATER):
            dense_groups.products(config)
    else:
        # a file that is there and cannot be imported is a fault, not absence
        later_family(1, "import no_such_module_of_any_name\n")
        with pytest.raises(ModuleNotFoundError, match="no_such_module"):
            dense_groups.products(config)


def test_no_shared_reader_names_a_family():
    """``families/transformer_lm.py``'s rule: nothing in the harness but a
    file found by the ``family`` key knows a family by name."""
    root = os.path.join(cells.ROOT)
    families = {f[:-3] for f in os.listdir(os.path.join(root, "families"))
                if f.endswith(".py") and f != "__init__.py"}
    shipped = {f[:-3] for f in os.listdir(os.path.join(
        root, "dense_products")) if f != "__init__.py" and f.endswith(".py")}
    assert shipped <= families and {"transformer_lm", "mellum_moe",
                                    "kimi_linear", "afmoe"} <= shipped
    for path in ["dense_groups.py", "model_scopes.py",
                 os.path.join("tools", "matmul_table.py")] + [
            os.path.join("layer_metrics", f"{m}.py") for m in METRICS]:
        with open(os.path.join(root, path)) as f:
            code = f.read()
        assert not [name for name in families
                    if re.search(rf"\b{name}\b", code)], path


# -- the readers, on a made-up trace ------------------------------------------

STEP = "jit(multi_round_fn)/fed.round/fed.step/"
FWD, BWD = STEP + "jvp(fed.model)/", STEP + "transpose(jvp(fed.model))/"


def op(tf_op, seconds, klass="matmul"):
    return trace_reduce.Op("fusion.1 [convolution] x", 0.0, 0.0,
                           self_ns=seconds * 1e9, klass=klass,
                           stats={"tf_op": tf_op})


def context(ops, cell="gpt2l_silo_fused", busy_s=2.0, calls=3):
    """``calls`` traced calls of one round of 16 padded samples each."""
    cell = cells.load_cell(cell)
    device = trace_reduce.DeviceSummary(
        plane="/device:TPU:0", busy_ns=busy_s * 1e9, class_ns={},
        collective_ns=0.0, collective_exposed_ns=0.0, busy=[], ops=ops)
    summary = trace_reduce.Summary(window_ns=busy_s * 1e9, devices=[device],
                                   host_gaps=[], calls=calls)
    session = types.SimpleNamespace(padded_samples_per_round=lambda: 16)
    return trace_reduce.Context(
        summary=summary, cell=cell, session=session,
        calls=[(0.0, 0.1, 1, {})] * calls, device_kind="TPU v5 lite")


SCOPED = [
    op(FWD + "TransformerLM/model.head/wte.attend/dot_general", 0.25),
    op(BWD + "TransformerLM/model.head/wte.attend/dot_general", 0.5),
    # a transpose beside the product: the head's time, not its matmul's
    op(BWD + "TransformerLM/model.head/wte.attend/transpose", 0.05, "other"),
    op(FWD + "TransformerLM/model.embed/wte/jit(_take)/gather", 0.01,
       "other"),
    op(BWD + "TransformerLM/model.embed/wte/jit(_take)/scatter-add", 0.03,
       "other"),
    op(FWD + "TransformerLM/Block_0/MultiHeadAttention_0/model.attn_proj/"
       "Dense_0/dot_general", 0.2),
    op(BWD + "TransformerLM/Block_0/model.mlp_dense/Dense_1/dot_general",
       0.4),
    op(FWD + "TransformerLM/Block_0/add", 0.06, "other"),  # a residual
    op(STEP + "fed.optimizer/add", 0.1, "other"),
]
UNSCOPED = [op(FWD + "TransformerLM/wte.attend/dot_general", 0.75),
            op(FWD + "TransformerLM/Block_0/Dense_1/dot_general", 0.6)]
TOKENS = 3 * 16 * 1024  # computed in the made-up calls
PEAK = 197e12


def by_hand(metric):
    if metric == "head_pct":
        return 100 * 0.8 / 2.0
    if metric == "embed_pct":
        return 100 * 0.04 / 2.0
    if metric == "model_scope_coverage_pct":
        return 100 * 1.44 / 1.5
    d, layers, vocab = 1280, 8, 50257
    fwd = {"head_roofline": (2 * d * vocab, 0.75),
           "attn_proj_roofline": (layers * 2 * 4 * d * d, 0.2),
           "mlp_roofline": (layers * 2 * 2 * d * 5120, 0.4)}[metric]
    return 100 * (3 * fwd[0] * TOKENS / PEAK) / fwd[1]


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_reader_gives_the_share_by_hand_and_nothing_without_the_scope(
        metric):
    read = cells.load_layer_metric(metric).read
    assert read(context(SCOPED)) == pytest.approx(by_hand(metric), rel=1e-12)
    assert read(context(UNSCOPED)) is None


def test_an_op_belongs_to_its_last_model_scope_and_groups_keep_their_cells():
    inner = op(FWD + "DecoderLM/model.mla_proj/x/model.attn_proj/dot", 1.0)
    assert model_scopes.innermost(inner) == "model.attn_proj"
    assert model_scopes.innermost(UNSCOPED[0]) is None
    ctx = context(SCOPED)
    assert model_scopes.seconds(ctx, "model.head") == pytest.approx(0.8)
    assert model_scopes.seconds(ctx, "model.head", klass="matmul",
                                backward=True) == pytest.approx(0.5)
    assert model_scopes.seconds(ctx, "model.kda_scan") is None
    # a configuration without the group has no roofline for it, whatever
    # the trace holds: Mellum has no dense MLP
    assert dense_groups.roofline(
        context(SCOPED, "mellum2_silo_code8k"), "mlp_dense") is None
    # over four devices the seconds are a device's mean and the tokens all
    # four chips': the share is the same as one chip's at a quarter the work
    four = context(SCOPED, "gpt2l_silo_spmd4")
    four.summary.devices = four.summary.devices * 4
    assert dense_groups.roofline(four, "head") == pytest.approx(
        by_hand("head_roofline") / 4)


@pytest.mark.parametrize("klass", ["matmul", "other", "all"])
def test_the_table_splits_a_class_by_scope_and_its_rows_sum_to_it(klass):
    """``tools/matmul_table.py``: every op of the class is in one row, the
    ops of no part in the last, and ``all`` is how ``model.norm`` and the
    parts' seconds outside their products are read."""
    from benchmark.tools import matmul_table

    norm = op(BWD + "TransformerLM/Block_0/model.norm/LayerNorm_0/mul", 0.02,
              "other")
    summary = context(SCOPED + [norm]).summary
    rows = matmul_table.table(summary, klass)
    kept = [o for o in SCOPED + [norm] if klass in ("all", o.klass)]
    assert sum(s for s, _ in rows.values()) == pytest.approx(
        sum(o.self_ns for o in kept) / 1e9)
    assert {c for _, c, _ in rows} == {o.klass for o in kept}
    if klass == "matmul":
        assert rows[("model.head", "matmul", True)][0] == pytest.approx(0.5)
        assert set(s for s, _, _ in rows) == {
            "model.head", "model.attn_proj", "model.mlp_dense"}
    else:
        assert rows[("model.norm", "other", True)][0] == pytest.approx(0.02)
        assert rows[("model.head", "other", True)][0] == pytest.approx(0.05)
        # no part: the residual inside the model, the optimizer outside it
        assert rows[("(fed.model)", "other", False)][0] == pytest.approx(0.06)
        assert rows[("(fed.optimizer)", "other", False)][0] == pytest.approx(
            0.1)


def test_the_table_counts_tokens_as_the_metrics_do():
    """The tool takes the cell, the seed, the rounds and the device kind from
    the traced run's result line and hands ``computed_units`` the cell's own
    resident block: one source with the metrics, on any chip."""
    from benchmark.layer_metrics.step_mfu_pct import computed_units
    from benchmark.tools import matmul_table

    summary = context(SCOPED).summary
    result = {"device": {"kind": "TPU v5e"},
              "detail": {"cell": "gpt2l_silo_fused", "seed": 3000000371,
                         "calls": 3, "rounds": 6}}
    ctx = matmul_table.context_of(result, summary)
    geometry = ctx.cell.geometry
    assert ctx.device_kind == "TPU v5e"
    assert ctx.session.padded_samples_per_round() == (
        geometry["cohort"] * geometry["batch"] * geometry["sizes"]["steps"])
    assert computed_units(ctx) == 6 * 4 * 8 * 4 * 1024
    result["detail"]["calls"] = 2  # another run's line
    with pytest.raises(SystemExit):
        matmul_table.context_of(result, summary)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_benchmark_json_has_the_metric_with_its_fields_and_cells(name):
    manifest = cells.manifest()
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    _, layer, better, reads, silent = METRICS[name]
    assert (entry["layer"], entry["better"], entry["unit"], entry["source"],
            entry["moves"]) == (layer, better, "%", "device_trace",
                                "tokens_per_s")
    assert set(reads) <= set(entry["workloads"])
    assert not set(silent) & set(entry["workloads"])
    assert len(json.dumps(manifest)) < 64 * 1024


# -- linear_attn_kernel_pct (PR 38): the scan's seconds that are kernels -------

KDA_FWD = FWD + "DecoderLM/Block_1/LinearAttention_0/model.kda_scan/vmap("
KDA_BWD = BWD + "DecoderLM/Block_1/LinearAttention_0/model.kda_scan/vmap("


def scan_op(tf_op, seconds, category):
    made = op(tf_op, seconds, "other")
    made.stats["hlo_category"] = category
    return made


SCAN = [
    scan_op(KDA_FWD + "kda_pairs_fwd)/pallas_call", 0.3, "custom-call"),
    scan_op(KDA_BWD + "kda_scan_bwd)/pallas_call", 0.5, "custom-call"),
    # the solve between the kernels is a custom call too, but no Pallas one
    scan_op(KDA_FWD + "triangular_solve)", 0.15, "custom-call"),
    scan_op(KDA_BWD + "mul)", 0.05, "data formatting"),
    # a kernel of another layer is no part of the scan
    scan_op(FWD + "DecoderLM/Block_4/model.attn_latent/vmap(flash_fwd)/"
            "pallas_call", 0.7, "custom-call"),
]


@pytest.mark.parametrize("ops, want", [
    (SCAN, 100 * 0.8 / 1.0), (SCAN[2:], 0.0), (SCAN[4:] + SCOPED, None),
], ids=["kernels_and_lax_ops", "lax_ops_alone", "no_op_under_the_scope"])
def test_the_scans_kernel_share_is_its_pallas_calls_seconds(ops, want):
    read = cells.load_layer_metric("linear_attn_kernel_pct").read
    got = read(context(ops, cell=KIMI))
    assert got is None if want is None else got == pytest.approx(want,
                                                                 rel=1e-12)


def test_benchmark_json_names_the_scans_kernel_share_and_its_file():
    (entry,) = [m for m in cells.manifest()["per_layer"]
                if m["name"] == "linear_attn_kernel_pct"]
    assert entry == {"name": "linear_attn_kernel_pct", "unit": "%",
                     "better": "higher", "source": "device_trace",
                     "layer": "kernels", "moves": "tokens_per_s",
                     "workloads": [KIMI]}
    root = os.path.dirname(os.path.abspath(cells.__file__))
    assert os.path.isfile(os.path.join(
        root, "layer_metrics", "linear_attn_kernel_pct.py"))
    # it stood last when it was added (PR 38): nothing before it moved, and
    # what later PRs add stands after it
    assert cells.manifest()["per_layer"][34] == entry
