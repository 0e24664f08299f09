"""``MultiHeadAttention``'s output gate (``gate=True``: a sigmoid of a fourth
projection of the layer's input multiplies the attention function's output
before the output projection), and the window at 8 q heads a k/v head in the
flash kernels (interpret mode) against the lax path: float32, toy sizes."""

import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.transformer import MultiHeadAttention, lax_attention
from fedml_tpu.obs import scopes
from fedml_tpu.ops.flash_attention import flash_attention

PINNED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                      "lowered_parent_pr40.json")
B, L, E, H, G, D = 2, 16, 32, 8, 1, 4  # 8 q heads share the one k/v head


def module(**over):
    return MultiHeadAttention(H, attn_fn=lax_attention, num_kv_heads=G,
                              head_dim=D, **over)


@pytest.fixture(scope="module")
def gated():
    x = jax.random.normal(jax.random.PRNGKey(1), (B, L, E))
    params = module(gate=True).init(jax.random.PRNGKey(0), x)["params"]
    return x, params


def without_gate(params):
    return {k: v for k, v in params.items() if k != "gate"}


def test_the_gate_is_a_fourth_projection_of_its_own(gated):
    _, params = gated
    assert set(params) == {"Dense_0", "Dense_1", "gate"}
    assert params["gate"]["kernel"].shape == (E, H * D)
    assert set(params["gate"]) == {"kernel"}  # no bias
    # the fused q/k/v and the output projection are what they were
    assert params["Dense_0"]["kernel"].shape == (E, (H + 2 * G) * D)
    assert params["Dense_1"]["kernel"].shape == (H * D, E)


def test_a_gate_projection_of_zeros_halves_the_ungated_output(gated):
    x, params = gated
    zeros = {**params, "gate": {"kernel": jnp.zeros((E, H * D))}}
    ungated = module().apply({"params": without_gate(params)}, x)
    got = module(gate=True).apply({"params": zeros}, x)
    np.testing.assert_allclose(got, 0.5 * ungated, rtol=1e-6, atol=1e-7)


def test_the_gated_output_is_the_equation_written_out(gated):
    """``y = (concat_i(o_i) * sigmoid(a Wz)) Wo``, per head and channel."""
    x, params = gated
    # the attention function's output: the ungated module before ``Wo``
    eye = {**without_gate(params), "Dense_1": {"kernel": jnp.eye(H * D, E)}}
    o = module().apply({"params": eye}, x)  # H * D == E here
    assert H * D == E
    want = (o * jax.nn.sigmoid(x @ params["gate"]["kernel"])) \
        @ params["Dense_1"]["kernel"]
    got = module(gate=True).apply({"params": params}, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # a large positive gate passes the head's output, a large negative one
    # closes it
    for value, factor in ((40.0, 1.0), (-40.0, 0.0)):
        # x's first channel is made 1 and the gate reads only it
        kernel = jnp.zeros((E, H * D)).at[0].set(value)
        xs = x.at[..., 0].set(1.0)
        got = module(gate=True).apply(
            {"params": {**params, "gate": {"kernel": kernel}}}, xs)
        ungated = module().apply({"params": without_gate(params)}, xs)
        np.testing.assert_allclose(got, factor * ungated, rtol=1e-5,
                                   atol=1e-6)


def test_the_gate_learns_and_its_ops_carry_its_scope_outside_the_vmap(gated):
    x, params = gated
    loss = lambda p: (module(gate=True).apply({"params": p}, x) ** 2).sum()  # noqa: E731
    grads = jax.grad(loss)(params)
    assert float(jnp.abs(grads["gate"]["kernel"]).max()) > 0
    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    under = [n for n in names if scopes.ATTN_GATE in n]
    assert any("transpose(" in n for n in under)
    assert any("transpose(" not in n for n in under)
    # the projection, the sigmoid and the multiply, and nothing of the
    # attention function: the trace's attention class is what runs under the
    # module's vmap
    assert any("gate/dot_general" in n for n in under)
    assert any("logistic" in n for n in under)
    assert not [n for n in under if "vmap(" in n]
    assert not [n for n in under if scopes.ATTN_PROJ in n]
    assert scopes.ATTN_GATE in scopes.MODEL_SCOPES
    assert scopes.ATTN_GATE not in scopes.SCOPES


def lowered_sha(fn, *args):
    return hashlib.sha256(jax.jit(fn).lower(*args).as_text().encode()
                          ).hexdigest()


def ungated_program():
    """Forward and gradients of the module with the gate off, lowered from
    shapes alone: what the parent commit's module lowers to, op for op."""
    x = jax.ShapeDtypeStruct((B, L, E), jnp.float32)
    params = jax.eval_shape(
        lambda k: module().init(k, jnp.zeros((B, L, E)))["params"],
        jax.random.PRNGKey(0))
    return lowered_sha(jax.value_and_grad(
        lambda p, x: (module().apply({"params": p}, x) ** 2).sum()), params, x)


def test_with_the_gate_off_the_module_is_the_parents_to_the_bit(gated):
    x, _ = gated
    tree = module().init(jax.random.PRNGKey(0), x)["params"]
    assert set(tree) == {"Dense_0", "Dense_1"}
    with open(PINNED) as f:
        pinned = json.load(f)
    assert ungated_program() == pinned["multi_head_attention_ungated"], (
        "MultiHeadAttention with every option off no longer lowers to the "
        "program it lowered to at PR 40 (tests/testdata/"
        "lowered_parent_pr40.json; tests/lowered_programs.py writes it)")


# -- the window at 8 q heads a k/v head -------------------------------------------

def flash(block):
    return lambda q, k, v, window: flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, window=window,
        interpret=True)


def lax(q, k, v, window):
    return lax_attention(q, k, v, True, window=window)


@pytest.mark.parametrize("window", [256, 100, None],
                         ids=["window_4_blocks", "window_off_the_blocks",
                              "full"])
def test_flash_kernels_match_the_lax_path_at_8_to_1(window):
    """[512, 16 q heads on 2 k/v heads, 128] in blocks of 64: the gated
    cell's sliding layers have a window of four blocks (2048 = 4 x 512) at 8
    q heads a k/v head, its full layer none."""
    length, heads, kv_heads, size = 512, 16, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (length, heads, size))
    k, v = (jax.random.normal(ks[i], (length, kv_heads, size))
            for i in (1, 2))
    probe = jax.random.normal(ks[3], (length, heads, size))

    def both(attn):
        out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, window), q, k, v)
        return (out,) + vjp(probe)

    for got, want in zip(both(flash(64)), both(lax)):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
