"""The flash kernels compiled for a v5e that is described, not attached:
what Mosaic refuses (a block off the tiling, a transpose it cannot lower,
more VMEM than a kernel may use) shows here and not on the chip.
Interpret mode accepts all of that, so the other flash tests cannot.

The topology is described inside a fixture, never at import (one process
at a time may load the TPU's library; see the on-chip-measurement guide),
and every test of the kind lives in this one file: so also the memory pin
of the round whose client loop carries the weighted sum (ISSUE 27)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fedml_tpu.ops.flash_attention import (
    MAX_LENGTH, flash_attention, pick_block,
)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


# (shape, dtype): the benchmark cells' own call (gpt2-large: a batch of 8
# under the model's vmap, two heads to a 128-lane block); width 1280 / 10
# at the lengths of chip_smoke.py's flash leg; float32, which the policy
# sends here from 2048 on; the longest sequence pick_block accepts
CASES = [
    pytest.param((8, 1024, 20, 64), jnp.bfloat16, id="cells_b8_L1024_h20_d64"),
    pytest.param((2048, 10, 128), jnp.bfloat16, id="L2048_h10_d128"),
    pytest.param((8192, 10, 128), jnp.bfloat16, id="L8192_h10_d128"),
    pytest.param((2048, 10, 128), jnp.float32, id="L2048_float32"),
    pytest.param((MAX_LENGTH, 2, 128), jnp.float32, id="max_length_float32"),
]


@pytest.mark.parametrize("shape, dtype", CASES)
def test_forward_and_backward_compile_for_v5e(one_chip, shape, dtype):
    block = pick_block(shape[-3], shape[-1])
    assert block

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block)

    if len(shape) == 4:
        attn = jax.vmap(attn)

    def grads(q, k, v, do):
        return jax.grad(lambda q, k, v: (
            attn(q, k, v).astype(jnp.float32) * do.astype(jnp.float32)
        ).sum(), argnums=(0, 1, 2))(q, k, v)

    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = jax.jit(grads).lower(x, x, x, x).compile().as_text()
    # forward and backward, each one Mosaic kernel
    assert text.count("tpu_custom_call") == 2
    assert "flash_fwd" in text and "flash_bwd" in text
    assert "flash_dq" not in text and "flash_dkv" not in text


# (q heads, k/v heads, window) at [1, 8192, ., 128] bf16 under the model's
# vmap: the decoder cell's sliding and full layers (4 q heads share one k/v
# head, window 1024), a window that is no multiple of the block, and the
# ``trinitymini_silo_chat8k`` cell's layers (8 q heads share each of 4 k/v
# heads: a window of 2048 = 4 blocks of 512 on its sliding layers, none on
# its full one)
WINDOWED = [
    pytest.param(4, 1, 1024, id="decoder_cell_sliding"),
    pytest.param(4, 1, None, id="decoder_cell_full"),
    pytest.param(8, 2, 1000, id="window_off_the_blocks"),
    pytest.param(32, 4, 2048, id="gated_cell_sliding_8_to_1"),
    pytest.param(32, 4, None, id="gated_cell_full_8_to_1"),
]


@pytest.mark.parametrize("heads, kv_heads, window", WINDOWED)
def test_window_and_shared_kv_heads_compile_for_v5e(one_chip, heads,
                                                    kv_heads, window):
    L, D = 8192, 128
    block = pick_block(L, D)

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block, window=window)

    def grads(q, k, v, do):
        return jax.grad(lambda q, k, v: (
            jax.vmap(attn)(q, k, v).astype(jnp.float32)
            * do.astype(jnp.float32)).sum(), argnums=(0, 1, 2))(q, k, v)

    spec = lambda h: jax.ShapeDtypeStruct(  # noqa: E731
        (1, L, h, D), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(grads).lower(
        spec(heads), spec(kv_heads), spec(kv_heads), spec(heads)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "flash_fwd" in text and "flash_bwd" in text


@pytest.mark.parametrize("heads, kv_heads", [
    pytest.param(32, 4, id="sparse_cell_8_to_1"),
    pytest.param(4, 4, id="a_head_each"),
])
def test_a_choice_of_keys_compiles_for_v5e(one_chip, heads, kv_heads):
    """The sparse-attention layer of the ``keyevl2_silo_text8k`` cell under
    the model's vmap: bf16 [1, 8192, 32, 128] over 4 k/v heads, the choice as
    an [8192, 8192] int8 mask by 512-tiles and its [16, 16] table in SMEM."""
    L, D = 8192, 128
    block = pick_block(L, D)

    def attn(q, k, v, keep, tiles):
        return flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block, keep=keep, tiles=tiles)

    def grads(q, k, v, do, keep, tiles):
        return jax.grad(lambda q, k, v: (
            jax.vmap(attn)(q, k, v, keep, tiles).astype(jnp.float32)
            * do.astype(jnp.float32)).sum(), argnums=(0, 1, 2))(q, k, v)

    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    text = jax.jit(grads).lower(
        spec((1, L, heads, D)), spec((1, L, kv_heads, D)),
        spec((1, L, kv_heads, D)), spec((1, L, heads, D)),
        spec((1, L, L), jnp.int8),
        spec((1, L // block, L // block), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "flash_fwd" in text and "flash_bwd" in text


def test_the_choice_of_keys_is_one_kernel_on_a_v5e(one_chip, monkeypatch):
    """``select_topk`` of the ``keyevl2_silo_text8k`` cell under the model's
    vmap: bf16 index queries [1, 8192, 16, 64] against one index key a token,
    ``topk`` 2048.  Where the shape test reads a TPU it is one Mosaic kernel,
    ``select_topk``, whose row block keeps its [512, 8192] images, a head's
    weights along the lanes and two buffers of its int8 mask in VMEM; no
    ``while`` over row blocks and no [512, 16, 8192] product is left."""
    from fedml_tpu.ops import sparse_select as ss

    L, heads, dim, topk = 8192, 16, 64, 2048
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    qI, kI = spec((1, L, heads, dim)), spec((1, L, dim))
    assert ss.kernel_tiles(*jax.eval_shape(lambda q, k: (q[0], k[0]), qI, kI))
    compiled = jax.jit(jax.vmap(
        lambda *i: ss.select_topk(*i, topk))).lower(
        qI, kI, spec((1, L, heads), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert sum("tpu_custom_call" in line and "select_topk" in line
               for line in text.splitlines()) == 1
    assert " while(" not in text and f"[512,{heads},{L}]" not in text
    keep, tiles = jax.eval_shape(
        jax.vmap(lambda *i: ss.select_topk(*i, topk)), qI, kI,
        spec((1, L, heads), jnp.float32))
    assert (keep.shape, keep.dtype) == ((1, L, L), jnp.int8)
    assert (tiles.shape, tiles.dtype) == ((1, 16, 16), jnp.int32)
    # the operands' relayouts beside the kernel: qI by columns, the keys twice
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05 * 2**30


def test_unequal_head_sizes_compile_for_v5e(one_chip):
    """The latent-attention layer of the ``kimilin_silo_doc8k`` cell under
    the model's vmap: 4 heads, q and k 192 wide, v 128 wide, bf16 at 8192
    tokens: two heads a column block, 384 lanes of q and k, 256 of v."""
    from fedml_tpu.ops.flash_attention import head_group

    L, H = 8192, 4
    assert head_group(H, 192, 128) == 2 and pick_block(L, 192) == 512

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=512, block_k=512)

    def grads(q, k, v, do):
        return jax.grad(lambda q, k, v: (
            jax.vmap(attn)(q, k, v).astype(jnp.float32)
            * do.astype(jnp.float32)).sum(), argnums=(0, 1, 2))(q, k, v)

    spec = lambda d: jax.ShapeDtypeStruct(  # noqa: E731
        (1, L, H, d), jnp.bfloat16, sharding=one_chip)
    args = (spec(192), spec(192), spec(128), spec(128))
    text = jax.jit(grads).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "flash_fwd" in text and "flash_bwd" in text
    dq, dk, dv = jax.eval_shape(grads, *args)
    assert (dq.shape, dk.shape, dv.shape) == (
        (1, L, H, 192), (1, L, H, 192), (1, L, H, 128))


def test_the_gated_delta_rules_kernels_compile_for_v5e(one_chip, monkeypatch):
    """The linear-attention layer's scan of the ``kimilin_silo_doc8k`` cell
    under the model's vmap: [1, 8192, 4, 128] in chunks of 64, ``v`` bf16,
    forward and backward: the pair sums' kernel and the recurrence's, each
    with its hand-written backward, four Mosaic kernels around XLA's
    triangular solve; no [n, H, nb, 16, 16, d] block of differences and no
    ``while`` over the chunks is left."""
    import re

    from fedml_tpu.ops import linear_attention as la

    L, H, d, chunk = 8192, 4, 128, 64
    assert la.kernels_tile(d, d, chunk)
    # the path asks the backend whether to interpret its kernels; the compile
    # is for the described chip whatever this process runs on
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def grads(q, k, v, g, beta, do):
        return jax.grad(lambda *a: (
            jax.vmap(lambda *t: la.gated_delta_rule(*t, chunk=chunk))(*a)
            .astype(jnp.float32) * do.astype(jnp.float32)).sum(),
            argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    spec = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    wide, half = spec((1, L, H, d)), spec((1, L, H, d), jnp.bfloat16)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(grads).lower(
            wide, wide, half, wide, spec((1, L, H)), half).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    for name in ("kda_pairs_fwd", "kda_pairs_bwd", "kda_scan_fwd",
                 "kda_scan_bwd"):
        assert sum("tpu_custom_call" in line and name in line
                   for line in text.splitlines()) == 1, name
    assert " while(" not in text
    blocks = re.findall(rf"\w+\[(?:\d+,)*{la.SUB},{la.SUB},{d}\]", text)
    assert not blocks, sorted(set(blocks))
    dq, dk, dv, dg, dbeta = jax.eval_shape(grads, wide, wide, half, wide,
                                           spec((1, L, H)), half)
    assert (dq.shape, dv.dtype, dbeta.shape) == (
        (1, L, H, d), jnp.bfloat16, (1, L, H))
    # what the scan keeps for its backward and works in: the chunks' entry
    # states, 32 MiB, and the terms between the kernels
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2 * 2**30


def test_the_expert_layers_grouped_products_compile_for_v5e(one_chip):
    """``megablox.gmm`` at the decoder cell's expert shapes and the tiling
    ``gmm_tiling`` picks, forward and both gradients, with group sizes
    that are no constants.  The library kernel's dots take the config's
    precision, and Mosaic refuses "highest" (conftest's) on bf16 operands:
    compiled under the default, which is what a chip run has."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from fedml_tpu.models.decoder import gmm_tiling

    m, h, f, held = 65536, 2304, 896, 8
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    for k, n in ((h, f), (f, h)):
        tiling = gmm_tiling(m, k, n)
        assert tiling and tiling[0] == 512

        def loss(x, w, sizes):
            return gmm(x, w, sizes, jnp.bfloat16, tiling).astype(
                jnp.float32).sum()

        with jax.default_matmul_precision("default"):
            text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
                spec((m, k)), spec((held, k, n)), spec((held,), jnp.int32)
            ).compile().as_text()
        assert text.count("tpu_custom_call") >= 2


# (experts routed, rows of the short buffer, the scratch the parent of PR 36
# compiled to at that shape in GiB): the two decoder cells' expert layers
EXPERT_LAYERS = [
    pytest.param(64, 16384, 0.515, id="mellum2_silo_code8k"),
    pytest.param(256, 4096, 0.408, id="kimilin_silo_doc8k"),
]


@pytest.mark.parametrize("routed, short, parent_gib", EXPERT_LAYERS)
def test_the_expert_layer_with_its_short_buffer_compiles_for_v5e(
        one_chip, monkeypatch, routed, short, parent_gib):
    """One ``ExpertLayer`` at a decoder cell's shapes (8192 tokens, top 8, 8
    experts held of ``routed``), forward and backward: the branch on the
    count of routed rows is two conditionals; the nine grouped-product kernels
    stand in the short buffer's branch only (the other runs plain matmuls),
    beside two calls of the ``from_buffer`` kernel (combine forward, dispatch
    backward) and not a third in the backward branch, where combine's forward
    is dead; no array of the 65,536 (token, slot) pairs by the hidden size
    exists anywhere; and the program's scratch stays under the parent's."""
    import re

    from fedml_tpu.models.decoder import ExpertLayer, buffer_capacities

    T, h, f, held, k = 8192, 2304, 896, 8, 8
    assert buffer_capacities(T, k, held, routed) == (short, T * k)
    # the layer asks the backend for its kernels; the compile is for the
    # described chip whatever this process runs on
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layer = ExpertLayer(routed, tuple(range(held)), k, f)
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    params = {"router": spec((h, routed), jnp.float32),
              "gate": spec((held, h, f)), "up": spec((held, h, f)),
              "down": spec((held, f, h))}

    def loss(p, x, dy):
        y, counters = layer.apply({"params": p}, x)
        return (y.astype(jnp.float32) * dy).sum() + sum(counters.values())

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            params, spec((1, T, h), jnp.float32), spec((1, T, h), jnp.float32)
        ).compile()
    text = compiled.as_text()
    assert text.count(" conditional(") == 2
    assert text.count('custom_call_target="tpu_custom_call"') == 9 + 2
    assert sum("tpu_custom_call" in line and "from_buffer" in line
               for line in text.splitlines()) == 2
    pairs = re.findall(rf"\w+\[(?:{T * k},{h}|{T},{k},{h})\]", text)
    assert not pairs, sorted(set(pairs))
    assert compiled.memory_analysis().temp_size_in_bytes < parent_gib * 2**30


def test_folded_round_peak_is_below_the_stacked_rounds_by_two_models(one_chip):
    """K = 4 clients on one chip: the fused round whose client loop carries
    the weighted sum never holds the fp32 ``[K, ...]`` stack of trained
    models that an (identity) ``aggregate_transform`` keeps.  The compiler's
    own peak must show it, by two parameter trees at least (at the
    benchmark cell's sizes it showed three)."""
    from fedml_tpu.algorithms.fedavg import (
        ServerState, make_multi_round_fn,
    )
    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.models.transformer import transformer_lm

    k, steps, batch, seq = 4, 2, 2, 128
    bundle = transformer_lm(vocab_size=4096, embed_dim=256, num_heads=4,
                            num_layers=2, seq_len=seq)
    lu = make_local_update(bundle, make_client_optimizer("sgd", 3e-4),
                           epochs=1, compute_dtype=jnp.bfloat16)
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    key = jax.random.PRNGKey(0)
    state = shaped(jax.eval_shape(
        lambda: ServerState(bundle.init(key), (), jnp.zeros((), jnp.int32),
                            key)))
    tree_bytes = sum(4 * l.size
                     for l in jax.tree_util.tree_leaves(state.variables))
    spec = jax.ShapeDtypeStruct
    block = shaped((
        spec((k, steps, batch, seq), jnp.int32),
        spec((k, steps, batch, seq), jnp.int32),
        spec((k, steps, batch), jnp.float32),
        spec((k,), jnp.float32), spec((k,), jnp.float32),
        spec((k,), jnp.int32),
    ))

    def peak(**round_kw):
        fn = jax.jit(make_multi_round_fn(lu, 1, **round_kw))
        return fn.lower(state, *block).compile(
        ).memory_analysis().peak_memory_in_bytes

    stacked = peak(aggregate_transform=lambda old, stack, w, rngs: stack)
    folded = peak()
    assert stacked - folded >= 2 * tree_bytes, (stacked, folded, tree_bytes)
