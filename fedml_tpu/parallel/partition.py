"""Partition-rule sharding engine: ordered ``(regex → PartitionSpec)``
tables matched against param-tree path names.

This module is the one place that knows which leaf of a model goes on
which mesh axis, and it knows it as data: a rule table is
an ordered list of ``(pattern, spec)`` pairs; each leaf's '/'-joined
path (``params/Block_0/Dense_1/kernel``) is matched with ``re.search``
and the FIRST matching rule wins — the fmengine/EasyLM lineage of
GSPMD sharding, where the layout of a whole model family fits in a
dozen visible lines instead of a tree of if/elifs.  Scalars (ndim 0)
are always replicated; an explicit ``_unmatched`` policy decides
whether unmatched leaves replicate or raise.

Canonical tables ship for the two model families the bench drives:
``fedllm`` (the ``models/transformer.py`` LM: vocab-sharded embedding,
column/row attention and MLP projections, replicated LayerNorms) and
``resnet`` (output-channel-sharded convs).  Custom tables load from
JSON (``resolve_rules``).

On top of the matcher sit the appliers: ``shard_by_rules`` lays a
pytree out on a ``(dp, mp)`` mesh (``parallel/mesh.py``);
``server_state_sharding`` extends the plan to the full
``ServerState`` — optimizer moments via the shape-matching
``opt_state_sharding_like`` and the EF residual store with its
leading client axis on ``dp``; ``make_rule_round_fn``, the only round
that shards a model by GSPMD, jits the FedAvg
round with the packed client block over ``dp`` and the model over
``mp``; ``cohort_shardings`` produces the sharding tuple the muxed
cohort engine (``algorithms/fedavg_mux.py``) feeds to
``jit_sharded`` so thousands of virtual clients and a tensor-sharded
model run in ONE jit step.
"""

from __future__ import annotations

import json
import re
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from fedml_tpu.parallel.mesh import DP_AXIS, MP_AXIS

PyTree = Any

UNMATCHED_REPLICATE = "replicate"
UNMATCHED_RAISE = "raise"


class RuleTable(NamedTuple):
    """An ordered partition-rule table.

    ``rules`` are ``(pattern, spec_dims)`` pairs where ``spec_dims`` is
    the PartitionSpec as a plain tuple (``(None, "mp")``) so the table
    is importable without jax; ``unmatched`` is ``"replicate"`` or
    ``"raise"``.
    """

    name: str
    rules: Tuple[Tuple[str, Tuple], ...]
    unmatched: str = UNMATCHED_REPLICATE


# fedllm transformer (models/transformer.py): paths look like
#   params/wte/embedding                                  [V, E]
#   params/wpe/embedding                                  [S, E]
#   params/Block_i/MultiHeadAttention_0/Dense_0/kernel    [E, 3E] qkv
#   params/Block_i/MultiHeadAttention_0/Dense_1/kernel    [E, E]  out
#   params/Block_i/Dense_0/{kernel,bias}                  [E, 4E] mlp up
#   params/Block_i/Dense_1/kernel                         [4E, E] mlp down
#   params/Block_i/LayerNorm_{0,1}/{scale,bias}
#   params/ln_f/{scale,bias}                              final norm
# Megatron plan: qkv/up column-parallel, out/down row-parallel (GSPMD
# inserts the psum), embedding vocab-sharded (weight tying makes the
# logits matmul row-parallel for free), norms replicated.
FEDLLM_RULES = RuleTable(
    name="fedllm",
    rules=(
        (r"wte/embedding", (MP_AXIS, None)),
        (r"wpe/embedding", (None, None)),
        (r"MultiHeadAttention_\d+/Dense_0/kernel", (None, MP_AXIS)),
        (r"MultiHeadAttention_\d+/Dense_1/kernel", (MP_AXIS, None)),
        (r"Block_\d+/Dense_0/kernel", (None, MP_AXIS)),
        (r"Block_\d+/Dense_0/bias", (MP_AXIS,)),
        (r"Block_\d+/Dense_1/kernel", (MP_AXIS, None)),
        # row-parallel down projection: bias adds AFTER the psum, so it
        # replicates
        (r"Block_\d+/Dense_1/bias", ()),
        (r"LayerNorm_\d+|ln_f", ()),
    ),
    unmatched=UNMATCHED_REPLICATE,
)

# CIFAR ResNets (models/resnet.py): output-channel-sharded convs and
# classifier, BatchNorm params/stats replicated (they're per-channel
# vectors small enough that sharding buys nothing and complicates the
# running-stats update).
RESNET_RULES = RuleTable(
    name="resnet",
    rules=(
        (r"Conv_\d+/kernel", (None, None, None, MP_AXIS)),
        (r"Dense_\d+/kernel", (None, MP_AXIS)),
        (r"Dense_\d+/bias", (MP_AXIS,)),
        (r"BatchNorm_\d+|batch_stats", ()),
    ),
    unmatched=UNMATCHED_REPLICATE,
)

_NAMED_TABLES = {t.name: t for t in (FEDLLM_RULES, RESNET_RULES)}


def resolve_rules(name_or_path: str) -> RuleTable:
    """A canonical table by name (``fedllm``, ``resnet``) or a custom
    one from a JSON file::

        {"_unmatched": "raise",
         "rules": [["Dense_\\\\d+/kernel", [null, "mp"]], ...]}
    """
    if name_or_path in _NAMED_TABLES:
        return _NAMED_TABLES[name_or_path]
    try:
        with open(name_or_path) as f:
            doc = json.load(f)
    except OSError:
        raise ValueError(
            f"unknown rule table {name_or_path!r}: not a canonical name "
            f"({sorted(_NAMED_TABLES)}) and not a readable JSON file"
        ) from None
    unmatched = doc.get("_unmatched", UNMATCHED_REPLICATE)
    if unmatched not in (UNMATCHED_REPLICATE, UNMATCHED_RAISE):
        raise ValueError(
            f"rule file {name_or_path}: _unmatched must be "
            f"'{UNMATCHED_REPLICATE}' or '{UNMATCHED_RAISE}', "
            f"got {unmatched!r}"
        )
    rules = []
    for entry in doc.get("rules", ()):
        pattern, dims = entry
        re.compile(pattern)  # fail loud at load, not first match
        rules.append((str(pattern), tuple(dims)))
    return RuleTable(name=name_or_path, rules=tuple(rules),
                     unmatched=unmatched)


def _leaf_path(path) -> str:
    """'/'-joined names of a ``tree_util`` key path: a dict key, a
    sequence index, anything else as it prints."""
    return "/".join(
        str(getattr(k, "key", getattr(k, "idx", k))) for k in path
    )


def _spec_of(dims: Sequence):
    from jax.sharding import PartitionSpec as P

    return P(*dims)


def match_partition_rules(table: RuleTable, tree: PyTree) -> PyTree:
    """PartitionSpec tree for ``tree`` under ``table``: first
    ``re.search`` match on the '/'-joined path wins; ndim-0 leaves are
    always replicated; a matched spec with more dims than the leaf has
    is a table bug and raises; unmatched leaves follow
    ``table.unmatched``."""
    import jax
    from jax.sharding import PartitionSpec as P

    compiled = [(re.compile(p), dims) for p, dims in table.rules]

    def spec_for(path, leaf):
        name = _leaf_path(path)
        ndim = np.ndim(leaf)
        if ndim == 0:
            return P()
        for pat, dims in compiled:
            if pat.search(name):
                if len(dims) > ndim:
                    raise ValueError(
                        f"rule table {table.name!r}: pattern "
                        f"{pat.pattern!r} gives {len(dims)}-dim spec "
                        f"{tuple(dims)} for {ndim}-dim leaf {name!r}"
                    )
                return _spec_of(dims)
        if table.unmatched == UNMATCHED_RAISE:
            raise ValueError(
                f"rule table {table.name!r}: no rule matches leaf "
                f"{name!r} and _unmatched=raise"
            )
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, tree)


def rule_coverage(table: RuleTable, tree: PyTree) -> Dict[str, Any]:
    """Per-rule match accounting for the evidence file: how many leaves
    (and parameters) each rule claimed, which paths fell through, and
    the sharded/replicated split."""
    import jax

    compiled = [(re.compile(p), dims) for p, dims in table.rules]
    per_rule = [
        {"pattern": p, "spec": list(dims), "leaves": 0, "params": 0,
         "example": None}
        for p, dims in table.rules
    ]
    unmatched: List[str] = []
    sharded = replicated = 0
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    for path, leaf in leaves:
        name = _leaf_path(path)
        size = int(np.prod(np.shape(leaf), dtype=np.int64))
        if np.ndim(leaf) == 0:
            replicated += 1
            continue
        for i, (pat, dims) in enumerate(compiled):
            if pat.search(name):
                per_rule[i]["leaves"] += 1
                per_rule[i]["params"] += size
                if per_rule[i]["example"] is None:
                    per_rule[i]["example"] = name
                if any(d is not None for d in dims):
                    sharded += 1
                else:
                    replicated += 1
                break
        else:
            unmatched.append(name)
            replicated += 1
    return {
        "table": table.name,
        "unmatched_policy": table.unmatched,
        "rules": per_rule,
        "unmatched_paths": unmatched,
        "leaves_total": len(leaves),
        "leaves_sharded": sharded,
        "leaves_replicated": replicated,
    }


def validate_divisibility(tree: PyTree, specs: PyTree,
                          axis_sizes: Dict[str, int]) -> None:
    """Every sharded dim must divide evenly by the product of its mesh
    axes — GSPMD would silently pad instead, which wastes chips and
    (worse) hides a wrong rule.  Raises naming the leaf, dim and axis."""
    import jax
    from jax.sharding import PartitionSpec as P

    leaves = jax.tree_util.tree_leaves_with_path(tree)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    for (path, leaf), spec in zip(leaves, spec_leaves):
        shape = np.shape(leaf)
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            factor = 1
            for ax in axes:
                if ax not in axis_sizes:
                    raise ValueError(
                        f"leaf {_leaf_path(path)!r}: spec names mesh "
                        f"axis {ax!r}, mesh has {sorted(axis_sizes)}"
                    )
                factor *= int(axis_sizes[ax])
            if shape[dim] % factor:
                raise ValueError(
                    f"leaf {_leaf_path(path)!r}: dim {dim} of shape "
                    f"{tuple(shape)} not divisible by mesh axes "
                    f"{axes} (size {factor})"
                )


def named_sharding_tree(mesh, specs: PyTree) -> PyTree:
    """PartitionSpec tree → NamedSharding tree on ``mesh``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_by_rules(mesh, tree: PyTree, table: RuleTable) -> Tuple[PyTree, PyTree]:
    """Lay ``tree`` out on ``mesh`` under ``table``: validate
    divisibility, then ``device_put`` each leaf with its
    ``NamedSharding``.  Returns ``(sharded_tree, specs)``."""
    import jax

    specs = match_partition_rules(table, tree)
    validate_divisibility(tree, specs,
                          {k: int(v) for k, v in mesh.shape.items()})
    shardings = named_sharding_tree(mesh, specs)
    return jax.device_put(tree, shardings), specs


def jit_sharded(fn, *, in_shardings=None, out_shardings=None, **jit_kwargs):
    """The partition-rule engine's jit entry point: ``jax.jit`` with
    sharding annotations.  Exists as a named wrapper so fedlint's
    jit-purity root scan covers every function compiled through the
    sharding subsystem (``analysis/jit_purity.py`` lists it in
    ``JIT_TRANSFORMS``)."""
    import jax

    if in_shardings is not None:
        jit_kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        jit_kwargs["out_shardings"] = out_shardings
    return jax.jit(fn, **jit_kwargs)


# --- ServerState / round-engine integration ---------------------------------

def opt_state_sharding_like(mesh, variables_template: PyTree,
                            opt_state_template: PyTree,
                            pspec: PyTree) -> PyTree:
    """Sharding tree for server-optimizer state whose leaves mirror the
    parameters (FedAdam/FedYogi moments): each opt leaf with the shape
    of some param leaf inherits that param's spec from ``pspec`` (the
    rule-derived spec tree of ``variables_template``); everything else
    (counts, scalars) is replicated.  Shape-based matching is a
    heuristic — two same-shaped params with different specs resolve to
    whichever appears first, which only changes layout, not values."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    shape_to_spec = {}
    for leaf, spec in zip(
        jax.tree_util.tree_leaves(variables_template),
        jax.tree_util.tree_leaves(pspec, is_leaf=lambda x: isinstance(x, P)),
    ):
        shape_to_spec.setdefault(np.shape(leaf), spec)
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, shape_to_spec.get(np.shape(l), P())),
        opt_state_template,
    )


def server_state_sharding(mesh, variables_template: PyTree,
                          table: RuleTable, *,
                          opt_state_template: Optional[PyTree] = None,
                          error_feedback: bool = False):
    """ServerState-shaped tree of shardings under ``table``: variables
    by rules, optimizer moments via the shape-matching
    ``opt_state_sharding_like`` reusing the SAME rule-derived
    specs, EF residuals (leading ``[num_clients, ...]`` axis) with the
    client axis on ``dp`` and the param dims inheriting the param's
    spec.  Scalars (round_idx, key) replicate."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fedml_tpu.algorithms.fedavg import ServerState

    specs = match_partition_rules(table, variables_template)
    var_sharding = named_sharding_tree(mesh, specs)
    repl = NamedSharding(mesh, P())
    if opt_state_template is not None:
        opt_sharding = opt_state_sharding_like(
            mesh, variables_template, opt_state_template, specs
        )
    else:
        opt_sharding = repl
    if error_feedback:
        import jax

        residual_sharding = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, P(DP_AXIS, *s)), specs,
            is_leaf=lambda x: isinstance(x, P),
        )
    else:
        residual_sharding = ()
    return ServerState(
        variables=var_sharding,
        opt_state=opt_sharding,
        round_idx=repl,
        key=repl,
        residuals=residual_sharding,
    ), specs


def make_rule_round_fn(
    mesh,
    local_update,
    variables_template: PyTree,
    table: RuleTable = FEDLLM_RULES,
    *,
    server_update=None,
    aggregate_transform=None,
    opt_state_template: Optional[PyTree] = None,
    codec=None,
    error_feedback: bool = False,
):
    """jit the FedAvg round on a ``(dp, mp)`` mesh with the packed
    client block over ``dp`` and the model laid out by ``table``: the
    one round that shards a model by GSPMD.

    The round function is ``make_round_fn(client_axis_impl="vmap")``
    with no axis_name: the partitioner derives every collective —
    client-parallel local scans, tensor-sharded matmuls inside each
    client's forward and backward — from the annotations alone.  The
    layout comes from the table, and the in-engine compression path
    (``codec`` name or LeafCodec, plus ``error_feedback``) keeps its
    residual store sharded — client rows on ``dp``, param dims like
    the params.  When ``server_update`` carries parameter-sized
    optimizer state (FedAdam moments), pass ``opt_state_template``:
    the moments then follow the params' layout; without it
    ``opt_state`` is replicated.

    The dp-sharded round is BIT-identical to the single-device one in
    fp32: the per-client heavy compute stays sharded, but the
    cross-client weighted sum is an ordered scan over the gathered
    update stack (``exact_agg`` below) and the tiny ``[K]`` weight
    vectors stay replicated throughout.  Left to the GSPMD
    partitioner, an einsum over K may partial-sum the axis per device
    — reassociating the fp32 reduction and breaking the sha256 parity
    pins (a with_sharding_constraint on the operand is NOT enough;
    the partitioner may still split the reduction).  Costs an
    all-gather of the update stack per round.

    Returns ``(round_fn, shard_state, shard_data)``:
    ``shard_state(state)`` lays server state out on the mesh;
    ``shard_data(arrays)`` places the packed client block.  The state
    ``round_fn`` returns keeps the same shardings (donated input).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fedml_tpu.algorithms.fedavg import make_round_fn
    from fedml_tpu.compress import get_codec

    if isinstance(codec, str):
        codec = get_codec(codec)

    repl = NamedSharding(mesh, P())

    def exact_agg(w, cv):
        # sequential scan over the K axis, NOT einsum: a reduction's
        # accumulation strategy (lane splits, partial sums per
        # device, horizontal adds) is a partitioner/fusion decision,
        # so the "same" einsum can reassociate between the 1-device
        # and SPMD lowerings (measured on CPU host meshes).  The
        # scan carry chain is explicitly ordered, its xs interface
        # MATERIALIZES the weighted update stack (a while-loop
        # operand is a real buffer — fusions cannot duplicate the
        # decode chain past it with different contraction choices,
        # another measured 1-ulp source), and a sequential loop is
        # not partitionable, so GSPMD all-gathers the stack and
        # every device runs the identical full-K reduction.  A
        # shard_map(P() -> P()) wrapper is NOT equivalent: its
        # boundary changes the producer fusions and was measured to
        # break bit-parity where this form holds it.
        weighted = jax.tree_util.tree_map(
            lambda l: w.reshape((-1,) + (1,) * (l.ndim - 1))
            * l.astype(jnp.float32),
            cv,
        )

        def body(acc, row):
            return jax.tree_util.tree_map(jnp.add, acc, row), None

        zeros = jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape[1:], jnp.float32), cv
        )
        acc, _ = jax.lax.scan(body, zeros, weighted)
        return acc

    kwargs = {"aggregate_impl": exact_agg}
    if server_update is not None:
        kwargs["server_update"] = server_update
    if codec is not None:
        kwargs["codec"] = codec
        kwargs["error_feedback"] = error_feedback
    inner = make_round_fn(
        local_update,
        aggregate_transform=aggregate_transform,
        client_axis_impl="vmap",
        **kwargs,
    )

    state_sharding, specs = server_state_sharding(
        mesh, variables_template, table,
        opt_state_template=opt_state_template,
        error_feedback=codec is not None and error_feedback,
    )
    validate_divisibility(variables_template, specs,
                          {k: int(v) for k, v in mesh.shape.items()})
    data_sharding = NamedSharding(mesh, P(DP_AXIS))
    # (x, y, mask) carry the client compute and shard over dp; the [K]
    # scalar vectors (num_samples, participation, slot_ids) stay
    # replicated so weight products and their sums keep single-device
    # reduction order
    arg_shardings = (data_sharding, data_sharding, data_sharding,
                     repl, repl, repl)

    def shard_state(state):
        return jax.device_put(state, state_sharding)

    def shard_data(arrays):
        return tuple(jax.device_put(np.asarray(a), s)
                     for a, s in zip(arrays, arg_shardings))

    round_fn = jit_sharded(
        inner,
        in_shardings=(state_sharding,) + arg_shardings,
        out_shardings=(state_sharding, repl),
        donate_argnums=(0,),
    )
    return round_fn, shard_state, shard_data


def cohort_shardings(mesh, variables_template: PyTree, table: RuleTable):
    """Sharding tuple for the muxed cohort engine's ONE jit step:
    broadcast variables by rules over ``mp``, every per-client stacked
    array (data rows, rng keys, the vmapped output tree and its metric
    dict) with the cohort axis on ``dp``.

    Returns ``(var_in, data, var_out, stacked)`` where ``stacked`` is
    the plain ``P("dp")`` sharding usable as a pytree prefix for the
    metrics dict.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    specs = match_partition_rules(table, variables_template)
    validate_divisibility(variables_template, specs,
                          {k: int(v) for k, v in mesh.shape.items()})
    var_in = named_sharding_tree(mesh, specs)
    stacked = NamedSharding(mesh, P(DP_AXIS))
    import jax

    var_out = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, P(DP_AXIS, *s)), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return var_in, stacked, var_out, stacked
