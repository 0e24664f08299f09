"""Partition-rule sharding engine (PR 19): rule-table semantics, the
host-mesh digest matrix, and the per-shard wire-byte pins.

The unit tests exercise the matcher/validator on host trees (no
devices needed).  The parity matrix spawns SUBPROCESSES via
``tools/fed_shard_run.py``'s child modes because
``--xla_force_host_platform_device_count`` must be set before jax
initializes: each cell runs the same synthetic federation on a dp-wide
host mesh and the final-model sha256 must be byte-identical to the
plain single-device engine — fp32 AND int8+EF (rows-per-device >= 2 by
construction: 16 clients over dp <= 8).  mp stays 1 in the digest
cells; mp > 1 splits the matmul contraction dim, which reassociates
fp32 reductions by construction and is covered by the evidence file's
allclose cell instead.

The marked-slow test runs the REAL muxed federation on a host mesh
(``distributed_fedavg.launch --mesh 4,1``) against the per-process
baseline — upload digests and the final model byte-identical.
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from fedml_tpu.parallel.mesh import parse_mesh_spec
from fedml_tpu.parallel.partition import (
    FEDLLM_RULES,
    RESNET_RULES,
    RuleTable,
    UNMATCHED_RAISE,
    match_partition_rules,
    resolve_rules,
    rule_coverage,
    validate_divisibility,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "fed_shard_run.py")


# --- rule-table semantics ----------------------------------------------------

def _tree():
    return {
        "params": {
            "Dense_0": {"kernel": np.zeros((4, 8), np.float32),
                        "bias": np.zeros((8,), np.float32)},
            "LayerNorm_0": {"scale": np.zeros((8,), np.float32)},
            "step": np.zeros((), np.int32),
        }
    }


def test_first_match_wins_ordering():
    from jax.sharding import PartitionSpec as P

    # both patterns match Dense_0/kernel; the FIRST rule must claim it
    table = RuleTable("t", ((r"Dense_0/kernel", ("mp", None)),
                            (r"kernel", (None, "mp"))))
    specs = match_partition_rules(table, _tree())
    assert specs["params"]["Dense_0"]["kernel"] == P("mp", None)
    # reversed order: the generic rule now wins
    rev = RuleTable("t2", ((r"kernel", (None, "mp")),
                           (r"Dense_0/kernel", ("mp", None))))
    specs = match_partition_rules(rev, _tree())
    assert specs["params"]["Dense_0"]["kernel"] == P(None, "mp")


def test_unmatched_policy_replicate_vs_raise():
    from jax.sharding import PartitionSpec as P

    table = RuleTable("t", ((r"kernel", (None, "mp")),))
    specs = match_partition_rules(table, _tree())
    assert specs["params"]["LayerNorm_0"]["scale"] == P()  # replicated
    strict = RuleTable("t", ((r"kernel", (None, "mp")),),
                       unmatched=UNMATCHED_RAISE)
    with pytest.raises(ValueError,
                       match=r"no rule matches leaf 'params/Dense_0/bias'"):
        match_partition_rules(strict, _tree())


def test_scalars_always_replicate_even_under_raise():
    from jax.sharding import PartitionSpec as P

    # the scalar leaf matches no rule, yet _unmatched=raise must not
    # fire: ndim-0 leaves replicate unconditionally
    strict = RuleTable("t", ((r".", (None,)),), unmatched=UNMATCHED_RAISE)
    specs = match_partition_rules(strict, {"step": np.zeros((), np.int32)})
    assert specs["step"] == P()


def test_overlong_spec_is_a_table_bug():
    table = RuleTable("t", ((r"bias", (None, "mp")),))  # 2-dim spec, 1-dim leaf
    with pytest.raises(ValueError, match="2-dim spec"):
        match_partition_rules(table, _tree())


def test_validate_divisibility_names_leaf_dim_axis():
    table = RuleTable("t", ((r"Dense_0/kernel", (None, "mp")),))
    tree = _tree()
    specs = match_partition_rules(table, tree)
    # 8 % 3 != 0 — silent GSPMD padding would hide a wrong rule
    with pytest.raises(ValueError, match=r"Dense_0/kernel.*dim 1"):
        validate_divisibility(tree, specs, {"dp": 1, "mp": 3})
    with pytest.raises(ValueError, match="mesh has"):
        validate_divisibility(tree, specs, {"dp": 1})
    validate_divisibility(tree, specs, {"dp": 1, "mp": 2})  # clean


def test_resolve_rules_canonical_json_and_errors(tmp_path):
    assert resolve_rules("fedllm") is FEDLLM_RULES
    assert resolve_rules("resnet") is RESNET_RULES
    doc = {"_unmatched": "raise",
           "rules": [["Dense_\\d+/kernel", [None, "mp"]]]}
    p = tmp_path / "custom.json"
    p.write_text(json.dumps(doc))
    table = resolve_rules(str(p))
    assert table.unmatched == UNMATCHED_RAISE
    assert table.rules == (("Dense_\\d+/kernel", (None, "mp")),)
    with pytest.raises(ValueError, match="unknown rule table"):
        resolve_rules("no_such_table")
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"_unmatched": "explode", "rules": []}))
    with pytest.raises(ValueError, match="_unmatched"):
        resolve_rules(str(bad))
    badre = tmp_path / "badre.json"
    badre.write_text(json.dumps({"rules": [["([unclosed", [None]]]}))
    with pytest.raises(Exception):  # re.error at load, not first match
        resolve_rules(str(badre))


def test_fedllm_table_covers_the_transformer():
    import jax

    from fedml_tpu.models.transformer import transformer_lm

    bundle = transformer_lm(vocab_size=64, embed_dim=32, num_heads=2,
                            num_layers=2, seq_len=16)
    variables = bundle.init(jax.random.PRNGKey(0))
    cov = rule_coverage(FEDLLM_RULES, variables)
    assert cov["unmatched_paths"] == []
    assert all(r["leaves"] > 0 for r in cov["rules"]), cov["rules"]
    assert cov["leaves_sharded"] > 0
    # strict form must also pass: every leaf is claimed by some rule
    strict = FEDLLM_RULES._replace(unmatched=UNMATCHED_RAISE)
    match_partition_rules(strict, variables)


def test_parse_mesh_spec_forms():
    assert parse_mesh_spec("8,1") == (8, 1)
    assert parse_mesh_spec("dp=2,mp=4") == (2, 4)
    assert parse_mesh_spec("mp=4,dp=2") == (2, 4)  # order-free
    assert parse_mesh_spec("auto,2", device_count=8) == (4, 2)
    assert parse_mesh_spec("-1,2", device_count=8) == (4, 2)
    for bad in ("2", "0,2", "a,b", "auto,auto", "dp=2,dp=2",
                "auto,3"):  # 3 does not divide the 8 below
        with pytest.raises(ValueError):
            parse_mesh_spec(bad, device_count=8)


# --- host-mesh digest matrix (subprocess cells) ------------------------------

def _spawn_child(child, devices, **kw):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}"
        if devices > 1 else ""
    )
    cmd = [sys.executable, _TOOL, "--child", child]
    for k, v in kw.items():
        cmd += [f"--{k.replace('_', '-')}", str(v)]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=600, cwd=_REPO)
    assert out.returncode == 0, (
        f"child {child} {kw} rc={out.returncode}:\n"
        f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}"
    )
    return json.loads(out.stdout.splitlines()[-1])


@pytest.mark.parametrize("codec,ef", [("", 0), ("int8", 1)],
                         ids=["fp32", "int8_ef"])
def test_host_mesh_digest_matrix_sharded_equals_replicated(codec, ef):
    """THE tentpole pin: same seed, same rules — the dp-sharded round
    engine's final model is byte-identical to the plain single-device
    engine at dp 1, 2 and 8 (16 clients: >= 2 rows per device)."""
    cells = [_spawn_child("pin", devices=1, engine="plain", dp=1, mp=1,
                          codec=codec, ef=ef, clients=16, rounds=2)]
    for dp in (1, 2, 8):
        cells.append(_spawn_child("pin", devices=dp, engine="rules",
                                  dp=dp, mp=1, codec=codec, ef=ef,
                                  clients=16, rounds=2))
    digests = {c["digest"] for c in cells}
    assert len(digests) == 1, (
        f"digest split across cells: "
        f"{[(c['engine'], c['dp'], c['digest'][:12]) for c in cells]}"
    )
    assert all(c["nan_free"] for c in cells)
    # the sharded cells really ran on that many host devices
    assert [c["devices"] for c in cells[1:]] == [1, 2, 8]


def test_per_shard_wire_bytes_identical_to_single_device_encode():
    """Per-shard QSGD encode on a dp2 x mp2 mesh: every shard's packed
    wire buffers byte-identical to a single-device encode of that
    shard's slice under the same fold_in stream, each element visited
    exactly once (no gather, no overlap)."""
    for codec in ("int8", "int4"):
        cell = _spawn_child("bytes", devices=4, codec=codec, dp=2, mp=2)
        assert cell["per_shard_bytes_identical"], cell
        assert cell["element_accounting_exact"], cell
        assert cell["decode_finite"], cell
        assert cell["multi_shard_leaves"] > 0, (
            "mesh produced no actually-split leaves — the pin would be "
            f"vacuous: {cell}"
        )


# --- muxed federation on a host mesh (the full topology) ---------------------

@pytest.mark.slow
def test_muxed_host_mesh_federation_byte_identical_to_per_process(tmp_path):
    from fedml_tpu.experiments.distributed_fedavg import launch

    def env(devices):
        e = dict(os.environ)
        e["JAX_PLATFORMS"] = "cpu"
        e["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}"
            if devices > 1 else ""
        )
        return e

    runs = {}
    for tag, kw, devices in (
        ("proc", dict(muxers=0), 1),
        ("mux_mesh", dict(muxers=1, muxed_clients=8, mesh="4,1"), 4),
    ):
        out = str(tmp_path / f"{tag}.npz")
        info = {}
        rc = launch(num_clients=8, rounds=2, seed=0, batch_size=16,
                    out_path=out, env=env(devices),
                    info=info, timeout=300.0, **kw)
        assert rc == 0, tag
        z = np.load(out)
        runs[tag] = (
            {k: v for k, v in sorted(info.items())
             if k.endswith("_upload_digest")},
            [np.asarray(z[k]) for k in sorted(z.files)
             if k.startswith("leaf_")],
        )
    d_proc, leaves_proc = runs["proc"]
    d_mux, leaves_mux = runs["mux_mesh"]
    assert len(d_proc) == 8 and d_proc == d_mux
    assert all(np.array_equal(a, b)
               for a, b in zip(leaves_proc, leaves_mux))
