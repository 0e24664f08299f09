"""L5 experiments layer: typed config CLI + the unified runner
(smoke tests in the reference's CI style — tiny end-to-end runs,
``CI-script-*.sh`` semantics, SURVEY.md §4.2)."""

import dataclasses
import json

import numpy as np
import pytest

from fedml_tpu.core.config import config_to_json, parse_config
from fedml_tpu.experiments.registry import create_model, load_data
from fedml_tpu.experiments.run import ExperimentConfig, run_experiment


def test_parse_config_overrides_and_serializes():
    cfg = parse_config(ExperimentConfig, [
        "--algorithm", "fedprox", "--lr", "0.5", "--mu", "0.01",
        "--comm_round", "3",
    ])
    assert cfg.algorithm == "fedprox" and cfg.lr == 0.5
    assert cfg.mu == 0.01 and cfg.comm_round == 3
    rec = json.loads(config_to_json(cfg))
    assert rec["mu"] == 0.01


def test_registry_model_dataset_pairs():
    ds = load_data("synthetic", num_clients=3)
    b = create_model("lr", "mnist", 10)
    assert b.input_shape == (784,)
    b2 = create_model("rnn", "fed_shakespeare", 90)
    assert b2.input_dtype.__name__ == "int32"
    with pytest.raises(ValueError):
        create_model("nope", "mnist", 10)
    with pytest.raises(ValueError):
        load_data("nope")


def _ci_cfg(**kw):
    return dataclasses.replace(
        ExperimentConfig(dataset="synthetic", model="lr",
                         client_num_in_total=3, client_num_per_round=3,
                         comm_round=2, batch_size=8, epochs=1,
                         frequency_of_the_test=1, lr=0.1),
        **kw,
    )


@pytest.mark.parametrize("algo", ["fedavg", "fedprox", "fedopt", "fednova"])
def test_run_experiment_fedavg_family(algo):
    out = run_experiment(_ci_cfg(algorithm=algo), log_fn=None)
    assert np.isfinite(out["final"]["test_acc"])
    assert len(out["history"]) == 2


def test_run_experiment_centralized_and_decentralized():
    out = run_experiment(_ci_cfg(algorithm="centralized"), log_fn=None)
    assert "test_acc" in out["final"]
    out2 = run_experiment(_ci_cfg(algorithm="decentralized"), log_fn=None)
    assert "test_acc" in out2["final"]


def test_run_experiment_hierarchical_and_vfl():
    out = run_experiment(_ci_cfg(algorithm="hierarchical", group_num=2,
                                 group_comm_round=1), log_fn=None)
    assert np.isfinite(out["final"]["test_acc"])
    out2 = run_experiment(_ci_cfg(algorithm="vfl", comm_round=2,
                                  batch_size=64), log_fn=None)
    assert "auc" in out2["history"][-1] or "acc" in out2["history"][-1]


# ---------------------------------------------------------------------------
# The REAL benchmark matrix (reference benchmark/README.md tables): every
# (model, dataset) pair the reference publishes numbers for runs through
# run_experiment with the real loader + model + task loss — no ci task
# substitution (the r2 stackoverflow_lr crash survived two rounds behind
# the reference-style synthetic swap).  Sizes are cut via the shrink
# knobs only; dataset stand-ins keep every loader's real output contract.
# Conv-family pairs are compile-heavy on the 1-core CPU box and live in
# the slow tier; wiring-distinct light pairs gate every change.
# ---------------------------------------------------------------------------

BENCHMARK_PAIRS_LIGHT = [
    ("lr", "mnist"),             # Linear Models row 1
    ("lr", "femnist"),           # Linear Models row 2
    ("lr", "synthetic"),         # Linear Models row 3, Synthetic(α,β)
    ("lr", "stackoverflow_lr"),  # multi-label tag prediction (r2 crash)
    ("cnn", "femnist"),          # shallow-NN row 1
    ("rnn", "fed_shakespeare"),  # shallow-NN row 3 (seq output)
    ("rnn", "stackoverflow_nwp"),  # shallow-NN row 4
]

BENCHMARK_PAIRS_HEAVY = [
    ("rnn", "shakespeare"),          # LEAF variant (non-seq output)
    ("resnet18_gn", "fed_cifar100"),  # shallow-NN row 2
    ("resnet56", "cifar10"),         # cross-silo DNN rows
    ("resnet56", "cifar100"),
    ("resnet56", "cinic10"),
    ("mobilenet", "cifar10"),
    ("mobilenet", "cifar100"),
    ("mobilenet", "cinic10"),
]


def _matrix_cfg(model, dataset):
    return ExperimentConfig(
        algorithm="fedavg", model=model, dataset=dataset,
        client_num_in_total=3, client_num_per_round=2, comm_round=1,
        batch_size=4, epochs=1, lr=0.05, frequency_of_the_test=1,
        max_samples_per_client=8, max_test_samples=16, ci=0,
    )


def test_cifar_dispatcher_wires_reference_augmentation(monkeypatch):
    """fedavg+cifar-family through the dispatcher must construct the
    simulation WITH the reference's unconditional CIFAR augmentation
    (crop+flip+cutout for cifar10/100, no cutout for cinic10 — the
    published accuracies are unreachable without it), and must NOT
    augment non-image data or when --data_augmentation 0.  Spied at the
    constructor (no conv compile needed)."""
    from fedml_tpu.algorithms import fedavg as fa

    captured = {}

    class _Stop(Exception):
        pass

    real = fa.FedAvgSimulation

    class Spy(real):
        def __init__(self, bundle, ds, config, **kw):
            captured["augment_fn"] = kw.get("augment_fn")
            raise _Stop

    monkeypatch.setattr(fa, "FedAvgSimulation", Spy)

    def probe(**kw):
        cfg = dataclasses.replace(ExperimentConfig(
            algorithm="fedavg", model="resnet20", dataset="cifar10",
            client_num_in_total=2, client_num_per_round=2, comm_round=1,
            batch_size=8, max_samples_per_client=16, max_test_samples=16,
        ), **kw)
        captured.clear()
        with pytest.raises(_Stop):
            run_experiment(cfg, log_fn=None)
        return captured["augment_fn"]

    assert probe() is not None                        # cifar10: on
    assert probe(dataset="cifar100") is not None
    assert probe(dataset="cinic10") is not None
    assert probe(data_augmentation=0) is None         # ablation off
    assert probe(dataset="mnist", model="lr") is None  # non-cifar: off


@pytest.mark.parametrize("model,dataset", BENCHMARK_PAIRS_LIGHT)
def test_benchmark_matrix(model, dataset):
    out = run_experiment(_matrix_cfg(model, dataset), log_fn=None)
    final = out["final"]
    assert np.isfinite(final["test_acc"]) and np.isfinite(final["test_loss"])
    if dataset == "stackoverflow_lr":
        # reference tag-prediction metrics (my_model_trainer_tag_prediction.py)
        assert np.isfinite(final["test_precision"])
        assert np.isfinite(final["test_recall"])


@pytest.mark.slow  # conv compiles ~25-40s each on the 1-core CPU box
@pytest.mark.parametrize("model,dataset", BENCHMARK_PAIRS_HEAVY)
def test_benchmark_matrix_conv(model, dataset):
    out = run_experiment(_matrix_cfg(model, dataset), log_fn=None)
    final = out["final"]
    assert np.isfinite(final["test_acc"]) and np.isfinite(final["test_loss"])


def test_ci_never_swaps_the_task():
    """--ci 1 must shrink sizes, not substitute model/dataset (r2 Weak #1)."""
    from fedml_tpu.experiments.run import _apply_ci

    cfg = _apply_ci(ExperimentConfig(
        algorithm="fedavg", model="resnet56", dataset="cifar10", ci=1))
    assert cfg.model == "resnet56" and cfg.dataset == "cifar10"
    assert cfg.max_samples_per_client > 0 and cfg.max_test_samples > 0
    assert cfg.comm_round <= 2 and cfg.batch_size <= 8
    llm = _apply_ci(ExperimentConfig(
        algorithm="fedllm", dataset="stackoverflow_nwp", ci=1))
    assert llm.dataset == "stackoverflow_nwp"


def test_shrink_dataset_caps_shards():
    from fedml_tpu.experiments.registry import shrink_dataset

    ds = load_data("synthetic", num_clients=4)
    small = shrink_dataset(ds, max_samples_per_client=5, max_test_samples=7)
    assert all(len(v) <= 5 for v in small.train_client_idx.values())
    assert len(small.test_y) == 7
    assert small.num_classes == ds.num_classes
    # no-op path returns the dataset unchanged
    assert shrink_dataset(ds) is ds


def test_shrink_dataset_strided_test_slice_keeps_classes():
    """Folder-tree loaders emit CLASS-GROUPED test arrays; a [:N] prefix
    slice would collapse the smoke test set to one class (advisor r3).
    The strided selection must keep every class represented and remap
    test_client_idx to compacted positions pointing at the same rows."""
    import dataclasses

    from fedml_tpu.experiments.registry import shrink_dataset

    ds = load_data("synthetic", num_clients=4)
    order = np.argsort(ds.test_y, kind="stable")  # class-grouped layout
    grouped = dataclasses.replace(
        ds, test_x=ds.test_x[order], test_y=ds.test_y[order],
        test_client_idx={0: np.arange(len(ds.test_y))},
    )
    small = shrink_dataset(grouped, max_test_samples=30)
    assert len(small.test_y) == 30
    assert len(np.unique(small.test_y)) == ds.num_classes
    # the client owned every test row before the shrink, so its remapped
    # indices must cover exactly the 30 compacted positions
    kept = small.test_client_idx[0]
    assert sorted(int(i) for i in kept) == list(range(30))


def test_multilabel_bce_matches_reference_semantics():
    """masked_multilabel_bce vs torch BCELoss(sum) + the reference's
    exact-match/precision/recall math on random multi-hot labels."""
    import torch

    from fedml_tpu.core.losses import masked_multilabel_bce

    rng = np.random.RandomState(0)
    logits = rng.randn(6, 11).astype(np.float32)
    y = (rng.rand(6, 11) < 0.25).astype(np.float32)
    mask = np.array([1, 1, 1, 1, 1, 0], np.float32)

    loss, aux = masked_multilabel_bce(logits, y, mask)
    tl = torch.tensor(logits[:5])
    ty = torch.tensor(y[:5])
    ref_loss = torch.nn.BCELoss(reduction="sum")(torch.sigmoid(tl), ty)
    np.testing.assert_allclose(float(aux["loss_sum"]), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(float(loss), float(ref_loss) / 5.0, rtol=1e-5)

    pred = (torch.sigmoid(tl) > 0.5).int()
    correct = pred.eq(ty).sum(axis=-1).eq(ty.size(1)).sum()
    tp = ((ty * pred) > 0.1).int().sum(axis=-1)
    precision = tp / (pred.sum(axis=-1) + 1e-13)
    recall = tp / (ty.sum(axis=-1) + 1e-13)
    assert float(aux["correct"]) == float(correct)
    np.testing.assert_allclose(float(aux["precision_sum"]),
                               float(precision.sum()), rtol=1e-5)
    np.testing.assert_allclose(float(aux["recall_sum"]),
                               float(recall.sum()), rtol=1e-5)


def test_run_experiment_fedllm_and_dp_tp():
    from fedml_tpu.experiments.run import ExperimentConfig, run_experiment

    out = run_experiment(ExperimentConfig(
        algorithm="fedllm", dataset="fed_shakespeare", comm_round=2,
        client_num_in_total=4, client_num_per_round=4, batch_size=4,
        embed_dim=32, num_heads=4, num_layers=1, lr=0.1, ci=0,
    ), log_fn=None)
    assert len(out["history"]) == 2
    # DP x TP path: 4-way DP x 2-way TP over the faked 8-device mesh
    # (the fedllm table shards the vocabulary over mp: 90 % 2 == 0)
    out2 = run_experiment(ExperimentConfig(
        algorithm="fedllm", dataset="fed_shakespeare", comm_round=2,
        client_num_in_total=4, client_num_per_round=4, batch_size=4,
        embed_dim=32, num_heads=4, num_layers=1, lr=0.1, mesh="4,2",
    ), log_fn=None)
    assert len(out2["history"]) == 2
    assert "'dp': 4" in out2["mesh"] and "'mp': 2" in out2["mesh"]
    import numpy as np
    assert np.isfinite(out2["history"][-1]["loss_sum"])
    # the tp path evaluates like the simulation driver: both finals
    # carry comparable test metrics
    assert np.isfinite(out["final"]["test_acc"])
    assert np.isfinite(out2["final"]["test_acc"])
    assert np.isfinite(out2["final"]["test_loss"])


def test_run_experiment_fedllm_dp_sp():
    """DP x SP fedllm path: 2-way DP x 4-way SP over the faked 8-device
    mesh — federated long-context fine-tuning from the CLI config."""
    import numpy as np

    from fedml_tpu.experiments.run import ExperimentConfig, run_experiment

    out = run_experiment(ExperimentConfig(
        algorithm="fedllm", dataset="fed_shakespeare", comm_round=2,
        client_num_in_total=4, client_num_per_round=4, batch_size=4,
        embed_dim=32, num_heads=4, num_layers=1, lr=0.1, sp_degree=4,
    ), log_fn=None)
    assert len(out["history"]) == 2
    assert "mesh" in out
    assert np.isfinite(out["history"][-1]["loss_sum"])
    assert np.isfinite(out["final"]["test_acc"])

    import pytest

    with pytest.raises(ValueError):
        run_experiment(ExperimentConfig(
            algorithm="fedllm", dataset="fed_shakespeare", comm_round=1,
            client_num_in_total=4, client_num_per_round=4, batch_size=4,
            embed_dim=32, num_heads=4, num_layers=1, mesh="4,2",
            sp_degree=2,
        ), log_fn=None)


def _fedllm_mesh_2x4(**kw):
    from fedml_tpu.experiments.run import ExperimentConfig

    return ExperimentConfig(
        algorithm="fedllm", dataset="fed_shakespeare", comm_round=1,
        client_num_in_total=4, client_num_per_round=4, batch_size=4,
        embed_dim=32, num_heads=4, num_layers=1, lr=0.1, mesh="2,4", **kw,
    )


def test_run_experiment_fedllm_mesh_refuses_undivided_vocab():
    """The fedllm table shards ``wte`` over mp: a vocabulary mp does not
    divide (fed_shakespeare's 90 over mp=4) is refused by name, not
    padded."""
    import pytest

    from fedml_tpu.experiments.run import run_experiment

    with pytest.raises(ValueError, match="wte/embedding"):
        run_experiment(_fedllm_mesh_2x4(), log_fn=None)


def test_run_experiment_fedllm_mesh_custom_rules_replicate_wte(tmp_path):
    """...and runs when ``--partition_rules`` names the fedllm table
    with its ``wte/embedding`` rule set to replicate."""
    import json

    import numpy as np

    from fedml_tpu.experiments.run import run_experiment
    from fedml_tpu.parallel.partition import FEDLLM_RULES

    rules = [
        [pat, [None, None] if pat == "wte/embedding" else list(dims)]
        for pat, dims in FEDLLM_RULES.rules
    ]
    assert rules[0] == ["wte/embedding", [None, None]]
    path = tmp_path / "fedllm_wte_replicated.json"
    path.write_text(json.dumps({"rules": rules}))
    out = run_experiment(
        _fedllm_mesh_2x4(partition_rules=str(path)), log_fn=None
    )
    assert "'dp': 2" in out["mesh"] and "'mp': 4" in out["mesh"]
    assert np.isfinite(out["final"]["loss_sum"])
    assert np.isfinite(out["final"]["test_acc"])
