"""Unified experiment entry point — the L5 layer.

The reference ships one ``main_<algo>.py`` + mpirun shell script per
algorithm (``fedml_experiments/``, SURVEY.md §1 L5).  Here a single
typed config + dispatcher covers the whole matrix; per-algorithm
``main_<algo>.py`` shims (same directory) preserve the familiar entry
names.  Usage:

    python -m fedml_tpu.experiments.run --algorithm fedavg \
        --model resnet56 --dataset cifar10 --client_num_in_total 10 \
        --client_num_per_round 4 --comm_round 10 --epochs 1

Flag names follow the reference's canonical set
(``main_fedavg.py:46-105``).  ``--ci 1`` shrinks everything for smoke
runs (reference ``FedAVGAggregator.py:115-120`` semantics).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

from fedml_tpu.core.config import config_to_json, parse_config
from fedml_tpu.core.metrics import MetricsLogger, setup_logging
from fedml_tpu.experiments.registry import (create_model, load_data,
                                            shrink_dataset,
                                            task_loss_for_dataset)

ALGORITHMS = (
    "fedavg", "fedopt", "fedprox", "fednova", "fedavg_robust",
    "hierarchical", "decentralized", "fedgkt", "fednas", "centralized",
    "turboaggregate", "splitnn", "vfl", "base_framework", "fedllm",
)


@dataclasses.dataclass
class ExperimentConfig:
    """Canonical experiment flags (reference main_fedavg.py:46-105)."""

    algorithm: str = "fedavg"
    model: str = "resnet56"
    dataset: str = "cifar10"
    data_dir: str = ""
    partition_method: str = "hetero"
    partition_alpha: float = 0.5
    client_num_in_total: int = 10
    client_num_per_round: int = 4
    batch_size: int = 64
    client_optimizer: str = "sgd"
    lr: float = 0.03
    momentum: float = 0.0
    wd: float = 0.001
    epochs: int = 1
    comm_round: int = 10
    frequency_of_the_test: int = 5
    seed: int = 0
    ci: int = 0
    # fedopt
    server_optimizer: str = "sgd"
    server_lr: float = 1.0
    # fedprox
    mu: float = 0.1
    # robust
    defense_type: str = "norm_diff_clipping"
    norm_bound: float = 5.0
    stddev: float = 0.025
    # hierarchical
    group_num: int = 2
    group_comm_round: int = 2
    # fednas
    stage: str = "search"
    arch_lr: float = 3e-4
    lr_min: float = 0.001  # cosine weight-LR floor (--learning_rate_min)
    lambda_train_regularizer: float = 1.0
    arch_order: int = 1  # 2 = unrolled second-order DARTS architect
    # fedgkt
    temperature: float = 3.0
    alpha_kd: float = 1.0
    epochs_server: int = 1
    # fedllm (federated transformer fine-tuning; beyond-reference family)
    embed_dim: int = 64
    num_heads: int = 4
    num_layers: int = 2
    sp_degree: int = 1  # >1: DP x SP — long-context clients, ring attention
    # --model decoder_lm: the configuration-driven decoder
    # (models/decoder.py) at the sizes of this config.json-style file;
    # vocabulary and length come from the dataset
    model_config: str = ""
    # rule-driven sharding engine (fedml_tpu/parallel/partition.py):
    # --mesh "dp,mp" (also "dp=4,mp=2" / "auto,2") lays the cohort over
    # dp and the model over mp in ONE jit step; --partition_rules picks
    # the (regex -> PartitionSpec) table: a canonical name (fedllm,
    # resnet) or a JSON rule file.  Exclusive with sp_degree;
    # composes with compress/compress_ef (the residual store shards
    # client rows over dp).
    mesh: str = ""
    partition_rules: str = ""
    # beyond-reference knobs available on the FedAvg-engine family
    compute_dtype: str = ""  # "bf16" = mixed-precision local training
    drop_prob: float = 0.0  # failure injection: P(client dies mid-round)
    # update compression (fedml_tpu/compress; FedAvg-engine family):
    # lossy uplink codec simulated inside the compiled round —
    # int8/qsgd8, int4/qsgd4, bf16, topk<rate>; "" = off.  compress_ef
    # threads the error-feedback residual store (required for topk).
    compress: str = ""
    compress_ef: int = 0
    # the reference's CIFAR-family loaders augment UNCONDITIONALLY
    # (crop+flip, +Cutout(16) for cifar10/100 — cifar10/data_loader.py:
    # 57-99, cifar100:85-91, cinic10:91-92); 0 disables for ablations
    data_augmentation: int = 1
    # smoke-tier shrink knobs (0 = unlimited): cap each client's shard /
    # the test set AFTER the real loader runs — the task is never swapped
    max_samples_per_client: int = 0
    max_test_samples: int = 0
    # observability: every main() run emits <run_dir>/metrics.jsonl
    # (per-round spans, comm counters, compile events — read it with
    # tools/trace_summary.py); "" = auto runs/<algo>-<dataset>-<stamp>
    run_dir: str = ""
    # fault tolerance (fedml_tpu/faults; FedAvg-engine family): save the
    # full (variables, opt state, round_idx, rng key) pytree every N
    # completed rounds; --resume 1 continues BIT-identically from the
    # latest readable checkpoint.  checkpoint_dir defaults to a stable
    # runs/ckpt/<algo>-<dataset>-seed<seed> path so a resumed process
    # finds its predecessor's saves without sharing a run_dir.
    checkpoint_every: int = 0
    checkpoint_dir: str = ""
    resume: int = 0
    # fault injection: hard-exit (os._exit, as a SIGKILL would) right
    # before this round trains — the crash half of the chaos layer's
    # crash-then-resume bit-identity check; -1 = off
    crash_at_round: int = -1


def _apply_ci(cfg: ExperimentConfig) -> ExperimentConfig:
    """``--ci 1`` = shrink-only smoke preset.

    The reference's CI substitutes the task itself (its CI scripts run a
    fixed tiny config regardless of flags), which lets broken (model,
    dataset) wiring survive — the round-2 stackoverflow_lr crash lived
    in exactly that blind spot.  Here CI clamps sizes via the public
    shrink knobs and NEVER changes algorithm/model/dataset/loss.
    """
    if cfg.ci:
        if cfg.algorithm == "fedllm":  # token-sequence family: keep task,
            # shrink the transformer too
            return dataclasses.replace(
                cfg,
                client_num_in_total=min(cfg.client_num_in_total, 4),
                client_num_per_round=min(cfg.client_num_per_round, 4),
                comm_round=min(cfg.comm_round, 2),
                batch_size=min(cfg.batch_size, 4),
                embed_dim=min(cfg.embed_dim, 32), num_layers=1,
                max_samples_per_client=cfg.max_samples_per_client or 16,
                max_test_samples=cfg.max_test_samples or 32,
            )
        return dataclasses.replace(
            cfg, client_num_in_total=min(cfg.client_num_in_total, 3),
            client_num_per_round=min(cfg.client_num_per_round, 3),
            comm_round=min(cfg.comm_round, 2), batch_size=min(cfg.batch_size, 8),
            max_samples_per_client=cfg.max_samples_per_client or 16,
            max_test_samples=cfg.max_test_samples or 64,
        )
    return cfg


def _run_fedllm(cfg: ExperimentConfig, ds, t0, log_fn, metrics=None) -> dict:
    """Federated transformer fine-tuning over token sequences (the
    long-context family the reference lacks).  Three drivers:

    - neither ``mesh`` nor ``sp_degree``: the standard simulation driver;
    - ``mesh``: DP x TP on a (dp, mp) mesh, cohort over ``dp`` and the
      transformer laid out over ``mp`` by the rule table
      (``parallel/partition.py``) inside every client;
    - ``sp_degree > 1``: DP x SP on a (clients, sp) mesh
      (``parallel/dp_sp.py``), each client's sequences sharded with
      ring attention — federated long-context fine-tuning.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.models.transformer import lax_attention, transformer_lm

    seq_len = int(ds.train_x.shape[1])
    vocab = max(int(ds.num_classes), int(ds.train_x.max()) + 1)
    # the rule engine shards the model (and the cohort) by GSPMD, where
    # a pallas_call has no partitioning rule: its mesh keeps the lax
    # attention, every other driver the model's own policy
    if cfg.model == "decoder_lm":
        from fedml_tpu.models.decoder import decoder_lm

        if cfg.mesh or cfg.sp_degree > 1:
            raise ValueError(
                "--model decoder_lm trains through the simulation driver "
                "only: the rule table and the ring attention of --mesh / "
                "sp_degree know the transformer_lm parameter tree"
            )
        with open(cfg.model_config) as f:
            bundle = decoder_lm({**json.load(f), "vocab_size": vocab,
                                 "n_positions": seq_len})
    else:
        bundle = transformer_lm(
            vocab_size=vocab, embed_dim=cfg.embed_dim,
            num_heads=cfg.num_heads, num_layers=cfg.num_layers,
            seq_len=seq_len, attn_fn=lax_attention if cfg.mesh else None,
        )

    if cfg.mesh and cfg.sp_degree > 1:
        raise ValueError(
            "--mesh (dp x mp, the rule-driven sharding engine) and "
            "sp_degree (dp x sp, ring attention) cannot both be set: a "
            "3-D dp x mp x sp mesh is not wired up"
        )
    if cfg.sp_degree <= 1 and not cfg.mesh:
        from fedml_tpu.algorithms.fedavg import FedAvgConfig, FedAvgSimulation

        sim = FedAvgSimulation(bundle, ds, FedAvgConfig(
            num_clients=ds.num_clients,
            clients_per_round=min(cfg.client_num_per_round, ds.num_clients),
            comm_rounds=cfg.comm_round, epochs=cfg.epochs,
            batch_size=cfg.batch_size, client_optimizer=cfg.client_optimizer,
            lr=cfg.lr, momentum=cfg.momentum, weight_decay=cfg.wd,
            frequency_of_the_test=cfg.frequency_of_the_test, seed=cfg.seed,
            compute_dtype=cfg.compute_dtype or None, drop_prob=cfg.drop_prob,
        ), metrics=metrics)
        # run() already merges evaluate_global() into the final round
        hist = sim.run(log_fn=log_fn)
        return {"history": hist, "final": hist[-1],
                "wall_s": time.time() - t0}

    from fedml_tpu.algorithms.fedavg import ServerState, resolve_compute_dtype
    from fedml_tpu.core.client import make_client_optimizer, make_local_update
    from fedml_tpu.core.types import pack_clients

    K = min(cfg.client_num_per_round, ds.num_clients)
    if cfg.mesh:
        from fedml_tpu.parallel.mesh import mesh_from_spec

        mesh = mesh_from_spec(cfg.mesh)
        dp = int(mesh.shape["dp"])
    else:
        if jax.device_count() % cfg.sp_degree:
            raise ValueError(
                f"sp_degree {cfg.sp_degree} does not divide device count "
                f"{jax.device_count()}"
            )
        dp = jax.device_count() // cfg.sp_degree
    if K % dp:
        raise ValueError(f"cohort {K} not divisible by dp width {dp}")
    opt = make_client_optimizer(
        cfg.client_optimizer, cfg.lr, momentum=cfg.momentum,
        weight_decay=cfg.wd,
    )
    cdtype = resolve_compute_dtype(cfg.compute_dtype or None)
    key = jax.random.PRNGKey(cfg.seed)

    if cfg.mesh:
        from fedml_tpu.parallel.partition import (
            make_rule_round_fn, resolve_rules,
        )

        table = resolve_rules(cfg.partition_rules or "fedllm")
        lu = make_local_update(
            bundle, opt, epochs=cfg.epochs, compute_dtype=cdtype,
        )
        variables = bundle.init(key)
        codec = cfg.compress or None
        ef = bool(cfg.compress_ef) and codec is not None
        residuals = ()
        if ef:
            # EF residual store rows shard over dp alongside the cohort
            if ds.num_clients % dp:
                raise ValueError(
                    f"client_num_in_total {ds.num_clients} not divisible "
                    f"by dp width {dp} (the EF residual store shards its "
                    f"client rows over dp)"
                )
            residuals = jax.tree_util.tree_map(
                lambda l: jnp.zeros(
                    (ds.num_clients,) + l.shape, jnp.float32
                ),
                variables,
            )
        state = ServerState(
            variables=variables, opt_state=(),
            round_idx=jnp.zeros((), jnp.int32), key=key,
            residuals=residuals,
        )
        round_fn, shard_state, shard_data = make_rule_round_fn(
            mesh, lu, variables, table,
            codec=codec, error_feedback=ef,
        )
        state = shard_state(state)
        # the unsharded tree init left on the first device would
        # otherwise stay there, whole, for the entire run
        del variables
    else:
        # DP x SP: each client's sequences sharded over an sp axis with
        # ring attention — federated LONG-CONTEXT fine-tuning
        # (parallel/dp_sp.py; parity vs single-device in tests/test_dp_sp.py)
        from fedml_tpu.parallel.dp_sp import (
            make_dp_sp_mesh, make_dp_sp_round_fn,
        )

        if seq_len % cfg.sp_degree:
            raise ValueError(
                f"sequence length {seq_len} not divisible by sp_degree "
                f"{cfg.sp_degree}"
            )
        mesh = make_dp_sp_mesh(dp, cfg.sp_degree)
        round_fn, shard_data, init_fn = make_dp_sp_round_fn(
            mesh, vocab_size=vocab, embed_dim=cfg.embed_dim,
            num_heads=cfg.num_heads, num_layers=cfg.num_layers,
            max_len=seq_len, optimizer=opt, epochs=cfg.epochs,
            compute_dtype=cdtype,
            block_size=max(1, min(512, seq_len // cfg.sp_degree)),
        )
        state = ServerState(
            variables=init_fn(key), opt_state=(),
            round_idx=jnp.zeros((), jnp.int32), key=key,
        )
    hist = []
    from fedml_tpu.core.types import cohort_steps_per_epoch

    steps = cohort_steps_per_epoch(ds, cfg.batch_size)
    from fedml_tpu.core.sampling import host_sample_ids

    # same evaluator + cadence as the simulation driver, so all fedllm
    # paths stay comparable (jit runs the fp32 eval forward with the
    # TP-sharded or replicated variables in place — no gather needed)
    from fedml_tpu.core.client import eval_summary, make_evaluator
    from fedml_tpu.core.types import batch_eval_pack

    evaluator = make_evaluator(bundle)
    tx, ty, tm = batch_eval_pack(ds.test_x, ds.test_y, max(cfg.batch_size, 64))

    def eval_global(variables):
        return eval_summary(evaluator(
            variables, jnp.asarray(tx), jnp.asarray(ty), jnp.asarray(tm)
        ))

    for r in range(cfg.comm_round):
        # shared sampler: all fedllm paths are cohort-comparable
        ids = host_sample_ids(cfg.seed, r, ds.num_clients, K)
        # round-independent pack seed: same convention as the simulation
        # and cross-device drivers (the local update re-permutes per
        # epoch on-device; the base order carries no stochasticity)
        pack = pack_clients(ds, ids, cfg.batch_size, steps_per_epoch=steps,
                            seed=cfg.seed, reuse_buffers=True)
        participation = np.ones(K, np.float32)
        if cfg.drop_prob > 0.0:
            from fedml_tpu.core.sampling import inject_dropout

            participation = np.asarray(inject_dropout(
                jax.random.PRNGKey(cfg.seed), r,
                jnp.asarray(participation), cfg.drop_prob,
            ))
        state, m = round_fn(state, *shard_data((
            pack.x, pack.y, pack.mask, pack.num_samples,
            participation, np.asarray(ids, np.int32),
        )))
        row = {"round": r, **{k: float(v) for k, v in m.items()}}
        if row.get("count"):
            row["train_loss"] = row["loss_sum"] / row["count"]
        if r % cfg.frequency_of_the_test == 0 or r == cfg.comm_round - 1:
            row.update(eval_global(state.variables))
        hist.append(row)
        if metrics is not None:
            metrics.log(row, step=r)
        if log_fn:
            log_fn(row)
    return {"history": hist, "final": hist[-1], "mesh": str(mesh.shape),
            "wall_s": time.time() - t0}


# sims that take the observability sink directly (per-round spans, comm
# accounting, compile tracking live INSIDE their round loop); everything
# else gets its history rows logged post-hoc by run_experiment
_METRICS_NATIVE = frozenset((
    "fedavg", "fedprox", "fedopt", "fednova", "fedavg_robust",
    "hierarchical", "fedllm",
))

# the FedAvg-engine family: the only drivers wired into
# CheckpointManager (attach_checkpointing/resume on FedAvgSimulation)
_RESUMABLE = frozenset((
    "fedavg", "fedprox", "fedopt", "fednova", "fedavg_robust",
    "hierarchical",
))


def run_experiment(cfg: ExperimentConfig, log_fn=print, metrics=None) -> dict:
    cfg = _apply_ci(cfg)
    if (cfg.resume or cfg.checkpoint_every) and cfg.algorithm not in _RESUMABLE:
        # checked BEFORE any work: an explicit resume/checkpoint ask on
        # a driver without checkpoint wiring would otherwise be silently
        # ignored — for --resume that means retraining from round 0
        # while claiming rc=0, the exact masquerade the fail-loud
        # contract below exists to prevent
        raise SystemExit(
            f"--resume/--checkpoint_every: algorithm {cfg.algorithm!r} has "
            f"no checkpoint wiring (supported: {sorted(_RESUMABLE)})"
        )
    t0 = time.time()
    # a file-less logger still feeds the process telemetry registry;
    # main() passes a run_dir-backed one so metrics.jsonl is emitted
    metrics = metrics if metrics is not None else MetricsLogger()
    out = _dispatch(cfg, log_fn, metrics, t0)
    if cfg.algorithm not in _METRICS_NATIVE:
        # post-hoc logging for drivers without a metrics sink of their
        # own.  fednas --stage train already logged its TRAIN rounds
        # live (metrics= threaded into fednas_train_stage) under the
        # same round indices the search stage used — so the search rows
        # are written as kind-tagged records, keeping exactly one plain
        # round stream per file (trace_summary keys round rows on the
        # absence of "kind")
        search_aside = "train_history" in out
        for row in out.get("history") or []:
            if isinstance(row, dict):
                if search_aside:
                    metrics.log({"kind": "search_round", **row})
                else:
                    metrics.log(row, step=row.get("round"))
    return out


def _dispatch(cfg: ExperimentConfig, log_fn, metrics, t0) -> dict:
    """Algorithm switch: build data/model/driver and run (L5 body)."""
    if cfg.algorithm == "base_framework":  # tutorial template: no model/data
        from fedml_tpu.algorithms.base_framework import run_base_framework

        hist = run_base_framework(cfg.client_num_in_total, cfg.comm_round)
        return {"history": hist, "final": hist[-1] if hist else None,
                "wall_s": time.time() - t0}

    if cfg.algorithm == "vfl":  # vertical FL uses its own tabular data
        from fedml_tpu.algorithms.vfl import VerticalFederation, run_vfl
        from fedml_tpu.data.tabular import load_lending_club
        from fedml_tpu.models.finance import vfl_party

        x, y, splits = load_lending_club(cfg.data_dir or "./data/lending_club_loan")
        if cfg.max_samples_per_client:
            # the shrink contract holds for vfl too: parties share rows,
            # so cap the TABLE (train rows + test rows), not per-party
            cap = (cfg.max_samples_per_client * cfg.client_num_in_total
                   + (cfg.max_test_samples or 64))
            x, y = x[:cap], y[:cap]
        n_test = max(32, len(y) // 5)
        xs = [x[:, s] for s in splits]
        fed = VerticalFederation(
            [vfl_party(xi.shape[1], 16) for xi in xs], lr=cfg.lr
        )
        _, hist = run_vfl(
            fed, [xi[:-n_test] for xi in xs], y[:-n_test],
            [xi[-n_test:] for xi in xs], y[-n_test:],
            epochs=cfg.comm_round, batch_size=cfg.batch_size,
        )
        return {"history": hist, "wall_s": time.time() - t0}

    ds = shrink_dataset(
        load_data(cfg.dataset, cfg.data_dir, cfg.client_num_in_total,
                  cfg.partition_method, cfg.partition_alpha, cfg.seed),
        cfg.max_samples_per_client, cfg.max_test_samples,
    )
    loss_fn = task_loss_for_dataset(cfg.dataset)

    if cfg.algorithm == "splitnn":
        from fedml_tpu.algorithms.splitnn import SplitNNSimulation
        from fedml_tpu.models.cnn import cnn_split_pair

        bottom, top = cnn_split_pair(ds.num_classes, ds.train_x.shape[1:])
        parts = [
            (ds.train_x[idx], ds.train_y[idx])
            for idx in ds.train_client_idx.values()
        ]
        sim = SplitNNSimulation(
            bottom, top, parts, test_data=(ds.test_x, ds.test_y),
            batch_size=cfg.batch_size, lr=cfg.lr, seed=cfg.seed,
        )
        hist = []
        for _ in range(cfg.comm_round):
            hist.extend(sim.run_epoch())
        return {"history": hist, "wall_s": time.time() - t0}

    if cfg.algorithm == "fedgkt":
        from fedml_tpu.algorithms.fedgkt import FedGKT, FedGKTConfig
        from fedml_tpu.models.resnet_gkt import resnet8_56, resnet56_server

        img = ds.train_x.shape[1]
        algo = FedGKT(
            resnet8_56(ds.num_classes, img), resnet56_server(ds.num_classes, img),
            ds, FedGKTConfig(
                num_clients=ds.num_clients, comm_rounds=cfg.comm_round,
                epochs_client=cfg.epochs, epochs_server=cfg.epochs_server,
                batch_size=cfg.batch_size, lr_client=cfg.lr, lr_server=cfg.lr,
                temperature=cfg.temperature, alpha=cfg.alpha_kd, seed=cfg.seed,
            ))
        hist = algo.run()
        return {"history": hist, "wall_s": time.time() - t0}

    if cfg.algorithm == "fednas":
        from fedml_tpu.algorithms.fedavg import FedAvgConfig
        from fedml_tpu.algorithms.fednas import (FedNASConfig, FedNASSearch,
                                                 fednas_train_stage)
        from fedml_tpu.models.darts.search import darts_search

        img = ds.train_x.shape[1]
        chans = int(ds.train_x.shape[-1])
        search = FedNASSearch(
            darts_search(C=8, num_classes=ds.num_classes, layers=4,
                         image_size=img, in_channels=chans),
            ds, FedNASConfig(
                num_clients=ds.num_clients, comm_rounds=cfg.comm_round,
                epochs=cfg.epochs, batch_size=cfg.batch_size, lr=cfg.lr,
                lr_min=cfg.lr_min, arch_lr=cfg.arch_lr,
                lambda_train_regularizer=cfg.lambda_train_regularizer,
                arch_order=cfg.arch_order,
                seed=cfg.seed,
            ))
        hist = search.run()
        genotype = search.genotype()
        out = {"history": hist, "genotype": str(genotype),
               "wall_s": time.time() - t0}
        if cfg.stage == "train":
            # reference train stage: SGD(momentum, wd) + grad clip
            # (FedNASTrainer.py:134-141,185) — same knobs as search
            sim = fednas_train_stage(genotype, ds, metrics=metrics, config=FedAvgConfig(
                num_clients=ds.num_clients,
                clients_per_round=cfg.client_num_per_round,
                comm_rounds=cfg.comm_round, epochs=cfg.epochs,
                batch_size=cfg.batch_size, lr=cfg.lr, seed=cfg.seed,
                momentum=cfg.momentum or 0.9, weight_decay=cfg.wd,
                grad_clip=5.0,
            ), C=8, layers=4, image_size=img, in_channels=chans,
                lr_min=cfg.lr_min)
            out["train_history"] = sim.run(log_fn=log_fn)
        return out

    if cfg.algorithm == "fedllm":
        return _run_fedllm(cfg, ds, t0, log_fn, metrics=metrics)

    bundle = create_model(cfg.model, cfg.dataset, ds.num_classes,
                          input_shape=tuple(ds.train_x.shape[1:]))

    if cfg.algorithm == "centralized":
        from fedml_tpu.algorithms.centralized import CentralizedTrainer

        trainer = CentralizedTrainer(
            bundle, ds, batch_size=cfg.batch_size, lr=cfg.lr,
            optimizer=cfg.client_optimizer, weight_decay=cfg.wd,
            momentum=cfg.momentum, seed=cfg.seed, loss_fn=loss_fn,
        )
        hist = [trainer.train(epochs=cfg.epochs)
                for _ in range(cfg.comm_round)]
        hist[-1].update(trainer.evaluate())
        return {"history": hist, "final": hist[-1],
                "wall_s": time.time() - t0}

    if cfg.algorithm == "decentralized":
        from fedml_tpu.algorithms.decentralized import DecentralizedSimulation
        from fedml_tpu.core.topology import SymmetricTopologyManager

        tm = SymmetricTopologyManager(
            ds.num_clients, neighbor_num=min(2, ds.num_clients - 1),
            seed=cfg.seed,
        )
        sim = DecentralizedSimulation(
            bundle, ds, tm.generate_topology(), epochs=cfg.epochs,
            batch_size=cfg.batch_size, lr=cfg.lr, seed=cfg.seed,
            loss_fn=loss_fn,
        )
        hist = sim.run(cfg.comm_round)
        final = sim.evaluate_worker(0)
        return {"history": hist, "final": final, "wall_s": time.time() - t0}

    if cfg.algorithm == "turboaggregate":
        from fedml_tpu.algorithms.turboaggregate import (
            TurboAggregateConfig, TurboAggregateSimulation)

        algo = TurboAggregateSimulation(bundle, ds, TurboAggregateConfig(
            num_clients=ds.num_clients, comm_rounds=cfg.comm_round,
            epochs=cfg.epochs, batch_size=cfg.batch_size, lr=cfg.lr,
            seed=cfg.seed,
        ), loss_fn=loss_fn)
        hist = algo.run()
        return {"history": hist, "wall_s": time.time() - t0}

    # the FedAvg-engine family
    from fedml_tpu.algorithms import fedavg as fa

    # reference parity: the CIFAR-family loaders bake augmentation into
    # their train transform — published accuracies are unreachable
    # without it (measured: the r3 north-star run memorized).  Here it
    # is the jit-compiled per-epoch augment inside the local update.
    engine_kw = {"metrics": metrics}
    if cfg.data_augmentation and ds.train_x.ndim == 4:
        from fedml_tpu.data.augment import make_image_augment

        if cfg.dataset in ("cifar10", "cifar100"):
            engine_kw["augment_fn"] = make_image_augment(
                pad=4, flip=True, cutout=16)
        elif cfg.dataset == "cinic10":
            engine_kw["augment_fn"] = make_image_augment(
                pad=4, flip=True, cutout=None)

    common = dict(
        num_clients=ds.num_clients,
        clients_per_round=cfg.client_num_per_round,
        comm_rounds=cfg.comm_round, epochs=cfg.epochs,
        batch_size=cfg.batch_size, client_optimizer=cfg.client_optimizer,
        lr=cfg.lr, momentum=cfg.momentum, weight_decay=cfg.wd,
        frequency_of_the_test=cfg.frequency_of_the_test, seed=cfg.seed,
        compute_dtype=cfg.compute_dtype or None,
        drop_prob=cfg.drop_prob,
        compress_codec=cfg.compress or None,
        compress_ef=bool(cfg.compress_ef),
    )
    if cfg.algorithm == "fedavg":
        sim = fa.FedAvgSimulation(bundle, ds, fa.FedAvgConfig(**common),
                                  loss_fn=loss_fn, **engine_kw)
    elif cfg.algorithm == "fedprox":
        from fedml_tpu.algorithms.fedprox import FedProxSimulation

        sim = FedProxSimulation(bundle, ds, fa.FedAvgConfig(**common),
                                mu=cfg.mu, loss_fn=loss_fn, **engine_kw)
    elif cfg.algorithm == "fedopt":
        from fedml_tpu.algorithms.fedopt import FedOptSimulation

        sim = FedOptSimulation(
            bundle, ds, fa.FedAvgConfig(**common),
            server_optimizer=cfg.server_optimizer, server_lr=cfg.server_lr,
            loss_fn=loss_fn, **engine_kw,
        )
    elif cfg.algorithm == "fednova":
        nova_cfg = fa.FedAvgConfig(**{**common, "weight_decay": 0.0})
        from fedml_tpu.algorithms.fednova import FedNovaSimulation

        sim = FedNovaSimulation(bundle, ds, nova_cfg, loss_fn=loss_fn,
                                **engine_kw)
    elif cfg.algorithm == "fedavg_robust":
        from fedml_tpu.algorithms.fedavg_robust import FedAvgRobustSimulation

        sim = FedAvgRobustSimulation(
            bundle, ds, fa.FedAvgConfig(**common),
            defense_type=cfg.defense_type, norm_bound=cfg.norm_bound,
            stddev=cfg.stddev, loss_fn=loss_fn, **engine_kw,
        )
    elif cfg.algorithm == "hierarchical":
        from fedml_tpu.algorithms.hierarchical import HierarchicalSimulation

        sim = HierarchicalSimulation(
            bundle, ds, fa.FedAvgConfig(**common),
            num_groups=cfg.group_num, group_comm_round=cfg.group_comm_round,
            loss_fn=loss_fn, **engine_kw,
        )
    else:
        raise ValueError(f"unknown algorithm: {cfg.algorithm}")

    # no hasattr guard: run_experiment already refused non-_RESUMABLE
    # algorithms up front, and every _RESUMABLE driver is a
    # FedAvgSimulation subclass — a drifted entry should AttributeError
    # loudly here, not silently skip the resume
    done = 0
    if cfg.checkpoint_every or cfg.resume:
        import hashlib

        from fedml_tpu.core.checkpoint import CheckpointManager

        # the default dir must be (a) stable between the original run
        # and its `--resume 1` relaunch and (b) UNIQUE per experiment:
        # keying only (algo, dataset, seed) would let two sweep arms
        # differing in lr/model/... share a dir and silently resume
        # from each other's state (same treedef — no error would fire).
        # Hash the full config minus the knobs that legitimately differ
        # across the crash/resume pair.
        # comm_round excluded too: "train 6 rounds, then resume with
        # --comm_round 12 to extend" is the canonical resume move and
        # must map to the SAME directory
        stable = {k: v for k, v in dataclasses.asdict(cfg).items()
                  if k not in ("run_dir", "resume", "crash_at_round",
                               "checkpoint_dir", "checkpoint_every",
                               "comm_round")}
        tag = hashlib.sha1(
            json.dumps(stable, sort_keys=True).encode()
        ).hexdigest()[:10]
        ckdir = cfg.checkpoint_dir or os.path.join(
            "runs", "ckpt",
            f"{cfg.algorithm}-{cfg.dataset}-seed{cfg.seed}-{tag}",
        )
        sim.attach_checkpointing(
            CheckpointManager(ckdir), cfg.checkpoint_every or 1
        )
        if cfg.resume:
            done = sim.resume()
            if done == 0:
                # an EXPLICIT resume that restores nothing must fail
                # loudly: silently retraining from round 0 (typo'd
                # --checkpoint_dir, relocated default dir) would
                # masquerade as a successful resume
                raise SystemExit(
                    f"--resume 1: no readable checkpoint in {ckdir} "
                    "(pass the original --checkpoint_dir, or drop "
                    "--resume to start fresh)"
                )
    if cfg.crash_at_round >= 0:
        sim.crash_at_round = cfg.crash_at_round
    hist = sim.run(rounds=max(0, cfg.comm_round - done), log_fn=log_fn)
    # run() merges evaluate_global() into the final round already
    out = {"history": hist, "final": hist[-1] if hist else None,
           "wall_s": time.time() - t0}
    if done:
        out["resumed_rounds"] = done
    return out


def main(argv=None):
    from fedml_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    cfg = parse_config(ExperimentConfig, argv)
    if cfg.algorithm not in ALGORITHMS:
        raise SystemExit(f"--algorithm must be one of {ALGORITHMS}")
    print(config_to_json(cfg))
    setup_logging()
    from fedml_tpu.obs.jax_hooks import (install_jax_monitoring,
                                         record_device_memory)
    from fedml_tpu.utils.device import device_report

    install_jax_monitoring()
    # pid suffix: two arms of a sweep launched in the same wall-clock
    # second must not append into one metrics.jsonl
    run_dir = cfg.run_dir or os.path.join(
        "runs",
        f"{cfg.algorithm}-{cfg.dataset}-"
        f"{time.strftime('%Y%m%d-%H%M%S')}-p{os.getpid()}",
    )
    # context manager: the JSONL handle closes on EVERY exit path —
    # a crashed run still leaves a readable metrics.jsonl behind
    with MetricsLogger(run_dir=run_dir) as metrics:
        # where it ran is part of the record: this entry point runs on
        # any backend (tests use the CPU), so it reports, it does not
        # refuse
        metrics.log({"kind": "config", **device_report(),
                     **json.loads(config_to_json(cfg))})
        # log_fn=None: with INFO logging on, MetricsLogger already
        # surfaces every row on the console — print would double it
        out = run_experiment(cfg, log_fn=None, metrics=metrics)
        # final telemetry merge: device-memory high-water gauges, then
        # counters/histograms + pending compile events into metrics.jsonl
        record_device_memory(metrics.telemetry)
        metrics.log_telemetry()
    tail = out.get("final") or (out["history"][-1] if out.get("history") else {})
    print(json.dumps({"final": tail, "wall_s": round(out["wall_s"], 2),
                      "run_dir": run_dir}, default=str))
    return out


if __name__ == "__main__":
    main(sys.argv[1:])
