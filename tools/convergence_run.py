"""North-star convergence evidence (round 4: the full reference recipe).

VERDICT r3 weak #1: the r3 run omitted the one ingredient of the
reference recipe the repo already shipped — data augmentation — so the
net memorized (train acc 1.0 by round 10) and both runs stalled below
the pre-declared 0.81 target.  The reference's 93.19/87.12 numbers are
trained WITH RandomCrop(32, pad 4) + RandomHorizontalFlip + Cutout(16)
(``/root/reference/fedml_api/data_preprocessing/cifar10/data_loader.py:57-99``).
Round 4 wires the repo's jit-compiled equivalent (``data/augment.py``,
``cifar_augment()``) into the preset — the ONLY change to the r3
configuration — and reports rounds-to-target against the pre-declared
0.9×ceiling target alone (the r3 post-hoc ``relative_target`` is gone).

The r3 fixes this builds on:

- **Hardness**: the synthetic task gets ``label_noise`` η — that
  fraction of train AND test labels flipped to a uniformly random wrong
  class — giving a documented irreducible ceiling ≈ 1−η (a model that
  perfectly learns the clean prototypes scores ≈ 1−η on the noisy test
  set).  Trajectories can no longer saturate at 1.0.
- **IID vs non-IID pair**: the EXACT north-star hyperparameters
  (ResNet-56, 10 clients all participating, SGD lr 1e-3 wd 1e-3, E=20,
  batch 64 — ``/root/reference/benchmark/README.md:105``, 93.19 IID vs
  87.12 non-IID on real CIFAR-10) run twice with ONE flag changed:
  ``partition homo`` (IID) vs ``partition hetero`` LDA α=0.5.  The
  artifact records both trajectories, the fixed-round accuracy gap, and
  rounds-to-target (first round reaching 90% of ceiling) — reproducing
  the reference's ordering (IID ≥ non-IID, fewer rounds to target).
- **Fused driver**: rounds between evals run through
  ``FedAvgSimulation.run_fused`` (``make_multi_round_fn`` chunks — the
  benchmarked fast path, bit-identical to ``run()``), so
  wall-clock/round is the framework's real number.

A second preset, ``--preset mnist_lr``, covers the reference's
cross-DEVICE benchmark row (``benchmark/README.md:12``: MNIST + LR,
1000 power-law clients, 10 sampled/round) — the sampled-cohort regime
— on the per-round driver (sampling 10/1000 on a resident 1000-client
block would waste 100× the compute).

Round 5 additions: ``--model mobilenet`` runs the cross-silo recipe on
the reference's second conv family (README.md:108); presets
``emnist_lr`` / ``synthetic_lr`` (the README.md:13-14 linear rows —
synthetic_lr needs NO stand-in, the dataset is the reference's own
generative family) and ``stackoverflow_nwp`` (README.md:57, the
342,477-client population-scale row on a ceiling-calibrated peaked
chain); fed_cifar100 defaults to the full 4000-round horizon.

Usage: python tools/convergence_run.py
       [--preset northstar|mnist_lr|femnist_cnn|shakespeare_rnn|
                 fed_cifar100|stackoverflow_nwp|emnist_lr|synthetic_lr]
       [--model resnet56|mobilenet]
       [--rounds N] [--partitions both|iid|noniid] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def trajectory_rows(hist):
    return [
        {"round": h["round"], "test_acc": round(h["test_acc"], 5),
         "test_loss": round(h["test_loss"], 5),
         **({"train_acc": round(h["train_acc"], 5)} if "train_acc" in h
            else {})}
        for h in hist if "test_acc" in h
    ]


def rounds_to_target(hist, target):
    for h in hist:
        if "test_acc" in h and h["test_acc"] >= target:
            return h["round"]
    return None


def build_comparison(runs):
    """IID vs non-IID comparison: final-acc gap, ordering, and
    rounds-to-target at the single PRE-DECLARED target
    (0.9 × the label-noise ceiling).  The r3 post-hoc relative target is
    deliberately gone: a comparison that moves its own goalposts after
    seeing the data certifies nothing (VERDICT r3 weak #1).

    Mismatched horizons (one arm truncated mid-run — the r5 c100
    non-IID arm stopped at round 53 vs iid's 100) are compared at
    ``min(rounds_completed)``: a final-vs-final gap across different
    horizons silently assumes matched training budgets, so the verdict
    additionally carries ``truncated_arm``/``compared_at_round``
    (ADVICE r5)."""
    a, b = runs["iid"], runs["noniid_lda0.5"]
    if a["final_test_acc"] is None or b["final_test_acc"] is None:
        # a run with per-round rows but no eval rows (crashed before its
        # first eval) must not fabricate a comparison
        return {"incomplete": True,
                "reason": "a run has no evaluation rows; no comparison"}

    def last_eval_round(run):
        traj = run.get("trajectory") or []
        return traj[-1]["round"] if traj else None

    def eval_at_or_before(run, r):
        """Last (round, acc) eval row at or before ``r`` — None when
        the arm has no eval that early (mis-aligned cadences)."""
        rows = [t for t in (run.get("trajectory") or [])
                if t["round"] <= r]
        return (rows[-1]["round"], rows[-1]["test_acc"]) if rows else None

    ra, rb = last_eval_round(a), last_eval_round(b)
    truncation = {}
    acc_a, acc_b = a["final_test_acc"], b["final_test_acc"]
    if ra is not None and rb is not None and ra != rb:
        common = min(ra, rb)
        ea, eb = eval_at_or_before(a, common), eval_at_or_before(b, common)
        if ea is None or eb is None:
            # the longer arm has no eval row inside the truncated
            # horizon: no comparable operating point exists
            return {"incomplete": True,
                    "truncated_arm": "iid" if ra < rb else "noniid",
                    "horizons": {"iid": ra, "noniid": rb},
                    "reason": "an arm has no eval at or before the "
                              "common horizon; no comparison"}
        acc_a, acc_b = ea[1], eb[1]

        def censor(rtt):
            # a crossing AFTER the common horizon used training budget
            # the truncated arm never had — not comparable
            return rtt if (rtt is not None and rtt <= common) else None

        truncation = {
            "truncated_arm": "iid" if ra < rb else "noniid",
            # eval cadences can mis-align: record the ACTUAL round each
            # arm's compared accuracy comes from, not one nominal round
            "compared_at_round": {"iid": ea[0], "noniid": eb[0]},
            "horizons": {"iid": ra, "noniid": rb},
            "note": "arms ran to different horizons; gap/ordering "
                    "computed from each arm's last eval inside the "
                    "common horizon — the longer arm's extra rounds "
                    "are NOT part of this verdict",
            # rounds_to_target under the SAME budget for both arms;
            # the raw full-horizon values stay below for the record
            "rounds_to_target_within_common_horizon": {
                "iid": censor(a["rounds_to_target"]),
                "noniid": censor(b["rounds_to_target"]),
            },
        }
    gap = round(acc_a - acc_b, 5)
    return {
        "final_acc_gap_iid_minus_noniid": gap,
        # a gap within +-0.001 (10 test images) is below the eval's
        # resolution — when both arms sit at the stand-in ceiling that
        # is a TIE (the saturation phenomenon documented in
        # CONVERGENCE_r04_hard.json), not an ordering result
        **({"ordering_matches_reference": gap >= 0}
           if abs(gap) > 0.001 else
           {"ordering_matches_reference": None,
            "tie_within_eval_resolution": True}),
        **truncation,
        "rounds_to_target": {
            "iid": a["rounds_to_target"],
            "noniid": b["rounds_to_target"],
            **({"caveat": "per-arm full-horizon values; see "
                          "rounds_to_target_within_common_horizon for "
                          "the budget-matched comparison"}
               if truncation else {}),
        },
    }


def per_round_seconds(stamps, burst_gap: float = 0.2):
    """Per-round wall seconds from one log's timestamps.

    ``run_fused`` logs a fused chunk's rows in one burst, so rows are
    grouped into bursts (gap < ``burst_gap``) and each burst's wall
    delta is normalized by its row count — a raw per-row delta would
    collapse to ~0 whenever rounds_per_call > 1.  The first burst
    (compile + first chunk) has no predecessor and is excluded, like
    bench warmup.  ``stamps[0]`` must be the 0.0 pre-run marker.
    Returns the unsorted per-round list (callers pool lists across
    resumed-run segments before taking a median)."""
    bursts = []  # (last stamp of burst, rows in burst)
    for s in stamps[1:]:
        if bursts and s - bursts[-1][0] < burst_gap:
            bursts[-1] = (s, bursts[-1][1] + 1)
        else:
            bursts.append((s, 1))
    return [(b[0] - a[0]) / b[1] for a, b in zip(bursts, bursts[1:])]


def median_round_seconds(stamps, burst_gap: float = 0.2):
    """Steady-state per-round seconds: median of ``per_round_seconds``."""
    per_round = sorted(per_round_seconds(stamps, burst_gap))
    return per_round[len(per_round) // 2] if per_round else None


def northstar_metadata(*, noise=1.2, label_noise=0.1, epochs=20,
                       rounds=100, num_train=50000, num_test=10000,
                       augment=True, smooth_sigma=2.0,
                       flip_symmetric=True, model="resnet56",
                       num_classes=10):
    """The artifact's standard header sections (shared with
    tools/convergence_from_log.py so a log-reconstructed artifact has
    the same schema as a tool-written one)."""
    ceiling = 1.0 - label_noise
    # the four cross-silo (model, dataset) rows, benchmark/README.md
    # :105/:106/:108/:109 — (iid acc, non-iid acc, line)
    rows = {("resnet56", 10): (93.19, 87.12, 105),
            ("resnet56", 100): (68.91, 64.70, 106),
            ("mobilenet", 10): (91.12, 86.32, 108),
            ("mobilenet", 100): (55.12, 53.54, 109)}
    iid_acc, noniid_acc, line = rows[(model, num_classes)]
    return {
        "experiment": "north-star convergence, IID vs non-IID pair "
                      f"(synthetic CIFAR-{num_classes} stand-in, "
                      "fused driver)",
        "reference_target": {
            "dataset": f"CIFAR-{num_classes} (real, unavailable "
                       "offline: zero egress)",
            "iid_acc": iid_acc,
            "non_iid_acc": noniid_acc,
            "rounds": 100,
            "source": f"/root/reference/benchmark/README.md:{line}",
            "claim_reproduced": "ordering (IID >= non-IID at fixed "
                                "rounds) + rounds-to-target worsening "
                                "under LDA, on a task with a documented "
                                "accuracy ceiling",
        },
        "hardness": {
            "feature_noise_sigma": noise,
            "label_noise_eta": label_noise,
            "accuracy_ceiling": ceiling,
            "target_for_rounds_to_target": round(0.9 * ceiling, 4),
        },
        "standin_statistics": {
            "prototype_smooth_sigma_px": smooth_sigma,
            "flip_symmetric_signal": flip_symmetric,
            "why": "the two natural-image statistics that make the "
                   "reference's crop/flip/cutout recipe label-preserving; "
                   "with iid-pixel prototypes the augmented run is pinned "
                   "at chance (measured, data/synthetic.py docstring)",
        },
        "config": {
            "model": model, "clients": 10, "clients_per_round": 10,
            "optimizer": "sgd", "lr": 1e-3, "weight_decay": 1e-3,
            "local_epochs": epochs, "batch_size": 64,
            "rounds": rounds, "compute_dtype": "bf16",
            "train_samples": num_train, "test_samples": num_test,
            "augmentation": (
                "crop(pad 4) + horizontal flip + Cutout(16), jit-compiled "
                "inside the local update (data/augment.py cifar_augment; "
                "reference recipe fedml_api/data_preprocessing/cifar10/"
                "data_loader.py:57-99)" if augment else "none"),
            "driver": "FedAvgSimulation.run_fused (make_multi_round_fn "
                      "between evals)",
        },
    }


def write_artifact(out, artifact, summary):
    """One writer for every preset: platform stamp + dump + summary line
    (schema changes happen in ONE place)."""
    import jax

    artifact["platform"] = jax.devices()[0].platform
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"wrote {out}: {json.dumps(summary)}")


def cleanup_partial(out: str) -> None:
    """Remove the crash-recovery ``.partial`` sidecar once its rows are
    merged into the FINAL artifact: a stale sidecar outliving its merge
    shadows the merged rows for the NEXT resumed session (the repo root
    carried three such orphans before this existed)."""
    partial = out + ".partial"
    if os.path.exists(partial):
        os.remove(partial)


def run_northstar_once(partition, args, log_prefix):
    import jax

    from fedml_tpu.algorithms.fedavg import FedAvgConfig, FedAvgSimulation
    from fedml_tpu.core.checkpoint import CheckpointManager
    from fedml_tpu.data.augment import cifar_augment
    from fedml_tpu.data.synthetic import synthetic_classification

    cfg = FedAvgConfig(
        num_clients=10,
        clients_per_round=10,          # all participating (BASELINE.md)
        comm_rounds=args.rounds,
        epochs=args.epochs,            # E=20
        batch_size=64,
        client_optimizer="sgd",
        lr=1e-3,
        weight_decay=1e-3,
        frequency_of_the_test=args.eval_every,
        compute_dtype="bf16",
        seed=0,
    )
    ds = synthetic_classification(
        num_train=args.num_train,
        num_test=args.num_test,
        input_shape=(32, 32, 3),
        num_classes=args.num_classes,
        num_clients=cfg.num_clients,
        partition=partition,           # "homo" = IID, "hetero" = LDA
        partition_alpha=0.5,
        noise=args.noise,
        label_noise=args.label_noise,
        seed=0,
        name=f"cifar{args.num_classes}-standin-{partition}",
        # natural-image statistics (spatial smoothness + flip-invariant
        # class signal) — without them the reference's crop/flip/cutout
        # recipe erases an iid-pixel prototype signal entirely (measured:
        # train acc pinned at 0.11 for 12 rounds on the real chip); see
        # data/synthetic.py
        smooth_sigma=args.smooth_sigma,
        flip_symmetric=bool(args.flip_symmetric),
    )
    if args.model == "mobilenet":
        # reference cross-silo row benchmark/README.md:108 — same
        # recipe/hyperparameters as the ResNet-56 row, MobileNet model
        # (fedml_api/model/cv/mobilenet.py)
        from fedml_tpu.models.mobilenet import mobilenet

        bundle = mobilenet(num_classes=args.num_classes)
    else:
        from fedml_tpu.models.resnet import resnet56

        bundle = resnet56(num_classes=args.num_classes)
    sim = FedAvgSimulation(
        bundle, ds, cfg,
        augment_fn=cifar_augment() if args.augment else None,
    )

    # resume support: a multi-hour session can die mid-run — checkpoint
    # the full ServerState at every eval chunk and continue from the
    # latest on restart.  run_fused keys its eval cadence on the ABSOLUTE
    # state.round_idx, so a resumed run evaluates on the same rounds.
    mgr = None
    start_round = 0
    if getattr(args, "checkpoint_dir", ""):
        tag = "iid" if partition == "homo" else "noniid"
        if args.model != "resnet56":
            tag = f"{args.model}_{tag}"
        if args.num_classes != 10:
            tag = f"c{args.num_classes}_{tag}"
        ckdir = os.path.join(args.checkpoint_dir, tag)
        # config stamp: a checkpoint from a DIFFERENT experiment (other
        # noise/seed/epochs — same pytree shapes, so the shape guard
        # can't catch it) must never be silently resumed into this run
        stamp = {"model": args.model, "num_classes": args.num_classes,
                 "noise": args.noise, "label_noise": args.label_noise,
                 "epochs": args.epochs,
                 "num_train": args.num_train, "seed": 0,
                 "augment": bool(args.augment),
                 "smooth_sigma": args.smooth_sigma,
                 "flip_symmetric": bool(args.flip_symmetric)}
        check_config_stamp(ckdir, stamp,
                           legacy_fill={"model": "resnet56",
                                        "num_classes": 10})
        mgr = CheckpointManager(ckdir, max_to_keep=2)
        if mgr.latest_step() is not None:
            sim.state = mgr.restore(like=sim.state)
            start_round = int(sim.state.round_idx)
            if start_round >= args.rounds:
                raise SystemExit(
                    f"checkpoint at round {start_round} >= --rounds "
                    f"{args.rounds}: this run already completed — "
                    "remove the checkpoint dir to start fresh (a "
                    "0-round 'run' would write a degenerate artifact)"
                )
            print(f"{log_prefix} resumed from checkpoint at round "
                  f"{start_round}", flush=True)

    t0 = time.time()
    stamps = [0.0]

    def log_fn(m):
        line = {k: round(v, 5) if isinstance(v, float) else v
                for k, v in m.items()}
        line["elapsed_s"] = round(time.time() - t0, 1)
        stamps.append(time.time() - t0)
        print(f"{log_prefix} {json.dumps(line)}", flush=True)
        if mgr is not None and "test_acc" in m:
            mgr.save(m["round"] + 1, sim.state)

    # default 1 round/call; an explicit value is honored as given
    hist = sim.run_fused(
        rounds=args.rounds - start_round, log_fn=log_fn,
        rounds_per_call=(1 if args.rounds_per_call is None
                         else args.rounds_per_call) or None,
    )
    wall = time.time() - t0
    # median per-round wall = the framework's steady-state number (see
    # median_round_seconds: burst-aware, first/compile burst excluded);
    # the MEAN additionally carries any stall of the session, which is
    # environment, not framework
    return hist, wall, median_round_seconds(stamps), cfg, start_round


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--preset",
                   choices=["northstar", "mnist_lr", "femnist_cnn",
                            "shakespeare_rnn", "fed_cifar100",
                            "stackoverflow_nwp", "emnist_lr",
                            "synthetic_lr"],
                   default="northstar")
    p.add_argument("--rounds", type=int, default=None,
                   help="horizon (default: northstar 100, mnist_lr 400, "
                   "femnist_cnn 1500, shakespeare_rnn 1200, fed_cifar100 "
                   "4000, stackoverflow_nwp 1500 — the reference rows' "
                   "scales)")
    p.add_argument("--num-train", type=int, default=None)
    p.add_argument("--num-test", type=int, default=None)
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--eval-every", type=int, default=None,
                   help="test-eval cadence (default: northstar 5, "
                   "cross-device presets 25 — chunks end on eval "
                   "rounds, so a tighter cadence also caps the fused "
                   "chunk length)")
    p.add_argument("--noise", type=float, default=1.2,
                   help="feature noise sigma (cluster overlap hardness; "
                   "1.6 measured too hard — the net memorizes instead of "
                   "generalizing; 0.8 saturates — r2's flaw)")
    p.add_argument("--label-noise", type=float, default=None,
                   help="label flip rate eta: test ceiling ~= 1 - eta "
                   "(image presets; default 0.1).  For the text presets "
                   "it is the peaked chain's JUMP RATE: shakespeare "
                   "default 0.1 (ceiling ~0.9); stackoverflow_nwp "
                   "default 0.75, putting the Bayes ceiling (0.2501) "
                   "just above the reference row's absolute 0.195 "
                   "target so rounds-to-target stays meaningful "
                   "(VERDICT r4 weak #2)")
    p.add_argument("--augment", type=int, choices=[0, 1], default=1,
                   help="train with the reference CIFAR recipe "
                   "(crop+flip+cutout, data/augment.py) — the reference "
                   "numbers are produced WITH it; 0 reproduces the r3 "
                   "memorizing configuration")
    p.add_argument("--smooth-sigma", type=float, default=2.0,
                   help="prototype spatial smoothness (px); natural-image "
                   "statistic the augmentation recipe relies on")
    p.add_argument("--flip-symmetric", type=int, choices=[0, 1], default=1,
                   help="flip-invariant class signal (natural-image "
                   "statistic RandomHorizontalFlip relies on)")
    p.add_argument("--partitions", choices=["both", "iid", "noniid"],
                   default="both")
    p.add_argument("--model", choices=["resnet56", "mobilenet"],
                   default="resnet56",
                   help="northstar-preset model: resnet56 (README.md:105) "
                   "or mobilenet (README.md:108 — same recipe, second "
                   "conv family: depthwise-separable MXU profile)")
    p.add_argument("--num-classes", type=int, default=10,
                   choices=[10, 100],
                   help="northstar-preset class count: 10 = the CIFAR-10 "
                   "rows; 100 = the CIFAR-100 cross-silo rows "
                   "(README.md:106/109 — same recipe, 100-way head)")
    p.add_argument("--rounds-per-call", type=int, default=None,
                   help="cap on rounds fused per device call (default: "
                   "northstar 1, cross-device presets 25)")
    p.add_argument("--out", default=None)
    p.add_argument("--checkpoint-dir", default="/tmp/conv_r04_ckpt",
                   help="ServerState checkpoints per eval chunk; on "
                   "restart the run resumes from the latest. '' "
                   "disables")
    args = p.parse_args()

    from fedml_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    if args.rounds is None:
        args.rounds = {"northstar": 100, "mnist_lr": 400,
                       "femnist_cnn": 1500,
                       "shakespeare_rnn": 1200,
                       "fed_cifar100": 4000,
                       "stackoverflow_nwp": 1500,
                       "emnist_lr": 400, "synthetic_lr": 400}[args.preset]
    if args.eval_every is None:
        args.eval_every = 5 if args.preset == "northstar" else 25
    if args.label_noise is None:
        args.label_noise = 0.75 if args.preset == "stackoverflow_nwp" else 0.1
    if args.preset in ("mnist_lr", "femnist_cnn", "shakespeare_rnn",
                       "fed_cifar100", "stackoverflow_nwp",
                       "emnist_lr", "synthetic_lr"):
        run_cross_device(args)
        return

    args.num_train = args.num_train or 50000
    args.num_test = args.num_test or 10000
    args.epochs = 20 if args.epochs is None else args.epochs
    suffix = ("" if args.model == "resnet56" else f"_{args.model}") + (
        "" if args.num_classes == 10 else f"_c{args.num_classes}")
    args.out = args.out or f"CONVERGENCE_r05{suffix}.json"
    ceiling = 1.0 - args.label_noise
    target = 0.9 * ceiling

    runs = {}
    wants = {"both": ["homo", "hetero"], "iid": ["homo"],
             "noniid": ["hetero"]}[args.partitions]
    for partition in wants:
        tag = "iid" if partition == "homo" else "noniid_lda0.5"
        hist, wall, med_s, cfg, resumed_from = run_northstar_once(
            partition, args, f"[{tag}]"
        )
        evals = [h for h in hist if "test_acc" in h]
        runs[tag] = {
            "partition": ("IID (homo)" if partition == "homo"
                          else "LDA alpha=0.5"),
            "final_test_acc": evals[-1]["test_acc"] if evals else None,
            "rounds_to_target": rounds_to_target(hist, target),
            "wall_clock_s": round(wall, 1),
            # rounds run IN THIS PROCESS (a resumed run does fewer)
            "wall_clock_per_round_s": round(wall / max(1, len(hist)), 2),
            "steady_state_s_per_round_median": (
                round(med_s, 2) if med_s is not None else None
            ),
            # a resumed process only holds post-resume history: the
            # trajectory below starts at this round and rounds_to_target
            # may miss an earlier first-crossing — rebuild the complete
            # artifact from the streamed logs (convergence_from_log.py)
            # when this is set
            **({"resumed_from_round": resumed_from,
                "trajectory_truncated_before_resume": True}
               if resumed_from else {}),
            "trajectory": trajectory_rows(hist),
        }
        # incremental write after EVERY partition: a multi-hour two-run
        # session that dies mid-second-run must not lose the first run's
        # on-chip evidence
        write_artifact(args.out + ".partial", {"runs": dict(runs)},
                       {"partial_after": tag})

    artifact = {**northstar_metadata(
        noise=args.noise, label_noise=args.label_noise,
        epochs=args.epochs, rounds=args.rounds,
        num_train=args.num_train, num_test=args.num_test,
        augment=bool(args.augment), smooth_sigma=args.smooth_sigma,
        flip_symmetric=bool(args.flip_symmetric), model=args.model,
        num_classes=args.num_classes,
    ), "runs": runs}
    if {"iid", "noniid_lda0.5"} <= set(runs):
        artifact["comparison"] = build_comparison(runs)
    write_artifact(args.out, artifact, {
        t: {"final": r["final_test_acc"], "rtt": r["rounds_to_target"],
            "s_per_round": r["wall_clock_per_round_s"]}
        for t, r in runs.items()})
    cleanup_partial(args.out)


def run_cross_device(args):
    """Cross-device presets: the reference's sampled-cohort benchmark
    rows (``mnist_lr``: MNIST + LR, 1000 clients, README.md:12;
    ``femnist_cnn``: FEMNIST + CNN_DropOut, 3400 clients, README.md:54)
    on matched synthetic stand-ins, via the ``run_fused_sampled``
    scheduled-cohort fast path."""
    if args.num_train is not None or args.num_test is not None:
        raise SystemExit(
            "--num-train/--num-test apply to the northstar preset only "
            "(the cross-device presets follow the reference's sizing)"
        )
    spec = {"mnist_lr": _mnist_lr_spec,
            "femnist_cnn": _femnist_cnn_spec,
            "shakespeare_rnn": _shakespeare_rnn_spec,
            "fed_cifar100": _fed_cifar100_spec,
            "stackoverflow_nwp": _stackoverflow_nwp_spec,
            "emnist_lr": _emnist_lr_spec,
            "synthetic_lr": _synthetic_lr_spec}[args.preset](args)
    run_sampled_preset(args, spec)


def _mnist_lr_spec(args):
    """Reference row ``benchmark/README.md:12``: MNIST + LR, 1000
    power-law clients, 10/round, SGD lr 0.03, E=1, batch 10,
    >75 @ >100 rounds."""
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.data.mnist import load_mnist
    from fedml_tpu.models.linear import logistic_regression

    cfg = FedAvgConfig(
        num_clients=1000, clients_per_round=10, comm_rounds=args.rounds,
        epochs=1 if args.epochs is None else args.epochs, batch_size=10,
        client_optimizer="sgd", lr=0.03,
        frequency_of_the_test=args.eval_every, seed=0,
    )
    ds = load_mnist(num_clients=1000, partition="power_law",
                    standin_label_noise=args.label_noise)
    return {
        "tag": "mnist_lr",
        "standin_rev": 4,
        "out": "CONVERGENCE_r05_mnist_lr.json",
        "cfg": cfg,
        "ds": ds,
        "bundle": logistic_regression(784, 10),
        "model_desc": "logistic_regression(784, 10)",
        "experiment": "cross-device convergence (synthetic MNIST stand-in)",
        "reference_target": {
            "dataset": "MNIST LEAF power-law (real, unavailable offline)",
            "acc": ">75", "rounds": ">100",
            "source": "/root/reference/benchmark/README.md:12",
        },
        # ">75" on real MNIST (ceiling ~1.0): ceiling-relative analogue
        "target_frac": 0.75,
    }


def _femnist_cnn_spec(args):
    """Reference row ``benchmark/README.md:54``: Federated EMNIST +
    CNN (2 conv + 2 FC = CNN_DropOut), 3400 power-law clients, 10/round,
    SGD lr 0.1, E=1, batch 20, 84.9 @ >1500 rounds.

    ONE documented deviation: lr .03 instead of the row's .1.  Measured
    on the real chip (r4): lr .1 NaN'd within round 0 on the stand-in
    even at the real dataset's pixel mean/std, because the Gaussian
    stand-in's variance is PATCH-DENSE (every 5×5 conv patch carries
    σ≈.33 signal) while real FEMNIST ink is sparse — most real patches
    are constant background, so real per-patch gradients are far
    smaller at the same global pixel moments.  CPU bisect: epoch-3 mean
    loss 6.12 (diverging) at .1, 1.80 at .03, 1.10 at .01 — .03 is the
    largest stable step.  All other knobs are reference-exact."""
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.data.emnist import load_femnist
    from fedml_tpu.models.cnn import cnn_dropout

    cfg = FedAvgConfig(
        num_clients=3400, clients_per_round=10, comm_rounds=args.rounds,
        epochs=1 if args.epochs is None else args.epochs, batch_size=20,
        client_optimizer="sgd", lr=0.03,
        frequency_of_the_test=args.eval_every, seed=0,
    )
    ds = load_femnist(num_clients=3400, only_digits=False,
                      standin_label_noise=args.label_noise,
                      standin_max_clients=3400)
    return {
        "standin_rev": 4,
        "deviations": {
            "lr": "0.03 vs the reference row's 0.1 — the row lr "
                  "diverges on the patch-dense Gaussian stand-in "
                  "(measured NaN at round 0 on the real chip even at "
                  "matched pixel mean/std; real FEMNIST ink is sparse, "
                  "so its per-patch gradients are smaller). Largest "
                  "stable step from a CPU bisect (.1 diverges, .03 "
                  "learns)."},
        "tag": "femnist_cnn",
        "out": "CONVERGENCE_r05_femnist_cnn.json",
        "cfg": cfg,
        "ds": ds,
        "bundle": cnn_dropout(only_digits=False),
        "model_desc": "CNN_DropOut (2 conv + 2 FC, 62 classes)",
        "experiment": ("cross-device convergence "
                       "(synthetic FEMNIST stand-in, 3400 clients)"),
        "reference_target": {
            "dataset": "Federated EMNIST TFF h5 (real, unavailable offline)",
            "acc": "84.9", "rounds": ">1500",
            "source": "/root/reference/benchmark/README.md:54",
        },
        # 84.9 on real FEMNIST (ceiling ~1.0): ceiling-relative analogue
        "target_frac": 0.849,
    }


def _shakespeare_rnn_spec(args):
    """Reference row ``benchmark/README.md:56``: Shakespeare (LEAF
    realistic partition) + RNN (2 LSTM + 1 FC), 715 clients, 10/round,
    SGD lr 1.0, E=1, batch 4, 56.9 @ >1200 rounds.  The stand-in is the
    peaked Markov chain (``data/shakespeare.py _synthetic_text``):
    --label-noise is reused as the chain's jump rate η, giving the
    documented Bayes next-char ceiling (1-η) + η/86."""
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.data.shakespeare import VOCAB_SIZE, load_shakespeare
    from fedml_tpu.models.rnn import rnn_shakespeare

    ds = load_shakespeare(num_clients=715, windows_per_client=64,
                          standin_peak_eta=args.label_noise,
                          standin_test_windows=2000)
    cfg = FedAvgConfig(
        # real LEAF json ignores the stand-in kwargs and brings its own
        # user count — cfg must follow the DATASET or cohort sampling
        # would draw client ids the partition doesn't hold
        num_clients=ds.num_clients, clients_per_round=10,
        comm_rounds=args.rounds,
        epochs=1 if args.epochs is None else args.epochs, batch_size=4,
        client_optimizer="sgd", lr=1.0,
        frequency_of_the_test=args.eval_every, seed=0,
    )
    eta = args.label_noise
    return {
        "tag": "shakespeare_rnn",
        "out": "CONVERGENCE_r05_shakespeare_rnn.json",
        "cfg": cfg,
        "ds": ds,
        "bundle": rnn_shakespeare(),
        "model_desc": "rnn_shakespeare (embed 8 + 2xLSTM(256) + FC, "
                      "90-symbol vocab)",
        "experiment": ("cross-device convergence "
                       "(peaked-Markov Shakespeare stand-in, 715 clients)"),
        "reference_target": {
            "dataset": "Shakespeare LEAF (real, unavailable offline)",
            "acc": "56.9", "rounds": ">1200",
            "source": "/root/reference/benchmark/README.md:56",
        },
        # 56.9 on real Shakespeare (~1.0-style ceiling-relative analogue)
        "target_frac": 0.569,
        # honest stand-in description: shard SIZES are heterogeneous
        # (lognormal, mirroring LEAF), the text DISTRIBUTION is one
        # shared chain — iid across clients, unlike real LEAF roles
        "partition": "lognormal shard sizes, iid shared-chain text "
                     "(stand-in; no distributional heterogeneity)",
        # Bayes next-char accuracy of the peaked chain, NOT 1-eta
        "ceiling": (1.0 - eta) + eta / (VOCAB_SIZE - 4),
        # the --label-noise flag is the chain's JUMP RATE here (no
        # labels are flipped); record it under an accurate key
        "hardness_knob": "standin_markov_jump_eta",
    }


def _fed_cifar100_spec(args):
    """Reference row ``benchmark/README.md:55``: fed_CIFAR100 (TFF
    natural 500-client partition) + ResNet-18-GN, 10/round, SGD lr 0.1,
    E=1, batch 20, 44.7 @ >4000 rounds.  The reference trains on
    normalized 24×24 crops with crop+flip
    (``fed_cifar100/utils.py:8-26``); the stand-in's unit-variance
    features already sit at that scale, and the preset trains with the
    same crop+flip (no cutout — the reference recipe has none here).
    The default horizon is the reference's full 4000 rounds (r4 stopped
    at a declared-truncated 600; r5 resumed that checkpoint to the full
    horizon — the 600→4000 extension is why the config stamp excludes
    --rounds)."""
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.data.augment import make_image_augment
    from fedml_tpu.data.emnist import load_fed_cifar100
    from fedml_tpu.models.resnet_gn import resnet18_gn

    ds = load_fed_cifar100(num_clients=500,
                           standin_label_noise=args.label_noise,
                           standin_natural_stats=True)
    if "standin" not in ds.name:
        # the real TFF h5 path returns raw 32×32 /255 images; the
        # reference recipe (32→24 crop + Normalize, utils.py:8-26) is
        # applied by the experiments dispatcher, not this preset —
        # training resnet18_gn(24) on un-normalized 32×32 would neither
        # run nor mean anything
        raise SystemExit(
            "real fed_cifar100 h5 detected: this convergence preset "
            "targets the offline stand-in; run the real dataset via "
            "experiments/run.py --dataset fed_cifar100 instead")
    cfg = FedAvgConfig(
        num_clients=ds.num_clients, clients_per_round=10,
        comm_rounds=args.rounds,
        epochs=1 if args.epochs is None else args.epochs, batch_size=20,
        client_optimizer="sgd", lr=0.1,
        frequency_of_the_test=args.eval_every, compute_dtype="bf16",
        seed=0,
    )
    return {
        "tag": "fed_cifar100",
        "out": "CONVERGENCE_r05_fed_cifar100.json",
        "cfg": cfg,
        "ds": ds,
        "bundle": resnet18_gn(num_classes=100, image_size=24),
        "model_desc": "ResNet-18-GN (GroupNorm, 24x24 input)",
        "experiment": ("cross-device convergence "
                       "(synthetic fed-CIFAR100 stand-in, 500 clients)"),
        "reference_target": {
            "dataset": "fed_CIFAR100 TFF h5 (real, unavailable offline)",
            "acc": "44.7", "rounds": ">4000",
            "source": "/root/reference/benchmark/README.md:55",
        },
        "target_frac": 0.447,
        "partition": "homo, 100 samples/client (TFF natural-partition "
                     "analogue)",
        # reference recipe: RandomCrop(24, pad implied by 32->24 crop)
        # + flip + Normalize; the stand-in is generated at 24x24, so
        # crop uses the same pad-4 shift convention as cifar_augment
        "augment_fn": make_image_augment(pad=4, flip=True, cutout=None),
    }


def _emnist_lr_spec(args):
    """Reference row ``benchmark/README.md:13``: Federated EMNIST + LR,
    200 power-law clients, 10/round, SGD lr 0.003, E=1, batch 10,
    10~40 @ >200 rounds.  The row publishes a BAND, not a point: the
    only level it guarantees is the band's floor (10), so
    rounds_to_target pre-declares THAT, and the artifact additionally
    reports where the final accuracy lands relative to the full band.
    Same 62-class FEMNIST stand-in as the femnist_cnn row (rev-4
    mean+std calibration); a linear model on the patch-dense stand-in
    is stable at the reference lr, so no lr deviation is needed."""
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.data.emnist import load_femnist
    from fedml_tpu.models.linear import logistic_regression

    cfg = FedAvgConfig(
        num_clients=200, clients_per_round=10, comm_rounds=args.rounds,
        epochs=1 if args.epochs is None else args.epochs, batch_size=10,
        client_optimizer="sgd", lr=0.003,
        frequency_of_the_test=args.eval_every, seed=0,
    )
    ds = load_femnist(num_clients=200, only_digits=False,
                      standin_label_noise=args.label_noise,
                      standin_max_clients=200)
    return {
        "tag": "emnist_lr",
        "standin_rev": 4,
        "out": "CONVERGENCE_r05_emnist_lr.json",
        "cfg": cfg,
        "ds": ds,
        "bundle": logistic_regression(28 * 28, 62),
        "model_desc": "logistic_regression(784, 62)",
        "experiment": ("cross-device convergence "
                       "(synthetic FEMNIST stand-in, 200 clients, LR)"),
        "reference_target": {
            "dataset": "Federated EMNIST TFF h5 (real, unavailable "
                       "offline)",
            "acc": "10~40 (band)", "rounds": ">200",
            "source": "/root/reference/benchmark/README.md:13",
        },
        # floor of the published 10~40 band (the level the row
        # guarantees), ceiling-relative analogue; the band's top is
        # recorded so the final accuracy can be read against it
        "target_frac": 0.10,
        "deviations": {
            "target": "the reference publishes a 10~40 BAND; "
                      "rounds_to_target pre-declares its floor (0.10 x "
                      "ceiling). Measured r5: the run passes THROUGH "
                      "the ceiling-relative band (rounds 25-125) and "
                      "keeps climbing to ~0.84 — the linearly-separable "
                      "prototype stand-in cannot reproduce real "
                      "EMNIST's linear-capacity plateau"},
    }


def _synthetic_lr_spec(args):
    """Reference row ``benchmark/README.md:14``: Synthetic(α,β) + LR,
    30 clients, 10/round, SGD lr 0.01, E=1, batch 10, >60 @ >200
    rounds.  UNLIKE the other rows this needs NO stand-in: the
    reference's dataset is itself generated (the LEAF/FedProx
    Synthetic(1,1) process — client-specific softmax weights
    W_k ~ N(u_k, 1), u_k ~ N(0, α); features x ~ N(v_k, diag(j^-1.2)),
    v_k ~ N(B_k, 1), B_k ~ N(0, β); lognormal shard sizes), and
    ``data/synthetic.synthetic_alpha_beta`` implements the same
    generative family — so the run's accuracy is DIRECTLY comparable
    to the published >60 with real distributional heterogeneity
    (every client owns a different W_k)."""
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.data.synthetic import synthetic_alpha_beta
    from fedml_tpu.models.linear import logistic_regression

    ds = synthetic_alpha_beta(alpha=1.0, beta=1.0, num_clients=30)
    cfg = FedAvgConfig(
        num_clients=30, clients_per_round=10, comm_rounds=args.rounds,
        epochs=1 if args.epochs is None else args.epochs, batch_size=10,
        client_optimizer="sgd", lr=0.01,
        frequency_of_the_test=args.eval_every, seed=0,
    )
    return {
        "tag": "synthetic_lr",
        "out": "CONVERGENCE_r05_synthetic_lr.json",
        "cfg": cfg,
        "ds": ds,
        "bundle": logistic_regression(60, 10),
        "model_desc": "logistic_regression(60, 10)",
        "experiment": ("cross-device convergence (Synthetic(1,1) — the "
                       "reference's own generative dataset family, no "
                       "stand-in)"),
        "reference_target": {
            "dataset": "Synthetic(1,1), LEAF/FedProx generator "
                       "(re-implemented; directly comparable)",
            "acc": ">60", "rounds": ">200",
            "source": "/root/reference/benchmark/README.md:14",
        },
        # absolute: the dataset is the real generative family, ceiling 1.0
        "target_frac": 0.60,
        "ceiling": 1.0,
        "has_target": True,
        "partition": "natural (client-specific W_k; lognormal sizes)",
    }


def _stackoverflow_nwp_spec(args):
    """Reference row ``benchmark/README.md:57``: StackOverflow NWP
    (TFF natural partition, **342,477 clients**) + RNN (1 LSTM(670),
    embed 96), 50/round, SGD lr 10^-0.5, E=1, batch 16,
    19.5 @ >1500 rounds — the one published row that stresses
    cross-device machinery at real population scale: host sampling
    from 342k-client metadata + scheduled-cohort packing
    (VERDICT r4 missing #1).

    Stand-in: the calibrated peaked-Markov methodology
    (``data/stackoverflow._peaked_chain``) with jump rate η = 0.75 by
    default and ZIPF(1.1) jump targets, so the Bayes next-token
    ceiling ≈ 0.2501 sits JUST ABOVE the reference row's 19.5 — the
    pre-declared target is the row's ABSOLUTE accuracy (0.195 ≈ 78% of
    ceiling), keeping rounds-to-target a genuine signal rather than an
    early crossing on a saturating task.  The zipf unigram is the
    learnability-critical refinement: a UNIFORM-unigram chain was
    measured unlearnable at the row's SGD lr (100-round chip pilots:
    loss 9.211→9.207 at lr 10^-0.5, 3x faster but still glacial at
    1.0, NaN at 3.0 — every one of 10k classes needs its own
    averaged-over-clients signal), while real text's zipf head gives
    frequent words many sightings per round, the same head start real
    NWP training has.  Per-token CE/accuracy over all 20 positions
    (the reference NWP convention); the stand-in emits full windows,
    so there are no pad positions to mask."""
    from fedml_tpu.algorithms.fedavg import FedAvgConfig
    from fedml_tpu.data.stackoverflow import load_stackoverflow_nwp
    from fedml_tpu.models.rnn import rnn_stackoverflow

    import resource

    eta = args.label_noise
    t0 = time.time()
    ds = load_stackoverflow_nwp(num_clients=342477,
                                standin_peak_eta=eta)
    gen_s = time.time() - t0
    host_note = {
        "what": "342,477-client population on ONE host: sampling reads "
                "metadata only (host_sample_ids is O(K log N)); the "
                "scheduled-cohort driver ships just the 50-client "
                "cohort block per round",
        "standin_generation_s": round(gen_s, 1),
        "train_array_bytes": int(ds.train_x.nbytes + ds.train_y.nbytes),
        "peak_rss_gb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 1),
        "client_metadata_entries": ds.num_clients,
    }
    cfg = FedAvgConfig(
        num_clients=ds.num_clients, clients_per_round=50,
        comm_rounds=args.rounds,
        epochs=1 if args.epochs is None else args.epochs, batch_size=16,
        client_optimizer="sgd", lr=10 ** -0.5,
        frequency_of_the_test=args.eval_every, seed=0,
    )
    # empirical Bayes ceiling of the generated chain (zipf jumps make
    # the additive eta*E[q(perm(cur))] term chain-dependent); only the
    # stand-in branch sets it — with the real h5 present this preset's
    # absolute-target calibration doesn't apply (same guard as the
    # fed_cifar100 spec's real-data path)
    ceiling = getattr(ds, "standin_bayes_ceiling", None)
    if ceiling is None:
        raise SystemExit(
            "real stackoverflow h5 detected: this convergence preset "
            "targets the calibrated offline stand-in; run the real "
            "dataset via experiments/run.py --dataset stackoverflow_nwp")
    return {
        "tag": "stackoverflow_nwp",
        "out": "CONVERGENCE_r05_stackoverflow_nwp.json",
        "cfg": cfg,
        "ds": ds,
        "bundle": rnn_stackoverflow(),
        "model_desc": "RNNStackOverflow (embed 96 + LSTM(670) + "
                      "2 dense, 10004-way per-token head)",
        "experiment": ("cross-device convergence at population scale "
                       "(peaked-Markov StackOverflow NWP stand-in, "
                       "342,477 clients, 50/round)"),
        "reference_target": {
            "dataset": "StackOverflow NWP TFF h5 (real, unavailable "
                       "offline)",
            "acc": "19.5", "rounds": ">1500",
            "source": "/root/reference/benchmark/README.md:57",
        },
        # ABSOLUTE-target calibration: target = 0.195 exactly (the
        # reference row's number); expressed as a fraction of the
        # chain's Bayes ceiling for the shared target machinery
        "target_frac": 0.195 / ceiling,
        "ceiling": ceiling,
        "partition": "clipped-lognormal shard sizes [16, 512], iid "
                     "shared-chain text (stand-in; no distributional "
                     "heterogeneity)",
        "hardness_knob": "standin_markov_jump_eta",
        "host_note": host_note,
        "deviations": {
            "shard_sizes": "stand-in mean ~130 sequences/client vs the "
                           "real TFF partition's ~397 (135.8M examples "
                           "/ 342,477 users): per-round token volume "
                           "is ~1/3 of the real row's — full scale "
                           "would cost ~13 GB host generation per run"},
    }


def check_config_stamp(ckdir: str, stamp: dict,
                       legacy_fill: dict = None) -> None:
    """One stamp policy for BOTH preset families: the stamp holds every
    knob that changes the training dynamics a checkpoint encodes; the
    horizon (``--rounds``) is deliberately NOT in it — per-round
    randomness is ``fold_in``-keyed on the absolute round index, so a
    state at round R is identical whether the run was launched with
    ``--rounds 600`` or ``4000``, and extending a finished run to a
    longer horizon (fed_cifar100 600→4000) is exactly the resume use
    case.  Stamps written by the pre-r5 code carried a legacy
    ``rounds`` key (dropped — it never affected dynamics) and lacked
    keys later ADDED to the stamp (``legacy_fill`` maps those to the
    value every pre-r5 run implicitly had, e.g. model=resnet56);
    migrated stamps are rewritten in the new format."""
    stamp_path = os.path.join(ckdir, "config_stamp.json")
    os.makedirs(ckdir, exist_ok=True)

    def write_atomic():
        # a session can die at any point — never truncate a good stamp
        # in place
        with open(stamp_path + ".tmp", "w") as f:
            json.dump(stamp, f)
        os.replace(stamp_path + ".tmp", stamp_path)

    if os.path.exists(stamp_path):
        prior = json.load(open(stamp_path))
        legacy = prior.pop("rounds", None)
        for k, v in (legacy_fill or {}).items():
            if k not in prior:
                prior[k] = v
                legacy = True
        if prior != stamp:
            raise SystemExit(
                f"checkpoint dir {ckdir} holds a run with a different "
                f"config ({prior} != {stamp}); pass --checkpoint-dir "
                "'' or remove the directory")
        if legacy is not None:
            write_atomic()
    else:
        write_atomic()


def run_sampled_preset(args, spec):
    """Shared driver for the sampled-cohort (cross-device) benchmark
    rows: ``run_fused_sampled`` fast path (the host pre-draws each
    chunk's cohorts, one device call per chunk — the per-round dispatch
    loop measured 6.6 s/round in round 3, almost all host
    overhead), checkpoint/resume, and a resume-merged streamed
    artifact."""
    from fedml_tpu.algorithms.fedavg import FedAvgSimulation
    from fedml_tpu.core.checkpoint import CheckpointManager

    tag, cfg, ds = spec["tag"], spec["cfg"], spec["ds"]
    out = args.out or spec["out"]
    ceiling = spec.get("ceiling", 1.0 - args.label_noise)
    target = spec["target_frac"] * ceiling
    has_target = spec.get("has_target", False) or "standin" in spec["ds"].name
    sim = FedAvgSimulation(spec["bundle"], ds, cfg,
                           augment_fn=spec.get("augment_fn"))

    # checkpoint/resume mirrors the north-star preset: multi-hundred-
    # round horizons outlive a session.
    # standin_rev chronicles each PRESET's stand-in DATA changes a
    # same-shape checkpoint can't detect (specs carry their own rev so
    # one dataset's recalibration doesn't invalidate another's
    # checkpoints): mnist/femnist are at rev 4 — 2 = pixel-scale
    # matching, 3 = FEMNIST moved to the raw TFF white-background
    # scale, 4 = mean+std affine matching (match_pixel_moments;
    # variance-only placement of the white-background second moment
    # NaN'd femnist at the reference lr).  A checkpoint trained on
    # differently-scaled gradients must never resume into a rescaled
    # run.  The .partial-merge stamp is the SAME dict (advisor r4:
    # dropping epochs let a stale .partial from a different --epochs
    # merge into a resumed run); stamp policy, incl. why the horizon
    # is excluded, lives in check_config_stamp's docstring.
    stamp = {"label_noise": args.label_noise,
             "epochs": cfg.epochs, "lr": cfg.lr, "seed": 0,
             "standin_rev": spec.get("standin_rev", 1)}
    stamp_for_partial = stamp
    mgr = None
    start_round = 0
    if getattr(args, "checkpoint_dir", ""):
        ckdir = os.path.join(args.checkpoint_dir, tag)
        check_config_stamp(ckdir, stamp)
        mgr = CheckpointManager(ckdir, max_to_keep=2)
        if mgr.latest_step() is not None:
            sim.state = mgr.restore(like=sim.state)
            start_round = int(sim.state.round_idx)
            if start_round >= args.rounds:
                raise SystemExit(
                    f"checkpoint at round {start_round} >= --rounds "
                    f"{args.rounds}: already completed — remove the "
                    "checkpoint dir to start fresh")
            print(f"[{tag}] resumed from checkpoint at round "
                  f"{start_round}", flush=True)

    # resume-correct trajectory: the in-process history only holds
    # post-resume rounds, so eval rows are streamed into a .partial
    # artifact and a resumed session prepends the prior partial's
    # pre-resume rows — rounds_to_target and wall_clock then cover the
    # WHOLE run, not just the surviving session (advisor: a target first
    # crossed before the crash must not be reported as later/None)
    prior_traj: list = []
    prior_wall = 0.0
    if start_round and os.path.exists(out + ".partial"):
        prior = json.load(open(out + ".partial"))
        if prior.get("stamp") == stamp_for_partial:
            prior_traj = [r for r in prior.get("trajectory", [])
                          if r["round"] < start_round]
            prior_wall = prior.get("wall_clock_s", 0.0)
        else:
            # a resumed run whose pre-resume rows are silently dropped
            # mis-reports rounds_to_target (the exact bug the merge
            # exists to fix) — make the skip LOUD (review r5); legacy
            # pre-r5 partials (stamp carried 'rounds', lacked 'epochs')
            # also land here rather than re-opening the epochs hole
            print(f"[{tag}] WARNING: {out}.partial stamp "
                  f"{prior.get('stamp')} != {stamp_for_partial}; "
                  "pre-resume trajectory rows will NOT be merged — "
                  "rounds_to_target/wall_clock cover only this session",
                  flush=True)

    t0 = time.time()

    def merged_traj(hist_now):
        return prior_traj + trajectory_rows(hist_now)

    def log_fn(m):
        if "test_acc" in m:
            line = {k: round(v, 5) if isinstance(v, float) else v
                    for k, v in m.items()}
            line["elapsed_s"] = round(time.time() - t0, 1)
            print(f"[{tag}] {json.dumps(line)}", flush=True)
            # save ONLY when this row is the fused chunk's last round:
            # sim.state already sits at end-of-chunk while log_fn
            # replays the chunk's rows, so labeling that state with an
            # intermediate round would make resume re-apply rounds the
            # state already contains (review r4)
            if mgr is not None and m["round"] + 1 == int(
                sim.state.round_idx
            ):
                mgr.save(m["round"] + 1, sim.state)
            with open(out + ".partial", "w") as f:
                json.dump({"stamp": stamp_for_partial,
                           "trajectory": merged_traj(sim.history),
                           "wall_clock_s": round(
                               prior_wall + time.time() - t0, 1)}, f)

    # fused chunks: default 25 rounds/device-call; an EXPLICIT
    # --rounds-per-call (including 1) is honored as given
    rpc = 25 if args.rounds_per_call is None else args.rounds_per_call
    hist = sim.run_fused_sampled(rounds=args.rounds - start_round,
                                 log_fn=log_fn, rounds_per_call=rpc)
    full_traj = merged_traj(hist)
    artifact = {
        "experiment": spec["experiment"],
        "reference_target": spec["reference_target"],
        "dataset_loaded": ds.name,
        # the noise ceiling exists ONLY for the synthetic stand-in —
        # the loaders never modify real on-disk data, so claiming an
        # irreducible-error ceiling there would misdescribe the run
        **({"hardness": {
                spec.get("hardness_knob",
                         "standin_label_noise"): args.label_noise,
                "accuracy_ceiling": round(ceiling, 4),
                # reference accuracy is on a ~1.0-ceiling real dataset:
                # the ceiling-relative analogue, pre-declared
                "target_for_rounds_to_target": round(target, 4)}}
           if "standin" in ds.name else {}),
        # a preset whose dataset IS the reference's generative family
        # (synthetic_lr) declares its target without a stand-in ceiling
        **({"pre_declared_target": round(target, 4)}
           if has_target and "standin" not in ds.name else {}),
        **({"host_note": spec["host_note"]} if "host_note" in spec else {}),
        "config": {
            "model": spec["model_desc"],
            "clients": cfg.num_clients,
            "clients_per_round": cfg.clients_per_round,
            "partition": spec.get("partition", "power_law"),
            "optimizer": "sgd", "lr": cfg.lr,
            "local_epochs": cfg.epochs, "batch_size": cfg.batch_size,
            "rounds": args.rounds,
            "driver": ("run_fused_sampled (scheduled cohorts, "
                       f"{min(rpc, args.eval_every)} rounds/device call"
                       " — chunks end on eval rounds)"),
            # stand-in-specific departures from the reference row,
            # stated in the artifact itself (not just the code)
            **({"deviations_from_reference_row": spec["deviations"]}
               if "deviations" in spec else {}),
        },
        # merged across crash/resume sessions via the .partial sidecar
        "wall_clock_s": round(prior_wall + time.time() - t0, 1),
        "final_test_acc": (full_traj[-1]["test_acc"] if full_traj else None),
        "rounds_to_target": (rounds_to_target(full_traj, target)
                             if has_target else None),
        **({"resumed_from_round": start_round,
            "pre_resume_rounds_recovered": len(prior_traj)}
           if start_round else {}),
        "trajectory": full_traj,
    }
    write_artifact(out, artifact,
                   {"final_test_acc": artifact["final_test_acc"],
                    "rounds_to_target": artifact["rounds_to_target"]})
    cleanup_partial(out)


if __name__ == "__main__":
    main()
