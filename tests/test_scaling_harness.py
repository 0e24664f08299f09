"""CI coverage for tools/bench_scaling.py (VERDICT r1 #3): the chips-mode
weak-scaling ladder must run end-to-end on the faked CPU mesh and emit
well-formed efficiency points, and the clients-mode fused driver must
report throughput per point.

The conftest already forces the 8-device CPU mesh (jax is started), so
the harness's own --platform cpu env mutation is a no-op here.
"""

import json
import os
import sys

import numpy as np

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir, "tools")
)

import bench_scaling  # noqa: E402


def _run(capsys, argv):
    old = sys.argv
    sys.argv = ["bench_scaling.py"] + argv
    try:
        bench_scaling.main()
    finally:
        sys.argv = old
    out = capsys.readouterr().out.strip().splitlines()
    return [json.loads(line) for line in out if line.startswith("{")]


def test_chips_mode_ladder(capsys):
    rows = _run(capsys, [
        "--mode", "chips", "--platform", "cpu", "--devices", "8",
        "--model", "mlp",
        "--rounds", "1", "--steps", "1", "--batch", "2",
    ])
    assert [r["devices"] for r in rows] == [1, 2, 4, 8]
    assert rows[0]["efficiency"] == 1.0
    for r in rows:
        assert r["metric"] == "weak_scaling_round_time"
        assert r["value"] > 0
        # STRUCTURAL check only: efficiency is finite and positive.
        # A numeric upper bound (r2: <= 1.5) is a wall-clock RATIO on a
        # loaded 1-core box and flaked the gating suite (VERDICT r2
        # Weak #4) — faked-mesh CPU timings validate the harness shape,
        # not ICI scaling, so bounding them asserts nothing real.
        assert np.isfinite(r["efficiency"]) and r["efficiency"] > 0


def test_clients_mode_points(capsys):
    rows = _run(capsys, [
        "--mode", "clients", "--platform", "cpu", "--model", "mlp",
        "--rounds", "1", "--rounds-per-call", "2",
        "--steps", "1", "--batch", "2",
    ])
    assert [r["clients"] for r in rows] == [1, 2, 4, 8, 16]
    for r in rows:
        assert r["metric"] == "clients_per_chip_throughput"
        assert r["value"] > 0
        assert r["rounds_per_call"] == 2


def test_convergence_median_round_seconds():
    """Burst-aware steady-state median (tools/convergence_run.py):
    chunked run_fused logging must not collapse the median to ~0, and
    the compile-laden first burst is excluded."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir, "tools"))
    from convergence_run import median_round_seconds

    # rpc=1: [0, compile+r0, then 35s rounds with one 600s stall]
    stamps = [0.0, 147.0, 182.5, 218.0, 253.5, 853.5, 889.0]
    assert abs(median_round_seconds(stamps) - 35.5) < 0.01

    # rpc=3: rows logged in bursts of 3 (same stamp); 3 rounds per 105s
    t, stamps = 0.0, [0.0]
    stamps += [150.0] * 3            # compile + first chunk (excluded)
    for chunk in range(4):
        t = 150.0 + (chunk + 1) * 105.0
        stamps += [t] * 3
    med = median_round_seconds(stamps)
    assert abs(med - 35.0) < 0.01, med

    assert median_round_seconds([0.0]) is None


def test_from_log_merges_resumed_continuation():
    """A resumed continuation log has FEWER rows but LATER rounds than
    the pre-crash log; the merge must keep the post-resume trajectory
    (later rounds win on overlap) instead of picking by row count
    (advisor r3)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir, "tools"))
    from convergence_from_log import pick_runs, summarize

    def rows(rounds, accs, dt=10.0):
        return [{"round": r, "test_acc": a, "test_loss": 1.0,
                 "elapsed_s": (i + 1) * dt}
                for i, (r, a) in enumerate(zip(rounds, accs))]

    # pre-crash: rounds 0..6 (7 rows); continuation resumes at 4: 4..9
    pre = rows(range(0, 7), [0.1, 0.2, 0.3, 0.4, 0.45, 0.5, 0.55])
    cont = rows(range(4, 10), [0.46, 0.51, 0.56, 0.6, 0.65, 0.7])
    merged = pick_runs([("pre.log", {"iid": pre}),
                        ("cont.log", {"iid": cont})])
    out = summarize(merged["iid"], target=0.6)
    assert out["rounds_completed"] == 10
    assert out["final_test_acc"] == 0.7
    # overlap rounds 4-6 must hold the continuation's rerun values
    traj = {t["round"]: t["test_acc"] for t in out["trajectory"]}
    assert traj[4] == 0.46 and traj[6] == 0.56
    assert out["rounds_to_target"] == 7
    # wall-clock sums the per-segment elapsed, never mixes clocks
    assert out["wall_clock_s"] == 70.0 + 60.0


def test_hlo_allreduce_bytes_pin_scaling_volume():
    """VERDICT r4 weak #3: the scaling model's per-round communication
    volume (the V in 2V(N-1)/N) must match what XLA actually emits.
    Compile the real SPMD round program on the 8-device CPU mesh and
    assert the optimized HLO's all-reduce payload equals the fp32
    variable tree plus only the handful of psum'd scalar metrics."""
    from scaling_model import measure_hlo_volume, parse_collective_bytes

    vol = measure_hlo_volume(n_devices=8, model="logreg")
    coll = vol["hlo_collective_bytes"]
    tree = vol["variable_tree_fp32_bytes"]
    ar = coll.get("all-reduce", 0)
    # psum'd scalars: weighted-sum denominator + train metrics — a few
    # f32s, never more than 64 bytes
    assert tree <= ar <= tree + 64, (tree, coll)
    # the ONLY cross-device traffic in the round is that all-reduce:
    # no all-gathers/reduce-scatters the model fails to charge for
    assert set(coll) <= {"all-reduce", "n_ops"}, coll

    # parser unit: tuple-shaped async pair counted once, done-op skipped
    fake = (
        "  %ar = (f32[10]{0}, bf16[4]{0}) all-reduce-start(...)\n"
        "  %d = (f32[10]{0}, bf16[4]{0}) all-reduce-done(%ar)\n"
        "  %ag = f32[16,8]{1,0} all-gather(f32[2,8]{1,0} %x)\n"
    )
    parsed = parse_collective_bytes(fake)
    assert parsed["all-reduce"] == 10 * 4 + 4 * 2
    assert parsed["all-gather"] == 16 * 8 * 4
    assert parsed["n_ops"] == 2


def test_build_comparison_truncated_arm():
    """ADVICE r5: arms at different horizons (the c100 noniid arm
    stopped at round 53 vs iid's 100) must be compared at the common
    min horizon and carry the truncation caveat, not silently compare
    final-vs-final across mismatched training budgets."""
    from convergence_run import build_comparison

    def run(rounds, accs, rtt=None):
        return {"final_test_acc": accs[-1], "rounds_to_target": rtt,
                "trajectory": [{"round": r, "test_acc": a,
                                "test_loss": 1.0}
                               for r, a in zip(rounds, accs)]}

    # matched horizons: plain comparison, no truncation keys
    cmp_full = build_comparison({
        "iid": run([50, 99], [0.8, 0.9], rtt=50),
        "noniid_lda0.5": run([50, 99], [0.7, 0.85], rtt=99),
    })
    assert cmp_full["final_acc_gap_iid_minus_noniid"] == 0.05
    assert "truncated_arm" not in cmp_full

    # noniid truncated at 53: compare iid's value at <=53 (0.8 from
    # round 50), NOT its round-99 final
    cmp_tr = build_comparison({
        "iid": run([50, 99], [0.8, 0.9]),
        "noniid_lda0.5": run([25, 53], [0.7, 0.85]),
    })
    assert cmp_tr["truncated_arm"] == "noniid"
    # mis-aligned cadences: each arm's ACTUAL compared round is recorded
    assert cmp_tr["compared_at_round"] == {"iid": 50, "noniid": 53}
    assert cmp_tr["horizons"] == {"iid": 99, "noniid": 53}
    assert cmp_tr["final_acc_gap_iid_minus_noniid"] == \
        round(0.8 - 0.85, 5)
    # rounds_to_target censored to the common budget: an iid crossing
    # at round 99 is NOT comparable against a 53-round arm
    cmp_rtt = build_comparison({
        "iid": run([50, 99], [0.8, 0.9], rtt=99),
        "noniid_lda0.5": run([25, 53], [0.7, 0.85], rtt=25),
    })
    assert cmp_rtt["rounds_to_target_within_common_horizon"] == \
        {"iid": None, "noniid": 25}
    assert cmp_rtt["rounds_to_target"]["iid"] == 99  # raw kept
    assert "caveat" in cmp_rtt["rounds_to_target"]

    # the longer arm has NO eval inside the truncated horizon: no
    # comparable operating point — incomplete, never a TypeError
    cmp_none = build_comparison({
        "iid": run([60, 99], [0.8, 0.9]),
        "noniid_lda0.5": run([25, 53], [0.7, 0.85]),
    })
    assert cmp_none["incomplete"] is True
    assert cmp_none["truncated_arm"] == "noniid"


def test_parse_collective_bytes_reduce_scatter_scaling():
    """ADVICE r5: a reduce-scatter's OUTPUT is V/N — the parser must
    scale it by the replica-group size so the returned number is the
    logical payload V (what the 2V(N-1)/N wire term charges), for both
    replica_groups syntaxes; an unparsable group raises instead of
    under-counting N x."""
    import pytest

    from scaling_model import parse_collective_bytes

    explicit = ('  %rs = f32[4,8]{1,0} reduce-scatter(f32[32,8]{1,0} %x), '
                'replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}\n')
    parsed = parse_collective_bytes(explicit)
    assert parsed["reduce-scatter"] == 4 * 8 * 4 * 8  # output bytes x N

    iota = ('  %rs = bf16[2,8]{1,0} reduce-scatter(bf16[8,8]{1,0} %x), '
            'replica_groups=[2,4]<=[8], dimensions={0}\n')
    parsed = parse_collective_bytes(iota)
    assert parsed["reduce-scatter"] == 2 * 8 * 2 * 4  # x group size 4

    # async -start form: the tuple signature carries (operand, output);
    # only the OUTPUT (last shape) scales — summing the tuple would
    # over-count (N+1)x
    start = ('  %rs = (f32[32,8]{1,0}, f32[4,8]{1,0}) '
             'reduce-scatter-start(f32[32,8]{1,0} %x), '
             'replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}\n')
    parsed = parse_collective_bytes(start)
    assert parsed["reduce-scatter"] == 4 * 8 * 4 * 8  # output bytes x N

    # all-gather-start's tuple is (operand_alias, output): only the
    # gathered output is the payload
    ag = ('  %ag = (f32[4,8]{1,0}, f32[32,8]{1,0}) '
          'all-gather-start(f32[4,8]{1,0} %x), dimensions={0}\n')
    assert parse_collective_bytes(ag)["all-gather"] == 32 * 8 * 4

    with pytest.raises(ValueError, match="replica_groups"):
        parse_collective_bytes(
            "  %rs = f32[4]{0} reduce-scatter(f32[32]{0} %x)\n")
