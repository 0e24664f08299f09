"""Medium-N real-process federation evidence (VERDICT r4 next #9).

The cross-device DCN-role path's only prior evidence was 2-3 client
processes on CPU (``tests/test_distributed_process.py``).  This tool
runs the SAME machinery at a medium process count: hub + server + N
client OS processes over the TCP
hub (``comm/tcp.py``), round deadline armed, one SAMPLED client
SIGKILLed mid-round — then

- pins the final global model against the compiled masked-participation
  oracle (``make_round_fn`` with the server's LOGGED participation per
  round — the inject_dropout semantics), and
- records per-round wall-clock (from the server's round-close stamps)
  next to the inproc simulation's wall-clock for the same problem.

Every process of this federation is host-side — ``launch`` starts hub,
server and per-process clients with ``JAX_PLATFORMS=cpu`` (the server
folds in fp64 numpy; only a muxer's cohort step takes the accelerator,
and this tool launches none).  The oracle below runs afterwards, in this
process, on whatever backend it has.

Usage: python tools/federation_run.py [--clients 16] [--rounds 8]
       [--out FEDERATION_r05.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--clients", type=int, default=16)
    p.add_argument("--rounds", type=int, default=8)
    p.add_argument("--round-timeout", type=float, default=60.0,
                   help="per-round deadline; generous because a 1-core "
                   "host serializes N client processes' first-round jit "
                   "compiles")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--out", default="FEDERATION_r05.json")
    args = p.parse_args()

    import numpy as np

    from fedml_tpu.experiments.distributed_fedavg import (
        _build_problem,
        launch,
    )

    env = dict(os.environ)
    env["XLA_FLAGS"] = ""

    # TWO federations: a CLEAN one (every client lives) whose round-close
    # stamps give the real per-round wall-clock, and a STRAGGLER one
    # (one sampled client SIGKILLed mid-round) whereevery round necessarily
    # closes BY deadline — the honest price of a dead sampled client
    # under the timeout policy, but useless as a wall-clock measure.
    def run_one(tag, rounds, **kw):
        npz = f"/tmp/federation_{tag}.npz"
        t0 = time.time()
        rc = launch(
            num_clients=args.clients, rounds=rounds, seed=args.seed,
            batch_size=args.batch_size, out_path=npz,
            round_timeout=args.round_timeout,
            env=env,
            timeout=300.0 + rounds * args.round_timeout, **kw,
        )
        if rc != 0:
            raise SystemExit(f"{tag} server subprocess failed rc={rc}")
        z = np.load(npz)
        log = json.loads(str(z["round_log"]))
        recs = [r for r in log if "participants" in r]
        return z, log, recs, round(time.time() - t0, 1)

    z, log, rounds, wall = run_one("clean", args.rounds)
    per_round_s = [round(b["t"] - a["t"], 3)
                   for a, b in zip(rounds, rounds[1:])]
    zs, slog, srounds, swall = run_one(
        "straggler", max(2, args.rounds // 2),
        # the LAST sampled client sleeps, then is SIGKILLed mid-round
        slow_client_delay=600.0, kill_slow_client_after=2.0,
    )

    # compiled masked-participation oracle, driven by the LOGGED
    # participants (the per-round deadline decided them, not us)
    import jax
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import ServerState, make_round_fn
    from fedml_tpu.core.types import cohort_steps_per_epoch, pack_clients

    ds, bundle, init, lu = _build_problem(seed=args.seed,
                                          num_clients=args.clients)
    steps = cohort_steps_per_epoch(ds, args.batch_size)
    pack = pack_clients(ds, list(range(args.clients)), args.batch_size,
                        steps_per_epoch=steps, seed=args.seed)
    rf = jax.jit(make_round_fn(lu))

    def oracle_err(z_, recs):
        st = ServerState(variables=init, opt_state=(),
                         round_idx=jnp.zeros((), jnp.int32),
                         key=jax.random.PRNGKey(args.seed))
        for rec in recs:
            if not rec["participants"]:
                # the server treats a zero-participant round as a no-op
                # for the MODEL but still advances round_idx
                # (fedavg_cross_device._close_round) — and clients key
                # their next round's rng on that index, so the oracle
                # must advance it too (review r5: replaying with an
                # all-zero mask would zero the model; skipping without
                # advancing would desync every later round's shuffle)
                st = st._replace(round_idx=st.round_idx + 1)
                continue
            part = np.zeros(args.clients, np.float32)
            part[[n - 1 for n in rec["participants"]]] = 1.0
            st, _ = rf(st, jnp.asarray(pack.x), jnp.asarray(pack.y),
                       jnp.asarray(pack.mask),
                       jnp.asarray(pack.num_samples), jnp.asarray(part),
                       jnp.arange(args.clients, dtype=jnp.int32))
        want = jax.tree_util.tree_leaves(st.variables)
        got = [np.asarray(z_[f"leaf_{i}"]) for i in range(len(want))]
        return max(float(np.abs(a - np.asarray(b)).max())
                   for a, b in zip(got, want))

    # threshold: f32 weighted sums accumulate order-dependent rounding
    # over N clients x R rounds; 16x8 measured ~1.6e-4 max abs on O(1)
    # weights — 5e-4 bounds that with margin while still catching any
    # REAL divergence (a missed round or client is O(1e-2))
    max_err = oracle_err(z, rounds)
    straggler_err = oracle_err(zs, srounds)
    parity_ok = max_err < 5e-4 and straggler_err < 5e-4

    # inproc comparison: same problem, same rounds, simulation driver
    from fedml_tpu.algorithms.fedavg import FedAvgConfig, FedAvgSimulation

    sim = FedAvgSimulation(bundle, ds, FedAvgConfig(
        num_clients=args.clients, clients_per_round=args.clients,
        comm_rounds=args.rounds, epochs=1, batch_size=args.batch_size,
        lr=0.1, seed=args.seed, frequency_of_the_test=10 ** 9,
    ))
    t1 = time.time()
    sim.run_fused()
    inproc_wall = time.time() - t1

    artifact = {
        "experiment": f"real-process federation: hub + server + "
                      f"{args.clients} client OS processes over the TCP "
                      "hub (clean run for wall-clock; straggler run "
                      "with one sampled client SIGKILLed mid-round)",
        "server_backend": "cpu",
        "oracle_backend": jax.devices()[0].platform,
        "host": "1-core box: client processes TIMESHARE one CPU — "
                "per-round wall is an upper bound on a real multi-host "
                "deployment's",
        "processes": args.clients + 2,
        "round_timeout_s": args.round_timeout,
        "clean_run": {
            "rounds": int(z["rounds"]),
            "round_log": log,
            "per_round_wall_s": per_round_s,
            "total_wall_s": wall,
            "oracle_max_abs_err": max_err,
        },
        "straggler_run": {
            "rounds": int(zs["rounds"]),
            "killed_client_node": args.clients,
            "round_log": slog,
            "total_wall_s": swall,
            "oracle_max_abs_err": straggler_err,
            "note": "every round necessarily closes BY the deadline "
                    "(the dead sampled client never uploads) — the "
                    "timeout policy's price, not a throughput figure",
        },
        "oracle_parity": {
            "what": "final global model vs the compiled round kernel "
                    "driven by the server's LOGGED per-round "
                    "participation (masked-psum semantics), both runs",
            "threshold": 5e-4,
            "ok": bool(parity_ok),
        },
        "inproc_comparison": {
            "driver": "FedAvgSimulation.run_fused, full participation, "
                      "same problem/rounds",
            "wall_s": round(inproc_wall, 2),
            "note": "the gap is the DCN-role price: process spawn + jax "
                    "import + per-round socket round-trips vs one "
                    "compiled program",
        },
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps({"out": args.out,
                      "clean_rounds": int(z["rounds"]),
                      "straggler_rounds": int(zs["rounds"]),
                      "parity_max_abs_err": [max_err, straggler_err],
                      "per_round_wall_s": per_round_s,
                      "inproc_wall_s": artifact["inproc_comparison"]["wall_s"]}))
    if not parity_ok:
        raise SystemExit("PARITY FAILURE vs masked oracle")


if __name__ == "__main__":
    main()
