"""Compile-only rehearsal: which depth of a transformer configuration fits one
described v5e chip through a cell's round program (on-chip-measurement guide,
section 2.3).  Nothing runs and no chip is needed; what it prints is the
compiler's own memory analysis, never a chip measurement.  The program
chooses its attention and its grouped products from ``jax.default_backend()``,
which is the CPU here: while the program is traced the tool answers "tpu" in
its place, so that what compiles is the program the cells run (the flash
kernels, ``megablox.gmm``), not the lax path of this sandbox.

    JAX_PLATFORMS=cpu python benchmark/tools/fit_depth.py \
        --workload gpt2l_silo_fused --layers 12 18 24
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


@contextlib.contextmanager
def backend_reads_tpu():
    import jax

    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = real


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--layers", type=int, nargs="+", required=True)
    args = p.parse_args()

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import cells, traffic

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    cell = cells.load_cell(args.workload)
    mesh = None
    if cell.chips > 1:  # the driver's mesh, over described devices
        from jax.sharding import NamedSharding, PartitionSpec
        from fedml_tpu.parallel.spmd import make_client_mesh

        mesh = make_client_mesh(cell.chips, devices=topo.devices[:cell.chips])
        on_state = NamedSharding(mesh, PartitionSpec())
        on_block = NamedSharding(mesh, PartitionSpec("clients"))
    else:
        on_state = on_block = SingleDeviceSharding(topo.devices[0])
    shaped = lambda tree, sharding: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)
    for n_layer in args.layers:
        cell.config["n_layer"] = n_layer
        bundle = cells.build_bundle(cell.config)
        round_fn = cells.load_driver(cell.workload["driver"]).build_round_fn(
            cell, bundle, mesh)
        state = shaped(jax.eval_shape(
            lambda k: cells.initial_state(bundle, k), jax.random.PRNGKey(0)),
            on_state)
        block = shaped(traffic.resident_block(cell.config, cell.geometry, 0),
                       on_block)
        t0 = time.time()
        try:
            with backend_reads_tpu():
                compiled = round_fn.lower(state, *block).compile()
        except Exception as e:  # the compiler's refusal is the answer
            print(str(e)[:6000], file=sys.stderr)  # the largest buffers
            print(json.dumps({"n_layer": n_layer, "fits": False,
                              "error": str(e).splitlines()[0][:300]}))
            continue
        m = compiled.memory_analysis()
        print(json.dumps({
            "n_layer": n_layer, "fits": True,
            "compile_s": round(time.time() - t0, 1),
            "argument_gib": round(m.argument_size_in_bytes / 2**30, 2),
            "output_gib": round(m.output_size_in_bytes / 2**30, 2),
            "peak_gib": round(m.peak_memory_in_bytes / 2**30, 2),
        }), flush=True)


if __name__ == "__main__":
    main()
