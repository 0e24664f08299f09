"""Test harness: multi-client without a cluster.

The reference fakes a cluster with `mpirun -np N` on localhost
(SURVEY.md §4.4); here an 8-device CPU mesh is faked via XLA host
devices.  Both the platform and the device count are set through the
environment, before jax is imported (jax reads ``JAX_PLATFORMS`` and
XLA reads ``XLA_FLAGS`` once, at start-up): the suite then runs on the
CPU however pytest was invoked, and every process a test spawns from
``os.environ`` inherits the same pin, so no test ever opens an
accelerator.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from fedml_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
# persistent compile cache: the suite is compile-bound on the CPU mesh.
# Threshold 0: the cache is keyed by HLO hash, so identical programs
# compiled by DIFFERENT jit closures across test modules dedupe even
# within one cold run.
configure_compile_cache(min_compile_secs=0.0)

assert jax.default_backend() == "cpu" and jax.device_count() >= 8, (
    "test harness expected a faked 8-device CPU mesh; got "
    f"{jax.device_count()} {jax.devices()[:2]}"
)
