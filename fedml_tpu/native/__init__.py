"""Native (C++) host-runtime components.

The compute path of the framework is JAX/XLA (compiled native code by
construction); these are the HOST-side pieces where Python/numpy is the
bottleneck — currently the per-round client-shard packer
(``gather_rows``).  Built on demand with the system ``g++`` via ctypes
(no pip/pybind dependency) from the source in this checkout.  Every
entry point has a pure-numpy twin that takes over, with a warning,
where no toolchain exists (``FEDML_TPU_NO_NATIVE=1`` selects it);
``native_status()`` says which one is running.
"""

from fedml_tpu.native.packer import (gather_rows, native_available,
                                     native_status)

__all__ = ["gather_rows", "native_available", "native_status"]
