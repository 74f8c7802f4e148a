"""Test configuration: the suite runs on the CPU.

JAX is held to the CPU backend and every pallas kernel runs in interpret
mode (kernels/launch.interpret), on a virtual 8-device CPU platform so
multi-chip sharding paths compile and execute without TPU hardware.  The
chip is reached only through the chip tool, with `python chip_smoke.py`;
tests/test_tpu_compile.py compiles the fast kernels for a described
v5e without one.

`XLA_FLAGS` is amended before the CPU client exists (XLA parses it once).
The persistent compilation cache goes where the one launcher helper
(lodestar_tpu/utils/compile_cache.py) puts it, so repeat runs on a
checkout start warm.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (import order is the point here)

jax.config.update("jax_platforms", "cpu")

from lodestar_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()
