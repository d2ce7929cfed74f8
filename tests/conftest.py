"""Test configuration: force JAX onto 8 virtual CPU devices so multi-device
sharding (the TPU analogue of the reference's localhost-gloo multiprocess
testing, SURVEY.md §4) is exercised without TPU hardware.

The CPU mesh is the design, not a fallback: tests pin the platform and
the device count themselves, before the backend initializes, so the
suite behaves the same on a machine that has a chip. The persistent
compilation cache is off for the suite (and for the subprocesses it
starts, which inherit the variable): tests must neither read nor write
the checkout's .jax_cache. tests/test_backend.py turns it on explicitly
in its own subprocesses.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax

jax.config.update("jax_platforms", "cpu")
