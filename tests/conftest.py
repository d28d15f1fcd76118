"""Test configuration.

Runs JAX on a virtual 8-device CPU platform, set before any test touches
a device, so multi-chip sharding (mesh over per-key searches) is
exercised without TPU hardware.  JAX_PLATFORMS=cpu selects the same;
the jax.config call makes the suite independent of the caller's env.
The persistent compilation cache stays off: the suite compiles small
CPU kernels, and tests/test_tpu_compile.py compiles for a described
chip whose executables no CPU process can load.  Set
JEPSEN_TPU_TEST_PLATFORM=tpu to run the suite on real hardware instead
(single chip; mesh tests skip themselves).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Off for child processes the tests start, too.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
if os.environ.get("JEPSEN_TPU_TEST_PLATFORM", "cpu") != "tpu":
    jax.config.update("jax_platforms", "cpu")


def free_port() -> int:
    """A fresh localhost port for host-net suite tests: hardcoded
    ports collide with daemons leaked by interrupted earlier runs or
    with a concurrent builder's suites on this machine (the round-5
    7401 false-conviction incident)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
