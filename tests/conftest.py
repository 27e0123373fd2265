"""Test bootstrap: force an 8-device virtual CPU mesh.

Multi-chip behavior is tested the way the reference tests multi-node
behavior — in one process (DistributedQueryRunner boots coordinator+workers
in one JVM, presto-testing/.../DistributedQueryRunner.java:73).  Here the
"cluster" is 8 virtual XLA CPU devices, so sharding/collective code paths
compile and execute without TPU hardware.

Must run before the first ``import jax`` anywhere in the test session.
"""

import os

# Force the CPU backend whatever the environment says: unit tests must be
# hardware-independent and fast; the chip is exercised by chip_smoke.py.
os.environ["JAX_PLATFORMS"] = "cpu"
# The program keeps a persistent compile cache (presto_tpu/config.py).  The
# suite keeps it off, for this process and every child it starts:
# serialising large XLA:CPU executables has crashed in-process, and CPU
# entries are worthless to the chip.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
if "collective_call_terminate_timeout" not in _flags:
    # single-core hosts run the 8 virtual devices' shards sequentially;
    # XLA's default 40s collective-rendezvous abort is too eager for
    # the larger mesh-SQL programs (the wait is progress, not deadlock).
    # XLA aborts the process on an unknown XLA_FLAGS entry; the installed
    # jaxlib 0.9.0 accepts this one (checked once by hand, PR 25), so it
    # is set without a per-worker probe process.
    _flags += " --xla_cpu_collective_call_terminate_timeout_seconds=1200"
os.environ["XLA_FLAGS"] = _flags

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
