"""The benchmark's own tests run on the CPU (this sandbox holds JAX to it
from the environment) at a tiny scale; they are run by hand and by the
builder (``python -m pytest benchmark/tests -q``), not by the driver."""

import os
import sys

# four virtual devices for the mesh rehearsal; CPU programs stay out of the
# persistent compile cache, which is for the chip's
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
