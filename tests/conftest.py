"""Test configuration.

Tests run on the CPU (JAX_PLATFORMS=cpu), on a virtual 8-device CPU mesh so
multi-chip sharding paths are exercised without TPU hardware (the JAX
kernels are backend-neutral; the CPU backend is the conformance twin of the
TPU path). The program runs on the chip through `python chip_smoke.py`
(and `--chips 4`); tests/test_chip_compile.py compiles the kernels for a
described v5e here.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
