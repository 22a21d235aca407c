"""Test harness config.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding code
paths execute without TPU hardware (chip_smoke.py and the benchmark
run on the real chip and must NOT import this). Env must be set before
jax initializes its backends.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compile cache: the verdict kernels shape-bucket their
# tables, so across pytest runs nearly every jit hits this cache.
from cilium_tpu import compile_cache  # noqa: E402

compile_cache.enable()
