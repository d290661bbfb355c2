"""Test configuration: force JAX onto 8 virtual CPU devices BEFORE any jax
import, so sharding tests exercise a real multi-device mesh without TPU
hardware. Tests never turn the persistent compile cache on."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
