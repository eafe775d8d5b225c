"""Test bootstrap: force an 8-device virtual CPU platform so every
multi-device/sharding test runs hermetically without TPU hardware
(SURVEY.md §4 'implication' (c))."""

import os

# Must run before jax initializes its backends.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

from milnce_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

# The tests run on the CPU whatever the host holds.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

# Persistent compilation cache: the S3D train step takes ~2 min to compile
# on the virtual 8-device CPU mesh; identical HLO across test runs hits disk.
# JAX_COMPILATION_CACHE_DIR wins where it is set; else this fixed path.
configure_compile_cache("/tmp/jax_test_cache")
