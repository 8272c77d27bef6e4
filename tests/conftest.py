"""Test configuration.

Tests never require the real TPU: JAX runs on CPU with 8 virtual devices so
sharding/mesh tests exercise real multi-device code paths
(xla_force_host_platform_device_count, see task spec / SURVEY.md §7).

The platform is chosen through the environment, before any test imports a
jax-dependent module (handel_tpu/utils/jaxenv.py); child processes that
tests spawn inherit it.
"""

import os

# force CPU even if the caller exported HANDEL_TPU_PLATFORM=tpu: test
# correctness must be checkable on any chip-less machine
os.environ["HANDEL_TPU_PLATFORM"] = "cpu"

from handel_tpu.utils.jaxenv import apply_platform_env, enable_compile_cache

apply_platform_env(force_host_device_count=8)
enable_compile_cache()
