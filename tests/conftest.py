"""Test harness: real-collective multi-device tests on a virtual CPU mesh.

The reference tests multi-GPU behavior with ``torch.distributed.launch``
subprocesses (SURVEY.md §4). Here a single process gets 8 virtual CPU devices
via XLA flags, so collectives in tests are real. Must run before jax imports.
"""

import os

# Force CPU regardless of ambient JAX_PLATFORMS: the test suite needs 8
# virtual devices. Set APEX_TPU_TEST_PLATFORM to override.
os.environ["JAX_PLATFORMS"] = os.environ.get("APEX_TPU_TEST_PLATFORM", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# jax reads JAX_PLATFORMS when it is first imported; a pytest plugin may have
# imported it before this file ran, so select the platform in code as well.
jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
jax.config.update("jax_default_matmul_precision", "highest")
