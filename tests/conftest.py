"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Tests never need an accelerator: the platform is pinned to the CPU here
(jax.config, before any test imports jax — the same thing JAX_PLATFORMS=cpu
asks for from outside) and XLA is asked for eight virtual host devices, so
shardings and the multichip dry run have a mesh to lay out over. What only
a chip can show — that every program compiles on the TPU and that results
are bit-identical there — is `python chip_smoke.py` at the repo root, run
through the chip tool: one process per chip, children sharing the compile
cache at JAX_COMPILATION_CACHE_DIR or the checkout's .jax_cache/.
tests/test_chip_smoke.py keeps that script's CPU rehearsal in tier-1.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import pytest

jax.config.update("jax_platforms", "cpu")


@pytest.fixture
def staged_walk():
    """`with staged_walk():` puts every chunk prepared inside it on the
    staged per-page walk of kernels/pipeline.py: the native walk declines, as
    it does under a memory ceiling or without the library. The program has
    no switch for it; the ladder's own fallback is what is driven."""
    from unittest import mock

    from parquet_tpu.kernels import pipeline

    return lambda: mock.patch.object(pipeline, "_native_prepare", lambda *a, **kw: (None, None))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: extended sweeps (fault-injection etc.) excluded from the "
        "tier-1 `-m 'not slow'` run; `make fuzz` includes them",
    )
