"""Test harness config: force CPU jax with an 8-device virtual platform.

This mirrors the reference's multi-node-without-a-cluster strategy
(``correctness.py:22-29`` runs 6 localhost processes): correctness gates run
on CPU so they're cheap. What the compiled Pallas kernels do on a TPU is
checked without a chip by ``tests/test_tpu_compile.py`` (compile-only, real
Mosaic) and on the chip by ``chip_smoke.py``.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # the env var is enough

import jax  # noqa: E402

# Persistent XLA compilation cache: the suite is dominated by CPU
# compiles of tiny-model program variants, and the module-boundary
# ``jax.clear_caches()`` below (required — see the fixture) forces
# cross-module recompiles of identical programs. A disk cache turns
# those, and every rerun of the suite, into deserialize hits (keys hash
# the optimized HLO + backend fingerprint, so code changes miss
# naturally and staleness is impossible). Opt out with
# RADIXMESH_NO_COMPILE_CACHE=1; relocate with JAX_COMPILATION_CACHE_DIR.
if not os.environ.get("RADIXMESH_NO_COMPILE_CACHE"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.environ.get(
            "JAX_COMPILATION_CACHE_DIR", "/tmp/radixmesh_xla_cache"
        ),
    )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


import pytest


@pytest.fixture(autouse=True, scope="module")
def _bound_jit_residency():
    """Free compiled executables at module boundaries.

    The full suite JIT-compiles thousands of program variants; keeping
    every executable alive for the whole run exhausts a per-process
    resource (the crash signature is a deterministic XLA:CPU
    ``backend_compile_and_load`` segfault at ~91% of the suite — LLVM
    JIT code mappings against ``vm.max_map_count``, not Python memory:
    the machine has >100 GB free when it dies). Clearing per module
    bounds live executables at one module's worth; cross-module cache
    hits were minimal anyway because engines differ in shape."""
    yield
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _fresh_metrics_registry():
    """Process-global metric counters must not leak between tests."""
    from radixmesh_tpu.obs.metrics import Registry, get_registry, set_registry

    old = get_registry()
    set_registry(Registry())
    yield
    set_registry(old)


@pytest.fixture(autouse=True)
def _fresh_trace_recorder():
    """Process-global flight recorder must not leak between tests (a test
    that enables sampling would otherwise leave every later engine test
    allocating spans)."""
    from radixmesh_tpu.obs.trace_plane import (
        FlightRecorder,
        get_recorder,
        set_recorder,
    )

    old = get_recorder()
    set_recorder(FlightRecorder())
    yield
    set_recorder(old)
