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


@pytest.fixture(autouse=True)
def _no_program_store(request, monkeypatch):
    """Engines of the suite launch through ``jax.jit``, not through stored
    executables (``engine/programs.py``), unless a test asks for
    ``program_store``. The store keys an executable by what it is built
    FROM — the package's files, the static values, the argument types —
    and that cannot see a function patched in this process: a test that
    patches code under trace would write an entry every later test (and
    run of the suite) loads under the unpatched key, where JAX's own cache
    above, keyed by the traced module, only misses."""
    if "program_store" not in request.fixturenames:
        monkeypatch.setattr(
            "radixmesh_tpu.engine.programs.store_dir", lambda: None)


@pytest.fixture(autouse=True)
def _chunks_as_given(request, monkeypatch):
    """Engines of the suite launch chunks of the ``prefill_chunk`` their
    test gave them, unless the test asks for ``planned_width``. The width
    the planner reads from the weights (``prefill_plan.chunk_width``, PR
    54) is the power of two that holds the floor — hundreds of tokens for
    any model, a toy one included (the floor is a ratio of bytes to
    arithmetic, not a size) — so a toy prompt of 40 tokens would be ONE
    launch whatever ``prefill_chunk=16`` says, and the tests that chunk a
    prompt to reach the paths between chunks would stop reaching them."""
    if "planned_width" not in request.fixturenames:
        monkeypatch.setattr(
            "radixmesh_tpu.engine.engine.chunk_width",
            lambda chunk, *_: chunk)


@pytest.fixture
def planned_width():
    """Engines built in this test take the chunk width the planner reads
    from their weights, as a served one does."""


@pytest.fixture
def program_store(tmp_path):
    """A program store of the test's own: JAX's persistent cache configured
    at a temporary directory, which is all that turns the store on, and
    JAX's own cache moved there with the setting (``reset_cache``) so that
    what the test builds first is compiled, not handed back: XLA:CPU does
    not serialise a handed-back executable whole, and the store takes none
    such there. Returns the store's directory (not there until an entry is
    written)."""
    from jax.experimental.compilation_cache import compilation_cache

    from radixmesh_tpu.engine import programs

    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "xla"))
    compilation_cache.reset_cache()
    try:
        yield programs.store_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
        compilation_cache.reset_cache()
