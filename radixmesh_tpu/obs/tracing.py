"""Profiling helpers over ``jax.profiler`` (SURVEY §5: the reference has no
tracing at all — only rank-prefixed logging, ``util/log.py:5-13``).

Two levels:

- :data:`annotate` — ``jax.profiler.TraceAnnotation`` itself: a named span
  written into the profiler's own trace, so it is on the device trace's
  clock. Without a capture it is one atomic load. The program's spans
  (the ``rm.*`` names: ``engine/engine.py``, ``server/http_frontend.py``,
  ``slo/runner.py``) all go through it; names are constants.
- :func:`profile` — capture a full device+host trace of a block into a
  TensorBoard logdir.

Neither hides a missing profiler: ``jax.profiler`` is part of the one
installation this repo runs on, so a failure to import or start it raises
— a capture that silently recorded nothing is worse than an error
(``POST /profile`` answers 500 with the message).
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Iterator

from radixmesh_tpu.obs.trace_plane import get_recorder

__all__ = ["annotate", "profile", "recorded"]


@functools.cache
def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use: mesh-only
    processes (routers, the chaos benches) import this module for
    :func:`recorded` and never pay the jax import unless a span is opened."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


def __getattr__(name: str):  # PEP 562: ``from ...tracing import annotate``
    if name == "annotate":
        return _trace_annotation()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@contextlib.contextmanager
def profile(log_dir: str, python_tracer: bool = False) -> Iterator[None]:
    """Capture a device+host profiler trace of the block to ``log_dir``
    (view with TensorBoard's profile plugin, or read the ``.xplane.pb``
    with ``jax.profiler.ProfileData``). The python tracer is most of what
    a capture costs and most of its bytes, and the ``rm.*`` annotations do
    not need it (the host tracer writes them to plane ``/host:CPU``): off
    unless asked for."""
    import jax.profiler as _prof

    options = _prof.ProfileOptions()
    options.python_tracer_level = 1 if python_tracer else 0
    with _prof.trace(log_dir, profiler_options=options):
        yield


@contextlib.contextmanager
def recorded(lane: str, name: str, **args) -> Iterator[None]:
    """Both observability planes in one block: an xplane annotation for
    profiler captures AND a flight-recorder span (``obs/trace_plane.py``)
    on ``lane`` for the request-flight timeline. One branch when the
    recorder is disabled (it still annotates — that is already a no-op
    without a live profiler trace)."""
    ann = _trace_annotation()
    rec = get_recorder()
    if not rec.enabled:
        with ann(name):
            yield
        return
    t0 = time.monotonic()
    with ann(name):
        try:
            yield
        finally:
            rec.event(lane, name, t0, time.monotonic() - t0, **args)
