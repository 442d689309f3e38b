"""Profiling helpers over ``jax.profiler`` (SURVEY §5: the reference has no
tracing at all — only rank-prefixed logging, ``util/log.py:5-13``).

Two levels:

- :func:`annotate` — named span inside an already-running trace; shows up
  on the TensorBoard/xplane timeline alongside XLA ops. No-op overhead when
  no trace is active.
- :func:`profile` — capture a full device+host trace of a block into a
  TensorBoard logdir.

Neither hides a missing profiler: ``jax.profiler`` is part of the one
installation this repo runs on, so a failure to import or start it raises
— a capture that silently recorded nothing is worse than an error
(``POST /profile`` answers 500 with the message).
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator

from radixmesh_tpu.obs.metrics import Histogram
from radixmesh_tpu.obs.trace_plane import get_recorder

__all__ = ["annotate", "profile", "timed", "recorded"]


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named span on the profiler timeline (xplane TraceAnnotation)."""
    import jax.profiler as _prof

    with _prof.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def profile(log_dir: str) -> Iterator[None]:
    """Capture a device+host profiler trace of the block to ``log_dir``
    (view with TensorBoard's profile plugin, or read the ``.xplane.pb``
    with ``jax.profiler.ProfileData``)."""
    import jax.profiler as _prof

    with _prof.trace(log_dir):
        yield


@contextlib.contextmanager
def timed(hist: Histogram, name: str | None = None) -> Iterator[None]:
    """Observe the block's wall time into ``hist`` and, when a profiler
    trace is running, annotate the span with ``name``."""
    t0 = time.monotonic()
    with annotate(name or hist.name):
        try:
            yield
        finally:
            hist.observe(time.monotonic() - t0)


@contextlib.contextmanager
def recorded(lane: str, name: str, **args) -> Iterator[None]:
    """Both observability planes in one block: an xplane annotation for
    profiler captures AND a flight-recorder span (``obs/trace_plane.py``)
    on ``lane`` for the request-flight timeline. One branch when the
    recorder is disabled (it still annotates — that is already a no-op
    without a live profiler trace)."""
    rec = get_recorder()
    if not rec.enabled:
        with annotate(name):
            yield
        return
    t0 = time.monotonic()
    with annotate(name):
        try:
            yield
        finally:
            rec.event(lane, name, t0, time.monotonic() - t0, **args)
