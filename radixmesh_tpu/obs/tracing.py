"""Profiling helpers over ``jax.profiler`` (SURVEY §5: the reference has no
tracing at all — only rank-prefixed logging, ``util/log.py:5-13``).

Four levels:

- :data:`annotate` — ``jax.profiler.TraceAnnotation`` itself: a named span
  written into the profiler's own trace, so it is on the device trace's
  clock. Without a capture it is one atomic load. Names are constants.
- :class:`SpanBook` — the one way the engine thread opens a span: an
  ``annotate`` span that also adds its count and host seconds to a book
  that runs always (a 3 s capture is taken after the measured window and
  holds too few of most spans for a statistic). The ``rm.*`` spans of
  ``engine/engine.py``, ``server/http_frontend.py``'s runner and
  ``slo/runner.py`` go through it; ``rm.http.emit`` (the SSE streams'
  emitter thread and the handler threads) stays a bare ``annotate``.
- :class:`ProcessAccount` — the process's pauses: garbage collections by
  generation and XLA compilations by name, from ``gc.callbacks`` and
  ``jax.monitoring``. Both hooks are global to the process, so only an
  entry point (``launch.py serve``) installs one.
- :func:`profile` — capture a full device+host trace of a block into a
  TensorBoard logdir.

Neither hides a missing profiler: ``jax.profiler`` is part of the one
installation this repo runs on, so a failure to import or start it raises
— a capture that silently recorded nothing is worse than an error
(``POST /profile`` answers 500 with the message).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import gc
import threading
import time
from typing import Iterator

from radixmesh_tpu.obs.trace_plane import get_recorder

__all__ = [
    "ENGINE_SPANS",
    "OUTSIDE_STEP",
    "ProcessAccount",
    "SpanBook",
    "annotate",
    "profile",
    "recorded",
    "since_import",
]

# The engine thread's span names (tests/test_host_spans.py::ENGINE_SPANS
# holds their nesting). A book is seeded with them, so /debug/state shows
# every name from the first read on.
ENGINE_SPANS = (
    "rm.runner.pre_step",
    "rm.slo.pump",
    "rm.engine.step",
    "rm.admit",
    "rm.tree.match",
    "rm.prefill.dense",
    "rm.prefill.group",
    "rm.first_token.sync",
    "rm.decode.build",
    "rm.decode.dispatch",
    "rm.decode.sample",
    "rm.decode.emit",
    "rm.decode.sync",
    "rm.decode.consume",
    "rm.tree.publish",
)
# The book's one entry that is no span: the seconds between the exit of one
# ``rm.engine.step`` and the entry of the next (the runner's loop, the lock
# hand-over, ``rm.runner.pre_step`` — and the wait of a runner with no work).
OUTSIDE_STEP = "outside_step"
_STEP = "rm.engine.step"
# ``at_s`` of every record this module writes counts from here: the import,
# which an entry point reaches in its first second.
_T_IMPORT = time.monotonic()


def since_import() -> float:
    """Seconds since this module was imported: the ``at_s`` of a slow-step
    or compilation record, to lay two records of one process side by side."""
    return time.monotonic() - _T_IMPORT


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


class _Span:
    """One ``with book.span(name)``: the annotation, and on exit the
    book's count and seconds. Saves the book's innermost open name on
    entry and restores it on exit (an exception included), so the book
    needs no stack."""

    __slots__ = ("_book", "_name", "_ann", "_outer", "t0", "seconds")

    def __init__(self, book: "SpanBook", name: str):
        self._book, self._name = book, name

    def __enter__(self):
        book = self._book
        self._ann = book.annotate(self._name)
        self._ann.__enter__()
        self.t0 = book.clock()
        self._outer, book.innermost = book.innermost, self._name
        return self

    def __exit__(self, *exc):
        book = self._book
        self.seconds = dt = book.clock() - self.t0
        book.n[self._name] = book.n.get(self._name, 0) + 1
        book.s[self._name] = book.s.get(self._name, 0.0) + dt
        book.innermost = self._outer
        self._ann.__exit__(*exc)


class _StepSpan(_Span):
    """``rm.engine.step``: besides the span, the gap since the step
    before goes to ``outside_step``, the thread is remembered as the
    engine's, and the book's seconds at entry are kept (``entry``) for
    whoever wants the step's own spans (``SpanBook.top_since``)."""

    __slots__ = ("entry",)

    def __enter__(self):
        book = self._book
        book.thread = threading.get_ident()
        super().__enter__()
        if book._step_exit is not None:
            book.n[OUTSIDE_STEP] += 1
            book.s[OUTSIDE_STEP] += self.t0 - book._step_exit
        self.entry = dict(book.s)
        return self

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self._book._step_exit = self.t0 + self.seconds


class SpanBook:
    """Count and host seconds of every span one thread opened, by name.

    One per engine, written by the engine thread alone and read by anyone
    (``snapshot`` copies under the GIL). ``innermost`` is the name of the
    span open right now on that thread, None between spans; ``thread``
    its ident once a step has run. ``clock`` is ``time.monotonic`` unless
    a test hands in its own."""

    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self.annotate = _trace_annotation()  # resolved once, not a span
        self.n: dict[str, int] = dict.fromkeys((*ENGINE_SPANS, OUTSIDE_STEP), 0)
        self.s: dict[str, float] = dict.fromkeys(self.n, 0.0)
        self.innermost: str | None = None
        self.thread: int | None = None
        self._step_exit: float | None = None

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def step(self) -> _StepSpan:
        return _StepSpan(self, _STEP)

    def snapshot(self) -> dict:
        n, s = dict(self.n), dict(self.s)
        return {name: {"n": n[name], "s": s.get(name, 0.0)} for name in n}

    def top_since(self, entry: dict, k: int = 3) -> list:
        """The ``k`` names that gained most seconds since ``entry`` (a
        copy of ``s``), the step itself left out: ``[[name, s], ...]``."""
        gained = [
            (v - entry.get(name, 0.0), name)
            for name, v in self.s.items()
            if name not in (_STEP, OUTSIDE_STEP)
        ]
        top = sorted(gained, reverse=True)[:k]
        return [[name, round(d, 6)] for d, name in top if d > 0]


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class ProcessAccount:
    """What paused the whole process, counted always once installed.

    ``gc``: per generation ``{"n", "s", "max_s"}`` from one ``gc.callbacks``
    entry that stamps ``start`` and adds at ``stop``. ``compiles``: every
    backend compilation JAX reports (``backend_compile_duration`` wraps the
    persistent cache's lookup too, so ``n`` counts hits as the
    ``Finished XLA compilation`` log lines do; ``cache_hits`` says how many
    were), its seconds, and the last 32 by name with the span the engine
    thread had open (``book``'s innermost) or, from any other thread, that
    thread's name. ``gc_n`` / ``gc_s`` are the sums over generations, for a
    reader that wants a cheap before/after."""

    RING = 32

    def __init__(self, book: SpanBook | None = None):
        self.book = book
        self.gc = [{"n": 0, "s": 0.0, "max_s": 0.0} for _ in range(3)]
        self.gc_n, self.gc_s = 0, 0.0
        self.compiles = {"n": 0, "s": 0.0, "cache_hits": 0}
        self.last: collections.deque = collections.deque(maxlen=self.RING)
        self._gc_t0 = 0.0
        self._hit_threads: set[int] = set()
        self._lock = threading.Lock()  # compilations run on any thread
        self._installed = False

    def install(self) -> "ProcessAccount":
        if not self._installed:
            from jax import monitoring

            gc.callbacks.append(self._on_gc)
            monitoring.register_event_duration_secs_listener(self._on_duration)
            self._installed = True
        return self

    def uninstall(self) -> None:
        if self._installed:
            from jax import monitoring

            gc.callbacks.remove(self._on_gc)
            monitoring.unregister_event_duration_listener(self._on_duration)
            self._installed = False

    def _on_gc(self, phase: str, info: dict) -> None:
        # Collections do not overlap (the collector is not re-entrant), so
        # one stamp is enough; nothing here allocates a container.
        if phase == "start":
            self._gc_t0 = time.monotonic()
            return
        dt = time.monotonic() - self._gc_t0
        g = self.gc[info["generation"]]
        g["n"] += 1
        g["s"] += dt
        if dt > g["max_s"]:
            g["max_s"] = dt
        self.gc_n += 1
        self.gc_s += dt

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event == _CACHE_RETRIEVAL:
            # Fires inside the backend-compile interval of the same thread.
            self._hit_threads.add(threading.get_ident())
            return
        if event != _BACKEND_COMPILE:
            return
        ident = threading.get_ident()
        book = self.book
        if book is not None and book.thread == ident:
            where = book.innermost or OUTSIDE_STEP
        else:
            where = threading.current_thread().name
        with self._lock:
            hit = ident in self._hit_threads
            self._hit_threads.discard(ident)
            c = self.compiles
            c["n"] += 1
            c["s"] += seconds
            c["cache_hits"] += hit
            self.last.append({
                "fun_name": str(kw.get("fun_name")),
                "seconds": round(seconds, 6),
                "cache_hit": hit,
                "at_s": round(since_import(), 3),
                "span": where,
            })

    def snapshot(self) -> dict:
        return {
            "gc": {str(i): dict(g) for i, g in enumerate(self.gc)},
            "compiles": {**self.compiles, "last": list(self.last)},
        }


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
