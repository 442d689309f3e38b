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
- :class:`ProcessAccount` — the process's pauses and its set-up: garbage
  collections by generation; every program's tracing, lowering,
  compilation and cache-load seconds, by name and by where the thread
  was; the start-up's phases. From ``gc.callbacks`` and ``jax.monitoring``:
  both hooks are global to the process, so only an entry point
  (``launch.py serve``) installs one.
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
import os
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


# The three intervals ``jax._src.dispatch.log_elapsed_time`` reports of a
# program — a scalar of the event's name as it opens, a duration as it
# closes — and the key each one's seconds are counted under.
_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def _process_age() -> float:
    """Seconds since the kernel started this process: ``/proc/self/stat``'s
    start time (field 22, clock ticks since boot) against ``/proc/uptime``.
    It counts the interpreter's start and every import, which no stamp
    inside the program can; what it leaves out is the parent's ``fork`` and
    ``exec``. One tick is 10 ms. Where there is no such ``/proc`` (off
    Linux), or its two clocks disagree (a container whose ``/proc/uptime``
    is its own while the start time counts from the host's boot reads an
    age below this module's), the age is :func:`since_import`'s, and
    ``imports`` is simply short."""
    floor = since_import()
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return floor
    return max(age, floor)


def _program(fun_name) -> str:
    """One key for a program's three events: tracing names the function
    (``outer``), lowering and the compiler the module (``jit(outer)``)."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        name = name[name.index("(") + 1 : -1]
    return name


class _OpenIntervals(threading.local):
    """What one thread has open of the three intervals, and what it carries
    from one of a program's intervals to the next."""

    depth = 0  # intervals open on this thread, one inside the other
    where = None  # the outermost one's ``by_span`` key, taken as it opened
    nested_s = 0.0  # compilations that ended inside the outermost one
    hit = False  # the open compilation was served by the persistent cache
    traced = None  # {name, trace_s, lower_s} of a program waiting for its compilation


class ProcessAccount:
    """What paused the whole process, and what its set-up went to, counted
    always once installed.

    ``gc``: per generation ``{"n", "s", "max_s"}`` from one ``gc.callbacks``
    entry that stamps ``start`` and adds at ``stop``. ``gc_n`` / ``gc_s`` are
    the sums over generations, for a reader that wants a cheap before/after.

    ``compiles``: every backend compilation JAX reports
    (``backend_compile_duration`` wraps the persistent cache's lookup too,
    so ``n`` counts hits as the ``Finished XLA compilation`` log lines do;
    ``cache_hits`` says how many were and ``cache_load_s`` what they took of
    ``s``), and the last 32 by name with the span the engine thread had
    open (``book``'s innermost) or, from any other thread, that thread's
    name. ``trace_s`` / ``lower_s``: the seconds of tracing and of lowering
    to MLIR. The three intervals nest (tracing ``outer`` traces ``inner``;
    lowering traces again), so a second goes to the kind of the OUTERMOST
    interval its thread has open and to nothing else — but for a
    compilation that ends inside a trace (``ensure_compile_time_eval``),
    which keeps its place in ``n`` and ``s`` and is taken off the interval
    around it. So ``trace_s + lower_s + s`` counts no second twice.
    ``by_program`` (the same seconds by function name, ``max_s`` the longest
    single interval) and ``by_span`` (by where the thread was when the
    outermost interval opened) hold at most ``TABLE`` names and
    ``"(other)"``; each sums to the totals. ``programs``: the names in
    ``by_program``; ``first_at_s``: when a name was first heard, to lay
    beside a slow step's ``at_s``.

    ``compiles["stored"]``: the programs that went through no compile path
    at all or filled the store that spares the next start one
    (``engine/programs.py``, which calls the three ``program_*`` methods):
    ``hits`` loaded in ``load_s`` (a program's row has its own ``hits`` /
    ``load_s``, so a loaded program has a row and a ``first_at_s``),
    ``misses`` compiled as above and written in ``write_s``, ``bytes`` of
    the entries read and written, ``refused`` by reason — what stayed on or
    fell back to ``jax.jit``. JAX hears nothing of a load: ``trace_s``,
    ``lower_s``, ``n`` and ``cache_load_s`` count the programs that are not
    stored, and a stored one's first build.

    ``startup``: the phases ``launch.py serve`` marks with :meth:`started`
    and :meth:`phase`; ``ready_at_s``, the process's age (see
    ``_process_age``) as the last of them ended; ``compiling_s``, what
    ``trace_s + lower_s + s`` read then (drawing the weights is a program
    too: those seconds are the start-up's AND the compile path's, and a
    reader that adds the two takes them off one). ``at_s`` /
    ``first_at_s`` stay on :func:`since_import`'s clock.

    The ``jax.monitoring`` listeners run when JAX traces, lowers, compiles
    or looks a program up and never in a step that does none of these."""

    RING = 32
    TABLE = 256

    def __init__(self, book: SpanBook | None = None):
        self.book = book
        self.gc = [{"n": 0, "s": 0.0, "max_s": 0.0} for _ in range(3)]
        self.gc_n, self.gc_s = 0, 0.0
        self.compiles = {"n": 0, "s": 0.0, "cache_hits": 0, "trace_s": 0.0,
                         "lower_s": 0.0, "cache_load_s": 0.0}
        self.stored = {"hits": 0, "misses": 0, "load_s": 0.0, "write_s": 0.0,
                       "bytes": 0, "refused": {}}
        self.last: collections.deque = collections.deque(maxlen=self.RING)
        self.by_program: dict[str, dict] = {}
        self.by_span: dict[str, dict] = {}
        self.startup = {"phases": [], "ready_at_s": None, "compiling_s": 0.0}
        self._t_start = time.monotonic() - _process_age()
        self._gc_t0 = 0.0
        self._open = _OpenIntervals()
        self._lock = threading.Lock()  # compilations run on any thread
        self._installed = False

    def install(self) -> "ProcessAccount":
        if not self._installed:
            from jax import monitoring

            gc.callbacks.append(self._on_gc)
            monitoring.register_scalar_listener(self._on_open)
            monitoring.register_event_duration_secs_listener(self._on_duration)
            self._installed = True
        return self

    def uninstall(self) -> None:
        if self._installed:
            from jax import monitoring

            gc.callbacks.remove(self._on_gc)
            monitoring.unregister_scalar_listener(self._on_open)
            monitoring.unregister_event_duration_listener(self._on_duration)
            self._installed = False

    def started(self, name: str) -> None:
        """The phase that began with the process ends here (``imports``)."""
        self._phase_ended(name, self._t_start)

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        """One phase of the start-up: its seconds. No ``annotate`` span:
        ``serve`` has no way to capture its own start (the profile endpoint
        exists only after ``listen``), so nothing could read one."""
        t0 = time.monotonic()
        yield
        self._phase_ended(name, t0)

    def _phase_ended(self, name: str, t0: float) -> None:
        now = time.monotonic()
        c = self.compiles
        self.startup["phases"].append([name, now - t0])
        self.startup["ready_at_s"] = now - self._t_start
        self.startup["compiling_s"] = c["trace_s"] + c["lower_s"] + c["s"]

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

    def _where(self) -> str:
        book = self.book
        if book is not None and book.thread == threading.get_ident():
            return book.innermost or OUTSIDE_STEP
        return threading.current_thread().name

    def _row(self, table: dict, key: str, **more) -> dict:
        """``table``'s row for ``key``, made with ``more`` beside the four
        counts; past ``TABLE`` names a new one is ``"(other)"``, so nothing
        a request sends grows a table."""
        row = table.get(key)
        if row is None:
            if len(table) >= self.TABLE:
                key = "(other)"
            row = table.setdefault(
                key, {"n": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0} | more)
        return row

    def _program_row(self, name: str, first_at_s: float) -> dict:
        return self._row(self.by_program, name, cache_hits=0, max_s=0.0,
                         first_at_s=round(first_at_s, 3), hits=0, load_s=0.0)

    def program_loaded(self, name: str, seconds: float, nbytes: int) -> None:
        """A stored executable was read and loaded instead of built."""
        at_s = since_import()
        with self._lock:
            stored = self.stored
            program = self._program_row(name, at_s - seconds)
            for counts in (stored, program):
                counts["hits"] += 1
                counts["load_s"] += seconds
            stored["bytes"] += nbytes

    def program_missed(self, name: str, seconds: float, nbytes: int) -> None:
        """The store had no entry: the program was compiled (JAX reported
        that) and its executable written in ``seconds``."""
        with self._lock:
            stored = self.stored
            stored["misses"] += 1
            stored["write_s"] += seconds
            stored["bytes"] += nbytes

    def program_refused(self, reason: str) -> None:
        with self._lock:
            refused = self.stored["refused"]
            refused[reason] = refused.get(reason, 0) + 1

    def _on_open(self, event: str, value: float, **kw) -> None:
        if event in _KINDS:
            t = self._open
            if t.depth == 0:
                t.where = self._where()
            t.depth += 1

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        t = self._open
        if event == _CACHE_RETRIEVAL:
            # Fires inside the backend-compile interval of the same thread.
            t.hit = True
            return
        kind = _KINDS.get(event)
        if kind is None:
            return
        if t.depth:  # 0: installed while this interval was open
            t.depth -= 1
        backend = kind == "backend_s"
        if t.depth and not backend:
            return  # inside another interval: that one's seconds
        own = seconds
        if t.depth:  # a compilation inside a trace: taken off the trace
            t.nested_s += seconds
        else:
            own -= t.nested_s
            t.nested_s = 0.0
        name = _program(kw.get("fun_name"))
        where = t.where or self._where()
        traced = t.traced
        if traced is None or traced["name"] != name:
            traced = {"name": name, "trace_s": 0.0, "lower_s": 0.0}
        if backend:
            hit, t.hit, t.traced = t.hit, False, None
        else:
            traced[kind] += own
            t.traced = traced
        at_s = since_import()
        with self._lock:
            c = self.compiles
            program = self._program_row(name, at_s - seconds)
            span = self._row(self.by_span, where)
            program[kind] += own
            span[kind] += own
            program["max_s"] = max(program["max_s"], own)
            if not backend:
                c[kind] += own
                return
            for counts in (c, program, span):
                counts["n"] += 1
            c["s"] += seconds
            c["cache_hits"] += hit
            program["cache_hits"] += hit
            if hit:
                c["cache_load_s"] += seconds
            self.last.append({
                "fun_name": str(kw.get("fun_name")),
                "seconds": round(seconds, 6),
                "trace_s": round(traced["trace_s"], 6),
                "lower_s": round(traced["lower_s"], 6),
                "cache_hit": hit,
                "at_s": round(at_s, 3),
                "span": where,
            })

    def snapshot(self) -> dict:
        with self._lock:
            by_program = {k: dict(v) for k, v in self.by_program.items()}
            by_span = {k: dict(v) for k, v in self.by_span.items()}
            compiles = {**self.compiles, "last": list(self.last),
                        "stored": {**self.stored,
                                   "refused": dict(self.stored["refused"])}}
        return {
            "gc": {str(i): dict(g) for i, g in enumerate(self.gc)},
            "compiles": {**compiles, "programs": len(by_program),
                         "by_program": by_program, "by_span": by_span},
            "startup": {**self.startup, "phases": list(self.startup["phases"])},
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
