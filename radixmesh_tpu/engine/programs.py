"""The served programs, kept compiled from one start to the next.

JAX's persistent compilation cache is keyed by the LOWERED module: a warm
start still runs the model's Python into a jaxpr and lowers it to MLIR for
every launch shape, only to find the key of an executable it already has —
a third to a half of a warm set-up (PERF.md section 5, PR 51). A
:class:`ProgramStore` keys the executable by what it was BUILT FROM: the
first launch of a program at a shape compiles as ``jax.jit`` would
(``lower().compile()``), serialises the executable and writes it; at every
later start the first launch at that shape loads it and calls it — no
trace, no lowering, no look-up in JAX's cache.

A wrong executable answers wrongly without failing, so the key holds all
that the build read: the program's name, its static arguments, the tree,
shapes, dtypes and weak types of every traced argument (``None`` included),
its donation, JAX's own trace context, the ``jax`` / ``jaxlib`` / backend
versions, the device, ``XLA_FLAGS`` / ``LIBTPU_INIT_ARGS``, and a digest of
the CONTENTS of every ``.py`` of this package under its relative name (two
checkouts of one tree share entries; any edit retires them all). What the
key cannot see is a function patched in the running process: a test that
patches code under trace runs with no store (``tests/conftest.py``).

No knob of its own. The store lives where JAX's persistent cache is
configured, in a subdirectory (JAX's LRU lists only the ``*-cache`` files
of the top level), capped by ``jax_compilation_cache_max_size`` where that
is positive, least recently used first. No cache directory, a device mesh
or arguments that do not all live on one device: the ``jax.jit`` call as
before; an entry that cannot be read, unpacked or loaded: built again as
``jax.jit`` would and replaced — the reason counted either way
(:class:`~radixmesh_tpu.obs.tracing.ProcessAccount` ``stored``), never a
failed request. Entries are pickles under zstd: the directory is as trusted
as the compile cache beside it, whose executables the process loads too.
"""

from __future__ import annotations

import functools
import hashlib
import inspect
import os
import pickle
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import jax
import jaxlib
from jax import monitoring
from jax._src.config import trace_context
from jax.experimental.serialize_executable import deserialize_and_load, serialize

try:  # JAX's cache makes the same choice (an executable shrinks 4-5x)
    import zstandard
except ImportError:
    zstandard = None

from radixmesh_tpu.utils.logging import get_logger

__all__ = ["ProgramStore", "source_digest", "store_dir"]

_SUBDIR = "radixmesh-programs"
_SUFFIX = ".program"
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_STALE_S = 3600.0  # a temporary name this old has no writer any more
_NEW = object()  # a signature the table has not met (None: it stays on jax.jit)
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

log = get_logger("programs")


@functools.cache
def source_digest() -> str:
    """One digest of every ``.py`` of the package: relative name and
    contents, in sorted order. Read once a process — a deployed version
    does not change under a running server."""
    h = hashlib.sha256()
    for root, dirs, files in os.walk(_PACKAGE):
        dirs.sort()
        for name in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(root, name)
            h.update(os.path.relpath(path, _PACKAGE).encode() + b"\0")
            with open(path, "rb") as fh:
                h.update(fh.read() + b"\0")
    return h.hexdigest()


def store_dir() -> str | None:
    """The store's directory: beside JAX's persistent cache where that is
    in use on a local path, ``None`` where it is not."""
    cfg = jax.config
    root = cfg.jax_compilation_cache_dir
    if (not root or "://" in root or not cfg.jax_enable_compilation_cache
            or cfg.jax_compilation_cache_max_size == 0):
        return None
    return os.path.join(root, _SUBDIR)


def _pack(blob: bytes) -> bytes:
    if zstandard is not None:
        return b"S" + zstandard.ZstdCompressor().compress(blob)
    return b"L" + zlib.compress(blob)


def _unpack(data: bytes) -> bytes:
    codec, body = data[:1], data[1:]
    if codec == b"S" and zstandard is not None:
        return zstandard.ZstdDecompressor().decompress(body)
    if codec == b"L":
        return zlib.decompress(body)
    raise ValueError(f"an entry of codec {codec!r}")


def _aval(leaf) -> tuple:
    a = jax.typeof(leaf)
    return (a.shape, str(a.dtype), bool(a.weak_type))


def _tree_token(tree) -> str:
    """A pytree's structure and leaf types as one short string (a tuple of
    hundreds of leaves would be hashed anew on every launch)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    text = repr((str(treedef), [_aval(leaf) for leaf in leaves]))
    return hashlib.sha256(text.encode()).hexdigest()


class ProgramStore:
    """The launches of one engine: ``store(jitted, *args, **kwargs)`` is
    ``jitted(*args, **kwargs)``, through a stored executable where
    ``jitted`` is one of ``served`` (``{jitted function: its static
    argument names}``) and the store is in use.

    A launch splits static from traced arguments and looks an in-memory
    table up by a cheap signature: the static values and the shape, dtype
    and weak type of each array. An argument that is a pytree (``params``,
    a pool of two planes) or a static object (the model's config) is
    digested once and recognised by identity afterwards, so no launch walks
    the weights. A miss in the table builds the whole key and goes to the
    directory; a miss there compiles and writes. The executable checks its
    arguments itself: a signature that left something out raises
    ``TypeError``, it does not run.

    One thread launches (the engine's). An entry is serialised, packed and
    written by one writer thread of the store's own, started with the first
    write: the launch that compiled goes on at once (Ling's 26 programs are
    0.9 GB of executables, 15 s of a cold set-up when the engine thread
    wrote them; PERF.md section 6, PR 52); ``drain()`` waits for it. Two
    processes may write one entry (a temporary name and a rename)."""

    def __init__(self, served: dict, account=None, mesh=None):
        self.account = account
        self.dir = store_dir()
        self._served = {}
        self._seen: dict = {}  # argument slot -> (object, its token)
        self._writer: ThreadPoolExecutor | None = None
        self._writes: list = []  # the writer's futures not yet seen done
        off = "mesh" if mesh is not None else "no_cache_dir" if self.dir is None else None
        if off is not None:
            self._refused(off)
            return
        self._cap = jax.config.jax_compilation_cache_max_size
        for jitted, static_names in served.items():
            params = inspect.signature(jitted).parameters
            info = getattr(jitted, "_jit_info", None)  # the decorator's own
            self._served[jitted] = SimpleNamespace(
                jitted=jitted,
                name=jitted.__name__,
                static_names=frozenset(static_names),
                static_at={i: n for i, n in enumerate(params) if n in static_names},
                defaults={n: params[n].default for n in static_names},
                # The package's digest covers the decorator already; this
                # says it in the key where this JAX shows it.
                donation=repr(info and (info.donate_argnums, info.donate_argnames)),
                table={},
            )

    def __call__(self, jitted, *args, **kwargs):
        program = self._served.get(jitted)
        if program is None:
            return jitted(*args, **kwargs)
        static_at, static_names = program.static_at, program.static_names
        signature, traced, traced_kw = [], [], {}
        for i, value in enumerate(args):
            if i in static_at:
                signature.append(self._static_token(i, value))
            else:
                signature.append(self._traced_token(i, value))
                traced.append(value)
        for name, value in kwargs.items():
            if name in static_names:
                signature.append((name, self._static_token(name, value)))
            else:
                signature.append((name, self._traced_token(name, value)))
                traced_kw[name] = value
        signature = tuple(signature)
        call = program.table.get(signature, _NEW)
        if call is _NEW:
            call = program.table[signature] = self._executable(
                program, args, kwargs, traced, traced_kw)
        if call is None:
            return jitted(*args, **kwargs)
        return call(*traced, **traced_kw)

    def _once(self, slot, value, digest) -> str:
        """``digest(value)``, computed when ``slot`` last held another
        object: the weights and the model's config are recognised by
        identity, and held so that the identity stays theirs."""
        seen = self._seen.get(slot)
        if seen is None or seen[0] is not value:
            seen = self._seen[slot] = (value, digest(value))
        return seen[1]

    def _static_token(self, slot, value):
        if value is None or isinstance(value, (bool, int, str)):
            return value
        return self._once(slot, value, repr)

    def _traced_token(self, slot, value):
        """What the table's key holds of one traced argument."""
        if value is None:
            return None
        shape = getattr(value, "shape", None)
        if shape is not None:  # an array, the device's or numpy's
            return (shape, value.dtype, getattr(value, "weak_type", False))
        if isinstance(value, tuple):  # a pool of few planes: new every launch
            return tuple(self._traced_token(None, v) for v in value)
        return self._once(slot, value, _tree_token)

    def _executable(self, program, args, kwargs, traced, traced_kw):
        """The executable for one new signature — loaded, or compiled and
        written — or ``None`` where this launch stays on ``jax.jit``."""
        key = self._key(program, args, kwargs, traced, traced_kw)
        if key is None:
            return None
        text, device = key
        path = os.path.join(self.dir, hashlib.sha256(text.encode()).hexdigest() + _SUFFIX)
        compiled = self._load(program.name, path, text, device)
        if compiled is None:
            heard = set()  # what JAX's own cache says of this build

            def listener(event, **kw):
                heard.add(event)

            monitoring.register_event_listener(listener)
            try:
                compiled = program.jitted.lower(*args, **kwargs).compile()
            finally:
                monitoring.unregister_event_listener(listener)
            # XLA:CPU serialises an executable it was itself handed
            # serialised without its function library (jaxlib 0.9.0): the
            # entry loads and fails at its first call (NOT_FOUND). There only
            # what the compiler made in this call is stored — JAX's cache
            # was asked and had none; an executable memoised in the process
            # says nothing of where it came from. The TPU runtime hands back
            # what it was given (PERF.md section 6, PR 52).
            whole = device.platform != "cpu" or (
                _CACHE_ASKED in heard and _CACHE_HIT not in heard)
            if whole:
                if self._writer is None:
                    self._writer = ThreadPoolExecutor(
                        max_workers=1, thread_name_prefix="program-store")
                self._writes = [w for w in self._writes if not w.done()]
                self._writes.append(self._writer.submit(
                    self._write, program.name, path, text, compiled))
            else:
                self._refused("not_compiled_here")
                if self.account is not None:
                    self.account.program_missed(program.name, 0.0, 0)
        return compiled

    def _key(self, program, args, kwargs, traced, traced_kw):
        """``(key text, device)``, or ``None`` with the reason counted."""
        static = dict(program.defaults)
        static.update((n, args[i]) for i, n in program.static_at.items() if i < len(args))
        static.update((n, v) for n, v in kwargs.items() if n in program.static_names)
        static = sorted((n, repr(v)) for n, v in static.items())
        if any(" at 0x" in text for _, text in static):
            return self._refused("static")  # a rendering of its address
        leaves, tree = jax.tree_util.tree_flatten((tuple(traced), traced_kw))
        devices = set()
        for leaf in leaves:
            if isinstance(leaf, jax.Array):
                devices |= leaf.devices()
        if len(devices) != 1:
            return self._refused("placement")
        (device,) = devices
        client = device.client
        return repr((
            program.name, static, str(tree), [_aval(leaf) for leaf in leaves],
            program.donation, repr(trace_context()),
            jax.__version__, jaxlib.__version__, client.platform,
            client.platform_version, device.device_kind, device.id,
            jax.device_count(), os.environ.get("XLA_FLAGS", ""),
            os.environ.get("LIBTPU_INIT_ARGS", ""), source_digest(),
        )), device

    def _load(self, name, path, text, device):
        t0 = time.monotonic()
        try:
            with open(path, "rb") as fh:
                blob = fh.read()
        except FileNotFoundError:
            return None
        except OSError:
            log.warning("stored program %s: unreadable", path, exc_info=True)
            return self._refused("unreadable")
        try:
            entry = pickle.loads(_unpack(blob))
            if entry["key"] != text:
                raise ValueError("another key's entry under this name")
            payload, trees = entry["payload"], (entry["in_tree"], entry["out_tree"])
        except Exception:  # whatever a truncated pickle raises: compile, rewrite
            log.warning("stored program %s: corrupt, replaced", path, exc_info=True)
            return self._refused("corrupt")
        try:
            compiled = deserialize_and_load(
                payload, *trees, backend=device.client, execution_devices=[device])
        except Exception:  # the runtime's own refusal: compile, rewrite
            log.warning("stored program %s: did not load, replaced", path, exc_info=True)
            return self._refused("load")
        try:
            os.utime(path)  # used now: the last to be evicted
        except OSError:
            pass
        if self.account is not None:
            self.account.program_loaded(name, time.monotonic() - t0, len(blob))
        return compiled

    def drain(self) -> None:
        """Wait until every entry handed to the writer is on the disk (or
        refused): before a test looks, before another start is to find it."""
        for write in self._writes:
            write.result()
        self._writes = []

    def _write(self, name, path, text, compiled) -> None:
        """On the writer thread: ``compiled``'s entry, through a temporary
        name and a rename; the miss counted with the bytes written (0 with
        the reason counted)."""
        t0 = time.monotonic()
        nbytes = 0
        try:
            payload, in_tree, out_tree = serialize(compiled)
            blob = _pack(pickle.dumps({"key": text, "payload": payload,
                                       "in_tree": in_tree, "out_tree": out_tree}))
            partial = f"{path}.{os.getpid()}.tmp"
            os.makedirs(self.dir, exist_ok=True)
            with open(partial, "wb") as fh:
                fh.write(blob)
            os.replace(partial, path)
            nbytes = len(blob)
            self._evict()
        except OSError:
            log.warning("program %s: not written to %s", name, path, exc_info=True)
            self._refused("write")
        except Exception:  # an executable this runtime does not serialise
            log.warning("program %s: not serialisable, not stored", name, exc_info=True)
            self._refused("serialize")
        if self.account is not None:
            self.account.program_missed(name, time.monotonic() - t0, nbytes)

    def _evict(self) -> None:
        """Least recently used entries go until the directory fits the
        cap JAX's cache has (no cap where that is not positive); so does
        what a writer that was killed left under a temporary name."""
        entries, now = [], time.time()
        with os.scandir(self.dir) as it:
            for e in it:
                try:
                    st = e.stat()
                except FileNotFoundError:
                    continue  # another process evicted it
                if e.name.endswith(_SUFFIX):
                    entries.append((st.st_mtime_ns, st.st_size, e.path))
                elif e.name.endswith(".tmp") and now - st.st_mtime > _STALE_S:
                    entries.append((0, 0, e.path))
        total = sum(size for _, size, _ in entries)
        for stamp, size, path in sorted(entries):
            if stamp and (self._cap <= 0 or total <= self._cap):
                break
            try:
                os.unlink(path)
            except FileNotFoundError:
                pass
            total -= size

    def _refused(self, reason: str) -> None:
        if self.account is not None:
            self.account.program_refused(reason)
        return None
