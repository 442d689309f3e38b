"""The program store (``engine/programs.py``, PR 52): a start that finds
an executable for what it is about to build loads it instead of tracing,
lowering and looking it up.

The store alone, over a toy program with everything the served ones have
(a dict of weights, static arguments by position and by name, a donated
positional and a donated keyword argument, an optional one) and JAX's cache
directory at a temporary path — that setting is all that turns the store on
(``conftest.py::program_store``); then two engines in turn over one store,
for every kind of model the engine serves."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from radixmesh_tpu.engine import programs
from radixmesh_tpu.engine.engine import Engine
from radixmesh_tpu.engine.programs import ProgramStore
from radixmesh_tpu.engine.request import SamplingParams
from radixmesh_tpu.models import get_config, init_params
from radixmesh_tpu.obs.tracing import ProcessAccount

pytestmark = pytest.mark.quick

STATIC = ("k", "mode")


def program(traces: list):
    """The toy as a start defines it: a new function object every time, as
    in a new process; ``traces`` grows whenever its body runs."""

    @functools.partial(jax.jit, static_argnames=STATIC, donate_argnums=(2,),
                       donate_argnames=("extra",))
    def toy(params, k, pool, x, mode="a", opt=None, extra=None):
        traces.append(mode)

        def body(carry, _):
            return (carry * params["w"].sum() + k).astype(carry.dtype), None

        y, _ = jax.lax.scan(body, x, None, length=3)
        if mode == "b":
            y = -y
        if opt is not None:
            y = y + opt
        pool = pool.at[0].set(y[0].astype(pool.dtype))
        return (y, pool) if extra is None else (y, pool, extra + 1)

    return toy


def arguments(n=4, dtype=jnp.float32):
    params = {"w": jnp.arange(1.0, 4.0), "b": {"c": jnp.zeros((2,), jnp.bfloat16)}}
    return params, jnp.zeros((8,)), jnp.arange(n).astype(dtype)


def start(toy, **kw):
    """A start's launcher: ``start(toy)(toy, *args)`` is the store's call,
    back once the launch's entry is on the disk (the store's writer thread
    has it; the next start is to find it)."""
    store = ProgramStore({toy: STATIC}, **kw)

    def launch(*args, **kwargs):
        out = store(*args, **kwargs)
        store.drain()
        return out

    return launch


def entries(store_dir):
    return sorted(os.listdir(store_dir)) if os.path.isdir(store_dir) else []


def forget_jax_cache(store_dir):
    """Empty JAX's own cache (the store's parent directory): the next build
    of a program is the compiler's again, not a handed-back executable."""
    for name in os.listdir(os.path.dirname(store_dir)):
        if name.endswith(("-cache", "-atime")):
            os.unlink(os.path.join(os.path.dirname(store_dir), name))


def test_a_second_start_runs_the_program_without_tracing_it(program_store):
    traces = []
    params, pool, x = arguments()
    toy = program(traces)
    first = ProcessAccount()
    out = start(toy, account=first)(toy, params, 3, pool, x, mode="a", extra=jnp.ones(2))
    assert traces == ["a"] and len(entries(program_store)) == 1
    stored = first.snapshot()["compiles"]["stored"]
    size = os.path.getsize(os.path.join(program_store, entries(program_store)[0]))
    assert (stored["hits"], stored["misses"], stored["bytes"], stored["refused"]) == (
        0, 1, size, {})
    assert stored["write_s"] > 0.0

    again = program(traces)  # the next process's function object
    second = ProcessAccount()
    store = start(again, account=second)
    _, pool, _ = arguments()
    got = store(again, params, 3, pool, x, mode="a", extra=jnp.ones(2))
    assert traces == ["a"] and again._cache_size() == 0  # neither traced nor jitted
    assert len(got) == len(out) == 3
    for a, b in zip(got, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _, pool, _ = arguments()
    store(again, params, 3, pool, x, mode="a", extra=jnp.ones(2))  # the table's
    c = second.snapshot()["compiles"]
    assert c["stored"] == {"hits": 1, "misses": 0, "load_s": c["stored"]["load_s"],
                           "write_s": 0.0, "bytes": size, "refused": {}}
    row = c["by_program"]["toy"]
    assert (row["hits"], row["n"], row["trace_s"]) == (1, 0, 0.0)
    assert row["load_s"] == c["stored"]["load_s"] > 0.0 and row["first_at_s"] >= 0.0
    assert len(entries(program_store)) == 1


def test_the_launch_that_compiled_does_not_wait_for_the_entry(
        program_store, monkeypatch):
    """The entry is serialised and written on the store's own thread: the
    launch returns while the writer is still held, ``drain`` waits for it."""
    import threading

    release, seen = threading.Event(), []
    serialize = programs.serialize

    def held(compiled):
        seen.append(threading.current_thread().name)
        assert release.wait(timeout=60)
        return serialize(compiled)

    monkeypatch.setattr(programs, "serialize", held)
    params, pool, x = arguments()
    toy = program([])
    acct = ProcessAccount()
    store = ProgramStore({toy: STATIC}, account=acct)
    out = store(toy, params, 3, pool, x)  # back with the writer still held
    assert out[0].shape == (4,) and entries(program_store) == []
    assert acct.snapshot()["compiles"]["stored"]["misses"] == 0
    release.set()
    store.drain()
    store.drain()  # nothing left: returns at once
    assert seen and seen[0].startswith("program-store")
    assert seen[0] != threading.current_thread().name
    assert len(entries(program_store)) == 1
    assert acct.snapshot()["compiles"]["stored"]["misses"] == 1


def test_donation_holds_after_a_load(program_store):
    params, pool, x = arguments()
    toy = program([])
    start(toy)(toy, params, 3, pool, x, extra=jnp.ones(2))
    again = program([])
    _, pool, _ = arguments()
    extra = jnp.ones(2)
    _, new_pool, _ = start(again)(again, params, 3, pool, x, extra=extra)
    assert pool.is_deleted() and extra.is_deleted()
    assert not x.is_deleted() and not new_pool.is_deleted()
    assert not params["w"].is_deleted()


CHANGES = {
    # what the second start calls with; all else as the first
    "static_value": dict(k=4),
    "static_by_name": dict(mode="b"),
    "shape": dict(n=5),
    "dtype": dict(dtype=jnp.int32),
    "none_becomes_an_array": dict(opt=jnp.ones(4)),
    "weak_type": dict(opt=2.0),
    "tree": dict(weights={"w": jnp.arange(1.0, 4.0)}),
    "source_digest": dict(),
}


@pytest.mark.parametrize("what", CHANGES)
def test_the_key_changes_with(what, program_store, monkeypatch):
    """Nothing a program was built from may differ and still find its
    entry: the second start compiles (its body runs) and writes a second
    entry, and what it returns is the changed program's."""
    traces = []

    def call(store, toy, k=3, mode="a", n=4, dtype=jnp.float32, opt=None,
             weights=None):
        params, pool, x = arguments(n, dtype)
        return store(toy, weights or params, k, pool, x, mode=mode, opt=opt)

    toy = program(traces)
    base = call(start(toy), toy)
    if what == "source_digest":
        monkeypatch.setattr(programs, "source_digest", lambda: "another tree")
    if what in ("source_digest", "tree"):  # the same module under another key
        forget_jax_cache(program_store)
    again = program(traces)
    acct = ProcessAccount()
    got = call(start(again, account=acct), again, **CHANGES[what])
    stored = acct.snapshot()["compiles"]["stored"]
    assert (stored["hits"], stored["misses"], stored["refused"]) == (0, 1, {})
    assert len(traces) == 2 and len(entries(program_store)) == 2
    want = call(lambda f, *a, **kw: f(*a, **kw), program([]), **CHANGES[what])
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if what in ("static_value", "static_by_name", "none_becomes_an_array"):
        assert not np.array_equal(np.asarray(got[0]), np.asarray(base[0]))


def test_a_truncated_entry_falls_back_is_counted_and_is_rewritten(program_store):
    traces = []
    params, pool, x = arguments()
    toy = program(traces)
    want = start(toy)(toy, params, 3, pool, x)
    (name,) = entries(program_store)
    path = os.path.join(program_store, name)
    whole = os.path.getsize(path)
    with open(path, "r+b") as fh:
        fh.truncate(whole // 2)
    forget_jax_cache(program_store)

    acct = ProcessAccount()
    again = program(traces)
    _, pool, _ = arguments()
    got = start(again, account=acct)(again, params, 3, pool, x)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    stored = acct.snapshot()["compiles"]["stored"]
    assert (stored["hits"], stored["misses"], stored["refused"]) == (
        0, 1, {"corrupt": 1})
    assert traces == ["a", "a"]  # built again, not trusted
    assert entries(program_store) == [name] and os.path.getsize(path) > whole // 2

    acct = ProcessAccount()
    third = program(traces)
    _, pool, _ = arguments()
    start(third, account=acct)(third, params, 3, pool, x)
    stored = acct.snapshot()["compiles"]["stored"]
    assert (stored["hits"], stored["refused"], len(traces)) == (1, {}, 2)


def test_another_keys_entry_under_the_name_is_not_trusted(program_store):
    """An entry says which key it was written for: one that landed under
    another key's file name (a copy, a collision) is replaced."""
    params, pool, x = arguments()
    toy = program([])
    start(toy)(toy, params, 3, pool, x)
    _, pool, x5 = arguments(5)
    start(toy)(toy, params, 3, pool, x5)
    four, five = (os.path.join(program_store, n) for n in entries(program_store))
    with open(five, "rb") as src, open(four, "wb") as dst:
        dst.write(src.read())
    forget_jax_cache(program_store)
    acct = ProcessAccount()
    again = program([])
    for xs in (x, x5):
        _, pool, _ = arguments()
        out = start(again, account=acct)(again, params, 3, pool, xs)
        assert out[0].shape == xs.shape
    stored = acct.snapshot()["compiles"]["stored"]
    assert (stored["hits"], stored["misses"], stored["refused"]) == (
        1, 1, {"corrupt": 1})


def test_on_the_cpu_a_handed_back_executable_is_run_and_not_stored(program_store):
    """XLA:CPU serialises an executable that JAX's own cache handed back
    without its function library: such an entry would load and fail at its
    first call. With the store gone and JAX's cache still there the build
    is a hit; it runs, and the store takes nothing."""
    assert jax.devices()[0].platform == "cpu"
    traces = []
    params, pool, x = arguments()
    toy = program(traces)
    want = start(toy)(toy, params, 3, pool, x)
    (name,) = entries(program_store)
    os.unlink(os.path.join(program_store, name))
    for _ in range(2):  # and no later start finds a half-made entry
        acct = ProcessAccount()
        again = program(traces)
        _, pool, _ = arguments()
        got = start(again, account=acct)(again, params, 3, pool, x)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        stored = acct.snapshot()["compiles"]["stored"]
        assert (stored["hits"], stored["misses"], stored["bytes"], stored["refused"]) == (
            0, 1, 0, {"not_compiled_here": 1})
        assert entries(program_store) == []
    assert len(traces) == 3


def test_the_cap_evicts_the_least_recently_used(program_store):
    params, _, _ = arguments()
    traces = []

    def launch(n):
        fresh = program(traces)
        _, pool, x = arguments(n)
        return start(fresh)(fresh, params, 3, pool, x)

    launch(4)
    (first,) = entries(program_store)
    launch(5)
    (second,) = set(entries(program_store)) - {first}
    sizes = [os.path.getsize(os.path.join(program_store, n)) for n in (first, second)]
    for age, name in ((300, first), (200, second)):  # seconds ago
        at = os.stat(program_store).st_mtime - age
        os.utime(os.path.join(program_store, name), (at, at))
    # what two killed writers left: one long ago, one that may still write
    for name, age in (("dead.1.tmp", 7200), ("live.2.tmp", 5)):
        with open(os.path.join(program_store, name), "wb") as fh:
            fh.write(b"half an entry")
        at = os.stat(program_store).st_mtime - age
        os.utime(os.path.join(program_store, name), (at, at))
    old = jax.config.jax_compilation_cache_max_size
    jax.config.update("jax_compilation_cache_max_size", sum(sizes) + min(sizes) // 2)
    try:
        launch(4)  # a hit: the oldest entry is the most recently used now
        assert len(traces) == 2
        launch(6)  # a third entry does not fit beside the two
    finally:
        jax.config.update("jax_compilation_cache_max_size", old)
    assert len(traces) == 3
    left = entries(program_store)
    assert "live.2.tmp" in left and "dead.1.tmp" not in left
    left.remove("live.2.tmp")
    assert first in left and second not in left and len(left) == 2


@pytest.mark.parametrize("setting,value,there", [
    ("jax_compilation_cache_dir", None, False),
    ("jax_compilation_cache_dir", "gs://bucket/xla", False),  # no local path
    ("jax_enable_compilation_cache", False, False),
    ("jax_compilation_cache_max_size", 0, False),  # JAX's way to say "off"
    ("jax_compilation_cache_max_size", 1 << 20, True),
])
def test_the_store_lives_beside_jaxs_cache_or_nowhere(
        setting, value, there, program_store):
    assert program_store == os.path.join(
        jax.config.jax_compilation_cache_dir, "radixmesh-programs")
    old = getattr(jax.config, setting)
    jax.config.update(setting, value)
    try:
        assert programs.store_dir() == (program_store if there else None)
    finally:
        jax.config.update(setting, old)


@pytest.mark.parametrize("why", ["no_cache_dir", "cache_disabled", "mesh"])
def test_without_a_store_a_launch_is_jax_jits_own(why, program_store):
    traces = []
    toy = program(traces)
    params, pool, x = arguments()
    kw, restore = {}, []
    if why == "mesh":
        kw["mesh"] = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("tp",))
    else:
        name, off = {"no_cache_dir": ("jax_compilation_cache_dir", None),
                     "cache_disabled": ("jax_enable_compilation_cache", False)}[why]
        restore = [(name, getattr(jax.config, name))]
        jax.config.update(name, off)
    acct = ProcessAccount()
    try:
        store = start(toy, account=acct, **kw)
        out = store(toy, params, 3, pool, x, mode="a")
        _, pool, _ = arguments()
        store(toy, params, 3, pool, x, mode="a")
    finally:
        for name, value in restore:
            jax.config.update(name, value)
    assert traces == ["a"] and toy._cache_size() == 1  # jit's own cache served
    assert entries(program_store) == []
    stored = acct.snapshot()["compiles"]["stored"]
    assert stored == {"hits": 0, "misses": 0, "load_s": 0.0, "write_s": 0.0, "bytes": 0,
                      "refused": {"mesh" if why == "mesh" else "no_cache_dir": 1}}
    np.testing.assert_array_equal(
        np.asarray(out[0]), np.asarray(program([])(params, 3, arguments()[1], x)[0]))


class _Unstable:
    """A static value whose rendering is its address."""

    def __hash__(self):
        return 7

    def __eq__(self, other):
        return isinstance(other, _Unstable)


@pytest.mark.parametrize("why", ["static", "placement", "not_served"])
def test_what_the_key_cannot_hold_stays_on_jax_jit(why, program_store):
    traces = []
    toy = program(traces)
    params, pool, x = arguments()
    acct = ProcessAccount()
    store = start(toy, account=acct)
    if why == "static":  # no two starts would render it alike
        out = store(toy, params, 3, pool, x, mode=_Unstable())
    elif why == "placement":  # no argument says which device
        params = jax.tree_util.tree_map(np.asarray, params)
        out = store(toy, params, 3, np.asarray(pool), np.asarray(x))
    else:  # whatever else stands under the name: called as it is
        other = functools.partial(toy, mode="b")
        out = store(other, params, 3, pool, x)
    assert len(traces) == 1 and toy._cache_size() == 1
    assert entries(program_store) == []
    refused = acct.snapshot()["compiles"]["stored"]["refused"]
    assert refused == ({} if why == "not_served" else {why: 1})
    assert out[0].shape == (4,)


# ---- two engines in turn over one store ----------------------------------

MODELS = ["llama3-tiny", "qwen2-tiny", "mellum2-tiny", "glm-4.7-flash-tiny",
          "glm-5.2-tiny", "lfm2-tiny", "phi4flash-tiny", "laguna-tiny", "ling-tiny",
          "nemotron-tiny"]
SERVED = ("prefill_chunk_paged", "decode_step", "prefill_forward")


def serve(model, account, max_batch=4):
    """A start: an engine of its own, three prompts together (a dense
    launch, a chunk group), then one on a published prefix."""
    cfg, params = model
    eng = Engine(cfg, params, num_slots=2048, page_size=16, max_batch=max_batch,
                 max_seq_len=256, prefill_chunk=32, process_account=account)
    rng = np.random.default_rng([52, 1])
    prompts = [rng.integers(1, 500, n).tolist() for n in (5, 40, 75)]
    sampling = SamplingParams(max_new_tokens=6)
    tokens = eng.generate(prompts, sampling)
    tokens += eng.generate([prompts[2] + [7, 8, 9]], sampling)
    eng._launch.drain()  # the next start is to find every entry
    return eng, tokens


@pytest.mark.parametrize("name", MODELS)
def test_a_second_engine_serves_the_same_tokens_from_the_first_ones_programs(
        name, program_store):
    cfg = get_config(name)
    model = cfg, init_params(cfg, jax.random.PRNGKey(52))
    runs = []
    for _ in range(2):  # one account a start: each hears its own only
        acct = ProcessAccount().install()
        try:
            runs.append((*serve(model, acct), acct.snapshot()["compiles"]))
        finally:
            acct.uninstall()
    (first, want, built), (second, got, loaded) = runs
    assert got == want
    assert built["stored"]["refused"] == loaded["stored"]["refused"] == {}
    assert built["stored"]["hits"] == 0 and built["stored"]["misses"] >= 2
    assert loaded["stored"]["hits"] == built["stored"]["misses"]
    assert loaded["stored"]["misses"] == 0
    assert len(entries(program_store)) == built["stored"]["misses"]
    for program_name in SERVED:
        before = built["by_program"].get(program_name)
        if before is None:
            assert program_name not in loaded["by_program"]
            continue
        after = loaded["by_program"][program_name]
        assert before["trace_s"] > 0.0 and before["n"] == after["hits"] > 0
        assert (after["n"], after["trace_s"], after["lower_s"], after["backend_s"]) == (
            0, 0.0, 0.0, 0.0)
    # the same spans, as often: a loaded program is launched where a built one was
    assert second.spans.n == first.spans.n
    assert second.prefill["by_path"] == first.prefill["by_path"]


def test_an_engine_without_a_cache_directory_launches_as_before():
    """No ``program_store``: the suite's engines (``conftest.py``), like a
    plain ``serve``, stay on ``jax.jit``, the reason counted once."""
    cfg = get_config("llama3-tiny")
    acct = ProcessAccount().install()
    try:
        # (a batch of its own: no program of the test above, memoised)
        eng, tokens = serve(
            (cfg, init_params(cfg, jax.random.PRNGKey(52))), acct, max_batch=2)
        c = acct.snapshot()["compiles"]
    finally:
        acct.uninstall()
    assert eng._launch.dir is None and len(tokens) == 4
    assert c["stored"] == {"hits": 0, "misses": 0, "load_s": 0.0, "write_s": 0.0,
                           "bytes": 0, "refused": {"no_cache_dir": 1}}
    assert c["by_program"]["decode_step"]["trace_s"] > 0.0
