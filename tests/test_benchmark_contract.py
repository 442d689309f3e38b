"""The program keeps the benchmark's counters.

``BENCHMARK.json`` lists per-layer metrics whose ``source`` is
``program_counter`` or ``program_span``: each is computed by a reader
(``benchmarks/layer_metrics/<name>.py::read(ctx)``) from what the server
publishes on ``/debug/state`` and ``/stats`` before and after a cell's
window. A reader answers ``None`` when a key it looks for is gone, the
ledger then records ``null``, and the chip is the first to find out. This
file finds out on a CPU: it serves every configuration of the manifest at
the tiny size its own file names (``rehearsal``: the twin that
``benchmarks/run.py --rehearse-cpu`` uses), in process, sends a few
requests that share a prefix and take a second turn, snapshots both
endpoints the way ``run.py`` does, and holds every (metric, cell) pair
whose reader needs nothing but those snapshots to a finite number — with
every key the reader asked for present. It reads the manifest and the
readers as they are and edits nothing under ``benchmarks/``; a whole
rehearsed run of a cell stays under ``benchmarks/tests/``.
"""

from __future__ import annotations

import argparse
import json
import math
import threading
import urllib.request

import numpy as np
import pytest

from benchmarks.harness import registry
from benchmarks.run import snapshot  # both endpoints, as a cell's run reads them

MANIFEST = registry.manifest()

# Readers of these ``program_span`` metrics need a profiler capture or the
# server's stderr, which a tier-1 test does not take: their pairs are no
# cases here (``benchmarks/tests/`` holds their readers to fixtures).
NEEDS_CAPTURE = {
    "compiles_in_window",
    "step_host_exposed_ms",
    "between_steps_idle_ms",
    "idle_attributed_share",
}

# Pairs whose reader a CPU run at the twin's size cannot bring to a number,
# each with its reason. The reader still runs, still finds every key it
# asks for, and must answer None (a number means: take the pair out). At
# most ten.
_HOLDS_EVERY_EXPERT = (
    "the twin holds every expert (ep_size 1); the program publishes "
    "moe.<site>.assignments_elsewhere only under an expert share"
)
NO_NUMBER_ON_CPU: dict[tuple[str, str], str] = {
    ("moe_routed_here_share", "glm-5.2-ep16.repo-hotset"): _HOLDS_EVERY_EXPERT,
    ("moe_routed_here_share.ep8", "lfm2-24b-a2b-ep8.agent-sessions"): _HOLDS_EVERY_EXPERT,
}


def _cases():
    for m in MANIFEST["per_layer"]:
        if m["source"] not in ("program_counter", "program_span"):
            continue
        if m["name"].split(".")[0] in NEEDS_CAPTURE:
            continue
        for w in MANIFEST["workloads"]:
            if "workloads" in m and w["name"] not in m["workloads"]:
                continue
            yield pytest.param(
                w["config"], m["name"], w["name"], id=f"{w['name']}:{m['name']}"
            )


CASES = list(_cases())


class _Asked(dict):
    """A snapshot that remembers the keys a reader asked for with
    ``.get`` and did not find: the readers tolerate an older program that
    way, and that tolerance is what turns a renamed counter into a null."""

    missing: list

    def get(self, key, default=None):
        if key not in self:
            self.missing.append(key)
        return super().get(key, default)


def _asked(obj, missing: list):
    if isinstance(obj, dict):
        out = _Asked((k, _asked(v, missing)) for k, v in obj.items())
        out.missing = missing
        return out
    if isinstance(obj, list):
        return [_asked(v, missing) for v in obj]
    return obj


def _server_args(argv: list[str]) -> argparse.Namespace:
    """The rehearsal twins' ``server_args``, as ``launch.py serve`` reads
    them; an argument this does not know fails the fixture by name."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--page-size", type=int, required=True)
    ap.add_argument("--kv-slots", type=int, required=True)
    ap.add_argument("--max-batch", type=int, required=True)
    ap.add_argument("--weight-quant", default=None)
    return ap.parse_args([str(a) for a in argv])


def _generate(base: str, prompt: list[int], max_tokens: int) -> list[int]:
    """One streamed ``POST /generate``, read to its ``done`` event."""
    req = urllib.request.Request(
        base + "/generate",
        data=json.dumps({
            "input_ids": prompt, "max_tokens": max_tokens, "stream": True,
            "temperature": 0.0, "stop_token_ids": [],
        }).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=300) as resp:
        events = [json.loads(raw[5:]) for raw in resp if raw.startswith(b"data:")]
    done = events[-1]
    assert done.get("done") and not done.get("error"), done
    assert len(done["output_ids"]) == max_tokens, done
    return done["output_ids"]


def _drive(base: str, vocab: int, seed: int) -> None:
    """Three sessions that share a prefix, two turns each; the sessions of
    a turn are in flight together, so decode steps hold several rows. No
    length is a page multiple: a model with state slots then resumes at a
    snapshot on the second turn."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, vocab, size=48).tolist()
    prompts = [shared + rng.integers(1, vocab, size=20).tolist() for _ in range(3)]
    errors: list = []

    def turn(i: int, user: list[int]) -> None:
        try:
            prompts[i] = prompts[i] + _generate(base, prompts[i], 8) + user
        except Exception as e:  # noqa: BLE001 — raised below, on the test's thread
            errors.append(e)

    for _ in range(2):
        threads = [
            threading.Thread(
                target=turn, args=(i, rng.integers(1, vocab, size=12).tolist())
            )
            for i in range(len(prompts))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads), "a request never returned"
        assert not errors, errors


def _window_of(config_name: str) -> dict:
    """One configuration served in process at its twin's size, warmed,
    driven and snapshotted: what a reader's ``ctx`` holds of a window."""
    import jax

    from radixmesh_tpu.engine.engine import Engine
    from radixmesh_tpu.models import get_config, init_params
    from radixmesh_tpu.obs.tracing import ProcessAccount
    from radixmesh_tpu.server.http_frontend import ServingFrontend

    entry = next(c for c in MANIFEST["configs"] if c["name"] == config_name)
    with open(f"{registry.REPO}/{entry['file']}") as fh:
        config = json.load(fh)
    twin = config["rehearsal"]
    args = _server_args(twin["server_args"])
    cfg = get_config(twin["preset"])
    # launch.py serve installs the account and marks the start-up's phases;
    # a frontend built by hand has no ``process`` group without the one and
    # no ``startup.ready_at_s`` without the other.
    account = ProcessAccount().install()
    with account.phase("engine"):
        engine = Engine(
            cfg,
            init_params(cfg, jax.random.PRNGKey(0), weight_quant=args.weight_quant),
            num_slots=args.kv_slots,
            page_size=args.page_size,
            max_batch=args.max_batch,
            weight_quant=args.weight_quant,
            process_account=account,
        )
    frontend = ServingFrontend(engine, port=0)
    base = f"http://127.0.0.1:{frontend.port}"
    try:
        # As run.py does: traffic of the window's kind first (every span
        # and counter has been entered once), then the window.
        _drive(base, twin["vocab"], seed=1)
        before = snapshot(base)
        _drive(base, twin["vocab"], seed=0)
        after = snapshot(base)
    finally:
        frontend.close()
        account.uninstall()
    # One poll of the pool, as run.py takes many through a window.
    st = after["state"]
    polls = [(after["t"], st["pool"]["free_slots"], st["pool"]["num_slots"],
              st["cache"].get("evictable_tokens") or 0)]
    return {"before": before, "after": after, "polls": polls,
            "window": (before["t"], after["t"]), "config": config}


@pytest.fixture(scope="module")
def window_of():
    """Configuration name -> its window, served once, on first use."""
    windows: dict[str, dict] = {}

    def of(config_name: str) -> dict:
        if config_name not in windows:
            windows[config_name] = _window_of(config_name)
        return windows[config_name]

    return of


@pytest.mark.parametrize("config, metric, cell", CASES)
def test_reader_gets_a_number(window_of, config, metric, cell):
    window = window_of(config)
    missing: list = []
    ctx = {
        **window,
        "before": _asked(window["before"], missing),
        "after": _asked(window["after"], missing),
        "cell": registry.load_json("cells", cell),
    }
    value = registry.load_module("layer_metrics", metric).read(ctx)
    assert missing == [], f"{metric} asked the snapshots for {missing}: not there"
    if (metric, cell) in NO_NUMBER_ON_CPU:
        assert value is None, f"{metric} reads {value}: take it out of the table"
        return
    assert value is not None, f"{metric} read nothing from the snapshots"
    assert math.isfinite(value), value


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_the_window_ran_clean(window_of, config):
    """A number read from a window whose requests failed means nothing:
    no step of the twin failed, and every request sent has finished."""
    after = window_of(config)["after"]
    assert after["state"]["engine"]["step_failures"] == 0
    assert after["stats"]["finished"] == 12  # two drives of three sessions, two turns


def test_the_contract_covers_the_manifest():
    """Every counter pair is a case, the table of exceptions is short and
    names real pairs, and a span metric left out is one that needs a
    capture."""
    counters = sum(
        len(m.get("workloads", MANIFEST["workloads"]))
        for m in MANIFEST["per_layer"] if m["source"] == "program_counter"
    )
    pairs = {(c.values[1], c.values[2]) for c in CASES}
    assert len(CASES) >= counters > 0
    assert len(NO_NUMBER_ON_CPU) <= 10 and set(NO_NUMBER_ON_CPU) <= pairs
    names = {m["name"].split(".")[0] for m in MANIFEST["per_layer"]}
    assert NEEDS_CAPTURE <= names
