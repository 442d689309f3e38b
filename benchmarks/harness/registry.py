"""Everything is found BY NAME under ``benchmarks/``: a workload names a
cell file, the cell names its configuration and traffic files, the traffic
names its generator, the configuration names its launcher, a per-layer
metric names its reader. Adding one is adding a file (and a manifest
entry); no file here is edited for it."""
from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)


def manifest(root: str = ROOT) -> dict:
    with open(os.path.join(os.path.dirname(root), "BENCHMARK.json")) as fh:
        return json.load(fh)


def load_json(kind: str, name: str, root: str = ROOT) -> dict:
    path = os.path.join(root, kind, name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    with open(path) as fh:
        return json.load(fh)


def load_module(kind: str, name: str, root: str = ROOT):
    """``benchmarks/<kind>/<name>.py`` as a module (names may hold dots
    and hyphens, so this goes by path, not by import name)."""
    path = os.path.join(root, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {path}")
    modname = f"_bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, root: str = ROOT) -> dict:
    """A workload name -> its cell, configuration, traffic mix, generator
    and launcher, and the metric names the manifest lists for it."""
    man = manifest(root)
    entry = next((w for w in man["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(
            f"workload {workload!r} is not in BENCHMARK.json "
            f"(has: {[w['name'] for w in man['workloads']]})"
        )
    cell = load_json("cells", workload, root)
    if cell["config"] != entry["config"] or cell["traffic"] != entry["traffic"]:
        raise ValueError(
            f"cell file {workload}.json ({cell['config']}/{cell['traffic']}) "
            f"disagrees with BENCHMARK.json ({entry['config']}/{entry['traffic']})"
        )
    conf_entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    with open(os.path.join(os.path.dirname(root), conf_entry["file"])) as fh:
        config = json.load(fh)
    traffic = load_json("traffic", cell["traffic"], root)

    def listed(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "workload": workload,
        "chips": entry["chips"],
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "generator": load_module("generators", traffic["generator"], root),
        "launcher": load_module("launchers", config["launcher"], root),
        "end_to_end": [m for m in man["end_to_end"] if listed(m)],
        "per_layer": [m for m in man["per_layer"] if listed(m)],
    }
