"""A configuration, a traffic mix, a cell and a per-layer metric dropped
into a copy of the benchmark are found by name — no existing file edited —
and the whole command runs with them (CPU rehearsal). And the command
refuses to measure on a CPU."""
import json
import os
import shutil
import signal
import subprocess
import sys

import pytest

from benchmarks.harness import registry

REPO = registry.REPO


def run_cmd(argv, cwd, env, timeout):
    """One subprocess with a time limit; its whole process group is killed
    in a ``finally`` (the command starts a server of its own)."""
    proc = subprocess.Popen(argv, cwd=cwd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
        return proc.returncode, out, err
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait(timeout=30)


@pytest.fixture
def copy(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", ".work", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    os.symlink(os.path.join(REPO, "radixmesh_tpu"), tmp_path / "radixmesh_tpu")
    return tmp_path


def add_dummies(copy):
    b = copy / "benchmarks"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    conf = json.loads((b / "configs" / "qwen2-7b-w8a16.json").read_text())
    conf["name"] = "dummy-config"
    (b / "configs" / "dummy-config.json").write_text(json.dumps(conf))
    mix = json.loads((b / "traffic" / "batch-unshared.json").read_text())
    mix["name"] = "dummy-mix"
    mix["params"]["mix_seed"] = 5
    (b / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    cell = json.loads((b / "cells" / "qwen2-7b.batch-unshared.json").read_text())
    cell.update(name="dummy.cell", config="dummy-config", traffic="dummy-mix")
    (b / "cells" / "dummy.cell.json").write_text(json.dumps(cell))
    (b / "layer_metrics" / "dummy_metric.py").write_text(
        "def read(ctx):\n    return float(ctx['summary']['attempted'])\n")
    man = json.loads((copy / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "dummy-config", "source": conf["source"],
                           "file": "benchmarks/configs/dummy-config.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "dummy.cell", "config": "dummy-config",
                             "traffic": "dummy-mix", "chips": 1, "why": "test"})
    man["per_layer"].append({"name": "dummy_metric", "unit": "count",
                             "better": "higher", "source": "program_counter",
                             "layer": "load generator", "moves": "itl_p95_ms",
                             "workloads": ["dummy.cell"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(man))
    return before


def test_dummy_cell_is_found_by_name_and_runs(copy):
    before = add_dummies(copy)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    rc, out, err = run_cmd(
        [sys.executable, "benchmarks/run.py", "--workload", "dummy.cell",
         "--seed", "3", "--seconds", "3", "--trace", "1", "--rehearse-cpu"],
        cwd=copy, env=env, timeout=300)
    assert rc == 0, err[-2000:]
    result = json.loads(out.strip().splitlines()[-1])
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["device"]["platform"] == "cpu" and "rehearsal" in result
    assert result["metrics"]["dummy_metric"]["value"] == result["attempted"] > 0
    # only the dummy cell's per-layer metrics, and no file that was there changed
    assert "prefix_hit_share" not in result["metrics"]
    b = copy / "benchmarks"
    assert all(p.read_bytes() == data for p, data in before.items())


def test_an_unknown_workload_is_an_error(copy):
    with pytest.raises(KeyError):
        registry.resolve("no.such.cell", root=str(copy / "benchmarks"))


def test_refuses_to_measure_on_a_cpu():
    rc, out, err = run_cmd(
        [sys.executable, "benchmarks/run.py", "--workload",
         "qwen2-7b.batch-unshared", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert rc != 0
    assert out.strip() == ""  # no result line
    assert "TPU" in err


def test_refuses_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark: the
    server cannot start, so no result and a non-zero exit."""
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", ".work", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    rc, out, err = run_cmd(
        [sys.executable, "benchmarks/run.py", "--workload",
         "qwen2-7b.batch-unshared", "--seed", "1", "--seconds", "1", "--trace", "0",
         "--rehearse-cpu"],
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert rc != 0
    assert not any(line.startswith('{"correct"') for line in out.splitlines())
