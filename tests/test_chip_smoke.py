"""``chip_smoke.py`` on a machine without a TPU: it refuses, and the only
way round is the rehearsal argument — which runs every phase at tiny size
on the CPU and says so in every line it prints."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, devices: int = 1, timeout: float = 240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    if not os.environ.get("RADIXMESH_NO_COMPILE_CACHE"):
        # The suite's persistent compile cache (conftest.py), handed to
        # the smoke's child processes through the environment.
        env.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/radixmesh_xla_cache")
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_cpu_only_process_exits_nonzero_and_prints_no_result():
    p = _smoke()
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout == ""  # no phase ran, no result line
    assert "needs a TPU" in p.stderr and "'platform': 'cpu'" in p.stderr


def _assert_rehearsal(p, count: int) -> list[str]:
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": count},
    }
    # Every line names platform, kind and count — and that it is no chip run.
    for line in lines[:-1]:
        assert line.startswith(f"[cpu/cpu x{count}] [REHEARSAL"), line
    return lines


def test_cpu_rehearsal_runs_every_phase_and_says_so():
    lines = _assert_rehearsal(_smoke("--rehearse-cpu"), 1)
    out = "\n".join(lines)
    for phase in (
        "kernels: 16 compiled and matched",  # three the chunk kernel over a prefix, three the kda state kernels at each of two chunks, the two ssd state kernels
        "short prompt: 40 tok",
        "long prompt: 300 tok",
        "second turn: 332 tok, cached_tokens 304",
        "4 concurrent streams x 200 tok",
        "0 failed steps",
        "profile: 0.1 s window,",
        "trace: cpu capture",
        "four-chip leg: not applicable, 1 device(s)",
    ):
        assert phase in out, f"phase output missing: {phase!r}\n{out}"


@pytest.mark.slow
def test_cpu_rehearsal_of_the_four_chip_leg():
    """Four virtual CPU devices: the tp=4 leg runs (weights and pool born a
    quarter per device) instead of reporting a device count."""
    out = "\n".join(_assert_rehearsal(_smoke("--rehearse-cpu", devices=4), 4))
    assert "four-chip leg: passed" in out
    assert "share per device {0: 0.25" in out
