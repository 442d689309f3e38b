"""Multi-host compute plane: ``jax.distributed`` across OS processes.

The reference's multi-node story is the oplog ring; SURVEY §5 requires the
rebuild's COMPUTE to scale multi-host too (the role NCCL/MPI plays in
torch stacks). This runs the real thing on CPU: two processes join one
``jax.distributed`` job (Gloo collectives), form ONE global mesh over
their 4+4 virtual devices, and execute the same sharded train step the
single-host dryrun runs — cross-process collectives and all. Loss must be
finite and identical on every process AND equal to the single-process
8-device result (the mesh factorization is the same, so the math is)."""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(pid: int, nproc: int, port: int) -> subprocess.Popen:
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        # The per-process flag is set by init_multihost via --local-devices;
        # scrub the suite's 8-device conftest flag so it doesn't override.
        XLA_FLAGS="",
    )
    return subprocess.Popen(
        [
            sys.executable, "-m", "radixmesh_tpu.launch", "multihost-dryrun",
            "--coordinator", f"127.0.0.1:{port}",
            "--num-processes", str(nproc),
            "--process-id", str(pid),
            "--local-devices", "4",
            # Pin the mesh the single-process 8-device dryrun uses so the
            # pinned loss proves cross-process == single-host math (the
            # host-aligned DEFAULT plan would pick dp=2,sp=1,tp=4).
            "--mesh", "1,2,4",
        ],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def test_two_process_global_mesh_train_step():
    port = _free_port()
    procs = [_spawn(i, 2, port) for i in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost dryrun hung")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} rc={p.returncode}:\n{out[-2000:]}"

    losses = []
    for out in outs:
        m = re.search(
            r"devices 4 local / 8 global mesh=(\{[^}]*\}) loss=([\d.]+)", out
        )
        assert m, f"missing dryrun line in:\n{out[-2000:]}"
        losses.append(float(m.group(2)))
    assert losses[0] == losses[1], losses
    # Same mesh factorization as the single-process 8-device dryrun →
    # identical math; the known-good loss pins cross-process collectives
    # to the single-host result.
    # (6.9983 = run_dryrun_train_step on the single-process dp1/sp2/tp4
    # mesh; re-derived in PR 21 when init_params' draw changed.)
    assert abs(losses[0] - 6.9983) < 5e-3, losses
