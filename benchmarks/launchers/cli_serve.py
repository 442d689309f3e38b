"""The normal path: ``python -m radixmesh_tpu.launch serve`` with the
configuration's arguments, unmodified. Used by every one-chip
configuration."""
from __future__ import annotations

import sys


def argv(config: dict, *, seed: int, profile_dir: str | None) -> list[str]:
    cmd = [
        sys.executable, "-m", "radixmesh_tpu.launch", "serve",
        "--model", config["preset"], "--seed", str(seed), "--http-port", "0",
        *[str(a) for a in config["server_args"]],
    ]
    if profile_dir:
        cmd += ["--profile-dir", profile_dir]
    return cmd
