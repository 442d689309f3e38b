"""The system under test as a child process: start, wait, watch, stop.

The child holds the chip(s); this parent never imports JAX while it lives.
The child's stderr is read line by line and every line is stamped with THIS
process's ``time.monotonic()`` on arrival — the clock the measurement
window is on — so compilations (``JAX_LOG_COMPILES=1``) can be placed
inside or outside the window without trusting two clocks.
"""
from __future__ import annotations

import os
import re
import signal
import subprocess
import threading
import time

from .registry import REPO, ROOT

_COMPILED = re.compile(r"Finished XLA compilation of (\S+) in ([0-9.eE+-]+) sec")
_URL = re.compile(r" on (http://\S+)")
_DEVICES = re.compile(r"devices: platform=(\S+) device_kind=(.+?) count=(\d+)")


def child_env(base_env: dict | None = None) -> dict:
    """Environment of the server child only: the persistent compilation
    cache at ``JAX_COMPILATION_CACHE_DIR`` if the caller's environment sets
    it, else at the fixed path ``benchmarks/.cache/xla`` inside the
    checkout (the path is part of the cache key: it must not move); every
    compile cached however short; compiles logged."""
    env = dict(os.environ if base_env is None else base_env)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".cache", "xla"))
    os.makedirs(env["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env["JAX_LOG_COMPILES"] = "1"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.pop("BENCH_RUN", None)
    return env


class NoAccelerator(RuntimeError):
    pass


class Server:
    def __init__(self, argv: list[str], env: dict, log_path: str):
        self.log_path = log_path
        self.compiles: list[tuple[float, str, float]] = []  # (t, name, seconds)
        self.base: str | None = None
        self.platform: str | None = None  # from the child's start-up log line
        self.t0 = time.monotonic()
        self._url = threading.Event()
        self.proc = subprocess.Popen(
            argv, cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, errors="replace",
            start_new_session=True,
        )
        self._readers = [
            threading.Thread(target=self._read_stdout, daemon=True),
            threading.Thread(target=self._read_stderr, daemon=True),
        ]
        for t in self._readers:
            t.start()

    def _read_stdout(self) -> None:
        for line in self.proc.stdout:
            m = _URL.search(line)
            if m and self.base is None:
                self.base = m.group(1).strip()
                self._url.set()

    def _read_stderr(self) -> None:
        with open(self.log_path, "w") as log:
            for line in self.proc.stderr:
                now = time.monotonic()
                if self.platform is None:
                    d = _DEVICES.search(line)
                    if d:
                        self.platform = d.group(1)
                m = _COMPILED.search(line)
                if m:
                    c = (m.group(1), float(m.group(2)))
                    # JAX's logger and the program's root handler both
                    # print the line: the same compile, once.
                    if not self.compiles or self.compiles[-1][1:] != c:
                        self.compiles.append((now, *c))
                        log.write(f"{now:.3f} {line}")
                elif "Compiling " in line or "Finished " in line:
                    continue  # the shape dumps of JAX_LOG_COMPILES: huge
                else:
                    log.write(f"{now:.3f} {line}")
                log.flush()

    def wait_ready(self, timeout: float, need_platform: str | None = None) -> str:
        deadline = time.monotonic() + timeout
        while not self._url.wait(0.5):
            if need_platform and self.platform not in (None, need_platform):
                raise NoAccelerator(
                    f"the server started on platform {self.platform!r}, "
                    f"not {need_platform!r}")
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited with code {self.proc.returncode} before "
                    f"serving; log tail:\n{self.log_tail()}"
                )
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"server not up after {timeout:.0f} s; log tail:\n"
                    f"{self.log_tail()}"
                )
        return self.base

    def compiles_between(self, t0: float, t1: float) -> list[tuple[float, str, float]]:
        """Compilations that FINISHED in [t0, t1] or were running in it."""
        return [c for c in self.compiles if c[0] >= t0 and c[0] - c[2] <= t1]

    def log_tail(self, n: int = 3000) -> str:
        try:
            with open(self.log_path, errors="replace") as fh:
                return fh.read()[-n:]
        except OSError:
            return ""

    def stop(self) -> None:
        """SIGTERM the child's process group, wait, SIGKILL if it must."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
            try:
                self.proc.wait(timeout=8)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)  # stragglers of the group
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait(timeout=30)
        for t in self._readers:
            t.join(timeout=5)
