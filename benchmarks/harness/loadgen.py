"""Load generation over HTTP: an SSE client that stamps every token on
arrival, and the open and closed loops that drive sessions through it.

One process, one thread per in-flight session (a session is sequential by
nature); nothing here touches JAX. Stdlib only.
"""
from __future__ import annotations

import http.client
import json
import threading
import time
from urllib.parse import urlsplit

import numpy as np

from .ledger import Record, finalize


def get_json(base: str, path: str, timeout: float = 60.0) -> dict:
    u = urlsplit(base)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read()
        if resp.status != 200:
            raise RuntimeError(f"GET {path} -> HTTP {resp.status}: {body[:300]!r}")
        return json.loads(body)
    finally:
        conn.close()


def stream_generate(base: str, prompt, rec: Record, vocab: int,
                    timeout: float = 180.0) -> list[int]:
    """POST /generate with ``stream: true``; stamp each SSE token line with
    ``time.monotonic()`` as it arrives; settle ``rec``. Returns the output
    ids (empty on failure). Never raises: a failure is a status."""
    u = urlsplit(base)
    body = json.dumps({
        "input_ids": prompt if isinstance(prompt, list) else prompt.tolist(),
        "max_tokens": rec.max_tokens, "stream": True, "temperature": 0.0,
        "stop_token_ids": [],
    })
    ids: list[int] = []
    error = None
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    try:
        rec.send_t = time.monotonic()
        conn.request("POST", "/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            payload = resp.read()
            error = ("shed" if b'"shed"' in payload else f"http_{resp.status}")
        else:
            done = None
            for raw in resp:
                if not raw.startswith(b"data:"):
                    continue
                now = time.monotonic()
                if b'"done"' in raw:
                    done = json.loads(raw[5:])
                    break
                rec.token_t.append(now)
            rec.done_t = time.monotonic()
            if done is None:
                error = "error:stream_ended_without_done"
            elif done.get("error"):
                error = "error:" + str(done["error"])[:120]
            elif done.get("cancelled"):
                error = "cancelled"
            elif done.get("shed"):
                error = "shed"
            else:
                ids = done.get("output_ids") or []
    except (OSError, http.client.HTTPException, ValueError) as e:
        error = ("timeout" if isinstance(e, TimeoutError)
                 else f"error:{type(e).__name__}")
    finally:
        conn.close()
    finalize(rec, vocab, ids, error)
    return ids if rec.ok else []


class Load:
    """Runs a plan's sessions against ``base`` until stopped. Open loop:
    a dispatcher starts each session at ``t_start + arrival_s``. Closed
    loop: ``clients`` threads each run sessions back to back."""

    def __init__(self, base: str, plan, loop: dict, vocab: int,
                 request_timeout: float = 180.0):
        self.base, self.plan, self.loop, self.vocab = base, plan, loop, vocab
        self.request_timeout = request_timeout
        self.records: list[Record] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.t_start: float | None = None

    def _run_session(self, sess, due_t: float) -> None:
        history = np.zeros(0, dtype=np.int32)
        for k, turn in enumerate(sess.turns):
            if k:
                due_t = time.monotonic() + turn.think_s
                if self._stop.wait(max(0.0, turn.think_s)):
                    return
            if self._stop.is_set():
                return
            prompt = np.concatenate([history, turn.new_tokens])
            rec = Record(
                session=sess.index, turn=k, due_t=due_t, n_prompt=len(prompt),
                max_tokens=turn.max_tokens,
                resent_tokens=len(history) if k else sess.prefix_len,
                prefix_id=sess.prefix_id,
            )
            with self._lock:
                self.records.append(rec)
            ids = stream_generate(self.base, prompt, rec, self.vocab,
                                  self.request_timeout)
            if not rec.ok:
                return  # a broken session does not go on
            history = np.concatenate([prompt, np.asarray(ids, dtype=np.int32)])

    def _dispatch_open(self) -> None:
        for sess in self.plan:
            due = self.t_start + sess.arrival_s
            if self._stop.wait(max(0.0, due - time.monotonic())):
                return
            threading.Thread(target=self._run_session, args=(sess, due),
                             daemon=True).start()

    def _client_closed(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                sess = next(self.plan)
            self._run_session(sess, time.monotonic())

    def start(self) -> float:
        self.t_start = time.monotonic()
        if self.loop["kind"] == "open":
            threading.Thread(target=self._dispatch_open, daemon=True).start()
        else:
            for _ in range(int(self.loop["clients"])):
                threading.Thread(target=self._client_closed, daemon=True).start()
        return self.t_start

    def pending_in(self, t0: float, t1: float) -> int:
        with self._lock:
            return sum(1 for r in self.records
                       if t0 <= r.due_t < t1 and r.status == "inflight")

    def stop(self) -> list[Record]:
        """Stop offering load. Requests still in flight are settled as
        ``unfinished`` (their sockets die with the server)."""
        self._stop.set()
        with self._lock:
            for r in self.records:
                if r.status == "inflight":
                    r.status = "unfinished"
            return list(self.records)
