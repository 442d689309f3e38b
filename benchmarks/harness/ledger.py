"""The request ledger and the arithmetic from it to the end-to-end metrics.

One :class:`Record` per request the load generator was due to send. All
times are ``time.monotonic()`` seconds of the benchmark process. A
request's clock starts when it was DUE (open loop: its scheduled instant;
inside a session: the previous reply's end plus the think time), not when
the generator got round to sending it; ``send_t - due_t`` is the
generator's lateness and is reported beside the result.

Window membership: a request belongs to the window iff it was due in
``[t0, t1)``; a token or a gap belongs to it iff it ARRIVED in the window
(a gap: both of its tokens did).
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass, field


@dataclass
class Record:
    session: int
    turn: int
    due_t: float
    n_prompt: int
    max_tokens: int
    resent_tokens: int = 0  # prompt tokens the generator knows it sent before
    prefix_id: int | None = None
    send_t: float | None = None
    token_t: list[float] = field(default_factory=list)  # arrival of each token
    done_t: float | None = None
    n_output: int = 0  # ids in the final event
    ids_ok: bool = False  # every id an int in [0, vocab)
    status: str = "inflight"  # ok | http_<code> | shed | cancelled | error:<..> | short | timeout | inflight
    tag: str = "load"  # load | warmup | probe

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    @property
    def ttft_s(self) -> float | None:
        return self.token_t[0] - self.due_t if self.token_t else None

    @property
    def mean_gap_s(self) -> float | None:
        if len(self.token_t) < 2:
            return None
        return (self.token_t[-1] - self.token_t[0]) / (len(self.token_t) - 1)


def percentile(values: list[float], q: float) -> float | None:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; None of an empty sample."""
    if not values:
        return None
    s = sorted(values)
    if len(s) == 1:
        return s[0]
    pos = (len(s) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def finalize(rec: Record, vocab: int, ids, error: str | None) -> None:
    """Decide a finished stream's status from what came back. A record
    already settled (cut off when the load was stopped) stays as it is."""
    if rec.status != "inflight":
        return
    if error is not None:
        rec.status = error
        return
    rec.n_output = len(ids)
    rec.ids_ok = all(isinstance(t, int) and 0 <= t < vocab for t in ids)
    if len(ids) != rec.max_tokens or len(rec.token_t) != rec.max_tokens:
        rec.status = "short"
    elif not rec.ids_ok:
        rec.status = "error:token_id_out_of_vocab"
    else:
        rec.status = "ok"


def in_window(records: list[Record], t0: float, t1: float) -> list[Record]:
    return [r for r in records if r.tag == "load" and t0 <= r.due_t < t1]


def summarize(records: list[Record], t0: float, t1: float, limits: dict) -> dict:
    """Every end-to-end quantity of one window, plus the medians and the
    sample counts that go beside them. ``limits``: ``ttft_ms`` and
    ``mean_gap_ms``. A request that failed, or had not finished when the
    drain ended, misses every limit."""
    due = in_window(records, t0, t1)
    failed = [r for r in due if not r.ok]
    ttft = [r.ttft_s * 1e3 for r in due if r.ok]
    gaps: list[float] = []
    tokens_in = 0
    for r in records:
        if r.tag != "load":
            continue
        ts = r.token_t
        tokens_in += sum(1 for t in ts if t0 <= t < t1)
        gaps.extend(
            (b - a) * 1e3 for a, b in zip(ts, ts[1:]) if a >= t0 and b < t1
        )
    met = [
        r for r in due
        if r.ok and r.ttft_s * 1e3 <= limits["ttft_ms"]
        and (r.mean_gap_s is None or r.mean_gap_s * 1e3 <= limits["mean_gap_ms"])
    ]
    late = [(r.send_t - r.due_t) * 1e3 for r in due if r.send_t is not None]
    seconds = t1 - t0
    out = {
        "attempted": len(due),
        "failed": len(failed),
        "failed_by_status": _count(r.status for r in failed),
        "n_ttft": len(ttft),
        "n_gaps": len(gaps),
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
        "ttft_p50_ms": percentile(ttft, 50),
        "ttft_p90_ms": percentile(ttft, 90),
        "ttft_p95_ms": percentile(ttft, 95),
        "ttft_p99_ms": percentile(ttft, 99),
        "itl_p50_ms": percentile(gaps, 50),
        "itl_p95_ms": percentile(gaps, 95),
        "itl_p99_ms": percentile(gaps, 99),
        "tokens_per_s": tokens_in / seconds if seconds > 0 else None,
        "slo_share": 100.0 * len(met) / len(due) if due else None,
        "gen_late_p50_ms": percentile(late, 50),
        "gen_late_p95_ms": percentile(late, 95),
        "requests_per_s": len(due) / seconds if seconds > 0 else None,
    }
    return out


def _count(items) -> dict:
    out: dict = {}
    for x in items:
        out[x] = out.get(x, 0) + 1
    return out


def spread(values: list[float]) -> float:
    """Interquartile distance as a share of the median, by
    ``statistics.quantiles(values, n=4)`` — the rule bounds are set by."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
