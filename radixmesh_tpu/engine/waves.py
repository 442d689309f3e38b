"""Mixed compute waves: the token-budgeted wave scheduler.

The engine's pre-PR-19 schedule alternated WHOLE prefill waves against
WHOLE decode waves: ``step()`` ran ``_admit`` (which prefilled every
admissible request to completion, chunk loop and all) and only then one
decode step. A long prompt therefore monopolized the device for its
entire prefill while every running stream stalled — the ``prefill_convoy``
stall cause the token timeline attributes, and the reason the "wide"
workload's p50 TTFT sat at 5x "base" (r05 CPU record).

This module is the Sarathi-Serve/Orca answer, kept as PURE host-side
policy so its invariants are unit-testable without a device:

- every wave that has running decode rows *includes* their decode step
  (decode is never skipped by a mixed wave), and
- rides up to ``inline_budget`` tokens of chunked prefill from the
  inline backlog on the SAME fused launch (``prefill_chunk_paged``
  already attends ragged per-row windows; the decode rows are just
  width-1 windows of the same chunk call), and
- may run at most ``max_defer`` CONSECUTIVE prefill-only "boost" waves
  (full ``boost_tokens`` width, for a backlog so deep that budget-sized
  chunks would starve TTFT) before it MUST run a decode-bearing wave
  again — the starvation bound, stated in wave counts (virtual time),
  never wall-clock.

Allotment within a wave is shortest-remaining-first (SPT, FIFO
tiebreak): a late-arriving 16-token prompt jumps the line past a 32k
groupmate's remaining chunks — same policy rationale as
``prefill_wave_tokens`` sub-slicing, applied at chunk granularity.

The scheduler holds no references to requests or device state; the
engine feeds it integer remaining-token counts and applies the returned
per-job allotments. ``radixmesh_wave_*`` metrics make the wave mix
observable (/debug/state, fleet digest dashboards).
"""

from __future__ import annotations

from dataclasses import dataclass

from radixmesh_tpu.obs.metrics import get_registry

__all__ = ["WaveScheduler", "WavePlan", "WAVE_KINDS"]

# Every wave the engine runs is exactly one of these. ``decode`` and
# ``prefill`` are the legacy pure waves; ``mixed`` fuses both; ``boost``
# is a prefill-only catch-up wave that COUNTS AGAINST the defer bound.
WAVE_KINDS = ("decode", "prefill", "mixed", "boost")


@dataclass
class WavePlan:
    """One wave's composition, as decided by :meth:`WaveScheduler.plan`.

    ``kind``    — one of :data:`WAVE_KINDS`.
    ``allot``   — inline prefill tokens per backlog job, parallel to the
                  ``backlog`` list handed to ``plan()`` (0 = job sits
                  this wave out). Sums to ≤ ``inline_budget`` for mixed
                  waves and ≤ ``boost_tokens`` for boost waves — the
                  budget invariant the tests pin.
    ``decode``  — whether this wave carries the decode step for the
                  running rows (always True when ``kind`` is ``decode``
                  or ``mixed``).
    """

    kind: str
    allot: list[int]
    decode: bool


class WaveScheduler:
    def __init__(
        self,
        inline_budget: int,
        max_defer: int = 2,
        chunk: int = 512,
        boost_tokens: int = 4096,
        node: str = "",
    ):
        if inline_budget <= 0:
            raise ValueError("inline_budget must be > 0 (0 disables mixing)")
        self.inline_budget = int(inline_budget)
        self.max_defer = max(0, int(max_defer))
        self.chunk = max(1, int(chunk))
        self.boost_tokens = max(self.inline_budget, int(boost_tokens))
        # Consecutive decode-deferring (boost) waves since the last wave
        # that carried decode — THE starvation counter. Reset by every
        # decode-bearing wave; the bound is ``max_defer``.
        self._defer = 0
        self.max_defer_observed = 0
        reg = get_registry()
        lbl = {"engine": node or "engine"}
        self._m_waves = {
            kind: reg.counter(
                "radixmesh_wave_total",
                "compute waves by kind (decode / prefill / mixed / boost)",
                ("engine", "kind"),
            ).labels(engine=lbl["engine"], kind=kind)
            for kind in WAVE_KINDS
        }
        self._m_inline_tokens = reg.counter(
            "radixmesh_wave_inline_tokens_total",
            "prefill tokens advanced inside mixed/boost waves",
            ("engine",),
        ).labels(**lbl)
        self._m_defer = reg.gauge(
            "radixmesh_wave_decode_defer_waves",
            "consecutive waves the decode step has been deferred "
            "(bounded by --prefill-inline-max-defer)",
            ("engine",),
        ).labels(**lbl)
        # Point-in-time mirror of the counters for the lock-free
        # /debug/state snapshot (counter .value reads are fine too, but
        # a plain dict keeps the endpoint allocation-free).
        self.counts = dict.fromkeys(WAVE_KINDS, 0)
        self.inline_tokens = 0

    # ------------------------------------------------------------------
    # policy
    # ------------------------------------------------------------------

    def plan(self, decode_rows: int, backlog: list[int]) -> WavePlan:
        """Decide the next wave from ``decode_rows`` running decode rows
        and ``backlog`` = remaining UNPREFILLED tokens per inline job
        (engine admission order). Pure; :meth:`note` commits it."""
        remaining = [max(0, int(r)) for r in backlog]
        total = sum(remaining)
        if total <= 0:
            return WavePlan("decode", [0] * len(remaining), decode_rows > 0)
        if decode_rows <= 0:
            # Nobody to starve: catch the backlog up at full wave width
            # (the cold-start path keeps its pre-mixing throughput).
            return WavePlan(
                "prefill", self._allot(remaining, self.boost_tokens), False
            )
        if total >= self.boost_tokens and self._defer < self.max_defer:
            # Backlog deeper than a full legacy wave: budget-sized
            # chunks alone would push TTFT past the old alternating
            # schedule. Spend a bounded number of consecutive waves
            # prefill-only — each one counted against the defer bound,
            # so a decode stream's worst ITL gap is max_defer+1 waves.
            return WavePlan(
                "boost", self._allot(remaining, self.boost_tokens), False
            )
        return WavePlan(
            "mixed", self._allot(remaining, self.inline_budget), True
        )

    def _allot(self, remaining: list[int], budget: int) -> list[int]:
        """Split ``budget`` tokens across jobs, shortest-remaining-first
        (FIFO tiebreak), each share capped at ``chunk``."""
        allot = [0] * len(remaining)
        order = sorted(range(len(remaining)), key=lambda i: (remaining[i], i))
        left = budget
        for i in order:
            if left <= 0:
                break
            take = min(remaining[i], self.chunk, left)
            allot[i] = take
            left -= take
        return allot

    def note(self, plan: WavePlan) -> None:
        """Commit a planned-and-executed wave: defer accounting +
        metrics. The engine calls this exactly once per wave it runs."""
        if plan.kind == "boost":
            # Only boost waves defer anyone: a pure prefill wave runs
            # when there are NO decode rows, so nothing is starved and
            # the counter must not charge it against the bound.
            self._defer += 1
            self.max_defer_observed = max(self.max_defer_observed, self._defer)
        else:
            self._defer = 0
        self._m_defer.set(self._defer)
        self.counts[plan.kind] += 1
        self._m_waves[plan.kind].inc()
        inline = sum(plan.allot)
        if inline:
            self.inline_tokens += inline
            self._m_inline_tokens.inc(inline)

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    def snapshot(self) -> dict:
        """Lock-free wave-mix snapshot for /debug/state and telemetry."""
        return {
            "budget": self.inline_budget,
            "max_defer": self.max_defer,
            "counts": dict(self.counts),
            "inline_tokens": self.inline_tokens,
            "decode_defer": self._defer,
            "max_defer_observed": self.max_defer_observed,
        }
