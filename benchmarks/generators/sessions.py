"""The one general traffic generator: sessions of one or more turns.

A mix file gives distributions (``{"dist": ...}`` objects, below) for the
shared system prompts, turns per session, new tokens per turn, reply
length and think time. One turn and no system prompt is unshared
single-shot traffic; many turns over a few system prompts is chat or agent
traffic. Each turn's prompt is the system prompt + the whole history
(replies as the server returned them) + the turn's new tokens.

Sizes, arrival instants and their order all come from the mix file's
``mix_seed``: every ``--seed`` offers the SAME schedule. The seed draws the
token ids (and, through the launcher, the weights). Runs then differ by
content, not by work: measured on the chip (PERF.md, PR 24), giving each
seed the same sessions in ANOTHER order still moved the 95th-percentile
token gap by 9% and the first-token tail by 32% across seeds, while two runs
of one seed agreed within 1% and 7%.

Distributions: ``const`` (value), ``uniform`` (min, max; integers),
``lognormal`` (median, sigma, min, max), ``geometric`` (mean, min, max),
``exponential`` (mean), ``zipf`` (n, s: index in [0, n) with weight
1/(k+1)**s). Any of them may add ``quantum``: values are rounded to its
nearest multiple (at least one quantum).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np


def draw(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    kind = spec["dist"]
    if kind == "const":
        out = np.full(n, spec["value"], dtype=float)
    elif kind == "uniform":
        out = rng.integers(spec["min"], spec["max"] + 1, size=n).astype(float)
    elif kind == "lognormal":
        out = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], size=n))
    elif kind == "geometric":
        out = rng.geometric(1.0 / spec["mean"], size=n).astype(float)
    elif kind == "exponential":
        out = rng.exponential(spec["mean"], size=n)
    elif kind == "zipf":
        w = 1.0 / np.arange(1, spec["n"] + 1) ** spec.get("s", 1.0)
        out = rng.choice(spec["n"], size=n, p=w / w.sum()).astype(float)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in spec or "max" in spec:
        out = np.clip(out, spec.get("min", -np.inf), spec.get("max", np.inf))
    if spec.get("quantum"):
        # Token lengths on a grid: the engine compiles per distinct length,
        # and a warm-up can only reach a set of lengths it can enumerate.
        q = spec["quantum"]
        out = np.maximum(q, np.round(out / q) * q)
    return out


@dataclass
class Turn:
    new_tokens: np.ndarray  # this turn's input (first turn: system prompt first)
    max_tokens: int
    think_s: float  # wait after the previous reply before this turn is due


@dataclass
class Session:
    index: int
    arrival_s: float | None  # open loop: offset on the timeline; closed: None
    prefix_id: int | None  # which shared system prompt, if any
    prefix_len: int
    turns: list[Turn] = field(default_factory=list)


class Plan:
    """An endless, deterministic stream of sessions for one run."""

    def __init__(self, params: dict, loop: dict, vocab: int, seed: int,
                 horizon_s: float):
        self.params, self.loop, self.vocab, self.seed = params, loop, vocab, seed
        self.horizon_s = horizon_s
        mix = np.random.default_rng([int(params["mix_seed"]), 0])
        n_sys = int(params.get("system_prompts", 0))
        self.system_lens = (
            draw(params["system_len"], mix, n_sys).astype(int) if n_sys else
            np.zeros(0, dtype=int)
        )
        if loop["kind"] == "open":
            # A fixed set of Poisson arrival instants covering the horizon.
            rate = float(loop["sessions_per_s"])
            gaps = mix.exponential(1.0 / rate, size=int(rate * horizon_s * 2) + 16)
            times = np.cumsum(gaps)
            self.arrivals = times[times < horizon_s]
            self.pool = len(self.arrivals)
        else:
            self.arrivals = None
            self.pool = int(params.get("pool_sessions", 512))
        n = self.pool
        self.turn_counts = draw(params["turns"], mix, n).astype(int)
        self.sys_ids = (
            draw({"dist": "zipf", "n": n_sys, "s": params.get("zipf_s", 1.0)},
                 mix, n).astype(int) if n_sys else np.full(n, -1)
        )
        total = int(self.turn_counts.sum())
        self.turn_off = np.concatenate([[0], np.cumsum(self.turn_counts)])
        self.user_lens = draw(params["turn_tokens"], mix, total).astype(int)
        self.reply_lens = draw(params["max_tokens"], mix, total).astype(int)
        self.think = draw(params.get("think_s", {"dist": "const", "value": 0.0}),
                          mix, total)
        # Order too is the mix's; the seed's part is every token id.
        self._order = np.random.default_rng([int(params["mix_seed"]), 1])
        self._perm: list[int] = []
        self._cycle = 0
        self._emitted = 0
        self._sys_tokens = [
            np.random.default_rng([int(seed), 2, k]).integers(
                0, vocab, size=int(n_), dtype=np.int32)
            for k, n_ in enumerate(self.system_lens)
        ]

    def prefixes(self) -> list[np.ndarray]:
        """The shared system prompts, for a warm-up to publish."""
        return list(self._sys_tokens)

    def shapes(self) -> list[dict]:
        """The fixed multiset of sessions (lengths only), for sizing and
        for tests: identical for every seed."""
        out = []
        for i in range(self.pool):
            a, b = self.turn_off[i], self.turn_off[i + 1]
            sid = int(self.sys_ids[i])
            out.append({
                "prefix_len": int(self.system_lens[sid]) if sid >= 0 else 0,
                "turn_tokens": self.user_lens[a:b].tolist(),
                "max_tokens": self.reply_lens[a:b].tolist(),
            })
        return out

    def census(self, n_sessions: int, page: int) -> list[tuple[int, int]]:
        """``(cached prefix, new tokens)`` of every request of the first
        ``n_sessions`` sessions, as the server will see them when every
        earlier turn and every system prompt is still in its cache: the
        launch shapes a warm-up has to reach. Lengths only — the same for
        every seed. The cached prefix is page-aligned and leaves at least
        one token to compute; the last generated token has no K/V yet."""
        twin = Plan(self.params, self.loop, self.vocab, self.seed, self.horizon_s)
        out = []
        for _ in range(n_sessions):
            try:
                sess = next(twin)
            except StopIteration:
                break
            context = 0  # tokens the server holds K/V for, of this session
            total = 0
            for k, turn in enumerate(sess.turns):
                total += len(turn.new_tokens)
                known = context if k else sess.prefix_len
                reuse = min(known, total - 1) // page * page
                out.append((reuse, total - reuse))
                context = total + turn.max_tokens - 1
                total += turn.max_tokens
        return out

    def __iter__(self):
        return self

    def __next__(self) -> Session:
        if not self._perm:
            if self.arrivals is not None and self._cycle:
                raise StopIteration  # open loop: the horizon is the plan
            self._perm = self._order.permutation(self.pool).tolist()[::-1]
            self._cycle += 1
        i = self._perm.pop()
        k = self._emitted
        self._emitted += 1
        a, b = self.turn_off[i], self.turn_off[i + 1]
        sid = int(self.sys_ids[i])
        rng = np.random.default_rng([int(self.seed), 3, k])
        turns = []
        for j in range(a, b):
            toks = rng.integers(0, self.vocab, size=int(self.user_lens[j]),
                                dtype=np.int32)
            if j == a and sid >= 0:
                toks = np.concatenate([self._sys_tokens[sid], toks])
            turns.append(Turn(toks, int(self.reply_lens[j]),
                              0.0 if j == a else float(self.think[j])))
        return Session(
            index=k,
            arrival_s=None if self.arrivals is None else float(self.arrivals[k]),
            prefix_id=sid if sid >= 0 else None,
            prefix_len=int(self.system_lens[sid]) if sid >= 0 else 0,
            turns=turns,
        )


def plan(params: dict, loop: dict, vocab: int, seed: int, horizon_s: float) -> Plan:
    return Plan(params, loop, vocab, seed, horizon_s)
