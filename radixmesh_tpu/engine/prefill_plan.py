"""Which launches prefill a sub-wave: the wave planner (PR 47).

A sub-wave is the rows one admission prefills together and samples
together: requests of one power-of-two bucket of new tokens
(``Engine._admit``). Until PR 47 it was ONE box — every row padded to
the longest row's whole chunks, the rows padded to a power of two, every
chunk launched at the same ``[B, C]``. :func:`plan_prefill` launches the
tokens the rows have instead. It is a pure function of the rows' lengths
and of two numbers the engine derives from its weights
(:func:`launch_cost`): how few tokens a launch can hold before the time
to stream the weights, not the arithmetic, bounds it (``floor_tokens``),
and what a launch costs beside its tokens (``launch_tokens``) — both in
units of one token's arithmetic.

The same cost sets how wide a chunk may be (PR 54, :func:`chunk_width`):
the engine's ``prefill_chunk`` is the MINIMUM; where the floor lies above
it the widest chunk (``width`` below) is the power of two that holds the
floor, within ``prefill_wave_tokens`` and what the chunk kernels admit
from shapes alone. A sub-wave takes the widest chunk EVERY row of its
bucket fills (:func:`bucket_chunk`: half the bucket, from ``chunk`` to
``width``) — a row that HAS the tokens is not launched in pieces each
bound by the weights they stream, and no row's first launch is padded.

The shapes it draws from are closed, and a cell's warm-up reaches them
the way it reached the box's:

* a launch of SEVERAL rows is ``[B, C0, width]`` with ``C0`` and
  ``width`` what :func:`prefill_launch_shape` gives the sub-wave — a
  function of its bucket and of the engine's weights, never of how many
  rows it happens to hold — and ``B`` a power of two no greater than the
  box's: the shapes a burst of alike requests compiles;
* a launch of ONE row is ``[1, C, lone width]`` with ``C`` a power of two
  from 16 to ``C0`` — and exactly the launches the same request gets
  when it is admitted alone, so a request of every length sent alone
  (``benchmarks/run.py::census_cover``) has compiled them all.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence

__all__ = [
    "KV_BLOCK_PAGES",
    "PrefillLaunch",
    "boxed_tokens",
    "bucket_chunk",
    "chunk_width",
    "launch_cost",
    "plan_prefill",
    "pow2_at_least",
    "prefill_launch_shape",
    "subwaves",
]

# Pages per attention KV block in every chunked launch (prefill, spec
# verify, pp decode). Page tables are pow2-padded with this as the floor,
# and attend_chunk_hybrid requires max_pages to divide by it — one
# constant so the padding and the kernels can't drift apart.
KV_BLOCK_PAGES = 32
# A chunk's tokens are a power of two, at least this many.
_MIN_CHUNK = 16
# Peak FLOP/s and bytes/s of the chip the constants below were read on, one
# TPU v5e (Google Cloud documentation). Their ratio is what the planner
# uses: the FLOP a byte of weights must be worth before a launch is bound
# by arithmetic. Every device plans with them — a plan is the same wherever
# it is computed, and another chip's own reading belongs here with its own
# ``_LAUNCH_S`` when one is served.
_PEAK_FLOPS, _PEAK_BYTES = 197e12, 819e9
# What a launch costs whatever it holds: 2.4 ms between [1, 128] and
# [8, 512] on a v5e at Qwen2-7B (the line through 12.4 / 22.3 / 41.0 /
# 327.5 ms at 128 / 256 / 512 / 4 096 tokens), beside ~0.4 ms of the
# engine thread to send it (my chip run, PR 47: PERF.md section 6).
_LAUNCH_S = 2.5e-3


def pow2_at_least(n: int, floor: int = 8) -> int:
    n = max(n, floor)
    return 1 << (n - 1).bit_length()


def prefill_launch_shape(
    rows: int,
    new_tokens: int,
    total_tokens: int,
    *,
    chunk: int,
    page_size: int,
    max_pages: int,
    width_selects: bool = False,
    one_width: bool = False,
    width: int | None = None,
) -> tuple[int, int, int]:
    """``(B, C, max_pages)`` of the BOX around ``rows`` requests of at
    most ``new_tokens`` uncached tokens and ``total_tokens`` in all — the
    program key of ``prefill_chunk_paged``, and the largest launch
    :func:`plan_prefill` draws for them: its launches of several rows
    keep this ``C`` and this width under a ``B`` no greater, its
    launches of one row this function's shape for ``rows == 1`` under a
    ``C`` no greater.

    A lone row takes ONE table width, the engine's own (``max_pages``):
    the attention kernels end a row's page loop at the row's length, so
    a table wider than the row is a few KB uploaded and nothing on the
    device, and a row's shapes then depend on its new tokens alone —
    requests of two exact lengths in one bucket pair (new tokens, cached
    prefix) never meet two programs. Where the width itself chooses the
    program's attention (``width_selects``: learned sparse attention
    engages on it), and for several rows, whose widths their callers
    warm by burst, the width is the longest row's pages, rounded up to a
    power of two. ``one_width``: several rows too take the engine's own
    width — for a model whose chunk program traces several kinds of
    layer (conv and attention, dense and sparse FFN: ~30 s a compile at
    published widths), where contexts of 2 k-14 k tokens would otherwise
    multiply every (rows, chunk) pair by three widths. ``chunk`` is the
    engine's minimum chunk and ``width`` its widest (:func:`chunk_width`;
    the chunk when not given): ``C`` is :func:`bucket_chunk` of the
    bucket of ``new_tokens``."""
    B = pow2_at_least(rows, floor=1)
    C = bucket_chunk(pow2_at_least(new_tokens, floor=_MIN_CHUNK), chunk, width)
    pages = -(-total_tokens // page_size)
    if (rows == 1 or one_width) and not width_selects:
        pages = max_pages
    return B, C, pow2_at_least(pages, floor=KV_BLOCK_PAGES)


def launch_cost(stream_bytes: float, flop_per_token: float) -> tuple[float, float]:
    """``(floor_tokens, launch_tokens)`` of :func:`plan_prefill` for a
    model whose every launch streams ``stream_bytes`` of weights (for a
    model with experts: every expert it holds, which a chunk of a few
    hundred tokens touches) and whose every token costs
    ``flop_per_token`` (2 x the parameters a token meets): a launch
    takes ``max(stream_bytes / peak bytes/s, tokens x flop_per_token /
    peak FLOP/s)`` and a fixed charge, here divided by one token's
    time."""
    token_s = flop_per_token / _PEAK_FLOPS
    return (stream_bytes / _PEAK_BYTES) / token_s, _LAUNCH_S / token_s


def chunk_width(
    chunk: int, floor_tokens: float, wave_tokens: int,
    admits: Callable[[int], bool] = lambda width: True,
) -> int:
    """The widest chunk of a launch: the power of two that holds
    ``max(chunk, floor_tokens)`` — past the floor a launch is bound by
    its arithmetic and a wider one saves nothing but its fixed charge —
    no wider than a wave's ``wave_tokens``, and halved while the chunk
    kernels refuse it from shapes alone (``admits``); never under
    ``chunk``, which the kernels were built at."""
    width = pow2_at_least(
        int(min(max(chunk, floor_tokens), max(chunk, wave_tokens))))
    while width > chunk and (width > wave_tokens or not admits(width)):
        width //= 2
    return max(width, chunk)


def bucket_chunk(bucket: int, chunk: int, width: int | None = None) -> int:
    """The chunk ``C0`` of a sub-wave of one power-of-two ``bucket`` of new
    tokens: the bucket itself up to ``chunk``; above, the widest power of
    two that EVERY row of the bucket fills — its rows hold more than half
    of it — from ``chunk`` up to ``width``. A function of the bucket and
    of the engine's weights alone, never of the rows a sub-wave happens
    to hold: a burst of alike rows compiles what any wave of the bucket
    launches. Buckets up to twice ``chunk`` keep the chunk they had when
    ``chunk`` was the cap (as every bucket does where no ``width`` is
    given)."""
    return min(bucket, max(chunk, min(width or chunk, bucket // 2)))


def subwaves(
    news: Sequence[int], *, chunk: int, wave_tokens: int,
    width: int | None = None,
) -> list[list[int]]:
    """An admission's rows (by index, ``news[i]`` uncached tokens each) cut
    into sub-waves: by power-of-two bucket of new tokens, shortest first —
    a short request must not wait for a 32k groupmate's chunks to sample
    its first token — and each bucket sliced at the compute-saturating
    width, ``wave_tokens // C0`` rows of whole chunks with ``C0`` the
    chunk :func:`plan_prefill` will launch them at (:func:`bucket_chunk`:
    no launch holds more than ``wave_tokens`` tokens), in arrival order:
    slices finalize their first tokens as they complete instead of
    convoying behind the whole bucket. The bucket is UNCAPPED (a
    512-token prompt shares no sub-wave with a 32k prompt); the chunk
    inside a sub-wave stays capped at ``width`` (``chunk`` when not
    given)."""
    def bucket(i: int) -> int:
        return pow2_at_least(news[i], floor=_MIN_CHUNK)

    order = sorted(range(len(news)), key=bucket)
    cut: list[list[int]] = []
    start = 0
    for end in range(1, len(order) + 1):
        if end == len(order) or bucket(order[end]) != bucket(order[start]):
            c0 = bucket_chunk(bucket(order[start]), chunk, width)
            rows = max(1, wave_tokens // c0)
            cut.extend(
                order[j:min(j + rows, end)] for j in range(start, end, rows))
            start = end
    return cut


class PrefillLaunch(NamedTuple):
    """One ``prefill_chunk_paged`` launch of a plan: ``rows[j]`` (an
    index into the planned rows) computes ``lens[j]`` of its new tokens
    from its ``starts[j]``-th on, as batch row ``j`` of a ``[B, C]``
    launch under a page table ``pages`` wide."""

    rows: tuple[int, ...]
    starts: tuple[int, ...]
    lens: tuple[int, ...]
    B: int
    C: int
    pages: int


def boxed_tokens(
    news: Sequence[int], *, chunk: int, dense_up_to: int = 0,
    width: int | None = None,
) -> int:
    """What the rule before PR 47 launched for a sub-wave of rows with
    ``news`` new tokens each: the rows padded to a power of two, each to
    the longest row's whole chunks (of the sub-wave's own ``C0``); a lone
    cold prompt of at most ``dense_up_to`` tokens its power-of-two
    bucket, in one launch."""
    longest = max(news)
    if len(news) == 1 and longest <= dense_up_to:
        return pow2_at_least(longest)
    C = bucket_chunk(pow2_at_least(longest, floor=_MIN_CHUNK), chunk, width)
    return pow2_at_least(len(news), floor=1) * -(-longest // C) * C


def plan_prefill(
    rows: Sequence[tuple[int, int]],
    *,
    chunk: int,
    page_size: int,
    max_pages: int,
    floor_tokens: float,
    launch_tokens: float,
    width_selects: bool = False,
    one_width: bool = False,
    width: int | None = None,
) -> list[PrefillLaunch]:
    """The launches that prefill a sub-wave of ``rows`` = ``(new tokens,
    cached tokens)``, cheapest first by the estimate ``max(floor_tokens,
    B x C) + launch_tokens`` a launch, then fewest, then those of the
    fewest launched tokens.

    The rows advance by levels of ``C0`` tokens (the box's chunk:
    :func:`bucket_chunk` of their bucket, between ``chunk`` and
    ``width``). At a level, a row with ``C0`` tokens or more left RIDES a
    launch of several rows; a row with fewer — its tail — rides too where
    a seat costs less than a launch of its own, and else goes ALONE, in
    launches of one row whose chunks are the powers of two that hold the
    tail (288 tokens: 256 + 32, where two launches cost less than the
    224 padded tokens of one). The riders, longest first, are seated in
    launches of 2^k rows — 5 rows are 4 + 1, not 8, where a launch is
    cheaper than three padded rows — so a later level launches only the
    rows still live. Where a launch is bound by the weights it streams
    (``floor_tokens`` in the thousands: experts, or few new tokens on a
    long cached prefix) no plan of MORE launches is cheaper than the box,
    and a sub-wave of one level is the box; a tail that needs a launch
    of its own either way takes the power of two that holds it, not
    ``C0``."""
    news = [int(n) for n, _ in rows]
    box_b, c0, pages = prefill_launch_shape(
        len(rows), max(news), max(n + c for n, c in rows), chunk=chunk,
        page_size=page_size, max_pages=max_pages,
        width_selects=width_selects, one_width=one_width, width=width)
    lone_width = pages if width_selects else prefill_launch_shape(
        1, 1, 1, chunk=chunk, page_size=page_size, max_pages=max_pages)[2]

    def cost(b: int, c: int) -> tuple[float, int, int]:
        """Of one launch: its estimate; then 1 and its tokens — of two
        plans that cost the same the one of fewer launches is taken, and
        of those the one that launches fewer tokens (under the floor the
        estimate cannot tell ``[1, 256]`` from ``[1, 1024]``; the chip
        can, PERF.md section 6, PR 54)."""
        return max(floor_tokens, b * c) + launch_tokens, 1, b * c

    def add(x, y):
        return x[0] + y[0], x[1] + y[1], x[2] + y[2]

    @functools.lru_cache(maxsize=None)
    def seats(m: int) -> tuple[tuple[float, int, int], tuple[int, ...]]:
        """Cheapest launches of ``C0`` tokens that seat ``m`` rows: their
        estimate, and their batch sizes, largest first."""
        if m <= 0:
            return (0.0, 0, 0), ()
        best = None
        b = 1
        while b <= box_b:
            rest, sizes = seats(m - b)
            cand = add(cost(b, c0), rest), (b, *sizes)
            if best is None or cand[0] < best[0]:
                best = cand
            b *= 2
        return best[0], tuple(sorted(best[1], reverse=True))

    @functools.lru_cache(maxsize=None)
    def alone(t: int) -> tuple[tuple[float, int, int], tuple[int, ...]]:
        """Cheapest one-row launches for a tail of ``t`` tokens: their
        estimate, and their chunks in order."""
        c = pow2_at_least(t, floor=_MIN_CHUNK)
        best = cost(1, c), (c,)
        if c // 2 >= _MIN_CHUNK and t > c // 2:
            rest, chunks = alone(t - c // 2)
            cand = add(cost(1, c // 2), rest), (c // 2, *chunks)
            if cand[0] < best[0]:
                best = cand
        return best

    plan: list[PrefillLaunch] = []
    done = 0
    while True:
        live = sorted((i for i, n in enumerate(news) if n > done),
                      key=lambda i: -news[i])
        if not live:
            return plan
        full = sum(news[i] - done >= c0 for i in live)
        # The longest ``k`` tails ride; the others go alone.
        tails = [alone(news[i] - done) for i in live[full:]]
        best_k, best = 0, None
        for k in range(len(tails) + 1):
            est = seats(full + k)[0]
            for t in tails[k:]:
                est = add(est, t[0])
            if best is None or est <= best:
                best_k, best = k, est
        riders = live[:full + best_k]
        for b in seats(len(riders))[1]:
            seated, riders = riders[:b], riders[b:]
            plan.append(PrefillLaunch(
                tuple(seated), (done,) * len(seated),
                tuple(min(c0, news[i] - done) for i in seated),
                b, c0, lone_width if b == 1 else pages))
        for i, (_, chunks) in zip(live[full + best_k:], tails[best_k:]):
            at = done
            for c in chunks:
                plan.append(PrefillLaunch(
                    (i,), (at,), (min(c, news[i] - at),), 1, c, lone_width))
                at += c
        done += c0
