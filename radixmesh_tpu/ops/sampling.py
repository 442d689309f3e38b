"""Token sampling: greedy / temperature / top-k / top-p, fully jittable
(static control flow; masking instead of data-dependent branches), plus
the speculative-decoding verifier (exact rejection sampling against a
point-mass draft). Both take one static ``draws``: a launch none of whose
rows draws (the caller knows its rows' temperatures before it launches)
compiles to the argmax alone — no vocabulary sort, no softmax, no random
draw."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _filtered_logits(
    logits: jnp.ndarray,
    temperature: jnp.ndarray,  # broadcastable to logits.shape[:-1]
    top_p: jnp.ndarray,
    top_k: jnp.ndarray | int = 0,
) -> jnp.ndarray:
    """Temperature-scaled logits with top-k/top-p masking (-inf outside
    the nucleus) — the distribution both the sampler and the speculative
    verifier must agree on. ``top_k`` may be a per-row array (0 = off);
    the kth threshold is a per-row gather on the sorted logits, so k
    stays dynamic without recompiling."""
    t = jnp.maximum(temperature, 1e-6)[..., None]
    scaled = logits.astype(jnp.float32) / t
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), scaled.shape[:-1])
    V = scaled.shape[-1]
    # ONE sort serves both filters: the per-row kth threshold is a gather
    # on the ascending sort (rows with k == 0 use k = V, a no-op), and
    # the descending sorted view for top-p is the same sort reversed with
    # the below-threshold prefix masked — no second O(V log V) pass on
    # the per-token hot path.
    k_eff = jnp.where(top_k > 0, jnp.clip(top_k, 1, V), V)
    asc = jnp.sort(scaled, axis=-1)  # ascending
    kth = jnp.take_along_axis(asc, (V - k_eff)[..., None], axis=-1)
    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    # top-p (nucleus): keep the smallest set of tokens with cumulative
    # probability >= top_p, always including the argmax.
    sorted_logits = jnp.where(asc < kth, -jnp.inf, asc)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_mask = cum - probs >= top_p[..., None]
    # The argmax (sorted position 0) is always kept, even for top_p == 0.
    rank = jnp.arange(cutoff_mask.shape[-1])
    cutoff_mask = cutoff_mask & (rank > 0)
    sorted_filtered = jnp.where(cutoff_mask, -jnp.inf, sorted_logits)
    # Map the per-row threshold back to the unsorted logits.
    threshold = jnp.min(
        jnp.where(jnp.isfinite(sorted_filtered), sorted_filtered, jnp.inf),
        axis=-1,
        keepdims=True,
    )
    return jnp.where(scaled < threshold, -jnp.inf, scaled)


@partial(jax.jit, static_argnames=("draws",))
def sample_tokens(
    logits: jnp.ndarray,
    rng: jax.Array | None,
    temperature: float | jnp.ndarray = 0.0,
    top_k: int | jnp.ndarray = 0,
    top_p: float | jnp.ndarray = 1.0,
    *,
    draws: bool = True,
) -> jnp.ndarray:
    """Sample one token id per row of ``logits`` [..., vocab].

    ``temperature==0`` → greedy. ``top_k``/``top_p`` filter before the
    categorical draw. ``draws`` (static) says whether ANY row of this
    launch may have ``temperature > 0``. True: all paths execute and
    selection is by ``jnp.where``, so one executable serves every setting
    of the dynamic args — a single drawing row makes every row of the
    launch pay the sort. False: the argmax and nothing else; ``rng`` and
    the three settings are not read (``rng`` may be None). With every
    temperature 0 the two return the same ids.
    ``temperature``/``top_k``/``top_p`` may be scalars or per-row arrays
    of shape ``logits.shape[:-1]`` (the continuous-batching engine passes
    one value per batch row; ``top_k`` is dynamic — no recompile per k).
    """
    greedy = jnp.argmax(logits, axis=-1)
    if not draws:
        return greedy
    temperature = jnp.broadcast_to(
        jnp.asarray(temperature, dtype=jnp.float32), logits.shape[:-1]
    )
    top_p = jnp.broadcast_to(
        jnp.asarray(top_p, dtype=jnp.float32), logits.shape[:-1]
    )
    filtered = _filtered_logits(logits, temperature, top_p, top_k)
    sampled = jax.random.categorical(rng, filtered, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled)


@partial(jax.jit, static_argnames=("draws",))
def spec_verify_sample(
    logits: jnp.ndarray,  # [B, C, V] verify-pass logits (C = gamma + 1)
    drafts: jnp.ndarray,  # [B, C-1] draft token per position (pad arbitrary)
    draft_len: jnp.ndarray,  # [B] real draft tokens per row (0..C-1)
    rng: jax.Array | None,
    temperature: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    top_k: jnp.ndarray | int = 0,  # [B] (0 = off)
    *,
    draws: bool = True,
):
    """Exact speculative verification against a point-mass draft.

    Position ``i``'s target distribution ``p_i`` is the SAME filtered
    (temperature/top-p) distribution plain decode samples from. Draft
    ``d_i`` is accepted with probability ``p_i(d_i)`` (for a point-mass
    proposal this is the Leviathan/Chen rule); the first rejection at
    position ``a`` emits one token from the residual — ``p_a`` with
    ``d_a``'s mass removed, renormalized — and full acceptance emits from
    ``p_gamma``. The emitted sequence is then distributed EXACTLY as
    step-by-step sampling: P(d) = p(d) on accept, and for x != d,
    (1 - p(d)) * p(x)/(1 - p(d)) = p(x) on reject. Greedy rows
    (temperature 0) degrade to argmax-prefix matching, and a launch with
    no other row (static ``draws`` False, as in ``sample_tokens``) is that
    matching alone: no filtered distribution, no uniform draw, the same
    ``(accept_len, bonus)``.

    Returns ``(accept_len [B], bonus [B])``: rows emit
    ``drafts[:accept_len]`` then ``bonus``.
    """
    B, C, V = logits.shape
    if not draws:
        greedy_tok = jnp.argmax(logits, axis=-1)  # [B, C]
        ok = (greedy_tok[:, : C - 1] == drafts) & (
            jnp.arange(C - 1)[None, :] < draft_len[:, None]
        )
        accept_len = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        bonus = jnp.take_along_axis(greedy_tok, accept_len[:, None], axis=1)
        return accept_len, bonus[:, 0].astype(jnp.int32)
    temperature = jnp.asarray(temperature, jnp.float32)
    top_p = jnp.asarray(top_p, jnp.float32)
    greedy_row = temperature <= 0.0  # [B]
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), temperature.shape)
    filtered = _filtered_logits(
        logits, temperature[:, None], top_p[:, None], top_k[:, None]
    )  # [B, C, V]
    probs = jax.nn.softmax(filtered, axis=-1)
    greedy_tok = jnp.argmax(logits, axis=-1)  # [B, C]

    pos = jnp.arange(C - 1)
    p_draft = jnp.take_along_axis(
        probs[:, : C - 1], drafts[..., None], axis=-1
    )[..., 0]  # [B, C-1]
    accept_prob = jnp.where(
        greedy_row[:, None],
        (greedy_tok[:, : C - 1] == drafts).astype(jnp.float32),
        p_draft,
    )
    key_u, key_cat = jax.random.split(rng)
    u = jax.random.uniform(key_u, (B, C - 1))
    ok = (u < accept_prob) & (pos[None, :] < draft_len[:, None])
    # Longest all-accepted prefix.
    accept_len = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)

    # Bonus token from position accept_len: the residual on rejection
    # (accept_len < draft_len), the full distribution otherwise.
    p_a = probs[jnp.arange(B), accept_len]  # [B, V] one row-gather each
    d_a = jnp.take_along_axis(
        drafts, jnp.minimum(accept_len, C - 2)[:, None], axis=1
    )[:, 0]  # [B]
    rejected = accept_len < draft_len
    residual = jnp.where(
        rejected[:, None] & (jnp.arange(V)[None, :] == d_a[:, None]),
        0.0,
        p_a,
    )
    logres = jnp.log(jnp.maximum(residual, 1e-30))
    logres = jnp.where(residual > 0.0, logres, -jnp.inf)
    sampled_bonus = jax.random.categorical(key_cat, logres, axis=-1)
    greedy_bonus = jnp.take_along_axis(
        greedy_tok, accept_len[:, None], axis=1
    )[:, 0]
    bonus = jnp.where(greedy_row, greedy_bonus, sampled_bonus)
    return accept_len, bonus.astype(jnp.int32)
