"""The sampler as it stood before it learned to skip work (PR 26):
``ops/sampling.py`` of commit c7ff821, copied verbatim, one executable
that sorts the vocabulary for every row. The drawing variants
(``draws=True``) of ``sample_tokens`` and ``spec_verify_sample`` must
return these functions' bits for the same keys — seeded replay
(``server/recovery.py``) redraws a dead request's tokens on another node
and rests on that. Not a test file; imported by ``test_ops.py`` and
``test_spec_decode.py``. Do not "tidy" it toward the live code."""

import jax
import jax.numpy as jnp


def _filtered_logits(logits, temperature, top_p, top_k=0):
    t = jnp.maximum(temperature, 1e-6)[..., None]
    scaled = logits.astype(jnp.float32) / t
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), scaled.shape[:-1])
    V = scaled.shape[-1]
    k_eff = jnp.where(top_k > 0, jnp.clip(top_k, 1, V), V)
    asc = jnp.sort(scaled, axis=-1)  # ascending
    kth = jnp.take_along_axis(asc, (V - k_eff)[..., None], axis=-1)
    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    sorted_logits = jnp.where(asc < kth, -jnp.inf, asc)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_mask = cum - probs >= top_p[..., None]
    rank = jnp.arange(cutoff_mask.shape[-1])
    cutoff_mask = cutoff_mask & (rank > 0)
    sorted_filtered = jnp.where(cutoff_mask, -jnp.inf, sorted_logits)
    threshold = jnp.min(
        jnp.where(jnp.isfinite(sorted_filtered), sorted_filtered, jnp.inf),
        axis=-1,
        keepdims=True,
    )
    return jnp.where(scaled < threshold, -jnp.inf, scaled)


@jax.jit
def sample_tokens(logits, rng, temperature=0.0, top_k=0, top_p=1.0):
    greedy = jnp.argmax(logits, axis=-1)
    temperature = jnp.broadcast_to(
        jnp.asarray(temperature, dtype=jnp.float32), logits.shape[:-1]
    )
    top_p = jnp.broadcast_to(
        jnp.asarray(top_p, dtype=jnp.float32), logits.shape[:-1]
    )
    filtered = _filtered_logits(logits, temperature, top_p, top_k)
    sampled = jax.random.categorical(rng, filtered, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled)


@jax.jit
def spec_verify_sample(
    logits, drafts, draft_len, rng, temperature, top_p, top_k=0
):
    B, C, V = logits.shape
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
    accept_len = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)

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
