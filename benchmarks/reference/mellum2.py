"""Plain reference of the Mellum2 forward pass: ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, no kernel, no cache, no
request batching. The one copy: the CPU tests and
``benchmarks/tools/ref_compare.py`` import it; it imports nothing of the
program.

The layer (ISSUE 27, from the model's public ``config.json``): pre-norm
residual block, RMSNorm eps from the config, no biases, untied embedding
and head.

- Attention: ``q = W_q x`` (``num_attention_heads`` x ``head_dim``),
  ``k``, ``v`` (``num_key_value_heads`` x ``head_dim``), grouped queries,
  logits / sqrt(head_dim), softmax in float32. Key ``j`` is visible to
  query ``i`` iff ``0 <= i - j`` and, on a ``sliding_attention`` layer,
  ``i - j < sliding_window`` (the window holds that many keys, the query's
  own among them).
- RoPE per layer type (``rope_parameters``): ``default`` frequencies, or
  YaRN as transformers' ``_compute_yarn_parameters`` computes it, with
  cos and sin multiplied by ``attention_factor``. rotate_half convention.
- Expert layer in every layer: ``r = W_r x`` over all experts,
  ``p = softmax(r)``, ``S`` = the ``num_experts_per_tok`` largest,
  ``w_e = p_e / sum_S p`` (``norm_topk_prob``),
  ``y = sum_S w_e W_down,e (silu(W_gate,e x) * W_up,e x)``. Experts are a
  loop: each is applied to every token and weighted by ``w_e``, zero
  where it was not chosen.

Assumed (the config cannot settle them): no q/k normalisation, a router
without bias, no multi-token-prediction head.

**Weights.** The program's own pytree (``models/llama.py::init_params``),
by this mapping and no other: ``embed`` [V, H], ``lm_head`` [H, V],
``final_norm`` [H]; per layer ``l`` of ``params["layers"]``: ``attn_norm``
/ ``mlp_norm`` [H], ``wq`` [H, Hq*D], ``wk`` / ``wv`` [H, Hkv*D], ``wo``
[Hq*D, H] (all applied as ``x @ W``; head ``h`` owns columns
``h*D:(h+1)*D``), ``w_router`` [H, E], ``w_gate`` / ``w_up`` [E, H, F],
``w_down`` [E, F, H]. Leaves may be numpy or jax arrays of any float
dtype, on the host or the device: each layer's are cast to float32 when
its turn comes, so a model that fills the chip in bf16 is walked through
one float32 layer at a time.

``forward(..., forced=ids)`` uses a given table of expert choices
[S, L, k] instead of its own top-k (the weights are still its own
probabilities, renormalised over the forced set): random weights make
expert choice a near-tie often, and a lower-precision step flips such
ties, after which two right implementations diverge.

``forward_many(..., weight_map=)`` replaces each weight after its cast:
``tools/ref_compare.py`` passes int8 per-channel rounding there, the
"nearest precision below" that its tolerance has to refuse.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 1024  # queries per block of the attention product (memory only)


def spec_of(cfg) -> dict:
    """The numbers the forward pass needs, from the program's
    ``ModelConfig`` (read by attribute) or from a ``config.json`` dict of
    the source's keys (``benchmarks/configs/mellum2-*.json``)."""
    if isinstance(cfg, dict):
        return {
            "n_layers": cfg["num_hidden_layers"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "rms_eps": cfg["rms_norm_eps"],
            "layer_types": tuple(cfg["layer_types"]),
            "sliding_window": cfg["sliding_window"],
            "rope": {k: dict(v) for k, v in cfg["rope_parameters"].items()},
            "top_k": cfg["num_experts_per_tok"],
            "norm_topk": cfg["norm_topk_prob"],
        }
    return {
        "n_layers": cfg.n_layers,
        "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "rms_eps": cfg.rms_eps,
        "layer_types": tuple(cfg.layer_types),
        "sliding_window": cfg.sliding_window,
        "rope": {k: dict(v) for k, v in cfg.rope_by_type},
        "top_k": cfg.n_experts_per_tok,
        "norm_topk": cfg.norm_topk_prob,
    }


def rope_table(head_dim: int, rp: dict) -> tuple[np.ndarray, float]:
    """``(inverse frequencies [head_dim / 2], cos/sin factor)`` of one
    layer type's ``rope_parameters``."""
    theta = float(rp["rope_theta"])
    pos_freqs = theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if rp.get("rope_type", "default") == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rp['rope_type']!r}")
    factor, orig = float(rp["factor"]), int(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = correction_dim(float(rp.get("beta_fast", 32)))
    high = correction_dim(float(rp.get("beta_slow", 1)))
    if rp.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)
    return inv.astype(np.float32), float(rp["attention_factor"])


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, inv_freq, factor):
    """x [S, heads, D]; rotate_half convention, float32 angles."""
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _attention(q, k, v, window: int):
    """q [S, Hq, D], k/v [S, Hkv, D] -> [S, Hq*D]; softmax in float32,
    a block of queries at a time."""
    S, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    kpos = jnp.arange(S)[None, :]
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[s0:s0 + Q_BLOCK].reshape(-1, Hkv, G, D)
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k,
                            preferred_element_type=jnp.float32) / math.sqrt(D)
        qpos = (s0 + jnp.arange(qb.shape[0]))[:, None]
        seen = kpos <= qpos
        if window:
            seen = seen & (qpos - kpos < window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        ob = jnp.einsum("hgqk,khd->qhgd", probs.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        outs.append(ob.reshape(-1, Hq * D).astype(q.dtype))
    return jnp.concatenate(outs, axis=0)


def _experts(x, lp, top_e, top_w):
    E = lp["w_gate"].shape[0]
    hit = top_e[..., None] == jnp.arange(E)  # [S, k, E]
    w_full = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1)  # [S, E]

    def one(acc, xs):
        wg, wu, wd, w_e = xs
        gate = jnp.dot(x, wg, preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu, preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        return acc + w_e[:, None] * jnp.dot(
            h, wd, preferred_element_type=jnp.float32), None

    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                        (lp["w_gate"], lp["w_up"], lp["w_down"], w_full.T))
    return y.astype(x.dtype)


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim", "eps",
                                   "window", "top_k", "norm_topk"))
def layer(x, lp, inv_freq, rope_factor, forced, *, n_heads, n_kv_heads,
          head_dim, eps, window, top_k, norm_topk):
    """One block on ``x`` [S, H] float32 (positions 0..S-1).
    ``forced`` [S, k] int32 or None. Returns ``(x, expert ids [S, k],
    router probabilities [S, E] float32)``."""
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        positions = jnp.arange(S)
        h = _rms_norm(x, lp["attn_norm"], eps)
        q = jnp.dot(h, lp["wq"]).reshape(S, n_heads, head_dim)
        k = jnp.dot(h, lp["wk"]).reshape(S, n_kv_heads, head_dim)
        v = jnp.dot(h, lp["wv"]).reshape(S, n_kv_heads, head_dim)
        q = _rope(q, positions, inv_freq, rope_factor)
        k = _rope(k, positions, inv_freq, rope_factor)
        x = x + jnp.dot(_attention(q, k, v, window), lp["wo"])
        h2 = _rms_norm(x, lp["mlp_norm"], eps)
        p = jax.nn.softmax(jnp.dot(h2, lp["w_router"]), axis=-1)
        if forced is None:
            top_p, top_e = jax.lax.top_k(p, top_k)
        else:
            top_e = forced
            top_p = jnp.take_along_axis(p, forced, axis=1)
        if norm_topk:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        x = x + _experts(h2, lp, top_e, top_p)
        return x, top_e.astype(jnp.int32), p


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """Logits [rows, V] float32 of the hidden rows ``x``."""
    with jax.default_matmul_precision("highest"):
        return jnp.dot(_rms_norm(x, final_norm, eps), lm_head,
                       preferred_element_type=jnp.float32)


def forward_many(params: dict, cfg, seqs, *, forced=None, logits_from=None,
                 weight_map=None) -> list[dict]:
    """``forward`` for several sequences, one after the other inside a
    walk over the layers, so that each layer's weights are cast (and, from
    the host, uploaded) once. ``weight_map(name, w)`` may replace a
    layer's weight after the cast (``tools/ref_compare.py``'s
    lower-precision variant)."""
    spec = spec_of(cfg)
    n = len(seqs)
    seqs = [np.asarray(t, dtype=np.int32) for t in seqs]
    forced = forced or [None] * n
    logits_from = logits_from or [0] * n
    tables = {kind: rope_table(spec["head_dim"], rp)
              for kind, rp in spec["rope"].items()}
    fix = weight_map or (lambda name, w: w)

    def cast(name, w):
        return fix(name, jnp.asarray(w).astype(jnp.float32))

    embed = params["embed"]
    xs = [jnp.asarray(embed[t]).astype(jnp.float32) for t in seqs]
    ids = [[] for _ in seqs]
    probs = [[] for _ in seqs]
    for l in range(spec["n_layers"]):
        kind = spec["layer_types"][l]
        lp = {name: cast(name, w[l]) for name, w in params["layers"].items()}
        inv_freq, factor = tables[kind]
        for i in range(n):
            xs[i], e, p = layer(
                xs[i], lp, jnp.asarray(inv_freq), factor,
                None if forced[i] is None
                else jnp.asarray(forced[i][:, l], jnp.int32),
                n_heads=spec["n_heads"], n_kv_heads=spec["n_kv_heads"],
                head_dim=spec["head_dim"], eps=spec["rms_eps"],
                window=spec["sliding_window"] if kind == "sliding_attention" else 0,
                top_k=spec["top_k"], norm_topk=spec["norm_topk"],
            )
            ids[i].append(np.asarray(e))
            probs[i].append(np.asarray(p))
        del lp
    final_norm = cast("final_norm", params["final_norm"])
    lm_head = cast("lm_head", params["lm_head"])
    return [{
        "logits": np.asarray(head(xs[i][logits_from[i]:], final_norm, lm_head,
                                  eps=spec["rms_eps"])),
        "expert_ids": np.stack(ids[i], axis=1),
        "router_probs": np.stack(probs[i], axis=1),
    } for i in range(n)]


def forward(params: dict, cfg, tokens, *, forced=None,
            logits_from: int = 0) -> dict:
    """The full forward pass over ``tokens`` [S] (one sequence, positions
    0..S-1). Returns ``logits`` [S - logits_from, V] float32 (positions
    ``logits_from`` on), ``expert_ids`` [S, L, k] and ``router_probs``
    [S, L, E] (numpy). ``forced`` [S, L, k]: expert choices to use."""
    return forward_many(
        params, cfg, [tokens], forced=None if forced is None else [forced],
        logits_from=[logits_from])[0]


def expert_layer(x, lp: dict, *, top_k: int, norm_topk: bool = True):
    """The expert layer alone on ``x`` [S, H] (float32): ``(y, ids)``."""
    with jax.default_matmul_precision("highest"):
        r = jnp.dot(x, lp["w_router"], preferred_element_type=jnp.float32)
        p = jax.nn.softmax(r, axis=-1)
        top_p, top_e = jax.lax.top_k(p, top_k)
        if norm_topk:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        return _experts(x, lp, top_e, top_p), top_e
