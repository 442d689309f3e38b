"""Plain reference of the GLM-4.7-Flash (``glm4_moe_lite``) forward pass:
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
kernel, no cache, no request batching, and attention NOT absorbed: K and V
are derived from the latent for every token. The one copy: the CPU tests
and ``benchmarks/tools/ref_compare_glm.py`` import it; it imports nothing
of the program.

The layer (ISSUE 31, from the model's public ``config.json``): pre-norm
residual block, RMSNorm eps from the config, no biases, untied embedding
and head, ``rope_theta`` with no scaling.

- Attention, every layer, on ``h = RMSNorm(x)``:
  ``c_q = RMSNorm(h W_qa)`` [q_lora_rank]; ``q = c_q W_qb`` -> heads x
  [qk_nope ‖ qk_rope]; ``q_rope = RoPE(q_rope)``.
  ``h W_kva`` -> [kv_lora_rank ‖ qk_rope]: ``c_kv = RMSNorm(first part)``,
  ``k_rope = RoPE(last part)`` (one head, shared by all).
  ``c_kv W_kvb`` -> heads x [qk_nope ‖ v_head] = ``k_nope ‖ v``.
  ``score_ij = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) /
  sqrt(qk_nope + qk_rope)``, causal softmax in float32,
  ``o_i = sum_j p_ij v_j``; ``x += o W_o``.
- The first ``first_k_dense_replace`` layers: ``x += W_down(silu(W_gate
  h2) * W_up h2)``, width ``intermediate_size``.
- The other layers: ``s = sigmoid(h2 W_r)`` over all experts; the
  ``num_experts_per_tok`` largest of ``s + b`` are chosen (``b`` =
  ``e_score_correction_bias``; ``n_group`` = ``topk_group`` = 1: no group
  limit); ``w = s[chosen] / (sum s[chosen] + 1e-20) *
  routed_scaling_factor``; ``x += sum_e w_e E_e(h2) + E_shared(h2)``,
  every ``E`` a SwiGLU of width ``moe_intermediate_size`` (the shared one
  ``n_shared_experts`` times that). Experts are a loop: each is applied to
  every token and weighted by ``w_e``, zero where it was not chosen.
- Head: ``RMSNorm``, ``lm_head``.

Departures from the source, here and in the program alike:
the multi-token-prediction module (``num_nextn_predict_layers`` 1) is left
out — it changes no logit of the main model. Assumed, because the config
cannot settle them: the rotary pairing is rotate_half (no
``rope_interleave`` key), the selection bias is whatever the weights
pytree holds (a seeded draw), ``torch_dtype`` bfloat16 for the program.

**Weights.** The program's own pytree (``models/llama.py::init_params``):
``embed`` [V, H], ``lm_head`` [H, V], ``final_norm`` [H]; the leading dense
layers are ``params["dense_layers"]``, the sparse ones ``params["layers"]``
(each leaf stacked over its layers). A layer: ``attn_norm`` / ``mlp_norm``
[H], ``wq_a`` [H, q_lora], ``q_norm`` [q_lora], ``wq_b`` [q_lora, heads *
(nope + rope)], ``wkv_a`` [H, kv_lora + rope], ``kv_norm`` [kv_lora],
``wkv_b`` [kv_lora, heads * (nope + v)], ``wo`` [heads * v, H] (all
applied as ``x @ W``; head ``h`` owns a contiguous run of columns); dense:
``w_gate`` / ``w_up`` [H, I], ``w_down`` [I, H]; sparse: ``w_router``
[H, E], ``router_bias`` [E], ``w_gate`` / ``w_up`` [E, H, F], ``w_down``
[E, F, H], ``ws_gate`` / ``ws_up`` [H, F'], ``ws_down`` [F', H]. Leaves are
cast to float32 a layer at a time.

``forward_many(..., forced=ids)`` uses a given table of expert choices
[S, sparse layers, k] instead of its own top-k (the weights are still its
own scores of the forced set); ``weight_map=`` replaces each weight after
its cast (the lower precision a tolerance has to refuse).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512  # queries per block of the attention product (memory only)

_STATIC = ("n_heads", "nope", "rope", "v_dim", "kv_lora", "eps", "top_k",
           "norm_topk", "scaling", "sparse")


def spec_of(cfg) -> dict:
    """The numbers the forward pass needs, from the program's
    ``ModelConfig`` (read by attribute) or from a ``config.json`` dict of
    the source's keys (``benchmarks/configs/glm-4.7-flash-*.json``)."""
    if isinstance(cfg, dict):
        return {
            "n_layers": cfg["num_hidden_layers"],
            "first_k_dense": cfg["first_k_dense_replace"],
            "n_heads": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"],
            "v_dim": cfg["v_head_dim"],
            "kv_lora": cfg["kv_lora_rank"],
            "eps": cfg["rms_norm_eps"],
            "theta": float(cfg["rope_theta"]),
            "top_k": cfg["num_experts_per_tok"],
            "norm_topk": cfg["norm_topk_prob"],
            "scaling": float(cfg["routed_scaling_factor"]),
        }
    return {
        "n_layers": cfg.n_layers,
        "first_k_dense": cfg.first_k_dense,
        "n_heads": cfg.n_heads,
        "nope": cfg.qk_nope_head_dim,
        "rope": cfg.qk_rope_head_dim,
        "v_dim": cfg.v_head_dim,
        "kv_lora": cfg.kv_lora_rank,
        "eps": cfg.rms_eps,
        "theta": float(cfg.rope_theta),
        "top_k": cfg.n_experts_per_tok,
        "norm_topk": cfg.norm_topk_prob,
        "scaling": float(cfg.routed_scaling_factor),
    }


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, inv_freq):
    """x [S, heads, D] at positions 0..S-1; rotate_half, float32 angles."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """q, k [S, heads, D], v [S, heads, Dv] -> [S, heads * Dv]; causal,
    softmax in float32, a block of queries at a time."""
    S, n_heads, D = q.shape
    kpos = jnp.arange(S)[None, :]
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[s0:s0 + Q_BLOCK]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(D)
        seen = kpos <= (s0 + jnp.arange(qb.shape[0]))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v).reshape(qb.shape[0], -1))
    return jnp.concatenate(outs, axis=0)


def _swiglu(x, w_gate, w_up, w_down):
    return jnp.dot(jax.nn.silu(jnp.dot(x, w_gate)) * jnp.dot(x, w_up), w_down)


def route(x, lp, forced, *, top_k, norm_topk, scaling):
    """``(expert ids [S, k], weights [S, k], selection scores s + b
    [S, E])`` of ``x`` [S, H]."""
    s = jax.nn.sigmoid(jnp.dot(x, lp["w_router"]))
    biased = s + lp["router_bias"]
    top_e = jax.lax.top_k(biased, top_k)[1] if forced is None else forced
    w = jnp.take_along_axis(s, top_e, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return top_e.astype(jnp.int32), w * scaling, biased


def _experts(x, lp, top_e, top_w):
    E = lp["w_gate"].shape[0]
    hit = top_e[..., None] == jnp.arange(E)  # [S, k, E]
    w_full = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1)  # [S, E]

    def one(acc, xs):
        wg, wu, wd, w_e = xs
        return acc + w_e[:, None] * _swiglu(x, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (lp["w_gate"], lp["w_up"], lp["w_down"], w_full.T))
    return y


@partial(jax.jit, static_argnames=_STATIC)
def layer(x, lp, inv_freq, forced, *, n_heads, nope, rope, v_dim, kv_lora, eps,
          top_k, norm_topk, scaling, sparse):
    """One block on ``x`` [S, H] float32 (positions 0..S-1). Returns
    ``(x, expert ids [S, k], selection scores [S, E])`` (None, None for a
    dense layer)."""
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        h = _rms_norm(x, lp["attn_norm"], eps)
        c_q = _rms_norm(jnp.dot(h, lp["wq_a"]), lp["q_norm"], eps)
        q = jnp.dot(c_q, lp["wq_b"]).reshape(S, n_heads, nope + rope)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv_freq)], -1)
        kv = jnp.dot(h, lp["wkv_a"])
        c_kv = _rms_norm(kv[:, :kv_lora], lp["kv_norm"], eps)
        k_rope = _rope(kv[:, None, kv_lora:], inv_freq)  # [S, 1, rope]
        kv_up = jnp.dot(c_kv, lp["wkv_b"]).reshape(S, n_heads, nope + v_dim)
        k = jnp.concatenate(
            [kv_up[..., :nope], jnp.broadcast_to(k_rope, (S, n_heads, rope))], -1)
        x = x + jnp.dot(_attention(q, k, kv_up[..., nope:]), lp["wo"])
        h2 = _rms_norm(x, lp["mlp_norm"], eps)
        if not sparse:
            return x + _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"]), None, None
        top_e, top_w, biased = route(h2, lp, forced, top_k=top_k,
                                     norm_topk=norm_topk, scaling=scaling)
        y = _experts(h2, lp, top_e, top_w) + _swiglu(
            h2, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return x + y, top_e, biased


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """Logits [rows, V] float32 of the hidden rows ``x``."""
    with jax.default_matmul_precision("highest"):
        return jnp.dot(_rms_norm(x, final_norm, eps), lm_head)


def forward_many(params: dict, cfg, seqs, *, forced=None, logits_from=None,
                 weight_map=None) -> list[dict]:
    """The forward pass of several sequences, one after the other inside a
    walk over the layers, so that each layer's weights are cast (and, from
    the host, uploaded) once. Each result: ``logits`` [S - logits_from, V]
    float32, ``expert_ids`` [S, sparse layers, k], ``router_scores``
    [S, sparse layers, E] (the selection scores ``s + b``)."""
    spec = spec_of(cfg)
    n = len(seqs)
    seqs = [np.asarray(t, dtype=np.int32) for t in seqs]
    forced = forced or [None] * n
    logits_from = logits_from or [0] * n
    fix = weight_map or (lambda name, w: w)
    kd = spec["first_k_dense"]
    inv_freq = jnp.asarray(
        1.0 / spec["theta"] ** (np.arange(0, spec["rope"], 2, dtype=np.float64)
                                / spec["rope"]), jnp.float32)
    static = {k: spec[k] for k in _STATIC if k != "sparse"}

    def cast(name, w):
        return fix(name, jnp.asarray(w).astype(jnp.float32))

    xs = [jnp.asarray(params["embed"][t]).astype(jnp.float32) for t in seqs]
    ids = [[] for _ in seqs]
    scores = [[] for _ in seqs]
    for l in range(spec["n_layers"]):
        sparse = l >= kd
        stack, at = (params["layers"], l - kd) if sparse else (
            params["dense_layers"], l)
        lp = {name: cast(name, w[at]) for name, w in stack.items()}
        for i in range(n):
            choice = None
            if sparse and forced[i] is not None:
                choice = jnp.asarray(forced[i][:, l - kd], jnp.int32)
            xs[i], e, s = layer(xs[i], lp, inv_freq, choice, sparse=sparse, **static)
            if sparse:
                ids[i].append(np.asarray(e))
                scores[i].append(np.asarray(s))
        del lp
    final_norm = cast("final_norm", params["final_norm"])
    lm_head = cast("lm_head", params["lm_head"])
    return [{
        "logits": np.asarray(head(xs[i][logits_from[i]:], final_norm, lm_head,
                                  eps=spec["eps"])),
        "expert_ids": np.stack(ids[i], axis=1),
        "router_scores": np.stack(scores[i], axis=1),
    } for i in range(n)]


def forward(params: dict, cfg, tokens, *, forced=None,
            logits_from: int = 0) -> dict:
    """``forward_many`` of one sequence ``tokens`` [S]."""
    return forward_many(
        params, cfg, [tokens], forced=None if forced is None else [forced],
        logits_from=[logits_from])[0]
