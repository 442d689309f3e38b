"""Plain reference of the Ling-3.0-flash (``bailing_hybrid``) forward pass:
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
kernel, no cache, no state slots, no request batching; the linear-attention
layers as the TOKEN-BY-TOKEN recurrence, the latent attention NOT absorbed
(K and V derived from the latent for every token), the router with its
groups, the experts a loop over the ones held. The one copy: the CPU tests
and ``benchmarks/tools/ref_compare_kda.py`` import it; it imports nothing
of the program.

The layer (ISSUE 48, from the model's public ``config.json``): pre-norm
residual block, RMSNorm eps 1e-6, no bias anywhere, an untied head. Layer
``l`` (published numbering) is latent attention where ``(l + 1) % 6 == 0``
and KDA otherwise; the program's ``layer_types`` says which is which for
the layers held.

- **KDA layer** (Kimi Delta Attention, arXiv:2510.26692), ``heads`` heads
  of ``d`` keys and values, no positional encoding, ``h = RMSNorm(x)``:
  ``q~, k~, v~ = h W_q, h W_k, h W_v``; each passes a depth-wise causal
  convolution of ``taps`` taps (zeros before position 0) and SiLU;
  ``q = q~ / |q~| * d^-1/2``, ``k = k~ / |k~|`` a head (``|.|`` with 1e-6
  under the root), ``v = v~``. Decay a head and KEY CHANNEL: ``a = h
  W_alpha + dt_bias``; ``g = lower * sigmoid(exp(A_log_head) * a)`` in
  (lower, 0), ``lower`` = -5; ``alpha = exp(g)``. ``beta = sigmoid(h
  W_beta)`` a head. State ``S`` [d, d] a head, float32, zeros at position
  0: ``S <- (I - beta k k^T) Diag(alpha) S + beta k v^T``; ``o = S^T q``.
  ``o <- RMSNorm_head(o)`` (the head's own learned weight) ``* sigmoid(h
  W_g)``; ``x += o W_o``.
- **Latent layer**: ``q = h W_q`` -> heads x [nope ‖ rope] (NO bottleneck,
  no query norm); ``h W_kva`` -> [kv_lora ‖ rope]: ``c = RMSNorm(first)``,
  ``k_r = RoPE(last)`` (one head, shared); ``c W_kvb`` -> heads x [nope ‖
  v]; rotary on the ``rope``-wide parts (rotate_half); ``a_h = softmax(q_h
  [k_n; k_r]^T / sqrt(nope + rope) + causal) v_h``; ``a_h <- sigmoid(h
  W_g)_h a_h`` (one number a head); ``x += concat(a) W_o``.
- **FFN**, ``h2 = RMSNorm(x)``: the first ``first_k_dense`` layers a
  SwiGLU of ``intermediate``. The others: ``s = sigmoid(h2 W_r)`` over all
  ``E`` experts; groups of ``E / n_group``, a group's score the sum of its
  two largest ``s + b``, the ``topk_group`` best groups stay (ties to the
  lower group); the ``top_k`` largest ``s + b`` among their experts;
  ``w = scaling * s_sel / (sum s_sel + 1e-20)``; ``x += sum_e w_e E_e(h2)
  + E_shared(h2)`` over the experts the weights HOLD (an expert share:
  experts ``first_held .. first_held + held``; what the absent ones would
  add is left out, as the program leaves it out).
- Head: ``RMSNorm``, ``lm_head``.

Departures from the source, here and in the program alike: the
multi-token-prediction module is left out (it changes no logit of the
main model). Assumed, because the config cannot settle them (each with its
reason in ``benchmarks/configs/ling-3.0-flash-bf16-ep8.json``): ``W_alpha``
and the KDA output gate ``W_g`` are full projections; the safe gate's form
above; ``use_qk_norm`` is KDA's L2 normalisation and the latent layers
have the ``kv_a`` norm only; rotate_half; the selection bias is whatever
the pytree holds (a seeded draw); float32 state.

**Weights.** The program's own pytree (``models/llama.py::init_params``):
``embed`` [V, H], ``lm_head`` [H, V], ``final_norm`` [H]; per layer
``attn_norm`` / ``mlp_norm`` and the FFN in ``params["dense_layers"]``
(``w_gate`` / ``w_up`` [H, I], ``w_down`` [I, H]) or ``params["layers"]``
(``w_router`` [H, E], ``router_bias`` [E], ``w_gate`` / ``w_up`` [held, H,
F], ``w_down`` [held, F, H], ``ws_*``); a KDA layer's operator in
``params["kda"]`` (``wq`` / ``wk`` / ``wv`` / ``w_alpha`` / ``wg`` [H, C],
``conv_w`` [taps, 3C] over q ‖ k ‖ v with tap 0 the oldest position,
``dt_bias`` [C], ``A_log`` [heads], ``w_beta`` [H, heads], ``o_norm`` [C],
``wo`` [C, H]); a latent layer's in ``params["mla"]`` (``wq`` [H, heads *
(nope + rope)], ``wkv_a``, ``kv_norm``, ``wkv_b``, ``wg`` [H, heads],
``wo``). Leaves are cast to float32 a layer at a time.

``forward_many(..., forced=ids)`` uses a given table of expert choices
[S, sparse layers, k] instead of its own; ``weight_map=`` replaces each
weight after its cast and ``state_dtype=`` rounds the state after every
position (the lower precisions a tolerance has to refuse); ``without=``
names parts to leave out, each of which has to MATTER: ``"channel_decay"``
(one decay a head: the mean over its channels), ``"beta"`` (1),
``"conv"`` (q~, k~, v~ pass unconvolved), ``"gate"`` (the latent layers'
head-wise gate), ``"kda_gate"`` (the KDA output gate), ``"groups"`` (top-k
over all experts); ``forget_at=`` zeroes every KDA state and convolution
input at that position (a request that resumed there from a zeroed slot).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512  # queries per block of the attention product (memory only)


def spec_of(cfg) -> dict:
    """The numbers the forward pass needs, from the program's
    ``ModelConfig`` (read by attribute) or from the configuration file's
    dict of the source's keys (``benchmarks/configs/ling-3.0-flash-*.json``:
    ``layer_types`` is the file's own list of the layers held)."""
    if isinstance(cfg, dict):
        serving = cfg.get("serving", {})
        held = cfg["num_experts"]
        return {
            "layer_types": tuple(cfg["layer_types"]),
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
            "n_group": cfg["n_group"],
            "topk_group": cfg["topk_group"],
            "first_held": serving.get("ep_rank", 0) * held,
            "kda_heads": cfg["num_attention_heads"],
            "kda_dim": cfg["head_dim"],
            "taps": cfg["short_conv_kernel_size"],
            "lower": float(cfg["kda_lower_bound"]),
        }
    return {
        "layer_types": tuple(cfg.layer_types),
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
        "n_group": cfg.n_group,
        "topk_group": cfg.topk_group,
        "first_held": cfg.ep_rank * (cfg.n_experts // cfg.ep_size),
        "kda_heads": cfg.kda_heads,
        "kda_dim": cfg.kda_head_dim,
        "taps": cfg.kda_conv,
        "lower": float(cfg.kda_gate_lower_bound),
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
    """q, k [S, heads, D], v [S, heads, Dv] -> [S, heads, Dv]; causal,
    softmax in float32, a block of queries at a time."""
    S, _, D = q.shape
    kpos = jnp.arange(S)[None, :]
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[s0:s0 + Q_BLOCK]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(D)
        seen = kpos <= (s0 + jnp.arange(qb.shape[0]))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
    return jnp.concatenate(outs, axis=0)


def _swiglu(x, w_gate, w_up, w_down):
    return jnp.dot(jax.nn.silu(jnp.dot(x, w_gate)) * jnp.dot(x, w_up), w_down)


def _conv(u, w, taps, forget_at):
    """Depth-wise causal convolution of ``u`` [S, C] with ``w`` [taps, C]
    (tap 0 the oldest position), zeros before position 0 — and, for
    ``forget_at`` > 0, before that position too."""
    S = u.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u])
    out = 0.0
    for j in range(taps):
        part = padded[j:j + S]  # position t - (taps - 1) + j
        if forget_at:
            src = jnp.arange(S) - (taps - 1) + j
            dead = (src < forget_at) & (jnp.arange(S) >= forget_at)
            part = jnp.where(dead[:, None], 0.0, part)
        out = out + w[j] * part
    return out


def kda(h, lp, *, heads, dim, taps, lower, eps, without, state_dtype, forget_at):
    """A KDA layer's operator on ``h`` [S, H] -> [S, H]: the recurrence,
    one position at a time."""
    S = h.shape[0]
    C = heads * dim
    qkv = jnp.concatenate(
        [jnp.dot(h, lp["wq"]), jnp.dot(h, lp["wk"]), jnp.dot(h, lp["wv"])], -1)
    if "conv" not in without:
        qkv = _conv(qkv, lp["conv_w"], taps, forget_at)
    qkv = jax.nn.silu(qkv)
    q, k, v = (t.reshape(S, heads, dim) for t in jnp.split(qkv, 3, axis=-1))

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q, k = unit(q) * dim ** -0.5, unit(k)
    a = (jnp.dot(h, lp["w_alpha"]) + lp["dt_bias"]).reshape(S, heads, dim)
    g = lower * jax.nn.sigmoid(jnp.exp(lp["A_log"])[:, None] * a)
    if "channel_decay" in without:  # one decay a head
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    alpha = jnp.exp(g)
    beta = jax.nn.sigmoid(jnp.dot(h, lp["w_beta"]))  # [S, heads]
    if "beta" in without:
        beta = jnp.ones_like(beta)

    def step(S_, xs):
        qt, kt, vt, at, bt, t = xs
        if forget_at:
            S_ = jnp.where(t == forget_at, 0.0, S_)
        S_ = at[..., None] * S_  # Diag(alpha) S
        u = jnp.einsum("hkv,hk->hv", S_, kt)  # S^T k
        S_ = S_ + bt[:, None, None] * kt[..., None] * (vt - u)[:, None, :]
        if state_dtype is not None:
            # the state as a lower precision holds it. reduce_precision and
            # not a cast there and back: the TPU compiler drops such a pair
            # (excess precision is allowed), and the control then read 0.
            fi = jnp.finfo(state_dtype)
            S_ = jax.lax.reduce_precision(S_, fi.nexp, fi.nmant)
        return S_, jnp.einsum("hkv,hk->hv", S_, qt)

    _, o = jax.lax.scan(
        step, jnp.zeros((heads, dim, dim), jnp.float32),
        (q, k, v, alpha, beta, jnp.arange(S)))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = (o * lp["o_norm"].reshape(heads, dim)).reshape(S, C)
    if "kda_gate" not in without:
        o = o * jax.nn.sigmoid(jnp.dot(h, lp["wg"]))
    return jnp.dot(o, lp["wo"])


def latent(h, lp, inv_freq, *, n_heads, nope, rope, v_dim, kv_lora, eps, without):
    """A latent layer's operator on ``h`` [S, H] -> [S, H], not absorbed."""
    S = h.shape[0]
    q = jnp.dot(h, lp["wq"]).reshape(S, n_heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv_freq)], -1)
    kv = jnp.dot(h, lp["wkv_a"])
    c_kv = _rms_norm(kv[:, :kv_lora], lp["kv_norm"], eps)
    k_rope = _rope(kv[:, None, kv_lora:], inv_freq)  # [S, 1, rope]
    kv_up = jnp.dot(c_kv, lp["wkv_b"]).reshape(S, n_heads, nope + v_dim)
    k = jnp.concatenate(
        [kv_up[..., :nope], jnp.broadcast_to(k_rope, (S, n_heads, rope))], -1)
    a = _attention(q, k, kv_up[..., nope:])  # [S, heads, v]
    if "gate" not in without:
        a = a * jax.nn.sigmoid(jnp.dot(h, lp["wg"]))[..., None]
    return jnp.dot(a.reshape(S, -1), lp["wo"])


def route(x, lp, forced=None, *, top_k, norm_topk, scaling, n_group, topk_group):
    """``(expert ids [S, k], weights [S, k], selection scores s + b [S, E]
    — minus infinity outside the kept groups —, the same scores of every
    expert)`` of ``x`` [S, H]."""
    s = jax.nn.sigmoid(jnp.dot(x, lp["w_router"]))
    raw = biased = s + lp["router_bias"]
    if n_group > 1:
        S, E = biased.shape
        grouped = biased.reshape(S, n_group, E // n_group)
        two = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [S, n_group]
        kept = jax.lax.top_k(two, topk_group)[1]
        keep = jnp.any(kept[..., None] == jnp.arange(n_group), axis=1)
        biased = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(S, E)
    top_e = jax.lax.top_k(biased, top_k)[1] if forced is None else forced
    w = jnp.take_along_axis(s, top_e, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return top_e.astype(jnp.int32), w * scaling, biased, raw


def _experts(x, lp, top_e, top_w, first_held):
    """The held experts' part of the layer: expert ``first_held + j`` is
    the weights' ``j``-th."""
    held = lp["w_gate"].shape[0]
    hit = top_e[..., None] == first_held + jnp.arange(held)  # [S, k, held]
    w_full = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1)  # [S, held]

    def one(acc, xs):
        wg, wu, wd, w_e = xs
        return acc + w_e[:, None] * _swiglu(x, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (lp["w_gate"], lp["w_up"], lp["w_down"], w_full.T))
    return y


_STATIC = ("kind", "sparse", "n_heads", "nope", "rope", "v_dim", "kv_lora",
           "eps", "top_k", "norm_topk", "scaling", "n_group", "topk_group",
           "first_held", "kda_heads", "kda_dim", "taps", "lower", "without",
           "state_dtype", "forget_at")


@partial(jax.jit, static_argnames=_STATIC)
def layer(x, lp, inv_freq, forced=None, *, kind, sparse, n_heads, nope, rope,
          v_dim, kv_lora, eps, top_k, norm_topk, scaling, n_group, topk_group,
          first_held, kda_heads, kda_dim, taps, lower, without=(),
          state_dtype=None, forget_at=0):
    """One block on ``x`` [S, H] float32 (positions 0..S-1). Returns
    ``(x, expert ids [S, k], selection scores of every expert [S, E])``
    (None, None for a layer with the dense FFN)."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, lp["attn_norm"], eps)
        if kind == "kda":
            x = x + kda(h, lp, heads=kda_heads, dim=kda_dim, taps=taps,
                        lower=lower, eps=eps, without=without,
                        state_dtype=state_dtype, forget_at=forget_at)
        else:
            x = x + latent(h, lp, inv_freq, n_heads=n_heads, nope=nope,
                           rope=rope, v_dim=v_dim, kv_lora=kv_lora, eps=eps,
                           without=without)
        h2 = _rms_norm(x, lp["mlp_norm"], eps)
        if not sparse:
            return x + _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"]), None, None
        groups = (1, 1) if "groups" in without else (n_group, topk_group)
        top_e, top_w, _, raw = route(
            h2, lp, forced, top_k=top_k, norm_topk=norm_topk, scaling=scaling,
            n_group=groups[0], topk_group=groups[1])
        y = _experts(h2, lp, top_e, top_w, first_held) + _swiglu(
            h2, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return x + y, top_e, raw


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """Logits [rows, V] float32 of the hidden rows ``x``."""
    with jax.default_matmul_precision("highest"):
        return jnp.dot(_rms_norm(x, final_norm, eps), lm_head)


def forward_many(params: dict, cfg, seqs, *, forced=None, logits_from=None,
                 weight_map=None, without=(), state_dtype=None,
                 forget_at=None) -> list[dict]:
    """The forward pass of several sequences, one after the other inside a
    walk over the layers, so that each layer's weights are cast (and, from
    the host, uploaded) once. Each result: ``logits`` [S - logits_from, V]
    float32, ``expert_ids`` [S, sparse layers, k], ``router_scores`` [S,
    sparse layers, E] (the selection scores ``s + b`` of EVERY expert:
    ``shortfall`` applies the groups)."""
    spec = spec_of(cfg)
    n = len(seqs)
    seqs = [np.asarray(t, dtype=np.int32) for t in seqs]
    forced = forced or [None] * n
    logits_from = logits_from or [0] * n
    forget_at = forget_at or [0] * n
    fix = weight_map or (lambda name, w: w)
    kd = spec["first_k_dense"]
    types = spec["layer_types"]
    inv_freq = jnp.asarray(
        1.0 / spec["theta"] ** (np.arange(0, spec["rope"], 2, dtype=np.float64)
                                / spec["rope"]), jnp.float32)
    static = {k: spec[k] for k in _STATIC
              if k in spec} | {"without": tuple(without), "state_dtype": state_dtype}

    def cast(name, w):
        return fix(name, jnp.asarray(w).astype(jnp.float32))

    xs = [jnp.asarray(params["embed"][t]).astype(jnp.float32) for t in seqs]
    ids = [[] for _ in seqs]
    scores = [[] for _ in seqs]
    for l, kind in enumerate(types):
        sparse = l >= kd
        stack, at = (params["layers"], l - kd) if sparse else (
            params["dense_layers"], l)
        op = "kda" if kind == "kda" else "mla"
        place = sum(1 for t in types[:l] if (t == "kda") == (kind == "kda"))
        lp = {name: cast(name, w[at]) for name, w in stack.items()}
        lp |= {name: cast(name, w[place]) for name, w in params[op].items()}
        for i in range(n):
            choice = None
            if sparse and forced[i] is not None:
                choice = jnp.asarray(forced[i][:, l - kd], jnp.int32)
            xs[i], e, s = layer(
                xs[i], lp, inv_freq, choice, kind=op, sparse=sparse,
                forget_at=int(forget_at[i]), **static)
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


def shortfall(scores: np.ndarray, chosen: np.ndarray, n_group: int,
              topk_group: int) -> np.ndarray:
    """How far each of ``chosen`` [..., k] is from being chosen by the
    router whose selection scores are ``scores`` [..., E], under that
    router's OWN groups: 0 for an expert among its top k; for an expert of a
    kept group, what it lacks to the k-th score among the kept groups'
    experts; for an expert of a group that was not kept, what its group's
    score (the sum of its two best) lacks to the last kept group's."""
    *lead, E = scores.shape
    k = chosen.shape[-1]
    grouped = scores.reshape(*lead, n_group, E // n_group)
    g_score = np.sort(grouped, axis=-1)[..., -2:].sum(-1)  # [..., n_group]
    g_last = np.sort(g_score, axis=-1)[..., -topk_group]
    # ties to the lower group: a stable sort of the negated scores
    order = np.argsort(-g_score, axis=-1, kind="stable")[..., :topk_group]
    kept = np.zeros(g_score.shape, bool)
    np.put_along_axis(kept, order, True, axis=-1)
    masked = np.where(np.repeat(kept, E // n_group, axis=-1), scores, -np.inf)
    kth = np.sort(masked, axis=-1)[..., -k]
    of = chosen // (E // n_group)
    in_kept = np.take_along_axis(kept, of, axis=-1)
    lack_expert = kth[..., None] - np.take_along_axis(scores, chosen, axis=-1)
    lack_group = g_last[..., None] - np.take_along_axis(g_score, of, axis=-1)
    return np.maximum(np.where(in_kept, lack_expert, lack_group), 0.0)


def forward(params: dict, cfg, tokens, *, forced=None, logits_from: int = 0,
            forget_at: int = 0, **controls) -> dict:
    """``forward_many`` of one sequence ``tokens`` [S]."""
    return forward_many(
        params, cfg, [tokens], forced=None if forced is None else [forced],
        logits_from=[logits_from], forget_at=[forget_at], **controls)[0]
