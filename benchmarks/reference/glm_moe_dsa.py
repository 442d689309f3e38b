"""Plain reference of the GLM-5.2 (``glm_moe_dsa``) forward pass:
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
kernel, no cache, no request batching, attention NOT absorbed (K and V are
derived from the latent for every token) and the selection applied the
plain way: as a MASK over dense scores. The one copy: the CPU tests and
``benchmarks/tools/ref_compare_dsa.py`` import it; it imports nothing of
the program.

The layer (ISSUE 33, from the model's public ``config.json``): pre-norm
residual block, RMSNorm eps from the config, no biases, untied embedding
and head, ``rope_theta`` with no scaling.

- Attention, every layer, on ``h = RMSNorm(x)`` — as ``glm4_moe_lite``:
  ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb`` -> heads x [nope ‖ rope],
  rotary on the rope part; ``h W_kva`` -> [kv_lora ‖ rope]: ``c_kv =
  RMSNorm(first part)``, ``k_rope = RoPE(last part)`` (shared by all
  heads); ``c_kv W_kvb`` -> heads x [nope ‖ v] = ``k_nope ‖ v``;
  ``score_ts = q_t . k_s / sqrt(nope + rope)``.
- Indexer, on a layer whose ``indexer_types`` entry is "full": ``q^I =
  c_q W^I_q`` -> ``index_n_heads`` x ``index_head_dim``; ``k^I =
  LayerNorm(h W^I_k)`` (weight and bias, eps 1e-6), one key a token; the
  first ``qk_rope_head_dim`` elements of each rotate like the heads' rope
  part; ``w = h W^I_w * n_heads^-1/2 * head_dim^-1/2``; ``I_ts = sum_j
  w_tj relu(q^I_tj . k^I_s)`` for ``s <= t``; ``S_t`` = the ``index_topk``
  largest (all ``s <= t`` while ``t < index_topk``; a tie goes to the
  lower position).
- A "shared" layer uses the set of the nearest full layer before it.
- The softmax runs over ``s in S_t`` only; ``o_t = sum p_ts v_s``;
  ``x += o W_o``.
- FFN as ``glm4_moe_lite``: the leading layers dense SwiGLU; the others
  ``s = sigmoid(h2 W_r)`` over ALL routed experts, top-k by ``s + b``,
  weights ``s / (sum + 1e-20) * routed_scaling_factor``, plus the shared
  expert. **An expert share**: the weights pytree holds ``E_held`` experts
  (``w_gate`` [E_held, H, F]) of the router's ``E`` (``w_router`` [H, E]),
  experts ``[first_held, first_held + E_held)``; what the absent experts
  would have added is left out, here as in the program, and the weights
  stay normalised over all chosen.
- Head: ``RMSNorm``, ``lm_head`` (a vocabulary slice is a smaller
  vocabulary).

Departures from the source, here and in the program alike: no
multi-token-prediction module; no Hadamard rotation of ``q^I`` / ``k^I``
(orthogonal: no score changes) and no fp8 storage of them. Assumed:
rotate_half pairing (heads and indexer), the first ``qk_rope_head_dim``
index elements rotate, LayerNorm with bias on ``k^I``.

**Weights.** The program's pytree (``models/llama.py::init_params``): as
``glm4_moe_lite`` (``dense_layers`` / ``layers`` stacks), plus
``params["indexer"]`` stacked over the FULL layers in order: ``wq``
[q_lora, Hi * Di], ``wk`` [H, Di], ``k_norm`` / ``k_bias`` [Di], ``ww``
[H, Hi].

``forward_many(..., forced=ids)`` forces expert choices [S, sparse layers,
k] (global expert ids); ``forced_sel={position: [L_full, K] token
positions (-1: none)}`` forces the selection of those queries in each full
layer (and the shared layers after it); ``watch=[positions]`` returns the
reference's own index scores of those queries (``index_scores``
[L_full, len(watch), S], ``-inf`` past the query); ``selection="recent"``
replaces every selection by the most recent ``index_topk`` tokens (a
variant the comparison has to refuse); ``weight_map=`` replaces each
weight after its cast (another).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 128  # queries per block of the score products (memory only)
HEAD_BLOCK = 8  # heads per pass of the attention (memory only)

_STATIC = ("n_heads", "nope", "rope", "v_dim", "kv_lora", "eps", "top_k",
           "norm_topk", "scaling", "first_held", "index_heads", "index_dim",
           "index_topk", "sparse", "full", "selection")


def spec_of(cfg) -> dict:
    """The numbers the forward pass needs, from the program's
    ``ModelConfig`` (read by attribute) or from a ``config.json`` dict of
    the source's keys (``benchmarks/configs/glm-5.2-*.json``; the expert
    share under ``serving``)."""
    if isinstance(cfg, dict):
        rope = cfg["rope_parameters"]
        return {
            "n_layers": cfg["num_hidden_layers"],
            "first_k_dense": cfg["first_k_dense_replace"],
            "n_heads": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"],
            "v_dim": cfg["v_head_dim"],
            "kv_lora": cfg["kv_lora_rank"],
            "eps": cfg["rms_norm_eps"],
            "theta": float(rope["rope_theta"]),
            "top_k": cfg["num_experts_per_tok"],
            "norm_topk": cfg["norm_topk_prob"],
            "scaling": float(cfg["routed_scaling_factor"]),
            "first_held": cfg["serving"]["ep_rank"] * cfg["n_routed_experts"],
            "index_heads": cfg["index_n_heads"],
            "index_dim": cfg["index_head_dim"],
            "index_topk": cfg["index_topk"],
            "indexer_types": tuple(cfg["indexer_types"]),
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
        "first_held": cfg.ep_rank * (cfg.n_experts // cfg.ep_size),
        "index_heads": cfg.index_n_heads,
        "index_dim": cfg.index_head_dim,
        "index_topk": cfg.index_topk,
        "indexer_types": tuple(cfg.indexer_types),
    }


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps=1e-6):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w + b


def _rope(x, inv_freq):
    """x [S, heads, D] at positions 0..S-1; rotate_half, float32 angles."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(x, w_gate, w_up, w_down):
    return jnp.dot(jax.nn.silu(jnp.dot(x, w_gate)) * jnp.dot(x, w_up), w_down)


def route(x, lp, forced, *, top_k, norm_topk, scaling):
    """``(expert ids [S, k], weights [S, k], selection scores s + b
    [S, E])`` of ``x`` [S, H], over all ``E`` routed experts."""
    s = jax.nn.sigmoid(jnp.dot(x, lp["w_router"]))
    biased = s + lp["router_bias"]
    top_e = jax.lax.top_k(biased, top_k)[1] if forced is None else forced
    w = jnp.take_along_axis(s, top_e, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return top_e.astype(jnp.int32), w * scaling, biased


def _experts(x, lp, top_e, top_w, first_held: int):
    """The held experts' part: expert ``first_held + e`` of the router is
    ``w_gate[e]``; a chosen expert that is not held adds nothing."""
    held = lp["w_gate"].shape[0]
    hit = (top_e - first_held)[..., None] == jnp.arange(held)  # [S, k, held]
    w_full = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1)  # [S, held]

    def one(acc, xs):
        wg, wu, wd, w_e = xs
        return acc + w_e[:, None] * _swiglu(x, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (lp["w_gate"], lp["w_up"], lp["w_down"], w_full.T))
    return y


def _index_mask(c_q, h, ip, inv_freq, watch, *, index_heads, index_dim, rope,
                index_topk):
    """A full layer's selection as a mask [S, S] (query, key), and the
    index scores of the ``watch`` queries [len(watch), S] (-inf where the
    key is not visible)."""
    S = h.shape[0]
    qi = jnp.dot(c_q, ip["wq"]).reshape(S, index_heads, index_dim)
    ki = _layer_norm(jnp.dot(h, ip["wk"]), ip["k_norm"], ip["k_bias"])
    qi = jnp.concatenate([_rope(qi[..., :rope], inv_freq), qi[..., rope:]], -1)
    ki = jnp.concatenate(
        [_rope(ki[:, None, :rope], inv_freq)[:, 0], ki[:, rope:]], -1)
    wi = jnp.dot(h, ip["ww"]) * (index_heads ** -0.5 * index_dim ** -0.5)
    kpos = jnp.arange(S)[None, :]
    k = min(index_topk, S)

    def score_of(q_rows, w_rows, q_pos):
        dots = jnp.einsum("qjd,kd->qjk", q_rows, ki)
        score = jnp.einsum("qjk,qj->qk", jnp.maximum(dots, 0.0), w_rows)
        return jnp.where(kpos <= q_pos[:, None], score, -jnp.inf)

    def block(i):  # S is a multiple of Q_BLOCK (forward_many pads)
        s0 = i * Q_BLOCK
        score = score_of(
            jax.lax.dynamic_slice_in_dim(qi, s0, Q_BLOCK),
            jax.lax.dynamic_slice_in_dim(wi, s0, Q_BLOCK),
            s0 + jnp.arange(Q_BLOCK))
        vals, idx = jax.lax.top_k(score, k)  # ties: the lower position
        return jnp.put_along_axis(
            jnp.zeros(score.shape, bool), idx, vals > -jnp.inf, axis=-1,
            inplace=False)

    mask = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, S)
    return mask, score_of(qi[watch], wi[watch], watch)


def _attention(q, k, v, mask):
    """q, k [S, heads, D], v [S, heads, Dv], mask [S, S] (which keys a
    query attends: causal AND selected) -> [S, heads * Dv]; softmax in
    float32, a block of queries at a time."""
    S, n_heads, D = q.shape

    def block(i):
        s0 = i * Q_BLOCK
        qb = jax.lax.dynamic_slice_in_dim(q, s0, Q_BLOCK)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(D)
        seen = jax.lax.dynamic_slice_in_dim(mask, s0, Q_BLOCK)[None]
        m = jnp.max(jnp.where(seen, scores, -jnp.inf), axis=-1, keepdims=True)
        p = jnp.where(seen, jnp.exp(scores - jnp.where(m > -jnp.inf, m, 0.0)), 0.0)
        # a padded query past the sequence's end may see nothing: zeros
        probs = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
    return out.reshape(S, n_heads, v.shape[-1])


@partial(jax.jit, static_argnames=_STATIC, donate_argnames=("x", "mask"))
def layer(x, lp, ip, inv_freq, forced, mask, watch, forced_rows, *, n_heads,
          nope, rope, v_dim, kv_lora, eps, top_k, norm_topk, scaling,
          first_held, index_heads, index_dim, index_topk, sparse, full,
          selection):
    """One block on ``x`` [S, H] float32 (positions 0..S-1). ``mask``
    [S, S] is the selection carried from the nearest full layer (replaced
    when this layer is ``full``; its ``watch`` rows then overwritten by
    ``forced_rows`` [len(watch), S] where given). Returns ``(x, mask,
    expert ids, selection scores, index scores of the watched queries)``."""
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        h = _rms_norm(x, lp["attn_norm"], eps)
        c_q = _rms_norm(jnp.dot(h, lp["wq_a"]), lp["q_norm"], eps)
        watched = None
        if full:
            mask, watched = _index_mask(
                c_q, h, ip, inv_freq, watch, index_heads=index_heads,
                index_dim=index_dim, rope=rope, index_topk=index_topk)
            pos = jnp.arange(S)
            if selection == "recent":
                d = pos[:, None] - pos[None, :]
                mask = (d >= 0) & (d < index_topk)
            elif forced_rows is not None:
                mask = mask.at[watch].set(forced_rows)
        kv = jnp.dot(h, lp["wkv_a"])
        c_kv = _rms_norm(kv[:, :kv_lora], lp["kv_norm"], eps)
        k_rope = _rope(kv[:, None, kv_lora:], inv_freq)  # [S, 1, rope]
        w_q = lp["wq_b"].reshape(-1, n_heads, nope + rope)
        w_up = lp["wkv_b"].reshape(kv_lora, n_heads, nope + v_dim)
        w_o = lp["wo"].reshape(n_heads, v_dim, -1)
        attn = jnp.zeros_like(x)
        for h0 in range(0, n_heads, HEAD_BLOCK):  # a few heads at a time: memory only
            heads = slice(h0, h0 + HEAD_BLOCK)
            q = jnp.einsum("sr,rhd->shd", c_q, w_q[:, heads])
            q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv_freq)], -1)
            up = jnp.einsum("sr,rhd->shd", c_kv, w_up[:, heads])
            k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(
                k_rope, (S, up.shape[1], rope))], -1)
            o = _attention(q, k, up[..., nope:], mask)
            attn = attn + jnp.einsum("shv,hvd->sd", o, w_o[heads])
        x = x + attn
        h2 = _rms_norm(x, lp["mlp_norm"], eps)
        if not sparse:
            y = _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
            return x + y, mask, None, None, watched
        top_e, top_w, biased = route(h2, lp, forced, top_k=top_k,
                                     norm_topk=norm_topk, scaling=scaling)
        y = _experts(h2, lp, top_e, top_w, first_held) + _swiglu(
            h2, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return x + y, mask, top_e, biased, watched


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """Logits [rows, V] float32 of the hidden rows ``x``."""
    with jax.default_matmul_precision("highest"):
        return jnp.dot(_rms_norm(x, final_norm, eps), lm_head)


def _rows_of(positions, S: int):
    """[n, K] token positions (-1: none) as a mask [n, S]."""
    positions = np.asarray(positions)
    rows = np.zeros((len(positions), S), bool)
    for i, p in enumerate(positions):
        rows[i, p[p >= 0]] = True
    return jnp.asarray(rows)


def forward_many(params: dict, cfg, seqs, *, forced=None, logits_from=None,
                 weight_map=None, forced_sel=None, watch=None,
                 selection: str = "own") -> list[dict]:
    """The forward pass of several sequences, one after the other inside a
    walk over the layers, so that each layer's weights are cast (and, from
    the host, uploaded) once. Each result: ``logits`` [S - logits_from, V]
    float32, ``expert_ids`` [S, sparse layers, k], ``router_scores``
    [S, sparse layers, E] (the selection scores ``s + b``), ``selected``
    [S, L_full] (how many keys each query attends) and ``index_scores``
    [L_full, len(watch), S]."""
    spec = spec_of(cfg)
    n = len(seqs)
    real = [len(t) for t in seqs]
    # Whole blocks of queries: token 0 behind the end, which no real
    # position sees (causal) and whose rows are cut from every result.
    seqs = [np.concatenate([np.asarray(t, dtype=np.int32),
                            np.zeros(-len(t) % Q_BLOCK, np.int32)]) for t in seqs]
    if forced is not None:
        forced = [None if f is None else np.concatenate(
            [f, np.repeat(f[-1:], len(t) - len(f), axis=0)])
            for f, t in zip(forced, seqs)]
    forced = forced or [None] * n
    forced_sel = forced_sel or [None] * n
    watch = watch or [[] for _ in seqs]
    logits_from = logits_from or [0] * n
    fix = weight_map or (lambda name, w: w)
    kd = spec["first_k_dense"]
    kinds = spec["indexer_types"]
    inv_freq = jnp.asarray(
        1.0 / spec["theta"] ** (np.arange(0, spec["rope"], 2, dtype=np.float64)
                                / spec["rope"]), jnp.float32)
    static = {k: spec[k] for k in _STATIC if k in spec}

    def cast(name, w):
        return fix(name, jnp.asarray(w).astype(jnp.float32))

    # Between layers a sequence's hidden states and its selection wait on
    # the host: the device holds one sequence's and one layer's weights.
    xs = [np.asarray(params["embed"])[t].astype(np.float32) for t in seqs]
    masks = [np.zeros((len(t), len(t)), bool) for t in seqs]
    ids = [[] for _ in seqs]
    scores = [[] for _ in seqs]
    counts = [[] for _ in seqs]
    watched = [[] for _ in seqs]
    n_full = 0
    for l in range(spec["n_layers"]):
        sparse, full = l >= kd, kinds[l] == "full"
        stack, at = (params["layers"], l - kd) if sparse else (
            params["dense_layers"], l)
        lp = {name: cast(name, w[at]) for name, w in stack.items()}
        ip = None
        if full:
            ip = {name: cast("indexer." + name, w[n_full])
                  for name, w in params["indexer"].items()}
        for i in range(n):
            choice = rows = None
            if sparse and forced[i] is not None:
                choice = jnp.asarray(forced[i][:, l - kd], jnp.int32)
            w_i = jnp.asarray(watch[i], jnp.int32)
            if full and forced_sel[i] is not None:
                rows = _rows_of([forced_sel[i][p][n_full] for p in watch[i]],
                                len(seqs[i]))
            x, mask, e, s, seen = layer(
                jnp.asarray(xs[i]), lp, ip, inv_freq, choice,
                jnp.asarray(masks[i]), w_i, rows,
                sparse=sparse, full=full, selection=selection, **static)
            if sparse:
                ids[i].append(np.asarray(e))
                scores[i].append(np.asarray(s))
            if full:
                counts[i].append(np.asarray(jnp.sum(mask, axis=1)))
                watched[i].append(np.asarray(seen))
            xs[i], masks[i] = np.asarray(x), np.asarray(mask)
            del x, mask
        n_full += full
        del lp, ip
    final_norm = cast("final_norm", params["final_norm"])
    lm_head = cast("lm_head", params["lm_head"])
    return [{
        "logits": np.asarray(head(jnp.asarray(xs[i][logits_from[i]:real[i]]),
                                  final_norm, lm_head, eps=spec["eps"])),
        "expert_ids": np.stack(ids[i], axis=1)[:real[i]],
        "router_scores": np.stack(scores[i], axis=1)[:real[i]],
        "selected": np.stack(counts[i], axis=1)[:real[i]],
        "index_scores": np.stack(watched[i], axis=0)[..., :real[i]],
    } for i in range(n)]


def forward(params: dict, cfg, tokens, *, forced=None, logits_from: int = 0,
            forced_sel=None, watch=None, **kw) -> dict:
    """``forward_many`` of one sequence ``tokens`` [S]."""
    return forward_many(
        params, cfg, [tokens], forced=None if forced is None else [forced],
        logits_from=[logits_from],
        forced_sel=None if forced_sel is None else [forced_sel],
        watch=None if watch is None else [watch], **kw)[0]
