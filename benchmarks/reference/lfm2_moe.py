"""Plain reference of the LFM2-24B-A2B (``lfm2_moe``) forward pass:
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
kernel, no cache, no state slot, no request batching, one layer at a time
(so that the published depth fits). The one copy: the CPU tests and
``benchmarks/tools/ref_compare_lfm2.py`` import it; it imports nothing of
the program.

The block (ISSUE 39: the catalog's ``config``, the operator's internals
from the family's public modelling code): ``x += Op_l(RMSNorm(x;
attn_norm))``, ``x += FFN_l(RMSNorm(x; mlp_norm))``, eps from the config;
after the last block one more RMSNorm and the TIED embedding as the head.

- ``layer_types[l] == "conv"``, on ``u = RMSNorm(x)`` [S, H]: ``[B, C, xin]
  = split3(u W_in)`` (``W_in`` [H, 3H], no bias); ``z = B * xin``; ``c_t =
  sum_{k < taps} w[k] * z_{t - (taps - 1) + k}`` with zeros before
  position 0 (depthwise, causal; ``conv_L_cache`` = taps = 3; ``w`` [taps,
  H], tap 0 the oldest); ``Op = (C * c) W_out``. No activation inside. The
  whole sequence is convolved at once: nothing is carried.
- ``"full_attention"``: ``q, k, v = u W_q, u W_k, u W_v`` as heads of
  ``head_dim``; RMSNorm over each q and each k head with a learned
  [head_dim] weight; RoPE (rotate_half, ``rope_theta``, no scaling) over
  the whole head; scores ``q . k / sqrt(head_dim)`` (grouped: query head
  ``h`` reads kv head ``h // (heads / kv heads)``), causal softmax in
  float32; ``Op = o W_o``.
- ``l < num_dense_layers``: SwiGLU of width ``intermediate_size``. Else
  ``s = sigmoid(h2 W_r)`` over ALL experts; the ``num_experts_per_tok``
  largest of ``s + b`` are chosen; ``w = s[chosen] / (sum s[chosen] +
  1e-6) * routed_scaling_factor``; ``x += sum_e w_e E_e(h2)`` over the
  experts the weights HOLD (an expert share: experts ``first_held ..
  first_held + held``; what the absent ones would add is left out, as the
  program leaves it out — the deployment's other chips add it), each a
  SwiGLU of width ``moe_intermediate_size``. No shared expert.

Assumed, because the catalog's config cannot settle them (the
configuration file lists them): ``head_dim`` = hidden / heads, the
per-head norms, the order of ``W_in``'s three parts, no activation in the
operator, tied embeddings, the selection bias and the norms' weights
(whatever the weights pytree holds: seeded draws).

**Weights.** The program's own pytree (``models/llama.py::init_params``):
``embed`` [V, H], ``final_norm`` [H]; per-layer stacks ``dense_layers``
(``attn_norm`` / ``mlp_norm`` [H], ``w_gate`` / ``w_up`` [H, I], ``w_down``
[I, H]) and ``layers`` (the norms, ``w_router`` [H, E], ``router_bias``
[E], ``w_gate`` / ``w_up`` [held, H, F], ``w_down`` [held, F, H]); operator
stacks ``conv`` (``in_proj`` [H, 3H], ``conv_w`` [taps, H], ``out_proj``
[H, H]), one entry a conv layer in layer order, and ``attn`` (``wq`` [H,
heads * D], ``wk`` / ``wv`` [H, kv heads * D], ``wo`` [heads * D, H],
``q_head_norm`` / ``k_head_norm`` [D]), one an attention layer. Leaves are
cast to float32 a layer at a time; ``forward_many(..., forced=ids)`` uses a
given table of expert choices [S, sparse layers, k] instead of its own
top-k; ``weight_map=`` replaces each weight after its cast (the lower
precision a tolerance has to refuse); ``forget_at=`` is the other control
(``short_conv``).
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
    ``ModelConfig`` (read by attribute) or from a ``config.json`` dict of
    the source's keys (``benchmarks/configs/lfm2-24b-a2b-*.json``)."""
    if isinstance(cfg, dict):
        return {
            "layer_types": tuple(cfg["layer_types"]),
            "first_k_dense": cfg["num_dense_layers"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "taps": cfg["conv_L_cache"],
            "eps": cfg["norm_eps"],
            "theta": float(cfg["rope_parameters"]["rope_theta"]),
            "top_k": cfg["num_experts_per_tok"],
            "norm_topk": cfg["norm_topk_prob"],
            "scaling": float(cfg["routed_scaling_factor"]),
            # ``num_experts`` is what this chip holds of the router's
            # ``serving.router_experts``: rank r holds r x held onwards
            "first_held": (cfg.get("serving", {}).get("ep_rank", 0)
                           * cfg["num_experts"]),
        }
    return {
        "layer_types": tuple(cfg.layer_types),
        "first_k_dense": cfg.first_k_dense,
        "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "taps": cfg.conv_L_cache,
        "eps": cfg.rms_eps,
        "theta": float(cfg.rope_theta),
        "top_k": cfg.n_experts_per_tok,
        "norm_topk": cfg.norm_topk_prob,
        "scaling": float(cfg.routed_scaling_factor),
        "first_held": cfg.ep_rank * (cfg.n_experts // cfg.ep_size),
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
    """q [S, heads, D], k, v [S, kv heads, D] -> [S, heads * D]; grouped,
    causal, softmax in float32, a block of queries at a time."""
    S, n_heads, D = q.shape
    group = n_heads // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
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


def short_conv(u, lp, *, taps, forget_at=0):
    """The gated short convolution on ``u`` [S, H] from position 0.
    ``forget_at`` > 0 is the CONTROL a comparison has to refuse: positions
    from there on see nothing of the gated input before it — a request
    that resumed there from a zeroed state."""
    b_gate, c_gate, x_in = jnp.split(jnp.dot(u, lp["in_proj"]), 3, axis=-1)
    z = b_gate * x_in
    S = z.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), z.dtype), z])
    t = jnp.arange(S)
    c = 0.0
    for k in range(taps):
        seen = ~((t >= forget_at) & (t - (taps - 1) + k < forget_at))
        c = c + lp["conv_w"][k] * padded[k:k + S] * seen[:, None]
    return jnp.dot(c_gate * c, lp["out_proj"])


def attention(u, lp, inv_freq, *, n_heads, n_kv_heads, head_dim, eps):
    """One attention operator on ``u`` [S, H] (positions 0..S-1)."""
    S = u.shape[0]
    q = jnp.dot(u, lp["wq"]).reshape(S, n_heads, head_dim)
    k = jnp.dot(u, lp["wk"]).reshape(S, n_kv_heads, head_dim)
    v = jnp.dot(u, lp["wv"]).reshape(S, n_kv_heads, head_dim)
    q = _rope(_rms_norm(q, lp["q_head_norm"], eps), inv_freq)
    k = _rope(_rms_norm(k, lp["k_head_norm"], eps), inv_freq)
    return jnp.dot(_attention(q, k, v), lp["wo"])


def route(x, lp, forced=None, *, top_k, norm_topk, scaling):
    """``(expert ids [S, k], weights [S, k], selection scores s + b
    [S, E])`` of ``x`` [S, H], over all ``E`` experts; ``forced`` [S, k]
    replaces the top-k (the weights are still its own scores of the forced
    set)."""
    s = jax.nn.sigmoid(jnp.dot(x, lp["w_router"]))
    biased = s + lp["router_bias"]
    top_e = jax.lax.top_k(biased, top_k)[1] if forced is None else forced
    w = jnp.take_along_axis(s, top_e, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return top_e.astype(jnp.int32), w * scaling, biased


def _experts(x, lp, top_e, top_w, first_held):
    """The held experts' part of the layer: expert ``first_held + j`` is
    row ``j`` of the weights."""
    held = lp["w_gate"].shape[0]
    hit = top_e[..., None] == first_held + jnp.arange(held)  # [S, k, held]
    w_full = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1)  # [S, held]

    def one(acc, xs):
        wg, wu, wd, w_e = xs
        return acc + w_e[:, None] * _swiglu(x, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (lp["w_gate"], lp["w_up"], lp["w_down"], w_full.T))
    return y


_STATIC = ("kind", "sparse", "n_heads", "n_kv_heads", "head_dim", "taps", "eps",
           "top_k", "norm_topk", "scaling", "first_held", "forget_at")


@partial(jax.jit, static_argnames=_STATIC)
def layer(x, lp, inv_freq, forced=None, *, kind, sparse, n_heads, n_kv_heads,
          head_dim, taps, eps, top_k, norm_topk, scaling, first_held,
          forget_at=0):
    """One block on ``x`` [S, H] float32 (positions 0..S-1). Returns
    ``(x, expert ids [S, k], selection scores [S, E])`` (None, None for a
    dense-FFN layer)."""
    with jax.default_matmul_precision("highest"):
        u = _rms_norm(x, lp["attn_norm"], eps)
        if kind == "conv":
            x = x + short_conv(u, lp, taps=taps, forget_at=forget_at)
        else:
            x = x + attention(u, lp, inv_freq, n_heads=n_heads,
                              n_kv_heads=n_kv_heads, head_dim=head_dim, eps=eps)
        h2 = _rms_norm(x, lp["mlp_norm"], eps)
        if not sparse:
            return x + _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"]), None, None
        top_e, top_w, biased = route(h2, lp, forced, top_k=top_k,
                                     norm_topk=norm_topk, scaling=scaling)
        return x + _experts(h2, lp, top_e, top_w, first_held), top_e, biased


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, embed, *, eps):
    """Logits [rows, V] float32 of the hidden rows ``x`` (tied head)."""
    with jax.default_matmul_precision("highest"):
        return jnp.dot(_rms_norm(x, final_norm, eps), embed.T)


def forward_many(params: dict, cfg, seqs, *, forced=None, logits_from=None,
                 weight_map=None, forget_at=None) -> list[dict]:
    """The forward pass of several sequences, one after the other inside a
    walk over the layers, so that each layer's weights are cast (and, from
    the host, uploaded) once. Each result: ``logits`` [S - logits_from, V]
    float32, ``expert_ids`` [S, sparse layers, k], ``router_scores``
    [S, sparse layers, E] (the selection scores ``s + b``). ``forced[i]``
    [S, sparse layers, k] replaces sequence ``i``'s expert choices;
    ``forget_at[i]`` > 0 is ``short_conv``'s control."""
    spec = spec_of(cfg)
    seqs = [np.asarray(t, dtype=np.int32) for t in seqs]
    n = len(seqs)
    forced = forced or [None] * n
    forget_at = forget_at or [0] * n
    logits_from = logits_from or [0] * n
    fix = weight_map or (lambda name, w: w)
    kd = spec["first_k_dense"]
    D = spec["head_dim"]
    inv_freq = jnp.asarray(
        1.0 / spec["theta"] ** (np.arange(0, D, 2, dtype=np.float64) / D),
        jnp.float32)
    static = {k: spec[k] for k in _STATIC
              if k not in ("kind", "sparse", "forget_at")}

    def cast(name, w):
        return fix(name, jnp.asarray(w).astype(jnp.float32))

    xs = [jnp.asarray(params["embed"][t]).astype(jnp.float32) for t in seqs]
    ids = [[] for _ in seqs]
    scores = [[] for _ in seqs]
    place = {"conv": 0, "attn": 0}
    for l, kind_name in enumerate(spec["layer_types"]):
        kind = "conv" if kind_name == "conv" else "attn"
        sparse = l >= kd
        stack, at = (params["layers"], l - kd) if sparse else (
            params["dense_layers"], l)
        lp = {name: cast(name, w[at]) for name, w in stack.items()}
        lp |= {name: cast(name, w[place[kind]])
               for name, w in params[kind].items()}
        place[kind] += 1
        for i in range(n):
            choice = None
            if sparse and forced[i] is not None:
                choice = jnp.asarray(forced[i][:, l - kd], jnp.int32)
            xs[i], e, s = layer(xs[i], lp, inv_freq, choice, kind=kind,
                                sparse=sparse, forget_at=int(forget_at[i]),
                                **static)
            if sparse:
                ids[i].append(np.asarray(e))
                scores[i].append(np.asarray(s))
        del lp
    final_norm = cast("final_norm", params["final_norm"])
    embed = cast("embed", params["embed"])
    return [{
        "logits": np.asarray(head(xs[i][logits_from[i]:], final_norm, embed,
                                  eps=spec["eps"])),
        "expert_ids": np.stack(ids[i], axis=1),
        "router_scores": np.stack(scores[i], axis=1),
    } for i in range(n)]


def forward(params: dict, cfg, tokens, *, forced=None,
            logits_from: int = 0, forget_at: int = 0) -> dict:
    """``forward_many`` of one sequence ``tokens`` [S]."""
    return forward_many(
        params, cfg, [tokens], forced=None if forced is None else [forced],
        logits_from=[logits_from], forget_at=[forget_at])[0]
