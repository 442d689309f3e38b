"""Plain reference of the Laguna-XS.2 forward pass: ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, no kernel, no cache, no
request batching, experts as a loop. The one copy: the CPU tests and
``benchmarks/tools/ref_compare_laguna.py`` import it; it imports nothing of
the program.

The layer (ISSUE 45, from the model's public ``config.json``): pre-norm
residual block, RMSNorm eps 1e-6, no bias, untied embedding and head. For
layer ``l`` of type ``t`` in {full_attention, sliding_attention}
(``layer_types``) with ``H_t`` query heads (``num_attention_heads_per_layer``:
48 | 64) over 8 kv heads of 128:

1. ``h = RMSNorm(x)``; ``q = h Wq_t`` -> [H_t, D], ``k = h Wk``, ``v = h Wv``
   -> [Hkv, D].
2. Rotary (``rope_parameters`` of the type), transformers' convention: the
   first ``r = partial_rotary_factor * D`` dims of every head rotate as two
   halves (rotate_half over those ``r``), the rest pass. Full layers: ``r =
   D / 2``, YaRN as transformers' ``_compute_yarn_parameters`` computes it
   OVER ``r`` dims, cos and sin times ``attention_factor``. Sliding layers:
   ``r = D``, default frequencies.
3. ``a_h = softmax(q_h k_{h // (H_t / Hkv)}^T / sqrt(D) + mask) v``; causal;
   on a sliding layer query ``i`` sees key ``j`` iff ``0 <= i - j <
   sliding_window`` (the query's own among the window's keys).
4. Gate (``gating: true``): ``g = sigmoid(h Wg_t)`` -> [H_t]; ``a_h <- g_h
   a_h``; ``x <- x + concat_h(a_h) Wo_t``.
5. ``h2 = RMSNorm(x)``. A dense layer (``mlp_layer_types``: the first):
   ``x += W_down(silu(W_gate h2) * W_up h2)``. A sparse layer: ``s =
   sigmoid(h2 W_r)`` over all experts; the ``num_experts_per_tok`` largest
   of ``s + b``; ``w = moe_routed_scaling_factor * s_sel / sum(s_sel)``;
   ``x += sum_e w_e E_e(h2) + E_shared(h2)`` (weights on the outputs).
6. Final RMSNorm, the head.

Assumed (the config cannot settle them; ``models/laguna.py`` has the
reasons): the gate is head-wise, a sigmoid, from the layer's normed input;
the router is DeepSeek-V3's without expert groups; no q/k normalisation, no
multi-token-prediction head.

**Weights.** The program's own pytree (``models/llama.py::init_params``),
by this mapping and no other: ``embed`` [V, H], ``lm_head`` [H, V],
``final_norm`` [H]; the norms and the FFN of layer ``l`` from
``params["dense_layers"]`` (``l`` < the number of dense layers: ``w_gate`` /
``w_up`` [H, F], ``w_down`` [F, H]) or ``params["layers"]`` at ``l`` minus
that number (``w_router`` [H, E], ``router_bias`` [E], ``w_gate`` / ``w_up``
[E, H, F'], ``w_down`` [E, F', H], ``ws_gate`` / ``ws_up`` [H, F'],
``ws_down`` [F', H]); its attention matrices from ``params[t]`` at the
layer's place among the layers of type ``t``: ``wq`` [H, H_t * D], ``wk`` /
``wv`` [H, Hkv * D], ``wo`` [H_t * D, H], ``wg`` [H, H_t] (all applied as
``x @ W``; head ``h`` owns columns ``h * D:(h + 1) * D``). Leaves may be
numpy or jax arrays of any float dtype: each layer's are cast to float32
when its turn comes.

``forward(..., forced=ids)`` uses a given table of expert choices [S,
L_sparse, k] instead of its own top-k (weights still its own scores,
renormalised over the forced set). ``forward_many(..., weight_map=)``
replaces each weight after its cast (``tools/ref_compare_laguna.py``'s
int8 variant). ``without=`` names pieces to LEAVE OUT, for the controls
that have to disagree: ``"gate"`` (no output gate), ``"partial_rotary"``
(the sliding layers' rule on every layer: the whole head rotates by the
full layers' own table stretched to ``D``), ``"head_groups"`` (query head
``h`` reads kv head ``h // G`` with the OTHER type's ``G``).
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
    the source's keys (``benchmarks/configs/laguna-*.json``)."""
    if isinstance(cfg, dict):
        types = tuple(cfg["layer_types"])
        heads = {t: h for t, h in zip(types, cfg["num_attention_heads_per_layer"])}
        # one entry a layer type (the source repeats the full layers'
        # original_max_position_embeddings beside them: a number, no type)
        rope = {kind: dict(rp) for kind, rp in cfg["rope_parameters"].items()
                if isinstance(rp, dict)}
        return {
            "n_layers": cfg["num_hidden_layers"],
            "heads": heads,
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "rms_eps": cfg["rms_norm_eps"],
            "layer_types": types,
            "sliding_window": cfg["sliding_window"],
            "rope": rope,
            "gate": bool(cfg["gating"]),
            "n_dense": sum(t == "dense" for t in cfg["mlp_layer_types"]),
            "top_k": cfg["num_experts_per_tok"],
            "scaling": float(cfg["moe_routed_scaling_factor"]),
        }
    return {
        "n_layers": cfg.n_layers,
        "heads": dict(cfg.heads_by_type),
        "n_kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "rms_eps": cfg.rms_eps,
        "layer_types": tuple(cfg.layer_types),
        "sliding_window": cfg.sliding_window,
        "rope": {k: dict(v) for k, v in cfg.rope_by_type},
        "gate": cfg.attn_gate,
        "n_dense": cfg.first_k_dense,
        "top_k": cfg.n_experts_per_tok,
        "scaling": float(cfg.routed_scaling_factor),
    }


def rope_table(head_dim: int, rp: dict) -> tuple[np.ndarray, float, int]:
    """``(inverse frequencies [r / 2], cos/sin factor, r)`` of one layer
    type's ``rope_parameters``; ``r`` the rotating dims of a head."""
    theta = float(rp["rope_theta"])
    r = int(float(rp.get("partial_rotary_factor", 1.0)) * head_dim)
    pos_freqs = theta ** (np.arange(0, r, 2, dtype=np.float64) / r)
    if rp.get("rope_type", "default") == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0, r
    if rp["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rp['rope_type']!r}")
    factor, orig = float(rp["factor"]), int(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return r * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = correction_dim(float(rp.get("beta_fast", 32)))
    high = correction_dim(float(rp.get("beta_slow", 1)))
    if rp.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)
    return inv.astype(np.float32), float(rp["attention_factor"]), r


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, inv_freq, factor):
    """x [S, heads, D]: the first ``2 * len(inv_freq)`` dims rotate
    (rotate_half over them), the rest pass."""
    r = 2 * inv_freq.shape[0]
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], axis=-1)


def _attention(q, k, v, window: int, group: int):
    """q [S, Hq, D], k/v [S, Hkv, D] -> [S, Hq, D]; query head ``h`` reads
    kv head ``h // group``; a block of queries at a time."""
    S, Hq, D = q.shape
    kv_of = jnp.minimum(jnp.arange(Hq) // group, k.shape[1] - 1)
    k, v = k[:, kv_of], v[:, kv_of]  # [S, Hq, D]
    kpos = jnp.arange(S)[None, :]
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[s0:s0 + Q_BLOCK]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(D)
        qpos = (s0 + jnp.arange(qb.shape[0]))[:, None]
        seen = kpos <= qpos
        if window:
            seen = seen & (qpos - kpos < window)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
    return jnp.concatenate(outs, axis=0)


def _swiglu(x, w_gate, w_up, w_down):
    return jnp.dot(jax.nn.silu(jnp.dot(x, w_gate)) * jnp.dot(x, w_up), w_down)


def route(x, lp, forced, *, top_k, scaling):
    """``(expert ids [S, k], weights [S, k], selection scores s + b [S,
    E])`` of ``x`` [S, H]."""
    s = jax.nn.sigmoid(jnp.dot(x, lp["w_router"]))
    biased = s + lp["router_bias"]
    top_e = jax.lax.top_k(biased, top_k)[1] if forced is None else forced
    w = jnp.take_along_axis(s, top_e, axis=1)
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


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim", "eps",
                                   "window", "group", "gate", "top_k",
                                   "scaling", "sparse"))
def layer(x, lp, inv_freq, rope_factor, forced, *, n_heads, n_kv_heads,
          head_dim, eps, window, group, gate, top_k, scaling, sparse):
    """One block on ``x`` [S, H] float32 (positions 0..S-1). Returns ``(x,
    expert ids [S, k], selection scores [S, E])`` (None, None for a dense
    layer)."""
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        positions = jnp.arange(S)
        h = _rms_norm(x, lp["attn_norm"], eps)
        q = jnp.dot(h, lp["wq"]).reshape(S, n_heads, head_dim)
        k = jnp.dot(h, lp["wk"]).reshape(S, n_kv_heads, head_dim)
        v = jnp.dot(h, lp["wv"]).reshape(S, n_kv_heads, head_dim)
        q = _rope(q, positions, inv_freq, rope_factor)
        k = _rope(k, positions, inv_freq, rope_factor)
        a = _attention(q, k, v, window, group)  # [S, Hq, D]
        if gate:
            a = a * jax.nn.sigmoid(jnp.dot(h, lp["wg"]))[:, :, None]
        x = x + jnp.dot(a.reshape(S, -1), lp["wo"])
        h2 = _rms_norm(x, lp["mlp_norm"], eps)
        if not sparse:
            return x + _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"]), None, None
        top_e, top_w, biased = route(h2, lp, forced, top_k=top_k, scaling=scaling)
        y = _experts(h2, lp, top_e, top_w) + _swiglu(
            h2, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return x + y, top_e, biased


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """Logits [rows, V] float32 of the hidden rows ``x``."""
    with jax.default_matmul_precision("highest"):
        return jnp.dot(_rms_norm(x, final_norm, eps), lm_head)


def forward_many(params: dict, cfg, seqs, *, forced=None, logits_from=None,
                 weight_map=None, without=()) -> list[dict]:
    """``forward`` for several sequences, one after the other inside a walk
    over the layers, so that each layer's weights are cast (and, from the
    host, uploaded) once."""
    spec = spec_of(cfg)
    unknown = set(without) - {"gate", "partial_rotary", "head_groups"}
    if unknown:
        raise ValueError(f"unknown pieces {sorted(unknown)}")
    n = len(seqs)
    seqs = [np.asarray(t, dtype=np.int32) for t in seqs]
    forced = forced or [None] * n
    logits_from = logits_from or [0] * n
    D = spec["head_dim"]
    tables = {}
    for kind, rp in spec["rope"].items():
        if "partial_rotary" in without:
            rp = {k: v for k, v in rp.items() if k != "partial_rotary_factor"}
        tables[kind] = rope_table(D, rp)
    fix = weight_map or (lambda name, w: w)

    def cast(name, w):
        return fix(name, jnp.asarray(w).astype(jnp.float32))

    xs = [jnp.asarray(params["embed"][t]).astype(jnp.float32) for t in seqs]
    ids = [[] for _ in seqs]
    scores = [[] for _ in seqs]
    kd = spec["n_dense"]
    types = spec["layer_types"]
    for l in range(spec["n_layers"]):
        kind = types[l]
        stack, at = (params["dense_layers"], l) if l < kd else (
            params["layers"], l - kd)
        lp = {name: cast(name, w[at]) for name, w in stack.items()}
        place = types[:l].count(kind)
        lp |= {name: cast(name, w[place]) for name, w in params[kind].items()}
        inv_freq, factor, _ = tables[kind]
        n_heads = spec["heads"][kind]
        group = n_heads // spec["n_kv_heads"]
        if "head_groups" in without:
            other = next(h for t, h in spec["heads"].items() if t != kind)
            group = other // spec["n_kv_heads"]
        for i in range(n):
            xs[i], e, s = layer(
                xs[i], lp, jnp.asarray(inv_freq), factor,
                None if forced[i] is None or l < kd
                else jnp.asarray(forced[i][:, l - kd], jnp.int32),
                n_heads=n_heads, n_kv_heads=spec["n_kv_heads"], head_dim=D,
                eps=spec["rms_eps"],
                window=spec["sliding_window"] if kind == "sliding_attention" else 0,
                group=group, gate=spec["gate"] and "gate" not in without,
                top_k=spec["top_k"], scaling=spec["scaling"], sparse=l >= kd,
            )
            if l >= kd:
                ids[i].append(np.asarray(e))
                scores[i].append(np.asarray(s))
        del lp
    final_norm = cast("final_norm", params["final_norm"])
    lm_head = cast("lm_head", params["lm_head"])
    return [{
        "logits": np.asarray(head(xs[i][logits_from[i]:], final_norm, lm_head,
                                  eps=spec["rms_eps"])),
        "expert_ids": np.stack(ids[i], axis=1),
        "router_scores": np.stack(scores[i], axis=1),
    } for i in range(n)]


def forward(params: dict, cfg, tokens, *, forced=None, logits_from: int = 0,
            without=()) -> dict:
    """The full forward pass over ``tokens`` [S] (one sequence, positions
    0..S-1). Returns ``logits`` [S - logits_from, V] float32, ``expert_ids``
    [S, L_sparse, k] and ``router_scores`` (``s + b``) [S, L_sparse, E]
    (numpy). ``forced`` [S, L_sparse, k]: expert choices to use."""
    return forward_many(
        params, cfg, [tokens], forced=None if forced is None else [forced],
        logits_from=[logits_from], without=without)[0]
