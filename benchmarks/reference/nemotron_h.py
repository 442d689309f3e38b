"""Plain reference of the NVIDIA-Nemotron-3-Nano (``nemotron_h``) forward
pass: ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``,
no kernel, no cache, no state slots, no request batching; the Mamba-2
blocks as the TOKEN-BY-TOKEN recurrence, the convolution as a sum of four
taps, the experts a loop over the ones held with the routing weight. The
one copy: the CPU tests and ``benchmarks/tools/ref_compare_ssd.py`` import
it; it imports nothing of the program.

The model (ISSUE 53, from the public ``config.json``): ``layer_types`` names
BLOCKS, each ``x <- x + f(RMSNorm(x))`` with ONE ``f`` (eps 1e-5, no bias
but the convolution's, an untied head):

- **"mamba2"** (``heads`` heads of ``P`` channels, ``G`` groups, state
  ``N``, ``taps`` taps; ``di = heads * P``): ``[z | xBC | dt] = h W_in``
  (widths ``di | di + 2 G N | heads``); ``xBC <- SiLU(conv(xBC) + b)``,
  depth-wise, causal, zeros before position 0; ``[u | B | C] = xBC`` with
  ``u`` [heads, P], ``B``, ``C`` [G, N], head ``j`` reading group ``j //
  (heads / G)``; ``Delta = softplus(dt + dt_bias)`` a head (no clamp);
  ``a = exp(-exp(A_log) Delta)``. State ``S_j`` [P, N] float32, zeros at
  position 0: ``S_j <- a_j S_j + Delta_j u_j B_g^T``; ``y_j = S_j C_g + D_j
  u_j``. ``y <- y * SiLU(z)`` (the gate FIRST), then an RMS norm inside
  each of the ``G`` groups of ``di / G`` channels with one learned weight
  [di]; ``f = y W_out``.
- **"full_attention"**: ``q = h W_q`` -> [Hq, D]; ``k, v = h W_k, h W_v`` ->
  [Hkv, D]; NO positional encoding; ``softmax(q k^T / sqrt(D) + causal)
  v``, query head ``j`` on KV head ``j // (Hq / Hkv)``; ``f = concat W_o``.
- **"moe"**: ``s = sigmoid(h W_r)`` over all ``E`` experts; the ``top_k``
  largest ``s + b`` (no groups); ``w = scaling * s_sel / (sum s_sel +
  1e-20)``; ``f = sum_e w_e W_down,e relu(W_up,e h)^2 + W_down,s
  relu(W_up,s h)^2`` over the experts the weights HOLD (an expert share:
  experts ``first_held .. first_held + held``; what the absent ones would
  add is left out, as the program leaves it out).
- Head: ``RMSNorm``, ``lm_head``.

Assumed, because the config cannot settle them (each with its reason in
``benchmarks/configs/nemotron-3-nano-30b-a3b-bf16-ep8.json``): the Mamba-2
inner width is ``heads * P`` (``expand`` is unread); attention has no
rotary; the gated norm's order and groups above; ``dt_bias``, ``A_log``,
``D`` and the selection bias are whatever the pytree holds (seeded draws);
float32 state.

**Weights.** The program's own pytree (``models/llama.py::init_params``):
``embed`` [V, H], ``lm_head`` [H, V], ``final_norm`` [H]; every block's one
pre-norm in ``params["layers"]["attn_norm"]`` [blocks, H]; a block's
operator at its place in the stack of its kind: ``params["mamba2"]``
(``in_proj`` [H, 2 di + 2 G N + heads], ``conv_w`` [taps, di + 2 G N] with
tap 0 the oldest position, ``conv_b``, ``dt_bias`` / ``A_log`` / ``D``
[heads], ``ssd_norm`` [di], ``out_proj`` [di, H]), ``params["attn"]``
(``wq``, ``wk``, ``wv``, ``wo``), ``params["moe"]`` (``w_router`` [H, E],
``router_bias`` [E], ``w_up`` [held, H, F], ``w_down`` [held, F, H],
``ws_up``, ``ws_down``). Leaves are cast to float32 a block at a time.

``forward_many(..., forced=ids)`` uses a given table of expert choices [S,
moe blocks, k] instead of its own; ``weight_map=`` replaces each weight
after its cast and ``state_dtype=`` rounds the state after every position
(the lower precisions a tolerance has to refuse); ``without=`` names parts
to leave out, each of which has to MATTER: ``"decay"`` (a = 1), ``"conv"``
(xBC passes unconvolved), ``"conv_bias"``, ``"skip"`` (no ``D u``),
``"gate"`` (no ``SiLU(z)``), ``"group_norm"`` (one RMS norm over all
``di``), ``"groups"`` (every head reads group 0), ``"relu2"`` (SiLU in the
experts), ``"shared"`` (no shared expert), ``"scaling"`` (1), ``"bias"``
(choose by ``s`` alone); ``forget_at=`` zeroes every Mamba-2 state and
convolution input at that position (a request that resumed there from a
zeroed slot).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512  # queries per block of the attention product (memory only)
_KIND = {"mamba2": "mamba2", "moe": "moe", "full_attention": "attn"}


def spec_of(cfg) -> dict:
    """The numbers the forward pass needs, from the program's
    ``ModelConfig`` (read by attribute) or from the configuration file's
    dict of the source's keys (``benchmarks/configs/nemotron-3-nano-*.json``:
    ``layer_types`` is the file's own list of the blocks held)."""
    if isinstance(cfg, dict):
        serving = cfg.get("serving", {})
        return {
            "layer_types": tuple(cfg["layer_types"]),
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "eps": cfg["layer_norm_epsilon"],
            "top_k": cfg["num_experts_per_tok"],
            "scaling": float(cfg["routed_scaling_factor"]),
            "first_held": serving.get("ep_rank", 0) * cfg["n_routed_experts"],
            "heads": cfg["mamba_num_heads"],
            "groups": cfg["n_groups"],
            "taps": cfg["conv_kernel"],
        }
    return {
        "layer_types": tuple(cfg.layer_types),
        "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads,
        "eps": cfg.rms_eps,
        "top_k": cfg.n_experts_per_tok,
        "scaling": float(cfg.routed_scaling_factor),
        "first_held": cfg.ep_rank * (cfg.n_experts // cfg.ep_size),
        "heads": cfg.ssd_heads,
        "groups": cfg.ssd_groups,
        "taps": cfg.ssd_conv,
    }


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mamba2(h, lp, *, heads, groups, taps, eps, without, state_dtype, forget_at):
    """The Mamba-2 mixer on ``h`` [S, H]: [S, H]."""
    S = h.shape[0]
    di = lp["out_proj"].shape[0]
    P, N = di // heads, (lp["conv_w"].shape[1] - di) // (2 * groups)
    zxd = jnp.dot(h, lp["in_proj"])
    z, xbc, dt = zxd[:, :di], zxd[:, di:-heads], zxd[:, -heads:]
    alive = (jnp.arange(S) >= forget_at)[:, None]  # what a zeroed slot forgets
    if "conv" not in without:
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, xbc.shape[1])), jnp.where(alive, xbc, 0.0)])
        xbc = sum(lp["conv_w"][j] * padded[j:j + S] for j in range(taps))
        if "conv_bias" not in without:
            xbc = xbc + lp["conv_b"]
    xbc = jax.nn.silu(xbc)
    u = xbc[:, :di].reshape(S, heads, P)
    bm, cm = (t.reshape(S, groups, N) for t in jnp.split(xbc[:, di:], 2, axis=1))
    of = (jnp.zeros((heads,), jnp.int32) if "groups" in without
          else jnp.arange(heads) // (heads // groups))
    delta = jax.nn.softplus(dt + lp["dt_bias"])  # [S, heads]
    a = (jnp.ones_like(delta) if "decay" in without
         else jnp.exp(-jnp.exp(lp["A_log"]) * delta))

    def step(state, xs):
        ut, bt, ct, at, dlt, t = xs
        state = jnp.where(t == forget_at, 0.0, state)
        state = (at[:, None, None] * state
                 + (dlt[:, None] * ut)[:, :, None] * bt[of][:, None, :])
        if state_dtype is not None:
            # (not a cast there and back: the TPU compiler may keep the excess
            # precision of such a pair)
            fi = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, fi.nexp, fi.nmant)
        return state, jnp.einsum("hpn,hn->hp", state, ct[of])

    _, y = jax.lax.scan(step, jnp.zeros((heads, P, N)),
                        (u, bm, cm, a, delta, jnp.arange(S)))
    if "skip" not in without:
        y = y + lp["D"][:, None] * u
    y = y.reshape(S, di)
    if "gate" not in without:
        y = y * jax.nn.silu(z)
    parts = 1 if "group_norm" in without else groups
    y = y.reshape(S, parts, di // parts)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return jnp.dot(y.reshape(S, di) * lp["ssd_norm"], lp["out_proj"])


def attention(h, lp, *, n_heads, n_kv_heads):
    """Plain causal GQA on ``h`` [S, H], no positional encoding: [S, H]."""
    S = h.shape[0]
    D = lp["wq"].shape[1] // n_heads
    q = jnp.dot(h, lp["wq"]).reshape(S, n_kv_heads, n_heads // n_kv_heads, D)
    k = jnp.dot(h, lp["wk"]).reshape(S, n_kv_heads, D)
    v = jnp.dot(h, lp["wv"]).reshape(S, n_kv_heads, D)
    outs = []
    for q0 in range(0, S, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        s = jnp.einsum("tkgd,skd->kgts", qb, k) / math.sqrt(D)
        seen = (jnp.arange(S)[None, :] <= (q0 + jnp.arange(qb.shape[0]))[:, None])
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgts,skd->tkgd", p, v).reshape(qb.shape[0], -1))
    return jnp.dot(jnp.concatenate(outs), lp["wo"])


def route(x, lp, forced=None, *, top_k, scaling, without=()):
    """``(expert ids [S, k], weights [S, k], selection scores s + b of every
    expert [S, E])`` of ``x`` [S, H]."""
    s = jax.nn.sigmoid(jnp.dot(x, lp["w_router"]))
    biased = s if "bias" in without else s + lp["router_bias"]
    top_e = jax.lax.top_k(biased, top_k)[1] if forced is None else forced
    w = jnp.take_along_axis(s, top_e, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return top_e.astype(jnp.int32), w * (1.0 if "scaling" in without else scaling), biased


def _expert(x, w_up, w_down, without):
    up = jnp.dot(x, w_up)
    act = jax.nn.silu(up) if "relu2" in without else jnp.square(jax.nn.relu(up))
    return jnp.dot(act, w_down)


def experts(x, lp, top_e, top_w, first_held, without=()):
    """The held experts' part of the block: expert ``first_held + j`` is the
    weights' ``j``-th."""
    held = lp["w_up"].shape[0]
    hit = top_e[..., None] == first_held + jnp.arange(held)  # [S, k, held]
    w_full = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1)  # [S, held]

    def one(acc, xs):
        wu, wd, w_e = xs
        return acc + w_e[:, None] * _expert(x, wu, wd, without), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (lp["w_up"], lp["w_down"], w_full.T))
    return y


_STATIC = ("kind", "n_heads", "n_kv_heads", "eps", "top_k", "scaling",
           "first_held", "heads", "groups", "taps", "without", "state_dtype",
           "forget_at")


@partial(jax.jit, static_argnames=_STATIC)
def block(x, lp, forced=None, *, kind, n_heads, n_kv_heads, eps, top_k, scaling,
          first_held, heads, groups, taps, without=(), state_dtype=None,
          forget_at=0):
    """One block on ``x`` [S, H] float32 (positions 0..S-1). Returns ``(x,
    expert ids [S, k], selection scores of every expert [S, E])`` (None,
    None for a mixer)."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, lp["attn_norm"], eps)
        if kind == "mamba2":
            return x + mamba2(
                h, lp, heads=heads, groups=groups, taps=taps, eps=eps,
                without=without, state_dtype=state_dtype,
                forget_at=forget_at), None, None
        if kind == "attn":
            return x + attention(
                h, lp, n_heads=n_heads, n_kv_heads=n_kv_heads), None, None
        top_e, top_w, scores = route(
            h, lp, forced, top_k=top_k, scaling=scaling, without=without)
        y = experts(h, lp, top_e, top_w, first_held, without)
        if "shared" not in without:
            y = y + _expert(h, lp["ws_up"], lp["ws_down"], without)
        return x + y, top_e, scores


@partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """Logits [rows, V] float32 of the hidden rows ``x``."""
    with jax.default_matmul_precision("highest"):
        return jnp.dot(_rms_norm(x, final_norm, eps), lm_head)


def forward_many(params: dict, cfg, seqs, *, forced=None, logits_from=None,
                 weight_map=None, without=(), state_dtype=None,
                 forget_at=None) -> list[dict]:
    """The forward pass of several sequences, one after the other inside a
    walk over the blocks, so that each block's weights are cast (and, from
    the host, uploaded) once. Each result: ``logits`` [S - logits_from, V]
    float32, ``expert_ids`` [S, moe blocks, k], ``router_scores`` [S, moe
    blocks, E] (the selection scores ``s + b`` of every expert)."""
    spec = spec_of(cfg)
    n = len(seqs)
    seqs = [np.asarray(t, dtype=np.int32) for t in seqs]
    forced = forced or [None] * n
    logits_from = logits_from or [0] * n
    forget_at = forget_at or [0] * n
    fix = weight_map or (lambda name, w: w)
    types = spec["layer_types"]
    static = {k: spec[k] for k in _STATIC if k in spec} | {
        "without": tuple(without), "state_dtype": state_dtype}

    def cast(name, w):
        return fix(name, jnp.asarray(w).astype(jnp.float32))

    xs = [jnp.asarray(params["embed"][t]).astype(jnp.float32) for t in seqs]
    ids = [[] for _ in seqs]
    scores = [[] for _ in seqs]
    for l, kind in enumerate(types):
        place = types[:l].count(kind)
        lp = {"attn_norm": cast("attn_norm", params["layers"]["attn_norm"][l])}
        lp |= {name: cast(name, w[place]) for name, w in params[_KIND[kind]].items()}
        for i in range(n):
            choice = None
            if kind == "moe" and forced[i] is not None:
                choice = jnp.asarray(forced[i][:, place], jnp.int32)
            xs[i], e, s = block(xs[i], lp, choice, kind=_KIND[kind],
                                forget_at=int(forget_at[i]), **static)
            if kind == "moe":
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


def shortfall(scores: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """How far each of ``chosen`` [..., k] is from being chosen by the
    router whose selection scores are ``scores`` [..., E]: 0 for an expert
    among its top k, else what it lacks to the k-th score."""
    kth = np.sort(scores, axis=-1)[..., -chosen.shape[-1]]
    return np.maximum(
        kth[..., None] - np.take_along_axis(scores, chosen, axis=-1), 0.0)


def forward(params: dict, cfg, tokens, *, forced=None, logits_from: int = 0,
            forget_at: int = 0, **controls) -> dict:
    """``forward_many`` of one sequence ``tokens`` [S]."""
    return forward_many(
        params, cfg, [tokens], forced=None if forced is None else [forced],
        logits_from=[logits_from], forget_at=[forget_at], **controls)[0]
