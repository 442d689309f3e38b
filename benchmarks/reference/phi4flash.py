"""Plain reference of the Phi-4-mini-flash-reasoning (``phi4flash``) forward
pass: ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``,
the selective scan a plain ``lax.scan`` over positions, attention with an
explicit mask, no cache, no state slot, no batching, no kernel, EVERY layer
at EVERY position, one layer at a time (so that the published depth
fits). The one copy: the CPU tests and
``benchmarks/tools/ref_compare_phi4flash.py`` import it; it imports nothing
of the program (it shares the weights' draw and nothing else).

The block (ISSUE 41: the catalog's ``config`` and ``described_as``, the
operators' internals from the family's public modelling code and paper,
the decoder-hybrid-decoder "SambaY"): ``h += Op_l(LN(h))``, ``h +=
MLP(LN'(h))``, LayerNorm with weight and bias, eps ``layer_norm_eps``; a
last LayerNorm, then logits ``h E^T`` with the TIED embedding, no head
bias. ``MLP = (silu(u W_gate) * (u W_up)) W_down``.

- ``mamba`` (layers 0, 2, .., 16): ``[x, z] = u [W_x | W_z]`` (the input
  projection's halves, stored apart); ``x =
  silu(conv(x) + b_c)`` (depthwise, causal, ``d_conv`` taps, tap 0 the
  oldest, zeros before position 0); ``[delta, B, C] = split(x W_x)``
  (``dt_rank``, ``d_state``, ``d_state``); ``Delta = softplus(delta W_dt +
  b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(Delta_t A) * h_{t-1} + (Delta_t
  x_t) B_t`` per channel and state entry; ``y_t = h_t . C_t + D x_t``;
  ``Op = (y * silu(z)) W_out``. The last mamba layer's ``y`` (before the
  gate, with the D term) is the MEMORY ``M``.
- ``sliding_attention`` (window ``sliding_window`` keys, the query's own
  among them) and ``full_attention`` (all keys): differential attention.
  ``Q, K, V = u W + b`` as heads of 64; with ``w`` a wide head (two kv
  heads), ``half`` in {0, 1}, ``g`` < heads / kv heads: query head ``(2w +
  half) * 2 + g`` (plain GQA order) scores against kv head ``2w + half``
  with ``1 / sqrt(64)`` and takes values ``V_{2w} | V_{2w+1}`` (128 wide);
  ``a1`` is the map of half 0, ``a2`` of half 1; ``lambda = exp(lq1 . lk1)
  - exp(lq2 . lk2) + lambda_0``, ``lambda_0 = 0.8 - 0.6 exp(-0.3 l)``;
  ``o = RMSNorm_128(a1 - lambda a2; subln) * (1 - lambda_0)``; ``Op = o
  W_o + b_o`` over the 20 pairs' outputs in order ``(w, g)``.
- ``cross_attention`` (19, 21, ..): the same with ``Q`` only — its own
  ``W_q``, lambdas, sub-norm and ``W_o`` — and K, V those of the full
  layer, all keys.
- ``gmu`` (18, 20, ..): ``Op = (M * silu(u W_g)) W_out`` at the same
  position.

Departures from the published description (the configuration file's
``assumed`` list): the Mamba sizes (``d_inner`` 2 x hidden, ``d_state``
16, ``d_conv`` 4, ``dt_rank`` hidden / 16), the convolution's bias, which
heads pair (above; immaterial under random weights), no rotary and no
embedding scale, the memory before the gate with the D term.

**Weights.** The program's own pytree (``models/llama.py::init_params``):
``embed`` [V, H], ``final_norm`` / ``final_norm_b``; ``layers`` (every
layer: ``attn_norm`` / ``mlp_norm`` and ``_b``, ``w_gate`` / ``w_up`` /
``w_down``); ``mamba``, ``attn`` (sliding layers then the full one),
``gmu``, ``cross``: one entry a layer of the kind, in layer order.
``weight_map=`` replaces each matrix after its cast (the lower precision a
tolerance has to refuse). Controls that must fail the same tolerance:
``forget_state_at=p`` zeroes every mamba layer's state and convolution
inputs before position ``p`` (a restored state that is zeros);
``zero_tail_before=p`` gives queries at or past ``p`` zeros for the window
layers' K and V before ``p`` (a restored window tail that is zeros);
``lambda_zero`` forces ``lambda = 0``; ``window=`` another window.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512  # queries per block of the attention product (memory only)
F32 = jnp.float32


def spec_of(cfg) -> dict:
    """The numbers the forward pass needs, from the program's
    ``ModelConfig`` (read by attribute) or from a dict of the source's
    keys plus the configuration file's ``assumed_sizes``."""
    if isinstance(cfg, dict):
        n, sizes = cfg["num_hidden_layers"], cfg["assumed_sizes"]
        types = tuple(cfg["layer_types"])
        return {
            "layer_types": types, "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "window": cfg["sliding_window"], "eps": cfg["layer_norm_eps"],
            "d_state": sizes["d_state"], "dt_rank": sizes["dt_rank"],
            "n_layers": n,
        }
    return {
        "layer_types": tuple(cfg.layer_types), "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "window": cfg.sliding_window, "eps": cfg.rms_eps,
        "d_state": cfg.ssm_state, "dt_rank": cfg.ssm_dt_rank,
        "n_layers": cfg.n_layers,
    }


def _layer(stack: dict, i: int, weight_map=None) -> dict:
    out = {}
    for name, w in stack.items():
        w = jnp.asarray(w[i], F32)
        if weight_map is not None and w.ndim == 2 and name != "conv_w" \
                and not name.startswith("A_"):
            w = weight_map(w)
        out[name] = w
    return out


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w + b


def mlp(lp, u):
    return (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_up"])) @ lp["w_down"]


@partial(jax.jit, static_argnums=(2, 3, 4))
def mamba(mp, u, dt_rank: int, d_state: int, forget_at=None):
    """``u`` [S, H] -> ``(Op [S, H], y [S, d_inner])``."""
    S = u.shape[0]
    R, N = dt_rank, d_state
    x, z = u @ mp["in_x"], u @ mp["in_z"]
    taps = mp["conv_w"].shape[0]
    pos = jnp.arange(S)
    if forget_at is not None:  # the inputs before forget_at read as zeros
        keep = (pos >= forget_at)[:, None]
    xc = jnp.zeros_like(x)
    for k in range(taps):
        back = taps - 1 - k  # tap k reads the input `back` positions behind
        shifted = jnp.pad(x, ((back, 0), (0, 0)))[:S]
        if forget_at is not None:
            # a query at or past forget_at sees zeros where the source
            # position lies before it
            src_kept = jnp.pad(keep, ((back, 0), (0, 0)))[:S]
            shifted = jnp.where(keep & ~src_kept, 0.0, shifted)
        xc = xc + mp["conv_w"][k] * shifted
    xc = jax.nn.silu(xc + mp["conv_b"])
    dbc = xc @ mp["x_proj"]
    dt = jax.nn.softplus(dbc[:, :R] @ mp["dt_proj"] + mp["dt_bias"])
    b, c = dbc[:, R:R + N], dbc[:, R + N:]
    a = -jnp.exp(mp["A_log"])  # [N, d_inner]

    def step(h, xs):
        xt, dtt, bt, ct, t = xs
        if forget_at is not None:
            h = jnp.where(t == forget_at, 0.0, h)
        h = jnp.exp(dtt[None, :] * a) * h + (dtt * xt)[None, :] * bt[:, None]
        return h, jnp.sum(h * ct[:, None], axis=0) + mp["D"] * xt

    _, y = jax.lax.scan(step, jnp.zeros_like(a), (xc, dt, b, c, pos))
    return (y * jax.nn.silu(z)) @ mp["out_proj"], y


@partial(jax.jit, static_argnums=(5, 6, 7, 8))
def diff_attention(ap, q, k, v, layer, eps: float, window: int = 0,
                   zero_tail_before=None, lambda_zero: bool = False):
    """``q`` [S, Hq, D], ``k`` / ``v`` [S, Hkv, D] -> [S, Hq * D]: the
    pairs' outputs, combined and normalised, as ``W_o`` takes them. Every
    head at once, a block of queries at a time (memory only). ``layer``
    (the block's index, for ``lambda_0``) is a value, not a shape: one
    compiled program serves every layer of a kind."""
    S, Hq, D = q.shape
    Hkv = k.shape[1]
    wide, g = Hkv // 2, Hq // Hkv
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))
    lam = 0.0 if lambda_zero else (
        jnp.exp(jnp.sum(ap["lambda_q1"] * ap["lambda_k1"]))
        - jnp.exp(jnp.sum(ap["lambda_q2"] * ap["lambda_k2"])) + lam0)
    qs = q.reshape(S, wide, 2, g, D)  # query head (2w + half) * g + gi
    ks = k.reshape(S, wide, 2, D)  # kv head 2w + half
    vv = v.reshape(S, wide, 2 * D)  # v_2w | v_2w+1
    kpos = jnp.arange(S)[None, :]
    rows = []
    for s0 in range(0, S, Q_BLOCK):
        qpos = jnp.arange(s0, min(S, s0 + Q_BLOCK))[:, None]
        logits = jnp.einsum("qwhgd,kwhd->whgqk", qs[s0:s0 + Q_BLOCK], ks
                            ) / math.sqrt(D)
        seen = kpos <= qpos
        if window:
            seen &= qpos - kpos < window
        if zero_tail_before is not None:  # keys and values that read as zeros
            gone = (qpos >= zero_tail_before) & (kpos < zero_tail_before)
            logits = jnp.where(gone, 0.0, logits)
        p = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
        if zero_tail_before is not None:
            p = jnp.where(gone, 0.0, p)
        maps = jnp.einsum("whgqk,kwe->hqwge", p, vv)  # [half, q, w, g, 2D]
        o = maps[0] - lam * maps[1]
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        rows.append((o * ap["subln"] * (1.0 - lam0)).reshape(o.shape[0], -1))
    return jnp.concatenate(rows)


def forward(params: dict, cfg, tokens, at=None, weight_map=None,
            forget_state_at=None, zero_tail_before=None,
            lambda_zero: bool = False, window: int | None = None,
            upto_layer: int | None = None):
    """Logits [len(at), V] float32 of ``tokens`` [S] at positions ``at``
    (default: every position). ``upto_layer``: the hidden state after that
    many layers instead (tests)."""
    spec = spec_of(cfg)
    types = spec["layer_types"]
    Hq, Hkv, D = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    win = spec["window"] if window is None else window
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    def _attend(ap, q, k, v, layer, window, zero_before):
        return diff_attention(ap, q, k, v, layer, spec["eps"], window,
                              zero_before, lambda_zero)

    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], F32)
        h = embed[tokens]
        seen = {"mamba": 0, "attn": 0, "gmu": 0, "cross": 0}
        memory = full_k = full_v = None
        for l, kind in enumerate(types):
            if upto_layer is not None and l >= upto_layer:
                return h
            lp = _layer(params["layers"], l, weight_map)
            u = layer_norm(h, lp["attn_norm"], lp["attn_norm_b"], spec["eps"])
            if kind == "mamba":
                mp = _layer(params["mamba"], seen["mamba"], weight_map)
                seen["mamba"] += 1
                op, memory = mamba(mp, u, spec["dt_rank"], spec["d_state"],
                                   forget_state_at)
            elif kind in ("sliding_attention", "full_attention"):
                ap = _layer(params["attn"], seen["attn"], weight_map)
                seen["attn"] += 1
                q = (u @ ap["wq"] + ap["bq"]).reshape(S, Hq, D)
                k = (u @ ap["wk"] + ap["bk"]).reshape(S, Hkv, D)
                v = (u @ ap["wv"] + ap["bv"]).reshape(S, Hkv, D)
                sliding = kind == "sliding_attention"
                if not sliding:
                    full_k, full_v = k, v
                o = _attend(ap, q, k, v, l, win if sliding else 0,
                            zero_tail_before if sliding else None)
                op = o @ ap["wo"] + ap["bo"]
            elif kind == "cross_attention":
                cp = _layer(params["cross"], seen["cross"], weight_map)
                seen["cross"] += 1
                q = (u @ cp["wq"] + cp["bq"]).reshape(S, Hq, D)
                o = _attend(cp, q, full_k, full_v, l, 0, None)
                op = o @ cp["wo"] + cp["bo"]
            else:  # gmu
                gp = _layer(params["gmu"], seen["gmu"], weight_map)
                seen["gmu"] += 1
                op = (memory * jax.nn.silu(u @ gp["w_g"])) @ gp["w_out"]
            h = h + op
            h = h + mlp(lp, layer_norm(h, lp["mlp_norm"], lp["mlp_norm_b"],
                                       spec["eps"]))
        if at is not None:
            h = h[jnp.asarray(at, jnp.int32)]
        h = layer_norm(h, jnp.asarray(params["final_norm"], F32),
                       jnp.asarray(params["final_norm_b"], F32), spec["eps"])
        return h @ embed.T


def int8_weights(w):
    """Per-output-channel symmetric int8 and back (W8A16's precision: the
    one below the configuration's bf16)."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale) * scale


def distance(a, b) -> tuple[float, float]:
    """``(max|a-b| / max|b|, rms(a-b) / rms(b))`` of two logits arrays."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return (float(np.abs(a - b).max() / np.abs(b).max()),
            float(np.sqrt(np.mean((a - b) ** 2)) / np.sqrt(np.mean(b ** 2))))
