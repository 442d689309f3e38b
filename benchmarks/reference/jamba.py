"""Plain reference of the AI21-Jamba2-3B (``model_type: "jamba"``) forward
pass: ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``,
the selective scan a plain ``lax.scan`` over positions, attention with an
explicit causal mask in blocks of queries (so that 98 304 positions fit), no
cache, no state slot, no batching, no kernel, EVERY layer at EVERY position,
one layer at a time. The one copy: the CPU tests and
``benchmarks/tools/ref_compare_jamba.py`` import it; it imports nothing of
the program (it shares the weights' draw and nothing else).

The block (ISSUE 55: the catalog's ``config``; the operators' internals from
the family's public modelling code): ``h += Mixer_l(RMSNorm(h))``, ``h +=
MLP(RMSNorm'(h))``, RMSNorm with a weight, eps ``rms_norm_eps``; a last
RMSNorm, then logits ``h E^T`` with the TIED embedding. No bias but the
convolution's and ``dt_proj``'s, no positional encoding of any kind, no
embedding or logit scale. ``MLP = (silu(u W_gate) * (u W_up)) W_down``
(``num_experts`` 1: every FFN is the dense one). ``Mixer_l`` is attention
iff ``l % attn_layer_period == attn_layer_offset``, else Mamba.

- ``mamba``: ``[x, z] = u [W_x | W_z]`` (the input projection's halves,
  stored apart); ``x = silu(conv(x) + b_c)`` (depth-wise, causal, ``d_conv``
  taps, tap 0 the oldest, zeros before position 0); ``[delta, B, C] =
  split(x W_x)`` (``dt_rank``, ``d_state``, ``d_state``); **``delta =
  RMSNorm(delta; w_dt)``, ``B = RMSNorm(B; w_b)``, ``C = RMSNorm(C; w_c)``**
  (the family's ``dt_layernorm`` / ``b_layernorm`` / ``c_layernorm``);
  ``Delta = softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t =
  exp(Delta_t A) * h_{t-1} + (Delta_t x_t) B_t`` per channel and state entry;
  ``y_t = h_t . C_t + D x_t``; ``Op = (y * silu(z)) W_out``.
- ``full_attention``: ``Q, K, V = u W`` as heads of ``head_dim``; query head
  ``j`` scores against kv head ``j // (heads / kv heads)`` with ``1 /
  sqrt(head_dim)``, causal softmax, no rotary; ``Op = o W_o``.

Departures from the published description (the configuration file's
``assumed`` list): which layers are attention (period and offset as the
family's code reads them), ``head_dim`` = hidden / heads, the inner norms
(not a key of the config).

**Weights.** The program's own pytree (``models/llama.py::init_params``):
``embed`` [V, H], ``final_norm``; ``layers`` (every layer: ``attn_norm`` /
``mlp_norm``, ``w_gate`` / ``w_up`` / ``w_down``); ``mamba`` and ``attn``:
one entry a layer of the kind, in layer order. ``weight_map=`` replaces each
matrix after its cast (the lower precision a tolerance has to refuse).
Controls that must fail the same tolerance: ``state_dtype=bfloat16`` holds
the recurrent state in bf16 between positions (the precision below the
configuration's float32 state); ``forget_state_at=p`` zeroes every mamba
layer's state and convolution inputs before position ``p`` (a restored state
that is zeros); ``no_inner_norms`` leaves the three norms out.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

P_BLOCK = 8192  # positions a layer walks at a time (memory only)
Q_BLOCK = 128  # queries per block of the attention product (memory only:
# 20 heads x 128 queries x 98 304 keys of float32 logits are 1 GB)
F32 = jnp.float32


def spec_of(cfg) -> dict:
    """The numbers the forward pass needs, from the program's
    ``ModelConfig`` (read by attribute) or from a dict of the source's
    keys (``head_dim`` = hidden / heads, as the file's ``assumed_sizes``)."""
    if isinstance(cfg, dict):
        period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
        return {
            "layer_types": tuple(
                "full_attention" if l % period == offset else "mamba"
                for l in range(cfg["num_hidden_layers"])),
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "eps": cfg["rms_norm_eps"], "d_state": cfg["mamba_d_state"],
            "dt_rank": cfg["mamba_dt_rank"],
        }
    return {
        "layer_types": tuple(cfg.layer_types), "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "eps": cfg.rms_eps, "d_state": cfg.ssm_state, "dt_rank": cfg.ssm_dt_rank,
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


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mlp(lp, u):
    return (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_up"])) @ lp["w_down"]


@partial(jax.jit, static_argnums=(4, 5, 6, 8, 9))
def mamba_block(mp, u, carry, pos0, dt_rank: int, d_state: int, eps: float,
                forget_at=None, state_dtype=None, no_inner_norms: bool = False):
    """``u`` [S, H], the block's positions ``pos0 ..``, ``carry`` = ``(h [N,
    d_inner], the d_conv - 1 convolution inputs before pos0)`` -> ``(Op [S,
    H], carry after the block)``."""
    S = u.shape[0]
    R, N = dt_rank, d_state
    h0, x_before = carry
    x, z = u @ mp["in_x"], u @ mp["in_z"]
    taps = mp["conv_w"].shape[0]
    pos = pos0 + jnp.arange(S)
    ext = jnp.concatenate([x_before, x])  # positions pos0 - (taps - 1) ..
    xc = jnp.zeros_like(x)
    for k in range(taps):
        shifted = ext[k:k + S]  # tap k reads taps - 1 - k positions behind
        if forget_at is not None:
            # a position at or past forget_at reads zeros for what lies before it
            src = pos - (taps - 1 - k)
            shifted = jnp.where(((pos >= forget_at) & (src < forget_at))[:, None],
                                0.0, shifted)
        xc = xc + mp["conv_w"][k] * shifted
    xc = jax.nn.silu(xc + mp["conv_b"])
    dbc = xc @ mp["x_proj"]
    delta, b, c = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    if not no_inner_norms:
        delta = rms_norm(delta, mp["dt_norm"], eps)
        b = rms_norm(b, mp["b_norm"], eps)
        c = rms_norm(c, mp["c_norm"], eps)
    dt = jax.nn.softplus(delta @ mp["dt_proj"] + mp["dt_bias"])
    a = -jnp.exp(mp["A_log"])  # [N, d_inner]

    def step(h, xs):
        xt, dtt, bt, ct, t = xs
        if forget_at is not None:
            h = jnp.where(t == forget_at, 0.0, h)
        h = jnp.exp(dtt[None, :] * a) * h + (dtt * xt)[None, :] * bt[:, None]
        if state_dtype is not None:  # the state as a lower precision keeps it
            # (reduce_precision: a cast there and back is dropped by the TPU
            # compiler, which may keep excess precision — it read 0.0 exactly)
            info = jnp.finfo(state_dtype)
            h = jax.lax.reduce_precision(h, info.nexp, info.nmant)
        return h, jnp.sum(h * ct[:, None], axis=0) + mp["D"] * xt

    h, y = jax.lax.scan(step, h0, (xc, dt, b, c, pos))
    return (y * jax.nn.silu(z)) @ mp["out_proj"], (h, ext[S:])


@jax.jit
def attend_block(qb, k, v, q0):
    """A block of queries ``qb`` [Q, Hkv, G, D] at positions ``q0 ..`` over
    all keys ``k`` / ``v`` [S, Hkv, D] -> [Q, Hkv * G * D]: causal softmax
    at ``1 / sqrt(D)``, every head at once."""
    D = qb.shape[-1]
    qpos = q0 + jnp.arange(qb.shape[0])[:, None]
    kpos = jnp.arange(k.shape[0])[None, :]
    logits = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(D)
    p = jax.nn.softmax(jnp.where(kpos <= qpos, logits, -jnp.inf), axis=-1)
    return jnp.einsum("hgqk,khd->qhgd", p, v).reshape(qb.shape[0], -1)


def forward(params: dict, cfg, tokens, at=None, weight_map=None,
            forget_state_at=None, state_dtype=None, no_inner_norms: bool = False,
            upto_layer: int | None = None):
    """Logits [len(at), V] float32 of ``tokens`` [S] at positions ``at``
    (default: every position). The sequence is walked in blocks of
    ``P_BLOCK`` positions inside each layer (memory only: a Mamba layer
    carries its state and convolution inputs from block to block, an
    attention layer projects every block's K and V first). ``upto_layer``:
    the hidden state after that many layers instead (tests)."""
    spec = spec_of(cfg)
    types = spec["layer_types"]
    Hq, Hkv, D = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    eps = spec["eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    starts = list(range(0, S, P_BLOCK))
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(params["embed"], F32)
        hs = [embed[tokens[s0:s0 + P_BLOCK]] for s0 in starts]
        seen = {"mamba": 0, "attn": 0}
        for l, kind in enumerate(types):
            if upto_layer is not None and l >= upto_layer:
                break
            lp = _layer(params["layers"], l, weight_map)
            if kind == "mamba":
                mp = _layer(params["mamba"], seen["mamba"], weight_map)
                seen["mamba"] += 1
                taps, di = mp["conv_w"].shape
                carry = (jnp.zeros((spec["d_state"], di), F32),
                         jnp.zeros((taps - 1, di), F32))
                for i, s0 in enumerate(starts):
                    op, carry = mamba_block(
                        mp, rms_norm(hs[i], lp["attn_norm"], eps), carry, s0,
                        spec["dt_rank"], spec["d_state"], eps, forget_state_at,
                        state_dtype, no_inner_norms)
                    hs[i] = hs[i] + op
            else:
                ap = _layer(params["attn"], seen["attn"], weight_map)
                seen["attn"] += 1
                us = [rms_norm(h, lp["attn_norm"], eps) for h in hs]
                k = jnp.concatenate([u @ ap["wk"] for u in us]).reshape(S, Hkv, D)
                v = jnp.concatenate([u @ ap["wv"] for u in us]).reshape(S, Hkv, D)
                for i, s0 in enumerate(starts):
                    q = (us[i] @ ap["wq"]).reshape(-1, Hkv, Hq // Hkv, D)
                    o = jnp.concatenate([
                        attend_block(q[j:j + Q_BLOCK], k, v, s0 + j)
                        for j in range(0, q.shape[0], Q_BLOCK)])
                    hs[i] = hs[i] + o @ ap["wo"]
                del us, k, v
            for i in range(len(hs)):
                hs[i] = hs[i] + mlp(lp, rms_norm(hs[i], lp["mlp_norm"], eps))
        h = jnp.concatenate(hs)
        if upto_layer is not None:
            return h
        if at is not None:
            h = h[jnp.asarray(at, jnp.int32)]
        h = rms_norm(h, jnp.asarray(params["final_norm"], F32), eps)
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
