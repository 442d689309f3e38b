"""Pipeline-parallel SERVING: prefill chunks and decode steps over a
``(pp, tp)`` mesh.

VERDICT round-2 weak #4: ``parallel/pipeline.py`` proved GPipe numerics
but nothing in the serving stack could use a ``pp`` axis — the
Qwen2-72B/v5p gate (``BASELINE.md`` last row) realistically needs pp×tp.
This module is that path, shaped so the Engine's scheduler, radix tree,
page tables, and publish logic run UNCHANGED:

- The param pytree keeps its stacked ``[L, ...]`` layer leaves and the KV
  pool keeps its ``[2, L, Hkv, slots, D]`` layout — pp is purely a
  *sharding* of the existing layer axis (``shard_map`` hands each stage
  its contiguous ``L/pp`` block), tp a sharding of the head/ffn axes.
  No reshapes, no second checkpoint format.
- One function serves both phases: a decode step is a prefill chunk with
  ``C = 1`` (same page-table attention, same pool scatter), so the pp
  schedule exists in exactly one place.

Schedule: GPipe microbatches over the BATCH axis (rows are independent in
serving, so microbatching is free): ``n_micro`` row-groups enter stage 0
one tick apart, activations ``ppermute`` stage-to-stage, and each stage's
chunk-KV is collected per tick and scattered into the pool shard AFTER
the tick scan — keeping the pool out of the scan carry (the same
materialization bug ``prefill_chunk_paged`` documents). Weights never
move; activations ``[mb, C, H]`` are the only inter-stage traffic — the
layout that makes pp the memory-fit axis for models tp alone can't hold.

Tensor parallelism inside each stage is manual Megatron inside the same
``shard_map``: column-parallel wq/wk/wv/w_gate/w_up, row-parallel
wo/w_down, exactly two ``psum``s per block over the ``tp`` axis.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from radixmesh_tpu.cache.kv_pool import ALL_LAYERS, write_runs_all
from radixmesh_tpu.models.llama import (
    ModelConfig,
    _embed_lookup,
    _logits,
    _wmm,
)
from radixmesh_tpu.ops.attention import (
    default_use_kernel,
    paged_chunk_attention,
)
from radixmesh_tpu.ops.norm import rms_norm
from radixmesh_tpu.ops.rope import apply_rope, rope_frequencies

__all__ = [
    "make_pp_serving_mesh",
    "pp_layer_specs",
    "pp_pool_spec",
    "shard_params_pp",
    "pp_forward_chunk",
]


def make_pp_serving_mesh(pp: int, tp: int = 1, devices=None) -> Mesh:
    """A ``(pp, tp)`` mesh over the first ``pp*tp`` devices (tp innermost:
    its two psums per block are the bandwidth-hungry traffic and belong on
    the fastest ICI wraparound)."""
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    if pp * tp > len(devices):
        raise ValueError(f"pp={pp} x tp={tp} exceeds {len(devices)} devices")
    arr = np.asarray(devices[: pp * tp]).reshape(pp, tp)
    return Mesh(arr, axis_names=("pp", "tp"))


def pp_layer_specs() -> dict:
    """PartitionSpec per stacked-layer leaf: layer axis over ``pp``, head
    and ffn axes over ``tp`` (Megatron column/row split)."""
    return {
        "attn_norm": P("pp", None),
        "mlp_norm": P("pp", None),
        "wq": P("pp", None, "tp"),
        "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"),
        "wo": P("pp", "tp", None),
        "w_gate": P("pp", None, "tp"),
        "w_up": P("pp", None, "tp"),
        "w_down": P("pp", "tp", None),
        "bq": P("pp", "tp"),
        "bk": P("pp", "tp"),
        "bv": P("pp", "tp"),
        # W8A16 scale leaves (ops/wquant.py): per-out-channel, so they
        # shard like their weight's OUTPUT axis — column-split weights'
        # scales over tp, row-split weights' (wo, w_down) replicated.
        "wq_s": P("pp", "tp"),
        "wk_s": P("pp", "tp"),
        "wv_s": P("pp", "tp"),
        "wo_s": P("pp", None),
        "w_gate_s": P("pp", "tp"),
        "w_up_s": P("pp", "tp"),
        "w_down_s": P("pp", None),
    }


def pp_pool_spec() -> P:
    """KV pool ``[2, L, Hkv, slots, D]``: layers over pp, kv heads over tp
    — each stage holds only its own layers' KV, each tp chip its heads."""
    return P(None, "pp", "tp", None, None)


def shard_params_pp(params: dict, cfg: ModelConfig, mesh: Mesh) -> dict:
    """Place the UNCHANGED param pytree onto a ``(pp, tp)`` mesh."""
    specs = pp_layer_specs()
    out = dict(params)
    out["layers"] = {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params["layers"].items()
    }
    repl = NamedSharding(mesh, P())
    out["embed"] = jax.device_put(params["embed"], repl)
    out["final_norm"] = jax.device_put(params["final_norm"], repl)
    if "embed_s" in params:
        out["embed_s"] = jax.device_put(params["embed_s"], repl)
    if "lm_head" in params:
        out["lm_head"] = jax.device_put(
            params["lm_head"], NamedSharding(mesh, P(None, "tp"))
        )
    if "lm_head_s" in params:
        out["lm_head_s"] = jax.device_put(
            params["lm_head_s"], NamedSharding(mesh, P("tp"))
        )
    return out


def pp_scale_spec() -> P:
    """int8-pool scales ``[2, L, Hkv, slots]``: shard with their data."""
    return P(None, "pp", "tp", None)


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "page_size", "kv_block_pages", "mesh", "n_micro",
        "use_kernel", "interpret",
    ),
    donate_argnames=("kv_pool", "kv_scale"),
)
def pp_forward_chunk(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, C] chunk tokens (C=1 for a decode step)
    positions: jnp.ndarray,  # [B, C] absolute positions
    kv_pool: jnp.ndarray,  # [2, L, Hkv, slots, D] sharded pp_pool_spec()
    slots: jnp.ndarray,  # [B, C] pool slot per token (pad → scratch)
    page_table: jnp.ndarray,  # [B, max_pages]
    kv_lengths: jnp.ndarray,  # [B] valid context incl. this chunk
    *,
    page_size: int = 16,
    kv_block_pages: int = 32,
    mesh: Mesh,
    n_micro: int = 1,
    kv_scale: jnp.ndarray | None = None,  # [2, L, Hkv, slots] int8 pool
    use_kernel: bool | None = None,
    interpret: bool = False,
):
    """Logits + updated pool for one chunk through the layer pipeline.

    ``B`` must divide into ``n_micro`` microbatches. Returns
    ``(logits [B, C, V], kv_pool)`` with logits replicated — plus the
    updated ``kv_scale`` when the pool is int8-quantized (the chunk is
    quantized in-layer and attended dequantized, the same
    see-what-you-store invariant ``prefill_chunk_paged`` keeps).

    Stage bodies dispatch chunk attention by backend exactly like the
    single-chip path (``ops/attention.py::paged_chunk_attention``): the
    Pallas chunk kernel on TPU (heads already local inside the
    shard_map), the jnp hybrid elsewhere (VERDICT round-3 next-step #3).
    """
    pp = mesh.shape["pp"]
    tp = mesh.shape.get("tp", 1)
    if use_kernel is None:
        use_kernel = default_use_kernel(cfg.head_dim)
    L = cfg.n_layers
    if L % pp:
        raise ValueError(f"n_layers={L} not divisible by pp={pp}")
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        raise ValueError("head counts must divide tp")
    B, C = tokens.shape
    if B % n_micro:
        raise ValueError(f"batch {B} not divisible by n_micro={n_micro}")
    mb = B // n_micro
    n_ticks = n_micro + pp - 1
    hq_loc = cfg.n_heads // tp
    hkv_loc = cfg.n_kv_heads // tp
    D = cfg.head_dim
    num_slots = kv_pool.shape[3]
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)

    # Embed outside the shard_map (table replicated); group rows into
    # microbatches. Aux arrays get the same [n_micro, mb, ...] grouping.
    x_all = _embed_lookup(params, tokens).reshape(n_micro, mb, C, cfg.hidden)
    pos_all = positions.reshape(n_micro, mb, C)
    slots_all = slots.reshape(n_micro, mb, C)
    pt_all = page_table.reshape(n_micro, mb, -1)
    kvlen_all = kv_lengths.reshape(n_micro, mb)

    layer_specs = {
        k: v for k, v in pp_layer_specs().items() if k in params["layers"]
    }
    quant = kv_scale is not None
    scale_in_spec = pp_scale_spec() if quant else P()
    scale_arg = kv_scale if quant else jnp.zeros((), jnp.float32)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            layer_specs, pp_pool_spec(), scale_in_spec,
            P(), P(), P(), P(), P(),
        ),
        out_specs=(P(), pp_pool_spec(), scale_in_spec),
        check_vma=False,
    )
    def run(layers, pool, scale, x_all, pos_all, slots_all, pt_all, kvlen_all):
        # Per-device views: layers leaves [L/pp, ...] head-sliced; pool
        # [2, L/pp, Hkv/tp, slots, D]; scale [2, L/pp, Hkv/tp, slots].
        idx = jax.lax.axis_index("pp")
        l_loc = pool.shape[1]
        pages = pool.reshape(
            2, l_loc, hkv_loc, num_slots // page_size, page_size, D
        )
        scale_pages = (
            scale.reshape(
                2, l_loc, hkv_loc, num_slots // page_size, page_size
            )
            if quant
            else None
        )

        def stage(h, pos, pt, kvlen):
            """This stage's L/pp layers over one microbatch's chunk.
            Returns (h, per-layer chunk K/V payloads) — scattered into the
            pool AFTER the tick scan."""
            prior = jnp.minimum(pos[:, 0], kvlen)

            def body(h, xs):
                l_idx, lp = xs
                hn = rms_norm(h, lp["attn_norm"], cfg.rms_eps)
                q = _wmm(lp, "wq", "bsh,hd->bsd", hn)
                k = _wmm(lp, "wk", "bsh,hd->bsd", hn)
                v = _wmm(lp, "wv", "bsh,hd->bsd", hn)
                if cfg.qkv_bias:
                    q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
                q = q.reshape(mb, C, hq_loc, D)
                k = k.reshape(mb, C, hkv_loc, D)
                v = v.reshape(mb, C, hkv_loc, D)
                q = apply_rope(q, pos, inv_freq)
                k = apply_rope(k, pos, inv_freq)
                if quant:
                    # Quantize NOW, attend the dequantized copy — the
                    # shared see-what-you-store step (ops/quant.py).
                    from radixmesh_tpu.ops.quant import quantize_for_store

                    k_int, v_int, k_sc, v_sc, k, v = quantize_for_store(k, v)
                attn = paged_chunk_attention(
                    q, k, v, pages, pt, pos, prior, kvlen, l_idx,
                    kv_block_pages=kv_block_pages,
                    kv_scales=scale_pages,
                    use_kernel=use_kernel,
                    interpret=interpret,
                )
                # Row-split projections: the per-out-channel W8A16
                # scale is constant across tp shards, so applying it to
                # the partial sums before the psum is exact.
                o = _wmm(lp, "wo", "bsk,kh->bsh", attn.reshape(mb, C, -1))
                h = h + jax.lax.psum(o, "tp")
                h2 = rms_norm(h, lp["mlp_norm"], cfg.rms_eps)
                gate = jax.nn.silu(_wmm(lp, "w_gate", "bsh,hi->bsi", h2))
                up = _wmm(lp, "w_up", "bsh,hi->bsi", h2)
                down = _wmm(lp, "w_down", "bsi,ih->bsh", gate * up)
                h = h + jax.lax.psum(down, "tp")
                if quant:
                    return h, (k_int, v_int, k_sc, v_sc)
                return h, (k.astype(pool.dtype), v.astype(pool.dtype))

            return jax.lax.scan(
                body, h, (jnp.arange(l_loc), layers)
            )

        last = pp - 1

        def tick(carry, t):
            buf, outs = carry
            # Stage `idx` processes microbatch m = t - idx this tick (the
            # activation that entered stage 0 at tick m). Out-of-range m
            # is warm-up/drain garbage: computed (lockstep SPMD), masked
            # out of `outs` and out of the KV scatter below.
            m = t - idx
            safe_m = jnp.clip(m, 0, n_micro - 1)
            feed = jax.lax.dynamic_index_in_dim(
                x_all, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
            )
            inp = jnp.where(idx == 0, feed, buf)
            pos = jax.lax.dynamic_index_in_dim(pos_all, safe_m, 0, keepdims=False)
            pt = jax.lax.dynamic_index_in_dim(pt_all, safe_m, 0, keepdims=False)
            kvlen = jax.lax.dynamic_index_in_dim(
                kvlen_all, safe_m, 0, keepdims=False
            )
            y, kv_new = stage(inp, pos, pt, kvlen)
            done = y  # last stage's finished hidden for microbatch m
            cur = jax.lax.dynamic_index_in_dim(outs, safe_m, 0, keepdims=False)
            keep = jnp.logical_and(idx == last, jnp.logical_and(m >= 0, m < n_micro))
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(keep, done, cur), safe_m, 0
            )
            buf = jax.lax.ppermute(
                y, "pp", [(i, i + 1) for i in range(pp - 1)]
            )
            return (buf, outs), kv_new

        buf0 = jnp.zeros((mb, C, cfg.hidden), x_all.dtype)
        outs0 = jnp.zeros((n_micro, mb, C, cfg.hidden), x_all.dtype)
        (_, outs), kv_ticks = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(n_ticks)
        )
        # kv_ticks leaves: [ticks, L/pp, mb, C, Hkv/tp(, D)]. Write each
        # valid tick's microbatch payloads into the local pool shard, by
        # whole pages (cache/kv_pool.py); an invalid (warm-up/drain) tick
        # names slots past the pool, which hit nothing.
        for t in range(n_ticks):
            m = t - idx
            safe_m = jnp.clip(m, 0, n_micro - 1)
            valid = jnp.logical_and(m >= 0, m < n_micro)
            sl = jax.lax.dynamic_index_in_dim(
                slots_all, safe_m, 0, keepdims=False
            )  # [mb, C]
            sl = jnp.where(valid, sl, num_slots)
            # [L/pp, mb, C, Hkv/tp, D] → pool target [2, L/pp, Hkv/tp, mb, C, D]
            writes = [(pool, jnp.stack(
                [kv_ticks[0][t], kv_ticks[1][t]]
            ).transpose(0, 1, 4, 2, 3, 5), ALL_LAYERS)]
            if quant:
                writes.append((scale, jnp.stack(
                    [kv_ticks[2][t], kv_ticks[3][t]]
                ).transpose(0, 1, 4, 2, 3), ALL_LAYERS))
            pool, *rest = write_runs_all(writes, sl, page_size)
            if quant:
                (scale,) = rest
        # Finished activations live on the last stage; psum replicates
        # them over pp (other stages contribute zeros). tp is already
        # uniform (both block psums precede every write into `outs`).
        hidden = jax.lax.psum(
            jnp.where(idx == last, outs.astype(jnp.float32), 0.0), "pp"
        ).astype(x_all.dtype)
        return hidden, pool, scale

    hidden, kv_pool, kv_scale_out = run(
        params["layers"], kv_pool, scale_arg, x_all, pos_all, slots_all,
        pt_all, kvlen_all,
    )
    logits = _logits(params, cfg, hidden.reshape(B, C, cfg.hidden))
    if quant:
        return logits, kv_pool, kv_scale_out
    return logits, kv_pool
