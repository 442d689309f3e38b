#!/usr/bin/env python3
"""Which path should serve the CACHED part of a latent-attention prefill:
absorb the query and attend the latent rows where they lie in pages
(``radixmesh_mla_chunk``: 20 n 1088 MACs a cached token for n new tokens),
or derive K and V from the rows and attend those (20 n 512 + 512 x 8960)?
On paper they cross near n = 400. This times both at published widths on
the chip, one layer, one request, for ``--new`` tokens over ``--prefix``
cached ones:

    chiprun -- python3 benchmarks/tools/mla_prefill_paths.py \
        --prefix 16384 --new 64,128,256,512

The derived path is plain XLA (gather the pages, one product with
``W_kvb``, a dense attention over the derived K/V): a kernel for it could
be faster than this, never faster than its own products. Prints one JSON
line a size; milliseconds are the median of ``--reps`` timed calls after a
warm-up call (device time as the host sees it, ``block_until_ready``).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--prefix", type=int, default=16384)
    ap.add_argument("--new", default="64,128,256,512")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--model", default="glm-4.7-flash-l8")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from radixmesh_tpu.models import get_config
    from radixmesh_tpu.ops.attention import attend_prefill
    from radixmesh_tpu.ops.mla_attention import mla_chunk_kernel

    cfg = get_config(args.model)
    heads, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    width, page = cfg.kv_row[2], 16
    n_pages = args.prefix // page + 64
    key = jax.random.PRNGKey(0)
    pool = jax.random.normal(key, (1, 1, 1, n_pages, page, width), cfg.dtype)
    w_kvb = jax.random.normal(key, (r, heads, dn + dv), cfg.dtype) / 23.0
    scale = cfg.head_dim ** -0.5

    @jax.jit
    def absorbed(q, cur, pool, table, prior, kvlen):
        return mla_chunk_kernel(q, cur, pool, table, prior, kvlen, 0,
                                v_width=r, scale=scale)

    @jax.jit
    def derived(q_nope, q_rope, pool, table, positions, kvlen):
        rows = pool[0, 0, 0][table[0]].reshape(-1, width)[None]  # [1, P, W]
        kv = jnp.einsum("bpr,rhd->bphd", rows[..., :r], w_kvb)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            rows[:, :, None, r:r + dr], (*kv.shape[:3], dr))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        return attend_prefill(q, k, kv[..., dn:], positions, kvlen)

    table = jnp.arange(args.prefix // page + 32, dtype=jnp.int32)[None]
    for n in (int(x) for x in args.new.split(",")):
        q = jax.random.normal(key, (1, n, heads, width), cfg.dtype)
        cur = jax.random.normal(key, (1, n, width), cfg.dtype)
        prior = jnp.full((1,), args.prefix, jnp.int32)
        pos = args.prefix + jnp.arange(n, dtype=jnp.int32)[None]
        # the derived path sees the prefix alone (its pages), as the
        # absorbed kernel's page part does; both leave the n x n block out
        # of the comparison's difference (it is the same work in either)
        runs = {
            "absorbed_ms": lambda: absorbed(q, cur, pool, table, prior, prior + n),
            "derived_ms": lambda: derived(
                q[..., :dn], q[..., dn:dn + dr], pool,
                table[:, :args.prefix // page], pos, prior),
        }
        line = {"prefix": args.prefix, "new": n, "device": jax.devices()[0].device_kind}
        for name, fn in runs.items():
            fn().block_until_ready()
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                fn().block_until_ready()
                times.append(1e3 * (time.perf_counter() - t0))
            line[name] = statistics.median(times)
        line["macs_absorbed"] = heads * n * (r + dr + r) * args.prefix
        line["macs_derived"] = (heads * n * (dn + dr + dv) + r * heads * (dn + dv)) * args.prefix
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
