#!/usr/bin/env python3
"""Microbenchmark, on the chip, of the two XLA pieces of learned sparse
attention at the cell's shapes: gathering 65 536 selected rows of 1 280 B
from a [7, 196608, 640] bf16 pool, and selecting 2 048 of 65 537 scores a
row for 32 rows. Variants side by side; wall time of a jitted loop.

    chiprun -- python3 benchmarks/tools/dsa_gather_bench.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    L, S, W, N, K = 7, 196608, 640, 32, 2048
    key = jax.random.PRNGKey(0)
    pool = jax.random.normal(key, (L * S, W), jnp.bfloat16)
    idx = jax.random.randint(jax.random.PRNGKey(1), (N, K), 0, 41000) + 3 * S
    idx_sorted = jnp.sort(idx, axis=-1)
    out = {}

    def timeit(name, fn, *args, n=20):
        f = jax.jit(fn)
        r = f(*args)
        jax.block_until_ready(r)
        t0 = time.monotonic()
        for _ in range(n):
            r = f(*args)
        jax.block_until_ready(r)
        out[name] = (time.monotonic() - t0) / n * 1e3
        print(name, round(out[name], 3), "ms", flush=True)
        return r

    def consume(g):  # keep the gather: reduce it to something small
        return jnp.sum(g.astype(jnp.float32), axis=(-1, -2))

    timeit("gather_take", lambda p, i: consume(jnp.take(p, i, axis=0)), pool, idx)
    timeit("gather_take_sorted", lambda p, i: consume(jnp.take(
        p, i, axis=0, indices_are_sorted=True)), pool, idx_sorted)
    timeit("gather_take_sorted_unhinted", lambda p, i: consume(jnp.take(
        p, i, axis=0)), pool, idx_sorted)

    def pairs(p, i):
        g = jnp.take(p.reshape(L * S // 2, 2, W), i // 2, axis=0)  # [N,K,2,W]
        return consume(jnp.where((i % 2 == 0)[..., None], g[..., 0, :], g[..., 1, :]))

    timeit("gather_pairs", pairs, pool, idx)

    def pages(p, i):
        g = jnp.take(p.reshape(L * S // 16, 16, W), i[:, :128] // 16, axis=0)
        return jnp.sum(g.astype(jnp.float32), axis=(-1, -2, -3))

    timeit("gather_pages_128_per_row(=2048 rows)", pages, pool, idx)
    timeit("gather_only_materialize", lambda p, i: jnp.take(p, i, axis=0), pool, idx)

    scores = jax.random.normal(jax.random.PRNGKey(2), (N, 65537), jnp.float32)
    timeit("top_k_65537", lambda s: jax.lax.top_k(s, K)[1], scores)
    timeit("top_k_49153", lambda s: jax.lax.top_k(s[:, :49153], K)[1], scores)
    timeit("sort_65537_keys_only", lambda s: jnp.sort(s, axis=-1)[:, -K:], scores)
    timeit("approx_max_k_0.99", lambda s: jax.lax.approx_max_k(
        s, K, recall_target=0.99)[1], scores)

    def seg_sort(s):  # 512 segments of 128: small sorts
        seg = s[:, :65536].reshape(N, 512, 128)
        return jax.lax.sort_key_val(-seg, jnp.broadcast_to(
            jnp.arange(128, dtype=jnp.int32), seg.shape), dimension=-1)[1][..., :8]

    timeit("sort_512_segments_of_128", seg_sort, scores)

    def threshold(s):  # the K-th largest value by bisection on the bits
        u = jax.lax.bitcast_convert_type(s, jnp.int32)
        keys = jnp.where(u < 0, jnp.int32(-2**31) - u - 1, u)  # monotone

        def body(i, lohi):
            lo, hi = lohi
            mid = lo + (hi - lo) // 2 + 1
            ok = jnp.sum(keys >= mid[:, None], axis=-1) >= K
            return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

        lo = jnp.full((N,), -2**31, jnp.int32)
        hi = jnp.full((N,), 2**31 - 1, jnp.int32)
        # int32 midpoints overflow: bisect the two halves of the range apart
        return jax.lax.fori_loop(0, 33, body, (lo // 2, hi // 2))[0]

    timeit("threshold_bisect_33_passes", threshold, scores)
    timeit("cumsum_65536_int32", lambda s: jnp.cumsum(
        (s > 1.5).astype(jnp.int32), axis=-1)[:, -1], scores)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/dsa_gather_bench.json", "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
