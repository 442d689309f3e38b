"""Kernel-level micro-benchmark: the Pallas paged-attention entry points
timed in isolation (no model around them), bf16 and int8, pool + fused +
chunk-prefill.

Exists because whole-step numbers hide where kernel time goes: the int8
fused-decode regression (0.57x bf16) was invisible until the pool kernel
measured at parity (0.95x) while the fused kernel didn't — the delta was
the in-kernel scale-row RMW, removed in favor of a wrapper-side scatter.
It answers in ~2 chip-minutes whether a kernel change helped, where
bench.py needs ~15.

Before timing anything it compares EVERY variant with its jnp oracle at
the requested head geometry (``ops/kernel_check.py`` — the default-path
kernels and the ones only a flag selects: int8 KV, per-head grids, the
read-only pool kernel); ``--hq 28 --hkv 4`` is Qwen2-7B's. A variant that
fails to compile or disagrees is recorded with the compiler's message and
the exit code is non-zero.

Round-5 axes (VERDICT r4 next-steps #1-#3): every decode case runs with
BOTH page-table layouts — ``run`` (consecutive page runs, the common
radix-allocator case, takes the coalesced one-descriptor-per-block DMA
path) and ``perm`` (fully permuted, per-page fallback) — and with both
grids (heads-batched default vs per-head), bf16 and int8 (prepared
scales). The chunk-prefill kernel gets its first on-chip timing.

Prints one JSON line naming the device; ``--out FILE`` also writes it.
Timing needs a TPU: without one the script exits non-zero, unless
``--interpret`` asks for the parity check alone (Pallas interpret mode,
numerics only — an interpreter timing is not a kernel time and is never
taken).

Usage: python scripts/kernelbench.py [--batch 64] [--ctx 1024] [--iters 10]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--ctx", type=int, default=1024)
    ap.add_argument("--hq", type=int, default=16)
    ap.add_argument("--hkv", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--chunk-batch", type=int, default=8)
    ap.add_argument("--skip-per-head", action="store_true",
                    help="decode cases: heads-batched grid only")
    ap.add_argument("--interpret", action="store_true",
                    help="parity check only, in Pallas interpret mode "
                    "(any backend; nothing is timed)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from radixmesh_tpu.ops.paged_attention import (
        paged_attention_pool_kernel,
        paged_chunk_attention_kernel,
        paged_decode_fused_kernel,
    )
    from radixmesh_tpu.ops.kernel_check import (
        DEFAULT_CASES,
        FLAG_CASES,
        check_kernels,
    )
    from radixmesh_tpu.ops.quant import quantize_kv

    dev = jax.devices()[0]
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
    }
    if dev.platform != "tpu" and not args.interpret:
        print(f"kernelbench: needs a TPU, found {out['device']} "
              "(--interpret runs the parity check alone)", file=sys.stderr)
        return 2
    out["parity"] = check_kernels(
        DEFAULT_CASES + FLAG_CASES, hq=args.hq, hkv=args.hkv,
        head_dim=args.head_dim, page=args.page, batch=args.chunk_batch,
        chunk=args.chunk, max_pages=max(args.ctx, 2 * args.chunk) // args.page,
        interpret=args.interpret,
    )
    failed = [r["kernel"] for r in out["parity"] if not r["ok"]]
    if args.interpret:
        print(json.dumps(out), flush=True)
        return 1 if failed else 0

    B, Hq, Hkv, D, page = args.batch, args.hq, args.hkv, args.head_dim, args.page
    ctx, L = args.ctx, 1
    if ctx % page:
        ap.error(f"--ctx ({ctx}) must be a multiple of --page ({page})")
    P = B * ctx // page
    rng = np.random.default_rng(0)
    kv = rng.standard_normal((2, L, Hkv, P * page, D)).astype(np.float32)
    q8, s8 = quantize_kv(jnp.asarray(kv), axis=-1)
    kv8 = jnp.asarray(np.asarray(q8).reshape(2, L, Hkv, P, page, D), jnp.int8)
    scales = jnp.asarray(np.asarray(s8).reshape(2, L, Hkv, P, page))
    kv16 = jnp.asarray(kv.reshape(2, L, Hkv, P, page, D), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.bfloat16)
    kn = jnp.asarray(rng.standard_normal((B, Hkv, D)), jnp.bfloat16)
    maxp = ctx // page
    # Two table layouts. ``run``: each row owns one consecutive page run
    # (rows themselves shuffled) — what the page-granular slot allocator
    # produces for a freshly prefilled sequence; every block coalesces to
    # one descriptor. ``perm``: fully permuted — the adversarial radix
    # fragmentation case, per-page fallback path.
    row_order = rng.permutation(B)
    pt_run_np = np.stack(
        [np.arange(r * maxp, (r + 1) * maxp, dtype=np.int32) for r in row_order]
    )
    pt_perm_np = rng.permutation(P).reshape(B, maxp).astype(np.int32)
    tables = {
        "run": (jnp.asarray(pt_run_np), jnp.asarray(
            pt_run_np[:, -1] * page + (page - 1))),
        "perm": (jnp.asarray(pt_perm_np), jnp.asarray(
            pt_perm_np[:, -1] * page + (page - 1))),
    }
    lens = jnp.full((B,), ctx, jnp.int32)

    def bench(fn, n=args.iters):
        r = fn()
        jax.block_until_ready(r)
        del r
        t = time.perf_counter()
        r = None
        for _ in range(n):
            r = fn()
        jax.block_until_ready(r)
        return (time.perf_counter() - t) / n * 1e3

    out["shape"] = {"batch": B, "ctx": ctx, "hq": Hq, "hkv": Hkv,
                    "head_dim": D, "page": page, "chunk": args.chunk}
    out["ms"] = {}
    cases = {}
    grids = [("mh", True)] if args.skip_per_head else [
        ("mh", True), ("ph", False)]
    for tname, (ptb, slots) in tables.items():
        for gname, fuse in grids:
            cases[f"pool_bf16_{gname}_{tname}"] = (
                lambda ptb=ptb, fuse=fuse: paged_attention_pool_kernel(
                    q, kv16, ptb, lens, 0, fuse_heads=fuse)
            )
            cases[f"pool_int8_{gname}_{tname}"] = (
                lambda ptb=ptb, fuse=fuse: paged_attention_pool_kernel(
                    q, kv8, ptb, lens, 0, kv_scales=scales,
                    fuse_heads=fuse)
            )
            cases[f"fused_bf16_{gname}_{tname}"] = (
                lambda ptb=ptb, slots=slots, fuse=fuse:
                paged_decode_fused_kernel(
                    q, kn, kn, kv16, slots, ptb, lens, 0,
                    fuse_heads=fuse)
            )
            cases[f"fused_int8_{gname}_{tname}"] = (
                lambda ptb=ptb, slots=slots, fuse=fuse:
                paged_decode_fused_kernel(
                    q, kn, kn, kv8, slots, ptb, lens, 0, kv_scales=scales, fuse_heads=fuse)
            )

    # Chunk-prefill (first on-chip timing — VERDICT r4 missing #2): Bc
    # rows each attending `ctx` prior pool tokens + a dense causal chunk.
    Bc, C = args.chunk_batch, args.chunk
    qc = jnp.asarray(rng.standard_normal((Bc, C, Hq, D)), jnp.bfloat16)
    kc = jnp.asarray(rng.standard_normal((Bc, C, Hkv, D)), jnp.bfloat16)
    prior = jnp.full((Bc,), ctx, jnp.int32)
    for tname in tables:
        ptb = tables[tname][0][:Bc]
        cases[f"chunk_bf16_{tname}"] = (
            lambda ptb=ptb: paged_chunk_attention_kernel(
                qc, kc, kc, kv16, ptb, prior, prior + C, 0)
        )
        cases[f"chunk_int8_{tname}"] = (
            lambda ptb=ptb: paged_chunk_attention_kernel(
                qc, kc, kc, kv8, ptb, prior, prior + C, 0,
                kv_scales=scales)
        )

    # Block-size sweep on the headline path: with run-coalesced DMAs the
    # descriptor count per sequence is blocks-per-ctx, so bigger blocks
    # trade fewer/larger descriptors against VMEM and tail waste — an
    # on-chip question (CPU numbers are meaningless here).
    ptb_run, slots_run = tables["run"]
    default_ppb = max(1, -(-128 // page))
    for ppb in (8, 16, 32):
        if ppb * page > ctx or ppb == default_ppb:
            # The resolved default is already timed as fused_bf16_mh_run.
            continue
        cases[f"fused_bf16_mh_run_ppb{ppb}"] = (
            lambda ppb=ppb: paged_decode_fused_kernel(
                q, kn, kn, kv16, slots_run, ptb_run, lens, 0,
                pages_per_block=ppb, fuse_heads=True)
        )

    # EVERY kernel timing is exception-guarded and partial results are
    # always printed/written: this repo's history shows kernels that fail
    # ONLY at on-chip Mosaic compile — one such failure must not discard
    # the numbers already measured (it does fail the exit code).
    for name, thunk in cases.items():
        try:
            out["ms"][name] = round(bench(thunk), 3)
        except Exception as e:  # noqa: BLE001 — record, keep measuring
            out.setdefault("errors", {})[name] = str(e)[:300]
    ms = out["ms"]

    def ratio(a, b):
        return round(ms[a] / ms[b], 3) if a in ms and b in ms else None

    out["summary"] = {
        # >1.0 means the second (new/cheaper) case is faster.
        "coalesce_gain_pool": ratio("pool_bf16_mh_perm", "pool_bf16_mh_run"),
        "coalesce_gain_fused": ratio("fused_bf16_mh_perm", "fused_bf16_mh_run"),
        "mh_gain_pool": ratio("pool_bf16_ph_run", "pool_bf16_mh_run"),
        "mh_gain_fused": ratio("fused_bf16_ph_run", "fused_bf16_mh_run"),
        "int8_vs_bf16_pool": ratio("pool_bf16_mh_run", "pool_int8_mh_run"),
        "int8_vs_bf16_fused": ratio("fused_bf16_mh_run", "fused_int8_mh_run"),
        "int8_vs_bf16_chunk": ratio("chunk_bf16_run", "chunk_int8_run"),
    }
    # Achieved HBM read bandwidth of the best bf16 decode case (K+V
    # context bytes / time) — the roofline-facing number.
    ctx_bytes = B * ctx * Hkv * 2 * D * 2
    for key in ("fused_bf16_mh_run", "pool_bf16_mh_run"):
        if key in ms:
            out[f"{key}_gbps"] = round(ctx_bytes / (ms[key] / 1e3) / 1e9, 1)
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    return 1 if failed or out.get("errors") else 0


if __name__ == "__main__":
    sys.exit(main())
