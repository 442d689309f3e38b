"""Kernel-level micro-benchmark of the served decode-attention kernel
(``radixmesh_decode_fused_mh``), alone and at the geometries the cells run.

Whole-step numbers hide where kernel time goes. This script times one
decode step's worth of the fused decode kernel — a scan over the model's
layers with the pool donated, exactly how ``decode_step`` calls it — at
the three served geometries, ragged contexts and all:

- ``batch``    Qwen2-7B (Hq 28 / Hkv 4, D 128, page 16, 28 layers), 32
  rows, context 400–1 300 (``qwen2-7b.batch-unshared``);
- ``chat``     the same heads, 12 rows, context 1–5 k
  (``qwen2-7b.chat-sessions``);
- ``longtail`` Mellum2 (Hq 32 / Hkv 4, 12 layers, three of four with a
  1 024-token window), 32 rows, context 256–8 k
  (``mellum2-12b-l12.batch-longtail``).

Each geometry runs with two page-table layouts — ``perm`` (fully permuted
pages: the per-page DMA plan, which is what a pool under churn hands the
kernel) and ``run`` (every row one ascending run: the coalesced plan) —
and three ways: the kernel as it is, **copies only** (both waits, no
contraction: ``_mh_contract`` replaced by a no-op) and **math only** (the
contraction on whatever block is resident: ``_MhBlockCopy`` started and
awaited by no-ops). Per case it prints ms a step, µs a program (row ×
layer), µs a block, and the share of 819 GB/s that the bytes the rows
NEED (visible keys only, not a block's masked tail) make of the time.
``--pages-per-block`` sweeps explicit block sizes beside the kernel's own
choice.

Before timing anything it compares every kernel variant with its jnp
oracle at each geometry's heads (``ops/kernel_check.py`` — the
default-path kernels and the ones only a flag selects). A variant that
fails to compile or disagrees is recorded with the compiler's message and
the exit code is non-zero.

Prints one JSON line naming the device; ``--out FILE`` also writes it.
Timing needs a TPU: without one the script exits 2, unless ``--interpret``
asks for the parity check alone (Pallas interpret mode, numerics only —
an interpreter timing is not a kernel time and is never taken).

``--kernel chunk`` times the chunk-prefill kernel (``radixmesh_chunk_attn``)
instead, alone, as ``prefill_chunk_paged`` calls it — a scan over the
model's attention layers — at two geometries, one ascending-run row each
and the same rows on permuted pages:

- ``chat``   Qwen2-7B (Hq 28 / Hkv 4, 28 layers): 1 x 256 new tokens on a
  4.2 k prefix, a 512-page table (``qwen2-7b.chat-sessions``);
- ``agent``  LFM2-24B-A2B (two heads of 64 to a pool row: Hq 32 / Hkv 4
  wide, 10 attention layers, logits scaled by 1/8): 1 x 512 on 8 k, the
  2 048-page table every chunk program of that cell has
  (``lfm2-24b-a2b-ep8.agent-sessions``).

Each runs ``full`` (every query of the launched shape is a token) and
``padded`` (two thirds are: the cells' ``prefill_pad_share``), as it is,
copies only, math only, and with one part of the kernel put back to what it
was before PR 43 (``f32_operands``, ``per_head``), over
``--pages-per-block`` and ``--q-block``. Per case: ms a launch and a
layer, the seconds of the first call (the compile), and the share of 197
TFLOP/s that the products the VALID queries need (4 x D x visible keys a
query and q head) make of the time. A checkout
whose kernel lacks a hook (the parent's) runs what it can.

Usage: python scripts/kernelbench.py [--geometry batch,chat,longtail]
       python scripts/kernelbench.py --kernel chunk [--geometry chat,agent]
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

HBM_BYTES_PER_S = 819e9  # one TPU v5e chip (Google Cloud documentation)

# name -> heads, layers, rows, window (keys a query of a window layer sees;
# every fourth layer of such a model attends everything), context range.
GEOMETRIES = {
    "batch": dict(hq=28, hkv=4, layers=28, rows=32, window=0, ctx=(400, 1300)),
    "chat": dict(hq=28, hkv=4, layers=28, rows=12, window=0, ctx=(1000, 5000)),
    "longtail": dict(hq=32, hkv=4, layers=12, rows=32, window=1024,
                     ctx=(256, 8192)),
}
HEAD_DIM, PAGE = 128, 16
MXU_FLOPS_PER_S = 197e12  # bf16, the same chip and source

# --kernel chunk: heads, attention layers, rows x chunk tokens launched,
# tokens already in pages, page-table width, scale of the logits (None:
# 1/sqrt(D)).
CHUNK_GEOMETRIES = {
    "chat": dict(hq=28, hkv=4, layers=28, rows=1, chunk=256, prior=4200,
                 max_pages=512, scale=None),
    "agent": dict(hq=32, hkv=4, layers=10, rows=1, chunk=512, prior=8192,
                  max_pages=2048, scale=0.125),
}


def _contexts(name: str, g: dict, rng) -> "np.ndarray":
    import numpy as np

    lo, hi = g["ctx"]
    if name == "batch":  # prompts uniform, replies part-way through
        return rng.integers(lo, hi + 1, g["rows"]).astype(np.int32)
    if name == "chat":  # turn depths 1..8 of a session: geometric spread
        return rng.permutation(
            np.geomspace(lo, hi, g["rows"]).astype(np.int32)
        )
    ln = np.exp(rng.normal(np.log(900.0), 0.9, g["rows"]))
    return np.clip(ln, lo, hi).astype(np.int32)


def _windows(g: dict):
    """Per-layer window of the scanned body (0 = full attention), or None
    for a model without window layers (the kernel then compiles without a
    window start, as ``decode_step`` compiles it)."""
    import numpy as np

    if not g["window"]:
        return None
    return np.asarray(
        [0 if l % 4 == 3 else g["window"] for l in range(g["layers"])], np.int32
    )


def _needed_tokens(lengths, windows, layers: int) -> int:
    """Keys the rows must read from pool pages in one step, summed over
    rows and layers: everything before the current token, from the window
    start on where the layer has one."""
    import numpy as np

    hbm = np.maximum(lengths.astype(np.int64) - 1, 0)
    if windows is None:
        return int(hbm.sum()) * layers
    total = 0
    for w in windows:
        start = np.maximum(lengths - w, 0) if w else 0
        total += int(np.maximum(hbm - start, 0).sum())
    return total


def _blocks(lengths, windows, layers: int, bk: int) -> int:
    """Blocks the kernel's loops walk in one step (a window row starts at
    the block that holds its first visible key)."""
    import numpy as np

    hbm = np.maximum(lengths.astype(np.int64) - 1, 0)
    last = -(-hbm // bk)
    if windows is None:
        return int(last.sum()) * layers
    total = 0
    for w in windows:
        first = (np.maximum(lengths - w, 0) // bk) if w else 0
        total += int(np.maximum(last - first, 0).sum())
    return total


@contextlib.contextmanager
def _stubbed(pa, what: str | None):
    """Replace one half of the block loops by a no-op for the duration
    (``copies``: no contraction; ``math``: no block DMA, start nor wait),
    or put one part of the chunk kernel back to what it was before PR 43.
    A checkout whose kernel lacks the hook raises ``AttributeError``."""
    import jax

    saved = {}
    # The kernel entry points are jitted: a trace made under another stub
    # (or none) must not be served from their caches.
    jax.clear_caches()

    def swap(obj, name, new):
        saved[(obj, name)] = getattr(obj, name)
        setattr(obj, name, new)

    try:
        if what == "copies":
            swap(pa, "_mh_contract", lambda **kw: None)
            swap(pa, "_chunk_fold", lambda *a, **kw: None)
        elif what == "math":
            for copy in (pa._MhBlockCopy, pa._GatedCopy):
                swap(copy, "start", lambda self: None)
                swap(copy, "wait", lambda self: None)
        elif what == "f32_operands":  # q, K, V and p float32 into the products
            operand_q = pa._operand_q
            swap(pa, "_operand_q",
                 lambda q, kv_dtype, quantized, scale=None: operand_q(
                     q, kv_dtype, True, scale))
        elif what == "per_head":  # grid (B, Hkv, C // Cblk)
            swap(pa, "_auto_fuse_heads", lambda *a: False)
        yield
    finally:
        for (obj, name), old in saved.items():
            setattr(obj, name, old)
        jax.clear_caches()


def _finish(out: dict, args, failed: list) -> int:
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    return 1 if failed else 0


def _chunk_cases(args, names, sweeps, pa, bench, cases: list) -> list[str]:
    """``--kernel chunk``: time ``paged_chunk_attention_kernel`` over a
    model's attention layers at each named geometry (module docstring);
    appends one dict a case to ``cases`` and returns the names of those
    that raised."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    failed = []
    # the kernel's own block and query block, then each swept alone
    combos = [(ppb, None) for ppb in sweeps] + [
        (None, int(x)) for x in args.q_block.split(",") if x]
    for name in names:
        g = CHUNK_GEOMETRIES[name]
        rng = np.random.default_rng(args.seed)
        B, C, Hq, Hkv, L = g["rows"], g["chunk"], g["hq"], g["hkv"], g["layers"]
        maxp, prior = g["max_pages"], g["prior"]
        held = -(-(prior + C) // PAGE)  # pages a row holds after the chunk
        P = B * held + maxp
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        q = jax.random.normal(ks[0], (B, C, Hq, HEAD_DIM), jnp.bfloat16)
        kc = jax.random.normal(ks[1], (B, C, Hkv, HEAD_DIM), jnp.bfloat16)
        vc = jax.random.normal(ks[2], (B, C, Hkv, HEAD_DIM), jnp.bfloat16)
        kv = jax.random.normal(
            ks[3], (2, L, Hkv, P, PAGE, HEAD_DIM), jnp.bfloat16
        )
        perm = rng.permutation(B * held)
        tables = {}
        for layout in args.layouts.split(","):
            pt = np.zeros((B, maxp), np.int32)
            for b in range(B):
                ids = np.arange(b * held, (b + 1) * held)
                pt[b, :held] = perm[ids] if layout == "perm" else ids
            tables[layout] = jnp.asarray(pt)
        priors = jnp.full((B,), prior, jnp.int32)

        def make(pt, new, ppb, qb):
            kvlen = priors + new

            def step(kv):
                def body(l, acc):
                    o = pa.paged_chunk_attention_kernel(
                        q, kc, vc, kv, pt, priors, kvlen, l,
                        pages_per_block=ppb, q_block=qb, scale=g["scale"],
                    )
                    return acc + o.astype(jnp.float32)

                return kv, jax.lax.fori_loop(
                    0, L, body, jnp.zeros(q.shape, jnp.float32))

            return jax.jit(step)

        for layout, pt in tables.items():
            for fill, new in (("full", C), ("padded", C * 2 // 3)):
                # products the valid queries need: q.k and p.v over the
                # prefix and the causal half of the chunk
                keys = new * prior + new * (new + 1) // 2
                flops = 4 * HEAD_DIM * keys * Hq * L * B
                for ppb, qb in combos:
                    whats = (None,) if args.no_split or ppb or qb else (
                        None, "copies", "math", "f32_operands", "per_head")
                    for stub in whats:
                        case = {
                            "geometry": name, "layout": layout,
                            "fill": fill, "new_tokens": new,
                            "pages_per_block": ppb, "q_block": qb,
                            "what": stub or "kernel",
                        }
                        try:
                            with _stubbed(pa, stub):
                                kv, sec, first = bench(make(pt, new, ppb, qb), kv)
                        except Exception as e:  # noqa: BLE001 — record, keep measuring
                            case["error"] = f"{type(e).__name__}: {e}"[:600]
                            if not isinstance(e, AttributeError):
                                failed.append(f"{name}:{layout}:{stub}")
                            cases.append(case)
                            continue
                        case.update({
                            "first_call_s": round(first, 2),
                            "ms_per_launch": round(sec * 1e3, 4),
                            "ms_per_layer": round(sec * 1e3 / L, 4),
                            "needed_gflop": round(flops / 1e9, 2),
                            "mxu_share_pct": round(
                                100 * flops / sec / MXU_FLOPS_PER_S, 2),
                        })
                        cases.append(case)
                        print(json.dumps(case), file=sys.stderr, flush=True)
        del kv
    return failed


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--kernel", choices=("decode", "chunk"), default="decode",
                    help="decode: radixmesh_decode_fused_mh (the default); "
                    "chunk: radixmesh_chunk_attn")
    ap.add_argument("--geometry", default=None,
                    help="comma list of " + ", ".join(GEOMETRIES) + " (decode: "
                    "all three) or of " + ", ".join(CHUNK_GEOMETRIES)
                    + " (chunk: both)")
    ap.add_argument("--q-block", default="",
                    help="chunk: comma list of explicit query blocks (tokens a "
                    "program) to time beside the kernel's own choice")
    ap.add_argument("--layouts", default="perm,run",
                    help="page-table layouts: perm (per-page DMA plan), "
                    "run (coalesced plan)")
    ap.add_argument("--pages-per-block", default="",
                    help="comma list of explicit block sizes (pages) to time "
                    "beside the kernel's own choice")
    ap.add_argument("--no-parity", action="store_true",
                    help="skip the kernel-vs-oracle check")
    ap.add_argument("--no-split", action="store_true",
                    help="skip the copies-only / math-only runs")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true",
                    help="parity check only, in Pallas interpret mode "
                    "(any backend; nothing is timed)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    known = CHUNK_GEOMETRIES if args.kernel == "chunk" else GEOMETRIES
    names = [n for n in (args.geometry or ",".join(known)).split(",") if n]
    for n in names:
        if n not in known:
            ap.error(f"unknown geometry {n!r}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    # (the package re-exports a FUNCTION named paged_attention)
    pa = importlib.import_module("radixmesh_tpu.ops.paged_attention")
    from radixmesh_tpu.ops.kernel_check import (
        DEFAULT_CASES,
        FLAG_CASES,
        WINDOW_CASES,
        check_kernels,
    )

    dev = jax.devices()[0]
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
    }
    if dev.platform != "tpu" and not args.interpret:
        print(f"kernelbench: needs a TPU, found {out['device']} "
              "(--interpret runs the parity check alone)", file=sys.stderr)
        return 2
    out["parity"] = {}
    failed = []
    heads = sorted({(known[n]["hq"], known[n]["hkv"]) for n in names})
    for hq, hkv in [] if args.no_parity else heads:
        tiny = args.interpret
        res = check_kernels(
            DEFAULT_CASES + FLAG_CASES, hq=hq, hkv=hkv, head_dim=HEAD_DIM,
            page=PAGE, batch=4 if tiny else 8, chunk=32 if tiny else 512,
            max_pages=8 if tiny else 64, interpret=args.interpret,
        )
        if any(known[n].get("window") for n in names
               if (known[n]["hq"], known[n]["hkv"]) == (hq, hkv)):
            res += check_kernels(
                WINDOW_CASES, hq=hq, hkv=hkv, head_dim=HEAD_DIM, page=PAGE,
                batch=8, chunk=32 if tiny else 512,
                max_pages=16 if tiny else 128, window=40 if tiny else 1024,
                interpret=args.interpret,
            )
        out["parity"][f"hq{hq}_hkv{hkv}"] = res
        failed += [f"hq{hq}:{r['kernel']}" for r in res if not r["ok"]]
    if args.interpret:
        print(json.dumps(out), flush=True)
        return 1 if failed else 0

    def bench(fn, kv, n=args.iters):
        """``(kv, seconds a call, seconds of the first call)``: the better
        of two rounds of ``n`` (one round in a dozen meets a stall of tens
        of ms that is the machine's), and the call that compiled."""
        t = time.perf_counter()
        kv, r = fn(kv)  # compile + warm
        jax.block_until_ready(r)
        first = time.perf_counter() - t
        rounds = []
        for _ in range(2):
            t = time.perf_counter()
            for _ in range(n):
                kv, r = fn(kv)
            jax.block_until_ready(r)
            rounds.append((time.perf_counter() - t) / n)
        return kv, min(rounds), first

    sweeps = [None] + [int(x) for x in args.pages_per_block.split(",") if x]
    out["cases"] = []
    if args.kernel == "chunk":
        failed += _chunk_cases(args, names, sweeps, pa, bench, out["cases"])
        return _finish(out, args, failed)
    for name in names:
        g = GEOMETRIES[name]
        rng = np.random.default_rng(args.seed)
        B, Hq, Hkv, L = g["rows"], g["hq"], g["hkv"], g["layers"]
        lengths = _contexts(name, g, rng)
        windows = _windows(g)
        row_pages = -(-lengths // PAGE)
        maxp = int(1 << int(np.ceil(np.log2(row_pages.max()))))
        P = int(row_pages.sum()) + maxp  # the tail keeps a run in bounds
        key = jax.random.PRNGKey(args.seed)
        kq, kk, kv_key = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, Hq, HEAD_DIM), jnp.bfloat16)
        kn = jax.random.normal(kk, (B, Hkv, HEAD_DIM), jnp.bfloat16)
        kv = jax.random.normal(
            kv_key, (2, L, Hkv, P, PAGE, HEAD_DIM), jnp.bfloat16
        )
        starts = np.concatenate([[0], np.cumsum(row_pages)[:-1]])
        perm = rng.permutation(int(row_pages.sum()))
        tables = {}
        for layout in args.layouts.split(","):
            pt = np.zeros((B, maxp), np.int32)
            for b in range(B):
                ids = np.arange(starts[b], starts[b] + row_pages[b])
                pt[b, : row_pages[b]] = perm[ids] if layout == "perm" else ids
            pos = lengths - 1
            slots = pt[np.arange(B), pos // PAGE] * PAGE + pos % PAGE
            tables[layout] = (jnp.asarray(pt), jnp.asarray(slots, jnp.int32))
        lens = jnp.asarray(lengths)
        w_arr = None if windows is None else jnp.asarray(windows)
        needed = _needed_tokens(lengths, windows, L) * Hkv * HEAD_DIM * 2 * 2

        def make(pt, slots, ppb):
            def step(kv):
                def body(l, carry):
                    kv, acc = carry
                    o, kv = pa.paged_decode_fused_kernel(
                        q, kn, kn, kv, slots, pt, lens, l,
                        pages_per_block=ppb,
                        window=None if w_arr is None else w_arr[l],
                    )
                    return kv, acc + o.astype(jnp.float32)

                return jax.lax.fori_loop(
                    0, L, body, (kv, jnp.zeros(q.shape, jnp.float32))
                )

            return jax.jit(step, donate_argnums=0)

        for layout, (pt, slots) in tables.items():
            for ppb in sweeps:
                chosen = ppb or pa._mh_pages_per_block(
                    Hkv, PAGE, HEAD_DIM, kv.dtype, maxp
                )
                blocks = _blocks(lengths, windows, L, chosen * PAGE)
                for stub in (None,) if args.no_split else (None, "copies", "math"):
                    case = {
                        "geometry": name, "layout": layout,
                        "pages_per_block": chosen, "chosen_by_kernel": ppb is None,
                        "what": stub or "kernel",
                    }
                    try:
                        with _stubbed(pa, stub):
                            kv, sec, _ = bench(make(pt, slots, ppb), kv)
                    except Exception as e:  # noqa: BLE001 — record, keep measuring
                        case["error"] = f"{type(e).__name__}: {e}"[:600]
                        failed.append(f"{name}:{layout}:{stub}")
                        out["cases"].append(case)
                        continue
                    case.update({
                        "ms_per_step": round(sec * 1e3, 4),
                        "us_per_program": round(sec * 1e6 / (B * L), 3),
                        "us_per_block": round(sec * 1e6 / blocks, 4),
                        "blocks_per_step": blocks,
                        "programs_per_step": B * L,
                        "needed_mb_per_step": round(needed / 1e6, 2),
                        "hbm_share_pct": round(
                            100 * needed / sec / HBM_BYTES_PER_S, 2
                        ),
                    })
                    out["cases"].append(case)
                    print(json.dumps(case), file=sys.stderr, flush=True)
        del kv
    # batch and chat share heads and block size and differ in rows and
    # context: time = bytes / rate + programs x cost has one solution.
    out["fit"] = []
    for layout in tables:
        for what in ("kernel", "copies", "math"):
            pair = [
                c for c in out["cases"]
                if c["layout"] == layout and c["what"] == what
                and c["chosen_by_kernel"] and "ms_per_step" in c
                and c["geometry"] in ("batch", "chat")
            ]
            if len(pair) != 2:
                continue
            ms_per_mb, ms_per_program = np.linalg.solve(
                [[c["needed_mb_per_step"], c["programs_per_step"]] for c in pair],
                [c["ms_per_step"] for c in pair],
            )
            out["fit"].append({
                "layout": layout, "what": what,
                "gb_per_s_on_needed_bytes": round(1.0 / ms_per_mb, 1),
                "us_per_program": round(ms_per_program * 1e3, 3),
            })
    return _finish(out, args, failed)


if __name__ == "__main__":
    sys.exit(main())
