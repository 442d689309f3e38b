#!/usr/bin/env python3
"""One decode step and one prefill chunk of a configuration with learned
sparse attention, alone on the chip under the profiler: where the step's
device time goes (kernels by name, the largest XLA ops by self time), at
the cell's shapes, without a server.

    chiprun -- python3 benchmarks/tools/dsa_step_probe.py \
        --model glm-5.2-ep16 --slots 196608 --rows 32 --context 41000 \
        --pages 4096 --chunk 256 --out chiprun_out/dsa_probe.json

Random weights and random pool contents from ``--seed``; every row reads
the same ``context`` tokens of pages (a shared document) and writes its
own new token. Times here are of the programs alone: no scheduler, no
host gaps. The largest ops are printed with their full event names: the
instruction's text, result shape first, which is what
``harness/dsa_trace.py`` matches the XLA pieces on.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="glm-5.2-ep16")
    ap.add_argument("--slots", type=int, default=196608)
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--context", type=int, default=41000)
    ap.add_argument("--pages", type=int, default=4096)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--chunk-rows", type=int, default=1)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=33)
    ap.add_argument("--out", default="chiprun_out/dsa_probe.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import xplane
    from radixmesh_tpu.models import get_config, init_params
    from radixmesh_tpu.models.llama import (
        decode_step, new_moe_stats, prefill_chunk_paged,
    )

    cfg = get_config(args.model)
    dev = jax.devices()[0]
    t0 = time.monotonic()
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    key = jax.random.PRNGKey(args.seed + 1)

    @jax.jit
    def fill(key):
        k1, k2 = jax.random.split(key)
        pool = jnp.concatenate([
            jax.random.normal(jax.random.fold_in(k1, l),
                              cfg.kv_pool_shape(args.slots)[2:], cfg.dtype)[None]
            for l in range(cfg.n_layers)])[None]
        index = jax.random.normal(k2, cfg.kv_index_shape(args.slots), cfg.dtype)
        return pool, index

    pool, index = fill(key)
    jax.block_until_ready((params, pool, index))
    setup_s = time.monotonic() - t0
    B, ps = args.rows, args.page
    n_pages = -(-(args.context + args.chunk + 8) // ps)
    doc = np.arange(1, n_pages + 1, dtype=np.int32)  # page 0: scratch
    pt = np.zeros((B, args.pages), np.int32)
    pt[:, :n_pages] = doc
    # each row's tail page is its own (the new token goes there)
    tail = args.context // ps
    pt[:, tail] = n_pages + 1 + np.arange(B)
    lengths = np.full((B,), args.context + 1, np.int32)
    slots = pt[np.arange(B), tail] * ps + args.context % ps
    tokens = jnp.zeros((B,), jnp.int32)
    moe = new_moe_stats(cfg.ep_size > 1)  # as the engine starts them
    out = {"model": args.model, "device": dev.device_kind, "setup_s": setup_s,
           "rows": B, "context": args.context, "pages": args.pages}

    def decode(pool, index, moe):
        res = decode_step(params, cfg, tokens, pool, jnp.asarray(slots),
                          jnp.asarray(pt), jnp.asarray(lengths), ps,
                          index_pool=index, moe_stats=moe)
        return res[0], res[1], res[2], res[3]["moe_stats"]

    C, R = args.chunk, args.chunk_rows
    c_pos = (args.context + np.arange(C, dtype=np.int32))[None].repeat(R, 0)
    c_slots = (pt[:R, tail, None] * ps + (args.context % ps + np.arange(C))[None]
               ) % args.slots

    def chunk(pool, index, moe):
        res = prefill_chunk_paged(
            params, cfg, jnp.zeros((R, C), jnp.int32), jnp.asarray(c_pos), pool,
            jnp.asarray(c_slots), jnp.asarray(pt[:R]),
            jnp.full((R,), args.context + C, jnp.int32), page_size=ps,
            logits_at=jnp.full((R,), C - 1, jnp.int32), index_pool=index,
            moe_stats=moe)
        return res[0], res[1], res[2], res[3]["moe_stats"]

    for name, fn in (("decode", decode), ("chunk", chunk)):
        t0 = time.monotonic()
        lg, pool, index, moe = fn(pool, index, moe)
        jax.block_until_ready(lg)
        out[name + "_first_call_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        for _ in range(args.steps):
            lg, pool, index, moe = fn(pool, index, moe)
        jax.block_until_ready(lg)
        out[name + "_wall_ms"] = (time.monotonic() - t0) / args.steps * 1e3
        out[name + "_finite"] = bool(jnp.isfinite(lg).all())
        prof = tempfile.mkdtemp(prefix="dsa_probe_")
        with jax.profiler.trace(prof):
            for _ in range(args.steps):
                lg, pool, index, moe = fn(pool, index, moe)
            jax.block_until_ready(lg)
        path = xplane.find_capture(prof)
        planes = xplane.load(path)
        red = xplane.reduce(planes)
        p = next(iter(red["planes"].values()))
        n = args.steps
        out[name] = {
            "modules_ms": {k: v["seconds"] / max(v["count"], 1) * 1e3
                           for k, v in p["modules"].items()},
            "kernels_ms_per_call": {k: v["seconds"] / n * 1e3
                                    for k, v in p["kernels"].items()},
            "kernel_counts_per_call": {k: v["count"] / n
                                       for k, v in p["kernels"].items()},
            "top_ops_ms_per_call": [
                [k, v / n * 1e3] for k, v in sorted(
                    p["op_self_s"].items(), key=lambda kv: -kv[1])[:45]],
        }
        # what a reader of the capture can match an XLA op on: the event's
        # name is the instruction's text, result shape first
        lines = next(iter(planes.values()))
        full = {}
        for ev_name, _, dur in lines.get(xplane.OPS_LINE, []):
            short = xplane.short_name(ev_name)
            c = full.setdefault(short, [ev_name[:400], 0, 0])
            c[1] += 1
            c[2] += dur
        out[name]["full_names_of_the_largest"] = [
            {"name": v[0], "count_per_call": v[1] / n, "ms_per_call": v[2] / n / 1e6}
            for _, v in sorted(full.items(), key=lambda kv: -kv[1][2])[:60]]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps({k: v for k, v in out.items() if not isinstance(v, dict)}))
    for name in ("decode", "chunk"):
        print(name, json.dumps(out[name]["modules_ms"]),
              json.dumps(out[name]["kernels_ms_per_call"]))
        print(name, "top ops", json.dumps(out[name]["top_ops_ms_per_call"][:14]))
        for row in out[name]["full_names_of_the_largest"][:25]:
            print(name, round(row["ms_per_call"], 3), row["count_per_call"],
                  row["name"][:230])
    return 0


if __name__ == "__main__":
    sys.exit(main())
