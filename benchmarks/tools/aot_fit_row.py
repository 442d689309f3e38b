#!/usr/bin/env python3
"""Compile-only sizing of a configuration against a described v5e:2x2 host,
for any cache row: the pool's shape and the cached prefix's are the
program's own (``ModelConfig.kv_pool_shape`` / ``kv_row``), so a latent
(MLA) row is sized as K/V per head is (``tools/aot_fit.py`` spells the
K/V-per-head pool and serves the configurations that have it).

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_fit_row.py \
        --model glm-4.7-flash-l8 --slots 196608 --max-batch 32 \
        --decode-pages 2048 --wave 8x256x2048 --dense 1024x0

Lowers the launch shapes a cell's traffic reaches (decode at ``max_batch``
rows, the widest chunk-prefill wave, the widest dense prefill) at the
published widths for the chip's real compiler and prints
``memory_analysis()`` per program: what the compiler refuses here costs no
chip time. Nothing runs, so this says nothing about times. One chip only;
the numbers go into the configuration file's ``aot`` record by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

GIB = 2.0 ** 30


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", required=True)
    ap.add_argument("--weight-quant", default=None)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--max-batch", type=int, required=True)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--decode-pages", type=int, default=128)
    ap.add_argument("--wave", default="8x512x256",
                    help="rows x chunk x page-table width of the chunk wave")
    ap.add_argument("--dense", default="1024x4096",
                    help="new tokens x cached-prefix bucket of the dense prefill")
    ap.add_argument("--programs", default="decode,wave,dense")
    args = ap.parse_args()

    from radixmesh_tpu.models import get_config
    from radixmesh_tpu.models.llama import (
        _init_params, decode_step, prefill_chunk_paged, prefill_forward,
    )

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    cfg = get_config(args.model)
    rep = SingleDeviceSharding(topo.devices[0])

    def sd(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    shapes = jax.eval_shape(
        lambda k: _init_params(k, cfg, args.weight_quant, None),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    params = jax.tree.map(lambda x: sd(x.shape, x.dtype), shapes)
    pool = sd(cfg.kv_pool_shape(args.slots), cfg.dtype)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    out = {
        "model": args.model, "weight_quant": args.weight_quant,
        "slots": args.slots, "max_batch": args.max_batch,
        "weights_gib": round(weights / GIB, 3),
        "pool_shape": list(pool.shape),
        "pool_gib": round(pool.size * pool.dtype.itemsize / GIB, 3),
        "programs": {},
    }

    def report(name, lowered):
        t0 = time.monotonic()
        try:
            compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001 - the refusal IS the result
            out["programs"][name] = {"refused": str(e)[:600]}
            return
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        out["programs"][name] = {
            "compile_s": round(time.monotonic() - t0, 1),
            "args_gib": round(mem.argument_size_in_bytes / GIB, 3),
            "temp_gib": round(mem.temp_size_in_bytes / GIB, 3),
            "alias_gib": round(mem.alias_size_in_bytes / GIB, 3),
            "live_gib": round(live / GIB, 3),
            "kernels": sorted(set(re.findall(
                r"radixmesh_(?:mla|moe|decode|chunk|pool)[a-z_]*", compiled.as_text()))),
        }

    B = args.max_batch
    todo = args.programs.split(",")
    if "decode" in todo:
        report(f"decode_step[{B}x{args.decode_pages}p]", decode_step.lower(
            params, cfg, sd((B,), jnp.int32), pool, sd((B,), jnp.int32),
            sd((B, args.decode_pages), jnp.int32), sd((B,), jnp.int32),
            args.page))
    if "wave" in todo:
        r, c, w = (int(x) for x in args.wave.split("x"))
        report(f"prefill_chunk_paged[{r}x{c}x{w}p]", prefill_chunk_paged.lower(
            params, cfg, sd((r, c), jnp.int32), sd((r, c), jnp.int32), pool,
            sd((r, c), jnp.int32), sd((r, w), jnp.int32), sd((r,), jnp.int32),
            page_size=args.page, logits_at=sd((r,), jnp.int32)))
    if "dense" in todo:
        s, p = (int(x) for x in args.dense.split("x"))
        kv = sd((cfg.n_layers, 1, p, *cfg.kv_row[1:]), cfg.dtype)
        report(f"prefill_forward[{s}+{p}]", prefill_forward.lower(
            params, cfg, sd((1, s), jnp.int32), sd((1, s), jnp.int32), kv, kv,
            sd((1,), jnp.int32)))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
