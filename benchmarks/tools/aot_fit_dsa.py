#!/usr/bin/env python3
"""Compile-only sizing of a configuration with TWO pool arrays (the cache
rows and the index keys of learned sparse attention) against a described
v5e:2x2 host — ``tools/aot_fit_row.py`` with the second array, which that
tool cannot take.

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_fit_dsa.py \
        --model glm-5.2-ep16 --slots 196608 --max-batch 32 \
        --decode-pages 4096 --wave 8x256x4096 --dense 1024

Lowers the launch shapes the cell's traffic reaches (decode at
``max_batch`` rows, the widest chunk-prefill wave, the widest dense
prefill) at the published widths for the chip's real compiler and prints
``memory_analysis()`` per program, the Mosaic kernels it holds, and every
``copy`` of the optimized program as large as a quarter of the smaller pool
array (a pool that does not flow through in place shows up here). Nothing
runs: sizes, never times. The numbers go into the configuration file's
``aot`` record by hand.
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
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

GIB = 2.0 ** 30
_ITEM = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "s8": 1, "u8": 1}


def large_copies(hlo: str, floor_bytes: int) -> list[str]:
    """``copy`` instructions of an optimized HLO text whose result is at
    least ``floor_bytes``, as ``"name dtype[shape] GiB"``."""
    found = []
    for m in re.finditer(
            r"%?([\w.\-]+) = (\w+)\[([\d,]*)\][^ ]* copy\(", hlo):
        name, dtype, dims = m.groups()
        size = _ITEM.get(dtype, 4) * int(np.prod(
            [int(d) for d in dims.split(",") if d] or [1]))
        if size >= floor_bytes:
            found.append(f"{name} {dtype}[{dims}] {size / GIB:.3f} GiB")
    return found


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", required=True)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--max-batch", type=int, required=True)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--decode-pages", type=int, default=4096)
    ap.add_argument("--wave", default="8x256x4096",
                    help="rows x chunk x page-table width of the chunk wave")
    ap.add_argument("--dense", type=int, default=1024,
                    help="new tokens of the dense prefill (no cached prefix)")
    ap.add_argument("--programs", default="decode,wave,dense")
    ap.add_argument("--dump-hlo", default=None,
                    help="directory for the optimized HLO text of each program")
    args = ap.parse_args()

    from radixmesh_tpu.models import get_config
    from radixmesh_tpu.models.llama import (
        _init_params, decode_step, prefill_chunk_paged, prefill_forward,
    )

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    cfg = get_config(args.model)
    rep = SingleDeviceSharding(topo.devices[0])

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    shapes = jax.eval_shape(
        lambda k: _init_params(k, cfg, None, None),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    params = jax.tree.map(lambda x: sd(x.shape, x.dtype), shapes)
    pool = sd(cfg.kv_pool_shape(args.slots), cfg.dtype)
    index = sd(cfg.kv_index_shape(args.slots), cfg.dtype)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    index_bytes = index.size * index.dtype.itemsize
    out = {
        "model": args.model, "slots": args.slots, "max_batch": args.max_batch,
        "weights_gib": round(weights / GIB, 3),
        "pool_shape": list(pool.shape),
        "pool_gib": round(pool.size * pool.dtype.itemsize / GIB, 3),
        "index_shape": list(index.shape),
        "index_gib": round(index_bytes / GIB, 3),
        "programs": {},
    }

    def report(name, lowered):
        t0 = time.monotonic()
        try:
            compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001 - the refusal IS the result
            out["programs"][name] = {"refused": str(e)[:1500]}
            return
        mem = compiled.memory_analysis()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        text = compiled.as_text()
        if args.dump_hlo:
            os.makedirs(args.dump_hlo, exist_ok=True)
            with open(os.path.join(args.dump_hlo, re.sub(r"\W", "_", name) + ".txt"),
                      "w") as fh:
                fh.write(text)
        out["programs"][name] = {
            "compile_s": round(time.monotonic() - t0, 1),
            "args_gib": round(mem.argument_size_in_bytes / GIB, 3),
            "temp_gib": round(mem.temp_size_in_bytes / GIB, 3),
            "alias_gib": round(mem.alias_size_in_bytes / GIB, 3),
            "live_gib": round(live / GIB, 3),
            "kernels": sorted(set(re.findall(
                r"radixmesh_(?:mla|moe|dsa)[a-z_]*", text))),
            "large_copies": large_copies(text, index_bytes // 4),
        }

    B = args.max_batch
    todo = args.programs.split(",")
    if "decode" in todo:
        report(f"decode_step[{B}x{args.decode_pages}p]", decode_step.lower(
            params, cfg, sd((B,), jnp.int32), pool, sd((B,), jnp.int32),
            sd((B, args.decode_pages), jnp.int32), sd((B,), jnp.int32),
            args.page, index_pool=index))
    if "wave" in todo:
        r, c, w = (int(x) for x in args.wave.split("x"))
        report(f"prefill_chunk_paged[{r}x{c}x{w}p]", prefill_chunk_paged.lower(
            params, cfg, sd((r, c), jnp.int32), sd((r, c), jnp.int32), pool,
            sd((r, c), jnp.int32), sd((r, w), jnp.int32), sd((r,), jnp.int32),
            page_size=args.page, logits_at=sd((r,), jnp.int32),
            index_pool=index))
    if "dense" in todo:
        s = args.dense
        kv = sd((cfg.n_layers, 1, 0, *cfg.kv_row[1:]), cfg.dtype)
        report(f"prefill_forward[{s}+0]", prefill_forward.lower(
            params, cfg, sd((1, s), jnp.int32), sd((1, s), jnp.int32), kv, kv,
            sd((1,), jnp.int32)))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
