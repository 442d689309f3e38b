#!/usr/bin/env python3
"""Compile-only sizing of a configuration whose layers carry a state
beside the rows (``ModelConfig.state_shape``), against a described v5e:2x2
host: ``tools/aot_fit_row.py`` with the second array — the state slots,
sized as the engine sizes them (``ModelConfig.default_state_slots``: no
knob) — and the state inputs of the three entry points.

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_fit_lfm2.py \
        --model lfm2-24b-a2b-ep8 --slots 196608 --max-batch 32 \
        --decode-pages 2048 --wave 8x512x2048 --dense 1024

Lowers the launch shapes the cell's traffic reaches at the published
widths for the chip's real compiler and prints ``memory_analysis()`` per
program, the Mosaic kernels in it, and every ``copy`` in the optimized
program that is as large as the smallest of (the pool, the state slots, a
quarter of the conv stack's ``in_proj``): what the compiler refuses or
copies here costs no chip time. Nothing runs: no times. One chip only.
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
_ITEM = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1}


def big_copies(text: str, at_least: int) -> list[str]:
    """``dtype[shape]`` of every copy in the optimized program whose result
    holds ``at_least`` bytes or more."""
    out = []
    for dtype, shape in re.findall(r"= (\w+)\[([\d,]+)\]\S* copy\(", text):
        size = _ITEM.get(dtype, 4) * int(np.prod([int(x) for x in shape.split(",")]))
        if size >= at_least:
            out.append(f"{dtype}[{shape}]")
    return sorted(set(out))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", required=True)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--max-batch", type=int, required=True)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--decode-pages", type=int, default=2048)
    ap.add_argument("--wave", default="8x512x2048",
                    help="rows x chunk x page-table width of the chunk wave")
    ap.add_argument("--dense", type=int, default=1024,
                    help="new tokens of the dense prefill (it takes no prefix)")
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

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    def ints(*shape):
        return sd(shape, jnp.int32)

    shapes = jax.eval_shape(
        lambda k: _init_params(k, cfg, None, None),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    params = jax.tree.map(lambda x: sd(x.shape, x.dtype), shapes)
    pool = sd(cfg.kv_pool_shape(args.slots), cfg.dtype)
    n_states = cfg.default_state_slots(args.slots, args.max_batch)
    state = sd(cfg.state_shape(n_states), cfg.dtype)

    def nbytes(x):
        return int(np.prod(x.shape)) * x.dtype.itemsize

    weights = sum(nbytes(x) for x in jax.tree.leaves(shapes))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    floor = min(nbytes(pool), nbytes(state),
                nbytes(shapes["conv"]["in_proj"]) // 4)
    out = {
        "model": args.model, "slots": args.slots, "max_batch": args.max_batch,
        "parameters": n_params, "weights_gib": round(weights / GIB, 3),
        "pool_shape": list(pool.shape), "pool_gib": round(nbytes(pool) / GIB, 3),
        "kv_bytes_per_token": nbytes(pool) // args.slots,
        "state_shape": list(state.shape), "state_gib": round(nbytes(state) / GIB, 3),
        "state_slot_bytes": nbytes(state) // n_states,
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
        text = compiled.as_text()
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        out["programs"][name] = {
            "compile_s": round(time.monotonic() - t0, 1),
            "args_gib": round(mem.argument_size_in_bytes / GIB, 3),
            "temp_gib": round(mem.temp_size_in_bytes / GIB, 3),
            "alias_gib": round(mem.alias_size_in_bytes / GIB, 3),
            "live_gib": round(live / GIB, 3),
            "kernels": sorted(set(re.findall(r"radixmesh_[a-z_]+[a-z]", text)) - {"radixmesh_tpu"}),
            "big_copies": big_copies(text, floor),
        }

    B = args.max_batch
    todo = args.programs.split(",")
    if "decode" in todo:
        report(f"decode_step[{B}x{args.decode_pages}p]", decode_step.lower(
            params, cfg, ints(B), pool, ints(B), ints(B, args.decode_pages),
            ints(B), args.page, state_pool=state, state_slots=ints(2, B)))
    if "wave" in todo:
        r, c, w = (int(x) for x in args.wave.split("x"))
        report(f"prefill_chunk_paged[{r}x{c}x{w}p]", prefill_chunk_paged.lower(
            params, cfg, ints(r, c), ints(r, c), pool, ints(r, c), ints(r, w),
            ints(r), page_size=args.page, logits_at=ints(r), state_pool=state,
            state_in=ints(r), snap_at=ints(r, 3), state_out=ints(r, 3)))
    if "dense" in todo:
        s = args.dense
        kv = sd((len(cfg.attn_layers), 1, 0, *cfg.kv_row[1:]), cfg.dtype)
        report(f"prefill_forward[{s}]", prefill_forward.lower(
            params, cfg, ints(1, s), ints(1, s), kv, kv, ints(1),
            snap_at=ints(1, 3)))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
