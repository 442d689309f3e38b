#!/usr/bin/env python3
"""Compile-only sizing of a configuration with Mamba-2 blocks beside plain
GQA attention and expert blocks (``ModelConfig.kv_pool_shape``: the
attention blocks' rows; ``state_shape`` / ``conv_state_shape``: a float32
matrix a head and the convolution's inputs a Mamba-2 block), against a
described v5e:2x2 host: ``tools/aot_fit_kda.py`` for the other kind of
matrix state, each array sized as the engine sizes it
(``engine.pool_groups``: no knob).

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_fit_ssd.py \
        --model nemotron-3-nano-ep8 --slots 524288 --max-batch 64 \
        --decode-pages 1024 --wave 64x64x1024,8x512x1024 --dense 1024

Lowers the launch shapes the cell's traffic reaches at the published
widths for the chip's real compiler and prints ``memory_analysis()`` per
program, the Mosaic kernels in it (no launch may lower the ``lax.scan``
recurrence, the jnp attention where pages are read or the dense expert
loop: ``radixmesh_ssd_*``, the paged attention kernels and
``radixmesh_moe_gmm_*`` must appear), and every ``copy`` of the optimized
program that is as large as the smallest of (the pool, a state array).
Nothing runs: no times.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from aot_fit_lfm2 import GIB, big_copies  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", required=True)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--max-batch", type=int, required=True)
    ap.add_argument("--page", type=int, default=16)
    ap.add_argument("--decode-pages", type=int, default=1024)
    ap.add_argument("--wave", default="8x512x1024",
                    help="rows x chunk x page-table width of the chunk waves, "
                    "comma-separated")
    ap.add_argument("--dense", type=int, default=1024)
    ap.add_argument("--programs", default="decode,wave,dense")
    args = ap.parse_args()

    import re

    from radixmesh_tpu.engine.engine import pool_groups
    from radixmesh_tpu.models import get_config
    from radixmesh_tpu.models.llama import (
        _init_params, decode_step, new_moe_stats, prefill_chunk_paged,
        prefill_forward,
    )

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = get_config(args.model)
    if cfg.libtpu_init_args:  # as ``launch.py serve`` compiles them
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(
            [os.environ.get("LIBTPU_INIT_ARGS", ""), *cfg.libtpu_init_args]).strip()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
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
    groups = pool_groups(cfg, args.slots, args.max_batch, args.page)
    pool = sd(cfg.kv_pool_shape(args.slots), cfg.dtype)
    state = (sd(groups["state_shape"], groups["state_dtype"]),
             sd(groups["conv_state_shape"], cfg.dtype))
    n_states = state[0].shape[1]

    def nbytes(x):
        return int(np.prod(x.shape)) * x.dtype.itemsize

    weights = sum(nbytes(x) for x in jax.tree.leaves(shapes))
    floor = min(nbytes(pool), nbytes(state[0]), nbytes(state[1]))
    held = weights + nbytes(pool) + sum(map(nbytes, state))
    moe = jax.eval_shape(lambda: new_moe_stats(cfg.ep_size > 1, cfg.n_group > 1))
    moe = sd(moe.shape, moe.dtype)
    out = {
        "model": args.model, "slots": args.slots, "max_batch": args.max_batch,
        "parameters": sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)),
        "weights_gib": round(weights / GIB, 3),
        "kv_rows": {"shape": list(pool.shape),
                        "gib": round(nbytes(pool) / GIB, 3),
                        "bytes_per_token": nbytes(pool) // args.slots},
        "state": {"shapes": [list(s.shape) for s in state], "slots": n_states,
                  "gib": round(sum(map(nbytes, state)) / GIB, 3),
                  "slot_bytes": cfg.state_slot_bytes(),
                  "stored_slot_bytes": sum(map(nbytes, state)) // n_states},
        "held_gib": round(held / GIB, 3),
        "programs": {},
    }

    def report(name, lowered):
        t0 = time.monotonic()
        try:
            compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001 - the refusal IS the result
            out["programs"][name] = {"refused": str(e)[:900]}
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
            "kernels": sorted(set(re.findall(r"radixmesh_[a-z_]+[a-z]", text))
                              - {"radixmesh_tpu"}),
            "big_copies": big_copies(text, floor),
        }

    B = args.max_batch
    todo = args.programs.split(",")
    if "decode" in todo:
        w = args.decode_pages
        report(f"decode_step[{B}x{w}p]", decode_step.lower(
            params, cfg, ints(B), pool, ints(B), ints(B, w), ints(B), args.page,
            state_pool=state, state_slots=ints(2, B), moe_stats=moe))
    for wave in args.wave.split(",") if "wave" in todo else ():
        r, c, w = (int(x) for x in wave.split("x"))
        report(f"prefill_chunk_paged[{r}x{c}x{w}p]", prefill_chunk_paged.lower(
            params, cfg, ints(r, c), ints(r, c), pool, ints(r, c), ints(r, w),
            ints(r), page_size=args.page, logits_at=ints(r), state_pool=state,
            state_in=ints(r), snap_at=ints(r, 3), state_out=ints(r, 3),
            moe_stats=moe))
    if "dense" in todo:
        s = args.dense
        kv = sd((len(cfg.attn_layers), 1, 0, *cfg.kv_row[1:]), cfg.dtype)
        report(f"prefill_forward[{s}]", prefill_forward.lower(
            params, cfg, ints(1, s), ints(1, s), kv, kv, ints(1),
            new_lengths=ints(1), snap_at=ints(1, 3), moe_stats=moe))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
