#!/usr/bin/env python3
"""The served path of a latent-attention (MLA) configuration against the
float32 reference, at published widths, on the chip: what the cell's own
server produced at the cell's own shapes.

    python benchmarks/tools/ref_compare_glm.py \
        --workload glm-4.7-flash-l8.docs-hotset --seed 31 \
        --out chiprun_out/ref_compare_glm.json

Starts the cell's server command (the configuration's launcher, unedited)
and sends, each with ``"return_logits": true`` and ``--new`` generated
tokens: a prompt of 256 tokens (dense prefill, ``prefill_forward``), one of
4 096 cold (chunked prefill: ``radixmesh_mla_chunk`` over the rows earlier
chunks wrote), a DOCUMENT of 16 384 tokens (which publishes it to the
radix tree) and the document + a 128-token question (its 16 384 rows come
from the tree and are read where they lie, in pages; the question is the
tail). The first generated token of each comes from the prefill, the rest
from ``decode_step`` through the paged latent cache
(``radixmesh_mla_decode``). Then the server stops, and only then this
process touches JAX: the same seeded weights are drawn again
(``init_params(cfg, PRNGKey(seed))``, as ``serve`` draws them), moved to
the host, and walked through ``benchmarks/reference/glm4_moe_lite.py`` one
float32 layer at a time — attention NOT absorbed, K and V derived for
every token, no cache.

Random weights make expert choice a near-tie often (top-4 of 64 sigmoid
scores), and a bf16 step flips such ties; two right implementations then
diverge. So the comparison has two parts, and prints both:

(a) **choices**: with the program's choices forced (so that the
    reference's hidden states follow the program's), every expert the
    program chose at every (position, sparse layer) is among the
    reference's own top k by ``s + b`` there, or misses it by less than
    ``--margin`` in the reference's float32 selection scores. The share of
    pairs whose chosen SET differs is printed.
(b) **logits**: with the choices forced, the program's float32 logits rows
    against the reference's, as ``max|a-b| / max|b|`` and as ``rms(a-b) /
    rms(b)`` over the generated positions of each request, each held to its
    tolerance (``--tol-max``, ``--tol-rms``).

And the lower precision that the tolerances have to refuse: the same
reference with every weight matrix quantised to int8 per output channel
and dequantised (``ops/wquant.py``'s W8A16, the precision below the bf16
the configuration states) must FAIL (b) against the float32 reference.

Exit code 0 when (a) and (b) pass and the int8 variant fails; 1 otherwise.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench  # noqa: E402
from benchmarks.harness import registry  # noqa: E402
from benchmarks.harness.server import Server, child_env  # noqa: E402
from benchmarks.tools.ref_compare import choice_table, generate, w8  # noqa: E402


# The limits, each between its two readings on the chip (TPU v5 lite, PR 31,
# 16 requests over 3 seeds and two draws of the selection bias;
# configs/glm-4.7-flash-*.json "reference_check"): what the bf16 program
# gives, and what the same reference gives with int8 per-channel weights —
# the precision below the one the configuration states, which has to come
# out as NOT correct. Why the program is not exact: bf16 weights and
# activations (8 bits of mantissa, rounded after every projection, norm,
# the absorbed query and the un-absorbed output, every expert product and
# residual add over 8 layers), bf16 probabilities in the attention kernels'
# value product, float32 accumulation, softmax and router.
MARGIN = 0.04    # worst shortfall read 0.0177 (scores in (0, 1)): near-ties only
TOL_MAX = 0.040  # max|a-b|/max|b|: program 0.0197-0.0245, int8 weights 0.0638-0.0855
TOL_RMS = 0.037  # rms(a-b)/rms(b): program 0.0182-0.0214, int8 weights 0.0652-0.0745


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=27)
    ap.add_argument("--lengths", default="256,4096",
                    help="prompts sent once, cold")
    ap.add_argument("--document", type=int, default=16384,
                    help="tokens of the document that is published, then asked")
    ap.add_argument("--question", type=int, default=128)
    ap.add_argument("--new", type=int, default=8)
    ap.add_argument("--margin", type=float, default=MARGIN)
    ap.add_argument("--tol-max", type=float, default=TOL_MAX)
    ap.add_argument("--tol-rms", type=float, default=TOL_RMS)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    r = registry.resolve(args.workload)
    source = r["config"]  # the source's keys, for the reference
    if args.rehearse_cpu:
        r = bench.rehearsal_view(r)
    config = r["config"]
    vocab = int(config["vocab"])
    lengths = [int(x) // (32 if args.rehearse_cpu else 1)
               for x in args.lengths.split(",")]
    work = os.path.join(bench.HERE, ".work", "ref_compare")
    os.makedirs(work, exist_ok=True)
    env = child_env()
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    rng = np.random.default_rng([int(args.seed), 27])
    prompts = [rng.integers(0, vocab, size=n, dtype=np.int32) for n in lengths]
    shrink = 32 if args.rehearse_cpu else 1
    prompts.append(rng.integers(0, vocab, size=args.document // shrink,
                                dtype=np.int32))
    asked = len(prompts)  # the document again, with a question behind it
    prompts.append(np.concatenate([prompts[-1], rng.integers(
        0, vocab, size=max(args.question // shrink, 2), dtype=np.int32)]))
    server = Server(r["launcher"].argv(config, seed=args.seed, profile_dir=None),
                    env, os.path.join(work, "server.log"))
    try:
        base = server.wait_ready(
            1100.0, need_platform=None if args.rehearse_cpu else "tpu")
        replies = [generate(base, p, args.new) for p in prompts]
        device = bench.loadgen.get_json(base, "/debug/state")["device"]
    finally:
        server.stop()
    bench.note(f"served {len(replies)} requests on {device['platform']} "
               f"(cached: {[rep['cached_tokens'] for rep in replies]}); "
               "the server has stopped, the reference starts")

    import jax  # the chip is free now
    import jax.numpy as jnp

    from benchmarks.reference import glm4_moe_lite as ref
    from radixmesh_tpu.models import get_config, init_params

    cfg = get_config(config["preset"])
    params = jax.device_get(init_params(cfg, jax.random.PRNGKey(args.seed)))
    spec = cfg if args.rehearse_cpu else source
    seqs, forced, froms = [], [], []
    for i, (p, rep) in enumerate(zip(prompts, replies)):
        seq = np.concatenate([p, np.asarray(rep["output_ids"][:-1], np.int32)])
        # the document's positions were computed by the request before
        first = replies[asked - 1]["runs"] if i == asked else []
        seqs.append(seq)
        forced.append(choice_table(len(seq), first, rep["runs"]))
        froms.append(len(p) - 1)
    t0 = time.monotonic()
    exact = ref.forward_many(params, spec, seqs, forced=forced, logits_from=froms)
    lower = ref.forward_many(params, spec, seqs, forced=forced, logits_from=froms,
                             weight_map=w8)
    bench.note(f"reference: {time.monotonic() - t0:.1f} s on "
               f"{jax.devices()[0].platform}")

    def distance(a, b):
        return {"rel_max": float(np.abs(a - b).max() / np.abs(b).max()),
                "rel_rms": float(np.sqrt(np.mean((a - b) ** 2))
                                 / np.sqrt(np.mean(b ** 2)))}

    k = forced[0].shape[-1]
    rows = []
    for i, (rep, ex, lo) in enumerate(zip(replies, exact, lower)):
        probs, mine = ex["router_scores"], forced[i]  # [S, L, E], [S, L, k]
        kth = np.sort(probs, axis=-1)[..., -k]  # the reference's k-th largest
        chosen = np.take_along_axis(probs, mine, axis=-1)
        short = np.maximum(kth[..., None] - chosen, 0.0)  # 0 = in its top k
        own = np.argsort(-probs, axis=-1, kind="stable")[..., :k]
        flipped = (np.sort(own, -1) != np.sort(mine, -1)).any(-1)
        rows.append({
            "prompt_tokens": int(len(prompts[i])),
            "cached_tokens": int(rep["cached_tokens"]),
            "pairs": int(flipped.size), "pairs_flipped": int(flipped.sum()),
            "share_flipped": float(flipped.mean()),
            "worst_shortfall": float(short.max()),
            "greedy_agree": int(sum(
                a == b for a, b in zip(ex["logits"].argmax(-1), rep["output_ids"]))),
            "program": distance(rep["logits"], ex["logits"]),
            "int8_weights": distance(lo["logits"], ex["logits"]),
        })
        print(json.dumps(rows[-1]), flush=True)
    verdict = {
        "choices_ok": all(x["worst_shortfall"] < args.margin for x in rows),
        "logits_ok": all(x["program"]["rel_max"] < args.tol_max
                         and x["program"]["rel_rms"] < args.tol_rms for x in rows),
        "int8_weights_refused": all(
            x["int8_weights"]["rel_max"] >= args.tol_max
            or x["int8_weights"]["rel_rms"] >= args.tol_rms for x in rows),
    }
    result = {"workload": args.workload, "seed": args.seed, "new_tokens": args.new,
              "margin": args.margin, "tol_max": args.tol_max,
              "tol_rms": args.tol_rms, "served_on": device["platform"],
              "reference_on": jax.devices()[0].platform, "requests": rows,
              **verdict}
    print(json.dumps({k_: v for k_, v in result.items() if k_ != "requests"}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=1)
    return 0 if all(verdict.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
