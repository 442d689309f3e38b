#!/usr/bin/env python3
"""The served path of a configuration whose attention kinds are stacks of
their own (window layers on a group of rows with counted pages, full
layers on the pool, a gated output, a half-rotated head, 256 small
experts) against the float32 reference, at published widths, on the chip:
what the cell's own server produced.

    python benchmarks/tools/ref_compare_laguna.py \
        --workload laguna-xs.2-l5.agent-sessions --seed 45 \
        --out chiprun_out/ref_compare_laguna.json

Starts the cell's server command (the configuration's launcher, unedited)
and sends, each with ``"return_logits": true``; the compared ones take
``--new`` generated tokens (the first from the prefill, the rest through
``decode_step`` and BOTH groups of rows):

- ``cold 256``: a dense prefill, nothing cached;
- ``cold 1290``: a chunked paged prefill in chunks of 512, past the window
  of 512: the window kernel reads earlier chunks' rows from the window
  group's pages, the full layers' from the pool;
- ``again 1290``: the same once more — 1 280 rows of the full layers from
  the radix tree AND the 32 window pages its first visit's prompt cut left
  there as a tail, a 10-token tail of its own (1 290 is no page multiple
  on purpose: an aligned prompt has to leave a token to compute and
  resumes a page earlier);
- ``base 1290``: another prompt, ``--long`` (40) tokens out: decode steps
  pass two page boundaries, the release leaves its tail at 1 328;
- ``short``: the first 1 300 tokens of ``base``'s sequence + 7 new: the
  tree's rows match to 1 296, the deepest tail at or before lies at 1 280 —
  ONE PAGE SHORT of the match: 16 tokens are computed again on pages of
  their own;
- ``turn 2``: ``cold 1290`` + what it generated + ``--tail`` new tokens
  (1): resumes at 1 296 from the tail the RELEASE left at the last page
  boundary a decode step passed (pages no prefill wrote).

Then the server stops, and only then this process touches JAX: the same
seeded weights are drawn again, moved to the host, and walked through
``benchmarks/reference/laguna.py`` one float32 layer at a time — the whole
sequence at once, no cache, no group, every one of the 256 experts a loop.

Random weights make expert choice a near-tie often, and a bf16 step flips
such ties. So, as for the other sparse configurations: (a) **choices** —
every expert the program chose is among the reference's own top 8 by ``s +
b`` or misses it by less than ``--margin``; (b) **logits** — with the
program's choices forced, its float32 logits rows against the reference's
as ``max|a-b| / max|b|`` and ``rms(a-b) / rms(b)``, each held to its
tolerance. One control has to FAIL (b): the reference with every weight
matrix quantised to int8 per output channel (the precision below the
configuration's bf16).

Exit code 0 when (a) and (b) pass, the control fails, every request
resumed where it had to, and no attention launch took the jnp path; 1
otherwise.
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

# The limits, each between its two readings on the chip (configs/
# laguna-xs.2-bf16-l5.json "reference_check" has every reading; 18 requests
# over three seeds): the bf16 program reads 0.020-0.025 (max) / 0.020-0.023
# (rms) on every path and its worst chosen expert is 0.006-0.012 short of
# the reference's 8th score; the int8 reference reads 0.061-0.079 /
# 0.062-0.071 and its own top 8 is 0.027-0.034 short. Why the program is
# not exact: bf16 weights, activations and cache rows (8 bits of mantissa,
# rounded after every projection, gate, norm, expert product and residual
# add over 5 layers), bf16 probabilities in the attention kernels' value
# product; float32 accumulation, softmax, router scores and gate. As
# GLM-4.7-Flash's 8 layers (0.02): depth sets the reading, not the path.
MARGIN = 0.02
TOL_MAX = 0.04
TOL_RMS = 0.04


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=45)
    ap.add_argument("--dense", type=int, default=256)
    ap.add_argument("--chunked", type=int, default=1290)
    ap.add_argument("--long", type=int, default=40)
    ap.add_argument("--tail", type=int, default=1)
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
    vocab, page = int(config["vocab"]), int(config["page_size"])
    shrink = 8 if args.rehearse_cpu else 1
    n_dense = args.dense // shrink
    n_chunk = args.chunked // shrink // page * page + 10  # no page multiple
    work = os.path.join(bench.HERE, ".work", "ref_compare")
    os.makedirs(work, exist_ok=True)
    env = child_env()
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    rng = np.random.default_rng([int(args.seed), 45])

    def fresh(n):
        return rng.integers(0, vocab, size=n, dtype=np.int32)

    def aligned(n):  # where a prompt of n tokens resumes at best
        return (n - 1) // page * page

    cut = n_chunk // page * page  # the prompt cut's tail; the match is a page on
    # (name, prompt or a function of (prompts, replies), tokens out, the
    # request whose positions it re-uses, where it has to resume)
    plan = [
        (f"cold {n_dense}", fresh(n_dense), args.new, None, 0),
        (f"cold {n_chunk}", fresh(n_chunk), args.new, None, 0),
        (f"again {n_chunk}", lambda p, o: p[1], args.new, 1, aligned(n_chunk)),
        (f"base {n_chunk}", fresh(n_chunk), args.long, None, 0),
        ("short", lambda p, o: np.concatenate(
            [p[3], o[3]])[:cut + page + 4].tolist() + fresh(7).tolist(),
         args.new, 3, cut),
        ("turn 2", lambda p, o: np.concatenate([p[1], o[1], fresh(args.tail)]),
         args.new, 1, aligned(n_chunk + args.new)),
    ]

    server = Server(r["launcher"].argv(config, seed=args.seed, profile_dir=None),
                    env, os.path.join(work, "server.log"))
    prompts, replies = [], []
    try:
        base = server.wait_ready(
            1100.0, need_platform=None if args.rehearse_cpu else "tpu")
        for name, prompt, n_out, _, _ in plan:
            if callable(prompt):
                prompt = prompt(prompts, [np.asarray(rep["output_ids"], np.int32)
                                          for rep in replies])
            prompts.append(np.asarray(prompt, np.int32))
            t0 = time.monotonic()
            replies.append({**generate(base, prompts[-1], n_out), "n_out": n_out})
            bench.note(f"{name}: {len(prompts[-1])} in, {n_out} out, cached "
                       f"{replies[-1]['cached_tokens']}, {time.monotonic() - t0:.1f} s")
        state = bench.loadgen.get_json(base, "/debug/state")
    finally:
        server.stop()
    device = state["device"]
    bench.note(f"served {len(replies)} requests on {device['platform']} "
               f"(cached: {[rep['cached_tokens'] for rep in replies]}); "
               "the server has stopped, the reference starts")

    import jax  # the chip is free now

    from benchmarks.reference import laguna as ref
    from radixmesh_tpu.models import get_config, init_params

    cfg = get_config(config["preset"])
    params = jax.device_get(init_params(cfg, jax.random.PRNGKey(args.seed)))
    spec = cfg if args.rehearse_cpu else source  # the source's keys
    seqs, forced, froms = [], [], []
    for (name, _, _, earlier, _), p, rep in zip(plan, prompts, replies):
        seq = np.concatenate([p, np.asarray(rep["output_ids"][:-1], np.int32)])
        first = replies[earlier]["runs"] if earlier is not None else []
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

    def refused(d):
        return d["rel_max"] >= args.tol_max or d["rel_rms"] >= args.tol_rms

    k = forced[0].shape[-1]
    rows = []
    for i, ((name, _, _, _, want), rep, ex, lo) in enumerate(
            zip(plan, replies, exact, lower)):
        scores, mine = ex["router_scores"], forced[i]  # [S, L, E], [S, L, k]
        kth = np.sort(scores, axis=-1)[..., -k]  # the reference's k-th largest
        chosen = np.take_along_axis(scores, mine, axis=-1)
        short = np.maximum(kth[..., None] - chosen, 0.0)  # 0 = in its top k
        own = np.argsort(-scores, axis=-1, kind="stable")[..., :k]
        flipped = (np.sort(own, -1) != np.sort(mine, -1)).any(-1)
        # the same measure of the int8 reference's OWN top k
        low = np.argsort(-lo["router_scores"], axis=-1, kind="stable")[..., :k]
        low_short = np.maximum(
            kth[..., None] - np.take_along_axis(scores, low, axis=-1), 0.0)
        rows.append({
            "request": name, "prompt_tokens": int(len(prompts[i])),
            "generated": int(rep["n_out"]),
            "cached_tokens": int(rep["cached_tokens"]), "resumes_at": int(want),
            "share_flipped": float(flipped.mean()),
            "worst_shortfall": float(short.max()),
            "int8_worst_shortfall": float(low_short.max()),
            "greedy_agree": int(sum(
                a == b for a, b in zip(ex["logits"].argmax(-1), rep["output_ids"]))),
            "program": distance(rep["logits"], ex["logits"]),
            "int8_weights": distance(lo["logits"], ex["logits"]),
        })
        print(json.dumps(rows[-1]), flush=True)
    path = (state.get("decode_dispatch") or {}).get("path")
    verdict = {
        "choices_ok": all(x["worst_shortfall"] < args.margin for x in rows),
        "logits_ok": all(not refused(x["program"]) for x in rows),
        "int8_weights_refused": all(refused(x["int8_weights"]) for x in rows),
        "resumed_where_expected": all(
            x["cached_tokens"] == x["resumes_at"] for x in rows),
        "kernels_served": bool(args.rehearse_cpu) or path == "paged",
    }
    result = {"workload": args.workload, "seed": args.seed, "new_tokens": args.new,
              "margin": args.margin, "tol_max": args.tol_max,
              "tol_rms": args.tol_rms, "served_on": device["platform"],
              "reference_on": jax.devices()[0].platform, "decode_path": path,
              "tail": state.get("tail"), "window": state.get("window"),
              "kv_groups": (state.get("kv") or {}).get("groups"),
              "attn": {k_: v for k_, v in (state.get("attn") or {}).items()
                       if k_.endswith("_tokens_read")},
              "memory_peak_bytes": max(
                  [m.get("peak_bytes_in_use") or 0 for m in device["memory"]] or [0]),
              "requests": rows, **verdict}
    print(json.dumps({k_: v for k_, v in result.items() if k_ != "requests"}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=1)
    return 0 if all(verdict.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
