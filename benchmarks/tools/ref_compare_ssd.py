#!/usr/bin/env python3
"""The served path of NVIDIA-Nemotron-3-Nano's one-chip cut (Mamba-2 blocks
whose matrix state lives in state slots, plain GQA 32/2 without a rotary,
un-gated experts over an expert share, one sublayer a block) against the
float32 reference, at published widths, on the chip: what the cell's own
server produced. ``tools/ref_compare_kda.py`` for the other kind of matrix
state: the same requests, the same verdicts.

    python benchmarks/tools/ref_compare_ssd.py \
        --workload nemotron-3-nano-ep8.agent-sessions --seed 53 \
        --out chiprun_out/ref_compare_ssd.json

Starts the cell's server command (the configuration's launcher, unedited)
and sends, each with ``"return_logits": true`` and ``--new`` generated
tokens (the first from the prefill, the rest through ``decode_step``: the
state read from and written back to the row's working slot IN PLACE by
``radixmesh_ssd_decode``):

- ``cold 256``: a dense prefill from a zero state; ``cold 4096``: chunked
  paged prefill (512-token chunks: the 20.4 MiB state rides from chunk to
  chunk through the working slot, ``radixmesh_ssd_chunk`` each);
- ``preamble`` (1 290 tokens, cold; leaves a snapshot at its page-aligned
  cut, 1 280) and ``preamble + 128``: the same tokens and 128 new ones —
  1 280 K/V rows from the radix tree AND the state restored from that
  snapshot, 138 tokens to compute;
- ``short`` / ``short again``: the preamble's first 1 280 tokens alone, a
  page multiple: a token has to be left to compute, so the rows serve
  1 264 and no snapshot lies there — the first visit computes from zero
  and leaves one AT the match (1 264); the second resumes there, one page
  short of the rows the tree holds, and computes the page between again;
- ``turn 1`` / ``turn 2``: a session of 697 tokens, then the same + what
  turn 1 generated + ``--tail`` new tokens: turn 2 resumes at 704 from the
  snapshot turn 1's RELEASE left — a BOUNDARY COPY made by the decode step
  that completed the page, a state no prefill ever computed.

Then the server stops, and only then this process touches JAX: the same
seeded weights are drawn again, moved to the host, and walked through
``benchmarks/reference/nemotron_h.py`` one float32 block at a time — the
recurrence token by token, the convolution as a sum of four taps, the
experts a loop, the same expert share, no cache, no state slots.

Random weights make expert choice a near-tie often, and a bf16 step flips
such ties. So, as for the other sparse configurations: (a) **choices** —
every expert the program chose is among the reference's own top k by ``s +
b`` or misses it by less than ``--margin``; (b) **logits** — with the
program's choices forced, its float32 logits rows against the reference's
as ``max|a-b| / max|b|`` and ``rms(a-b) / rms(b)``, each held to its
tolerance. Two controls have to FAIL (b): the reference with every weight
matrix quantised to int8 per output channel (the precision below the
configuration's bf16 weights), and — on the requests that resumed within a
page of their first logits row — the reference resuming from a ZEROED
state at the position the program resumed at. A third reading is printed
and decides nothing: the reference with a bf16 matrix state (what tells a
state's precision on the chip is ``ops/kernel_check.py::check_ssd``, and
the CPU tests at 1e-4).

Exit code 0 when (a) and (b) pass, the two controls fail, every request
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
# nemotron-3-nano-30b-a3b-bf16-ep8.json "reference_check" has the readings
# and the seeds). Why the program is not exact: bf16 weights, activations,
# K/V rows and convolution inputs (8 bits of mantissa, rounded after every
# projection, gate, norm, expert product and residual add) over 23 blocks of
# one sublayer, bf16 probabilities in the attention kernels' value product;
# float32 matrix state, recurrence, softmax, router, norms and accumulation.
# Readings (my chip runs, PR 53, four seeds x 8 requests): the program 0.0145
# - 0.0194 / 0.0156 - 0.0169 on the two measures, the int8-weights reference
# 0.0403 - 0.0590 / 0.0456 - 0.0492: each limit lies between, with room on both
# sides (the siblings' 0.05 would have passed the int8 control on a measure).
MARGIN = 0.05
TOL_MAX = 0.03
TOL_RMS = 0.03


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=53)
    ap.add_argument("--lengths", default="256,4096")
    ap.add_argument("--preamble", type=int, default=1290)
    ap.add_argument("--extra", type=int, default=128)
    ap.add_argument("--session", type=int, default=697)
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
    lengths = [int(x) // shrink for x in args.lengths.split(",")]
    work = os.path.join(bench.HERE, ".work", "ref_compare")
    os.makedirs(work, exist_ok=True)
    env = child_env()
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    rng = np.random.default_rng([int(args.seed), 53])

    def fresh(n):
        return rng.integers(0, vocab, size=n, dtype=np.int32)

    # (name, prompt or None = the request before + what it said + the tail,
    # the request whose positions it re-uses, where it has to resume)
    plan = [(f"cold {n}", fresh(n), None, 0) for n in lengths]
    n_pre = args.preamble // shrink
    cut = n_pre // page * page  # where the preamble's publish cuts its key
    preamble = fresh(n_pre)
    first = len(plan)
    plan.append((f"preamble {n_pre}", preamble, None, 0))
    plan.append((f"preamble + {args.extra // shrink}",
                 np.concatenate([preamble, fresh(args.extra // shrink)]),
                 first, cut))
    plan.append((f"short {cut}", preamble[:cut], first, 0))
    plan.append(("short again", preamble[:cut], len(plan) - 1, cut - page))
    n1 = args.session // shrink
    plan.append((f"turn 1 {n1}", fresh(n1), None, 0))
    tail = fresh(args.tail)
    plan.append(("turn 2", None, len(plan) - 1,
                 (n1 + args.new - 1) // page * page))

    server = Server(r["launcher"].argv(config, seed=args.seed, profile_dir=None),
                    env, os.path.join(work, "server.log"))
    prompts, replies = [], []
    try:
        base = server.wait_ready(
            1100.0, need_platform=None if args.rehearse_cpu else "tpu")
        for name, prompt, _, _ in plan:
            if prompt is None:  # turn 2: turn 1, what it said, and the tail
                prompt = np.concatenate([
                    prompts[-1], np.asarray(replies[-1]["output_ids"], np.int32),
                    tail])
            prompts.append(prompt)
            replies.append(generate(base, prompt, args.new))
        state = bench.loadgen.get_json(base, "/debug/state")
    finally:
        server.stop()
    device = state["device"]
    bench.note(f"served {len(replies)} requests on {device['platform']} "
               f"(cached: {[rep['cached_tokens'] for rep in replies]}); "
               "the server has stopped, the reference starts")

    import jax  # the chip is free now
    import jax.numpy as jnp

    from benchmarks.reference import nemotron_h as ref
    from radixmesh_tpu.models import get_config, init_params

    cfg = get_config(config["preset"])
    params = jax.device_get(init_params(cfg, jax.random.PRNGKey(args.seed)))
    # the reference reads the source's keys (the share from ``serving``)
    spec = cfg if args.rehearse_cpu else source
    seqs, forced, froms = [], [], []
    for (name, _, earlier, _), p, rep in zip(plan, prompts, replies):
        seq = np.concatenate([p, np.asarray(rep["output_ids"][:-1], np.int32)])
        first = replies[earlier]["runs"] if earlier is not None else []
        seqs.append(seq)
        forced.append(choice_table(len(seq), first, rep["runs"]))
        froms.append(len(p) - 1)
    t0 = time.monotonic()
    exact = ref.forward_many(params, spec, seqs, forced=forced, logits_from=froms)
    lower = ref.forward_many(params, spec, seqs, forced=forced, logits_from=froms,
                             weight_map=w8)
    rough = ref.forward_many(params, spec, seqs, forced=forced, logits_from=froms,
                             state_dtype=jnp.bfloat16)
    resumed = [i for i, rep in enumerate(replies) if rep["cached_tokens"] > 0]
    zeroed = dict(zip(resumed, ref.forward_many(
        params, spec, [seqs[i] for i in resumed],
        forced=[forced[i] for i in resumed],
        logits_from=[froms[i] for i in resumed],
        forget_at=[replies[i]["cached_tokens"] for i in resumed])))
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
    for i, ((name, _, _, want), rep, ex, lo, ro) in enumerate(
            zip(plan, replies, exact, lower, rough)):
        probs, mine = ex["router_scores"], forced[i]  # [S, L, E], [S, L, k]
        short = ref.shortfall(probs, mine)  # 0 = its own
        flipped = (short > 0).any(-1)
        rows.append({
            "request": name, "prompt_tokens": int(len(prompts[i])),
            "cached_tokens": int(rep["cached_tokens"]), "resumes_at": int(want),
            "share_flipped": float(flipped.mean()),
            "worst_shortfall": float(short.max()),
            "greedy_agree": int(sum(
                a == b for a, b in zip(ex["logits"].argmax(-1), rep["output_ids"]))),
            "program": distance(rep["logits"], ex["logits"]),
            "int8_weights": distance(lo["logits"], ex["logits"]),
            "bf16_state": distance(ro["logits"], ex["logits"]),
        })
        if i in zeroed:
            rows[-1]["zeroed_state"] = distance(zeroed[i]["logits"], ex["logits"])
        print(json.dumps(rows[-1]), flush=True)
    st = state.get("state") or {}
    path = (state.get("decode_dispatch") or {}).get("path")
    near = [i for i in resumed
            if len(prompts[i]) - replies[i]["cached_tokens"] <= page]
    verdict = {
        "choices_ok": all(x["worst_shortfall"] < args.margin for x in rows),
        "logits_ok": all(not refused(x["program"]) for x in rows),
        "int8_weights_refused": all(refused(x["int8_weights"]) for x in rows),
        # only where the resume point is within a page of the first logits
        # row: decays down to 0.2 a token forget a state further back
        "zeroed_state_refused": bool(near) and all(
            refused(rows[i]["zeroed_state"]) for i in near),
        "resumed_where_expected": all(
            x["cached_tokens"] == x["resumes_at"] for x in rows),
        "kernels_served": bool(args.rehearse_cpu) or path == "paged",
    }
    result = {"workload": args.workload, "seed": args.seed, "new_tokens": args.new,
              "margin": args.margin, "tol_max": args.tol_max,
              "tol_rms": args.tol_rms, "served_on": device["platform"],
              "reference_on": jax.devices()[0].platform,
              "decode_path": path, "state": st, "ssd": state.get("ssd"),
              "moe": state.get("moe"),
              "kv_bytes_per_token": st.get("kv_bytes_per_token"),
              "memory_peak_bytes": max(
                  [m.get("peak_bytes_in_use") or 0 for m in device["memory"]] or [0]),
              # a reading, not a control (the docstring): the largest of
              # the bf16-state reference's two distances over the requests
              "bf16_state_reads": max(
                  max(x["bf16_state"].values()) for x in rows),
              "requests": rows, **verdict}
    print(json.dumps({k_: v for k_, v in result.items() if k_ != "requests"}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=1)
    return 0 if all(verdict.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
