#!/usr/bin/env python3
"""The served path of AI21-Jamba2-3B (Mamba-1 layers with normed delta / B /
C beside two multi-query attention layers, served at a page of 128 to
131 072 positions) against the float32 reference, at published widths, on
the chip: what the cell's own server produced.

    python benchmarks/tools/ref_compare_jamba.py \
        --workload jamba2-3b.long-doc-sessions --seed 55 \
        --out chiprun_out/ref_compare_jamba.json

Starts the cell's server command (the configuration's launcher, unedited)
and sends, each with ``"return_logits": true`` and ``--new`` generated
tokens (the first from the prefill, the rest through ``decode_step``):

- ``cold``: prompts of 256 (dense prefill from a zero state), 4 096 and
  32 768 tokens (chunked paged prefill: the 8.9 MiB state rides from chunk
  to chunk through the working slot — 64 chunks of 512 for the document —
  and the chunk kernel attends every earlier page of the two attention
  layers);
- ``again``: the 32 768-token document once more + ``--tail`` (40) new
  tokens — its 256 pages from the radix tree AND the snapshot its first
  visit left at its page-aligned cut (32 768: the document is whole pages),
  the tail to compute;
- ``turn 1`` / ``turn 2``: a session of 697 tokens, then the same + what
  turn 1 generated + a token: turn 2 resumes at 640 from the snapshot turn
  1's RELEASE left (a state no prefill ever computed — the decode step that
  completed the page kept it);
- ``long`` / ``after long``: one request of 256 in / ``--long`` (632) out
  WITHOUT the logits hook — the timed path's plain launches — then its
  prompt + its output + 1 token with the hook: it resumes at 768 from the
  release snapshot, so its logits rows read the state 632 decode steps left;
- ``doc`` / ``doc turn``: a 98 304-token document PUBLISHED as the cell's
  warm-up publishes one (2 tokens out, no hook), then one turn on it —
  the document + ``--question`` (300) new tokens: it resumes at 98 304 from
  the publish's snapshot, prefills the question against 768 pages and
  decodes at the cell's longest contexts, a page table of 1 024 entries.

Then the server stops, and only then this process touches JAX: the same
seeded weights are drawn again, moved to the host, and walked through
``benchmarks/reference/jamba.py`` one float32 layer at a time — every layer
at every position, the scan a ``lax.scan``, no cache, no state.

The float32 logits rows against the reference's as ``max|a-b| / max|b|``
and ``rms(a-b) / rms(b)``, each held to its tolerance. Controls have to
FAIL at least one of the two (on sequences up to ``--controls-upto``: each
is another forward pass): the reference with the recurrent state held in
bf16 between positions (the precision below the configuration's float32
state), with every weight matrix quantised to int8 per output channel (the
precision below its bf16 weights), and — on the requests that resumed —
resuming from a ZEROED state at the position the program resumed at.

Exit code 0 when the logits pass, every control fails, every request
resumed where it had to, and no attention launch took the jnp path; 1
otherwise.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench  # noqa: E402
from benchmarks.harness import registry  # noqa: E402
from benchmarks.harness.server import Server, child_env  # noqa: E402
from benchmarks.tools.ref_compare import unpack  # noqa: E402

# The limits, each between its two readings on the chip (configs/
# jamba2-3b-bf16.json "reference_check" has the readings): the bf16 program
# reads 0.053-0.075 (max) / 0.053-0.062 (rms) on every path, the 98 304-token
# turn among them (0.060-0.061 / 0.054-0.056: nothing grows with the
# context), the int8 reference 0.172-0.222 / 0.179-0.201, a zeroed restored
# state 0.44-0.86 / 0.40-0.82, a bf16 state 0.03-0.04 under 1 k tokens and
# 0.09 at 4 096 (it grows with the sequence; PR 55, seeds 55 and 2718281855,
# calls 1 and 2); 0.11 is the geometric middle of the program's worst and
# the int8 control's least. Why the program is not exact: bf16 weights, activations, K/V
# and convolution inputs (8 bits of mantissa, rounded after every
# projection, gate, norm and residual add over 28 layers), bf16 delta / B /
# C into their norms, bf16 probabilities in the attention kernels' value
# product; float32 recurrence, state, softmax, norms and accumulation. Why
# above the Phi-4 configuration's 0.037-0.052 on the same Mamba operator:
# RMSNorm lets drift what LayerNorm re-centres (as LFM2's 0.08-0.11 did).
TOL_MAX = 0.11
TOL_RMS = 0.11


def generate(base: str, prompt: np.ndarray, n_new: int, logits: bool = True) -> dict:
    body = {"input_ids": [int(t) for t in prompt], "max_tokens": n_new,
            "temperature": 0.0, "return_logits": logits, "timeout": 1700}
    req = urllib.request.Request(
        base + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=1700) as resp:
        out = json.loads(resp.read())
    return {"output_ids": out["output_ids"], "cached_tokens": out["cached_tokens"],
            "logits": unpack(out["logits"]) if logits else None}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=55)
    ap.add_argument("--lengths", default="256,4096,32768")
    ap.add_argument("--tail", type=int, default=40,
                    help="new tokens behind the last of --lengths, sent again")
    ap.add_argument("--session", type=int, default=697)
    ap.add_argument("--new", type=int, default=8)
    ap.add_argument("--long", type=int, default=632,
                    help="tokens the long request generates (0: skip it)")
    ap.add_argument("--doc", type=int, default=98304,
                    help="the published document's tokens (0: skip it)")
    ap.add_argument("--question", type=int, default=300)
    ap.add_argument("--controls-upto", type=int, default=4200,
                    help="longest sequence the bf16-state and int8 controls "
                    "are computed for (a forward pass more each)")
    ap.add_argument("--zeroed-upto", type=int, default=33000,
                    help="longest sequence the zeroed-state control is computed for")
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
    shrink = 32 if args.rehearse_cpu else 1
    lengths = [max(40, int(x) // shrink) for x in args.lengths.split(",")]
    work = os.path.join(bench.HERE, ".work", "ref_compare")
    os.makedirs(work, exist_ok=True)
    env = child_env()
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    rng = np.random.default_rng([int(args.seed), 55])

    def fresh(n):
        return rng.integers(0, vocab, size=n, dtype=np.int32)

    def aligned(n):  # where a prompt of n tokens sent again resumes at best
        return (n - 1) // page * page

    def cut(n):  # where a prompt of n tokens leaves its snapshot
        return n // page * page

    # (name, prompt | None = the request before + what it said + a tail |
    # ("again", i, tail) = request i's prompt + a tail; tokens out, the
    # logits hook, where it has to resume)
    plan = [(f"cold {n}", fresh(n), args.new, True, 0) for n in lengths]
    plan.append((f"again {lengths[-1]}+{args.tail}",
                 ("again", len(lengths) - 1, args.tail), args.new, True,
                 cut(lengths[-1])))
    n1 = max(40, args.session // shrink)
    plan.append((f"turn 1 {n1}", fresh(n1), args.new, True, 0))
    plan.append(("turn 2", None, args.new, True, aligned(n1 + args.new)))
    n_long, out_long = max(40, 256 // shrink), args.long // shrink
    if out_long:
        plan.append((f"long {n_long}+{out_long}", fresh(n_long), out_long, False, 0))
        plan.append(("after long", None, args.new, True, aligned(n_long + out_long)))
    n_doc = args.doc // shrink
    if n_doc:
        plan.append((f"doc {n_doc}", fresh(n_doc), 2, False, 0))
        n_q = max(8, args.question // shrink)
        plan.append((f"doc turn +{n_q}", ("again", len(plan) - 1, n_q),
                     args.new, True, cut(n_doc)))
    tails = {i: fresh(1) for i, p in enumerate(plan) if p[1] is None}

    server = Server(r["launcher"].argv(config, seed=args.seed, profile_dir=None),
                    env, os.path.join(work, "server.log"))
    prompts, replies = [], []
    try:
        base = server.wait_ready(
            1100.0, need_platform=None if args.rehearse_cpu else "tpu")
        for i, (name, prompt, n_out, hook, _) in enumerate(plan):
            if prompt is None:
                prompt = np.concatenate([
                    prompts[-1], np.asarray(replies[-1]["output_ids"], np.int32),
                    tails[i]])
            elif isinstance(prompt, tuple):
                prompt = np.concatenate([prompts[prompt[1]], fresh(prompt[2])])
            prompts.append(prompt)
            t0 = time.monotonic()
            replies.append(generate(base, prompt, n_out, hook))
            bench.note(f"{name}: {len(prompt)} in, {n_out} out, cached "
                       f"{replies[-1]['cached_tokens']}, {time.monotonic() - t0:.1f} s")
        state = bench.loadgen.get_json(base, "/debug/state")
    finally:
        server.stop()
    device = state["device"]
    bench.note(f"served {len(replies)} requests on {device['platform']} "
               f"(cached: {[rep['cached_tokens'] for rep in replies]}); "
               "the server has stopped, the reference starts")

    import jax  # the chip is free now
    import jax.numpy as jnp

    from benchmarks.reference import jamba as ref
    from radixmesh_tpu.models import get_config, init_params

    cfg = get_config(config["preset"])
    params = jax.device_get(init_params(cfg, jax.random.PRNGKey(args.seed)))
    spec = cfg if args.rehearse_cpu else source  # the source's keys
    t0 = time.monotonic()

    def distance(a, b):
        d = ref.distance(a, b)
        return {"rel_max": d[0], "rel_rms": d[1]}

    def refused(d):
        return d["rel_max"] >= args.tol_max or d["rel_rms"] >= args.tol_rms

    rows = []
    for (name, _, n_out, hook, want), p, rep in zip(plan, prompts, replies):
        row = {"request": name, "prompt_tokens": int(len(p)),
               "cached_tokens": int(rep["cached_tokens"]), "resumes_at": int(want)}
        if hook:
            seq = np.concatenate([p, np.asarray(rep["output_ids"][:-1], np.int32)])
            at = np.arange(len(p) - 1, len(seq))
            t1 = time.monotonic()

            def logits(**control):
                return np.asarray(ref.forward(params, spec, seq, at=at, **control))

            exact = logits()
            row["reference_s"] = round(time.monotonic() - t1, 1)
            row["greedy_agree"] = int(sum(
                a == b for a, b in zip(exact.argmax(-1), rep["output_ids"])))
            row["program"] = distance(rep["logits"], exact)
            if len(seq) <= args.controls_upto // shrink:
                row["bf16_state"] = distance(
                    logits(state_dtype=jnp.bfloat16), exact)
                row["int8_weights"] = distance(
                    logits(weight_map=ref.int8_weights), exact)
            if rep["cached_tokens"] > 0 and len(seq) <= args.zeroed_upto:
                row["zeroed_state"] = distance(
                    logits(forget_state_at=int(rep["cached_tokens"])), exact)
        rows.append(row)
        print(json.dumps(row), flush=True)
    bench.note(f"reference: {time.monotonic() - t0:.1f} s on "
               f"{jax.devices()[0].platform}")
    judged = [x for x in rows if "program" in x]
    resumed = [x for x in judged if "zeroed_state" in x]
    path = (state.get("decode_dispatch") or {}).get("path")
    verdict = {
        "logits_ok": all(not refused(x["program"]) for x in judged),
        "lower_precision_refused": all(
            refused(x["bf16_state"]) or refused(x["int8_weights"])
            for x in judged if "bf16_state" in x),
        "zeroed_state_refused": bool(resumed) and all(
            refused(x["zeroed_state"]) for x in resumed),
        "resumed_where_expected": all(
            x["cached_tokens"] == x["resumes_at"] for x in rows),
        "kernels_served": bool(args.rehearse_cpu) or path == "paged",
    }
    result = {"workload": args.workload, "seed": args.seed, "new_tokens": args.new,
              "tol_max": args.tol_max, "tol_rms": args.tol_rms,
              "served_on": device["platform"],
              "reference_on": jax.devices()[0].platform, "decode_path": path,
              "state": state.get("state"), "attn": state.get("attn"),
              "pool": state.get("pool"),
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
