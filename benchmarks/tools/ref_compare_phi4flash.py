#!/usr/bin/env python3
"""The served path of Phi-4-mini-flash-reasoning (selective state-space
layers, window layers that give their pages back, a cross-decoder on one
layer's K/V) against the float32 reference, at published widths, on the
chip: what the cell's own server produced.

    python benchmarks/tools/ref_compare_phi4flash.py \
        --workload phi-4-mini-flash.reasoning-sessions --seed 41 \
        --out chiprun_out/ref_compare_phi4flash.json

Starts the cell's server command (the configuration's launcher, unedited)
and sends, each with ``"return_logits": true`` and ``--new`` generated
tokens (the first from the prefill, the rest through ``decode_step``):

- ``cold``: prompts of 256 (dense prefill from a zero state), 1 290 and
  4 096 tokens (chunked paged prefill, both past the 512-token window: the
  state rides from chunk to chunk through the working slot, the window
  kernel reads earlier chunks' rows from the window group's pages);
- ``again``: the 1 290 once more — 1 280 rows of the full layer from the
  radix tree AND the state and the 512-token window tail its first visit
  snapshotted there, a 10-token tail to compute (1 290 is no page multiple
  on purpose: ISSUE 41's 1 296 is one, has to leave a token to compute,
  resumes a page earlier and finds no snapshot there — PR 41's first chip
  call read ``cached_tokens`` 0 for it);
- ``turn 1`` / ``turn 2``: a session of 697 tokens, then the same + what
  turn 1 generated + ``--tail`` new tokens: turn 2 resumes at 704 from the
  snapshot turn 1's RELEASE left (a state no prefill ever computed — a
  decode step kept it — and a tail whose last rows decode steps wrote);
- ``long`` / ``after long``: one request of 256 in / ``--long`` (632) out
  WITHOUT the logits hook — the timed path's plain launches: inputs built a
  step ahead, 24 window pages given back while the window slides a whole
  window past the prompt — then its prompt + its output + 1 token with the
  hook: it resumes at 880 from the release snapshot, so its 8 logits rows
  (generated positions 633-640 of the continued sequence) read the state
  632 decode steps left and a window tail only decode steps wrote. (640
  rows of 200 064 float32 logits are 0.5 GB of JSON: the hook is not sent
  with the long request itself; its first 8 generated positions are what
  ``cold 256`` compares.)

Then the server stops, and only then this process touches JAX: the same
seeded weights are drawn again, moved to the host, and walked through
``benchmarks/reference/phi4flash.py`` one float32 layer at a time — every
layer at every position, the scan a ``lax.scan``, no cache, no state.

The float32 logits rows against the reference's as ``max|a-b| / max|b|``
and ``rms(a-b) / rms(b)``, each held to its tolerance. Four controls have
to FAIL at least one of the two: the reference with every weight matrix
quantised to int8 per output channel (the precision below the
configuration's bf16), with ``lambda`` forced to 0, and — on the requests
that resumed — resuming from a ZEROED state, and from a window tail whose
rows are ZEROS, at the position the program resumed at.

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
# phi-4-mini-flash-reasoning-bf16.json "reference_check" has the readings):
# the bf16 program reads 0.037-0.052 (max) / 0.039-0.052 (rms) on every
# path, the int8 reference 0.116-0.169 / 0.116-0.163, lambda = 0 0.67-1.06,
# a zeroed restored state 0.25-1.30 / 0.25-0.83, a zeroed restored tail
# 1.19-1.48 / 1.30-1.34 (PR 41, three seeds, 21 compared requests). Why the program is not exact: bf16
# weights, activations, cache rows and convolution states (8 bits of
# mantissa, rounded after every projection, gate, norm and residual add
# over 32 layers), bf16 probabilities in the attention kernels' value
# product; float32 recurrence, softmax, norms and accumulation. Why half of
# LFM2's readings (0.08-0.11) at nearly its depth: no expert choice to flip,
# and LayerNorm re-centres what RMSNorm lets drift.
TOL_MAX = 0.075
TOL_RMS = 0.075


def generate(base: str, prompt: np.ndarray, n_new: int, logits: bool = True) -> dict:
    body = {"input_ids": [int(t) for t in prompt], "max_tokens": n_new,
            "temperature": 0.0, "return_logits": logits, "timeout": 900}
    req = urllib.request.Request(
        base + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=900) as resp:
        out = json.loads(resp.read())
    return {"output_ids": out["output_ids"], "cached_tokens": out["cached_tokens"],
            "logits": unpack(out["logits"]) if logits else None}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=41)
    ap.add_argument("--lengths", default="256,1290,4096")
    ap.add_argument("--again", type=int, default=1,
                    help="which of --lengths is sent a second time")
    ap.add_argument("--session", type=int, default=697)
    ap.add_argument("--tail", type=int, default=1)
    ap.add_argument("--new", type=int, default=8)
    ap.add_argument("--long", type=int, default=632,
                    help="tokens the long request generates (0: skip it)")
    ap.add_argument("--controls-upto", type=int, default=2048,
                    help="longest sequence the int8 and lambda controls are "
                    "computed for (two more forward passes each)")
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
    rng = np.random.default_rng([int(args.seed), 41])

    def fresh(n):
        return rng.integers(0, vocab, size=n, dtype=np.int32)

    def aligned(n):  # where a prompt of n tokens resumes at best
        return (n - 1) // page * page

    # (name, prompt or None = the request before + what it said + a tail,
    # tokens out, the logits hook, where it has to resume)
    plan = [(f"cold {n}", fresh(n), args.new, True, 0) for n in lengths]
    rep_len = lengths[args.again]
    plan.append((f"again {rep_len}", plan[args.again][1], args.new, True,
                 aligned(rep_len)))
    n1 = args.session // shrink
    plan.append((f"turn 1 {n1}", fresh(n1), args.new, True, 0))
    plan.append(("turn 2", None, args.new, True, aligned(n1 + args.new)))
    n_long, out_long = 256 // shrink, args.long // shrink
    if out_long:
        plan.append((f"long {n_long}+{out_long}", fresh(n_long), out_long, False, 0))
        plan.append(("after long", None, args.new, True, aligned(n_long + out_long)))
    tails = {i: fresh(args.tail) for i, p in enumerate(plan) if p[1] is None}

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

    from benchmarks.reference import phi4flash as ref
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

            def logits(**control):
                return np.asarray(ref.forward(params, spec, seq, at=at, **control))

            exact = logits()
            row["greedy_agree"] = int(sum(
                a == b for a, b in zip(exact.argmax(-1), rep["output_ids"])))
            row["program"] = distance(rep["logits"], exact)
            if len(seq) <= args.controls_upto // shrink:
                row["int8_weights"] = distance(
                    logits(weight_map=ref.int8_weights), exact)
                row["lambda_zero"] = distance(logits(lambda_zero=True), exact)
            if rep["cached_tokens"] > 0:
                at_ = int(rep["cached_tokens"])
                row["zeroed_state"] = distance(logits(forget_state_at=at_), exact)
                row["zeroed_tail"] = distance(logits(zero_tail_before=at_), exact)
        rows.append(row)
        print(json.dumps(row), flush=True)
    bench.note(f"reference: {time.monotonic() - t0:.1f} s on "
               f"{jax.devices()[0].platform}")
    judged = [x for x in rows if "program" in x]
    resumed = [x for x in judged if "zeroed_state" in x]
    path = (state.get("decode_dispatch") or {}).get("path")
    verdict = {
        "logits_ok": all(not refused(x["program"]) for x in judged),
        "int8_weights_refused": all(
            refused(x["int8_weights"]) for x in judged if "int8_weights" in x),
        "lambda_zero_refused": all(
            refused(x["lambda_zero"]) for x in judged if "lambda_zero" in x),
        "zeroed_state_refused": bool(resumed) and all(
            refused(x["zeroed_state"]) for x in resumed),
        "zeroed_tail_refused": bool(resumed) and all(
            refused(x["zeroed_tail"]) for x in resumed),
        "resumed_where_expected": all(
            x["cached_tokens"] == x["resumes_at"] for x in rows),
        "kernels_served": bool(args.rehearse_cpu) or path == "paged",
    }
    groups = (state.get("kv") or {}).get("groups") or {}
    result = {"workload": args.workload, "seed": args.seed, "new_tokens": args.new,
              "tol_max": args.tol_max, "tol_rms": args.tol_rms,
              "served_on": device["platform"],
              "reference_on": jax.devices()[0].platform, "decode_path": path,
              "state": state.get("state"), "window": state.get("window"),
              "kv_groups": groups,
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
