#!/usr/bin/env python3
"""The served path of a configuration with learned sparse attention and an
expert share against the float32 reference, at published widths, on the
chip: what the cell's own server produced at the cell's own shapes.

    python benchmarks/tools/ref_compare_dsa.py \
        --workload glm-5.2-ep16.repo-hotset --seed 33 \
        --out chiprun_out/ref_compare_dsa.json

Starts the cell's server command (the configuration's launcher, unedited)
and sends, each with ``"return_logits": true`` and ``--new`` generated
tokens: a prompt of 256 tokens (dense prefill: every row attended), one of
4 096 cold (chunked prefill: the selection engages past 2 048), a DOCUMENT
of 32 768 tokens cold (which publishes it to the radix tree, rows and index
keys) and the document + a 128-token question (its 32 768 rows AND index
keys come from the tree; the question is a sparse chunk over them). The
first generated token of each comes from the prefill, the rest from
``decode_step`` (index scoring in pages, selection, gathered rows). Then
the server stops, and only then this process touches JAX: the same seeded
weights are drawn again, moved to the host, and walked through
``benchmarks/reference/glm_moe_dsa.py`` one float32 layer at a time —
attention NOT absorbed, the selection as a mask over dense scores, no
cache, the same expert share and vocabulary slice.

Random weights make near-ties, among expert scores and among index scores,
and a bf16 step flips such ties; two right implementations then diverge.
So the comparison has two parts, and prints both:

(a) **choices**, with the program's forced in the reference: every expert
    the program chose AT A COMPARED QUERY is among the reference's own top
    k by ``s + b`` or short of its k-th by less than ``--margin`` (the same
    over all positions is printed, not held: see "NOT forced"); every
    token position the
    program SELECTED, at every compared query (the last prompt position
    where a chunk computed it, and the decode steps) in each full layer,
    is among the reference's own top ``index_topk`` index scores there or
    short of its ``index_topk``-th by less than ``--sel-margin`` standard
    deviations of that query's visible index scores.
(b) **logits**, with those choices forced: the program's float32 logits
    rows against the reference's, ``max|a-b| / max|b|`` and ``rms(a-b) /
    rms(b)`` over the generated positions of each request, each held to
    its tolerance.

NOT forced: the selections of the prompt's own positions (32 768 x 2 sets
of 2 048 would be 0.5 GB a request; the reply carries the compared queries'
only). Where the program's bf16 index scores flipped a near-tie at a
document position, that position attends a few other rows than the
reference's does, its hidden state differs in the layers after (by far
more than rounding, at some positions), its router may choose other
experts — which is why the expert check is held at the compared queries,
whose selections ARE forced — and the compared queries read those rows:
that is inside the readings of (b) on the two document requests.

And two variants that the tolerances have to refuse, each against the exact
reference: every weight matrix quantised to int8 per output channel and
dequantised (the precision below the configuration's bf16), and the
selection replaced by the most recent ``index_topk`` tokens (a sliding
window where the model selects).

Exit code 0 when (a) and (b) pass and both variants fail; 1 otherwise.
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
from benchmarks.tools.ref_compare import choice_table, unpack, w8  # noqa: E402

# The limits, each between its readings on the chip (configs/glm-5.2-*.json
# "reference_check" has the readings and the reasons).
MARGIN = 0.04      # expert choices: as GLM-4.7-Flash (sigmoid scores in (0, 1))
SEL_MARGIN = 1.5   # selected positions: shortfall in std of the query's scores
TOL_MAX = 0.040    # max|a-b|/max|b|
TOL_RMS = 0.038    # rms(a-b)/rms(b)


def generate(base: str, prompt: np.ndarray, n_new: int) -> dict:
    body = {"input_ids": [int(t) for t in prompt], "max_tokens": n_new,
            "temperature": 0.0, "return_logits": True, "timeout": 1800}
    req = urllib.request.Request(
        base + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=1800) as resp:
        out = json.loads(resp.read())
    return {"output_ids": out["output_ids"], "cached_tokens": out["cached_tokens"],
            "logits": unpack(out["logits"]),
            "runs": [(r["start"], unpack(r)) for r in out["expert_ids"]],
            "selected": {int(r["position"]): unpack(r)
                         for r in out.get("index_positions", [])}}


def distance(a, b):
    return {"rel_max": float(np.abs(a - b).max() / np.abs(b).max()),
            "rel_rms": float(np.sqrt(np.mean((a - b) ** 2))
                             / np.sqrt(np.mean(b ** 2)))}


def selection_row(rep: dict, ex: dict, topk: int) -> dict:
    """Part (a) for the selected positions of one request: per full layer,
    the worst shortfall of a position the program selected below the
    reference's own ``topk``-th score at that query (in standard deviations
    of the query's visible scores), the share of the program's set that
    is in the reference's own — and, as the reading that has to fail, the
    same shortfall of the most recent ``topk`` positions."""
    n_full = len(ex["index_scores"])
    worst, recent = [0.0] * n_full, [0.0] * n_full
    common, sizes, flipped, sets = [[] for _ in range(n_full)], [], 0, 0
    for w, position in enumerate(sorted(rep["selected"])):
        for layer, mine in enumerate(rep["selected"][position]):
            mine = mine[mine >= 0]
            scores = ex["index_scores"][layer][w][:position + 1]
            k = min(topk, position + 1)
            kth, std = np.sort(scores)[-k], max(float(scores.std()), 1e-30)
            own = set(np.argsort(-scores, kind="stable")[:k].tolist())
            worst[layer] = max(worst[layer], float(
                np.maximum(kth - scores[mine], 0.0).max() / std))
            recent[layer] = max(recent[layer], float(
                np.maximum(kth - scores[-k:], 0.0).max() / std))
            common[layer].append(len(own & set(mine.tolist())) / k)
            flipped += own != set(mine.tolist())
            sets += 1
            sizes.append(len(mine))
    return {"queries_compared": len(rep["selected"]), "sets": sets,
            "sets_flipped": flipped,
            "selected_shortfall_std_by_full_layer": worst,
            "worst_selected_shortfall_std": max(worst),
            "recent_rows_shortfall_std": max(recent),
            "share_of_set_in_the_references_own_by_full_layer": [
                float(np.mean(c)) if c else None for c in common],
            "rows_selected_min_max": [min(sizes), max(sizes)] if sizes else None}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=33)
    ap.add_argument("--lengths", default="256,4096", help="prompts sent once, cold")
    ap.add_argument("--document", type=int, default=32768)
    ap.add_argument("--question", type=int, default=128)
    ap.add_argument("--new", type=int, default=8)
    ap.add_argument("--margin", type=float, default=MARGIN)
    ap.add_argument("--sel-margin", type=float, default=SEL_MARGIN)
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
    shrink = 32 if args.rehearse_cpu else 1
    lengths = [int(x) // shrink for x in args.lengths.split(",")]
    work = os.path.join(bench.HERE, ".work", "ref_compare")
    os.makedirs(work, exist_ok=True)
    env = child_env()
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    rng = np.random.default_rng([int(args.seed), 33])
    prompts = [rng.integers(0, vocab, size=n, dtype=np.int32) for n in lengths]
    prompts.append(rng.integers(0, vocab, size=args.document // shrink, dtype=np.int32))
    asked = len(prompts)  # the document again, with a question behind it
    prompts.append(np.concatenate([prompts[-1], rng.integers(
        0, vocab, size=max(args.question // shrink, 2), dtype=np.int32)]))
    server = Server(r["launcher"].argv(config, seed=args.seed, profile_dir=None),
                    env, os.path.join(work, "server.log"))
    try:
        base = server.wait_ready(
            1100.0, need_platform=None if args.rehearse_cpu else "tpu")
        replies = [generate(base, p, args.new) for p in prompts]
        state = bench.loadgen.get_json(base, "/debug/state")
    finally:
        server.stop()
    device = state["device"]
    bench.note(f"served {len(replies)} requests on {device['platform']} "
               f"(cached: {[rep['cached_tokens'] for rep in replies]}; queries "
               f"with a selection: {[len(rep['selected']) for rep in replies]}); "
               "the server has stopped, the reference starts")

    import jax  # the chip is free now

    from benchmarks.reference import glm_moe_dsa as ref
    from radixmesh_tpu.models import get_config, init_params

    cfg = get_config(config["preset"])
    params = jax.device_get(init_params(cfg, jax.random.PRNGKey(args.seed)))
    spec = cfg if args.rehearse_cpu else source
    topk = cfg.index_topk
    seqs, forced, froms, watch, sel = [], [], [], [], []
    for i, (p, rep) in enumerate(zip(prompts, replies)):
        seq = np.concatenate([p, np.asarray(rep["output_ids"][:-1], np.int32)])
        # the document's positions were computed by the request before
        first = replies[asked - 1]["runs"] if i == asked else []
        seqs.append(seq)
        forced.append(choice_table(len(seq), first, rep["runs"]))
        froms.append(len(p) - 1)
        watch.append(sorted(rep["selected"]))
        sel.append(rep["selected"] or None)
    t0 = time.monotonic()
    common = dict(forced=forced, logits_from=froms)
    exact = ref.forward_many(params, spec, seqs, forced_sel=sel, watch=watch, **common)
    bench.note(f"reference (choices forced): {time.monotonic() - t0:.1f} s on "
               f"{jax.devices()[0].platform}")
    lower = ref.forward_many(params, spec, seqs, forced_sel=sel, watch=watch,
                             weight_map=w8, **common)
    recent = ref.forward_many(params, spec, seqs, selection="recent", **common)
    bench.note(f"reference, three passes: {time.monotonic() - t0:.1f} s")

    k = forced[0].shape[-1]
    rows = []
    for i, (rep, ex, lo, rc) in enumerate(zip(replies, exact, lower, recent)):
        probs, mine = ex["router_scores"], forced[i]  # [S, L, E], [S, L, k]
        kth = np.sort(probs, axis=-1)[..., -k]
        chosen = np.take_along_axis(probs, mine, axis=-1)
        short = np.maximum(kth[..., None] - chosen, 0.0)
        own = np.argsort(-probs, axis=-1, kind="stable")[..., :k]
        flipped = (np.sort(own, -1) != np.sort(mine, -1)).any(-1)
        at = list(range(froms[i], len(seqs[i])))  # the compared queries
        rows.append({
            "prompt_tokens": int(len(prompts[i])),
            "cached_tokens": int(rep["cached_tokens"]),
            "pairs": int(flipped.size), "share_flipped": float(flipped.mean()),
            "worst_shortfall_all_positions": float(short.max()),
            "worst_shortfall": float(short[at].max()),
            "share_flipped_compared": float(flipped[at].mean()),
            **selection_row(rep, ex, topk),
            "greedy_agree": int(sum(
                a == b for a, b in zip(ex["logits"].argmax(-1), rep["output_ids"]))),
            "program": distance(rep["logits"], ex["logits"]),
            "int8_weights": distance(lo["logits"], ex["logits"]),
            "recent_rows_instead_of_selected": distance(rc["logits"], ex["logits"]),
        })
        print(json.dumps(rows[-1]), flush=True)

    def refused(key, only=None):
        picked = [x for x in rows if only is None or only(x)]
        return bool(picked) and all(
            x[key]["rel_max"] >= args.tol_max or x[key]["rel_rms"] >= args.tol_rms
            for x in picked)

    verdict = {
        "choices_ok": all(x["worst_shortfall"] < args.margin for x in rows),
        "selections_ok": all(
            x["worst_selected_shortfall_std"] < args.sel_margin for x in rows),
        "recent_rows_selection_refused": all(
            x["recent_rows_shortfall_std"] >= args.sel_margin
            for x in rows if x["prompt_tokens"] > topk),
        "logits_ok": all(x["program"]["rel_max"] < args.tol_max
                         and x["program"]["rel_rms"] < args.tol_rms for x in rows),
        "int8_weights_refused": refused("int8_weights"),
        # a recent window IS the selection while the context fits in it
        "recent_rows_refused": refused(
            "recent_rows_instead_of_selected",
            lambda x: x["prompt_tokens"] > topk),
    }
    result = {"workload": args.workload, "seed": args.seed, "new_tokens": args.new,
              "margin": args.margin, "sel_margin": args.sel_margin,
              "tol_max": args.tol_max, "tol_rms": args.tol_rms,
              "served_on": device["platform"],
              "memory_peak_bytes": max([m.get("peak_bytes_in_use") or 0
                                        for m in device["memory"]] or [0]),
              "attn": state.get("attn"), "kv": state.get("kv"),
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
