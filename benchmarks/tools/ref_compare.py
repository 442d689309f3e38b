#!/usr/bin/env python3
"""The served path against the float32 reference, at published widths, on
the chip: what the cell's own server produced at the cell's own lengths.

    python benchmarks/tools/ref_compare.py \
        --workload mellum2-12b-l12.batch-longtail --seed 27 \
        --out chiprun_out/ref_compare.json

Starts the cell's server command (the configuration's launcher, unedited),
sends one prompt of each of ``--lengths`` (256: dense prefill; 1280 and
4096: chunked paged prefill across the window) and the second of them
AGAIN (its prefix then comes from the radix tree and the tail through the
dense prefill: window layers read pages another request wrote), each with
``"return_logits": true`` and ``--new`` generated tokens, stops the
server, and only then touches JAX: the same seeded weights are drawn
again (``init_params(cfg, PRNGKey(seed))``, as ``serve`` draws them),
moved to the host, and walked through ``benchmarks/reference/mellum2.py``
one float32 layer at a time.

Random weights make expert choice a near-tie often, and a bf16 step flips
such ties; two right implementations then diverge. So the comparison has
two parts, and prints both:

(a) **choices**: with the program's choices forced (so that the
    reference's hidden states follow the program's), every expert the
    program chose at every (position, layer) is among the reference's own
    top k there, or misses it by less than ``--margin`` in the reference's
    float32 probabilities. The share of (position, layer) pairs whose
    chosen SET differs is printed.
(b) **logits**: with the choices forced, the program's float32 logits rows
    (the first from the prefill, the rest from ``decode_step`` through the
    paged cache) against the reference's, as ``max|a-b| / max|b|`` and as
    ``rms(a-b) / rms(b)`` over the generated positions of each request,
    each held to its tolerance (``--tol-max``, ``--tol-rms``).

And the lower precision that the tolerances have to refuse: the same
reference with every weight matrix quantised to int8 per output channel
and dequantised (``ops/wquant.py``'s W8A16, the precision below the bf16
the configuration states) must FAIL (b) against the float32 reference.

Exit code 0 when (a) and (b) pass and the int8 variant fails; 1 otherwise.
"""
from __future__ import annotations

import argparse
import base64
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


# The limits, each between its two readings on the chip (TPU v5 lite, PR 27,
# 16 requests over 4 seeds; configs/mellum2-*.json "reference_check"):
# what the bf16 program gives, and what the same reference gives with
# int8 per-channel weights — the precision below the one the configuration
# states, which has to come out as NOT correct. Why the program is not
# exact: bf16 weights and activations (8 bits of mantissa, rounded after
# every projection, norm and residual add over 12 layers), float32
# accumulation, softmax and router.
MARGIN = 0.004   # worst shortfall read 0.0012: near-ties only
TOL_MAX = 0.016  # max|a-b|/max|b|: program 0.0073-0.0085, int8 weights 0.031-0.046
TOL_RMS = 0.015  # rms(a-b)/rms(b): program 0.0075-0.0078, int8 weights 0.032-0.039


def unpack(d: dict) -> np.ndarray:
    return np.frombuffer(base64.b64decode(d["b64"]), d["dtype"]).reshape(d["shape"])


def generate(base: str, prompt: np.ndarray, n_new: int) -> dict:
    body = {"input_ids": [int(t) for t in prompt], "max_tokens": n_new,
            "temperature": 0.0, "return_logits": True, "timeout": 900}
    req = urllib.request.Request(
        base + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=900) as resp:
        out = json.loads(resp.read())
    return {"output_ids": out["output_ids"], "cached_tokens": out["cached_tokens"],
            "logits": unpack(out["logits"]),
            "runs": [(r["start"], unpack(r)) for r in out["expert_ids"]]}


def choice_table(n: int, runs_first, runs=None) -> np.ndarray:
    """[n, L, k] from the runs a reply carried; a cached prefix's
    positions come from the request that computed them (``runs_first``)."""
    table = None
    for start, ids in list(runs_first) + list(runs or []):
        if table is None:
            table = np.full((n, *ids.shape[1:]), -1, dtype=np.int32)
        m = min(len(ids), n - start)
        table[start:start + m] = ids[:m]
    assert table is not None and (table >= 0).all(), "a position has no choices"
    return table


def w8(name: str, w):
    """int8 per output channel and back: ``ops/wquant.py``'s scheme."""
    import jax.numpy as jnp

    if w.ndim < 2:
        return w
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / jnp.maximum(scale, 1e-30)).clip(-127, 127) * scale


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=27)
    ap.add_argument("--lengths", default="256,1280,4096")
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
    server = Server(r["launcher"].argv(config, seed=args.seed, profile_dir=None),
                    env, os.path.join(work, "server.log"))
    try:
        base = server.wait_ready(
            1100.0, need_platform=None if args.rehearse_cpu else "tpu")
        replies = [generate(base, p, args.new) for p in prompts]
        again = min(1, len(prompts) - 1)
        replies.append(generate(base, prompts[again], args.new))
        prompts.append(prompts[again])
        device = bench.loadgen.get_json(base, "/debug/state")["device"]
    finally:
        server.stop()
    bench.note(f"served {len(replies)} requests on {device['platform']}; "
               "the server has stopped, the reference starts")

    import jax  # the chip is free now
    import jax.numpy as jnp

    from benchmarks.reference import mellum2 as ref
    from radixmesh_tpu.models import get_config, init_params

    cfg = get_config(config["preset"])
    params = jax.device_get(init_params(cfg, jax.random.PRNGKey(args.seed)))
    spec = cfg if args.rehearse_cpu else source
    seqs, forced, froms = [], [], []
    for i, (p, rep) in enumerate(zip(prompts, replies)):
        seq = np.concatenate([p, np.asarray(rep["output_ids"][:-1], np.int32)])
        first = replies[again]["runs"] if i == len(replies) - 1 else []
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
        probs, mine = ex["router_probs"], forced[i]  # [S, L, E], [S, L, k]
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
