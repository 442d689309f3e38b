#!/usr/bin/env python3
"""The cell benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

One run of one cell of ``BENCHMARK.json``, measured from the client's
side of the served HTTP path: start the configuration's server as a child
process (it holds the chips; this parent never touches JAX while it
lives) -> warm up (the cell's launch shapes, the prefix-consistency probe,
then the cell's own traffic for a stated time) -> a window of
``--seconds`` -> drain -> read the counters -> stop the server -> reduce
-> print. ``setup_s`` is process start to window start. The LAST line of
stdout is the result as one JSON object; earlier lines carry medians,
sample counts and diagnostics. No TPU, too few chips, or a tree that
lacks the program: no result line and a non-zero exit.

``--trace 0`` prints the end-to-end metrics. ``--trace 2`` is a ``--trace
0`` run followed by a short traced window in the same process: up to the
end of the drain it does exactly what ``--trace 0`` does, and every
end-to-end number and every check of ``correct`` comes from that state
alone; then, with the load still running, the server's profiler is started
and stopped once (thrown away: the first start's cost falls into no
number), a capture of 3 s is taken, the load stops, the server exits, the
capture is reduced and deleted, and the last line carries end-to-end and
per-layer metrics side by side. ``--trace 1`` (one capture in the middle of
the window, per-layer metrics only) is the older form of the same.

``--rehearse-cpu`` (this file's only extra switch) runs the same code on
the CPU with the ``qwen2-tiny`` preset and every length divided by 32, to
rehearse the command before a chip-minute is spent. Its result names the
device as ``cpu`` and is never a measurement.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import sys
import threading
import time

T_PROCESS = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

from benchmarks.harness import hostspans, ledger, loadgen, registry, xplane  # noqa: E402
from benchmarks.harness.opsbytes import kv_bytes_per_token  # noqa: E402
from benchmarks.harness.peaks import peaks_for  # noqa: E402
from benchmarks.harness.server import NoAccelerator, Server, child_env  # noqa: E402

REHEARSAL_SHRINK = 32
PLAN_TAIL_S = 30.0  # traffic goes on this long after the window
CAPTURE_S = 3.0  # the profiler capture of --trace 1 and --trace 2
_TOKEN_KEYS = ("system_len", "turn_tokens", "max_tokens")


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def note(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_PROCESS:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def _shrink(spec: dict, factor: int) -> dict:
    out = dict(spec)
    for k in ("value", "median", "min", "max"):
        if k in out:
            out[k] = max(2, int(out[k]) // factor)
    if "quantum" in out:
        out["quantum"] = max(1, int(out["quantum"]) // factor)
    return out


def rehearsal_view(r: dict) -> dict:
    """The same cell at tiny size: the configuration's ``rehearsal`` group
    replaces preset, server arguments and vocabulary; token lengths shrink
    by 32; the cell's ``rehearsal`` group may replace loop and warm-up."""
    r = copy.deepcopy({k: v for k, v in r.items()
                       if k not in ("generator", "launcher")}) | {
        "generator": r["generator"], "launcher": r["launcher"]}
    reh = r["config"]["rehearsal"]
    r["config"].update(preset=reh["preset"], server_args=reh["server_args"],
                       vocab=reh["vocab"])
    r["config"]["probe"] = {**r["config"]["probe"], **reh.get("probe", {})}
    for k in _TOKEN_KEYS:
        if k in r["traffic"]["params"]:
            r["traffic"]["params"][k] = _shrink(r["traffic"]["params"][k],
                                                REHEARSAL_SHRINK)
    r["cell"].update(r["cell"].get("rehearsal", {}))
    return r


# ----------------------------------------------------------------------
# warm-up: the cell's launch shapes, and the prefix-consistency probe
# ----------------------------------------------------------------------


def _one(base, prompt, max_tokens, vocab, tag, timeout=900.0):
    rec = ledger.Record(session=-1, turn=0, due_t=time.monotonic(),
                        n_prompt=len(prompt), max_tokens=max_tokens, tag=tag)
    ids = loadgen.stream_generate(base, prompt, rec, vocab, timeout)
    if not rec.ok:
        raise RuntimeError(f"{tag} request failed: {rec.status} "
                           f"({len(prompt)} tokens in, {max_tokens} out)")
    return ids, rec


def _as_list(x) -> list:
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _pow2(n: int, floor: int) -> int:
    return 0 if n == 0 else 1 << (max(n, floor) - 1).bit_length()


def census_cover(census: list[tuple[int, int]], page: int) -> list[tuple[int, int]]:
    """The fewest planned requests (cached prefix, new tokens) that between
    them show the server every distinct new-token length, every distinct
    cached-prefix length, and every pair of their power-of-two buckets:
    the engine compiles small programs per exact length and its prefill
    programs per bucket pair."""
    seen_new, seen_reuse, seen_pair, keep = set(), set(), set(), []
    for reuse, new in sorted(set(census)):
        pair = (_pow2(new, 8), _pow2(reuse, page))
        if new in seen_new and reuse in seen_reuse and pair in seen_pair:
            continue
        seen_new.add(new)
        seen_reuse.add(reuse)
        seen_pair.add(pair)
        keep.append((reuse, new))
    return keep


def warm_shapes(base: str, warm: dict, census: list[tuple[int, int]], vocab: int,
                seed: int, page: int, prefixes: list | None = None) -> dict:
    """Reach the cell's launch shapes before the window: the census cover,
    one request at a time (each a prefix of one published document + fresh
    tokens, 2 tokens out), then the cell's ``bursts`` — ``n`` requests sent
    together behind a ``blocker`` whose prefill holds the engine, so that
    they are admitted as one group (the batched prefill and sampler
    shapes). A burst entry whose ``n``, ``new_tokens`` or ``prefix_len`` is
    a list stands for every combination. The cached prefix the server
    reports for each census request is checked against the prediction.
    ``prefixes`` (the mix's shared system prompts) are published to the
    tree, so that no window pays the first use of one."""
    rng = np.random.default_rng([int(seed), 11])
    cover = census_cover(census, page)
    bursts = [
        {**b, "n": n, "new_tokens": t, "prefix_len": pl}
        for b in warm.get("bursts", [])
        for n in _as_list(b["n"]) for t in _as_list(b["new_tokens"])
        for pl in _as_list(b.get("prefix_len", 0))
    ]
    longest = max([r for r, _ in cover] + [int(b.get("prefix_len", 0)) for b in bursts] + [0])
    doc = rng.integers(0, vocab, size=longest, dtype=np.int32)
    out = int(warm.get("max_tokens", 2))
    sent = mismatched = 0
    if longest:
        _one(base, doc, out, vocab, "warmup")
        sent += 1
    for shared in prefixes or []:  # publish every shared system prompt
        _one(base, shared, out, vocab, "warmup")
        sent += 1
    for reuse, new in cover:
        fresh = rng.integers(0, vocab, size=new, dtype=np.int32)
        before = loadgen.get_json(base, "/stats")["cached_tokens"]
        _one(base, np.concatenate([doc[:reuse], fresh]), out, vocab, "warmup")
        got = loadgen.get_json(base, "/stats")["cached_tokens"] - before
        mismatched += got != reuse
        sent += 1
    for burst in bursts:
        errors: list[str] = []

        def go(prompt, m=int(burst.get("max_tokens", out))):
            try:
                _one(base, prompt, m, vocab, "warmup")
            except RuntimeError as e:
                errors.append(str(e))

        def prompt_of(n_new):
            return np.concatenate([
                doc[:int(burst.get("prefix_len", 0))],
                rng.integers(0, vocab, size=int(n_new), dtype=np.int32)])

        threads = [threading.Thread(target=go, args=(prompt_of(burst["new_tokens"]),))
                   for _ in range(int(burst["n"]))]
        blocker = None
        if burst.get("blocker"):
            blocker = threading.Thread(
                target=go, args=(rng.integers(0, vocab, size=int(burst["blocker"]),
                                              dtype=np.int32),))
            blocker.start()
            time.sleep(0.05)
        for t in threads:
            t.start()
        for t in threads + ([blocker] if blocker else []):
            t.join()
        if errors:
            raise RuntimeError(errors[0])
        sent += len(threads) + bool(blocker)
    return {"requests": sent, "census": len(census), "cover": len(cover),
            "cached_prefix_not_as_predicted": mismatched}


def probe(base: str, spec: dict, vocab: int, seed: int) -> dict:
    """Prefix consistency, outside the window: each seeded prompt is
    generated cold (nothing of it in the tree) and again (all but its
    last page from the tree), greedy. Two scores, each held to the margin
    the configuration file states: the mean share of tokens that agree
    before the first disagreement, and the share of first tokens that
    agree (a cache serving wrong K/V leaves both near 0)."""
    rng = np.random.default_rng([int(seed), 12])
    shares, first = [], []
    for _ in range(int(spec["n"])):
        prompt = rng.integers(0, vocab, size=int(spec["prompt_len"]), dtype=np.int32)
        cold, _ = _one(base, prompt, int(spec["max_tokens"]), vocab, "probe")
        again, _ = _one(base, prompt, int(spec["max_tokens"]), vocab, "probe")
        same = 0
        for a, b in zip(cold, again):
            if a != b:
                break
            same += 1
        shares.append(same / len(cold))
        first.append(cold[0] == again[0])
    share, first_share = sum(shares) / len(shares), sum(first) / len(first)
    return {"n": len(shares), "match_share": share,
            "per_prompt": [round(x, 4) for x in shares],
            "first_token_match": first_share, "min_match": spec["min_match"],
            "min_first_token_match": spec["min_first_token_match"],
            "ok": (share >= spec["min_match"]
                   and first_share >= spec["min_first_token_match"])}


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------


class Poller(threading.Thread):
    """``/debug/state`` once a second: pool occupancy and device memory."""

    def __init__(self, base: str):
        super().__init__(daemon=True)
        self.base, self.samples, self._stop_evt = base, [], threading.Event()

    def run(self) -> None:
        while not self._stop_evt.wait(1.0):
            try:
                st = loadgen.get_json(self.base, "/debug/state", 10.0)
            except (OSError, RuntimeError, ValueError):
                continue
            self.samples.append((time.monotonic(), st["pool"]["free_slots"],
                                 st["pool"]["num_slots"],
                                 st["cache"].get("evictable_tokens") or 0))

    def stop(self) -> None:
        self._stop_evt.set()


def snapshot(base: str) -> dict:
    return {"t": time.monotonic(),
            "stats": loadgen.get_json(base, "/stats"),
            "state": loadgen.get_json(base, "/debug/state")}


def sleep_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.5))


def frozen(rec: ledger.Record) -> ledger.Record:
    """A request's record as it stands now, for --trace 2: the load goes
    on while the capture runs, and nothing that is sent or finishes then
    may reach the measured numbers. The status is read first
    (``ledger.finalize`` sets it last), so a copy that says ``ok`` is
    whole; one still in flight is settled as ``load.stop()`` would."""
    status = rec.status
    out = copy.copy(rec)
    out.token_t = list(rec.token_t)
    out.status = "unfinished" if status == "inflight" else status
    return out


def end_to_end(metrics: list[dict], summary: dict, setup_s: float) -> dict:
    """The end-to-end part of the result line — one code path for
    ``--trace 0`` and ``--trace 2``: the window's ledger summary and the
    set-up time, nothing of the capture."""
    out = {}
    for m in metrics:
        value = setup_s if m["name"] == "setup_s" else summary.get(m["name"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def capture_after_drain(base: str, prof_s: float) -> dict:
    """--trace 2, after the end-of-drain snapshot and with the load still
    running: start and stop the profiler once and throw that away, then
    one capture of ``prof_s``. Returns the server's answer and, on this
    process's clock, when the capture ran and the backlog at its ends."""
    loadgen.get_json(base, "/debug/profile?seconds=0.2", 300.0)

    def backlog() -> int:
        eng = loadgen.get_json(base, "/debug/state")["engine"]
        return eng["waiting"] + eng["batch_rows_active"]

    b0, t_a = backlog(), time.monotonic()
    answer = loadgen.get_json(base, f"/debug/profile?seconds={prof_s}", 300.0)
    t_b = time.monotonic()
    return {**answer, "t": (t_a, t_b), "backlog_start_end": [b0, backlog()]}


def capture_line(captured: dict, records: list, server, t1: float,
                 summary: dict, last_arrival_t: float | None) -> dict:
    """What the ``timeline`` line says of the --trace 2 capture: when it
    began relative to the window's end, the backlog at its two ends, the
    tokens that arrived during it (against the window's rate: what a
    capture costs), compilations inside it, and whether the plan's arrivals
    had already stopped — then its per-step numbers are not the cell's."""
    if "t" not in captured:
        return {"error": captured.get("error")}
    t_a, t_b = captured["t"]
    tokens = sum(1 for r_ in records if r_.tag == "load"
                 for t in r_.token_t if t_a <= t < t_b)
    return {
        "began_after_window_s": round(t_a - t1, 2),
        "seconds": round(t_b - t_a, 3),
        "decode_steps": captured.get("decode_steps"),
        "backlog_start_end": captured["backlog_start_end"],
        "tokens_per_s": tokens / (t_b - t_a),
        "tokens_per_s_of_window": summary["tokens_per_s"],
        "compiles": len(server.compiles_between(t_a, t_b)),
        "arrivals_had_stopped": last_arrival_t is not None and t_a > last_arrival_t,
    }


def run(args) -> int:
    r = registry.resolve(args.workload)
    if args.rehearse_cpu:
        r = rehearsal_view(r)
    elif os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        note("JAX_PLATFORMS=cpu: this benchmark needs the TPU "
             "(--rehearse-cpu rehearses it at tiny size)")
        return 2
    cell, config, traffic = r["cell"], r["config"], r["traffic"]
    vocab = int(config["vocab"])
    work = os.path.join(HERE, ".work", f"{args.workload}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    profile_dir = os.path.join(work, "profile") if args.trace else None
    env = child_env()
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        if r["chips"] > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count={r['chips']}")
    argv = r["launcher"].argv(config, seed=args.seed, profile_dir=profile_dir)
    note("starting: " + " ".join(argv[1:]))
    server = Server(argv, env, os.path.join(work, "server.log"))
    poller = None
    load = None
    try:
        base = server.wait_ready(
            1100.0, need_platform=None if args.rehearse_cpu else "tpu")
        dev = loadgen.get_json(base, "/debug/state")["device"]
        note(f"server up at {base} on {dev['platform']}/{dev['device_kind']} "
             f"x{dev['count']}")
        if not args.rehearse_cpu and dev["platform"] != "tpu":
            raise NoAccelerator(f"the server runs on {dev['platform']}, not a TPU")
        if dev["count"] < r["chips"]:
            raise NoAccelerator(
                f"the cell needs {r['chips']} chips, JAX found {dev['count']}")
        t_up = time.monotonic()

        warm_s = float(cell["warmup"]["traffic_s"])
        drain_s = float(cell["drain_s"])
        # The plan's length fixes an open loop's schedule (how many sessions,
        # so which sizes): it must not move with the drain cap.
        plan = r["generator"].plan(
            traffic["params"], cell["loop"], vocab, args.seed,
            horizon_s=warm_s + args.seconds + PLAN_TAIL_S)
        page = int(config["page_size"])
        census = plan.census(plan.pool, page)
        warmed = warm_shapes(base, cell["warmup"], census, vocab, args.seed, page,
                             prefixes=plan.prefixes())
        t_shapes = time.monotonic()
        pr = probe(base, config["probe"], vocab, args.seed)
        t_probe = time.monotonic()
        note(f"warm shapes {warmed} {t_shapes - t_up:.1f} s; probe {pr}")
        load = loadgen.Load(base, plan, cell["loop"], vocab,
                            request_timeout=float(cell.get("request_timeout_s", 180)))
        poller = Poller(base)
        t_start = load.start()
        # An open loop's arrivals end with the plan (a closed loop cycles).
        arrivals = getattr(plan, "arrivals", None)
        last_arrival_t = (t_start + float(arrivals[-1])
                          if arrivals is not None and len(arrivals) else None)
        poller.start()
        t0 = t_start + warm_s
        t1 = t0 + args.seconds
        sleep_until(t0)
        before = snapshot(base)
        t0 = max(t0, before["t"])  # the window opens once the counters are read
        setup_s = t0 - T_PROCESS

        captured: dict = {}
        tracer = None
        prof_s = min(CAPTURE_S, max(0.2, args.seconds / 2.0))
        if args.trace == 1:

            def capture():
                sleep_until(t0 + (t1 - t0) / 2.0 - prof_s / 2.0)
                try:
                    captured.update(loadgen.get_json(
                        base, f"/debug/profile?seconds={prof_s}", 300.0))
                except (OSError, RuntimeError, ValueError) as e:
                    captured["error"] = str(e)

            tracer = threading.Thread(target=capture, daemon=True)
            tracer.start()

        sleep_until(t1)
        after = snapshot(base)
        t1 = after["t"]
        deadline = t1 + drain_s
        while load.pending_in(t0, t1) and time.monotonic() < deadline:
            time.sleep(0.1)
        drained_s = time.monotonic() - t1
        if args.trace == 2:
            # The measured state ends here; the load goes on for the capture.
            with load._lock:
                records = [frozen(r_) for r_ in load.records]
            end = snapshot(base)
            polls = list(poller.samples)
            try:
                captured = capture_after_drain(base, prof_s)
            except (OSError, RuntimeError, ValueError) as e:
                captured = {"error": str(e)}
            all_records = load.stop()
            final = snapshot(base)
        else:
            records = all_records = load.stop()
            if tracer is not None:
                tracer.join(timeout=300.0)
            end = final = snapshot(base)
            polls = poller.samples
        poller.stop()
    except NoAccelerator as e:
        note(f"no accelerator: {e}")
        return 2
    finally:
        if load is not None:
            load.stop()
        if poller is not None:
            poller.stop()
        server.stop()

    # ---- reduce (the child is gone; JAX on the CPU is safe now) -------
    limits = cell["limits"]
    summary = ledger.summarize(records, t0, t1, limits)
    in_win = server.compiles_between(t0, t1)
    dev = final["state"]["device"]  # memory peak: of the whole run
    peaks = None if args.rehearse_cpu else peaks_for(dev["device_kind"])
    trace = spans = None
    if args.trace:
        path = xplane.find_capture(profile_dir) if "error" not in captured else None
        if path is None:
            note(f"no profiler capture: {captured}")
            return 1
        planes = hostspans.device_planes(path)  # = xplane.load, parsed once
        trace = xplane.reduce(planes, captured.get("profiled_s"))
        spans = hostspans.of_capture(path)
        if args.dump_trace:
            os.makedirs(os.path.dirname(args.dump_trace) or ".", exist_ok=True)
            with open(args.dump_trace, "w") as fh:
                json.dump({"lines": {p: {ln: len(ev) for ln, ev in ls.items()}
                                     for p, ls in planes.items()},
                           "fixture": xplane.fixture_slice(planes),
                           "host_fixture": hostspans.fixture_slice(path),
                           "host_spans": spans,
                           "reduced": trace}, fh)
    ctx = {
        "summary": summary, "records": records, "window": (t0, t1),
        "before": before, "after": after, "polls": polls,
        "compiles_in_window": in_win, "trace": trace, "config": config,
        "cell": cell, "peaks": peaks,
        "kv_bytes_per_token": kv_bytes_per_token(config),
    }

    # ---- correct ------------------------------------------------------
    due = ledger.in_window(records, t0, t1)
    d_stats = {k: after["stats"][k] - before["stats"][k]
               for k in ("prompt_tokens", "cached_tokens", "generated_tokens",
                         "finished", "preemptions")}
    # The cache served: the server counts a request's cached tokens when it
    # admits it, the generator knows what it re-sent when the request was
    # due. Cut at the window's edges the two disagree by every request that
    # is late (a host stall of 7 s read 0.91 with a cache that served all),
    # so both run to the end of the drain: every request due from the
    # window's start on that ended whole was admitted in between.
    since = [r_ for r_ in records if r_.tag == "load" and r_.due_t >= t0 and r_.ok]
    resent = sum(r_.resent_tokens // page * page for r_ in since)
    cached_since = end["stats"]["cached_tokens"] - before["stats"]["cached_tokens"]
    resent_window = sum(r_.resent_tokens // page * page for r_ in due if r_.ok)
    need_share = float(cell.get("min_cached_share", 0.0))
    checks = {
        "some_requests": len(due) > 0,
        "all_ok_requests_whole": all(
            r_.n_output == r_.max_tokens and r_.ids_ok for r_ in due if r_.ok),
        "no_step_failures": end["state"]["engine"]["step_failures"] == 0,
        "cache_served": (need_share == 0.0 or resent == 0
                         or cached_since >= need_share * resent),
        "prefix_probe": pr["ok"],
    }
    correct = all(checks.values())

    with open(os.path.join(work, "records.jsonl"), "w") as fh:  # for diagnosis
        for r_ in records:
            fh.write(json.dumps({
                "session": r_.session, "turn": r_.turn, "status": r_.status,
                "n_prompt": r_.n_prompt, "max_tokens": r_.max_tokens,
                "due": r_.due_t - t0, "sent": r_.send_t and r_.send_t - t0,
                "first": r_.token_t[0] - t0 if r_.token_t else None,
                "done": r_.done_t and r_.done_t - t0}) + "\n")

    # ---- print --------------------------------------------------------
    say({"diag": "timeline", "server_up_s": round(t_up - T_PROCESS, 2),
         "warm_shapes_s": round(t_shapes - t_up, 2), "probe_s": round(t_probe - t_shapes, 2),
         "warm_traffic_s": warm_s, "window_s": round(t1 - t0, 3),
         "drained_s": round(drained_s, 2), "warm_shapes": warmed,
         **({"capture": capture_line(captured, all_records, server, t1, summary,
                                     last_arrival_t)} if args.trace == 2 else {})})
    say({"diag": "window", **{k: v for k, v in summary.items()},
         "backlog_start_end": [snap["state"]["engine"]["waiting"]
                               + snap["state"]["engine"]["batch_rows_active"]
                               for snap in (before, after)],
         "stats_delta": d_stats, "resent_tokens_page_rounded": resent,
         "cached_over_resent": (cached_since / resent) if resent else None,
         "cached_over_resent_window_edges": (
             d_stats["cached_tokens"] / resent_window if resent_window else None),
         "pool_polls_free_slots_evictable": [[f, e] for _, f, _, e in polls][-12:],
         "slo_tier": after["stats"].get("slo", {}).get("tier"),
         "slo_tier_events": after["stats"].get("slo", {}).get("tier_events")})
    say({"diag": "compiles", "total": len(server.compiles),
         "total_s": round(sum(c[2] for c in server.compiles), 2),
         "in_window": [[round(c[0] - t0, 2), c[1], round(c[2], 3)] for c in in_win][:40]})
    say({"diag": "correct", **checks, "probe": pr})
    if spans is not None:
        say({"diag": "host_spans", **spans})

    metrics = {} if args.trace == 1 else end_to_end(r["end_to_end"], summary, setup_s)
    if args.trace:
        for m in r["per_layer"]:
            value = registry.load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.trace == 1:
        say({"diag": "end_to_end_of_traced_run", "setup_s": setup_s,
             **{m["name"]: summary.get(m["name"]) for m in r["end_to_end"]
                if m["name"] != "setup_s"}})
    if args.trace == 2:  # reduced: the capture goes
        shutil.rmtree(profile_dir, ignore_errors=True)
    memory = [x["peak_bytes_in_use"] for x in dev["memory"]
              if x.get("peak_bytes_in_use") is not None]
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["count"],
              "memory_peak_bytes": max(memory) if memory else None}
    result = {"correct": correct, "attempted": summary["attempted"],
              "failed": summary["failed"], "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        # --trace 2 names the idle time by what the engine thread was doing
        # (harness/hostspans.py); --trace 1, and a program without the
        # spans, by the device modules on either side.
        by_host = spans.get("by_span") if args.trace == 2 and spans else None
        result["breakdown"] = {"device_ops": trace.get("device_ops", []),
                               "idle_gaps": by_host or trace.get("idle_gaps", [])}
    if args.rehearse_cpu:
        result["rehearsal"] = "tiny model on the cpu: not a measurement"
    say(result)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--dump-trace", default=None,
                    help="with --trace 1 or 2: write line names, a fixture slice and "
                    "the reduction as JSON here (for building test fixtures)")
    args = ap.parse_args()
    try:
        return run(args)
    except (RuntimeError, OSError, KeyError, ValueError) as e:
        note(f"FAILED: {type(e).__name__}: {e}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
