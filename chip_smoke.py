#!/usr/bin/env python3
"""First light: the quickest proof that the system still starts on the chip.

    python chip_smoke.py                 # on a machine with a TPU
    python chip_smoke.py --rehearse-cpu  # same phases, tiny size, CPU

Drives the main path once through the entry point a user would call —
``python -m radixmesh_tpu.launch serve --model qwen2-7b --weight-quant int8
--slo`` (HTTP → SLORunner → Engine → radix tree → paged pool → Pallas
kernels) at Qwen2-7B's published widths, all 28 layers, W8A16, random
weights from a seed — and checks what comes out by the repo's own means:

1. ``device``    what JAX finds. Not a TPU → exit 2, nothing else runs.
2. ``kernels``   every Pallas kernel the default ``serve`` flags reach,
                 compiled at the served head geometry and compared with
                 its jnp oracle (``radixmesh_tpu/ops/kernel_check.py``);
                 the decode kernel also at Mellum2's heads with a window
                 start, context past the window; the latent (MLA) decode
                 kernel at GLM-4.7-Flash's row, with and without rows
                 that share their first pages; the chunk kernel over a
                 prefix of several blocks at a chat wave's, an agent
                 turn's and a window model's geometry (edge blocks,
                 plain blocks, query blocks with no token).
3. ``serve``     start the server; send a short prompt (dense prefill), a
                 prompt over ``long_prefill_threshold`` (chunk kernel), a
                 second turn sharing its prefix (``cached_tokens`` > 0),
                 and concurrent streams (fused decode kernel at batch > 1);
                 check every reply, the dispatch records on
                 ``/debug/state`` (kernel, not the reference), zero failed
                 steps, peak device memory; capture one
                 ``/debug/profile?seconds=1`` window during decode.
4. ``trace``     the capture left an ``.xplane.pb`` whose TPU device plane
                 holds the decode kernel's events.
5. ``four-chip`` with four or more devices: ``Engine(device_mesh=tp4)`` +
                 ``ServingFrontend`` in one process serve the same requests
                 in bf16, weights and pool a quarter per chip. Otherwise
                 "not applicable, N device(s)" — a device count, not a skip.

One process per chip: this parent never imports jax; every phase that
needs jax is a child process, run one at a time, and the server is stopped
before the next child starts. Exits non-zero when JAX finds no TPU, when
any phase fails, and when any phase did not run; the last line of stdout
is the JSON result only on success. Every line names the platform, device
kind and device count; set-up and first-call (compile) times are reported
apart from repeat-request times. ``--rehearse-cpu`` is the only way to run
it without a TPU, and every line it prints then says ``cpu``.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

# Chip run. KV pool sized from compile-time memory analysis on the v5e
# topology (ROADMAP/PERF.md): W8A16 weights 7.10 GiB + 65 536 slots ×
# 56 KiB = 3.50 GiB resident; decode adds ~0; an 8 × 512 chunk-prefill wave
# adds 3.72 GiB of temporaries (a pool-sized copy) → 14.3 of 15.75 GiB.
CHIP = {
    "model": "qwen2-7b",
    "kv_slots": 65536,
    "vocab": 152064,
    "page": 16,
    "geometry": dict(hq=28, hkv=4, head_dim=128, page=16, batch=8, chunk=512,
                     max_pages=64),
    # Mellum2's heads (G = 8) and window: rows shorter than, at and past
    # the 1 024 keys a window layer sees (the table holds 2 048).
    "geometry_window": dict(hq=32, hkv=4, head_dim=128, page=16, batch=8,
                            chunk=512, max_pages=128, window=1024),
    # GLM-4.7-Flash's latent row (20 heads over 576 stored in 640, values
    # its first 512): six rows on 1 280 shared tokens, two on their own.
    "geometry_latent": dict(hq=20, width=640, v_width=512, page=16, batch=8,
                            max_pages=128),
    # Ling-3.0-flash's linear-attention state: 32 heads of 128 x 128 float32,
    # 2 MiB a row and layer, chunks from the rows' slots as the cells launch
    # them: a turn's 64, a cold prompt's 512 (sixteen blocks, half of them
    # at the gate's bound), tails that allow one part a block (48, 16), and
    # more rows than a call takes.
    "geometry_kda": [
        dict(heads=32, dim=128, batch=4, chunk=64),
        dict(heads=32, dim=128, batch=2, chunk=512),
        dict(heads=32, dim=128, batch=17, chunk=48, slots=72),
        dict(heads=32, dim=128, batch=4, chunk=16),
    ],
    # Nemotron-3-Nano's Mamba-2 state: 64 heads of 64 x 128 float32 in 8
    # groups, 2 MiB a row and block: a turn's 64, a cold prompt's 512 (four
    # blocks of 128), more rows than a call takes.
    "geometry_ssd": [
        dict(heads=64, dim=64, groups=8, state=128, batch=4, chunk=64),
        dict(heads=64, dim=64, groups=8, state=128, batch=2, chunk=512),
        dict(heads=64, dim=64, groups=8, state=128, batch=17, chunk=128, slots=72),
    ],
    # The chunk kernel over a prefix of several 512-token blocks, as a turn
    # of a session launches it: a chat wave (256 new on up to 3.8 k), an
    # agent turn (LFM2's two heads of 64 to a row: 4 wide heads, G = 8,
    # logits scaled for 64; 512 new on up to 7.7 k) and Mellum2's window.
    "geometry_prefix": [
        dict(hq=28, hkv=4, head_dim=128, page=16, chunk=256, max_pages=256),
        dict(hq=32, hkv=4, head_dim=128, page=16, chunk=512, max_pages=512,
             scale=0.125),
        dict(hq=32, hkv=4, head_dim=128, page=16, chunk=512, max_pages=256,
             window=1024),
        # Laguna-XS.2's two kinds in one model: 48 query heads over 8 kv
        # heads (G = 6) on the pool, 64 (G = 8) under a window of 512 on the
        # window group; a query block of all heads falls back to what fits.
        dict(hq=48, hkv=8, head_dim=128, page=16, chunk=512, max_pages=512),
        dict(hq=64, hkv=8, head_dim=128, page=16, chunk=512, max_pages=256,
             window=512),
    ],
    "interpret": False,
    "short": 40,  # dense prefill
    "long": 1100,  # > long_prefill_threshold (1024) → chunk kernel
    "long_path": "chunk",
    "turn": 24,  # new tokens in the second turn
    "gen": 8,
    "streams": 4,
    "stream_prompt": 100,
    "stream_gen": 320,
    "profile_seconds": 1,
    # What a TPU with head_dim 128 must report (ops/attention.py).
    "decode_path": "paged",
    "chunk_attention": "paged",
    "fourchip_overrides": {},
}
# CPU rehearsal: same phases, tiny preset (head_dim 32 → reference path by
# design, kernels checked in Pallas interpret mode). The tiny model's 512
# positions cannot exceed the 1 024-token chunk threshold; its "long"
# prompt takes the chunk launches all the same, because its dense launch
# (512 tokens) would be bound by arithmetic, not by the weights it streams
# (``Engine._admit``, PR 47).
REHEARSAL = {
    "model": "qwen2-tiny",
    "kv_slots": 4096,
    "vocab": 512,
    "page": 16,
    "geometry": dict(hq=4, hkv=2, head_dim=32, page=4, batch=2, chunk=8,
                     max_pages=4),
    "geometry_window": dict(hq=8, hkv=1, head_dim=32, page=4, batch=8,
                            chunk=8, max_pages=8, window=12),
    "geometry_latent": dict(hq=4, width=128, v_width=32, page=4, batch=8,
                            max_pages=16, block_tokens=16),
    "geometry_kda": [dict(heads=4, dim=16, batch=3, chunk=16),
                     dict(heads=4, dim=16, batch=2, chunk=64)],
    "geometry_ssd": [dict(heads=8, dim=32, groups=2, state=32, batch=3, chunk=16)],
    "geometry_prefix": [
        dict(hq=7, hkv=1, head_dim=32, page=4, chunk=16, max_pages=16),
        dict(hq=8, hkv=2, head_dim=32, page=4, chunk=16, max_pages=16,
             scale=0.125, window=20),
        dict(hq=6, hkv=2, head_dim=32, page=4, chunk=16, max_pages=16),
    ],
    "interpret": True,
    "short": 40,
    "long": 300,
    "long_path": "chunk",
    "turn": 24,
    "gen": 8,
    "streams": 4,
    "stream_prompt": 24,
    "stream_gen": 200,
    "profile_seconds": 0.1,
    "decode_path": "dense",
    "chunk_attention": "reference",
    # tp=4 needs the kv heads to split four ways; the tiny preset has 2.
    "fourchip_overrides": {"n_heads": 4, "n_kv_heads": 4},
}

_TAG = "[device not probed yet]"


def _set_tag(platform: str, kind: str, count: int, rehearsal: bool) -> None:
    """Every line names the device it came from (and a rehearsal says so)."""
    global _TAG
    _TAG = f"[{platform}/{kind} x{count}]"
    if rehearsal:
        _TAG += " [REHEARSAL: tiny model on the cpu, not a chip run]"


def say(msg: str) -> None:
    print(f"{_TAG} {msg}", flush=True)


class PhaseFailed(Exception):
    pass


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# ----------------------------------------------------------------------
# HTTP client (stdlib only — the parent stays off jax and off the repo)
# ----------------------------------------------------------------------


def _fetch(req, timeout: float) -> dict:
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        url = req if isinstance(req, str) else req.full_url
        raise PhaseFailed(
            f"{url} -> HTTP {e.code}: {e.read().decode(errors='replace')[:600]}"
        ) from e


def _get(url: str, timeout: float = 120.0) -> dict:
    return _fetch(url, timeout)


def _post(url: str, obj: dict, timeout: float = 900.0) -> dict:
    return _fetch(urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"},
    ), timeout)


def _post_stream(url: str, obj: dict, timeout: float = 900.0) -> dict:
    """SSE ``/generate``: returns the final ``done`` event (its
    ``output_ids`` are the whole stream)."""
    req = urllib.request.Request(
        url, data=json.dumps({**obj, "stream": True}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for raw in r:
            line = raw.decode().strip()
            if line.startswith("data:"):
                evt = json.loads(line[5:])
                if evt.get("done"):
                    return evt
    raise PhaseFailed(f"stream from {url} ended without a done event")


def _check_reply(what: str, reply: dict, n_tokens: int, vocab: int) -> list[int]:
    ids = reply.get("output_ids")
    _require(isinstance(ids, list), f"{what}: no output_ids in {reply}")
    _require("error" not in reply, f"{what}: server error {reply.get('error')}")
    _require(not reply.get("cancelled"), f"{what}: reply is flagged cancelled")
    _require(not reply.get("shed"), f"{what}: request was shed ({reply})")
    _require(
        len(ids) == n_tokens,
        f"{what}: {len(ids)} tokens, expected {n_tokens}",
    )
    _require(
        all(isinstance(t, int) and 0 <= t < vocab for t in ids),
        f"{what}: token id outside [0, {vocab})",
    )
    return ids


def _prompt(rng: random.Random, n: int, vocab: int) -> list[int]:
    return [rng.randrange(1, vocab) for _ in range(n)]


def drive(base: str, m: dict, capture_trace: bool) -> dict:
    """The four request kinds against a serving frontend at ``base``, each
    sent twice — conversation A pays the compiles, conversation B (same
    lengths, other tokens) is the repeat — then the state checks. Shared by
    the one-chip ``serve`` phase (over a real server process) and the
    four-chip leg (frontend in the calling process)."""
    vocab, gen = m["vocab"], m["gen"]
    rng = random.Random(21)
    out: dict = {"requests": 0}

    def generate(what, prompt, n=gen, **kw):
        t0 = time.monotonic()
        reply = _post(base + "/generate",
                      {"input_ids": prompt, "max_tokens": n, **kw})
        dt = time.monotonic() - t0
        out["requests"] += 1
        return _check_reply(what, reply, n, vocab), reply, dt

    def state():
        return _get(base + "/debug/state")

    # 1. short prompt → single-request dense prefill.
    times = []
    for conv in "AB":
        _, reply, dt = generate(f"short prompt {conv}",
                                _prompt(rng, m["short"], vocab))
        _require(reply["cached_tokens"] == 0,
                 f"short prompt {conv}: unexpected cache hit {reply}")
        times.append(dt)
    path = state().get("prefill_dispatch", {}).get("path")
    _require(path == "dense", f"short prompt took prefill path {path!r}")
    say(f"short prompt: {m['short']} tok -> {gen} tok, prefill path dense; "
        f"first call {times[0]:.2f} s (compiles included), repeat "
        f"{times[1]:.2f} s")

    # 2. long prompt → chunked paged prefill (chunk kernel); 3. a second
    # turn on the same conversation must be served from the radix tree.
    t_long, t_turn = [], []
    for conv in "AB":
        long_prompt = _prompt(rng, m["long"], vocab)
        ids, reply, dt = generate(f"long prompt {conv}", long_prompt)
        t_long.append(dt)
        st = state()
        path = st.get("prefill_dispatch", {}).get("path")
        _require(path == m["long_path"],
                 f"long prompt {conv} took prefill path {path!r}, "
                 f"expected {m['long_path']!r}")
        att = st.get("prefill_dispatch", {}).get("chunk_attention")
        _require(att == m["chunk_attention"],
                 f"chunk launch attention {att!r}, expected "
                 f"{m['chunk_attention']!r}")
        turn = long_prompt + ids + _prompt(rng, m["turn"], vocab)
        _, reply, dt = generate(f"second turn {conv}", turn)
        t_turn.append(dt)
        floor = m["long"] // m["page"] * m["page"]
        _require(reply["cached_tokens"] >= floor,
                 f"second turn {conv}: cached_tokens "
                 f"{reply['cached_tokens']} < {floor}")
        out["cached_tokens"] = reply["cached_tokens"]
    say(f"long prompt: {m['long']} tok -> {gen} tok, prefill path "
        f"{m['long_path']}; first call {t_long[0]:.2f} s (compiles "
        f"included), repeat {t_long[1]:.2f} s")
    say(f"second turn: {m['long'] + gen + m['turn']} tok, cached_tokens "
        f"{out['cached_tokens']}; first call {t_turn[0]:.2f} s (compiles "
        f"included), repeat {t_turn[1]:.2f} s")

    # 4. concurrent streams → decode at batch > 1. Wave A compiles; wave B
    # repeats, and one profiler window is captured while it decodes.
    t_wave = []
    for wave in "AB":
        before = state()["engine"]
        gen0 = _get(base + "/stats")["generated_tokens"]
        replies: list = [None] * m["streams"]

        def one(i, prompt):
            try:
                replies[i] = _post_stream(
                    base + "/generate",
                    {"input_ids": prompt, "max_tokens": m["stream_gen"]},
                )
            except Exception as e:  # noqa: BLE001 — reported by the checks below
                replies[i] = {"error": f"{type(e).__name__}: {e}"}

        threads = [
            threading.Thread(
                target=one, args=(i, _prompt(rng, m["stream_prompt"], vocab))
            )
            for i in range(m["streams"])
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        max_batch = 0
        captured = None
        while any(t.is_alive() for t in threads):
            st = state()
            d = st.get("decode_dispatch") or {}
            running = st["engine"]["batch_rows_active"]
            if running >= 2:
                max_batch = max(max_batch, d.get("batch", 0))
            if (capture_trace and wave == "B" and captured is None
                    and running >= 2 and d.get("batch", 0) >= 2):
                captured = _get(
                    f"{base}/debug/profile?seconds={m['profile_seconds']}",
                    300.0,
                )
            time.sleep(0.05)
        for t in threads:
            t.join()
        t_wave.append(time.monotonic() - t0)
        for i, reply in enumerate(replies):
            _check_reply(f"stream {wave}{i}", reply, m["stream_gen"], vocab)
        out["requests"] += m["streams"]
        after = state()
        steps = after["engine"]["decode_steps"] - before["decode_steps"]
        toks = _get(base + "/stats")["generated_tokens"] - gen0
        occupancy = toks / max(steps, 1)
        d = after.get("decode_dispatch") or {}
        _require(d.get("path") == m["decode_path"],
                 f"decode dispatch {d}, expected path {m['decode_path']!r}")
        _require(max_batch >= 2 and occupancy > 1.5,
                 f"wave {wave}: decode never ran at batch > 1 (max batch "
                 f"seen {max_batch}, {toks} tokens in {steps} steps)")
        out["decode_occupancy"] = round(occupancy, 2)
        if capture_trace and wave == "B":
            _require(captured is not None,
                     "no profiler window was captured during wave B")
            out["profile"] = captured
            # What the window must hold is checked in the trace itself
            # (the decode kernel's events on the TPU plane).
            say(f"profile: {captured['profiled_s']} s window, "
                f"{captured['decode_steps']} decode steps inside it")
    say(f"{m['streams']} concurrent streams x {m['stream_gen']} tok: decode "
        f"path {m['decode_path']}, mean batch occupancy "
        f"{out['decode_occupancy']}; first wave {t_wave[0]:.2f} s (compiles "
        f"included), repeat {t_wave[1]:.2f} s")

    # State after the traffic.
    st = state()
    eng = st["engine"]
    _require(eng["step_failures"] == 0,
             f"{eng['step_failures']} engine step(s) failed: "
             f"{eng['last_step_error']}")
    _require(eng["finished"] == out["requests"],
             f"{eng['finished']} finished of {out['requests']} sent")
    _require(eng["preemptions"] == 0, f"{eng['preemptions']} preemption(s)")
    out["device"] = st["device"]
    return out


def _memory_line(device: dict) -> str:
    if not device["memory"]:
        return (f"peak device memory: not reported by the "
                f"{device['platform']} backend")
    return "peak device memory: " + "; ".join(
        f"dev{x['id']} {x['peak_bytes_in_use'] / 2**30:.2f} GiB of "
        f"{x['bytes_limit'] / 2**30:.2f} GiB"
        for x in device["memory"]
    )


# ----------------------------------------------------------------------
# child phases (each its own process; these import jax)
# ----------------------------------------------------------------------


def phase_device(_args) -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}


def phase_kernels(args) -> dict:
    from radixmesh_tpu.ops.kernel_check import (
        DEFAULT_CASES,
        WINDOW_CASES,
        check_chunk_prefix,
        check_kda,
        check_kernels,
        check_latent_decode,
        check_ssd,
    )

    m = REHEARSAL if args.rehearse_cpu else CHIP
    t0 = time.monotonic()
    results = [
        {**r, "geometry": m[key]}
        for cases, key in ((DEFAULT_CASES, "geometry"),
                           (WINDOW_CASES, "geometry_window"))
        for r in check_kernels(cases, interpret=m["interpret"], **m[key])
    ] + [
        {**r, "geometry": m["geometry_latent"]}
        for r in check_latent_decode(
            interpret=m["interpret"], **m["geometry_latent"])
    ] + [
        {**r, "geometry": g}
        for g in m["geometry_prefix"]
        for r in check_chunk_prefix(interpret=m["interpret"], **g)
    ] + [
        {**r, "geometry": g}
        for g in m["geometry_kda"]
        for r in check_kda(interpret=m["interpret"], **g)
    ] + [
        {**r, "geometry": g}
        for g in m["geometry_ssd"]
        for r in check_ssd(interpret=m["interpret"], **g)
    ]
    return {"results": results, "seconds": round(time.monotonic() - t0, 2)}


def phase_trace(args) -> dict:
    """Reduce the capture to per-plane event counts and the busiest ops of
    the device planes (``jax.profiler.ProfileData`` — nothing but jax)."""
    import glob

    from jax.profiler import ProfileData

    files = sorted(glob.glob(
        os.path.join(args.logdir, "**", "*.xplane.pb"), recursive=True
    ))
    if not files:
        return {"files": [], "planes": []}
    planes = []
    for plane in ProfileData.from_file(files[-1]).planes:
        busy: dict[str, float] = {}
        kernels: dict[str, list] = {}
        n = 0
        for line in plane.lines:
            for ev in line.events:
                n += 1
                busy[ev.name] = busy.get(ev.name, 0.0) + ev.duration_ns
                # The repo's own Pallas kernels: ops/paged_attention.py
                # names every pallas_call radixmesh_*, and the device op
                # is "%<name>.<n> = … custom-call(…)".
                k = re.match(r"%?(radixmesh_[a-z_]+)", ev.name)
                if k:
                    calls_ms = kernels.setdefault(k.group(1), [0, 0.0])
                    calls_ms[0] += 1
                    calls_ms[1] += ev.duration_ns / 1e6
        top = sorted(busy.items(), key=lambda kv: -kv[1])[:8]
        planes.append({
            "name": plane.name,
            "events": n,
            "top": [[k[:96], round(v / 1e6, 3)] for k, v in top],
            "kernels": {
                k: {"calls": c, "ms": round(ms, 3)}
                for k, (c, ms) in sorted(kernels.items())
            },
        })
    return {"files": [os.path.basename(f) for f in files], "planes": planes,
            "bytes": os.path.getsize(files[-1])}


def phase_fourchip(args) -> dict:
    """tp=4 serving in ONE process through the constructors ``_run_serve``
    uses (``serve`` has no way to ask for a mesh): weights born sharded,
    the pool born sharded, the same requests over HTTP."""
    import jax

    from radixmesh_tpu.engine.engine import Engine
    from radixmesh_tpu.models import get_config, init_params
    from radixmesh_tpu.parallel.sharding import MeshPlan, make_mesh
    from radixmesh_tpu.server.http_frontend import ServingFrontend
    from radixmesh_tpu.slo import SLOConfig
    from radixmesh_tpu.utils.logging import configure_logger

    d = jax.devices()
    _set_tag(d[0].platform, d[0].device_kind, len(d), args.rehearse_cpu)
    configure_logger("fourchip")
    m = REHEARSAL if args.rehearse_cpu else CHIP
    cfg = get_config(m["model"], **m["fourchip_overrides"])
    mesh = make_mesh(MeshPlan(tp=4))
    t0 = time.monotonic()
    params = jax.block_until_ready(
        init_params(cfg, jax.random.PRNGKey(0), mesh=mesh)
    )
    engine = Engine(cfg, params, num_slots=m["kv_slots"], device_mesh=mesh,
                    name="fourchip")
    frontend = ServingFrontend(engine, port=0, slo=SLOConfig())
    setup_s = time.monotonic() - t0
    say(f"four-chip leg: {m['model']} {cfg.n_layers} layers "
        f"{jax.numpy.dtype(cfg.dtype).name} tp=4 resident in {setup_s:.1f} s "
        "(set-up: weight draw + pool + server)")
    try:
        res = drive(f"http://127.0.0.1:{frontend.port}", m, capture_trace=False)
    finally:
        frontend.close()
    # Placement: every tp-sharded leaf and the pool a quarter per device.
    per_dev = {dev.id: 0 for dev in mesh.devices.flat}
    total = 0
    for leaf in jax.tree.leaves(engine.params) + [engine.pool.kv]:
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    shares = {k: round(v / total, 4) for k, v in per_dev.items()}
    say(f"four-chip leg: weights+pool {total / 2**30:.3f} GiB; share per "
        f"device {shares}")
    _require(
        len(per_dev) == 4 and all(0.24 <= s <= 0.27 for s in shares.values()),
        f"weights and pool are not a quarter per chip: {shares}",
    )
    say("four-chip leg: " + _memory_line(res["device"]))
    peaks = [x["peak_bytes_in_use"] for x in res["device"]["memory"]]
    if peaks:
        _require(
            max(peaks) <= 1.5 * min(peaks),
            f"per-device peak memory is lopsided: {peaks}",
        )
    return {"ok": True, "setup_s": round(setup_s, 1), "shares": shares,
            "requests": res["requests"], "peak_bytes": peaks}


PHASES = {
    "device": phase_device,
    "kernels": phase_kernels,
    "trace": phase_trace,
    "fourchip": phase_fourchip,
}


def _child(phase: str, env: dict, extra=(), timeout: float = 900) -> dict:
    """Run one jax-touching phase as its own process; its last stdout line
    is its JSON result, everything before it is passed through."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--phase", phase, *extra],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True, timeout=timeout,
    )
    lines = proc.stdout.strip().splitlines()
    for line in lines[:-1]:
        print(line, flush=True)
    if proc.returncode != 0:
        if lines:
            print(lines[-1], flush=True)
        raise PhaseFailed(f"phase {phase!r} exited {proc.returncode}")
    try:
        return json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError) as e:
        raise PhaseFailed(f"phase {phase!r} printed no JSON result") from e


# ----------------------------------------------------------------------
# the server under test
# ----------------------------------------------------------------------


class Server:
    """``python -m radixmesh_tpu.launch serve`` as a child process."""

    def __init__(self, m: dict, env: dict, workdir: str):
        self.log_path = os.path.join(workdir, "serve.log")
        self.profile_dir = os.path.join(workdir, "profile")
        self._log = open(self.log_path, "w")
        cmd = [
            sys.executable, "-m", "radixmesh_tpu.launch", "serve",
            "--model", m["model"], "--weight-quant", "int8",
            "--kv-slots", str(m["kv_slots"]), "--slo",
            "--http-port", "0", "--profile-dir", self.profile_dir,
        ]
        say("starting: " + " ".join(cmd[1:]))
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            cmd, cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=self._log,
            text=True, start_new_session=True,
        )
        self.base = None

    def wait_ready(self, timeout: float = 900.0) -> float:
        """Block until the server prints its ``serving … on http://…``
        line; returns the set-up time (imports + weight draw + pool)."""
        found: list[str] = []

        def reader():
            for line in self.proc.stdout:
                if " on http://" in line:
                    found.append(line.strip().rsplit(" on ", 1)[1])
                    return

        t = threading.Thread(target=reader, daemon=True)
        t.start()
        deadline = time.monotonic() + timeout
        while t.is_alive() and time.monotonic() < deadline:
            if self.proc.poll() is not None:
                break
            t.join(timeout=0.5)
        if not found:
            raise PhaseFailed(
                f"server did not come up (exit code {self.proc.poll()}); "
                f"log tail:\n{self.log_tail()}"
            )
        self.base = found[0]
        return time.monotonic() - self.t0

    def log_tail(self, n: int = 4000) -> str:
        self._log.flush()
        with open(self.log_path) as fh:
            return fh.read()[-n:]

    def stop(self) -> None:
        if self.proc.poll() is None:
            os.killpg(self.proc.pid, signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                os.killpg(self.proc.pid, signal.SIGKILL)
                self.proc.wait(timeout=30)
        self._log.close()


def phase_serve(m: dict, env: dict, workdir: str) -> dict:
    server = Server(m, env, workdir)
    try:
        setup_s = server.wait_ready()
        say(f"server up at {server.base} in {setup_s:.1f} s (set-up: imports, "
            "layer-at-a-time weight draw, KV pool; no request served yet)")
        st = _get(server.base + "/debug/state")
        say("after set-up: " + _memory_line(st["device"]))
        t0 = time.monotonic()
        res = drive(server.base, m, capture_trace=True)
        say(f"{res['requests']} requests answered and checked in "
            f"{time.monotonic() - t0:.1f} s; 0 failed steps")
        say(_memory_line(res["device"]))
        return res
    except Exception:
        print(f"--- server log tail ---\n{server.log_tail()}", file=sys.stderr)
        raise
    finally:
        server.stop()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="run the same phases at tiny size on the CPU (Pallas interpret "
        "mode); the result says rehearsal and names the cpu device",
    )
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--logdir", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:  # child: run one phase, print its JSON result last
        try:
            print(json.dumps(PHASES[args.phase](args)), flush=True)
        except PhaseFailed as e:
            print(f"{_TAG} FAILED: {e}", flush=True)
            return 1
        return 0

    t_start = time.monotonic()
    m = REHEARSAL if args.rehearse_cpu else CHIP
    env = dict(os.environ)
    extra = []
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        extra = ["--rehearse-cpu"]

    dev = _child("device", env, timeout=300)
    _set_tag(dev["platform"], dev["kind"], dev["count"], args.rehearse_cpu)
    if not args.rehearse_cpu and dev["platform"] != "tpu":
        print(f"{_TAG} chip_smoke needs a TPU; JAX found {dev}. "
              "(--rehearse-cpu rehearses the phases at tiny size.)",
              file=sys.stderr)
        return 2
    say(f"device: {dev}")

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        k = _child("kernels", env, extra, timeout=600)
        for r in k["results"]:
            say(f"kernel parity: {r}")
        _require(all(r["ok"] for r in k["results"]) and k["results"],
                 "a kernel disagrees with its oracle or failed to compile")
        say(f"kernels: {len(k['results'])} compiled and matched in "
            f"{k['seconds']} s (compile + run)")

        served = phase_serve(m, env, workdir)

        # The trace reader never needs the chip (and the server is gone).
        tr = _child("trace", {**env, "JAX_PLATFORMS": "cpu"},
                    ["--logdir", os.path.join(workdir, "profile")], timeout=300)
        _require(tr["files"], "the profile capture left no .xplane.pb")
        for p in tr["planes"]:
            if p["events"]:
                say(f"trace plane {p['name']}: {p['events']} events; repo "
                    f"kernels: {p['kernels']}; busiest (ms): {p['top']}")
        if dev["platform"] == "tpu":
            tpu = [p for p in tr["planes"] if p["name"].startswith("/device:TPU")]
            _require(tpu and all(p["events"] > 0 for p in tpu),
                     f"no non-empty TPU device plane in {tr['files']}")
            _require(
                any("radixmesh_decode_fused" in k
                    for p in tpu for k in p["kernels"]),
                "the TPU plane holds no fused-decode kernel event "
                f"(kernel events: {[p['kernels'] for p in tpu]})",
            )
        else:
            _require(any(p["events"] > 0 for p in tr["planes"]),
                     "the capture holds no events at all")
            say("trace: cpu capture (host planes only; a TPU device plane "
                "is checked on the chip)")
        say(f"trace: {tr['files'][-1]} {tr['bytes']} bytes")

        if dev["count"] >= 4:
            fc = _child("fourchip", env, extra, timeout=900)
            _require(fc.get("ok") is True, f"four-chip leg failed: {fc}")
            say(f"four-chip leg: passed ({fc})")
        else:
            say(f"four-chip leg: not applicable, {dev['count']} device(s)")
    except (PhaseFailed, subprocess.TimeoutExpired, urllib.error.URLError,
            OSError, KeyError) as e:
        print(f"{_TAG} FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    say(f"all phases passed in {time.monotonic() - t_start:.0f} s; peak "
        f"memory and paths above; requests served: {served['requests']}")
    result = {"ok": True, "device": dev}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
