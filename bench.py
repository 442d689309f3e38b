"""Headline benchmark: paged-decode throughput on one chip.

ONE process on one TPU. Without a TPU it exits non-zero before measuring
anything — there is no CPU fallback and no CPU shape; a phase that raises
ends the run non-zero. Prints ONE **compact** JSON line (headline metric,
device, gates) and writes the FULL report to ``BENCH_FULL_r{N}.json``
in-repo; the compact line carries ``full_report`` naming that file.

The reference publishes no numbers (SURVEY §6: ``README.md:58`` unchecked,
``BASELINE.json`` ``published: {}``; its ``src.test.benchmark`` has no
timers), so the baseline is a reference-style dense-cache decode (what a
naive contiguous-KV torch port would keep) measured in the same run, same
chip, same model.

``vs_baseline`` is decode throughput at an **equal KV HBM budget** on a
mixed-length serving batch (``serving_mix`` in the JSON): the paged pool
stores only real tokens and its page tables are per-launch, so the batch
is larger and short rows don't attend over long rows' padding; the dense
cache must pad every sequence to the longest, which caps its batch at the
same byte budget. That is the capability the radix-paged design exists
for. ``vs_dense_same_shape`` additionally reports the same-shape
per-step ratio (~1 is expected where both paths stream identical bytes),
and ``ctx_sweep`` records it across context lengths.

Model: Llama-architecture ~1B config (bf16) at batch 64 / context 1024 /
page_size 16. This file also hosts the artifact schemas the other planes'
benches validate against; those import it WITHOUT jax (only the
``__main__`` run imports a backend). ROADMAP S0 replaces the measuring
half with a cell benchmark.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

_REPO = os.path.dirname(os.path.abspath(__file__))


def current_round() -> int:
    """The round in progress = 1 + the highest recorded ``BENCH_r{N}``
    artifact (the driver writes one at the END of each round)."""
    import re

    rounds = [0]
    for name in os.listdir(_REPO):
        m = re.fullmatch(r"BENCH_r(\d+)\.json", name)
        if m:
            rounds.append(int(m.group(1)))
    return max(rounds) + 1

if __name__ == "__main__":  # schema importers stay jax-free
    import jax
    import jax.numpy as jnp
    import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------------------
# RINGBENCH stable schema (VERDICT round-5 weak #6: r04 lacked
# lap_latency at top level, r05 dropped the r04 ratio/paired fields —
# cross-round comparability was eroding). scripts/ringbench.py emits this
# shape every round and validates against it before writing; the schema
# is documented in BASELINE.md. Bump the version ONLY when adding fields
# (fields are never removed or renamed).
# ----------------------------------------------------------------------

RINGBENCH_SCHEMA_VERSION = 2

# Every per-configuration run section must carry these.
RINGBENCH_RUN_FIELDS = (
    "metric", "value", "unit", "transport", "topology",
    "inserts_per_writer", "key_len_tokens", "page_size",
    "wire_bytes_per_insert", "ingest_s_max", "converge_s_max",
    "oplog_applies_per_s", "lap_latency", "route", "wall_s",
)

# The artifact's top level: both configurations (page-granular wire vs
# the token-granular baseline, same keys/inserts), their ratios, and the
# fixed round-3 wire-format reference point.
RINGBENCH_TOP_FIELDS = (
    "schema_version", "metric", "value", "unit", "workload",
    "page_granular", "token_granular_baseline", "bytes_per_insert_ratio",
    "inserts_per_s_ratio", "lap_latency", "round3_wire_bytes_per_insert",
    "vs_round3_wire",
)

# The same 256-token insert cost 2092 wire bytes on the round-3 v2 wire
# (int32 arrays, token-granular) — the fixed denominator of
# ``vs_round3_wire`` (RINGBENCH_r04.json first recorded it).
RINGBENCH_ROUND3_WIRE_BYTES = 2092

RINGBENCH_LAP_FIELDS = ("p50_ms", "p99_ms", "mean_ms", "n")


def validate_ringbench(report: dict) -> list[str]:
    """Missing-field paths of a RINGBENCH artifact vs the pinned schema
    (empty = valid). Import-safe from scripts (no jax at module scope)."""
    missing = [f for f in RINGBENCH_TOP_FIELDS if f not in report]
    for section in ("page_granular", "token_granular_baseline"):
        run = report.get(section)
        if not isinstance(run, dict):
            continue  # the absent section is already reported above
        missing += [
            f"{section}.{f}" for f in RINGBENCH_RUN_FIELDS if f not in run
        ]
        lap = run.get("lap_latency")
        if isinstance(lap, dict):
            missing += [
                f"{section}.lap_latency.{f}"
                for f in RINGBENCH_LAP_FIELDS
                if f not in lap
            ]
    return missing


# ----------------------------------------------------------------------
# RINGSCALE v2 schema (scripts/ringscale.py): the wire-scaling sweep,
# extended by prefix-ownership sharding (cache/sharding.py). v2 adds
# per-row rf/mode (live threaded vs simulated transport — sizes above
# the sim threshold run the real delivery/serialization code over an
# in-memory pump with MODELED hop latency) and the structural gates the
# sharding claim rides on:
#   * FLATNESS — for every rf > 0 row group, bytes-per-insert at the
#     largest N must stay within RINGSCALE_FLATNESS_MAX_RATIO of the
#     smallest N (the O(N) wire wall is broken, not just bent);
#   * PROPAGATION — sharded propagation-to-owners p99 must be no worse
#     than the full-replica ring's p99 at the SMALLEST size, compared
#     within the same hop delay and measurement mode.
# v1 artifacts (no schema_version; full-replica rows only) stay valid.
# ----------------------------------------------------------------------

# v3 (PR 15): the sweep carries at least one owner-propagation row
# measured WITH an adopted ShardOverrides map (the PR 14 deferral) —
# the override row must pass the same propagation gate as every sharded
# row, and its measured writer-side serial cost must stay within
# RINGSCALE_OVERRIDES_SERIAL_MAX_RATIO of the matching no-override row.
# v1/v2 artifacts stay valid as-is.
RINGSCALE_SCHEMA_VERSION = 3

RINGSCALE_TOP_FIELDS = (
    "schema_version", "metric", "mode", "sizes", "hop_delays_ms", "rfs",
    "results", "bytes_per_insert_growth",
)
RINGSCALE_ROW_FIELDS = (
    "n_nodes", "topology", "rf", "mode", "hop_delay_ms", "frame_bytes",
    "frames_per_insert", "measured_frames_per_insert",
    "ring_bytes_per_insert", "prop_p50_ms", "prop_p99_ms",
)
RINGSCALE_OVERRIDE_ROW_FIELDS = (
    "overrides_active", "boosted_shards", "rf_boost",
    "writer_serial_p50_ms", "writer_serial_p99_ms",
)
RINGSCALE_FLATNESS_MAX_RATIO = 1.5
RINGSCALE_OVERRIDES_SERIAL_MAX_RATIO = 3.0


def validate_ringscale(report) -> list[str]:
    """Schema violations of a RINGSCALE artifact (empty = valid).
    v1 artifacts — ``metric == "ring_scale_sweep"`` with no
    ``schema_version`` — predate sharding and stay valid as-is; v2
    artifacts must carry the per-row fields plus the flatness and
    propagation gates documented above. Import-safe from scripts (no
    jax at module scope)."""
    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    if report.get("metric") != "ring_scale_sweep":
        return ["metric is not ring_scale_sweep"]
    if "schema_version" not in report:
        # v1 (pre-sharding): full-replica rows only; minimal contract.
        if not isinstance(report.get("results"), list) or not report["results"]:
            return ["v1 artifact has no results rows"]
        return []
    problems = [f for f in RINGSCALE_TOP_FIELDS if f not in report]
    rows = report.get("results") or []
    if not rows:
        problems.append("results is empty")
    for i, row in enumerate(rows):
        problems += [
            f"results[{i}].{f}" for f in RINGSCALE_ROW_FIELDS if f not in row
        ]
    if problems:
        return problems
    # Flatness gate: sharded bytes-per-insert must be ~independent of N.
    by_group: dict = {}
    for row in rows:
        if int(row.get("rf", 0)) > 0:
            by_group.setdefault(
                (row["rf"], row["hop_delay_ms"]), []
            ).append(row)
    for (rf, delay), group in by_group.items():
        group = sorted(group, key=lambda r: r["n_nodes"])
        if len(group) < 2:
            continue
        lo, hi = group[0], group[-1]
        ratio = hi["ring_bytes_per_insert"] / max(
            1, lo["ring_bytes_per_insert"]
        )
        if ratio > RINGSCALE_FLATNESS_MAX_RATIO:
            problems.append(
                f"flatness: rf={rf} bytes/insert grew {ratio:.2f}x from "
                f"N={lo['n_nodes']} to N={hi['n_nodes']} (max "
                f"{RINGSCALE_FLATNESS_MAX_RATIO}x) — the O(N) wall is back"
            )
    # Propagation gate: sharded owner-propagation p99 no worse than the
    # full-replica ring at the smallest size (same delay + mode — live
    # measurements and modeled sim rows are not comparable).
    for (delay, mode) in {
        (r["hop_delay_ms"], r["mode"]) for r in rows
    }:
        sub = [
            r for r in rows
            if r["hop_delay_ms"] == delay and r["mode"] == mode
        ]
        base = sorted(
            (r for r in sub if int(r.get("rf", 0)) == 0
             and r["topology"] == "ring"),
            key=lambda r: r["n_nodes"],
        )
        sharded = [r for r in sub if int(r.get("rf", 0)) > 0]
        if not base or not sharded:
            continue
        floor = base[0]
        for row in sharded:
            if row["prop_p99_ms"] > floor["prop_p99_ms"]:
                problems.append(
                    f"propagation: rf={row['rf']} N={row['n_nodes']} p99 "
                    f"{row['prop_p99_ms']}ms exceeds the full-replica "
                    f"N={floor['n_nodes']} ring's {floor['prop_p99_ms']}ms "
                    f"(delay={delay}ms, mode={mode})"
                )
    # v3: owner propagation under an ACTIVE override map (the PR 14
    # deferral). The override row already rode the propagation gate
    # above (it is a sharded row); additionally its writer-side serial
    # cost — the component a wider owner fan-out actually grows — must
    # stay within ratio of the matching no-override row.
    version = report.get("schema_version")
    if isinstance(version, int) and version >= 3:
        ov_rows = [r for r in rows if r.get("overrides_active")]
        if not ov_rows:
            problems.append(
                "v3 artifact has no overrides_active row — the "
                "owner-propagation-under-overrides measurement is the "
                "version's whole point"
            )
        for row in ov_rows:
            problems += [
                f"override row N={row.get('n_nodes')}: missing {f}"
                for f in RINGSCALE_OVERRIDE_ROW_FIELDS
                if f not in row
            ]
            if int(row.get("rf", 0)) <= 0:
                problems.append(
                    "override row must be sharded (rf > 0): overrides "
                    "mean nothing on a full-replica ring"
                )
            pair = next(
                (
                    r for r in rows
                    if not r.get("overrides_active")
                    and r["n_nodes"] == row["n_nodes"]
                    and r["rf"] == row["rf"]
                    and r["hop_delay_ms"] == row["hop_delay_ms"]
                    and r["mode"] == row["mode"]
                    and "writer_serial_p99_ms" in r
                ),
                None,
            )
            if pair is not None and "writer_serial_p99_ms" in row:
                lim = RINGSCALE_OVERRIDES_SERIAL_MAX_RATIO * max(
                    1e-6, pair["writer_serial_p99_ms"]
                )
                if row["writer_serial_p99_ms"] > lim:
                    problems.append(
                        f"overrides: N={row['n_nodes']} rf={row['rf']} "
                        f"writer-serial p99 {row['writer_serial_p99_ms']}"
                        f"ms exceeds {RINGSCALE_OVERRIDES_SERIAL_MAX_RATIO}x "
                        f"the no-override row's "
                        f"{pair['writer_serial_p99_ms']}ms"
                    )
    return problems


def validate_trace(obj) -> list[str]:
    """Schema violations of a Chrome trace-event artifact emitted by the
    flight recorder (``radixmesh_tpu/obs/trace_plane.py``) — empty list =
    valid. Pinned contract: a JSON object with a ``traceEvents`` list;
    every complete event (``ph == "X"``) carries numeric non-negative
    ``ts``/``dur`` and a ``tid``; within each tid lane the ``ts`` values
    are non-decreasing (Perfetto renders out-of-order lanes, but a
    regression here means the exporter's sort broke). Import-safe from
    artifact tests (no jax at module scope)."""
    problems: list[str] = []
    if not isinstance(obj, dict):
        return ["artifact is not a JSON object"]
    events = obj.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    last_ts: dict = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"traceEvents[{i}] is not an object")
            continue
        if ev.get("ph") != "X":
            continue  # metadata / instant events carry no duration
        ts, dur, tid = ev.get("ts"), ev.get("dur"), ev.get("tid")
        if not isinstance(ts, (int, float)) or ts < 0:
            problems.append(f"traceEvents[{i}].ts invalid: {ts!r}")
            continue
        if not isinstance(dur, (int, float)) or dur < 0:
            problems.append(f"traceEvents[{i}].dur invalid: {dur!r}")
        if tid is None:
            problems.append(f"traceEvents[{i}].tid missing")
            continue
        if ts < last_ts.get(tid, 0.0):
            problems.append(
                f"traceEvents[{i}].ts={ts} regresses within tid={tid} "
                f"(prev {last_ts[tid]})"
            )
        last_ts[tid] = ts
    return problems


# ----------------------------------------------------------------------
# FLEET stable schema (PR 3, fleet telemetry plane): one artifact per
# round recording digest fan-in, fingerprint-convergence behavior under
# churn/divergence, and health-score reaction to an injected stall
# (radixmesh_tpu/obs/fleet_plane.py + workload.run_fleet_churn_workload).
# Bump the version ONLY when adding fields (never remove or rename).
# ----------------------------------------------------------------------

FLEET_SCHEMA_VERSION = 1

FLEET_TOP_FIELDS = (
    "schema_version", "metric", "value", "unit", "workload", "nodes",
    "topology", "digest_interval_s", "digest_bytes", "digest_byte_budget",
    "fan_in", "convergence", "stall_reaction", "health_aware_demotion",
    "digests_published", "digest_frames_per_publish", "wall_s",
)
FLEET_FAN_IN_FIELDS = ("rounds", "p50_s", "max_s")
FLEET_CONVERGENCE_FIELDS = (
    "inserts", "writers", "churn_s", "max_age_during_churn_s",
    "quiesce_to_converged_s", "converged", "injected_divergence_detected",
    "age_while_diverged_s", "healed", "heal_s",
)
FLEET_STALL_FIELDS = (
    "injected", "detected", "reaction_s", "score_after", "threshold",
)


def validate_fleet(report) -> list[str]:
    """Schema violations of a FLEET artifact vs the pinned contract
    (empty = valid): all top/section fields present, the serialized
    digest within its pinned byte budget, and digest ring overhead at
    most one frame per origination. Import-safe from artifact tests (no
    jax at module scope)."""
    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    problems = [f for f in FLEET_TOP_FIELDS if f not in report]
    for section, fields in (
        ("fan_in", FLEET_FAN_IN_FIELDS),
        ("convergence", FLEET_CONVERGENCE_FIELDS),
        ("stall_reaction", FLEET_STALL_FIELDS),
    ):
        sec = report.get(section)
        if isinstance(sec, dict):
            problems += [f"{section}.{f}" for f in fields if f not in sec]
    db, budget = report.get("digest_bytes"), report.get("digest_byte_budget")
    if isinstance(db, (int, float)) and isinstance(budget, (int, float)):
        if db > budget:
            problems.append(
                f"digest_bytes {db} exceeds digest_byte_budget {budget}"
            )
    frames = report.get("digest_frames_per_publish")
    if isinstance(frames, (int, float)) and frames > 1.0 + 1e-9:
        problems.append(
            f"digest_frames_per_publish {frames} > 1 (piggyback contract)"
        )
    return problems


def build_fleet_report(res: dict) -> dict:
    """Assemble a schema-complete FLEET artifact from
    ``workload.run_fleet_churn_workload``'s result."""
    from radixmesh_tpu.obs.fleet_plane import DIGEST_BYTE_BUDGET

    conv = res.get("convergence", {})
    return {
        "schema_version": FLEET_SCHEMA_VERSION,
        "metric": "fleet_digest_fan_in_p50_s",
        "value": round(res["fan_in"]["p50_s"], 6),
        "unit": "s (one digest round visible on every node incl. router)",
        "workload": (
            f"{conv.get('inserts', 0)} inserts over "
            f"{conv.get('writers', 0)} writers + injected divergence + "
            "injected stall (inproc ring)"
        ),
        "digest_byte_budget": DIGEST_BYTE_BUDGET,
        **res,
    }


def _fleet_pass() -> dict:
    """The fleet telemetry bench: run the churn/stall workload and write
    the round's ``FLEET_r{N}.json`` (validated against the pinned
    schema before writing — a violation is recorded in the artifact, not
    silently shipped)."""
    from radixmesh_tpu.workload import run_fleet_churn_workload

    res = run_fleet_churn_workload()
    report = build_fleet_report(res)
    problems = validate_fleet(report)
    if problems:
        report["schema_violation"] = problems
        log(f"fleet pass: SCHEMA VIOLATION {problems}")
    path = os.path.join(_REPO, f"FLEET_r{current_round():02d}.json")
    with open(path, "w") as fh:
        json.dump(report, fh, indent=1)
    log(
        f"fleet pass: wrote {os.path.basename(path)} "
        f"(fan_in_p50={report['value']}s, "
        f"converged={report['convergence']['converged']}, "
        f"stall_reaction={report['stall_reaction']['reaction_s']}s)"
    )
    report["artifact"] = os.path.basename(path)
    return report


# ----------------------------------------------------------------------
# CHAOS stable schema (PR 5, self-healing mesh; v2 in PR 6, membership
# lifecycle; v3 in PR 7, request recovery): one artifact per round
# recording the chaos acceptance scenario — seeded frame loss + a
# scheduled partition (comm/faults.py) diverge replicas; the
# anti-entropy repair plane (cache/repair_plane.py) must converge every
# replica (router included) within a bounded number of repair rounds
# while requests keep being served, then go quiet.
# v2 adds the elastic-membership phases (policy/lifecycle.py): a
# graceful drain under sustained loss (zero failed requests, in-flight
# requeued-and-served, hot tokens written back, departure via LEAVE —
# never failure detection) and a cold rejoin during an active partition
# (bulk-bootstrap from a donor within the round budget, router
# withholding cache hits until convergence).
# v3 adds the crash phase (server/recovery.py): an UNCLEAN decode-node
# kill mid-stream under loss — zero failed requests, every interrupted
# stream resumed with a byte-identical delivered prefix, resurrection
# served ≥ 0.8 from the replicated cache, every recovery hop bounded by
# the admission deadline budget, hedged prefill first-writer-wins.
# Bump the version ONLY when adding fields (never remove or rename);
# v1/v2 artifacts — which predate the newer sections — stay valid.
# ----------------------------------------------------------------------

CHAOS_SCHEMA_VERSION = 4

CHAOS_TOP_FIELDS = (
    "schema_version", "metric", "value", "unit", "workload", "nodes",
    "topology", "round_budget", "fault_plan", "served", "divergence",
    "repair", "quiescence", "wall_s",
)
CHAOS_FAULT_FIELDS = (
    "seed", "drop_p", "drop_window_s", "partition_s", "partitioned_node",
    "frames_dropped", "frames_delivered",
)
CHAOS_SERVED_FIELDS = ("attempted", "ok", "ok_rate_during_fault")
CHAOS_DIVERGENCE_FIELDS = ("detected", "peak_diverged_pairs", "max_age_s")
CHAOS_REPAIR_FIELDS = (
    "converged", "converge_s", "max_episode_rounds", "within_round_budget",
    "probes_sent", "summaries_sent", "keys_pushed", "oplogs_reemitted",
    "heals",
)
CHAOS_QUIESCENCE_FIELDS = (
    "window_s", "traffic_before", "traffic_after", "quiet",
)
# v2 membership-lifecycle sections. Required when the section reports
# performed=True (a run that skipped the phase ships {"performed":
# false} and is schema-valid but gate-exempt).
CHAOS_DRAIN_FIELDS = (
    "performed", "node", "drop_p", "requeued", "requeued_served",
    "attempted_during_drain", "ok_during_drain", "zero_failed",
    "left_without_failure_detection", "writeback_tokens",
    "writeback_flushed", "drain_s",
)
CHAOS_JOIN_FIELDS = (
    "performed", "joiner", "donor_rank", "partition_active_at_join",
    "partition_s", "bootstrap_converge_s", "bootstrap_rounds",
    "round_budget", "within_round_budget", "converged_with_donor",
    "withheld_hits", "hits_to_bootstrapping",
    "fleet_converged_after_join",
)
# v3 request-recovery section (crash-mid-decode). Required when the
# section reports performed=True; {"performed": false} is schema-valid
# and gate-exempt, like the v2 sections.
CHAOS_CRASH_FIELDS = (
    "performed", "node", "drop_p", "streams", "tokens_per_stream",
    "killed_at_token", "interrupted", "resumed", "failed",
    "prefix_identical", "replayed_tokens", "replayed_cached_tokens",
    "resurrection_hit_ratio", "retries", "resurrections",
    "failover_routes", "detection", "budget", "hedge", "crash_s",
)
# The structural acceptance floor the resurrection claim rides on.
CHAOS_CRASH_MIN_HIT_RATIO = 0.8

# v4 robustness-loop sections (PR 14): heat-driven rebalancing under a
# zipf storm, and a router kill at an N>=2 multi-router front door.
# Required when performed=True; {"performed": false} is schema-valid
# and gate-exempt, the v2/v3 convention.
CHAOS_REBALANCE_FIELDS = (
    "performed", "skew_before", "skew_after", "skew_dropped", "moves",
    "max_moves_per_round", "moves_bounded", "boosted_shards", "hot_shard",
    "attempted_mid_move", "ok_mid_move", "failed_mid_move",
    "overrides_version", "overrides_converged", "handoff_entries",
    "rebalance_s",
)
CHAOS_ROUTER_KILL_FIELDS = (
    "performed", "routers", "killed", "survivor", "streams",
    "inflight_at_kill", "completed", "failed", "failovers",
    "survivor_served", "router_kill_s",
)


def _rebalance_section_problems(sec: dict) -> list[str]:
    """Gates for a performed rebalance-under-storm section (shared by
    validate_chaos and validate_rebalance): the skew score STRICTLY
    dropped, zero requests failed mid-move, movement happened and
    stayed bounded, and every node converged on the decider's override
    version."""
    problems = [
        f"rebalance.{f}" for f in CHAOS_REBALANCE_FIELDS if f not in sec
    ]
    before, after = sec.get("skew_before"), sec.get("skew_after")
    if (
        not isinstance(before, (int, float))
        or not isinstance(after, (int, float))
        or not (after < before)
    ):
        problems.append(
            f"rebalance: the zipf storm's skew score did not strictly "
            f"drop under rebalancing ({before} -> {after})"
        )
    if sec.get("failed_mid_move", 1) != 0:
        problems.append(
            f"rebalance: {sec.get('failed_mid_move')} request(s) failed "
            "mid-move — an ownership move must be invisible to traffic"
        )
    if not sec.get("moves", 0):
        problems.append(
            "rebalance: zero adopted moves (the storm never triggered "
            "the rebalancer — the drop proves nothing)"
        )
    if sec.get("moves_bounded") is not True:
        problems.append(
            f"rebalance: {sec.get('moves')} moves exceeded the per-round "
            f"bound of {sec.get('max_moves_per_round')}"
        )
    if sec.get("overrides_converged") is not True:
        problems.append(
            "rebalance: the fleet never converged on the decider's "
            "override version (split-brain owner sets)"
        )
    return problems


def _router_kill_section_problems(sec: dict) -> list[str]:
    """Gates for a performed router-kill section: N >= 2 routers, the
    kill landed mid-traffic, every in-flight request completed through
    the surviving router's edge, and the front door actually failed
    over (a kill nobody noticed proves nothing)."""
    problems = [
        f"router_kill.{f}" for f in CHAOS_ROUTER_KILL_FIELDS if f not in sec
    ]
    if int(sec.get("routers", 0) or 0) < 2:
        problems.append(
            f"router_kill: only {sec.get('routers')} router(s) — the "
            "multi-router front door needs N >= 2 to prove failover"
        )
    if sec.get("failed", 1) != 0:
        problems.append(
            f"router_kill: {sec.get('failed')} request(s) LOST to the "
            "router kill — the front door exists to make this zero"
        )
    if sec.get("completed") != sec.get("streams"):
        problems.append(
            "router_kill: in-flight requests did not all complete "
            f"({sec.get('completed')}/{sec.get('streams')})"
        )
    if not sec.get("inflight_at_kill", 0):
        problems.append(
            "router_kill: the kill interrupted zero in-flight streams "
            "(the failover path went unexercised)"
        )
    if not sec.get("failovers", 0):
        problems.append(
            "router_kill: the front door never failed over (was the "
            "victim really killed mid-traffic?)"
        )
    if sec.get("survivor_served") is not True:
        problems.append(
            "router_kill: the surviving router's edge served no "
            "post-kill routes"
        )
    return problems


def validate_chaos(report) -> list[str]:
    """Schema violations of a CHAOS artifact vs the pinned contract
    (empty = valid): all top/section fields present, plus the three
    structural acceptance gates — every replica converged, within the
    repair-round budget, and ZERO repair traffic once converged
    (quiescence). Import-safe from artifact tests and
    ``scripts/chaosbench.py`` (no jax at module scope)."""
    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    problems = [f for f in CHAOS_TOP_FIELDS if f not in report]
    for section, fields in (
        ("fault_plan", CHAOS_FAULT_FIELDS),
        ("served", CHAOS_SERVED_FIELDS),
        ("divergence", CHAOS_DIVERGENCE_FIELDS),
        ("repair", CHAOS_REPAIR_FIELDS),
        ("quiescence", CHAOS_QUIESCENCE_FIELDS),
    ):
        sec = report.get(section)
        if isinstance(sec, dict):
            problems += [f"{section}.{f}" for f in fields if f not in sec]
    rep = report.get("repair")
    if isinstance(rep, dict):
        if rep.get("converged") is not True:
            problems.append(
                "repair.converged is not True (replicas never healed)"
            )
        if rep.get("within_round_budget") is not True:
            problems.append(
                f"repair.max_episode_rounds {rep.get('max_episode_rounds')} "
                f"exceeded round_budget {report.get('round_budget')}"
            )
    div = report.get("divergence")
    if isinstance(div, dict) and div.get("detected") is not True:
        problems.append(
            "divergence.detected is not True (the fault injected nothing — "
            "the heal proves nothing)"
        )
    q = report.get("quiescence")
    if isinstance(q, dict) and q.get("quiet") is not True:
        problems.append(
            f"quiescence: repair traffic kept flowing after convergence "
            f"({q.get('traffic_before')} → {q.get('traffic_after')})"
        )
    # v2 membership-lifecycle sections + gates (v1 artifacts predate
    # them and stay valid without).
    v2 = int(report.get("schema_version", 0) or 0) >= 2
    drain = report.get("drain")
    if v2 and not isinstance(drain, dict):
        problems.append("drain section missing (schema v2)")
    if isinstance(drain, dict) and drain.get("performed"):
        problems += [
            f"drain.{f}" for f in CHAOS_DRAIN_FIELDS if f not in drain
        ]
        if drain.get("zero_failed") is not True:
            problems.append(
                "drain: requests failed during the graceful drain "
                f"({drain.get('ok_during_drain')}/"
                f"{drain.get('attempted_during_drain')} ok, "
                f"{drain.get('requeued_served')}/{drain.get('requeued')} "
                "requeued-and-served)"
            )
        if drain.get("requeued_served") != drain.get("requeued"):
            problems.append(
                "drain: parked requests were requeued but not all served "
                f"({drain.get('requeued_served')}/{drain.get('requeued')})"
            )
        if drain.get("left_without_failure_detection") is not True:
            problems.append(
                "drain: the planned departure tripped failure detection "
                "(a 'dead'-cause successor transition fired)"
            )
        if drain.get("writeback_flushed") is not True:
            problems.append(
                "drain: hot prefixes were not written back before LEAVE"
            )
    join = report.get("join")
    if v2 and not isinstance(join, dict):
        problems.append("join section missing (schema v2)")
    if isinstance(join, dict) and join.get("performed"):
        problems += [
            f"join.{f}" for f in CHAOS_JOIN_FIELDS if f not in join
        ]
        if join.get("converged_with_donor") is not True:
            problems.append(
                "join: the bootstrapping node never converged with its "
                "donor"
            )
        if join.get("within_round_budget") is not True:
            problems.append(
                f"join: bootstrap took {join.get('bootstrap_rounds')} "
                f"rounds, over the budget of {join.get('round_budget')}"
            )
        if join.get("hits_to_bootstrapping", 0) != 0:
            problems.append(
                "join: the router routed cache hits to a BOOTSTRAPPING "
                f"node ({join.get('hits_to_bootstrapping')} times)"
            )
        if not join.get("withheld_hits", 0) and not int(
            report.get("replication_factor", 0) or 0
        ):
            # Sharded runs (replication_factor > 0) are exempt: the
            # router routes from owner summaries there, and a COLD
            # joiner advertises no warmth — there is never a hit to
            # withhold, and hits_to_bootstrapping == 0 (gated above) is
            # the whole invariant.
            problems.append(
                "join: the router never withheld a hit during bootstrap "
                "(the withhold path went unexercised — the gate proves "
                "nothing)"
            )
    # v3 request-recovery section + gates (v1/v2 artifacts predate it
    # and stay valid without).
    v3 = int(report.get("schema_version", 0) or 0) >= 3
    crash = report.get("crash")
    if v3 and not isinstance(crash, dict):
        problems.append("crash section missing (schema v3)")
    if isinstance(crash, dict) and crash.get("performed"):
        problems += [
            f"crash.{f}" for f in CHAOS_CRASH_FIELDS if f not in crash
        ]
        if crash.get("failed") != 0:
            problems.append(
                f"crash: {crash.get('failed')} request(s) LOST to the "
                "unclean kill — a node death must be a latency blip, "
                "never a request loss"
            )
        if not crash.get("interrupted", 0):
            problems.append(
                "crash: the kill interrupted zero live streams (the "
                "resurrection path went unexercised — the gate proves "
                "nothing)"
            )
        if crash.get("resumed") != crash.get("interrupted"):
            problems.append(
                "crash: interrupted streams were not all resurrected "
                f"({crash.get('resumed')}/{crash.get('interrupted')})"
            )
        if crash.get("prefix_identical") is not True:
            problems.append(
                "crash: a resumed stream re-emitted, skipped, or "
                "corrupted already-delivered tokens (prefix not "
                "byte-identical)"
            )
        ratio = crash.get("resurrection_hit_ratio")
        if not isinstance(ratio, (int, float)) or (
            ratio < CHAOS_CRASH_MIN_HIT_RATIO
        ):
            problems.append(
                f"crash: resurrection cache-hit ratio {ratio} below "
                f"{CHAOS_CRASH_MIN_HIT_RATIO} — replay recomputed what "
                "the replicated tree should have served"
            )
        budget = crash.get("budget")
        if not isinstance(budget, dict) or (
            budget.get("within_one_backoff") is not True
        ):
            problems.append(
                "crash: a recovered request overran its admission "
                "deadline by more than one retry backoff (the budget "
                "was not threaded through every hop)"
            )
        hedge = crash.get("hedge")
        if isinstance(hedge, dict) and hedge.get("fired"):
            if hedge.get("first_writer_wins") is not True:
                problems.append(
                    "crash: the hedge's first successful writer did "
                    "not win"
                )
            if hedge.get("loser_cancelled") is not True:
                problems.append(
                    "crash: the hedge loser was not cancelled (its "
                    "pages would leak)"
                )
    # v4 robustness-loop sections + gates (v1-v3 artifacts predate them
    # and stay valid without).
    v4 = int(report.get("schema_version", 0) or 0) >= 4
    reb = report.get("rebalance")
    if v4 and not isinstance(reb, dict):
        problems.append("rebalance section missing (schema v4)")
    if isinstance(reb, dict) and reb.get("performed"):
        problems += _rebalance_section_problems(reb)
    rk = report.get("router_kill")
    if v4 and not isinstance(rk, dict):
        problems.append("router_kill section missing (schema v4)")
    if isinstance(rk, dict) and rk.get("performed"):
        problems += _router_kill_section_problems(rk)
    return problems


def build_chaos_report(res: dict) -> dict:
    """Assemble a schema-complete CHAOS artifact from
    ``workload.run_chaos_workload``'s result."""
    fp = res.get("fault_plan", {})
    rep = res.get("repair", {})
    return {
        "schema_version": CHAOS_SCHEMA_VERSION,
        "metric": "chaos_heal_converge_s",
        "value": rep.get("converge_s"),
        "unit": "s from fault-window close to ALL replicas (P/D/router) "
        "pairwise fingerprint-equal via anti-entropy repair",
        "workload": (
            f"{int(100 * fp.get('drop_p', 0))}% seeded frame loss for "
            f"{fp.get('drop_window_s', 0)}s + {fp.get('partition_s', 0)}s "
            f"symmetric partition of {fp.get('partitioned_node')} while "
            "routed requests keep flowing, then a graceful drain under "
            "re-opened loss, a cold rejoin during a fresh partition, "
            "and an unclean decode-node kill mid-stream with "
            "request resurrection from the replicated prefix cache "
            "(inproc ring; see workload.run_chaos_workload)"
        ),
        **res,
    }


def _chaos_pass() -> dict:
    """The self-healing bench: run the chaos acceptance scenario and
    write the round's ``CHAOS_r{N}.json`` (validated against the pinned
    schema before writing — a violation is recorded in the artifact,
    not silently shipped)."""
    from radixmesh_tpu.workload import run_chaos_workload

    res = run_chaos_workload()
    report = build_chaos_report(res)
    problems = validate_chaos(report)
    if problems:
        report["schema_violation"] = problems
        log(f"chaos pass: SCHEMA VIOLATION {problems}")
    path = os.path.join(_REPO, f"CHAOS_r{current_round():02d}.json")
    with open(path, "w") as fh:
        json.dump(report, fh, indent=1)
    log(
        f"chaos pass: wrote {os.path.basename(path)} "
        f"(converged={report['repair']['converged']} in "
        f"{report['repair']['converge_s']}s / "
        f"{report['repair']['max_episode_rounds']} rounds, "
        f"served_ok={report['served']['ok_rate_during_fault']}, "
        f"quiet={report['quiescence']['quiet']})"
    )
    report["artifact"] = os.path.basename(path)
    return report


# ----------------------------------------------------------------------
# OBS stable schema (PR 9, mesh-wide observability plane): one artifact
# per round recording the three legs of workload.run_obs_workload —
# (a) cross-node trace stitching (crash+resurrection under full tracing,
# one Perfetto export, interrupted request on >= OBS_MIN_NODE_TRACKS
# node tracks under a single trace id), (b) per-shard heat & skew (zipf
# inserts drive the skew score; the router names the hot shard + owner
# set from gossip alone), and (c) TPU step attribution (per-wave MFU +
# pad fraction for prefill AND decode), plus the wire gate (traceless
# frames bit-for-bit pre-PR-9). Bump the version ONLY when adding fields
# (never remove or rename).
# ----------------------------------------------------------------------

OBS_SCHEMA_VERSION = 1

OBS_TOP_FIELDS = (
    "schema_version", "metric", "value", "unit", "workload", "nodes",
    "topology", "replication_factor", "stitch", "heat", "steps", "wire",
    "wall_s",
)
OBS_STITCH_FIELDS = (
    "performed", "node", "streams", "tokens_per_stream", "interrupted",
    "resumed", "failed", "trace_id", "node_tracks", "nodes_on_track",
    "replication_edges", "publish_edges", "span_count", "stitched_events",
)
OBS_HEAT_FIELDS = (
    "performed", "inserts", "distinct_keys", "zipf_alpha", "skew_score",
    "hot_shard", "expected_hot_shard", "hot_owners", "expected_hot_owners",
    "owner_set_correct", "reporters",
)
OBS_STEP_FIELDS = ("performed", "n_params", "peak_tflops", "prefill", "decode")
OBS_WAVE_FIELDS = ("waves", "real_tokens", "padded_tokens", "mfu", "pad_fraction")
OBS_WIRE_FIELDS = (
    "rf0_traceless_unchanged", "trace_trailer_roundtrip", "trailer_bytes",
)
# Structural acceptance floors.
OBS_MIN_NODE_TRACKS = 3
OBS_MIN_SKEW_SCORE = 2.0


def validate_obs(report) -> list[str]:
    """Schema violations of an OBS artifact vs the pinned contract
    (empty = valid). Gates: the stitched trace shows the interrupted
    request on >= OBS_MIN_NODE_TRACKS node tracks under ONE trace id
    with replication edges visible and zero lost streams; the zipf hot
    shard is detected with the correct owner set and a skew score above
    the floor; per-wave MFU + pad fraction are reported for BOTH
    prefill and decode; and the traceless wire is bit-for-bit the
    pre-trace encoding. Sections with performed=False are schema-valid
    but gate-exempt (the CHAOS v2/v3 convention). Import-safe from
    artifact tests and scripts/obsbench.py (no jax at module scope)."""
    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    problems = [f for f in OBS_TOP_FIELDS if f not in report]
    stitch = report.get("stitch")
    if isinstance(stitch, dict) and stitch.get("performed"):
        problems += [
            f"stitch.{f}" for f in OBS_STITCH_FIELDS if f not in stitch
        ]
        if stitch.get("failed") != 0:
            problems.append(
                f"stitch: {stitch.get('failed')} stream(s) LOST during the "
                "traced crash drill"
            )
        if not stitch.get("interrupted", 0):
            problems.append(
                "stitch: the kill interrupted zero live streams (the "
                "cross-node path went unexercised)"
            )
        if stitch.get("resumed") != stitch.get("interrupted"):
            problems.append(
                "stitch: interrupted streams were not all resurrected "
                f"({stitch.get('resumed')}/{stitch.get('interrupted')})"
            )
        tracks = stitch.get("node_tracks")
        if not isinstance(tracks, int) or tracks < OBS_MIN_NODE_TRACKS:
            problems.append(
                f"stitch: interrupted request spans only {tracks} node "
                f"track(s) (< {OBS_MIN_NODE_TRACKS}) — the journey did "
                "not stitch"
            )
        if not stitch.get("replication_edges", 0):
            problems.append(
                "stitch: no replication edges under the trace id (the "
                "oplog trace trailer never landed receiver-side)"
            )
    heat = report.get("heat")
    if isinstance(heat, dict) and heat.get("performed"):
        problems += [f"heat.{f}" for f in OBS_HEAT_FIELDS if f not in heat]
        skew = heat.get("skew_score")
        if not isinstance(skew, (int, float)) or skew < OBS_MIN_SKEW_SCORE:
            problems.append(
                f"heat: skew score {skew} below {OBS_MIN_SKEW_SCORE} — the "
                "zipf workload failed to drive (or the plane failed to "
                "measure) a hot shard"
            )
        if heat.get("hot_shard") != heat.get("expected_hot_shard"):
            problems.append(
                f"heat: detected hot shard {heat.get('hot_shard')} != "
                f"ground truth {heat.get('expected_hot_shard')}"
            )
        if heat.get("owner_set_correct") is not True:
            problems.append(
                "heat: the hot shard's owner set was not correctly named "
                f"({heat.get('hot_owners')} vs "
                f"{heat.get('expected_hot_owners')})"
            )
        if not heat.get("reporters", 0):
            problems.append("heat: zero heat reporters (gossip never folded)")
    steps = report.get("steps")
    if isinstance(steps, dict) and steps.get("performed"):
        problems += [f"steps.{f}" for f in OBS_STEP_FIELDS if f not in steps]
        for kind in ("prefill", "decode"):
            wave = steps.get(kind)
            if not isinstance(wave, dict):
                continue
            problems += [
                f"steps.{kind}.{f}" for f in OBS_WAVE_FIELDS if f not in wave
            ]
            if not wave.get("waves", 0):
                problems.append(f"steps: zero {kind} waves accounted")
            mfu = wave.get("mfu")
            if not isinstance(mfu, (int, float)) or not (mfu > 0):
                problems.append(
                    f"steps: {kind} MFU {mfu!r} not a positive number"
                )
            pad = wave.get("pad_fraction")
            if not isinstance(pad, (int, float)) or not (0.0 <= pad < 1.0):
                problems.append(
                    f"steps: {kind} pad fraction {pad!r} outside [0, 1)"
                )
    wire = report.get("wire")
    if isinstance(wire, dict):
        problems += [f"wire.{f}" for f in OBS_WIRE_FIELDS if f not in wire]
        if wire.get("rf0_traceless_unchanged") is not True:
            problems.append(
                "wire: a traceless frame is NOT bit-for-bit the pre-trace "
                "encoding (tracing off must cost zero wire bytes)"
            )
        if wire.get("trace_trailer_roundtrip") is not True:
            problems.append("wire: the trace trailer did not round-trip")
    return problems


def build_obs_report(res: dict) -> dict:
    """Assemble a schema-complete OBS artifact from
    ``workload.run_obs_workload``'s result."""
    stitch = res.get("stitch", {})
    heat = res.get("heat", {})
    return {
        "schema_version": OBS_SCHEMA_VERSION,
        "metric": "obs_stitched_node_tracks",
        "value": stitch.get("node_tracks"),
        "unit": (
            "node tracks carrying the interrupted request's spans in ONE "
            "stitched Perfetto trace under a single 64-bit trace id"
        ),
        "workload": (
            f"{stitch.get('streams', 0)} traced streams, busiest decode "
            "node killed mid-stream, resurrection on the survivor "
            f"(rf={res.get('replication_factor')}); zipf(alpha="
            f"{heat.get('zipf_alpha')}) inserts over "
            f"{heat.get('distinct_keys')} subtree roots for the heat map; "
            "tiny-engine burst for step attribution "
            "(see workload.run_obs_workload)"
        ),
        **res,
    }


# ----------------------------------------------------------------------
# KVFLOW stable schema (PR 4, async KV-movement plane): one artifact per
# round recording restore-stall vs overlapped TTFT, write-back gather
# fusion, and prefetch hit-ahead rate (radixmesh_tpu/cache/kv_transfer.py
# + workload.run_kvflow_workload). Bump the version ONLY when adding
# fields (never remove or rename).
# ----------------------------------------------------------------------

KVFLOW_SCHEMA_VERSION = 1

KVFLOW_TOP_FIELDS = (
    "schema_version", "metric", "value", "unit", "workload",
    "restore", "writeback", "prefetch", "chunk_tokens",
    "ttft_chunk_tokens", "page_size", "wall_s",
)
KVFLOW_RESTORE_FIELDS = (
    "requests", "repeats", "sync_ttft_s", "overlapped_ttft_s",
    "overlap_ratio", "overlap_wins", "sync_ttft_trials_s",
    "overlapped_ttft_trials_s", "sync_restore_ttft_s",
    "overlapped_restore_ttft_s", "sync_fresh_ttft_s",
    "overlapped_fresh_ttft_s", "restored_tokens", "parked_requests",
    "decode_steps_during_restore", "sync_decode_steps_during_restore",
    "max_decode_gap_s", "sync_max_decode_gap_s",
)
KVFLOW_WRITEBACK_FIELDS = (
    "tokens_written_back", "sweeps", "gathers", "gathers_per_sweep",
    "sync_gathers_per_sweep", "evict_stall_s", "sync_evict_stall_s",
)
KVFLOW_PREFETCH_FIELDS = ("hints_sent", "hints_joined", "hit_ahead_rate")


def validate_kvflow(report) -> list[str]:
    """Schema violations of a KVFLOW artifact vs the pinned contract
    (empty = valid): all top/section fields present, plus the two
    deterministic structural contracts — write-back gathers fused to at
    most one per eviction sweep, and decode progress strictly greater
    than the synchronous path's zero while a restore is in flight. The
    TTFT comparison is REPORTED (``overlap_wins``), not schema-gated:
    on CPU it measures scheduling structure against ms-scale noise.
    Import-safe from artifact tests (no jax at module scope)."""
    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    problems = [f for f in KVFLOW_TOP_FIELDS if f not in report]
    for section, fields in (
        ("restore", KVFLOW_RESTORE_FIELDS),
        ("writeback", KVFLOW_WRITEBACK_FIELDS),
        ("prefetch", KVFLOW_PREFETCH_FIELDS),
    ):
        sec = report.get(section)
        if isinstance(sec, dict):
            problems += [f"{section}.{f}" for f in fields if f not in sec]
    wb = report.get("writeback")
    if isinstance(wb, dict):
        for key in ("gathers_per_sweep", "sync_gathers_per_sweep"):
            g = wb.get(key)
            if isinstance(g, (int, float)) and g > 1.0 + 1e-9:
                problems.append(
                    f"writeback.{key} {g} > 1 (fused-gather contract)"
                )
    rs = report.get("restore")
    if isinstance(rs, dict):
        a = rs.get("decode_steps_during_restore")
        s = rs.get("sync_decode_steps_during_restore")
        if isinstance(a, (int, float)) and isinstance(s, (int, float)):
            if not a > s:
                problems.append(
                    f"restore.decode_steps_during_restore {a} must exceed "
                    f"the synchronous path's {s} (decode-never-blocks "
                    "contract)"
                )
    return problems


def build_kvflow_report(res: dict) -> dict:
    """Assemble a schema-complete KVFLOW artifact from
    ``workload.run_kvflow_workload``'s result."""
    rs = res.get("restore", {})
    return {
        "schema_version": KVFLOW_SCHEMA_VERSION,
        "metric": "kv_restore_overlapped_ttft_ratio",
        "value": rs.get("overlap_ratio"),
        "unit": "overlapped/sync mean TTFT of a mixed restore+fresh burst "
        "(<= 1: staging restores off the scheduling thread stops fresh "
        "admissions convoying behind inline KV copies)",
        "workload": (
            f"{rs.get('requests', 0)} host-tier restore requests "
            f"interleaved with {rs.get('requests', 0)} fresh requests x "
            f"{rs.get('repeats', 0)} interleaved trials + background-"
            "decode overlap phase + prefetch hit-ahead phase (CPU-sized "
            "engine; see workload.run_kvflow_workload)"
        ),
        **res,
    }


def _kvflow_pass() -> dict:
    """The KV-movement bench: run the kvflow workload and write the
    round's ``KVFLOW_r{N}.json`` (validated against the pinned schema
    before writing — a violation is recorded in the artifact, not
    silently shipped)."""
    from radixmesh_tpu.workload import run_kvflow_workload

    res = run_kvflow_workload()
    report = build_kvflow_report(res)
    problems = validate_kvflow(report)
    if problems:
        report["schema_violation"] = problems
        log(f"kvflow pass: SCHEMA VIOLATION {problems}")
    path = os.path.join(_REPO, f"KVFLOW_r{current_round():02d}.json")
    with open(path, "w") as fh:
        json.dump(report, fh, indent=1)
    log(
        f"kvflow pass: wrote {os.path.basename(path)} "
        f"(overlap_ratio={report['value']}, "
        f"overlap_wins={report['restore']['overlap_wins']}, "
        f"hit_ahead={report['prefetch']['hit_ahead_rate']})"
    )
    report["artifact"] = os.path.basename(path)
    return report


# ----------------------------------------------------------------------
# ANALYSIS stable schema (PR 10, meshcheck): the static-analysis plane's
# artifact. One JSON per round recording (a) zero unsuppressed findings
# over the product tree, (b) every positive-control fixture tripping its
# checker — a "clean" report is only evidence when the controls prove
# the checkers still see the bug classes they claim to — and (c) the
# full justification-comment ledger, so reviewers audit the excuses,
# not grep for them. scripts/meshcheck.py emits this shape and
# validates against it before writing.
# ----------------------------------------------------------------------

ANALYSIS_SCHEMA_VERSION = 2

# Every checker the default meshcheck run must include — a report that
# silently dropped a checker would read as clean while checking less.
# v2 (PR 11) adds the concurrency plane: thread-roots / guarded-by /
# protocol. v1 artifacts validate against the v1 tuple.
ANALYSIS_CHECKER_IDS_V1 = (
    "lock-order", "single-writer", "hot-path", "wire-kinds",
    "metrics-vocab",
)
ANALYSIS_CHECKER_IDS = ANALYSIS_CHECKER_IDS_V1 + (
    "thread-roots", "guarded-by", "protocol",
)

ANALYSIS_TOP_FIELDS = (
    "schema_version", "metric", "value", "package", "files_indexed",
    "checkers", "findings", "suppressions", "positive_controls", "clean",
)
# v2: the derived thread map rides the artifact (root count + entries) —
# a concurrency verdict is only auditable alongside the roots it assumed.
ANALYSIS_TOP_FIELDS_V2 = ANALYSIS_TOP_FIELDS + ("thread_roots",)
ANALYSIS_CHECKER_FIELDS = (
    "id", "description", "raw_findings", "kept_findings", "suppressed",
)
# v2: per-checker positive-control accounting (count + tripped count).
ANALYSIS_CHECKER_FIELDS_V2 = ANALYSIS_CHECKER_FIELDS + (
    "controls", "controls_tripped",
)
ANALYSIS_CONTROL_FIELDS = ("fixture", "invariant", "file", "line", "tripped")
ANALYSIS_SUPPRESSION_FIELDS = (
    "file", "line", "scope", "invariants", "justification",
)
ANALYSIS_THREAD_ROOT_FIELDS = ("name", "target", "file", "line", "multi", "kind")


def validate_analysis(report) -> list[str]:
    """Schema violations of an ANALYSIS artifact vs the pinned contract
    (empty = valid). Gates: ZERO unsuppressed findings on the tree, all
    default checkers present (version-matched set), every positive
    control tripped, every suppression carrying a non-empty
    justification, and (v2) a non-empty thread map. v1 artifacts stay
    valid against the v1 field/checker sets. Import-safe from artifact
    tests and scripts/meshcheck.py (no jax at module scope)."""
    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    version = report.get("schema_version", 1)
    v2 = isinstance(version, int) and version >= 2
    top_fields = ANALYSIS_TOP_FIELDS_V2 if v2 else ANALYSIS_TOP_FIELDS
    checker_ids = ANALYSIS_CHECKER_IDS if v2 else ANALYSIS_CHECKER_IDS_V1
    checker_fields = (
        ANALYSIS_CHECKER_FIELDS_V2 if v2 else ANALYSIS_CHECKER_FIELDS
    )
    problems = [f for f in top_fields if f not in report]

    findings = report.get("findings")
    if not isinstance(findings, list):
        problems.append("findings is not a list")
    elif findings:
        problems.append(
            f"{len(findings)} unsuppressed finding(s) on the tree — the "
            "gate is zero (fix them or justify in-source)"
        )
    if report.get("clean") is not (findings == []):
        problems.append("clean flag disagrees with the findings list")

    checkers = report.get("checkers")
    if not isinstance(checkers, list):
        problems.append("checkers is not a list")
    else:
        seen = set()
        for c in checkers:
            if not isinstance(c, dict):
                problems.append("checkers entry is not an object")
                continue
            problems += [
                f"checkers[{c.get('id', '?')}].{f}"
                for f in checker_fields if f not in c
            ]
            seen.add(c.get("id"))
        for cid in checker_ids:
            if cid not in seen:
                problems.append(
                    f"checker {cid!r} missing from the report — the run "
                    "checked less than the default plane"
                )

    controls = report.get("positive_controls")
    if not isinstance(controls, list) or not controls:
        problems.append(
            "positive_controls empty — a clean tree proves nothing "
            "unless the checkers demonstrably still trip"
        )
    else:
        for c in controls:
            if not isinstance(c, dict):
                problems.append("positive_controls entry is not an object")
                continue
            problems += [
                f"positive_controls[{c.get('fixture', '?')}].{f}"
                for f in ANALYSIS_CONTROL_FIELDS if f not in c
            ]
            if c.get("tripped") is not True:
                problems.append(
                    f"positive control NOT tripped: {c.get('fixture')} "
                    f"{c.get('invariant')} at {c.get('file')}:"
                    f"{c.get('line')} — the checker went blind"
                )

    sups = report.get("suppressions")
    if isinstance(sups, list):
        for s in sups:
            if not isinstance(s, dict):
                problems.append("suppressions entry is not an object")
                continue
            problems += [
                f"suppressions[{s.get('file', '?')}:{s.get('line', '?')}].{f}"
                for f in ANALYSIS_SUPPRESSION_FIELDS if f not in s
            ]
            if not str(s.get("justification", "")).strip():
                problems.append(
                    f"suppression at {s.get('file')}:{s.get('line')} has "
                    "no justification — that is silencing, not excusing"
                )
    elif sups is not None:
        problems.append("suppressions is not a list")

    if v2:
        roots = report.get("thread_roots")
        if not isinstance(roots, dict):
            problems.append("thread_roots is not an object")
        else:
            count = roots.get("count")
            entries = roots.get("roots")
            if not isinstance(count, int) or count < 1:
                problems.append(
                    "thread_roots.count < 1 — a concurrency plane that "
                    "found no thread roots checked nothing"
                )
            if not isinstance(entries, list) or len(entries) != (count or 0):
                problems.append("thread_roots.roots disagrees with count")
            else:
                for r in entries:
                    problems += [
                        f"thread_roots[{r.get('name', '?')}].{f}"
                        for f in ANALYSIS_THREAD_ROOT_FIELDS if f not in r
                    ]
    return problems


def build_analysis_report(
    result, controls, files_indexed: int, thread_roots=None
) -> dict:
    """Assemble a schema-complete ANALYSIS artifact from a framework
    :class:`~radixmesh_tpu.analysis.core.AnalysisResult` plus the
    positive-control expectations (``analysis/controls.py``) and (v2)
    the derived thread map (``analysis/thread_roots.py``)."""
    checkers_meta = []
    from radixmesh_tpu.analysis import all_checkers

    # invariant-id -> checker-id, for the per-checker control counts;
    # framework invariants (syntax/suppression grammar/staleness) are
    # controls on the framework itself.
    owner: dict = {}
    checkers = all_checkers()
    for checker in checkers:
        for inv in getattr(checker, "invariants", ()):
            owner[inv] = checker.id
    for checker in checkers:
        raw = result.raw_by_checker.get(checker.id, [])
        kept = result.kept_by_checker.get(checker.id, [])
        mine = [c for c in controls if owner.get(c.invariant) == checker.id]
        checkers_meta.append({
            "id": checker.id,
            "description": checker.description,
            "raw_findings": len(raw),
            "kept_findings": len(kept),
            "suppressed": len(raw) - len(kept),
            "controls": len(mine),
            "controls_tripped": sum(c.tripped for c in mine),
        })
    root_entries = [r.as_dict() for r in (thread_roots or [])]
    return {
        "schema_version": ANALYSIS_SCHEMA_VERSION,
        "metric": "unsuppressed_findings",
        "value": len(result.findings),
        "package": "radixmesh_tpu",
        "files_indexed": files_indexed,
        "thread_roots": {"count": len(root_entries), "roots": root_entries},
        "checkers": checkers_meta,
        "findings": [
            {
                "file": f.file, "line": f.line,
                "invariant": f.invariant, "message": f.message,
            }
            for f in result.findings
        ],
        "suppressions": [
            {
                "file": s.file, "line": s.line, "scope": s.scope,
                "invariants": list(s.invariants),
                "justification": s.justification,
                "used": s.used,
            }
            for s in result.suppressions
        ],
        "positive_controls": [c.as_dict() for c in controls],
        "clean": not result.findings,
    }


# ----------------------------------------------------------------------
# DOCTOR stable schema (PR 12, the diagnosis plane): the acceptance
# artifact for ``obs/doctor.py`` + ``obs/attribution.py``. One JSON per
# round recording (a) ZERO findings over a provably healthy cluster
# phase with every rule running, (b) three deterministically seeded
# pathologies each NAMED by the doctor with correct pinned evidence
# (the hot shard's true owner set, the convoying shape, the throttled
# restore lane), (c) the critical-path decomposition summing to e2e
# within epsilon on every audited request, and (d) the benchdiff
# sentinel proving ``compare_rounds`` flags a synthetic regression while
# passing an identical pair. ``workload.run_doctor_workload`` produces
# the data; ``scripts/doctor.py --workload`` emits the artifact. Bump
# the version ONLY when adding fields (never remove or rename).
# ----------------------------------------------------------------------

# v2 (PR 14): the healthy-phase rules_checked gate grew the
# rebalancer_asleep rule. v3 (PR 15): it grew tier_thrash (the durable
# KV tier's flapping detector). v4 (PR 17): it grew the three fleet
# rules (straggler_node, fleet_burn_slope, telemetry_gap) judged over
# the FleetAggregator's cross-node store. v5 (PR 18): it grew the three
# token-plane rules (decode_stall, spec_misconfigured,
# goodput_regression) judged over the per-token timeline, the
# speculation ledger, and the history ring's goodput series. Artifacts
# validate against the rule set pinned for THEIR version (see
# _required_doctor_rules) — a checked-in artifact can never
# retroactively have run a rule that postdates it.
DOCTOR_SCHEMA_VERSION = 5

DOCTOR_TOP_FIELDS = (
    "schema_version", "metric", "value", "unit", "workload", "nodes",
    "topology", "replication_factor", "healthy", "pathologies",
    "attribution", "benchdiff", "wall_s",
)
DOCTOR_HEALTHY_FIELDS = (
    "performed", "findings", "rules_checked", "inputs", "audited_requests",
)
# Every pathology section: did the doctor fire the right rule, and did
# the finding's evidence match the seeded ground truth.
DOCTOR_PATHOLOGY_FIELDS = (
    "performed", "rule", "detected", "evidence_correct", "score",
    "summary", "evidence", "expected",
)
# The three seeded pathologies the acceptance run must name.
DOCTOR_PATHOLOGIES = ("hot_shard", "prefill_convoy", "restore_park_stall")
DOCTOR_ATTRIBUTION_FIELDS = (
    "audited", "refused", "max_sum_error_s", "epsilon_s", "sums_ok",
    "phases",
)
DOCTOR_BENCHDIFF_FIELDS = (
    "identical_clean", "regression_flagged", "mismatch_detected",
)
# |sum(exclusive phase times) - e2e| ceiling per audited request: the
# decomposition is exact by construction (each elementary segment lands
# in exactly one phase), so only float addition error is tolerated.
DOCTOR_SUM_EPSILON_S = 1e-6


# Doctor rules that existed when the v1 DOCTOR/BLACKBOX artifacts were
# pinned. Rules added later (rebalancer_asleep, PR 14) are required of
# artifacts emitted at HIGHER schema versions only — a checked-in v1
# artifact's healthy phase can never retroactively have run a rule that
# postdates it.
DOCTOR_RULES_V1 = (
    "hot_shard", "prefill_convoy", "restore_park_stall",
    "replication_lag", "slo_burn_rate", "spec_efficiency",
)
DOCTOR_RULES_V2 = DOCTOR_RULES_V1 + ("rebalancer_asleep",)
DOCTOR_RULES_V3 = DOCTOR_RULES_V2 + ("tier_thrash",)
DOCTOR_RULES_V4 = DOCTOR_RULES_V3 + (
    "straggler_node", "fleet_burn_slope", "telemetry_gap",
)


def _required_doctor_rules(report, live_rules) -> list[str]:
    version = int(report.get("schema_version", 0) or 0)
    if version <= 1:
        return [r for r in live_rules if r in DOCTOR_RULES_V1]
    if version == 2:
        return [r for r in live_rules if r in DOCTOR_RULES_V2]
    if version == 3:
        return [r for r in live_rules if r in DOCTOR_RULES_V3]
    if version == 4:
        return [r for r in live_rules if r in DOCTOR_RULES_V4]
    return list(live_rules)


def validate_doctor(report) -> list[str]:
    """Schema violations of a DOCTOR artifact vs the pinned contract
    (empty = valid). Gates: the healthy phase ran ALL rules and found
    nothing; each seeded pathology was detected by its rule with
    evidence matching the seeded ground truth (carrying at least the
    rule's pinned evidence fields); every audited request's phase
    decomposition summed to its e2e within epsilon with zero holed-trace
    refusals; and the benchdiff sentinel passed an identical pair while
    flagging a synthetic regression and a schema mismatch. Sections with
    performed=False are schema-valid but gate-exempt (the CHAOS v2/v3
    convention). Import-safe from artifact tests and scripts/doctor.py
    (no jax at module scope)."""
    from radixmesh_tpu.obs.doctor import RULE_EVIDENCE_FIELDS, RULES

    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    problems = [f for f in DOCTOR_TOP_FIELDS if f not in report]
    healthy = report.get("healthy")
    if isinstance(healthy, dict) and healthy.get("performed"):
        problems += [
            f"healthy.{f}" for f in DOCTOR_HEALTHY_FIELDS if f not in healthy
        ]
        if healthy.get("findings") != []:
            problems.append(
                "healthy: the doctor reported findings on the healthy "
                f"phase ({healthy.get('findings')}) — a diagnosis plane "
                "that cries wolf gets muted"
            )
        checked = healthy.get("rules_checked") or []
        missing_rules = [
            r for r in _required_doctor_rules(report, RULES)
            if r not in checked
        ]
        if missing_rules:
            problems.append(
                f"healthy: rules {missing_rules} never ran — 'no findings' "
                "is only evidence when every rule looked"
            )
        if not healthy.get("audited_requests", 0):
            problems.append(
                "healthy: zero audited requests — the healthy verdict "
                "never saw real traffic"
            )
    pathologies = report.get("pathologies")
    if isinstance(pathologies, dict):
        problems += [
            f"pathologies.{p}" for p in DOCTOR_PATHOLOGIES
            if p not in pathologies
        ]
        for name in DOCTOR_PATHOLOGIES:
            sec = pathologies.get(name)
            if not isinstance(sec, dict) or not sec.get("performed"):
                continue
            problems += [
                f"pathologies.{name}.{f}"
                for f in DOCTOR_PATHOLOGY_FIELDS
                if f not in sec
            ]
            if sec.get("detected") is not True:
                problems.append(
                    f"pathologies.{name}: the seeded pathology was NOT "
                    "detected"
                )
            if sec.get("evidence_correct") is not True:
                problems.append(
                    f"pathologies.{name}: finding evidence does not match "
                    f"the seeded ground truth ({sec.get('evidence')} vs "
                    f"expected {sec.get('expected')})"
                )
            ev = sec.get("evidence")
            if isinstance(ev, dict):
                missing_ev = [
                    k
                    for k in RULE_EVIDENCE_FIELDS.get(sec.get("rule"), ())
                    if k not in ev
                ]
                if missing_ev:
                    problems.append(
                        f"pathologies.{name}: evidence missing pinned "
                        f"fields {missing_ev}"
                    )
    attribution = report.get("attribution")
    if isinstance(attribution, dict):
        problems += [
            f"attribution.{f}"
            for f in DOCTOR_ATTRIBUTION_FIELDS
            if f not in attribution
        ]
        if not attribution.get("audited", 0):
            problems.append("attribution: zero audited waterfalls")
        if attribution.get("sums_ok") is not True:
            problems.append(
                "attribution: phase decomposition did NOT sum to e2e "
                f"within epsilon (max error "
                f"{attribution.get('max_sum_error_s')}s > "
                f"{attribution.get('epsilon_s')}s)"
            )
        if attribution.get("refused", 0):
            problems.append(
                f"attribution: {attribution.get('refused')} holed-trace "
                "refusal(s) during the acceptance run (the recorder ring "
                "was sized to lose nothing)"
            )
    bd = report.get("benchdiff")
    if isinstance(bd, dict):
        problems += [
            f"benchdiff.{f}" for f in DOCTOR_BENCHDIFF_FIELDS if f not in bd
        ]
        if bd.get("identical_clean") is not True:
            problems.append(
                "benchdiff: an identical artifact pair did not compare "
                "clean"
            )
        if bd.get("regression_flagged") is not True:
            problems.append(
                "benchdiff: a synthetically regressed artifact was NOT "
                "flagged"
            )
        if bd.get("mismatch_detected") is not True:
            problems.append(
                "benchdiff: a cross-schema pair was NOT rejected as a "
                "mismatch"
            )
    return problems


def build_doctor_report(res: dict) -> dict:
    """Assemble a schema-complete DOCTOR artifact from
    ``workload.run_doctor_workload``'s result."""
    pathologies = res.get("pathologies", {})
    detected = sum(
        1
        for p in DOCTOR_PATHOLOGIES
        if pathologies.get(p, {}).get("detected")
        and pathologies.get(p, {}).get("evidence_correct")
    )
    return {
        "schema_version": DOCTOR_SCHEMA_VERSION,
        "metric": "doctor_pathologies_named",
        "value": detected,
        "unit": (
            f"of {len(DOCTOR_PATHOLOGIES)} deterministically seeded "
            "pathologies named by the mesh doctor with correct pinned "
            "evidence (and zero findings on the healthy phase)"
        ),
        "workload": (
            "healthy balanced phase, then zipf heat storm + convoying "
            "long-prompt burst + throttled restore lane over one rf=3 "
            "inproc cluster and a traced CPU engine "
            "(see workload.run_doctor_workload)"
        ),
        **res,
    }


# ----------------------------------------------------------------------
# BLACKBOX stable schema (PR 13): the flight-recorder acceptance
# artifact. A node killed mid-zipf-storm must yield black-box dumps
# (obs/blackbox.py) from which the post-mortem doctor
# (obs/doctor.py::postmortem_report) names the seeded hot shard and the
# crash window FROM THE DUMPS ALONE, the live history-backed doctor must
# stay silent on the healthy phase, and the telemetry sampler's
# self-accounted overhead must stay under 1% of the (step-accounting)
# run. scripts/blackboxbench.py is the paired emitter.
# ----------------------------------------------------------------------

# v2 (PR 14): the healthy-phase rules_checked gate grew the
# rebalancer_asleep rule; v3 (PR 15): tier_thrash; v4 (PR 17): the
# three fleet rules (the workload arms an in-proc FleetAggregator for
# its healthy phase); v5 (PR 18): the three token-plane rules
# (decode_stall, spec_misconfigured, goodput_regression). Older
# artifacts validate against their version's pinned rule set
# (_required_doctor_rules).
BLACKBOX_SCHEMA_VERSION = 5

BLACKBOX_TOP_FIELDS = (
    "schema_version", "metric", "value", "unit", "workload", "nodes",
    "topology", "replication_factor", "healthy", "storm", "crash",
    "postmortem", "history", "blackbox", "wall_s",
)
BLACKBOX_HEALTHY_FIELDS = (
    "performed", "findings", "rules_checked", "inputs", "history_samples",
)
BLACKBOX_CRASH_FIELDS = (
    "performed", "victim_rank", "victim_is_hot_owner", "t_kill",
    "observer_detected_live",
)
BLACKBOX_OVERHEAD_FIELDS = (
    "sample_seconds_total", "wall_s", "fraction", "budget_fraction",
    "under_budget",
)
# The three post-mortem verdicts the acceptance run must name from the
# dumps alone.
BLACKBOX_NAMED_TOTAL = 3


def validate_blackbox(report) -> list[str]:
    """Schema violations of a BLACKBOX artifact vs the pinned contract
    (empty = valid). Gates: the healthy phase ran EVERY live rule and
    found nothing; the post-mortem doctor named the seeded hot shard
    and a crash window containing the true kill time from the
    OBSERVER's dump, and the unclean-death truncation from the
    VICTIM's segment-only dump; the victim's dump really is unclean
    (segments, no final); and the sampler's self-accounted overhead
    stayed under its budget. Sections with performed=False are
    schema-valid but gate-exempt (the CHAOS convention). Import-safe
    from artifact tests and scripts (no jax at module scope)."""
    from radixmesh_tpu.obs.doctor import RULES

    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    problems = [f for f in BLACKBOX_TOP_FIELDS if f not in report]
    healthy = report.get("healthy")
    if isinstance(healthy, dict) and healthy.get("performed"):
        problems += [
            f"healthy.{f}" for f in BLACKBOX_HEALTHY_FIELDS if f not in healthy
        ]
        if healthy.get("findings") != []:
            problems.append(
                "healthy: the live doctor reported findings on the "
                f"healthy phase ({healthy.get('findings')})"
            )
        missing_rules = [
            r for r in _required_doctor_rules(report, RULES)
            if r not in (healthy.get("rules_checked") or [])
        ]
        if missing_rules:
            problems.append(
                f"healthy: rules {missing_rules} never ran — 'no "
                "findings' is only evidence when every rule looked"
            )
        if not healthy.get("history_samples", 0):
            problems.append(
                "healthy: zero history samples — the rings never saw "
                "the healthy phase"
            )
    crash = report.get("crash")
    if isinstance(crash, dict) and crash.get("performed"):
        problems += [
            f"crash.{f}" for f in BLACKBOX_CRASH_FIELDS if f not in crash
        ]
        if crash.get("victim_is_hot_owner") is not True:
            problems.append(
                "crash: the killed node was not an owner of the hot "
                "shard — the scenario must kill where the storm lives"
            )
        if crash.get("observer_detected_live") is not True:
            problems.append(
                "crash: the observer's rings never recorded the "
                "victim's health collapse"
            )
    pm = report.get("postmortem")
    if isinstance(pm, dict):
        obs = pm.get("observer", {})
        victim = pm.get("victim", {})
        if obs.get("hot_shard_named") is not True:
            problems.append(
                "postmortem: the observer dump did not name the seeded "
                f"hot shard (evidence {obs.get('hot_shard_evidence')} vs "
                f"expected {pm.get('expected')})"
            )
        if obs.get("crash_window_named") is not True:
            problems.append(
                "postmortem: the observer dump's crash window does not "
                f"contain the true kill time (evidence "
                f"{obs.get('crash_evidence')} vs expected "
                f"{pm.get('expected')})"
            )
        if victim.get("truncation_named") is not True:
            problems.append(
                "postmortem: the victim's segment-only dump did not "
                "yield an unclean-death truncation window within one "
                "segment of the kill"
            )
        if victim.get("unclean") is not True:
            problems.append(
                "postmortem: the victim dump is not unclean — a final "
                "flush survived the 'hard kill', so nothing was proven "
                "about crash survival"
            )
    hist = report.get("history")
    if isinstance(hist, dict):
        overhead = hist.get("self_overhead")
        if not isinstance(overhead, dict):
            problems.append("history.self_overhead")
        else:
            problems += [
                f"history.self_overhead.{f}"
                for f in BLACKBOX_OVERHEAD_FIELDS
                if f not in overhead
            ]
            if overhead.get("under_budget") is not True:
                problems.append(
                    "history: sampler overhead "
                    f"{overhead.get('fraction')} exceeded the "
                    f"{overhead.get('budget_fraction')} budget"
                )
    if isinstance(pm, dict) and report.get("value") != BLACKBOX_NAMED_TOTAL:
        problems.append(
            f"value: {report.get('value')} of {BLACKBOX_NAMED_TOTAL} "
            "post-mortem verdicts named"
        )
    return problems


def build_blackbox_report(res: dict) -> dict:
    """Assemble a schema-complete BLACKBOX artifact from
    ``workload.run_blackbox_workload``'s result."""
    return {
        "schema_version": BLACKBOX_SCHEMA_VERSION,
        "metric": "blackbox_postmortem_named",
        "value": res.get("named", 0),
        "unit": (
            f"of {BLACKBOX_NAMED_TOTAL} post-mortem verdicts (hot shard, "
            "crash window, unclean-death truncation) named from "
            "black-box dumps alone, with zero live findings on the "
            "healthy phase and sampler overhead under budget"
        ),
        "workload": (
            "healthy balanced phase + zipf heat storm over one rf=3 "
            "inproc cluster with per-node fleet digesters and a "
            "step-accounted CPU engine; the hot shard's primary owner "
            "is killed hard mid-storm (segments survive, no final "
            "flush) and the post-mortem doctor diagnoses from the "
            "observer + victim dumps alone "
            "(see workload.run_blackbox_workload)"
        ),
        **res,
    }


# ----------------------------------------------------------------------
# REBALANCE stable schema (PR 14, the closed robustness loop): one
# artifact per round recording (a) the heat-driven rebalancer dropping
# a zipf storm's skew score with zero failed requests mid-move (elastic
# RF boost + zero-loss ownership handoff), (b) a router kill at an
# N>=2 multi-router front door completing every in-flight request
# through the surviving router's edge, and (c) meshcheck reporting the
# new rebalance plane clean. scripts/rebalancebench.py is the paired
# emitter; the sections share their gate logic with CHAOS v4.
# ----------------------------------------------------------------------

REBALANCE_SCHEMA_VERSION = 1

REBALANCE_TOP_FIELDS = (
    "schema_version", "metric", "value", "unit", "workload", "nodes",
    "topology", "replication_factor", "rebalance", "router_kill",
    "meshcheck", "wall_s",
)
REBALANCE_MESHCHECK_FIELDS = ("files", "findings", "clean")


def validate_rebalance(report) -> list[str]:
    """Schema violations of a REBALANCE artifact vs the pinned contract
    (empty = valid). Gates: the zipf storm's skew score strictly drops
    under rebalancing with zero failed requests mid-move and bounded,
    fleet-converged movement; a router kill at N >= 2 routers
    mid-traffic completes every in-flight request via the surviving
    router's edge with zero losses; and meshcheck reports 0 findings on
    the rebalance plane. performed=False sections are schema-valid but
    gate-exempt (the CHAOS convention). Import-safe from artifact tests
    and ``scripts/rebalancebench.py`` (no jax at module scope)."""
    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    problems = [f for f in REBALANCE_TOP_FIELDS if f not in report]
    reb = report.get("rebalance")
    if "rebalance" in report and not isinstance(reb, dict):
        # A present-but-garbage section must not silently skip every
        # gate (the validate_chaos v4 discipline).
        problems.append("rebalance section is not an object")
    if isinstance(reb, dict) and reb.get("performed"):
        problems += _rebalance_section_problems(reb)
    rk = report.get("router_kill")
    if "router_kill" in report and not isinstance(rk, dict):
        problems.append("router_kill section is not an object")
    if isinstance(rk, dict) and rk.get("performed"):
        problems += _router_kill_section_problems(rk)
    mc = report.get("meshcheck")
    if "meshcheck" in report and not isinstance(mc, dict):
        problems.append("meshcheck section is not an object")
    if isinstance(mc, dict):
        problems += [
            f"meshcheck.{f}" for f in REBALANCE_MESHCHECK_FIELDS
            if f not in mc
        ]
        if mc.get("clean") is not True or mc.get("findings", 1) != 0:
            problems.append(
                f"meshcheck: {mc.get('findings')} finding(s) on the "
                "rebalance plane — the new single-writer plane must be "
                "statically clean"
            )
    val = report.get("value")
    if isinstance(reb, dict) and reb.get("performed"):
        if not isinstance(val, (int, float)) or val <= 1.0:
            problems.append(
                f"value: skew drop ratio {val} is not > 1 (the storm "
                "did not get flatter)"
            )
    return problems


def build_rebalance_report(res: dict, meshcheck: dict | None = None) -> dict:
    """Assemble a schema-complete REBALANCE artifact from
    ``workload.run_chaos_workload``'s result (the rebalance +
    router-kill phases) plus a meshcheck verdict on the plane."""
    reb = res.get("rebalance", {}) or {}
    before = float(reb.get("skew_before") or 0.0)
    after = float(reb.get("skew_after") or 0.0)
    ratio = round(before / after, 4) if after > 0 else 0.0
    return {
        "schema_version": REBALANCE_SCHEMA_VERSION,
        "metric": "rebalance_skew_drop_ratio",
        "value": ratio,
        "unit": (
            "zipf-storm skew score before / after heat-driven "
            "rebalancing (elastic RF boost + zero-loss ownership "
            "handoff), with zero failed requests mid-move and a "
            "mid-traffic router kill losing nothing at an N>=2 "
            "multi-router front door"
        ),
        "workload": (
            "zipf heat storm over an rf>0 inproc cluster with a "
            "RebalancePlane decider on the view master, then a second "
            "storm wave under the adopted overrides; one of 2 routers "
            "process-killed mid-traffic with client-side front-door "
            "failover (see workload.run_chaos_workload rebalance / "
            "router_kill phases)"
        ),
        "nodes": res.get("nodes"),
        "topology": res.get("topology"),
        "replication_factor": res.get("replication_factor"),
        "rebalance": reb,
        "router_kill": res.get("router_kill", {}),
        "meshcheck": meshcheck or {"files": [], "findings": -1, "clean": False},
        "wall_s": res.get("wall_s"),
    }


# ----------------------------------------------------------------------
# TIER stable schema (PR 15, the durable KV spill tier): one artifact
# per round recording (a) hit-rate at a working set >= 10x host
# capacity beating the no-tier baseline (the tier stack finally
# outlives DRAM), (b) the restore-overlap contract extended one tier
# down — decode never blocks on disk restores (KVFLOW's
# decode-never-blocks discipline), (c) the cold-cell resurrection drill:
# the WHOLE serving cell killed hard mid-decode, restarted, every
# interrupted stream resumed byte-identical from disk alone, with
# seeded torn/corrupt extents detected and dropped rather than served,
# and (d) meshcheck clean on the new plane (the hotpath-file-io
# invariant live with its positive control tripping).
# scripts/tierbench.py is the paired emitter.
# ----------------------------------------------------------------------

TIER_SCHEMA_VERSION = 1

TIER_TOP_FIELDS = (
    "schema_version", "metric", "value", "unit", "workload",
    "capacity", "spill", "restore_overlap", "cold_start", "corruption",
    "meshcheck", "page_size", "wall_s",
)
TIER_CAPACITY_FIELDS = (
    "working_set_tokens", "host_slots", "working_set_ratio",
    "tier_hit_rate", "baseline_hit_rate", "hit_rate_gain",
    "requests", "distinct_prefixes",
)
TIER_SPILL_FIELDS = (
    "spilled_tokens", "extents", "demotes", "promotes", "drops",
    "resident_bytes",
)
TIER_RESTORE_FIELDS = (
    "parked_requests", "disk_restored_tokens",
    "decode_steps_during_restore", "max_decode_gap_s", "overlap_ok",
)
TIER_COLD_START_FIELDS = (
    "performed", "interrupted", "resumed", "byte_identical", "failed",
    "disk_hit_tokens", "grafted_nodes", "orphaned",
    "corrupt_detected", "corrupt_served", "restart_s",
)
TIER_CORRUPTION_FIELDS = (
    "extents_attacked", "truncated", "bitflipped", "detected",
    "served_corrupt",
)
TIER_MESHCHECK_FIELDS = ("files", "findings", "clean")
TIER_MIN_WORKING_SET_RATIO = 10.0


def validate_tier(report) -> list[str]:
    """Schema violations of a TIER artifact vs the pinned contract
    (empty = valid). Gates: working set >= 10x host capacity with the
    tier's hit-rate strictly beating the no-tier baseline; decode
    progress > 0 while disk restores were parked (the restore-overlap
    contract one tier down); the cold-start phase losing zero requests,
    resuming every interrupted stream byte-identical from disk alone,
    and detecting (never serving) every seeded corrupt/torn extent; and
    meshcheck clean on the tier plane. performed=False sections are
    schema-valid but gate-exempt (the CHAOS convention). Import-safe
    from artifact tests and scripts/tierbench.py (no jax at module
    scope)."""
    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    problems = [f for f in TIER_TOP_FIELDS if f not in report]
    for section, fields in (
        ("capacity", TIER_CAPACITY_FIELDS),
        ("spill", TIER_SPILL_FIELDS),
        ("restore_overlap", TIER_RESTORE_FIELDS),
        ("cold_start", TIER_COLD_START_FIELDS),
        ("corruption", TIER_CORRUPTION_FIELDS),
        ("meshcheck", TIER_MESHCHECK_FIELDS),
    ):
        sec = report.get(section)
        if section in report and not isinstance(sec, dict):
            problems.append(f"{section} section is not an object")
            continue
        if isinstance(sec, dict):
            if section == "cold_start" and not sec.get("performed"):
                # The CHAOS convention: a skipped phase is schema-valid
                # ({"performed": False}) but gate-exempt.
                continue
            problems += [f"{section}.{f}" for f in fields if f not in sec]
    cap = report.get("capacity")
    if isinstance(cap, dict):
        ratio = cap.get("working_set_ratio")
        if isinstance(ratio, (int, float)) and ratio < TIER_MIN_WORKING_SET_RATIO:
            problems.append(
                f"capacity: working set only {ratio}x host capacity "
                f"(gate {TIER_MIN_WORKING_SET_RATIO}x) — the claim is "
                "'past DRAM', not 'fits in DRAM'"
            )
        t, b = cap.get("tier_hit_rate"), cap.get("baseline_hit_rate")
        if (
            isinstance(t, (int, float))
            and isinstance(b, (int, float))
            and not t > b
        ):
            problems.append(
                f"capacity: tier hit-rate {t} does not beat the no-tier "
                f"baseline {b}"
            )
    ro = report.get("restore_overlap")
    if isinstance(ro, dict):
        if not ro.get("parked_requests", 0):
            problems.append(
                "restore_overlap: zero parked disk restores — the "
                "overlap claim never saw a disk restore"
            )
        steps = ro.get("decode_steps_during_restore")
        if isinstance(steps, (int, float)) and not steps > 0:
            problems.append(
                "restore_overlap: decode made zero progress while disk "
                "restores were in flight (decode-never-blocks contract, "
                "one tier down)"
            )
        if ro.get("overlap_ok") is not True:
            problems.append("restore_overlap: overlap_ok is not True")
    cs = report.get("cold_start")
    if isinstance(cs, dict) and cs.get("performed"):
        if cs.get("failed", 1) != 0:
            problems.append(
                f"cold_start: {cs.get('failed')} request(s) failed — "
                "the full-restart drill must lose nothing"
            )
        if not cs.get("interrupted", 0):
            problems.append(
                "cold_start: zero interrupted streams — nothing was "
                "proven about mid-decode crash recovery"
            )
        if cs.get("resumed") != cs.get("interrupted"):
            problems.append(
                f"cold_start: resumed {cs.get('resumed')} != interrupted "
                f"{cs.get('interrupted')}"
            )
        if cs.get("byte_identical") is not True:
            problems.append(
                "cold_start: resumed streams were NOT byte-identical to "
                "their pre-kill expectation"
            )
        if not cs.get("disk_hit_tokens", 0):
            problems.append(
                "cold_start: zero disk-served hit tokens after restart "
                "— recovery never actually read the durable tier"
            )
        if not cs.get("corrupt_detected", 0):
            problems.append(
                "cold_start: the seeded corrupt extent was not detected"
            )
        if cs.get("corrupt_served", 1) != 0:
            problems.append(
                f"cold_start: {cs.get('corrupt_served')} corrupt "
                "extent(s) SERVED — the checksum gate failed"
            )
    cor = report.get("corruption")
    if isinstance(cor, dict):
        attacked = int(cor.get("extents_attacked", 0) or 0)
        if attacked:
            if cor.get("detected") != attacked:
                problems.append(
                    f"corruption: {cor.get('detected')} of {attacked} "
                    "attacked extents detected — torn tails/bit-flips "
                    "must never go unnoticed"
                )
            if cor.get("served_corrupt", 1) != 0:
                problems.append(
                    f"corruption: {cor.get('served_corrupt')} corrupt "
                    "extent(s) served"
                )
    mc = report.get("meshcheck")
    if isinstance(mc, dict):
        if mc.get("clean") is not True or mc.get("findings", 1) != 0:
            problems.append(
                f"meshcheck: {mc.get('findings')} finding(s) on the "
                "tier plane — the hotpath-file-io boundary must be "
                "statically clean"
            )
    val = report.get("value")
    if isinstance(cap, dict):
        if not isinstance(val, (int, float)) or val <= 1.0:
            problems.append(
                f"value: hit-rate gain {val} is not > 1 (the tier did "
                "not beat the no-tier baseline)"
            )
    return problems


def build_tier_report(res: dict, meshcheck: dict | None = None) -> dict:
    """Assemble a schema-complete TIER artifact from
    ``workload.run_tier_workload``'s result plus a meshcheck verdict."""
    cap = res.get("capacity", {}) or {}
    return {
        "schema_version": TIER_SCHEMA_VERSION,
        "metric": "tier_hit_rate_gain",
        "value": cap.get("hit_rate_gain"),
        "unit": (
            "prefix-cache hit-rate with the durable disk tier / no-tier "
            "baseline, at a working set >= 10x host capacity (> 1 = the "
            "tier serves what DRAM alone cannot), with decode never "
            "blocking on disk restores and a whole-cell kill-and-restart "
            "resuming every stream byte-identical from disk alone"
        ),
        "workload": (
            "zipf re-visit traffic over a working set 10x the host "
            "arena (tier vs no-tier engines), a parked-disk-restore "
            "decode-overlap phase, and a cold-cell drill: every volatile "
            "tier destroyed mid-decode, one extent bit-flipped + one "
            "truncated, the cell restarted from the extent directory "
            "and interrupted streams resumed byte-identical "
            "(see workload.run_tier_workload)"
        ),
        "capacity": cap,
        "spill": res.get("spill", {}),
        "restore_overlap": res.get("restore_overlap", {}),
        "cold_start": res.get("cold_start", {}),
        "corruption": res.get("corruption", {}),
        "meshcheck": meshcheck
        or {"files": [], "findings": -1, "clean": False},
        "page_size": res.get("page_size"),
        "wall_s": res.get("wall_s"),
    }


# ----------------------------------------------------------------------
# AGG stable schema (PR 17, the control room): one artifact per round
# recording fleet-wide telemetry aggregation over an inproc 4P+2D+2R
# rf=3 cell — (a) the fleet-MERGED p99 TTFT (bucket counts summed
# across nodes, obs/aggregator.py) matching ground truth computed from
# raw request records within one histogram bucket, (b) a seeded
# straggler (delayed decode node) named BY RANK by the fleet doctor,
# (c) the fleet-p99-bucket exemplar resolving to a stitched trace
# containing the slow node's span, (d) a killed node surfacing as
# telemetry_gap rather than silence, (e) aggregation overhead under 1%
# of run wall time, and (f) an N=200 simulated-transport fan-in row
# completing one pull sweep within one cadence interval.
# scripts/aggbench.py is the paired emitter.
# ----------------------------------------------------------------------

AGG_SCHEMA_VERSION = 1

AGG_TOP_FIELDS = (
    "schema_version", "metric", "value", "unit", "workload", "nodes",
    "topology", "replication_factor", "percentiles", "straggler",
    "exemplar", "gap", "overhead", "fan_in", "wall_s",
)
AGG_PERCENTILE_FIELDS = (
    "performed", "tenant", "fleet_p99_s", "truth_p99_s", "bucket_lo_s",
    "bucket_hi_s", "within_one_bucket", "count", "nodes",
)
AGG_STRAGGLER_FIELDS = (
    "performed", "seeded_rank", "named_rank", "detected", "ratio",
    "signal",
)
AGG_EXEMPLAR_FIELDS = (
    "performed", "trace_id", "node", "le", "stitched",
    "has_straggler_span",
)
AGG_GAP_FIELDS = (
    "performed", "killed_peer", "detected", "verdict", "stalled_s",
)
AGG_OVERHEAD_FIELDS = (
    "pull_seconds_total", "wall_s", "fraction", "budget_fraction",
    "under_budget",
)
AGG_FANIN_FIELDS = (
    "performed", "peers", "sweep_s", "cadence_s", "within_cadence",
    "points",
)
# The four fleet verdicts the acceptance run must name (percentile
# match, straggler by rank, exemplar→trace, killed node as gap).
AGG_NAMED_TOTAL = 4


def validate_agg(report) -> list[str]:
    """Schema violations of an AGG artifact vs the pinned contract
    (empty = valid). Gates: the fleet-merged p99 TTFT lands within one
    histogram bucket of the raw-record ground truth; the seeded
    straggler is named by rank; the merged-p99-bucket exemplar resolves
    to a stitched trace carrying the slow node's span; the killed node
    surfaces as ``telemetry_gap`` (never silence); aggregation overhead
    stays under its budget; and the N=200 fan-in sweep completes inside
    one pull cadence. Sections with performed=False are schema-valid
    but gate-exempt (the CHAOS convention). Import-safe from artifact
    tests and scripts/aggbench.py (no jax at module scope)."""
    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    problems = [f for f in AGG_TOP_FIELDS if f not in report]
    named = 0
    pct = report.get("percentiles")
    if "percentiles" in report and not isinstance(pct, dict):
        problems.append("percentiles section is not an object")
    if isinstance(pct, dict) and pct.get("performed"):
        problems += [
            f"percentiles.{f}" for f in AGG_PERCENTILE_FIELDS if f not in pct
        ]
        if pct.get("within_one_bucket") is not True:
            problems.append(
                f"percentiles: fleet-merged p99 {pct.get('fleet_p99_s')}s "
                f"is NOT within one bucket of the raw-record truth "
                f"{pct.get('truth_p99_s')}s — the merge is the whole "
                "point; average-of-percentiles would fail exactly here"
            )
        else:
            named += 1
        if not pct.get("count", 0):
            problems.append(
                "percentiles: zero merged observations — the fleet "
                "store never saw a request"
            )
        if len(pct.get("nodes") or []) < 2:
            problems.append(
                "percentiles: fewer than 2 reporting nodes — nothing "
                "was merged ACROSS nodes"
            )
    strag = report.get("straggler")
    if "straggler" in report and not isinstance(strag, dict):
        problems.append("straggler section is not an object")
    if isinstance(strag, dict) and strag.get("performed"):
        problems += [
            f"straggler.{f}" for f in AGG_STRAGGLER_FIELDS if f not in strag
        ]
        if strag.get("detected") is not True or str(
            strag.get("named_rank")
        ) != str(strag.get("seeded_rank")):
            problems.append(
                f"straggler: seeded rank {strag.get('seeded_rank')} was "
                f"not named (doctor named {strag.get('named_rank')}, "
                f"detected={strag.get('detected')})"
            )
        else:
            named += 1
    ex = report.get("exemplar")
    if "exemplar" in report and not isinstance(ex, dict):
        problems.append("exemplar section is not an object")
    if isinstance(ex, dict) and ex.get("performed"):
        problems += [
            f"exemplar.{f}" for f in AGG_EXEMPLAR_FIELDS if f not in ex
        ]
        if (
            ex.get("stitched") is not True
            or ex.get("has_straggler_span") is not True
        ):
            problems.append(
                "exemplar: the fleet-p99-bucket exemplar did not "
                "resolve to a stitched trace containing the slow "
                f"node's span (stitched={ex.get('stitched')}, "
                f"straggler span={ex.get('has_straggler_span')})"
            )
        else:
            named += 1
    gap = report.get("gap")
    if "gap" in report and not isinstance(gap, dict):
        problems.append("gap section is not an object")
    if isinstance(gap, dict) and gap.get("performed"):
        problems += [f"gap.{f}" for f in AGG_GAP_FIELDS if f not in gap]
        if gap.get("detected") is not True or gap.get("verdict") not in (
            "node_dead", "sampler_dead",
        ):
            problems.append(
                f"gap: killed peer {gap.get('killed_peer')} did not "
                f"surface as telemetry_gap (detected="
                f"{gap.get('detected')}, verdict={gap.get('verdict')}) "
                "— a dead ring must never read as silence"
            )
        else:
            named += 1
    ov = report.get("overhead")
    if "overhead" in report and not isinstance(ov, dict):
        problems.append("overhead section is not an object")
    if isinstance(ov, dict):
        problems += [
            f"overhead.{f}" for f in AGG_OVERHEAD_FIELDS if f not in ov
        ]
        if ov.get("under_budget") is not True:
            problems.append(
                f"overhead: aggregation cost {ov.get('fraction')} of "
                f"wall exceeded the {ov.get('budget_fraction')} budget"
            )
    fi = report.get("fan_in")
    if "fan_in" in report and not isinstance(fi, dict):
        problems.append("fan_in section is not an object")
    if isinstance(fi, dict) and fi.get("performed"):
        problems += [f"fan_in.{f}" for f in AGG_FANIN_FIELDS if f not in fi]
        if int(fi.get("peers", 0) or 0) < 200:
            problems.append(
                f"fan_in: only {fi.get('peers')} simulated peers — the "
                "row exists to prove the N=200 ringscale regime"
            )
        if fi.get("within_cadence") is not True:
            problems.append(
                f"fan_in: one sweep took {fi.get('sweep_s')}s, past the "
                f"{fi.get('cadence_s')}s pull cadence — the aggregator "
                "would fall behind its own schedule"
            )
    performed_any = any(
        isinstance(report.get(s), dict) and report.get(s, {}).get("performed")
        for s in ("percentiles", "straggler", "exemplar", "gap")
    )
    if performed_any and report.get("value") != named:
        problems.append(
            f"value: {report.get('value')} does not equal the {named} "
            "fleet verdict(s) actually named"
        )
    return problems


def build_agg_report(res: dict) -> dict:
    """Assemble a schema-complete AGG artifact from
    ``workload.run_agg_workload``'s result."""
    return {
        "schema_version": AGG_SCHEMA_VERSION,
        "metric": "agg_fleet_verdicts_named",
        "value": res.get("named", 0),
        "unit": (
            f"of {AGG_NAMED_TOTAL} fleet verdicts (merged-p99-vs-truth "
            "within one bucket, straggler named by rank, p99 exemplar "
            "resolved to a stitched trace with the slow node's span, "
            "killed node surfaced as telemetry_gap) named over the "
            "aggregator's cross-node store, with aggregation overhead "
            "under budget and N=200 fan-in inside one cadence"
        ),
        "workload": (
            "inproc 4P+2D+2R rf=3 cell with per-node telemetry "
            "histories cursor-pulled by a router-hosted "
            "FleetAggregator; one decode node seeded slow, one node "
            "killed mid-run, plus an N=200 simulated-transport fan-in "
            "row (see workload.run_agg_workload)"
        ),
        **res,
    }


# ----------------------------------------------------------------------
# SPEC stable schema (PR 18, the speedometer): one artifact per round
# recording the speculation/token-plane verdicts — (a) draft-token
# conservation (proposed == accepted + rejected on every verify path)
# with accepted-tokens-per-verify-wave broken down BY SHAPE and BY
# DRAFT SOURCE (tree-peek vs n-gram), (b) per-token ITL percentiles
# from the bounded timeline ring with a SEEDED stall named by cause,
# (c) the adaptive-γ controller A-B: acceptance-weighted goodput no
# worse than the fixed-γ baseline, and (d) the token-timeline sampler's
# measured overhead under 1% of run wall. scripts/specbench.py is the
# paired emitter; ROADMAP item 1's gate names this artifact.
# ----------------------------------------------------------------------

SPEC_SCHEMA_VERSION = 1

SPEC_TOP_FIELDS = (
    "schema_version", "metric", "value", "unit", "workload",
    "acceptance", "itl", "adaptive", "overhead", "wall_s",
)
SPEC_ACCEPTANCE_FIELDS = (
    "performed", "proposed", "accepted", "rejected", "conserved",
    "accepted_per_step", "waves", "by_shape", "by_source",
)
SPEC_ITL_FIELDS = (
    "performed", "count", "p50_s", "p99_s", "stalls", "stall_seconds",
    "seeded_cause", "seeded_detected",
)
SPEC_ADAPTIVE_FIELDS = (
    "performed", "gamma_base", "fixed_goodput_tps",
    "adaptive_goodput_tps", "goodput_ratio", "no_worse",
    "fixed_acceptance", "adaptive_acceptance",
)
SPEC_OVERHEAD_FIELDS = (
    "tokens", "timeline_on_s", "timeline_off_s", "fraction",
    "budget_fraction", "under_budget",
)
# Adaptive-γ may not cost goodput: the A-B ratio floor (a hair under
# 1.0 — CPU-tier walltime jitter must not fail a controller that is
# actually neutral-or-better).
SPEC_ADAPTIVE_RATIO_FLOOR = 0.85
SPEC_OVERHEAD_BUDGET = 0.01


def validate_spec(report) -> list[str]:
    """Schema violations of a SPEC artifact vs the pinned contract
    (empty = valid). Gates: draft-token conservation held on every
    verify path (proposed == accepted + rejected) with per-shape AND
    per-draft-source breakdowns present and a positive
    accepted-per-wave rate; the ITL section saw real tokens and named
    the seeded stall by cause; the adaptive-γ A-B's acceptance-weighted
    goodput is no worse than fixed γ (ratio over the pinned floor); and
    the token-timeline sampler's measured overhead stays under its 1%
    budget. Sections with performed=False are schema-valid but
    gate-exempt (the CHAOS convention). Import-safe from artifact tests
    and scripts/specbench.py (no jax at module scope)."""
    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    problems = [f for f in SPEC_TOP_FIELDS if f not in report]
    acc = report.get("acceptance")
    if "acceptance" in report and not isinstance(acc, dict):
        problems.append("acceptance section is not an object")
    if isinstance(acc, dict) and acc.get("performed"):
        problems += [
            f"acceptance.{f}" for f in SPEC_ACCEPTANCE_FIELDS if f not in acc
        ]
        if acc.get("conserved") is not True:
            problems.append(
                f"acceptance: conservation broke — proposed "
                f"{acc.get('proposed')} != accepted {acc.get('accepted')}"
                f" + rejected {acc.get('rejected')} (a verify path is "
                "dropping draft tokens from the ledger)"
            )
        if not acc.get("proposed", 0):
            problems.append(
                "acceptance: zero proposed draft tokens — nothing was "
                "proven about speculation"
            )
        aps = acc.get("accepted_per_step")
        if isinstance(aps, (int, float)) and not aps > 0:
            problems.append(
                f"acceptance: accepted-per-wave {aps} is not > 0 — "
                "every draft missed; that is a broken drafter, not a "
                "measured one"
            )
        for axis in ("by_shape", "by_source"):
            ax = acc.get(axis)
            if isinstance(ax, dict) and not ax:
                problems.append(
                    f"acceptance: {axis} is empty — the per-class "
                    "breakdown is the artifact's reason to exist"
                )
    itl = report.get("itl")
    if "itl" in report and not isinstance(itl, dict):
        problems.append("itl section is not an object")
    if isinstance(itl, dict) and itl.get("performed"):
        problems += [f"itl.{f}" for f in SPEC_ITL_FIELDS if f not in itl]
        if not itl.get("count", 0):
            problems.append(
                "itl: zero timed inter-token gaps — the percentiles "
                "are vacuous"
            )
        if itl.get("seeded_detected") is not True:
            problems.append(
                f"itl: the seeded {itl.get('seeded_cause')!r} stall was "
                "not attributed — stall-cause attribution is the "
                "timeline's whole point"
            )
        p50, p99 = itl.get("p50_s"), itl.get("p99_s")
        if (
            isinstance(p50, (int, float))
            and isinstance(p99, (int, float))
            and p99 < p50
        ):
            problems.append(f"itl: p99 {p99} < p50 {p50}")
    ad = report.get("adaptive")
    if "adaptive" in report and not isinstance(ad, dict):
        problems.append("adaptive section is not an object")
    if isinstance(ad, dict) and ad.get("performed"):
        problems += [
            f"adaptive.{f}" for f in SPEC_ADAPTIVE_FIELDS if f not in ad
        ]
        if ad.get("no_worse") is not True:
            problems.append(
                f"adaptive: goodput ratio {ad.get('goodput_ratio')} "
                f"(adaptive/fixed) under the "
                f"{SPEC_ADAPTIVE_RATIO_FLOOR} floor — the controller "
                "costs more than it saves"
            )
    ov = report.get("overhead")
    if "overhead" in report and not isinstance(ov, dict):
        problems.append("overhead section is not an object")
    if isinstance(ov, dict):
        problems += [
            f"overhead.{f}" for f in SPEC_OVERHEAD_FIELDS if f not in ov
        ]
        if ov.get("under_budget") is not True:
            problems.append(
                f"overhead: timeline cost {ov.get('fraction')} of wall "
                f"exceeded the {ov.get('budget_fraction')} budget — the "
                "speedometer may not slow the car"
            )
    val = report.get("value")
    if isinstance(acc, dict) and acc.get("performed"):
        if not isinstance(val, (int, float)) or not val > 0:
            problems.append(
                f"value: accepted tokens per verify wave {val} is not "
                "> 0"
            )
    return problems


def build_spec_report(res: dict) -> dict:
    """Assemble a schema-complete SPEC artifact from
    ``workload.run_spec_workload``'s result."""
    acc = res.get("acceptance", {}) or {}
    return {
        "schema_version": SPEC_SCHEMA_VERSION,
        "metric": "spec_accepted_tokens_per_step",
        "value": acc.get("accepted_per_step"),
        "unit": (
            "draft tokens accepted per speculative verify wave, with "
            "conservation (proposed == accepted + rejected) on every "
            "verify path, per-shape and per-draft-source breakdowns, "
            "seeded-stall ITL attribution, adaptive-γ goodput no worse "
            "than fixed γ, and token-timeline overhead under 1% of wall"
        ),
        "workload": (
            "repetitive + replayed prompts over a tiny CPU model so "
            "tree-peek and n-gram drafts land; a mid-decode driver "
            "sleep seeding a scheduler_wait stall; fixed-γ vs "
            "adaptive-γ A-B on identical seeds; timeline on/off A-B "
            "for the overhead bound (see workload.run_spec_workload)"
        ),
        **res,
    }


CONVOY_SCHEMA_VERSION = 1

CONVOY_TOP_FIELDS = (
    "schema_version", "metric", "value", "unit", "workload",
    "interleave", "stalls", "starvation", "wall_s",
)
CONVOY_INTERLEAVE_FIELDS = (
    "performed", "reps", "inline_budget", "base_ttft_p50_s",
    "mixed_ttft_p50_s", "ttft_ratio", "base_itl_p99_s",
    "mixed_itl_p99_s", "outputs_match", "base_accepted_per_wave",
    "mixed_accepted_per_wave", "waves",
)
CONVOY_STALL_FIELDS = (
    "performed", "stall_threshold_s", "base_convoy_s_per_req",
    "mixed_convoy_s_per_req", "convoy_drop_ratio", "base_causes",
    "mixed_causes", "inline_attributed_s",
)
CONVOY_STARVATION_FIELDS = (
    "performed", "skew", "max_defer_bound", "max_step_gap",
    "max_defer_observed", "boost_waves", "bounded", "carrier_tokens",
)
# The ISSUE's acceptance bars: mixed waves must better the convoy'd
# TTFT by at least this factor, and the per-request prefill_convoy
# stall seconds must drop by at least the drop floor. The ITL ceiling
# and spec floor keep the win honest — interleaving may not buy TTFT
# by starving decode or breaking speculation.
CONVOY_TTFT_RATIO_FLOOR = 1.5
CONVOY_DROP_FLOOR = 2.0
CONVOY_ITL_CEILING = 1.5
CONVOY_SPEC_FLOOR = 0.9


def validate_convoy(report) -> list[str]:
    """Schema violations of a CONVOY artifact vs the pinned contract
    (empty = valid). Gates: the mixed-wave arm beats the legacy
    alternating schedule's late-arrival TTFT by the pinned floor with
    BIT-IDENTICAL outputs, decode ITL p99 within the ceiling, and spec
    accepted-per-wave within the floor; the per-request
    ``prefill_convoy`` stall seconds drop by the drop floor; and the
    starvation proof held its wave-count bound with boost waves
    actually exercised. Sections with
    performed=False are schema-valid but gate-exempt (the CHAOS
    convention). Import-safe from artifact tests and
    scripts/convoybench.py (no jax at module scope)."""
    if not isinstance(report, dict):
        return ["artifact is not a JSON object"]
    problems = [f for f in CONVOY_TOP_FIELDS if f not in report]
    il = report.get("interleave")
    if "interleave" in report and not isinstance(il, dict):
        problems.append("interleave section is not an object")
    if isinstance(il, dict) and il.get("performed"):
        problems += [
            f"interleave.{f}" for f in CONVOY_INTERLEAVE_FIELDS if f not in il
        ]
        ratio = il.get("ttft_ratio")
        if isinstance(ratio, (int, float)) and not (
            ratio >= CONVOY_TTFT_RATIO_FLOOR
        ):
            problems.append(
                f"interleave: late-arrival TTFT ratio {ratio} under the "
                f"{CONVOY_TTFT_RATIO_FLOOR} floor — mixed waves did not "
                "beat the convoy"
            )
        if il.get("outputs_match") is not True:
            problems.append(
                "interleave: outputs diverged between the legacy and "
                "mixed schedules — interleaving changed WHAT was "
                "generated, not just when"
            )
        b_itl, m_itl = il.get("base_itl_p99_s"), il.get("mixed_itl_p99_s")
        if (
            isinstance(b_itl, (int, float))
            and isinstance(m_itl, (int, float))
            and m_itl > b_itl * CONVOY_ITL_CEILING
        ):
            problems.append(
                f"interleave: mixed decode ITL p99 {m_itl} exceeds "
                f"base {b_itl} x{CONVOY_ITL_CEILING} — the TTFT win "
                "was bought by starving decode"
            )
        b_acc = il.get("base_accepted_per_wave")
        m_acc = il.get("mixed_accepted_per_wave")
        if (
            isinstance(b_acc, (int, float))
            and isinstance(m_acc, (int, float))
            and b_acc > 0
            and m_acc < b_acc * CONVOY_SPEC_FLOOR
        ):
            problems.append(
                f"interleave: spec accepted-per-wave fell {b_acc} -> "
                f"{m_acc} under the {CONVOY_SPEC_FLOOR} floor — inline "
                "chunks are breaking speculation"
            )
    st = report.get("stalls")
    if "stalls" in report and not isinstance(st, dict):
        problems.append("stalls section is not an object")
    if isinstance(st, dict) and st.get("performed"):
        problems += [
            f"stalls.{f}" for f in CONVOY_STALL_FIELDS if f not in st
        ]
        drop = st.get("convoy_drop_ratio")
        if isinstance(drop, (int, float)) and not (
            drop >= CONVOY_DROP_FLOOR
        ):
            problems.append(
                f"stalls: prefill_convoy s/req drop ratio {drop} under "
                f"the {CONVOY_DROP_FLOOR} floor — the convoy survived "
                "the interleave"
            )
        if not isinstance(st.get("base_causes"), dict) or not st.get(
            "base_causes"
        ):
            problems.append(
                "stalls: base_causes decomposition is empty — the base "
                "arm never even stalled; nothing was proven"
            )
    sv = report.get("starvation")
    if "starvation" in report and not isinstance(sv, dict):
        problems.append("starvation section is not an object")
    if isinstance(sv, dict) and sv.get("performed"):
        problems += [
            f"starvation.{f}" for f in CONVOY_STARVATION_FIELDS if f not in sv
        ]
        if sv.get("bounded") is not True:
            problems.append(
                f"starvation: decode went {sv.get('max_step_gap')} steps "
                f"(defer {sv.get('max_defer_observed')}) without a token "
                f"against a bound of {sv.get('max_defer_bound')} — the "
                "starvation bound broke"
            )
        if not sv.get("boost_waves", 0):
            problems.append(
                "starvation: zero boost waves fired — the skew never "
                "exercised deferral, so the bound was proven vacuously"
            )
    val = report.get("value")
    if isinstance(il, dict) and il.get("performed"):
        if not isinstance(val, (int, float)) or not val > 0:
            problems.append(
                f"value: late-arrival TTFT speedup {val} is not > 0"
            )
    return problems


def build_convoy_report(res: dict) -> dict:
    """Assemble a schema-complete CONVOY artifact from
    ``workload.run_convoy_workload``'s result."""
    il = res.get("interleave", {}) or {}
    return {
        "schema_version": CONVOY_SCHEMA_VERSION,
        "metric": "convoy_ttft_speedup",
        "value": il.get("ttft_ratio"),
        "unit": (
            "late-arrival p50 TTFT ratio (legacy alternating waves / "
            "decode-interleaved mixed waves) on an identical virtual "
            "arrival schedule, with bit-identical outputs, decode ITL "
            "p99 and spec accepted-per-wave no worse, prefill_convoy "
            "stall s/req dropped, and a wave-counted starvation bound"
        ),
        "workload": (
            "a decoding carrier stream convoyed by a 960-token prompt "
            "with a late 16-token arrival, A-B across "
            "prefill_inline_budget 0 vs >0; 20:1 skew with boost waves "
            "for the starvation proof (see workload.run_convoy_workload)"
        ),
        **res,
    }


# ----------------------------------------------------------------------
# compare_rounds (PR 12, the bench regression sentinel): schema-aware
# diffing of any two SAME-schema artifacts. The artifact schemas
# accumulated round over round with nothing machine-checking the
# trajectory between them — a silently regressed hit ratio or a halved
# ring throughput would ride a green round. Each kind pins the metrics
# worth guarding (dotted path, direction, relative significance
# threshold); everything else diffs informationally. scripts/benchdiff.py
# is the CLI with pinned exit codes (0 clean / 1 regression / 2 schema
# mismatch) so CI can gate on the trajectory.
# ----------------------------------------------------------------------

BENCHDIFF_EXIT_CLEAN = 0
BENCHDIFF_EXIT_REGRESSION = 1
BENCHDIFF_EXIT_MISMATCH = 2

# Relative-change denominator floor for zero-valued baselines (a clean
# 0.0 like attribution.max_sum_error_s must tolerate float dust without
# any threshold being able to): deltas are judged relative to at least
# this scale. 1e-6 = the attribution epsilon, the smallest magnitude any
# guarded metric treats as meaningful.
_ZERO_BASELINE_FLOOR = 1e-6

# kind → ((dotted path, direction, relative significance threshold), …).
# direction: "higher" = bigger is better, "lower" = smaller is better.
# A move AGAINST direction by more than the threshold (relative to the
# old value) is a regression; a move WITH it is an improvement; inside
# the threshold is noise ("ok"). Thresholds are deliberately loose —
# the sentinel exists to catch silent cliffs, not to litigate jitter.
COMPARE_RULES: dict = {
    "BENCH_FULL": (
        ("value", "higher", 0.15),
        ("vs_baseline", "higher", 0.15),
        ("serving_mix.ratio", "higher", 0.15),
        ("north_star.hit_rate", "higher", 0.10),
        ("north_star.p99_ttft_ms", "lower", 0.50),
    ),
    "RINGBENCH": (
        ("value", "higher", 0.20),
        ("wire_bytes_per_insert", "lower", 0.05),
        ("lap_latency.p99_ms", "lower", 0.50),
        ("converge_s_max", "lower", 0.50),
    ),
    "RINGSCALE": (
        ("bytes_per_insert_growth.rf3.growth", "lower", 0.25),
    ),
    "CHAOS": (
        ("value", "lower", 0.50),
        ("crash.resurrection_hit_ratio", "higher", 0.10),
        ("repair.converge_s", "lower", 0.50),
    ),
    "FLEET": (
        ("value", "lower", 0.50),
        ("stall_reaction.reaction_s", "lower", 0.50),
    ),
    "KVFLOW": (
        ("value", "lower", 0.20),
        ("restore.decode_steps_during_restore", "higher", 0.30),
        ("prefetch.hit_ahead_rate", "higher", 0.10),
    ),
    "OBS": (
        ("value", "higher", 0.0),
        ("heat.skew_score", "higher", 0.30),
        ("stitch.replication_edges", "higher", 0.50),
    ),
    "ANALYSIS": (
        ("value", "lower", 0.0),  # unsuppressed findings: any rise flags
        ("files_indexed", "higher", 0.20),
    ),
    "DOCTOR": (
        ("value", "higher", 0.0),
        ("attribution.audited", "higher", 0.50),
        ("attribution.max_sum_error_s", "lower", 10.0),
    ),
    "BLACKBOX": (
        ("value", "higher", 0.0),  # named post-mortem verdicts: any drop flags
        ("history.self_overhead.fraction", "lower", 2.0),
        ("history.points", "higher", 0.75),
    ),
    "REBALANCE": (
        ("value", "higher", 0.30),  # skew drop ratio
        ("rebalance.failed_mid_move", "lower", 0.0),  # any rise flags
        ("router_kill.failed", "lower", 0.0),
        ("meshcheck.findings", "lower", 0.0),
    ),
    "TIER": (
        ("value", "higher", 0.30),  # hit-rate gain over no-tier
        ("cold_start.failed", "lower", 0.0),  # any rise flags
        ("cold_start.corrupt_served", "lower", 0.0),
        ("restore_overlap.decode_steps_during_restore", "higher", 0.50),
        ("meshcheck.findings", "lower", 0.0),
    ),
    "AGG": (
        ("value", "higher", 0.0),  # named fleet verdicts: any drop flags
        ("overhead.fraction", "lower", 2.0),
        ("fan_in.sweep_s", "lower", 1.0),
        ("percentiles.count", "higher", 0.75),
    ),
    "SPEC": (
        ("value", "higher", 0.20),  # accepted draft tokens per verify wave
        ("acceptance.accepted_per_step", "higher", 0.20),
        ("adaptive.goodput_ratio", "higher", 0.20),
        ("itl.p99_s", "lower", 1.0),
        ("overhead.fraction", "lower", 2.0),
    ),
    "CONVOY": (
        ("value", "higher", 0.25),  # late-arrival TTFT speedup
        ("interleave.ttft_ratio", "higher", 0.25),
        ("stalls.mixed_convoy_s_per_req", "lower", 1.0),
        ("interleave.mixed_itl_p99_s", "lower", 1.0),
        ("starvation.max_defer_observed", "lower", 0.0),  # any rise flags
    ),
    # Kinds with no pinned directional metrics still get the schema
    # check + informational numeric diff.
    "SLO": (),
    "SOAK": (
        ("value", "higher", 0.20),
        ("server_p50_ttft_ms", "lower", 0.50),
    ),
}

# metric-name → kind, for artifacts compared without a filename (stdin,
# tests). Filename prefixes remain the primary detector.
_METRIC_KINDS = {
    "decode_tokens_per_sec_per_chip": "BENCH_FULL",
    "ring_insert_throughput": "RINGBENCH",
    "ring_scale_sweep": "RINGSCALE",
    "chaos_heal_converge_s": "CHAOS",
    "fleet_digest_fan_in_p50_s": "FLEET",
    "kv_restore_overlapped_ttft_ratio": "KVFLOW",
    "obs_stitched_node_tracks": "OBS",
    "unsuppressed_findings": "ANALYSIS",
    "doctor_pathologies_named": "DOCTOR",
    "blackbox_postmortem_named": "BLACKBOX",
    "rebalance_skew_drop_ratio": "REBALANCE",
    "tier_hit_rate_gain": "TIER",
    "agg_fleet_verdicts_named": "AGG",
    "spec_accepted_tokens_per_step": "SPEC",
    "convoy_ttft_speedup": "CONVOY",
    "slo_goodput_vs_offered_load": "SLO",
    "soak_requests": "SOAK",
}


def artifact_kind(report, filename: str | None = None) -> str | None:
    """The artifact's schema kind — from its ``<KIND>_r{N}.json``
    filename when given, else from its pinned ``metric`` name. None =
    unrecognized (compare_rounds refuses rather than guessing)."""
    import re

    if filename:
        m = re.fullmatch(
            r"([A-Z][A-Z0-9_]*?)_r\d+\.json",
            os.path.basename(filename),
        )
        if m:
            return m.group(1)
    if isinstance(report, dict):
        return _METRIC_KINDS.get(report.get("metric"))
    return None


def _dotted_get(obj, path: str):
    """Resolve ``a.b.c`` through nested dicts; None when any hop is
    absent or non-dict."""
    cur = obj
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def _numeric_leaves(obj, prefix: str = "", out: dict | None = None) -> dict:
    """dotted-path → value for every bool/int/float leaf (lists skipped:
    entry counts shift round-to-round and carry no stable identity)."""
    if out is None:
        out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            _numeric_leaves(v, f"{prefix}{k}.", out)
    elif isinstance(obj, bool) or isinstance(obj, (int, float)):
        out[prefix[:-1]] = float(obj)
    return out


def compare_rounds(
    old: dict,
    new: dict,
    kind: str | None = None,
    old_name: str | None = None,
    new_name: str | None = None,
    threshold_scale: float = 1.0,
) -> dict:
    """Schema-aware diff of two same-schema artifacts.

    Returns ``{"status": "clean"|"regression"|"schema_mismatch", ...}``
    with per-metric rows. ``status`` maps one-to-one onto the CLI's
    pinned exit codes (``BENCHDIFF_EXIT_*``). A diff across KINDS
    compares apples to oranges and refuses outright. Schema versions
    only bump additively in this repo (fields are never removed or
    renamed), so a version difference is same-schema and diffable: it
    is recorded in ``version_change`` and a pinned path present on
    only one side is listed in ``skipped`` instead of judged. At EQUAL
    versions a one-sided pinned path is real schema rot and refuses.
    ``threshold_scale`` scales every significance threshold (CLI
    ``--strict`` passes 0; 2.0 doubles the tolerance)."""
    mismatches: list[str] = []
    old_kind = kind or artifact_kind(old, old_name)
    new_kind = kind or artifact_kind(new, new_name)
    if old_kind is None or new_kind is None:
        mismatches.append(
            "unrecognized artifact kind "
            f"(old={old_kind!r}, new={new_kind!r}) — name the files "
            "<KIND>_r<N>.json or pass kind explicitly"
        )
    elif old_kind != new_kind:
        mismatches.append(f"kind mismatch: {old_kind} vs {new_kind}")
    if mismatches:
        return {
            "status": "schema_mismatch",
            "kind": old_kind if old_kind == new_kind else None,
            "mismatches": mismatches,
            "rows": [],
            "regressions": [],
            "improvements": [],
        }
    ver_old, ver_new = old.get("schema_version"), new.get("schema_version")
    version_change = (
        None if ver_old == ver_new else {"old": ver_old, "new": ver_new}
    )
    rows: list[dict] = []
    regressions: list[str] = []
    improvements: list[str] = []
    skipped: list[str] = []
    rules = COMPARE_RULES.get(old_kind, ())
    for path, direction, threshold in rules:
        a, b = _dotted_get(old, path), _dotted_get(new, path)
        if a is None and b is None:
            continue  # optional section absent in both rounds
        if a is None or b is None:
            if version_change is not None:
                # Additive schema change: the field arrived (or the
                # section is newer than the old round) — declared, not
                # silently dropped, and never judged.
                skipped.append(path)
            else:
                mismatches.append(
                    f"{path}: present in only one artifact at the same "
                    f"schema version ({a!r} vs {b!r})"
                )
            continue
        if not isinstance(a, (int, float)) or not isinstance(b, (int, float)):
            mismatches.append(f"{path}: non-numeric ({a!r} vs {b!r})")
            continue
        thr = threshold * threshold_scale
        delta = b - a
        # Zero baselines: a bare delta/0 makes ANY move from 0.0
        # infinitely relative — no threshold could ever tolerate it, so
        # a 2e-16 float-dust drift off a clean 0.0 (max_sum_error_s)
        # would flag forever. Floor the denominator instead: moves the
        # size of the floor read as moves relative to it, genuine
        # regressions (0 findings → 1) still blow past any threshold.
        rel = delta / max(abs(a), _ZERO_BASELINE_FLOOR)
        adverse = -rel if direction == "higher" else rel
        if adverse > thr:
            verdict = "regression"
            regressions.append(path)
        elif adverse < -thr:
            verdict = "improvement"
            improvements.append(path)
        else:
            verdict = "ok"
        rows.append({
            "path": path,
            "old": a,
            "new": b,
            "delta": round(delta, 6),
            "rel": round(rel, 6) if rel != float("inf") else None,
            "direction": direction,
            "threshold": thr,
            "verdict": verdict,
        })
    if mismatches:
        return {
            "status": "schema_mismatch",
            "kind": old_kind,
            "mismatches": mismatches,
            "rows": rows,
            "regressions": regressions,
            "improvements": improvements,
            "skipped": skipped,
        }
    # Informational sweep: every numeric leaf NOT already covered by a
    # pinned rule, so a reviewer sees what else moved (no verdicts —
    # direction is unknown there by definition).
    pinned = {r["path"] for r in rows}
    leaves_old = _numeric_leaves(old)
    leaves_new = _numeric_leaves(new)
    info: list[dict] = []
    for path in sorted(leaves_old.keys() & leaves_new.keys()):
        if path in pinned or path == "schema_version":
            continue
        a, b = leaves_old[path], leaves_new[path]
        if a != b:
            info.append({
                "path": path, "old": a, "new": b,
                "delta": round(b - a, 6),
            })
    return {
        "status": "regression" if regressions else "clean",
        "kind": old_kind,
        "schema_version": ver_new,
        "version_change": version_change,
        "mismatches": [],
        "rows": rows,
        "regressions": regressions,
        "improvements": improvements,
        "skipped": skipped,
        "info_changes": info,
    }


def benchdiff_selfcheck() -> dict:
    """The regression sentinel's positive control, pinned and
    deterministic (no checked-in files needed): an identical artifact
    pair must compare clean, a synthetically regressed copy must flag,
    and a cross-kind pair must refuse as a schema mismatch — proven for
    the CHAOS, BLACKBOX, TIER, AGG, SPEC, and CONVOY schemas, so every
    pinned rule table a sentinel relies on has a demonstrated trigger.
    The DOCTOR artifact carries the result (``validate_doctor`` gates
    the three headline fields) — a sentinel nobody proved can still
    fire is not a sentinel."""
    base = {
        "metric": "chaos_heal_converge_s",
        "schema_version": CHAOS_SCHEMA_VERSION,
        "value": 0.4,
        "crash": {"resurrection_hit_ratio": 0.95},
        "repair": {"converge_s": 0.4},
    }
    regressed = {
        **base,
        "value": 1.8,  # 4.5x slower heal: past the 50% threshold
        "repair": {"converge_s": 1.8},
    }
    other_kind = {
        "metric": "obs_stitched_node_tracks",
        "schema_version": OBS_SCHEMA_VERSION,
        "value": 6,
    }
    bb_base = {
        "metric": "blackbox_postmortem_named",
        "schema_version": BLACKBOX_SCHEMA_VERSION,
        "value": BLACKBOX_NAMED_TOTAL,
        "history": {"points": 4000, "self_overhead": {"fraction": 0.004}},
    }
    bb_regressed = {
        **bb_base,
        # One lost verdict: the zero-threshold value rule must flag it.
        "value": BLACKBOX_NAMED_TOTAL - 1,
    }
    tier_base = {
        "metric": "tier_hit_rate_gain",
        "schema_version": TIER_SCHEMA_VERSION,
        "value": 8.0,
        "cold_start": {"failed": 0, "corrupt_served": 0},
        "restore_overlap": {"decode_steps_during_restore": 40},
        "meshcheck": {"findings": 0},
    }
    tier_regressed = {
        **tier_base,
        # One corrupt extent served: the zero-threshold rule must flag.
        "cold_start": {"failed": 0, "corrupt_served": 1},
    }
    agg_base = {
        "metric": "agg_fleet_verdicts_named",
        "schema_version": AGG_SCHEMA_VERSION,
        "value": AGG_NAMED_TOTAL,
        "overhead": {"fraction": 0.002},
        "fan_in": {"sweep_s": 0.05},
        "percentiles": {"count": 400},
    }
    agg_regressed = {
        **agg_base,
        # One lost fleet verdict: the zero-threshold value rule must flag.
        "value": AGG_NAMED_TOTAL - 1,
    }
    spec_base = {
        "metric": "spec_accepted_tokens_per_step",
        "schema_version": SPEC_SCHEMA_VERSION,
        "value": 1.6,
        "acceptance": {"accepted_per_step": 1.6},
        "adaptive": {"goodput_ratio": 1.02},
        "itl": {"p99_s": 0.004},
        "overhead": {"fraction": 0.003},
    }
    spec_regressed = {
        **spec_base,
        # Acceptance nearly halved: past the 20% threshold.
        "value": 0.9,
        "acceptance": {"accepted_per_step": 0.9},
    }
    cv_base = {
        "metric": "convoy_ttft_speedup",
        "schema_version": CONVOY_SCHEMA_VERSION,
        "value": 4.0,
        "interleave": {"ttft_ratio": 4.0, "mixed_itl_p99_s": 0.05},
        "stalls": {"mixed_convoy_s_per_req": 0.0},
        "starvation": {"max_defer_observed": 2},
    }
    cv_regressed = {
        **cv_base,
        # The convoy came back: TTFT speedup down 70%, past 25%.
        "value": 1.2,
        "interleave": {"ttft_ratio": 1.2, "mixed_itl_p99_s": 0.05},
    }
    identical = compare_rounds(base, dict(base), kind="CHAOS")
    regression = compare_rounds(base, regressed, kind="CHAOS")
    mismatch = compare_rounds(base, other_kind)
    bb_identical = compare_rounds(bb_base, dict(bb_base), kind="BLACKBOX")
    bb_regression = compare_rounds(bb_base, bb_regressed, kind="BLACKBOX")
    bb_mismatch = compare_rounds(bb_base, base)
    t_identical = compare_rounds(tier_base, dict(tier_base), kind="TIER")
    t_regression = compare_rounds(tier_base, tier_regressed, kind="TIER")
    t_mismatch = compare_rounds(tier_base, base)
    a_identical = compare_rounds(agg_base, dict(agg_base), kind="AGG")
    a_regression = compare_rounds(agg_base, agg_regressed, kind="AGG")
    a_mismatch = compare_rounds(agg_base, base)
    s_identical = compare_rounds(spec_base, dict(spec_base), kind="SPEC")
    s_regression = compare_rounds(spec_base, spec_regressed, kind="SPEC")
    s_mismatch = compare_rounds(spec_base, base)
    c_identical = compare_rounds(cv_base, dict(cv_base), kind="CONVOY")
    c_regression = compare_rounds(cv_base, cv_regressed, kind="CONVOY")
    c_mismatch = compare_rounds(cv_base, base)
    return {
        "identical_clean": identical["status"] == "clean"
        and bb_identical["status"] == "clean"
        and t_identical["status"] == "clean"
        and a_identical["status"] == "clean"
        and s_identical["status"] == "clean"
        and c_identical["status"] == "clean",
        "regression_flagged": regression["status"] == "regression"
        and "repair.converge_s" in regression["regressions"]
        and bb_regression["status"] == "regression"
        and "value" in bb_regression["regressions"]
        and t_regression["status"] == "regression"
        and "cold_start.corrupt_served" in t_regression["regressions"]
        and a_regression["status"] == "regression"
        and "value" in a_regression["regressions"]
        and s_regression["status"] == "regression"
        and "acceptance.accepted_per_step" in s_regression["regressions"]
        and c_regression["status"] == "regression"
        and "interleave.ttft_ratio" in c_regression["regressions"],
        "mismatch_detected": mismatch["status"] == "schema_mismatch"
        and bb_mismatch["status"] == "schema_mismatch"
        and t_mismatch["status"] == "schema_mismatch"
        and a_mismatch["status"] == "schema_mismatch"
        and s_mismatch["status"] == "schema_mismatch"
        and c_mismatch["status"] == "schema_mismatch",
        "kinds_covered": ["CHAOS", "BLACKBOX", "TIER", "AGG", "SPEC", "CONVOY"],
        "regressions_seen": regression["regressions"]
        + bb_regression["regressions"]
        + t_regression["regressions"]
        + a_regression["regressions"]
        + s_regression["regressions"]
        + c_regression["regressions"],
    }


def _emit(full: dict) -> None:
    """Write the FULL report to ``BENCH_FULL_r{N}.json`` and print the
    compact summary as the final stdout line (a driver that records only
    the tail of stdout must still get the whole headline record)."""
    full_path = os.path.join(_REPO, f"BENCH_FULL_r{current_round():02d}.json")
    with open(full_path, "w") as fh:
        json.dump(full, fh, indent=1)
    north = full.get("north_star") or {}
    shapes = north.get("shapes") or {}
    compact = {
        "metric": full.get("metric"),
        "value": full.get("value"),
        "unit": full.get("unit"),
        "backend": full.get("backend"),
        "device": full.get("device"),
        "vs_baseline": full.get("vs_baseline"),
        "vs_dense_same_shape": full.get("vs_dense_same_shape"),
        "int8_vs_bf16": (full.get("int8") or {}).get("vs_bf16"),
        "int8_equal_hbm": (full.get("serving_mix") or {}).get(
            "int8_vs_bf16_equal_hbm"
        ),
        "mfu": (full.get("roofline") or {}).get("mfu"),
        "hbm_bw_util": (full.get("roofline") or {}).get("hbm_bw_util"),
        # Round-5 sections, compacted: the 8B W8A16 decode and the
        # real-weights gate (full detail in the FULL report).
        "llama3_8b_int8_tok_s": (full.get("llama3_8b_int8") or {}).get(
            "tok_s",
            (full.get("llama3_8b_int8") or {}).get("error"),
        ),
        "real_weights": (
            None
            if not isinstance(full.get("north_star_real_weights"), dict)
            else (
                full["north_star_real_weights"].get("skipped")
                or full["north_star_real_weights"].get("error")
                or {
                    "model": full["north_star_real_weights"].get("model"),
                    "base_hit_rate": (
                        (full["north_star_real_weights"].get("shapes") or {})
                        .get("base", {})
                        .get("hit_rate")
                    ),
                    "base_p50_ttft_ms": (
                        (full["north_star_real_weights"].get("shapes") or {})
                        .get("base", {})
                        .get("p50_ttft_ms")
                    ),
                }
            )
        ),
        "slo_overload": (
            None
            if not isinstance(full.get("slo_overload"), dict)
            else full["slo_overload"].get("error")
            or {
                "capacity_tok_s": full["slo_overload"].get("capacity_tok_s"),
                # offered_x → (goodput, shed, max_tier): the curve's shape
                # in one glance; full points in SLO_r{N}.json.
                "points": {
                    str(p.get("offered_x")): [
                        p.get("goodput_tok_s"),
                        p.get("shed_requests"),
                        p.get("max_tier"),
                    ]
                    for p in full["slo_overload"].get("points", [])
                },
            }
        ),
        "north_star": {
            "hit_rate": north.get("hit_rate"),
            "aggregate_hit_rate": north.get("aggregate_hit_rate"),
            "aggregate_reuse_efficiency": north.get(
                "aggregate_reuse_efficiency"
            ),
            "p50_ttft_ms": north.get("p50_ttft_ms"),
            "p99_ttft_ms": north.get("p99_ttft_ms"),
            "wide_p50_ttft_ms": (shapes.get("wide") or {}).get("p50_ttft_ms"),
        },
        "kernel_parity": {
            r["kernel"]: r.get("max_err") for r in full.get("kernel_parity", [])
        },
        "full_report": os.path.basename(full_path),
    }
    print(json.dumps(compact), flush=True)


def _dense_decode_step_fn(cfg):
    """Reference-style baseline: contiguous per-sequence KV cache
    [L, B, max_len, Hkv, D] (the layout a direct torch port would keep),
    dense attention over the full padded context."""
    from radixmesh_tpu.models.llama import _logits, _mlp, _qkv, _PREC
    from radixmesh_tpu.ops.norm import rms_norm
    from radixmesh_tpu.ops.rope import apply_rope, rope_frequencies

    def dense_attn(q, k, v, lengths):  # q [B,Hq,D], k/v [B,S,Hkv,D]
        b, hq, d = q.shape
        hkv = k.shape[2]
        qg = q.reshape(b, hkv, hq // hkv, d)
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
        logits = jnp.einsum(
            "bhgd,bkhd->bhgk", qg, k, preferred_element_type=jnp.float32
        ) * scale
        valid = jnp.arange(k.shape[1])[None, None, None, :] < lengths[:, None, None, None]
        w = jax.nn.softmax(jnp.where(valid, logits, -1e30), axis=-1)
        out = jnp.einsum(
            "bhgk,bkhd->bhgd", w, v.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return out.reshape(b, hq, d).astype(q.dtype)

    @partial(jax.jit, donate_argnums=(1, 2))
    def step(params, cache_k, cache_v, tokens, lengths):
        inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
        positions = lengths - 1
        x = params["embed"][tokens][:, None, :]
        b = tokens.shape[0]

        def layer(carry, xs):
            x, ck, cv = carry
            l_idx, lp = xs
            h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
            q, k, v = _qkv(lp, h, cfg)
            q = apply_rope(q, positions[:, None], inv_freq)
            k = apply_rope(k, positions[:, None], inv_freq)
            lk = jax.vmap(lambda c, kk, p: jax.lax.dynamic_update_slice(
                c, kk, (p, 0, 0)))(ck[l_idx], k.astype(ck.dtype), positions)
            lv = jax.vmap(lambda c, vv, p: jax.lax.dynamic_update_slice(
                c, vv, (p, 0, 0)))(cv[l_idx], v.astype(cv.dtype), positions)
            ck, cv = ck.at[l_idx].set(lk), cv.at[l_idx].set(lv)
            attn = dense_attn(q[:, 0], lk, lv, lengths)
            x = x + jnp.einsum(
                "bqd,qdh->bh",
                attn.reshape(b, cfg.n_heads, cfg.head_dim),
                lp["wo"].reshape(cfg.n_heads, cfg.head_dim, cfg.hidden),
                precision=_PREC,
            )[:, None, :]
            h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
            x = x + _mlp(lp, h2)
            return (x, ck, cv), None

        (x, cache_k, cache_v), _ = jax.lax.scan(
            layer, (x, cache_k, cache_v), (jnp.arange(cfg.n_layers), params["layers"])
        )
        return _logits(params, cfg, x)[:, 0], cache_k, cache_v

    return step


def _validate_kernels(cfg, page_size: int) -> list[dict]:
    """Compile every Pallas paged-attention variant through Mosaic on the
    chip at this config's head geometry and compare each with its jnp
    oracle BEFORE timing anything (``ops/kernel_check.py`` — the same
    check ``chip_smoke.py`` runs at the served geometry)."""
    from radixmesh_tpu.ops.kernel_check import (
        DEFAULT_CASES,
        FLAG_CASES,
        check_kernels,
    )

    results = check_kernels(
        DEFAULT_CASES + FLAG_CASES, hq=cfg.n_heads, hkv=cfg.n_kv_heads,
        head_dim=cfg.head_dim, page=page_size,
    )
    for r in results:
        log(f"kernel parity: {r}")
    bad = [r for r in results if not r["ok"]]
    if bad:
        raise AssertionError(f"kernels disagree with their oracles: {bad}")
    return results


# Public per-chip peaks (bf16 FLOPs, HBM bytes/s) keyed on device_kind
# substrings; used for roofline context only. Unknown chips report null.
_CHIP_PEAKS = {
    "v5 lite": (197e12, 819e9),  # v5e
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v6 lite": (918e12, 1640e9),  # v6e / Trillium
    "v6e": (918e12, 1640e9),
}


def _n_params(cfg) -> int:
    qd = cfg.n_heads * cfg.head_dim
    kvd = cfg.n_kv_heads * cfg.head_dim
    per_layer = (
        2 * cfg.hidden  # norms
        + cfg.hidden * qd  # wq
        + 2 * cfg.hidden * kvd  # wk, wv
        + qd * cfg.hidden  # wo
        + 3 * cfg.hidden * cfg.intermediate  # gate, up, down
    )
    head = 0 if cfg.tie_embeddings else cfg.hidden * cfg.vocab_size
    return cfg.vocab_size * cfg.hidden + cfg.n_layers * per_layer + cfg.hidden + head


def _roofline(cfg, batch: int, ctx: int, sec_per_step: float) -> dict:
    """MFU + HBM bandwidth utilization for one decode step (VERDICT
    round-1 weak #6: ``vs_baseline`` alone is self-referential — these
    anchor the number to the chip's physical ceilings)."""
    n_params = _n_params(cfg)
    # Matmul FLOPs: 2·params per token — minus the embedding table (a
    # lookup, not a matmul), plus the LM-head matmul when the table is
    # tied (it still multiplies) — and attention's QK^T + PV per head over
    # the context, EVERY layer.
    matmul_params = n_params - cfg.vocab_size * cfg.hidden
    if cfg.tie_embeddings:
        matmul_params += cfg.hidden * cfg.vocab_size
    flops = batch * (
        2 * matmul_params
        + 4 * ctx * cfg.n_heads * cfg.head_dim * cfg.n_layers
    )
    # HBM reads: all weights once (batch amortizes; decode is the
    # weight+KV streaming regime) + this layer-set's KV for every sequence.
    bytes_moved = 2 * n_params + batch * ctx * cfg.n_layers * (
        2 * cfg.n_kv_heads * cfg.head_dim * 2
    )
    kind = jax.devices()[0].device_kind.lower()
    peak = next(
        (v for k, v in _CHIP_PEAKS.items() if k in kind), None
    )
    out = {
        "flops_per_step": flops,
        "hbm_bytes_per_step": bytes_moved,
        "achieved_tflops": round(flops / sec_per_step / 1e12, 2),
        "achieved_hbm_gbs": round(bytes_moved / sec_per_step / 1e9, 1),
    }
    if peak:
        out["mfu"] = round(flops / sec_per_step / peak[0], 4)
        out["hbm_bw_util"] = round(bytes_moved / sec_per_step / peak[1], 4)
    else:
        out["mfu"] = out["hbm_bw_util"] = None
    return out


def _time_loop(run_once, iters: int) -> float:
    """Seconds per iteration. State is threaded through and ``run_once``
    receives the iteration number so every step computes something new —
    identical repeated steps can be served from an execution cache by a
    device runtime, which would make the timing fiction."""
    state = run_once(None, 0)  # warmup / compile
    state = run_once(state, 1)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for i in range(iters):
        state = run_once(state, 2 + i)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / iters


def _paged_layout(lengths: list[int], page_size: int):
    """Contiguous page runs for a batch of ragged sequences: page table,
    decode slots (each row writes position ``len-1``), total pool slots."""
    pages_per_row = [(l + page_size - 1) // page_size for l in lengths]
    maxp = max(pages_per_row)
    pt = np.zeros((len(lengths), maxp), np.int32)
    slots = np.zeros((len(lengths),), np.int32)
    next_page = 0
    for b, (l, n) in enumerate(zip(lengths, pages_per_row)):
        pt[b, :n] = np.arange(next_page, next_page + n)
        slots[b] = pt[b, (l - 1) // page_size] * page_size + (l - 1) % page_size
        next_page += n
    return pt, slots, next_page * page_size


def _measure_paged(cfg, params, page_size, buckets, iters, quant=False):
    """Seconds per decode iteration over a shared paged pool, where each
    iteration runs one ``decode_step`` launch PER BUCKET of same-max-length
    rows. Page tables are per-launch arrays into one pool, so bucketing by
    length costs nothing — short rows never attend over long rows'
    padding. A single uniform bucket is the plain case. Returns
    ``(sec_per_iter, pool_slots)``."""
    from radixmesh_tpu.models.llama import decode_step

    layouts = []
    pool_slots = 0
    for lengths in buckets:
        pt, slots, n = _paged_layout(lengths, page_size)
        layouts.append((
            jnp.asarray(pt + pool_slots // page_size),
            jnp.asarray(slots + pool_slots),
            jnp.asarray(np.asarray(lengths, np.int32)),
        ))
        pool_slots += n
    if quant:
        kv_pool = jnp.zeros(
            (2, cfg.n_layers, cfg.n_kv_heads, pool_slots, cfg.head_dim),
            jnp.int8)
        kv_scale = jnp.zeros(
            (2, cfg.n_layers, cfg.n_kv_heads, pool_slots), jnp.float32)
    else:
        kv_pool = jnp.zeros(
            (2, cfg.n_layers, cfg.n_kv_heads, pool_slots, cfg.head_dim),
            cfg.dtype)
        kv_scale = None
    rng = np.random.default_rng(7)
    token_iters = [
        jnp.asarray(
            rng.integers(0, cfg.vocab_size, (iters + 2, len(lengths))),
            jnp.int32,
        )
        for lengths in buckets
    ]

    def run(state, i):
        pool, scale = (kv_pool, kv_scale) if state is None else state
        for (pt, slots, lens), toks in zip(layouts, token_iters):
            res = decode_step(
                params, cfg, toks[i], pool, slots, pt, lens, page_size,
                kv_scale=scale,
            )
            if scale is not None:
                _, pool, scale = res
            else:
                _, pool = res
        return pool, scale

    return _time_loop(run, iters), pool_slots


def _measure_dense(cfg, params, lengths: list[int], max_len: int, iters):
    """Seconds per decode step for the reference-style contiguous cache
    ``[L, B, max_len, Hkv, D]`` — every row padded to ``max_len``, dense
    attention masked by length (the padding is read either way; that cost
    is the point of comparison)."""
    dense_step = _dense_decode_step_fn(cfg)
    batch = len(lengths)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    ck0 = jnp.zeros(shape, cfg.dtype)
    cv0 = jnp.zeros(shape, cfg.dtype)
    lens = jnp.asarray(np.asarray(lengths, np.int32))
    rng = np.random.default_rng(11)
    token_iters = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (iters + 2, batch)), jnp.int32
    )

    def run(state, i):
        ck, cv = (ck0, cv0) if state is None else state
        _, ck, cv = dense_step(params, ck, cv, token_iters[i], lens)
        return ck, cv

    return _time_loop(run, iters)


def _ctx_sweep(cfg, params, page_size) -> list[dict]:
    """Paged vs dense per-step time at the SAME uniform shape across
    context lengths (VERDICT round-2 next-step #2: record the crossover,
    not one toy point). Batch shrinks with ctx so the KV footprint stays
    inside one chip's HBM."""
    shapes = [(128, 64), (1024, 64), (4096, 16), (16384, 4)]
    iters = 16
    out = []
    for ctx, batch in shapes:
        sec_paged, _ = _measure_paged(
            cfg, params, page_size, [[ctx] * batch], iters
        )
        sec_dense = _measure_dense(cfg, params, [ctx] * batch, ctx, iters)
        row = {
            "ctx": ctx,
            "batch": batch,
            "paged_tok_s": round(batch / sec_paged, 1),
            "dense_tok_s": round(batch / sec_dense, 1),
            "ratio": round(sec_dense / sec_paged, 3),
        }
        log(
            f"ctx sweep ctx={ctx} batch={batch}: paged {sec_paged*1e3:.2f} "
            f"ms/step vs dense {sec_dense*1e3:.2f} ms/step "
            f"(ratio {row['ratio']})"
        )
        out.append(row)
    return out


def _serving_mix(cfg, params, page_size) -> dict:
    """The serving-relevant comparison at an EQUAL KV HBM budget.

    Workload: a mixed-length decode batch (1 in 8 rows at a long context,
    the rest short — the multi-turn tail shape). The paged pool stores
    exactly the tokens present, so the whole batch fits the budget, and
    per-bucket page tables mean short rows' attention reads only their own
    pages. The dense baseline must allocate every row at the longest
    context, so the SAME byte budget admits only ``budget // max_len``
    sequences — padding waste surfaced as throughput, which is the
    fundamental cost of the contiguous layout (bucketing dense compute
    cannot recover the allocation). Both paths then decode flat out;
    tokens/s is the recorded quantity."""
    long_len, short_len, batch, iters = 4096, 512, 32, 16
    lengths = [long_len if i % 8 == 0 else short_len for i in range(batch)]
    long_rows = [l for l in lengths if l == long_len]
    short_rows = [l for l in lengths if l != long_len]
    sec_paged, pool_slots = _measure_paged(
        cfg, params, page_size, [long_rows, short_rows], iters
    )
    dense_batch = max(pool_slots // long_len, 1)
    dense_lengths = lengths[:dense_batch]
    sec_dense = _measure_dense(cfg, params, dense_lengths, long_len, iters)
    paged_tok_s = batch / sec_paged
    dense_tok_s = dense_batch / sec_dense
    out = {
        "long_ctx": long_len,
        "short_ctx": short_len,
        "budget_kv_slots": pool_slots,
        "paged": {"batch": batch, "tok_s": round(paged_tok_s, 1)},
        "dense": {"batch": dense_batch, "tok_s": round(dense_tok_s, 1)},
        "ratio": round(paged_tok_s / dense_tok_s, 3),
    }
    log(
        f"serving mix (budget {pool_slots} KV slots): paged batch {batch} "
        f"-> {paged_tok_s:.1f} tok/s vs dense batch {dense_batch} -> "
        f"{dense_tok_s:.1f} tok/s (ratio {out['ratio']})"
    )
    # int8 at the SAME byte budget: D int8 bytes + one f32 scale per
    # (slot, layer, head) vs 2D bf16 bytes → ~1.94x the slots, spent on
    # MORE rows of the same mix. Capacity-as-throughput is the int8
    # story on chip — the same-shape comparison pays the scale-gather
    # overhead without banking the capacity it buys.
    slots8 = pool_slots * (2 * cfg.head_dim) // (cfg.head_dim + 4)

    def _mix_slots(n: int) -> int:
        return sum(long_len if i % 8 == 0 else short_len for i in range(n))

    batch8 = max(1, batch * slots8 // pool_slots)
    while batch8 > 1 and _mix_slots(batch8) > slots8:
        # The slot-ratio estimate can overshoot the byte budget by a few
        # rows (the mix is lumpy: every 8th row is long) — an "equal
        # HBM" comparison must fit INSIDE the budget, not near it.
        batch8 -= 1
    lengths8 = [long_len if i % 8 == 0 else short_len for i in range(batch8)]
    sec_int8, used8 = _measure_paged(
        cfg, params, page_size,
        [[l for l in lengths8 if l == long_len],
         [l for l in lengths8 if l != long_len]],
        iters, quant=True,
    )
    int8_tok_s = batch8 / sec_int8
    out["paged_int8"] = {
        "batch": batch8, "tok_s": round(int8_tok_s, 1), "slots": used8,
    }
    out["int8_vs_bf16_equal_hbm"] = round(int8_tok_s / paged_tok_s, 3)
    log(
        f"serving mix int8 (same bytes -> {used8} slots): batch {batch8} "
        f"-> {int8_tok_s:.1f} tok/s ({out['int8_vs_bf16_equal_hbm']}x vs "
        "bf16 paged)"
    )
    return out


def _overload_sweep(cfg, params, page_size: int) -> dict:
    """Goodput-vs-offered-load curve through the SLO control plane
    (``radixmesh_tpu/slo/``): calibrate this backend's serving capacity
    closed-loop, then drive open-loop multi-tenant traffic at 0.5/1/2/4×
    that capacity through an ``SLORunner`` and record goodput, shedding,
    TTFT percentiles, and per-tier degradation events at each point.
    Writes the full curve to ``SLO_r{N}.json`` (the round's overload
    artifact) and returns a summary for the bench report.

    The deterministic virtual-clock version of this scenario is pinned by
    ``tests/test_overload_storm.py``; this sweep is the wall-clock analog
    with the real engine (jit, batching, cache) in the loop."""
    from radixmesh_tpu.engine.engine import Engine
    from radixmesh_tpu.engine.request import SamplingParams
    from radixmesh_tpu.slo import SLOConfig, TenantConfig
    from radixmesh_tpu.slo.runner import SLORunner
    from radixmesh_tpu.workload import OverloadWorkload, run_overload_workload

    prompt_len, gen_len = 48, 8
    duration_s = 4.0
    tenants = {"a": 2.0, "b": 1.0, "c": 1.0}

    def fresh_engine(name):
        return Engine(
            cfg, params, num_slots=16384, page_size=page_size,
            max_batch=8, name=name,
        )

    # Calibration 1 (closed loop): warm the jit caches at the sweep's own
    # request shape and take the unloaded TTFT the deadline is a multiple
    # of (so the 4x point's shed decisions are relative to THIS backend,
    # not a hardcoded latency).
    eng = fresh_engine("slo-calib")
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, prompt_len).tolist() for _ in range(24)
    ]
    eng.generate(prompts[:8], SamplingParams(max_new_tokens=gen_len))  # warm/jit
    n_ttft = len(eng.stats.ttft_s)
    t0 = time.monotonic()
    eng.generate(prompts[8:], SamplingParams(max_new_tokens=gen_len))
    calib_s = time.monotonic() - t0
    closed_loop_tok_s = 16 * (prompt_len + gen_len) / calib_s
    ttft_unloaded = float(np.median(eng.stats.ttft_s[n_ttft:]))
    deadline_s = max(0.5, 10 * ttft_unloaded)

    # Calibration 2 (open loop): the closed-loop number overstates what
    # the admission path can move — engine.generate batches one wave with
    # zero scheduler overhead, while the sweep pays runner/pump/lock
    # costs per request. The sweep's offered-load multiples must be
    # relative to the path being swept, so saturate the SLO path itself
    # (no deadlines: nothing sheds, everything serves) and take the
    # achieved request rate as 1.0x. Capacity is in PROMPT tokens/s —
    # the same currency OverloadWorkload's offered rate is priced in.
    cap_engine = fresh_engine("slo-cap")
    # Same small-batch jit warm-up the per-point engines get: the cap
    # run's queue ramps from empty through batches of 1, 2, 4..., and a
    # compile stall inside the saturation window would deflate
    # capacity_tok_s — rescaling every offered_x multiple below.
    cap_engine.generate(prompts[:1], SamplingParams(max_new_tokens=gen_len))
    cap_engine.generate(prompts[:3], SamplingParams(max_new_tokens=gen_len))
    cap_runner = SLORunner(cap_engine, SLOConfig(
        tenants={k: TenantConfig(weight=w) for k, w in tenants.items()},
    )).start()
    try:
        cap_rep = run_overload_workload(cap_runner, OverloadWorkload(
            tenants=tenants,
            duration_s=min(duration_s, 2.0),
            offered_tokens_per_s=2.0 * closed_loop_tok_s,
            prompt_len=prompt_len,
            gen_len=gen_len,
            vocab_size=cfg.vocab_size,
            seed=99,
        ))
    finally:
        cap_runner.close()
    capacity_tok_s = (
        cap_rep["served_requests"] * prompt_len / cap_rep["elapsed_s"]
    )
    log(
        f"slo sweep: open-loop capacity ~{capacity_tok_s:.0f} prompt tok/s "
        f"(closed-loop ceiling {closed_loop_tok_s:.0f} tok/s), unloaded "
        f"TTFT {ttft_unloaded*1e3:.1f} ms, deadline {deadline_s*1e3:.0f} ms"
    )

    points = []
    for mult in (0.5, 1.0, 2.0, 4.0):
        engine = fresh_engine(f"slo-x{mult}")
        runner = SLORunner(engine, SLOConfig(
            tenants={k: TenantConfig(weight=w) for k, w in tenants.items()},
            default_ttft_slo_s=deadline_s,
            # Arm the degradation ladder BELOW the deadline backlog:
            # deadline shedding caps the estimated backlog near
            # deadline_s, so thresholds above it would never trip and
            # the artifact would record no tier events.
            tier_backlog_s=(
                0.25 * deadline_s, 0.5 * deadline_s, 0.75 * deadline_s,
            ),
        ))
        # Warm this engine's small-batch jit buckets before traffic: the
        # calibration engine only exercised full waves, and a mid-point
        # compile stall at light load reads as a spurious deadline miss.
        engine.generate(prompts[:1], SamplingParams(max_new_tokens=gen_len))
        engine.generate(prompts[:3], SamplingParams(max_new_tokens=gen_len))
        runner.start()
        try:
            wl = OverloadWorkload(
                tenants=tenants,
                duration_s=duration_s,
                offered_tokens_per_s=mult * capacity_tok_s,
                prompt_len=prompt_len,
                gen_len=gen_len,
                vocab_size=cfg.vocab_size,
                seed=int(mult * 10),
            )
            rep = run_overload_workload(
                runner, wl, ttft_deadline_s=deadline_s
            )
            snap = runner.ctl.snapshot()
            point = {
                "offered_x": mult,
                "offered_tok_s": round(mult * capacity_tok_s, 1),
                "offered_requests": rep["offered_requests"],
                "admitted_requests": rep["admitted_requests"],
                "shed_requests": rep["shed_requests"],
                "shed_by_reason": rep["shed_by_reason"],
                "goodput_tok_s": round(rep["goodput_tok_s"], 1),
                "deadline_met_frac": round(rep["deadline_met_frac"], 4),
                "p50_ttft_ms": round(rep["p50_ttft_s"] * 1e3, 1),
                "p99_ttft_ms": round(rep["p99_ttft_s"] * 1e3, 1),
                "admitted_tokens_by_tenant": rep["admitted_tokens_by_tenant"],
                "max_tier": max(
                    (new for _, _, new, _ in runner.ctl.tier_events),
                    default=0,
                ),
                "tier_events": [
                    {"t_s": round(t, 3), "from": old, "to": new,
                     "backlog_s": b}
                    for t, old, new, b in runner.ctl.tier_events
                ],
                "total_shed": snap["total_shed"],
            }
            points.append(point)
            log(
                f"slo sweep x{mult}: offered {rep['offered_requests']} "
                f"admitted {rep['admitted_requests']} shed "
                f"{rep['shed_requests']} goodput "
                f"{rep['goodput_tok_s']:.0f} tok/s p99_ttft "
                f"{rep['p99_ttft_s']*1e3:.0f} ms max_tier "
                f"{point['max_tier']}"
            )
        finally:
            runner.close()

    out = {
        "metric": "slo_goodput_vs_offered_load",
        "backend": jax.default_backend(),
        "capacity_tok_s": round(capacity_tok_s, 1),
        "capacity_basis": "prompt tokens/s served through the SLO "
                          "admission path at saturation (deadline-free)",
        "closed_loop_tok_s": round(closed_loop_tok_s, 1),
        "ttft_deadline_ms": round(deadline_s * 1e3, 1),
        "prompt_len": prompt_len,
        "gen_len": gen_len,
        "tenants": tenants,
        "duration_s_per_point": duration_s,
        "points": points,
    }
    path = os.path.join(_REPO, f"SLO_r{current_round():02d}.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    log(f"slo sweep: wrote {os.path.basename(path)}")
    out["artifact"] = os.path.basename(path)
    return out


def main() -> int:
    from radixmesh_tpu.models.llama import ModelConfig, init_params

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
    }
    if jax.default_backend() != "tpu":
        log(f"bench: needs a TPU, found {device} — nothing measured")
        return 2
    cfg = ModelConfig(
        vocab_size=32768, hidden=2048, n_layers=16, n_heads=16,
        n_kv_heads=8, head_dim=128, intermediate=8192, rope_scaling=None,
    )
    batch, ctx, page_size, iters = 64, 1024, 16, 32
    log(f"bench: device={device} batch={batch} ctx={ctx} "
        f"layers={cfg.n_layers} hidden={cfg.hidden}")
    parity = _validate_kernels(cfg, page_size)

    params = init_params(cfg, jax.random.PRNGKey(0))

    # --- headline shape: paged vs dense vs int8, uniform ctx -------------
    sec_paged, _ = _measure_paged(cfg, params, page_size, [[ctx] * batch], iters)
    tok_s = batch / sec_paged
    log(f"paged decode: {sec_paged*1e3:.2f} ms/step, {tok_s:.1f} tok/s")
    sec_dense = _measure_dense(cfg, params, [ctx] * batch, ctx, iters)
    log(f"dense decode: {sec_dense*1e3:.2f} ms/step, {batch/sec_dense:.1f} tok/s")
    sec_quant, _ = _measure_paged(
        cfg, params, page_size, [[ctx] * batch], iters, quant=True
    )
    log(f"int8 paged decode: {sec_quant*1e3:.2f} ms/step, "
        f"{batch/sec_quant:.1f} tok/s ({sec_paged/sec_quant:.2f}x vs bf16)")

    sweep = _ctx_sweep(cfg, params, page_size)
    mix = _serving_mix(cfg, params, page_size)

    roof = _roofline(cfg, batch, ctx, sec_paged)
    log(
        f"roofline: {roof['achieved_tflops']} TFLOP/s, "
        f"{roof['achieved_hbm_gbs']} GB/s (mfu={roof['mfu']}, "
        f"hbm_util={roof['hbm_bw_util']})"
    )

    north = _north_star(cfg, params, page_size)
    real = _real_weights_north_star()
    m8b = _bench_8b_int8()
    slo = _overload_sweep(cfg, params, page_size)
    fleet = _fleet_pass()
    kvflow = _kvflow_pass()
    chaos = _chaos_pass()

    _emit({
        "metric": "decode_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tok/s",
        "backend": jax.default_backend(),
        "device": device,
        "kernel_parity": parity,
        # Throughput at an equal KV HBM budget on the mixed-length batch
        # (see module docstring) — the serving-relevant baseline ratio.
        "vs_baseline": mix["ratio"],
        "vs_dense_same_shape": round(sec_dense / sec_paged, 3),
        "ctx_sweep": sweep,
        "serving_mix": mix,
        "int8": {
            "tok_s": round(batch / sec_quant, 1),
            "vs_bf16": round(sec_paged / sec_quant, 3),
        },
        "roofline": roof,
        "north_star": north,
        "north_star_real_weights": real,
        "llama3_8b_int8": m8b,
        "slo_overload": slo,
        "fleet": fleet,
        "kvflow": kvflow,
        "chaos": chaos,
    })
    return 0


_REAL_CKPT = os.path.join(_REPO, "artifacts", "real_ckpt")


def _real_weights_north_star() -> dict:
    """The serving gate with REAL machinery end to end (VERDICT round-4
    missing #1): a registry model loaded from an HF-format sharded
    safetensors checkpoint through ``models/hf_io.py``, a trained BPE
    tokenizer through ``server/tokenizer.py``, and a TEXT workload — not
    generated token ids. The checkpoint is produced in-environment by
    ``scripts/make_real_ckpt.py`` (random weights, declared — no
    checkpoint is fetchable with zero egress); hit-rate/TTFT mechanics
    are weight-value-independent, so the gate numbers are real."""
    if not os.path.isdir(_REAL_CKPT):
        return {"skipped": f"{_REAL_CKPT} missing — run "
                           f"scripts/make_real_ckpt.py first"}
    with open(os.path.join(_REAL_CKPT, "provenance.json")) as fh:
        provenance = json.load(fh)
    if provenance.get("tiny"):
        # A --tiny artifact's shards don't match the preset's dims; a
        # scarce TPU window must get a clear skip, not a shape error.
        return {"skipped": f"{_REAL_CKPT} holds a --tiny checkpoint — "
                           f"regenerate with scripts/make_real_ckpt.py "
                           f"(no --tiny)"}
    from radixmesh_tpu.engine.engine import Engine
    from radixmesh_tpu.models import get_config
    from radixmesh_tpu.models.hf_io import load_hf_checkpoint
    from radixmesh_tpu.server.tokenizer import load_tokenizer
    from radixmesh_tpu.workload import (
        TextMultiTurnWorkload,
        run_engine_workload,
    )

    preset = provenance["model"]
    cfg = get_config(preset)
    t0 = time.monotonic()
    params = load_hf_checkpoint(_REAL_CKPT, cfg)
    tokenizer = load_tokenizer(_REAL_CKPT)
    load_s = time.monotonic() - t0
    log(f"real-weights: loaded {preset} from {_REAL_CKPT} in "
        f"{load_s:.0f}s (tokenizer vocab {tokenizer.vocab_size})")
    engine = Engine(
        cfg, params, num_slots=32768, page_size=16, max_batch=16,
        name="bench-real",
    )
    shapes = {
        "base": dict(n_conversations=16, n_turns=4, system_sentences=10,
                     user_sentences=5, gen_len=16),
        "wide": dict(n_conversations=32, n_turns=2, system_sentences=10,
                     user_sentences=14, gen_len=16),
    }
    out_shapes = {}
    for i, (name, sizes) in enumerate(shapes.items()):
        # Distinct warm-up system prefix: the default head ("You are
        # a helpful assistant. ") is shared with the measured
        # workload, so warming with it seeds cross-workload prefix
        # hits the measured run's ceiling model does not credit —
        # reuse_efficiency could exceed its upper-bound semantics
        # (ADVICE round-5 #2). A disjoint head keeps the jit warmup
        # (same length buckets) without donating cache hits.
        warm = TextMultiTurnWorkload(
            tokenizer, seed=i + 1000,
            system_prefix="Calibration warmup preamble text. ", **sizes,
        )
        run_engine_workload(engine, warm)
        wl = TextMultiTurnWorkload(tokenizer, seed=i, **sizes)
        ns = run_engine_workload(engine, wl)
        out_shapes[name] = {
            "requests": ns["requests"],
            "hit_rate": round(ns["hit_rate"], 4),
            "ceiling_hit_rate": round(ns["ceiling_hit_rate"], 4),
            "reuse_efficiency": round(ns["reuse_efficiency"], 4),
            "p50_ttft_ms": round(ns["p50_ttft_s"] * 1e3, 2),
            "p99_ttft_ms": round(ns["p99_ttft_s"] * 1e3, 2),
        }
        log(f"real-weights[{name}]: hit_rate={ns['hit_rate']:.3f} "
            f"p50_ttft={ns['p50_ttft_s']*1e3:.1f} ms")
    return {
        "model": preset,
        "weights_source": provenance["weights"],
        "tokenizer": provenance["tokenizer"],
        "checkpoint_format": "HF sharded safetensors via models/hf_io.py",
        "load_s": round(load_s, 1),
        "shapes": out_shapes,
        "targets": {"hit_rate": 0.70, "p50_ttft_ms": 200.0},
    }


def _bench_8b_int8() -> dict:
    """Decode an 8B-class model on the one chip: llama3-8b with W8A16
    weights + int8 KV — ~8.1 GB weights + ~1.1 GB pool fit a 16 GB v5e
    that bf16 weights alone (16 GB) cannot. Weights are random
    (``init_params(weight_quant="int8")`` draws them directly in W8A16
    form on device, so the bf16 8B never materializes anywhere). Random
    weights don't change decode throughput: the step streams the same
    bytes through the same kernels regardless of values."""
    from radixmesh_tpu.models import get_config, init_params

    cfg = get_config("llama3-8b")
    batch, ctx, page_size, iters = 16, 1024, 16, 8
    t0 = time.monotonic()
    params = jax.block_until_ready(
        init_params(cfg, jax.random.PRNGKey(0), weight_quant="int8")
    )
    init_s = time.monotonic() - t0
    log(f"8b-int8: init {init_s:.0f}s; measuring decode "
        f"(batch={batch}, ctx={ctx}, int8 KV)")
    t0 = time.monotonic()
    sec, pool_slots = _measure_paged(
        cfg, params, page_size, [[ctx] * batch], iters, quant=True
    )
    log(f"8b-int8: {sec*1e3:.1f} ms/step, {batch/sec:.1f} tok/s "
        f"({pool_slots} pool slots)")
    return {
        "model": "llama3-8b",
        "weights_source": "random-init W8A16",
        "weight_quant": "int8",
        "kv_quant": "int8",
        "batch": batch,
        "ctx": ctx,
        "ms_per_step": round(sec * 1e3, 2),
        "tok_s": round(batch / sec, 1),
        "init_s": round(init_s, 1),
        "measure_s": round(time.monotonic() - t0, 1),
    }


def _north_star(cfg, params, page_size: int) -> dict:
    """ShareGPT-style multi-turn serving through the Engine: prefix-cache
    hit-rate and p50 TTFT vs the BASELINE.json targets (>=70%, <200 ms).

    Three adversarial workload SHAPES (VERDICT round-2 weak #3: one
    32-request configuration left the 70% gate one conversation from
    failing) — the base multi-turn mix, a deep-conversation shape (few
    users, many turns: within-conversation reuse dominates), and a wide
    fan-out shape (many users, two turns, long fresh user text: the
    hardest case, most unshared tokens per request) — >=256 requests
    total. Hit-rate is aggregated over ALL prompt tokens; each shape also
    reports its own. A warmup pass per shape with identical length
    buckets (different seed, so no cross-hits) takes jit compiles out of
    the measured TTFTs — steady-state serving latency is what the target
    speaks to."""
    from radixmesh_tpu.engine.engine import Engine
    from radixmesh_tpu.workload import MultiTurnWorkload, run_engine_workload

    shapes = {
        "base": dict(n_conversations=24, n_turns=4, system_len=128,
                     user_len=64, gen_len=16),
        "deep": dict(n_conversations=8, n_turns=10, system_len=128,
                     user_len=96, gen_len=16),
        "wide": dict(n_conversations=48, n_turns=2, system_len=128,
                     user_len=192, gen_len=32),
    }
    eng_slots, max_batch = 65536, 16  # 4.3 GB bf16 pool
    engine = Engine(
        cfg, params, num_slots=eng_slots, page_size=page_size,
        max_batch=max_batch, name="bench",
    )
    per_shape = {}
    shape_tokens: dict[str, int] = {}
    tot_prompt = tot_cached = tot_req = 0
    all_ttft: list[float] = []
    trace_artifact: dict = {}
    for shape_idx, (name, sizes) in enumerate(shapes.items()):
        # Warmup must mirror the measured run's SHAPES (same conversation
        # count → same batched-prefill buckets), or the group-prefill
        # compile variants land inside measured TTFTs.
        warm = MultiTurnWorkload(
            vocab_size=cfg.vocab_size, seed=shape_idx + 1000, **sizes
        )
        run_engine_workload(engine, warm)
        wl = MultiTurnWorkload(
            vocab_size=cfg.vocab_size, seed=shape_idx, **sizes
        )
        ns = run_engine_workload(engine, wl)
        per_shape[name] = {
            "requests": ns["requests"],
            "hit_rate": round(ns["hit_rate"], 4),
            # What an infinite cache would score on this shape — the wide
            # fan-out shape's traffic is MOSTLY unreusable by
            # construction, so raw hit-rate is not comparable across
            # shapes; measured/ceiling is.
            "ceiling_hit_rate": round(ns["ceiling_hit_rate"], 4),
            "reuse_efficiency": round(ns["reuse_efficiency"], 4),
            "p50_ttft_ms": round(ns["p50_ttft_s"] * 1e3, 2),
        }
        tot_prompt += ns["prompt_tokens"]
        tot_cached += ns["cached_tokens"]
        tot_req += ns["requests"]
        shape_tokens[name] = ns["prompt_tokens"]
        all_ttft.extend(ns["ttft_s"])
        log(
            f"north-star[{name}]: {ns['requests']} reqs, "
            f"hit_rate={ns['hit_rate']:.3f} "
            f"(ceiling {ns['ceiling_hit_rate']:.3f}, "
            f"efficiency {ns['reuse_efficiency']:.3f}), "
            f"p50_ttft={ns['p50_ttft_s']*1e3:.1f} ms"
        )
    # Request-flight trace artifact (TRACE_r{N}.json — load in Perfetto):
    # captured in a SEPARATE, UNTIMED pass after every measured shape, so
    # the gated rates above never include flight-recorder overhead and
    # stay comparable with pre-tracing rounds. The traced pass reuses the
    # base sizes under a fresh seed; its numbers fold into nothing.
    from radixmesh_tpu.obs.trace_plane import (
        FlightRecorder,
        configure,
        set_recorder,
    )

    try:
        configure(capacity=1 << 16, sample=1.0)
        trace_path = os.path.join(_REPO, f"TRACE_r{current_round():02d}.json")
        traced = run_engine_workload(
            engine,
            MultiTurnWorkload(
                vocab_size=cfg.vocab_size, seed=2000, **shapes["base"]
            ),
            trace_path=trace_path,
        )
        trace_artifact = {
            "trace_artifact": os.path.basename(trace_path),
            "trace_spans": traced.get("trace_spans", 0),
        }
        log(f"trace: {trace_artifact['trace_spans']} spans -> "
            f"{trace_artifact['trace_artifact']} (untimed pass)")
    finally:
        set_recorder(FlightRecorder())  # back to the disabled default

    hit_rate = tot_cached / tot_prompt if tot_prompt else 0.0
    # Aggregate ceiling: token-weighted over the shapes' own ceilings —
    # the wide shape's traffic is mostly unreusable BY CONSTRUCTION, so
    # the aggregate's first-class gate is reuse efficiency (how close to
    # an infinite cache), not the raw rate (VERDICT round-3 weak #2).
    agg_ceiling = (
        sum(
            per_shape[n]["ceiling_hit_rate"] * shape_tokens[n]
            for n in per_shape
        ) / tot_prompt
        if tot_prompt
        else 0.0
    )
    agg_eff = hit_rate / agg_ceiling if agg_ceiling else 0.0
    p50 = float(np.median(all_ttft)) if all_ttft else 0.0
    p99 = float(np.quantile(all_ttft, 0.99)) if all_ttft else 0.0
    log(
        f"north-star: {tot_req} reqs total, aggregate hit_rate={hit_rate:.3f}; "
        f"ShareGPT-like gate (base shape) hit_rate="
        f"{per_shape['base']['hit_rate']:.3f} (target >=0.70); "
        f"p50_ttft={p50*1e3:.1f} ms (target <200), p99_ttft={p99*1e3:.1f} ms"
    )
    return {
        # The BASELINE.json target speaks to ShareGPT-shaped multi-turn
        # traffic — the "base" shape. The aggregate additionally folds in
        # the deliberately adversarial deep/wide sweeps (weak #3's ask),
        # whose ceilings differ; per-shape efficiency tells cache quality.
        "hit_rate": round(per_shape["base"]["hit_rate"], 4),
        "aggregate_hit_rate": round(hit_rate, 4),
        "aggregate_ceiling_hit_rate": round(agg_ceiling, 4),
        "aggregate_reuse_efficiency": round(agg_eff, 4),
        "p50_ttft_ms": round(p50 * 1e3, 2),
        "p99_ttft_ms": round(p99 * 1e3, 2),
        "requests": tot_req,
        "shapes": per_shape,
        **trace_artifact,
        # First-class gates: base-shape raw rate (the ShareGPT-like
        # BASELINE target) AND aggregate reuse efficiency (raw aggregate
        # is ceiling-bound by the adversarial wide shape).
        "targets": {
            "hit_rate": 0.70,
            "aggregate_reuse_efficiency": 0.90,
            "p50_ttft_ms": 200.0,
        },
    }


if __name__ == "__main__":
    sys.exit(main())
