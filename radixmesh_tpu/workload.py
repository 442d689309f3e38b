"""Synthetic ShareGPT-style multi-turn serving workload.

The north-star benchmark (``BASELINE.json`` "north_star") targets ≥70%
prefix-cache hit-rate and p50 TTFT < 200 ms on ShareGPT multi-turn
conversations — the reference never measures it (its benchmark has no
timers, ``benchmark.py:24-31``, ``README.md:58``). No dataset download is
possible (or needed): what makes ShareGPT traffic cache-friendly is its
*shape* — a system prompt shared across conversations plus per-conversation
histories that grow turn by turn, so turn k's prompt is turn k-1's full
context plus a little new text. This module generates exactly that shape,
deterministically.

Usage::

    wl = MultiTurnWorkload(n_conversations=16, n_turns=4, ...)
    report = run_engine_workload(engine, wl)
    report["hit_rate"], report["p50_ttft_s"]
"""

# meshcheck: file-ok[sleep-audit] workload generators and scenario
# drivers pace traffic, settle gossip, and hold chaos windows by wall
# clock BY DESIGN — nothing here runs on a serving thread.

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

__all__ = [
    "MultiTurnWorkload",
    "TextMultiTurnWorkload",
    "run_blackbox_workload",
    "run_chaos_workload",
    "run_engine_workload",
    "run_obs_workload",
    "run_tier_workload",
    "synth_text",
]

# A small English word stock for deterministic synthetic conversations —
# the TEXT analog of the token-id workload, for runs with a real
# tokenizer in the loop (no dataset is fetchable in this environment;
# what matters for the cache is the ShareGPT *shape*, and for the
# tokenizer that input is realistic prose-like byte sequences, not
# uniform ids).
_WORDS = (
    "the of and to in is that it for on with as are this be at or from "
    "have an they which one you had not but what all were when we there "
    "can more if out so said about up its into than them then some could "
    "time these two may first new now people my made over did down only "
    "way find use work part take place years live back give most very "
    "after things our just name good sentence man think say great where "
    "help through much before line right too means old any same tell boy "
    "follow came want show also around form three small set put end does "
    "another well large must big even such because turn here why ask went "
    "men read need land different home us move try kind hand picture "
    "again change off play spell air away animal house point page letter "
    "mother answer found study still learn should world high every near "
    "add food between own below country plant last school father keep "
    "tree never start city earth eye light thought head under story saw "
    "left few while along might close something seem next hard open "
    "example begin life always those both paper together got group often "
    "run important until children side feet car mile night walk white "
    "sea began grow took river four carry state once book hear stop "
    "without second later miss idea enough eat face watch far really "
    "almost let above girl sometimes mountain cut young talk soon list "
    "song being leave family body music color stand sun question fish "
    "area mark dog horse birds problem complete room knew since ever "
    "piece told usually friends easy heard order red door sure become "
    "top ship across today during short better best however low hours "
    "black products happened whole measure remember early waves reached"
).split()


def synth_text(rng: np.ndarray, n_sentences: int) -> str:
    """Deterministic prose-like text: ``n_sentences`` sentences of 6-14
    stock words, capitalized and period-terminated."""
    out = []
    for _ in range(n_sentences):
        n = int(rng.integers(6, 15))
        words = [_WORDS[int(i)] for i in rng.integers(0, len(_WORDS), n)]
        out.append(words[0].capitalize() + " " + " ".join(words[1:]) + ".")
    return " ".join(out)


@dataclass
class _Conversation:
    conv_id: int
    context: list[int] = field(default_factory=list)  # grows with each turn


class MultiTurnWorkload:
    """Deterministic multi-turn conversations over a token-id vocabulary.

    Every conversation opens with the same ``system_len``-token system
    prefix (cross-conversation sharing); each turn appends fresh user
    tokens to the conversation's accumulated context (within-conversation
    sharing — the dominant ShareGPT pattern)."""

    def __init__(
        self,
        n_conversations: int = 16,
        n_turns: int = 4,
        system_len: int = 32,
        user_len: int = 16,
        gen_len: int = 8,
        vocab_size: int = 512,
        seed: int = 0,
    ):
        self.n_conversations = n_conversations
        self.n_turns = n_turns
        self.gen_len = gen_len
        rng = np.random.default_rng(seed)
        # Token 0 is avoided: engines commonly reserve low ids for specials.
        self.system = rng.integers(1, vocab_size, size=system_len).tolist()
        self._user_turns = [
            [
                rng.integers(1, vocab_size, size=user_len).tolist()
                for _ in range(n_turns)
            ]
            for _ in range(n_conversations)
        ]
        self.conversations = [
            _Conversation(conv_id=i, context=list(self.system))
            for i in range(n_conversations)
        ]

    def round_prompts(self, turn: int) -> list[tuple[_Conversation, list[int]]]:
        """Turn ``turn`` of every conversation: (conversation, full prompt)."""
        out = []
        for conv in self.conversations:
            prompt = conv.context + self._user_turns[conv.conv_id][turn]
            out.append((conv, prompt))
        return out

    def record_reply(self, conv: _Conversation, prompt: list[int], reply: list[int]) -> None:
        conv.context = prompt + reply

    @property
    def max_context_len(self) -> int:
        """Upper bound on final context length (for pool/engine sizing)."""
        per_turn = (
            max(len(t) for turns in self._user_turns for t in turns)
            + self.gen_len
        )
        return len(self.system) + self.n_turns * per_turn


class TextMultiTurnWorkload(MultiTurnWorkload):
    """The multi-turn workload built from TEXT through a real tokenizer
    (VERDICT round-4 missing #1: every on-chip number so far used
    generated token ids — this is the path with ``server/tokenizer.py``
    actually in the loop). Same interface and cache-shape as
    :class:`MultiTurnWorkload`: one shared system prompt, per-turn fresh
    user text appended to the conversation context."""

    def __init__(
        self,
        tokenizer,
        n_conversations: int = 16,
        n_turns: int = 4,
        system_sentences: int = 8,
        user_sentences: int = 4,
        gen_len: int = 8,
        seed: int = 0,
        system_prefix: str = "You are a helpful assistant. ",
    ):
        self.tokenizer = tokenizer
        self.n_conversations = n_conversations
        self.n_turns = n_turns
        self.gen_len = gen_len
        rng = np.random.default_rng(seed)
        # ``system_prefix`` is part of the cache key space: two workloads
        # share cross-workload prefix hits iff their prefixes tokenize to
        # the same head. Warm-up passes must pass a DISTINCT prefix so a
        # measured run's hit_rate credits only traffic its own ceiling
        # model accounts for (ADVICE round-5: the shared default head let
        # warm-up reuse inflate reuse_efficiency past its upper bound).
        self.system_text = system_prefix + synth_text(rng, system_sentences)
        self.system = tokenizer.encode(self.system_text)
        self._user_turns = [
            [
                tokenizer.encode(" User: " + synth_text(rng, user_sentences))
                for _ in range(n_turns)
            ]
            for _ in range(n_conversations)
        ]
        self.conversations = [
            _Conversation(conv_id=i, context=list(self.system))
            for i in range(n_conversations)
        ]


def run_engine_workload(
    engine, workload: MultiTurnWorkload, trace_path: str | None = None
) -> dict:
    """Drive the workload through an :class:`Engine` turn-round by
    turn-round (each round's requests run concurrently through the
    continuous batcher, like simultaneous users) and report the
    north-star metrics from the engine's own counters.

    With ``trace_path`` (and the flight recorder enabled — see
    ``obs/trace_plane.configure``), the run's spans are drained into a
    Chrome trace-event artifact next to the numeric report, so every
    number comes with the timeline that produced it.

    ``ceiling_hit_rate`` is what an INFINITE, never-evicting cache would
    score on the same traffic (page-aligned like real admission): turn
    k > 0 can reuse at most the conversation's full prior context, turn 0
    at most the shared system prefix. Workload shapes differ wildly in
    how much of their traffic is reusable at all — ``hit_rate /
    ceiling_hit_rate`` (``reuse_efficiency``) is the cache-quality signal
    that is comparable ACROSS shapes.

    Caveat on turn-0-heavy shapes: the ceiling credits every turn-0
    request after the very first with full system-prefix reuse, but all
    of a round's turn-0 requests run concurrently in ONE generate()
    batch, where admission order may publish the system prefix too late
    for siblings in the same wave to reuse it. ``reuse_efficiency`` can
    therefore structurally read < 1 on wide shapes even with a perfect
    cache — it is an upper-bound denominator, not an achievable one."""
    from radixmesh_tpu.engine.request import SamplingParams

    sampling = SamplingParams(
        temperature=0.0, max_new_tokens=workload.gen_len
    )
    page = getattr(engine, "page_size", 1)
    start_prompt = engine.stats.prompt_tokens
    start_cached = engine.stats.cached_tokens
    start_ttft = len(engine.stats.ttft_s)
    ceiling = 0
    total_prompt = 0
    served_system = False
    for turn in range(workload.n_turns):
        pairs = workload.round_prompts(turn)
        for conv, prompt in pairs:
            reusable = len(conv.context) if turn > 0 else (
                len(workload.system) if served_system else 0
            )
            # Admission reuse is page-floored and capped below the full
            # prompt (the final token always recomputes its logits).
            ceiling += min(reusable, len(prompt) - 1) // page * page
            total_prompt += len(prompt)
            served_system = True
        replies = engine.generate([p for _, p in pairs], sampling)
        for (conv, prompt), reply in zip(pairs, replies):
            workload.record_reply(conv, prompt, reply)
    prompt_tokens = engine.stats.prompt_tokens - start_prompt
    cached_tokens = engine.stats.cached_tokens - start_cached
    ttft = engine.stats.ttft_s[start_ttft:]
    hit_rate = cached_tokens / prompt_tokens if prompt_tokens else 0.0
    ceiling_rate = ceiling / total_prompt if total_prompt else 0.0
    trace_extra = {}
    if trace_path is not None:
        from radixmesh_tpu.obs.trace_plane import write_trace

        trace_extra = {
            "trace_artifact": trace_path,
            "trace_spans": write_trace(trace_path),
        }
    return {
        **trace_extra,
        "requests": workload.n_conversations * workload.n_turns,
        "prompt_tokens": prompt_tokens,
        "cached_tokens": cached_tokens,
        "hit_rate": hit_rate,
        "ceiling_hit_rate": ceiling_rate,
        "reuse_efficiency": hit_rate / ceiling_rate if ceiling_rate else 0.0,
        "p50_ttft_s": float(np.median(ttft)) if ttft else 0.0,
        "p99_ttft_s": float(np.quantile(ttft, 0.99)) if ttft else 0.0,
        # The exact per-request samples for this run (preemption retries
        # append extra entries to the engine's global list, so callers
        # must NOT slice that by request count).
        "ttft_s": list(ttft),
    }


def _pair_converged(a, b) -> bool:
    """Two replicas agree: scalar fingerprints on a full-replica mesh;
    per CO-OWNED shard under sharding (whole-tree fingerprints diverge
    by design there — cache/sharding.py)."""
    if not getattr(a, "sharded", False):
        return a.tree.fingerprint_ == b.tree.fingerprint_
    afp, bfp = a.tree.shard_fingerprints(), b.tree.shard_fingerprints()
    own = a.ownership
    if own is None:
        return True
    return all(
        afp.get(sid, 0) == bfp.get(sid, 0)
        for sid in own.owned_shards(a.rank)
        if own.is_owner(b.rank, sid)
    )


def _trees_converged(nodes, router_mesh=None) -> bool:
    """Fleet-wide convergence predicate for the chaos gates. Full
    replica: one fingerprint across every node (router included — its
    rank-only replica hashes value-blind). Sharded: every shard's owner
    set agrees on that shard's fingerprint, and (when given) the router
    mesh's gossip-fed shard-convergence audit concurs."""
    from radixmesh_tpu.config import NodeRole

    sharded = any(getattr(n, "sharded", False) for n in nodes)
    if not sharded:
        if len({n.tree.fingerprint_ for n in nodes}) != 1:
            return False
        if router_mesh is not None:
            return bool(router_mesh.fleet.convergence()["converged"])
        return True
    ring = [n for n in nodes if n.role is not NodeRole.ROUTER]
    by_rank = {n.rank: n for n in ring}
    for n in ring:
        own = n.ownership
        if own is None:
            continue
        for sid in own.owned_shards(n.rank):
            fps = {
                m.tree.shard_fingerprints().get(sid, 0)
                for r, m in by_rank.items()
                if own.is_owner(r, sid)
            }
            if len(fps) > 1:
                return False
    if router_mesh is not None:
        return bool(router_mesh.fleet.shard_convergence()["converged"])
    return True


def _chaos_join_drain_phases(
    *,
    nodes,
    ring,
    router_mesh,
    by_addr,
    cr,
    fleet_planes,
    repair_planes,
    lifecycle_planes,
    plan,
    faults,
    prefill,
    partitioned,
    rng,
    wait_for,
    key_len,
    seed,
    drop_p,
    drain_requests,
    drain_inflight,
    join_partition_s,
    digest_interval_s,
    repair_interval_s,
    age_threshold_s,
    bootstrap_probe_interval_s,
    bootstrap_round_budget,
    timeout_s,
) -> tuple[dict, dict]:
    """Phases 5+6 of ``run_chaos_workload`` (membership lifecycle,
    ``policy/lifecycle.py``): graceful drain of cp2 under re-opened
    seeded loss, then a COLD rejoin of the same address while cp1 sits
    behind a partition. Mutates the armed ``plan`` between phases (every
    wrapped edge shares the object, and the per-edge RNG streams stay
    seeded) and returns ``(drain_report, join_report)``."""
    import time as _time

    from radixmesh_tpu.cache.mesh_cache import MeshCache
    from radixmesh_tpu.cache.repair_plane import RepairConfig, RepairPlane
    from radixmesh_tpu.config import MeshConfig
    from radixmesh_tpu.obs.fleet_plane import FleetPlane
    from radixmesh_tpu.policy.lifecycle import (
        LifecycleConfig,
        LifecyclePlane,
        LifecycleState,
    )

    target_addr = prefill[2]
    target = by_addr[target_addr]
    t_rank = target.rank
    t_node_idx = nodes.index(target)
    t_ring_idx = ring.index(target)

    # A replicated warm set owned by the drain target: after the rejoin
    # these keys are exactly the hits the router must WITHHOLD while the
    # reincarnation bootstraps (its replica is cold; the router's rank-2
    # values are not).
    joiner_keys = [
        rng.integers(0, 600, size=key_len).astype(np.int32)
        for _ in range(6)
    ]
    for k in joiner_keys:
        target.insert(k, np.arange(key_len, dtype=np.int32))
    live = [n for n in nodes]
    wait_for(lambda: _trees_converged(live))

    # ---- phase 5: drain under sustained seeded loss -------------------
    plan.partitions = ()
    plan.drop_start_s, plan.drop_end_s = 0.0, float("inf")
    faults.rebase()
    # Simulated in-flight work parked at the target when the drain hits.
    inflight_keys = [
        rng.integers(0, 600, size=key_len).astype(np.int32)
        for _ in range(drain_inflight)
    ]
    for k in inflight_keys:
        target.insert(k, np.arange(key_len, dtype=np.int32))
    dead_before = sum(
        int(n._m_succ_trans["dead"].value) for n in nodes
    )
    requeue_state = {"served": 0}

    def _requeue_inflight() -> int:
        # The router must refuse the DRAINING node new work before
        # anything is re-placed (the state digest re-publishes every
        # interval, so a seeded drop of one frame only delays this).
        wait_for(
            lambda: router_mesh.fleet.lifecycle_of(t_rank)
            in ("draining", "left"),
            timeout=timeout_s,
        )
        served = 0
        for k in inflight_keys:
            res = cr.cache_aware_route(k)
            alt = by_addr.get(res.prefill_addr)
            if alt is None or alt is target:
                continue
            alt.insert(k, np.arange(key_len, dtype=np.int32))
            if alt.match_prefix(k).length == key_len:
                served += 1
        requeue_state["served"] = served
        return len(inflight_keys)

    def _writeback_stub() -> int:
        # Mesh-level stand-in for the engine's hot-prefix flush (the
        # real path — HierarchicalCache.evict through the PR 4 fused
        # write-back lane — is exercised by the engine-level lifecycle
        # tests): count the hot tokens the replica holds at drain time.
        with target._lock:
            return int(
                target.tree.evictable_size_ + target.tree.protected_size_
            )

    tlc = LifecyclePlane(
        target,
        repair=repair_planes[t_node_idx],
        fleet_plane=fleet_planes[t_ring_idx],
        cfg=LifecycleConfig(
            drain_timeout_s=10.0, leave_confirm_s=0.25, leave_retries=3,
        ),
        requeue_fn=_requeue_inflight,
        writeback_fn=_writeback_stub,
    )
    lifecycle_planes.append(tlc)
    dstats = tlc.drain(deadline_s=10.0)
    survivors = [n for n in nodes if n is not target]
    left_everywhere = wait_for(
        lambda: all(not n.view.contains(t_rank) for n in survivors),
        timeout=timeout_s,
    )
    # Serve a stream through the still-open loss window: ZERO failures
    # allowed, and nothing may land on the drained node.
    d_attempted = d_ok = 0
    for _ in range(drain_requests):
        key = rng.integers(0, 600, size=key_len).astype(np.int32)
        d_attempted += 1
        try:
            res = cr.cache_aware_route(key)
            alt = by_addr.get(res.prefill_addr)
            if alt is None or alt is target:
                raise RuntimeError(
                    f"routed to {res.prefill_addr} mid-drain"
                )
            alt.insert(key, np.arange(key_len, dtype=np.int32))
            if alt.match_prefix(key).length != key_len:
                raise RuntimeError("local match missed a local insert")
            d_ok += 1
        except Exception:  # noqa: BLE001 — failures are the measurement
            pass
        _time.sleep(0.01)
    dead_after = sum(int(n._m_succ_trans["dead"].value) for n in nodes)
    left_transitions = sum(
        int(n._m_succ_trans["left"].value) for n in survivors
    )
    # The drained process exits: stop its planes and close its mesh.
    fleet_planes[t_ring_idx].close()
    repair_planes[t_node_idx].close()
    target.close()
    del by_addr[target_addr]
    drain_report = {
        "performed": True,
        "node": target_addr,
        "drop_p": drop_p,
        "requeued": int(dstats["requeued"]),
        "requeued_served": int(requeue_state["served"]),
        "attempted_during_drain": d_attempted,
        "ok_during_drain": d_ok,
        "zero_failed": bool(
            d_ok == d_attempted
            and requeue_state["served"] == dstats["requeued"]
        ),
        "left_without_failure_detection": bool(
            left_everywhere and dead_after == dead_before
        ),
        "left_cause_transitions": left_transitions,
        "writeback_tokens": int(dstats["writeback_tokens"]),
        "writeback_flushed": bool(dstats["writeback_flushed"]),
        "shard_transfer": dstats.get("shard_transfer"),
        "drain_s": round(float(dstats["drain_s"]), 3),
    }

    # ---- phase 6: cold rejoin during an active partition --------------
    plan.drop_p = 0.0
    plan.partitions = (
        faults.PartitionSpec(
            start_s=0.0, end_s=join_partition_s, addrs=(partitioned,)
        ),
    )
    faults.rebase()
    t_join0 = _time.monotonic()
    base_cfg = target.cfg
    jcfg = MeshConfig(
        prefill_nodes=list(base_cfg.prefill_nodes),
        decode_nodes=list(base_cfg.decode_nodes),
        router_nodes=list(base_cfg.router_nodes),
        local_addr=target_addr,
        protocol="inproc",
        tick_interval_s=base_cfg.tick_interval_s,
        gc_interval_s=base_cfg.gc_interval_s,
        failure_timeout_s=base_cfg.failure_timeout_s,
        replication_factor=base_cfg.replication_factor,
        shard_summary_interval_s=base_cfg.shard_summary_interval_s,
        heat_half_life_s=base_cfg.heat_half_life_s,
    )
    joiner = MeshCache(jcfg, pool=None).start()
    nodes.append(joiner)
    by_addr[target_addr] = joiner
    jrepair = RepairPlane(
        joiner,
        RepairConfig(
            interval_s=repair_interval_s,
            age_threshold_s=age_threshold_s,
            backoff_base_s=max(0.25, repair_interval_s),
            backoff_max_s=5.0,
            round_budget=bootstrap_round_budget,
        ),
        seed=seed,
    ).start()
    repair_planes.append(jrepair)
    jlc = LifecyclePlane(
        joiner,
        repair=jrepair,
        cfg=LifecycleConfig(
            bootstrap_grace_s=max(10.0, 6.0 * join_partition_s),
            bootstrap_deadline_s=timeout_s,
            bootstrap_probe_interval_s=bootstrap_probe_interval_s,
            bootstrap_round_budget=bootstrap_round_budget,
            tick_interval_s=min(0.05, repair_interval_s),
        ),
        bootstrap=True,
    )
    lifecycle_planes.append(jlc)
    jfleet = FleetPlane(joiner, interval_s=digest_interval_s).start()
    jlc.fleet_plane = jfleet
    fleet_planes.append(jfleet)
    jlc.start()
    joiner.wait_ready(timeout=timeout_s)
    # While the reincarnation bootstraps, the router must withhold every
    # cache hit pointing at it (the warm set routes by rank-2 values the
    # router still holds) — hash-ring fallback serves instead.
    wh0 = cr.withheld_hits
    hits_to_bootstrapping = 0
    probe_deadline = _time.monotonic() + timeout_s
    while (
        jlc.state is LifecycleState.BOOTSTRAPPING
        and _time.monotonic() < probe_deadline
    ):
        for k in joiner_keys:
            res = cr.cache_aware_route(k)
            if res.prefill_addr == target_addr and res.prefill_cache_hit:
                hits_to_bootstrapping += 1
        _time.sleep(0.05)
    became_active = wait_for(
        lambda: jlc.state is LifecycleState.ACTIVE, timeout=timeout_s
    )
    donor_rank = jlc.bootstrap_donor
    donor_node = next(
        (n for n in nodes if n is not joiner and n.rank == donor_rank),
        None,
    )
    converged_with_donor = bool(
        became_active
        and donor_node is not None
        and _pair_converged(joiner, donor_node)
    )
    # Partition off; the whole surviving fleet must converge again.
    plan.partitions = ()
    live = [n for n in nodes if n is not target]
    fleet_converged = wait_for(
        lambda: _trees_converged(live), timeout=timeout_s
    )
    # Hits to the joiner resume once it is ACTIVE.
    wait_for(
        lambda: router_mesh.fleet.lifecycle_of(t_rank) == "active",
        timeout=timeout_s,
    )
    post_hits = 0
    for k in joiner_keys:
        res = cr.cache_aware_route(k)
        if res.prefill_addr == target_addr and res.prefill_cache_hit:
            post_hits += 1
    join_report = {
        "performed": True,
        "joiner": target_addr,
        "donor_rank": donor_rank,
        "partition_active_at_join": True,
        "partition_s": join_partition_s,
        "partitioned_node": partitioned,
        "bootstrap_converge_s": (
            None
            if jlc.bootstrap_converge_s is None
            else round(jlc.bootstrap_converge_s, 3)
        ),
        "bootstrap_rounds": int(jlc.bootstrap_rounds),
        "round_budget": bootstrap_round_budget,
        "within_round_budget": bool(
            became_active
            and jlc.bootstrap_rounds <= bootstrap_round_budget
        ),
        "converged_with_donor": converged_with_donor,
        "withheld_hits": int(cr.withheld_hits - wh0),
        "hits_to_bootstrapping": hits_to_bootstrapping,
        "post_bootstrap_hits": post_hits,
        "fleet_converged_after_join": bool(fleet_converged),
        "join_s": round(_time.monotonic() - t_join0, 3),
    }
    return drain_report, join_report


def _chaos_crash_phase(
    *,
    by_addr,
    cr,
    plan,
    faults,
    prefill,
    decode,
    rng,
    seed,
    drop_p,
    crash_streams,
    crash_tokens,
    crash_deadline_s,
    kill_planes=lambda node: (),
) -> dict:
    """Phase 7 of ``run_chaos_workload`` (request recovery,
    ``server/recovery.py``): an UNCLEAN decode-node kill mid-stream
    under re-opened seeded loss.

    ``crash_streams`` live streams decode round-robin (each emitted
    token grows the stream's replicated prefix, the engine's
    ``stream_publish_tokens`` behavior at mesh scale). Halfway through,
    one decode node is process-killed (``FaultPlan.kill`` — stops
    serving AND stops acking). The serving edge's recovery plane must
    then deliver the acceptance gates the CHAOS v3 schema pins:

    - ``failed == 0`` — every stream completes; an unclean death is a
      latency blip, not a request loss.
    - Every interrupted stream resumes with a **byte-identical**
      already-delivered prefix (final streams equal the deterministic
      per-stream expectation — a resume that re-emitted or skipped a
      token breaks equality).
    - Resurrection is a cache hit: the surviving node's match over
      ``prompt+delivered`` covers ≥ 0.8 of the replayed tokens (the
      replicated tree is what makes recovery nearly free).
    - Deadline budgets bound every hop: no stream overruns its
      admission deadline by more than one retry backoff.

    Failure detection here is the EDGE's per-hop timeout (a killed
    process stops acking; the edge's timer is the fast trigger — the
    mesh's ``cause=dead`` ring detection is deliberately out of window,
    exactly like production where failure_timeout >> hop timeout). A
    hedged-prefill drill (straggler duplicated, first-writer-wins,
    loser cancelled) runs under the same loss window."""
    import time as _time

    from radixmesh_tpu.policy.retry import RetryPolicy
    from radixmesh_tpu.server.recovery import (
        HopTimeout,
        NodeDied,
        RecoveryCoordinator,
    )

    t_phase = _time.monotonic()
    # Re-open the seeded loss window for the whole phase; no partitions.
    plan.partitions = ()
    plan.drop_p = drop_p
    plan.drop_start_s, plan.drop_end_s = 0.0, float("inf")
    faults.rebase()

    policy = RetryPolicy(
        hop_timeout_s=0.4,
        max_retries=4,
        backoff_base_s=0.05,
        backoff_max_s=0.4,
        jitter_frac=0.25,
        hedge_after_s=0.15,
    )
    coord = RecoveryCoordinator(policy, name="chaos-edge", seed=seed)
    detect_t = {"first": None}
    coord.on_node_dead.append(
        lambda addr, cause: detect_t.__setitem__(
            "first", detect_t["first"] or _time.monotonic()
        )
    )

    def token_of(stream_seed: int, i: int) -> int:
        # Deterministic continuation per (stream, position): byte-exact
        # resume verification needs the expected stream to be computable
        # independently of which node served which token.
        return int((stream_seed * 7919 + i * 104729 + 13) % 600)

    # -- admit streams and decode the first half (all live at the kill) --
    streams = []
    for s in range(crash_streams):
        prompt = rng.integers(0, 600, size=len(prefill) * 5 + 1).astype(
            np.int32
        )
        rec = coord.admit(
            prompt, deadline_s=crash_deadline_s, seed=seed * 1009 + s
        )
        res = cr.cache_aware_route(prompt)
        rec.addr = res.decode_addr
        streams.append(rec)

    def emit_one(rec) -> None:
        node = by_addr[rec.addr]
        i = len(rec.delivered)
        tok = token_of(rec.seed, i)
        key = np.concatenate(
            [rec.resume_key(), np.asarray([tok], dtype=np.int32)]
        )
        node.insert(key, np.arange(len(key), dtype=np.int32))
        rec.deliver(tok)

    half = crash_tokens // 2
    for i in range(half):
        for rec in streams:
            emit_one(rec)

    # -- process-level kill of the busiest decode node ------------------
    per_addr: dict = {}
    for rec in streams:
        per_addr[rec.addr] = per_addr.get(rec.addr, 0) + 1
    victim = max(decode, key=lambda a: per_addr.get(a, 0))
    interrupted = [r for r in streams if r.addr == victim]
    # The victim dies mid-stream, not mid-enqueue: its sender is a
    # thread, and on a busy machine a kill at whatever instant the loop
    # above ended can find every publish still queued in the victim. So
    # the kill waits (bounded) for the victim's own outbound lanes to
    # drain, and for nothing else — what the survivors then hold of the
    # interrupted streams, through the seeded loss and the ring's hops,
    # is what the resurrection gate measures.
    by_addr[victim].flush_outbound()
    plan.kill(victim)
    victim_node = by_addr[victim]
    for plane in kill_planes(victim_node):
        plane.close()  # the whole process dies: its planes die with it
    victim_node.close()
    t_kill = _time.monotonic()

    # -- the recovery plane drives every stream to completion -----------
    hit_acct = {"replayed": 0, "cached": 0, "measured": set()}
    route_stats = {"failover": 0}

    def make_route_fn(rec):
        # Sticky per-stream routing, like a production SSE edge: a live
        # stream keeps flowing to the node serving it and re-routes ONLY
        # once failure detection clears rec.addr (the coordinator nulls
        # it on HopTimeout/NodeDied). Re-consulting the router mid-
        # stream would let a healthy replica silently adopt the stream
        # (harmless, but it would bypass the recovery path this phase
        # exists to prove — especially under sharding, where co-owners
        # advertise depth ties).
        def route_fn(key, exclude):
            cur = rec.addr
            if cur is not None and cur not in exclude:
                return cur
            res = cr.cache_aware_route(key, exclude=exclude)
            if res.decode_failover:
                route_stats["failover"] += 1
            return res.decode_addr

        return route_fn

    def serve_fn(addr, rec, hop_deadline_s):
        deadline = _time.monotonic() + hop_deadline_s
        while len(rec.delivered) < crash_tokens:
            if plan.is_killed(addr):
                # A killed process stops acking: the edge sees silence
                # until its per-hop timer fires — THE fast trigger.
                wait = deadline - _time.monotonic()
                if wait > 0:
                    _time.sleep(wait)
                raise HopTimeout(f"no progress from {addr}")
            if rec.resurrections and rec.rid not in hit_acct["measured"]:
                # Resume prefill: measure the surviving replica's cached
                # coverage of prompt+delivered BEFORE re-inserting it.
                hit_acct["measured"].add(rec.rid)
                rkey = rec.resume_key()
                hit_acct["replayed"] += len(rkey)
                hit_acct["cached"] += int(
                    by_addr[addr].match_prefix(rkey).length
                )
            emit_one(rec)

    failed = 0
    reports = []
    for rec in streams:
        try:
            reports.append(
                coord.run_to_completion(rec, make_route_fn(rec), serve_fn)
            )
        except Exception:  # noqa: BLE001 — failures are the measurement
            failed += 1
    detect_s = (
        None
        if detect_t["first"] is None
        else round(detect_t["first"] - t_kill, 3)
    )

    # Byte-identical resume: every final stream must equal the
    # deterministic expectation token-for-token — a resumed stream that
    # re-emitted, skipped, or reordered a token breaks this.
    prefix_identical = all(
        rec.delivered == [token_of(rec.seed, i) for i in range(crash_tokens)]
        for rec in streams
        if not rec.failed
    )
    resumed = sum(1 for r in interrupted if r.done and r.resurrections)
    max_overrun = max((r.budget.overrun_s() for r in streams), default=0.0)
    max_backoff = max((r.max_backoff_s for r in streams), default=0.0)
    within_budget = all(r.overrun_within_one_backoff() for r in streams)

    # -- hedged-prefill drill: straggler duplicated, first-writer-wins --
    h_prompt = rng.integers(0, 600, size=16).astype(np.int32)
    h_rec = coord.admit(h_prompt, deadline_s=crash_deadline_s)
    survivors_p = [a for a in prefill if a in by_addr and not plan.is_killed(a)]
    straggler, backup = survivors_p[0], survivors_p[1]
    cancelled = []

    def slow_leg():
        # A straggling prefill: well past the hedge threshold.
        _time.sleep(4 * policy.hedge_after_s)
        by_addr[straggler].insert(
            h_prompt, np.arange(len(h_prompt), dtype=np.int32)
        )
        return straggler

    def fast_leg():
        by_addr[backup].insert(
            h_prompt, np.arange(len(h_prompt), dtype=np.int32)
        )
        return backup

    hedge_out = coord.hedged(
        h_rec,
        (straggler, slow_leg, lambda: cancelled.append(straggler)),
        (backup, fast_leg, lambda: cancelled.append(backup)),
    )
    coord.finish(h_rec)

    replayed = max(1, hit_acct["replayed"])
    return {
        "performed": True,
        "node": victim,
        "drop_p": drop_p,
        "streams": crash_streams,
        "tokens_per_stream": crash_tokens,
        "killed_at_token": half,
        "interrupted": len(interrupted),
        "resumed": resumed,
        "failed": failed,
        "prefix_identical": bool(prefix_identical),
        "replayed_tokens": int(hit_acct["replayed"]),
        "replayed_cached_tokens": int(hit_acct["cached"]),
        "resurrection_hit_ratio": round(hit_acct["cached"] / replayed, 4),
        "retries": int(sum(r["retries"] for r in reports)),
        "resurrections": int(sum(r["resurrections"] for r in reports)),
        "failover_routes": int(route_stats["failover"]),
        "detection": {
            "trigger": "hop_timeout",
            "hop_timeout_s": policy.hop_timeout_s,
            "detect_s": detect_s,
        },
        "budget": {
            "deadline_s": crash_deadline_s,
            "max_overrun_s": round(max_overrun, 4),
            "max_backoff_s": round(max_backoff, 4),
            "within_one_backoff": bool(within_budget),
        },
        "hedge": {
            "fired": bool(hedge_out["hedged"]),
            "winner": hedge_out["winner"],
            "first_writer_wins": hedge_out["winner"] == backup,
            "loser_cancelled": bool(hedge_out["loser_cancelled"]),
        },
        "crash_s": round(_time.monotonic() - t_phase, 3),
    }


def _chaos_rebalance_phase(
    *,
    ring,
    router_mesh,
    by_addr,
    rng,
    wait_for,
    key_len: int,
    zipf_keys: int = 24,
    zipf_inserts: int = 160,
    zipf_alpha: float = 1.6,
    hits_per_request: int = 5,
    wave_s: float = 2.0,
    settle_s: float = 2.0,
    mid_requests: int = 20,
    max_moves_per_round: int = 4,
    skew_trigger: float = 2.0,
    timeout_s: float = 30.0,
) -> dict:
    """Rebalance-under-storm (the closed robustness loop,
    cache/rebalance.py): a zipf-keyed storm concentrates insert+hit
    heat on one shard's owners; the view master's RebalancePlane must
    see the gossiped skew, boost the hot shards' owner sets (bounded
    moves), hand the cached entries to the gained owners with ZERO
    failed requests mid-move, and — once the fleet converges on the
    override version — a second storm wave's reads fan out across the
    boosted replicas until the router-observed skew score STRICTLY
    drops. Deterministic: zipf counts (not samples), manual decider
    ticks, deadline-bounded waits."""
    import time as _time

    from radixmesh_tpu.cache.rebalance import RebalanceConfig, RebalancePlane
    from radixmesh_tpu.cache.sharding import NUM_SHARDS, shard_of_tokens
    from radixmesh_tpu.router.cache_aware_router import CacheAwareRouter

    t_phase = _time.monotonic()
    # A phase-local router with an aggressive shed policy and a fresh
    # load tracker: fan-out-under-boost IS the mechanism being proven,
    # so the hot replica must shed to its (boosted) owner peers well
    # before the default production thresholds.
    cr = CacheAwareRouter(
        router_mesh, router_mesh.cfg,
        overload_factor=1.5, overload_floor=6.0, load_tau_s=5.0,
    )
    cr.finish_warm_up()
    # One plane per ring node; decisions are manual ticks (the thread
    # cadence is a production concern, not a phase invariant) and only
    # the view master's plane ever acts.
    planes = [
        RebalancePlane(
            n,
            RebalanceConfig(
                interval_s=3600.0,
                skew_trigger=skew_trigger,
                boost_factor=1.5,
                shrink_factor=1.1,
                rf_boost=2,
                max_moves_per_round=max_moves_per_round,
            ),
        )
        for n in ring
    ]
    try:
        weights = np.arange(1, zipf_keys + 1, dtype=np.float64) ** (
            -zipf_alpha
        )
        counts = np.maximum(
            1, np.floor(zipf_inserts * weights / weights.sum()).astype(int)
        )
        keys = [
            np.concatenate(
                [
                    np.asarray([8101 + k], dtype=np.int32),
                    rng.integers(1, 600, size=key_len - 1).astype(np.int32),
                ]
            )
            for k in range(zipf_keys)
        ]
        reqs = [k for k, c in enumerate(counts) for _ in range(int(c))]
        reqs = [keys[i] for i in rng.permutation(reqs)]
        page = max(1, ring[0].page)
        by_rank = {n.rank: n for n in ring}

        counters = {"attempted": 0, "ok": 0}

        def _serve_at(target, key) -> bool:
            counters["attempted"] += 1
            try:
                if target is None:
                    raise RuntimeError("no serving node for request")
                target.insert(key, np.arange(len(key), dtype=np.int32))
                for _ in range(hits_per_request):
                    target.match_prefix(key)
                counters["ok"] += 1
                return True
            except Exception:  # noqa: BLE001 — failures are the measurement
                return False

        def _serve_routed(key) -> bool:
            try:
                res = cr.cache_aware_route(key)
                target = by_addr.get(res.prefill_addr)
            except Exception:  # noqa: BLE001
                target = None
            return _serve_at(target, key)

        def _serve_primary(key) -> bool:
            # The storm's concentration leg: traffic lands where a
            # summary-warm router would send it — the shard's primary
            # owner (deepest advertiser once warm).
            sid = shard_of_tokens(key[:page])
            primary = decider_mesh.ownership.primary(sid)
            return _serve_at(by_rank.get(primary), key)

        def _wave(serve) -> None:
            pace = wave_s / max(1, len(reqs))
            t0 = _time.monotonic()
            for i, key in enumerate(reqs):
                serve(key)
                left = t0 + (i + 1) * pace - _time.monotonic()
                if left > 0:
                    _time.sleep(left)

        def _skew_at(mesh) -> dict:
            # Only ranks with nonzero OWNED-shard load ride the heat
            # trailer (cold reporters clear themselves) — wait for
            # exactly the set that just published something.
            expected = set()
            for n in ring:
                if n.ownership is not None:
                    owned = set(n.ownership.owned_shards(n.rank))
                    # heat_loads() snapshots under the mesh lock — the
                    # transport reader threads are still applying storm
                    # oplogs and mutating the heat cells.
                    if set(n.heat_loads()) & owned:
                        expected.add(n.rank)
                n.broadcast_shard_summary()
            wait_for(
                lambda m=mesh, e=expected: e
                <= {int(r) for r in m.fleet.shard_heat()["by_rank"]},
                timeout=timeout_s,
            )
            return mesh.fleet.shard_heat()

        decider = next((p for p in planes if p.is_decider()), None)
        if decider is None:
            return {"performed": False, "reason": "no decider in ring"}
        decider_mesh = decider.mesh

        # -- wave 1: concentrate ---------------------------------------
        _wave(_serve_primary)
        # Both the observer router AND the decider need the heat folds.
        heat_before = _skew_at(router_mesh)
        _skew_at(decider_mesh)
        skew_before = float(heat_before["skew_score"])
        attempted_wave1 = counters["attempted"]

        old_owners = {
            sid: decider.mesh.ownership.owners_of(sid)
            for sid in range(NUM_SHARDS)
        }

        # -- the move (traffic keeps flowing) --------------------------
        # The mid-move trickle is PACED across a settle window that
        # doubles as the wave-1 heat-decay gap: skew_after must measure
        # wave 2's fanned-out traffic, not wave 1's residue.
        mid0_attempted, mid0_ok = counters["attempted"], counters["ok"]
        tick = decider.tick()
        t_mid = _time.monotonic()
        for i, key in enumerate(reqs[:mid_requests]):
            _serve_routed(key)
            left = (
                t_mid + (i + 1) * settle_s / max(1, mid_requests)
            ) - _time.monotonic()
            if left > 0:
                _time.sleep(left)
        want = (decider_mesh.overrides.epoch, decider_mesh.overrides.version)
        every = list(ring) + [router_mesh]
        converged = wait_for(
            lambda: all(
                (n.overrides.epoch, n.overrides.version) == want
                for n in every
            ),
            timeout=timeout_s,
        )
        # Zero-loss handoff audit: each rank that GAINED ownership of a
        # boosted shard must hold that shard's hottest key (pushed
        # point-to-point by the old primary, not waiting out repair).
        sid_hot_key = {}
        for k, key in enumerate(keys):
            sid = shard_of_tokens(key[:page])
            if sid not in sid_hot_key:
                sid_hot_key[sid] = key
        handoff_entries = 0
        for sid in tick.get("boosted", []):
            key = sid_hot_key.get(sid)
            if key is None:
                continue
            gained = [
                r for r in decider_mesh.ownership.owners_of(sid)
                if r not in old_owners.get(sid, ()) and r in by_rank
            ]
            for r in gained:
                if wait_for(
                    lambda n=by_rank[r], k=key: n.tree.match_prefix(
                        k, split_partial=False
                    ).length
                    > 0,
                    timeout=timeout_s,
                ):
                    handoff_entries += 1

        # -- wave 2: fan out under the adopted overrides ---------------
        _wave(_serve_routed)
        heat_after = _skew_at(router_mesh)
        skew_after = float(heat_after["skew_score"])
        mid_attempted = counters["attempted"] - mid0_attempted
        mid_ok = counters["ok"] - mid0_ok
        moves = len(tick.get("boosted", [])) + len(tick.get("shrunk", []))
        return {
            "performed": True,
            "skew_before": round(skew_before, 4),
            "skew_after": round(skew_after, 4),
            "skew_dropped": bool(skew_after < skew_before),
            "moves": int(moves),
            "max_moves_per_round": int(max_moves_per_round),
            "moves_bounded": bool(moves <= max_moves_per_round),
            "boosted_shards": [int(s) for s in tick.get("boosted", [])],
            "hot_shard": heat_before.get("hot_shard"),
            "attempted_mid_move": int(mid_attempted),
            "ok_mid_move": int(mid_ok),
            "failed_mid_move": int(mid_attempted - mid_ok),
            "overrides_version": int(want[1]),
            "overrides_converged": bool(converged),
            "handoff_entries": int(handoff_entries),
            "requests_wave1": int(attempted_wave1),
            "rebalance_s": round(_time.monotonic() - t_phase, 3),
        }
    finally:
        for p in planes:
            p.close()


def _chaos_router_kill_phase(
    *,
    routers,
    by_addr,
    plan,
    kill_router,
    rng,
    seed: int,
    streams: int = 10,
    tokens_per_stream: int = 16,
    deadline_s: float = 30.0,
) -> dict:
    """Router-kill at the multi-router front door: live streams route
    EVERY token through a :class:`RouterFrontDoor` over N >= 2 router
    edges (each an independent RecoveryCoordinator edge); one router is
    process-killed mid-traffic (stops serving AND acking, like a
    blackholed peer); the front door's hop timeout detects it, hedges
    to the survivor, and every in-flight request completes through the
    surviving router's edge — zero lost requests. ``routers`` is an
    ordered list of (addr, CacheAwareRouter)."""
    import time as _time

    from radixmesh_tpu.policy.retry import RetryPolicy
    from radixmesh_tpu.router.front_door import RouterFrontDoor
    from radixmesh_tpu.server.recovery import RecoveryCoordinator

    t_phase = _time.monotonic()
    policy = RetryPolicy(
        hop_timeout_s=0.5, max_retries=4, backoff_base_s=0.05,
        backoff_max_s=0.3, jitter_frac=0.25,
    )
    coords = {
        addr: RecoveryCoordinator(policy, name=f"edge-{addr}", seed=seed)
        for addr, _ in routers
    }
    served_by: dict[str, int] = {addr: 0 for addr, _ in routers}

    def make_route_fn(addr, router):
        def fn(key):
            if plan.is_killed(addr):
                # A killed process stops acking: from the client this
                # is a hop that never answers, so the front door's
                # timeout — not a clean error — must detect it.
                _time.sleep(0.6)
                raise RuntimeError(f"router {addr} gave no answer")
            res = router.cache_aware_route(key)
            served_by[addr] += 1
            return res

        return fn

    fd = RouterFrontDoor(
        [(addr, make_route_fn(addr, r)) for addr, r in routers],
        hop_timeout_s=0.25,
        name="chaos-frontdoor",
    )
    victim = routers[0][0]
    survivor = routers[1][0] if len(routers) > 1 else None

    recs = []
    for s in range(streams):
        prompt = rng.integers(0, 600, size=9).astype(np.int32)
        rec = coords[victim].admit(
            prompt, deadline_s=deadline_s, seed=seed * 1361 + s
        )
        recs.append(rec)

    def token_of(stream_seed: int, i: int) -> int:
        return int((stream_seed * 6151 + i * 104729 + 29) % 600)

    failed = 0

    def emit_one(rec) -> None:
        key = rec.resume_key()
        res = fd.route(key)
        target = by_addr.get(res.prefill_addr)
        if target is None:
            raise RuntimeError("front door returned no prefill node")
        tok = token_of(rec.seed, len(rec.delivered))
        grown = np.concatenate([key, np.asarray([tok], dtype=np.int32)])
        target.insert(grown, np.arange(len(grown), dtype=np.int32))
        rec.deliver(tok)

    half = tokens_per_stream // 2
    for _ in range(half):
        for rec in recs:
            emit_one(rec)

    # -- the kill: one of N routers dies mid-traffic -------------------
    inflight_at_kill = sum(
        1 for r in recs if len(r.delivered) < tokens_per_stream
    )
    served_at_kill = dict(served_by)
    plan.kill(victim)
    kill_router(victim)

    # The victim's edge process died whole — its recovery records
    # resurrect on the SURVIVING router's edge: re-admit each in-flight
    # stream there (prompt + delivered replay) and finish through the
    # front door, which fails over on the first unanswered hop.
    migrated = []
    for rec in recs:
        if survivor is None:
            break
        nrec = coords[survivor].admit(
            rec.prompt,
            deadline_s=max(0.5, rec.budget.remaining()),
            seed=rec.seed,
            trace_id=rec.trace_id or None,
        )
        for tok in rec.delivered:
            nrec.deliver(tok)
        migrated.append(nrec)
    for _ in range(tokens_per_stream - half):
        for rec in migrated:
            try:
                if len(rec.delivered) < tokens_per_stream:
                    emit_one(rec)
            except Exception:  # noqa: BLE001 — failures are the measurement
                failed += 1
    completed = sum(
        1 for r in migrated if len(r.delivered) >= tokens_per_stream
    )
    survivor_served = bool(
        survivor is not None
        and served_by.get(survivor, 0) > served_at_kill.get(survivor, 0)
    )
    return {
        "performed": True,
        "routers": len(routers),
        "killed": victim,
        "survivor": survivor,
        "streams": streams,
        "inflight_at_kill": int(inflight_at_kill),
        "completed": int(completed),
        "failed": int(failed),
        "failovers": int(fd.failovers),
        "hedges": int(fd.hedges),
        "survivor_served": survivor_served,
        "router_kill_s": round(_time.monotonic() - t_phase, 3),
    }


def run_chaos_workload(
    drop_p: float = 0.2,
    partition_s: float = 10.0,
    partition_delay_s: float = 1.0,
    digest_interval_s: float = 0.25,
    repair_interval_s: float = 0.2,
    age_threshold_s: float = 0.5,
    n_requests: int = 150,
    key_len: int = 16,
    seed: int = 0,
    round_budget: int = 8,
    quiesce_window_s: float = 2.0,
    timeout_s: float = 90.0,
    join_drain: bool = True,
    drain_requests: int = 40,
    drain_inflight: int = 6,
    join_partition_s: float = 1.5,
    bootstrap_probe_interval_s: float = 0.25,
    bootstrap_round_budget: int = 16,
    crash: bool = True,
    crash_streams: int = 12,
    crash_tokens: int = 24,
    crash_deadline_s: float = 20.0,
    replication_factor: int = 0,
    rebalance: bool = True,
    rebalance_wave_s: float = 2.0,
    rebalance_keys: int = 24,
    rebalance_inserts: int = 160,
    router_kill: bool = True,
    router_kill_streams: int = 10,
    router_kill_tokens: int = 16,
) -> dict:
    """The chaos acceptance scenario (``validate_chaos`` below pins its
    report): a seeded FaultPlan injects ``drop_p`` frame loss across
    the whole fault window plus a symmetric ``partition_s`` partition of
    one prefill node, while routed requests keep flowing —

    1. **Serve through the fault.** Each simulated request routes at the
       cache-aware router and inserts+matches at the routed node; the
       success rate during the fault window is recorded (the partition
       impairs *replication*, never local serving).
    2. **Diverge.** Dropped INSERT frames permanently diverge replicas;
       the gossiped fingerprints detect it (peak diverged pairs + max
       convergence age recorded).
    3. **Repair.** After the partition heals, the anti-entropy repair
       plane (``cache/repair_plane.py``) must converge ALL replicas —
       the prefills, the decode node, and the router — to pairwise
       equal fingerprints within ``round_budget`` repair rounds.
    4. **Quiesce.** Once converged, a ``quiesce_window_s`` observation
       window must record ZERO further repair traffic (probes and
       summaries frozen) — repair can never storm a healthy ring.

    With ``join_drain`` (the PR 6 membership-lifecycle gates,
    ``policy/lifecycle.py``) two scale-in/scale-out phases follow:

    5. **Drain under loss.** The seeded ``drop_p`` loss window re-opens
       and one prefill node drains gracefully: the router refuses it
       new work once DRAINING gossips, its simulated in-flight requests
       are requeued-and-served elsewhere, hot tokens are written back,
       and a LEAVE drops it from every view with ZERO failed requests
       and ZERO failure-detection ("dead") successor transitions.
    6. **Join during a partition.** The drained node rejoins COLD while
       a partition isolates a different prefill. It enters
       BOOTSTRAPPING, picks a healthy donor from the fleet view, pulls
       a bulk repair session, and the router withholds cache hits from
       it (hash-ring fallback only) until its fingerprint converges
       with the donor — within the bootstrap round budget.

    With ``crash`` (the request-recovery gates, ``server/recovery.py``)
    a final unclean-death phase follows:

    7. **Crash mid-decode.** Live streams decode on both decode nodes
       under re-opened 20% loss; one decode node is process-KILLED
       (stops serving AND acking — ``FaultPlan.kill``). The edge's
       per-hop timeout detects it, every interrupted stream resurrects
       on the surviving node via the router's failover path (longest
       cached prefix over prompt+delivered), resumes byte-identically
       with ≥ 0.8 of replayed tokens served from cache, zero failures,
       and every recovery hop bounded by the admission deadline budget;
       a hedged-prefill drill (first-writer-wins, loser cancelled) runs
       in the same window.

    With ``rebalance`` (sharded runs only) a rebalance-under-storm
    phase runs after quiescence (``_chaos_rebalance_phase``): a zipf
    storm's skew score must STRICTLY drop once the view master's
    RebalancePlane boosts the hot shards' owner sets, with zero failed
    requests mid-move and the override version converged fleet-wide.
    With ``router_kill`` a final front-door phase
    (``_chaos_router_kill_phase``) process-kills one of the two
    routers mid-traffic: the client-side RouterFrontDoor must detect
    it by hop timeout, hedge to the survivor, and complete every
    in-flight request — zero lost.

    Deterministic by seeding: the FaultPlan's per-edge RNGs and the
    request stream derive from ``seed``; waits are deadline-bounded
    polls, never bare sleeps asserting timing."""
    import time as _time

    from radixmesh_tpu.cache.mesh_cache import MeshCache
    from radixmesh_tpu.cache.repair_plane import RepairConfig, RepairPlane
    from radixmesh_tpu.comm import faults
    from radixmesh_tpu.comm.inproc import InprocHub
    from radixmesh_tpu.config import MeshConfig, NodeRole
    from radixmesh_tpu.obs.fleet_plane import FleetPlane
    from radixmesh_tpu.router.cache_aware_router import CacheAwareRouter

    def wait_for(pred, timeout=timeout_s, interval=0.02):
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            if pred():
                return True
            _time.sleep(interval)
        return pred()

    rng = np.random.default_rng(seed)
    t_start = _time.monotonic()
    InprocHub.reset_default()
    # FOUR prefills: cp1 takes the phase-1 (and phase-6) partition;
    # cp2 is the drain/rejoin subject — its ring paths to the master
    # and its donor avoid cp1, so a join can START under the partition;
    # the fourth keeps sharded runs (rf <= 2) below the N <= RF
    # degeneracy so the rebalance phase has non-owners to boost ONTO.
    # TWO decodes: cd1 (or whichever serves more live streams) is the
    # phase-7 unclean-kill victim, and its sibling is the survivor the
    # recovery plane resurrects interrupted streams onto. TWO routers:
    # the multi-router front door — cr0 is the final-phase kill victim,
    # cr1 the surviving edge every in-flight request completes through.
    prefill, decode, router_addrs = (
        ["cp0", "cp1", "cp2", "cp3"], ["cd0", "cd1"], ["cr0", "cr1"],
    )
    partitioned = prefill[1]
    fault_end_s = partition_delay_s + partition_s
    plan = faults.FaultPlan(
        seed=seed,
        drop_p=drop_p,
        drop_end_s=fault_end_s,
        partitions=(
            faults.PartitionSpec(
                start_s=partition_delay_s,
                end_s=fault_end_s,
                addrs=(partitioned,),
            ),
        ),
    )
    nodes: list = []
    fleet_planes: list = []
    repair_planes: list = []
    lifecycle_planes: list = []
    try:
        with faults.injected(plan):
            for addr in prefill + decode + router_addrs:
                cfg = MeshConfig(
                    prefill_nodes=prefill,
                    decode_nodes=decode,
                    router_nodes=router_addrs,
                    local_addr=addr,
                    protocol="inproc",
                    tick_interval_s=0.1,
                    gc_interval_s=60.0,
                    # The partition must read as replication loss, not
                    # membership churn: keep failure detection out of
                    # the fault window.
                    failure_timeout_s=max(60.0, 4.0 * fault_end_s),
                    # Sharded rerun (cache/sharding.py): inserts deliver
                    # to owner sets; convergence gates become per-shard.
                    replication_factor=replication_factor,
                    shard_summary_interval_s=min(
                        digest_interval_s, repair_interval_s
                    ),
                    # Fast heat decay so the rebalance phase's second
                    # wave measures ITS traffic, not the first wave's
                    # residue (production keeps the 30 s default).
                    heat_half_life_s=1.0,
                )
                nodes.append(MeshCache(cfg, pool=None).start())
            for n in nodes:
                if not n.wait_ready(timeout=timeout_s):
                    raise RuntimeError(f"node {n.rank} never passed the barrier")
            ring = [n for n in nodes if n.role is not NodeRole.ROUTER]
            router_meshes = [n for n in nodes if n.role is NodeRole.ROUTER]
            router_mesh = router_meshes[0]
            by_addr = {n.cfg.local_addr: n for n in ring}
            fleet_planes = [
                FleetPlane(n, interval_s=digest_interval_s).start()
                for n in ring
            ]
            repair_planes = [
                RepairPlane(
                    n,
                    RepairConfig(
                        interval_s=repair_interval_s,
                        age_threshold_s=age_threshold_s,
                        backoff_base_s=max(0.25, repair_interval_s),
                        backoff_max_s=5.0,
                        round_budget=round_budget,
                    ),
                    seed=seed,
                ).start()
                for n in nodes
            ]
            cr = CacheAwareRouter(router_mesh, router_mesh.cfg)
            cr.watch_topology()
            cr.finish_warm_up()

            # -- 1+2: serve routed requests THROUGH the fault window ---
            # The plan's schedule restarts NOW: cluster startup (barrier
            # ticks, channel dials) must not consume the fault window.
            faults.rebase()
            attempted = ok = 0
            peak_diverged = 0
            max_age = 0.0
            pace = fault_end_s / max(1, n_requests)
            window_t0 = _time.monotonic()
            for i in range(n_requests):
                key = rng.integers(0, 600, size=key_len).astype(np.int32)
                attempted += 1
                try:
                    res = cr.cache_aware_route(key)
                    target = by_addr.get(res.prefill_addr)
                    if target is None:
                        raise RuntimeError("router returned no prefill node")
                    target.insert(key, np.arange(key_len, dtype=np.int32))
                    if target.match_prefix(key).length != key_len:
                        raise RuntimeError("local match missed a local insert")
                    ok += 1
                except Exception:  # noqa: BLE001 — failures are the measurement
                    pass
                if replication_factor > 0:
                    conv = router_mesh.fleet.shard_convergence()
                    peak_diverged = max(peak_diverged, len(conv["diverged"]))
                else:
                    conv = router_mesh.fleet.convergence()
                    peak_diverged = max(
                        peak_diverged,
                        sum(1 for v in conv["pairs"].values() if v > 0.0),
                    )
                max_age = max(max_age, conv["max_convergence_age_s"])
                sleep_left = window_t0 + (i + 1) * pace - _time.monotonic()
                if sleep_left > 0:
                    _time.sleep(sleep_left)
            # Let the fault window fully close (drops + partition off).
            tail = window_t0 + fault_end_s + 0.1 - _time.monotonic()
            if tail > 0:
                _time.sleep(tail)
            diverged_detected = (
                peak_diverged > 0
                or not _trees_converged(nodes)
            )

            # -- 3: repair converges every replica ---------------------
            heal_t0 = _time.monotonic()

            converged = wait_for(
                lambda: _trees_converged(nodes, router_mesh)
            )
            converge_s = _time.monotonic() - heal_t0
            # max_inflight_rounds covers peers still marked diverged
            # (episodes that never completed), so a non-heal can't
            # under-report its round spend.
            max_rounds = max(
                (
                    max(s["max_episode_rounds"], s["max_inflight_rounds"])
                    for s in (r.stats() for r in repair_planes)
                ),
                default=0,
            )

            # -- 4: quiescence -----------------------------------------
            def _repair_traffic() -> int:
                return sum(
                    s["probes_sent"] + s["summaries_sent"]
                    for s in (r.stats() for r in repair_planes)
                )

            # Settle: let every node's fleet view fold the CONVERGED
            # digests (a peer reading a stale pre-heal fingerprint would
            # legitimately probe once more) before opening the
            # zero-traffic observation window.
            _time.sleep(3.0 * digest_interval_s + repair_interval_s)
            traffic_before = _repair_traffic()
            q_deadline = _time.monotonic() + quiesce_window_s
            while _time.monotonic() < q_deadline:
                _time.sleep(repair_interval_s)
            traffic_after = _repair_traffic()

            # -- 4b: heat-driven rebalancing under a zipf storm --------
            # (sharded runs only: a full replica has no ownership to
            # move). Runs on the healed fleet, before membership churn.
            rebalance_report: dict = {"performed": False}
            if rebalance and replication_factor > 0:
                rebalance_report = _chaos_rebalance_phase(
                    ring=ring,
                    router_mesh=router_mesh,
                    by_addr=by_addr,
                    rng=rng,
                    wait_for=wait_for,
                    key_len=key_len,
                    zipf_keys=rebalance_keys,
                    zipf_inserts=rebalance_inserts,
                    wave_s=rebalance_wave_s,
                    timeout_s=timeout_s,
                )

            # -- 5: graceful drain of cp2 under re-opened seeded loss --
            join_report: dict = {"performed": False}
            drain_report: dict = {"performed": False}
            if join_drain:
                drain_report, join_report = _chaos_join_drain_phases(
                    nodes=nodes,
                    ring=ring,
                    router_mesh=router_mesh,
                    by_addr=by_addr,
                    cr=cr,
                    fleet_planes=fleet_planes,
                    repair_planes=repair_planes,
                    lifecycle_planes=lifecycle_planes,
                    plan=plan,
                    faults=faults,
                    prefill=prefill,
                    partitioned=partitioned,
                    rng=rng,
                    wait_for=wait_for,
                    key_len=key_len,
                    seed=seed,
                    drop_p=drop_p,
                    drain_requests=drain_requests,
                    drain_inflight=drain_inflight,
                    join_partition_s=join_partition_s,
                    digest_interval_s=digest_interval_s,
                    repair_interval_s=repair_interval_s,
                    age_threshold_s=age_threshold_s,
                    bootstrap_probe_interval_s=bootstrap_probe_interval_s,
                    bootstrap_round_budget=bootstrap_round_budget,
                    timeout_s=timeout_s,
                )

            # -- 7: unclean decode-node kill mid-stream ----------------
            crash_report: dict = {"performed": False}
            if crash:

                def _kill_planes(node):
                    planes = []
                    if node in nodes:
                        planes.append(repair_planes[nodes.index(node)])
                    if node in ring:
                        planes.append(fleet_planes[ring.index(node)])
                    return planes

                crash_report = _chaos_crash_phase(
                    by_addr=by_addr,
                    cr=cr,
                    plan=plan,
                    faults=faults,
                    prefill=prefill,
                    decode=decode,
                    rng=rng,
                    seed=seed,
                    drop_p=drop_p,
                    crash_streams=crash_streams,
                    crash_tokens=crash_tokens,
                    crash_deadline_s=crash_deadline_s,
                    kill_planes=_kill_planes,
                )

            # -- 8: router kill at the multi-router front door ---------
            # LAST: it takes a router down for good.
            router_kill_report: dict = {"performed": False}
            if router_kill and len(router_meshes) >= 2:
                routers_rk = []
                for rm in router_meshes:
                    r = CacheAwareRouter(rm, rm.cfg)
                    r.watch_topology()
                    r.finish_warm_up()
                    routers_rk.append((rm.cfg.local_addr, r))

                def _kill_router(addr):
                    rm = next(
                        n for n in router_meshes
                        if n.cfg.local_addr == addr
                    )
                    if rm in nodes:
                        repair_planes[nodes.index(rm)].close()
                    rm.close()

                router_kill_report = _chaos_router_kill_phase(
                    routers=routers_rk,
                    by_addr=by_addr,
                    plan=plan,
                    kill_router=_kill_router,
                    rng=rng,
                    seed=seed,
                    streams=router_kill_streams,
                    tokens_per_stream=router_kill_tokens,
                )

            repair_totals = {
                k: sum(r.stats()[k] for r in repair_planes)
                for k in (
                    "probes_sent", "summaries_sent", "keys_pushed",
                    "oplogs_reemitted", "heals",
                )
            }
            return {
                "nodes": len({n.cfg.local_addr for n in nodes}),
                "topology": "4 prefill + 2 decode + 2 routers (inproc)",
                "replication_factor": replication_factor,
                "round_budget": round_budget,
                "fault_plan": {
                    "seed": seed,
                    "drop_p": drop_p,
                    "drop_window_s": fault_end_s,
                    "partition_s": partition_s,
                    "partitioned_node": partitioned,
                    "frames_dropped": int(plan.counters.get("dropped", 0)),
                    "frames_delivered": int(plan.counters.get("delivered", 0)),
                },
                "served": {
                    "attempted": attempted,
                    "ok": ok,
                    "ok_rate_during_fault": round(ok / max(1, attempted), 4),
                },
                "divergence": {
                    "detected": bool(diverged_detected),
                    "peak_diverged_pairs": peak_diverged,
                    "max_age_s": round(max_age, 3),
                },
                "repair": {
                    "converged": bool(converged),
                    "converge_s": round(converge_s, 3),
                    "max_episode_rounds": int(max_rounds),
                    "within_round_budget": bool(
                        converged and max_rounds <= round_budget
                    ),
                    **repair_totals,
                },
                "quiescence": {
                    "window_s": quiesce_window_s,
                    "traffic_before": traffic_before,
                    "traffic_after": traffic_after,
                    "quiet": traffic_after == traffic_before,
                },
                "drain": drain_report,
                "join": join_report,
                "crash": crash_report,
                "rebalance": rebalance_report,
                "router_kill": router_kill_report,
                "wall_s": round(_time.monotonic() - t_start, 3),
            }
    finally:
        for lc in lifecycle_planes:
            lc.close()
        for r in repair_planes:
            r.close()
        for p in fleet_planes:
            p.close()
        for n in nodes:
            n.close()
        InprocHub.reset_default()


# ----------------------------------------------------------------------
# CHAOS report (PR 5, self-healing mesh; PR 6, membership lifecycle;
# PR 7, request recovery; PR 14, rebalance and router kill): what
# ``run_chaos_workload`` returns, held to its gates — seeded frame loss + a
# scheduled partition (comm/faults.py) diverge replicas; the
# anti-entropy repair plane (cache/repair_plane.py) must converge every
# replica (router included) within a bounded number of repair rounds
# while requests keep being served, then go quiet.
# The elastic-membership phases (policy/lifecycle.py): a
# graceful drain under sustained loss (zero failed requests, in-flight
# requeued-and-served, hot tokens written back, departure via LEAVE —
# never failure detection) and a cold rejoin during an active partition
# (bulk-bootstrap from a donor within the round budget, router
# withholding cache hits until convergence).
# The crash phase (server/recovery.py): an UNCLEAN decode-node
# kill mid-stream under loss — zero failed requests, every interrupted
# stream resumed with a byte-identical delivered prefix, resurrection
# served ≥ 0.8 from the replicated cache, every recovery hop bounded by
# the admission deadline budget, hedged prefill first-writer-wins.
# Every section is required: no report of an older version exists.
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
    (quiescence)."""
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
    # Membership-lifecycle sections + gates.
    drain = report.get("drain")
    if not isinstance(drain, dict):
        problems.append("drain section missing")
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
    if not isinstance(join, dict):
        problems.append("join section missing")
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
    # Request-recovery section + gates.
    crash = report.get("crash")
    if not isinstance(crash, dict):
        problems.append("crash section missing")
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
    # Robustness-loop sections + gates.
    reb = report.get("rebalance")
    if not isinstance(reb, dict):
        problems.append("rebalance section missing")
    if isinstance(reb, dict) and reb.get("performed"):
        problems += _rebalance_section_problems(reb)
    rk = report.get("router_kill")
    if not isinstance(rk, dict):
        problems.append("router_kill section missing")
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


def _obs_zipf_heat_phase(
    *,
    ring,
    router_mesh,
    by_rank,
    rng,
    wait_for,
    zipf_keys: int,
    zipf_inserts: int,
    zipf_alpha: float,
    key_len: int,
) -> dict:
    """OBS leg (b): per-shard heat & skew under a zipf-keyed insert mix.

    ``zipf_keys`` distinct subtree roots receive deterministic insert
    counts ∝ rank^-alpha (counts, not samples — the ground-truth shard
    load is then computable exactly). Each key's traffic lands at its
    shard's PRIMARY owner (what the router would do) and replicates to
    the co-owners; every node then publishes one SHARD_SUMMARY whose
    heat trailer gossips the decayed loads, and the ROUTER — which holds
    no tree and saw none of the inserts — must detect the hot shard,
    score the skew, and name the hot shard's owner set correctly."""
    import time as _time

    from radixmesh_tpu.cache.sharding import shard_of_tokens

    # Deterministic zipf counts per key (rank-frequency, heaviest first).
    weights = np.arange(1, zipf_keys + 1, dtype=np.float64) ** (-zipf_alpha)
    counts = np.maximum(
        1, np.floor(zipf_inserts * weights / weights.sum()).astype(int)
    )
    keys = [
        np.concatenate(
            [
                np.asarray([7001 + k], dtype=np.int32),
                rng.integers(1, 600, size=key_len - 1).astype(np.int32),
            ]
        )
        for k in range(zipf_keys)
    ]
    any_node = ring[0]
    page = max(1, any_node.page)
    ownership = any_node.ownership
    # Ground truth: tokens generated per shard (insert tokens + the hit
    # walks below) — the shard the workload actually made hottest, which
    # is the hot KEY's shard unless blake2b collided several mid-weight
    # keys into one (the truth is then that shard; the detector must
    # find IT, not our guess).
    truth: dict[int, int] = {}
    t0 = _time.monotonic()
    total = 0
    for k, key in enumerate(keys):
        sid = shard_of_tokens(key[:page])
        primary = ownership.primary(sid)
        node = by_rank[primary]
        slots = np.arange(len(key), dtype=np.int32)
        n = int(counts[k])
        for _ in range(n):
            node.insert(key, slots)
            # Every other insert also exercises the hit-heat path (a
            # served prefix is load too — a read-hot shard must read hot).
            node.match_prefix(key)
        truth[sid] = truth.get(sid, 0) + n * len(key) * 2
        total += n
    expected_sid = max(truth, key=truth.get)
    expected_owners = sorted(ownership.owners_of(expected_sid))
    for n in ring:
        n.broadcast_shard_summary()
    # The router folds heat from the gossiped summaries (master fan-out).
    wait_for(
        lambda: router_mesh.fleet.shard_heat()["reporters"] >= len(ring) - 1
    )
    report = router_mesh.shard_heat_report()
    detected = report.get("hot_shard")
    return {
        "performed": True,
        "inserts": int(total),
        "distinct_keys": int(zipf_keys),
        "zipf_alpha": float(zipf_alpha),
        "skew_score": report["skew_score"],
        "hot_shard": detected,
        "expected_hot_shard": int(expected_sid),
        "hot_owners": sorted(report.get("hot_owners", [])),
        "expected_hot_owners": expected_owners,
        "owner_set_correct": bool(
            detected == expected_sid
            and sorted(report.get("hot_owners", [])) == expected_owners
        ),
        "reporters": int(report["reporters"]),
        "reported_shards": len(report["shards"]),
        "heat_s": round(_time.monotonic() - t0, 3),
    }


def _obs_stitch_phase(
    *,
    by_addr,
    cr,
    plan,
    decode,
    rng,
    seed,
    streams: int,
    tokens_per_stream: int,
    deadline_s: float,
    on_kill=lambda addr: None,
) -> tuple[dict, list]:
    """OBS leg (a): crash + resurrection under full tracing — the
    chaos-style run whose spans must stitch into ONE multi-node
    timeline. Live streams decode with every emitted token published to
    the mesh UNDER THE STREAM'S TRACE ID (the oplog trace trailer); the
    busiest decode node is process-killed mid-stream; the recovery edge
    resurrects the interrupted streams on the survivor. Returns the
    phase report plus the interrupted records (the stitch audit reads
    their trace ids)."""
    import time as _time

    from radixmesh_tpu.policy.retry import RetryPolicy
    from radixmesh_tpu.server.recovery import HopTimeout, RecoveryCoordinator

    t_phase = _time.monotonic()
    policy = RetryPolicy(
        hop_timeout_s=0.3,
        max_retries=4,
        backoff_base_s=0.05,
        backoff_max_s=0.3,
        jitter_frac=0.25,
    )
    coord = RecoveryCoordinator(policy, name="obs-edge", seed=seed)

    def token_of(stream_seed: int, i: int) -> int:
        return int((stream_seed * 7919 + i * 104729 + 13) % 600)

    stream_recs = []
    for s in range(streams):
        prompt = rng.integers(0, 600, size=9).astype(np.int32)
        rec = coord.admit(prompt, deadline_s=deadline_s, seed=seed * 977 + s)
        res = cr.cache_aware_route(prompt)
        rec.addr = res.decode_addr
        stream_recs.append(rec)

    def emit_one(rec) -> None:
        node = by_addr[rec.addr]
        i = len(rec.delivered)
        tok = token_of(rec.seed, i)
        key = np.concatenate(
            [rec.resume_key(), np.asarray([tok], dtype=np.int32)]
        )
        # The mesh publish carries the stream's trace id: co-owner
        # replicas open replication_lag spans under it — the stitched
        # view's replication edges.
        node.insert(key, np.arange(len(key), dtype=np.int32),
                    trace_id=rec.trace_id)
        rec.deliver(tok)

    half = tokens_per_stream // 2
    for _ in range(half):
        for rec in stream_recs:
            emit_one(rec)

    per_addr: dict = {}
    for rec in stream_recs:
        per_addr[rec.addr] = per_addr.get(rec.addr, 0) + 1
    victim = max(decode, key=lambda a: per_addr.get(a, 0))
    interrupted = [r for r in stream_recs if r.addr == victim]
    plan.kill(victim)
    on_kill(victim)  # the process dies whole: its planes die with it
    by_addr[victim].close()

    def make_route_fn(rec):
        def route_fn(key, exclude):
            cur = rec.addr
            if cur is not None and cur not in exclude:
                return cur
            return cr.cache_aware_route(key, exclude=exclude).decode_addr

        return route_fn

    def serve_fn(addr, rec, hop_deadline_s):
        deadline = _time.monotonic() + hop_deadline_s
        while len(rec.delivered) < tokens_per_stream:
            if plan.is_killed(addr):
                wait = deadline - _time.monotonic()
                if wait > 0:
                    _time.sleep(wait)
                raise HopTimeout(f"no progress from {addr}")
            emit_one(rec)

    failed = 0
    for rec in stream_recs:
        try:
            coord.run_to_completion(rec, make_route_fn(rec), serve_fn)
        except Exception:  # noqa: BLE001 — failures are the measurement
            failed += 1
    resumed = sum(1 for r in interrupted if r.done and r.resurrections)
    report = {
        "performed": True,
        "node": victim,
        "streams": streams,
        "tokens_per_stream": tokens_per_stream,
        "interrupted": len(interrupted),
        "resumed": resumed,
        "failed": failed,
        "stitch_s": round(_time.monotonic() - t_phase, 3),
    }
    return report, interrupted


def run_obs_workload(
    seed: int = 0,
    replication_factor: int = 3,
    streams: int = 8,
    tokens_per_stream: int = 20,
    zipf_keys: int = 64,
    zipf_inserts: int = 400,
    zipf_alpha: float = 1.4,
    key_len: int = 8,
    summary_interval_s: float = 0.2,
    deadline_s: float = 20.0,
    timeout_s: float = 60.0,
    engine_steps: bool = True,
    stitched_trace_path: str | None = None,
) -> dict:
    """The mesh-wide observability acceptance scenario (PR 9;
    ``validate_obs`` below pins its report) — three legs over one
    sharded cluster (4 prefill + 2 decode + 1 router, rf defaults 3):

    a. **Cross-node trace stitching.** A chaos-style crash+resurrection
       run under full tracing: every emitted token's mesh publish
       carries the stream's 64-bit trace id (oplog trace trailer), the
       busiest decode node is killed mid-stream, interrupted streams
       resurrect on the survivor — and ONE stitched Perfetto export
       must show the interrupted request's spans on ≥ 3 node tracks
       under a single trace id, with publish/replication edges visible.
    b. **Per-shard heat & skew.** Zipf-keyed inserts provably drive the
       skew score: the router — no tree replica, fed only by SHARD_SUMMARY
       heat trailers — must name the hot shard, its owner set, and a
       skew score above the artifact's floor.
    c. **TPU step attribution.** A CPU-backed tiny engine with
       ``step_accounting=True`` serves a short burst and must report
       per-wave MFU + pad fraction for BOTH prefill and decode.

    Plus the **wire gate**: a traceless INSERT frame is bit-identical
    to the pre-PR-9 encoding (no flag, no trailer) and a traced frame
    differs by exactly the 8-byte trailer."""
    import time as _time

    from radixmesh_tpu.cache.mesh_cache import MeshCache
    from radixmesh_tpu.cache.oplog import Oplog, OplogType, serialize
    from radixmesh_tpu.comm import faults
    from radixmesh_tpu.comm.inproc import InprocHub
    from radixmesh_tpu.config import MeshConfig, NodeRole
    from radixmesh_tpu.obs.trace_plane import (
        FlightRecorder,
        get_recorder,
        set_recorder,
        stitch_traces,
    )

    def wait_for(pred, timeout=timeout_s, interval=0.02):
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            if pred():
                return True
            _time.sleep(interval)
        return pred()

    rng = np.random.default_rng(seed)
    t_start = _time.monotonic()
    InprocHub.reset_default()
    prev_recorder = get_recorder()
    set_recorder(
        FlightRecorder(capacity=1 << 16, sample=1.0, node="obs-edge")
    )
    # 4 prefills so rf=3 owner sets are PROPER subsets of the prefill
    # role (an all-nodes-own-everything fleet would make the hot-owner
    # gate vacuous); 2 decodes so the crash leaves a survivor that
    # co-owns every shard (decode owners = min(rf, 2)).
    prefill = ["op0", "op1", "op2", "op3"]
    decode = ["od0", "od1"]
    router_addrs = ["or0"]
    plan = faults.FaultPlan(seed=seed)
    nodes: list = []
    fleet_planes: list = []
    try:
        with faults.injected(plan):
            from radixmesh_tpu.obs.fleet_plane import FleetPlane

            for addr in prefill + decode + router_addrs:
                cfg = MeshConfig(
                    prefill_nodes=prefill,
                    decode_nodes=decode,
                    router_nodes=router_addrs,
                    local_addr=addr,
                    protocol="inproc",
                    tick_interval_s=0.1,
                    gc_interval_s=60.0,
                    failure_timeout_s=60.0,
                    replication_factor=replication_factor,
                    shard_summary_interval_s=summary_interval_s,
                )
                nodes.append(MeshCache(cfg, pool=None).start())
            for n in nodes:
                if not n.wait_ready(timeout=timeout_s):
                    raise RuntimeError(
                        f"node {n.rank} never passed the barrier"
                    )
            ring = [n for n in nodes if n.role is not NodeRole.ROUTER]
            router_mesh = nodes[-1]
            by_addr = {n.cfg.local_addr: n for n in ring}
            by_rank = {n.rank: n for n in ring}
            # Digest gossip feeds the stitcher's clock-offset estimates
            # (FleetView.clock_offsets) — the "correction from the
            # existing digest timestamps" leg of the stitch contract.
            fleet_planes = [
                FleetPlane(n, interval_s=0.2).start() for n in ring
            ]
            plane_of = dict(zip([n.cfg.local_addr for n in ring], fleet_planes))
            from radixmesh_tpu.router.cache_aware_router import (
                CacheAwareRouter,
            )

            cr = CacheAwareRouter(router_mesh, router_mesh.cfg)
            cr.watch_topology()
            cr.finish_warm_up()
            wait_for(lambda: len(router_mesh.fleet.clock_offsets()) >= 1)

            # -- leg (b) first: heat needs the full fleet alive --------
            heat_report = _obs_zipf_heat_phase(
                ring=ring,
                router_mesh=router_mesh,
                by_rank=by_rank,
                rng=rng,
                wait_for=wait_for,
                zipf_keys=zipf_keys,
                zipf_inserts=zipf_inserts,
                zipf_alpha=zipf_alpha,
                key_len=key_len,
            )

            # -- leg (a): crash + resurrection under full tracing ------
            stitch_report, interrupted = _obs_stitch_phase(
                by_addr=by_addr,
                cr=cr,
                plan=plan,
                decode=decode,
                rng=rng,
                seed=seed,
                streams=streams,
                tokens_per_stream=tokens_per_stream,
                deadline_s=deadline_s,
                on_kill=lambda addr: plane_of[addr].close(),
            )

            # Stitch audit: ONE merged export; the interrupted request's
            # spans must land on >= 3 distinct node tracks under its
            # single trace id, with publish + replication edges visible.
            rec = get_recorder()

            def audit():
                spans = rec.snapshot()
                best = {"trace_id": 0, "nodes": set(), "lag": 0, "publish": 0}
                for irec in interrupted:
                    tid = irec.trace_id
                    node_set = {
                        s.node for s in spans if s.trace_id == tid and s.node
                    }
                    lag = sum(
                        1
                        for s in spans
                        if s.trace_id == tid and s.name == "replication_lag"
                    )
                    pub = sum(
                        1
                        for s in spans
                        if s.trace_id == tid and s.name == "mesh_publish"
                    )
                    if len(node_set) > len(best["nodes"]):
                        best = {
                            "trace_id": tid, "nodes": node_set,
                            "lag": lag, "publish": pub,
                        }
                return spans, best

            # A replica records its replication edge when it applies the
            # frame, on its own thread: the audit reads the recorder once
            # the edges are there (or the scenario's timeout has passed),
            # not at whatever instant the serving loop happened to end.
            def edges_recorded() -> bool:
                _, best = audit()
                return (
                    len(best["nodes"]) >= OBS_MIN_NODE_TRACKS
                    and best["lag"] > 0
                )

            wait_for(edges_recorded)
            spans, best = audit()
            # Clock-offset correction from the digest timestamps the
            # fleet already gossips (rank-keyed → node-label-keyed).
            offsets = {
                by_rank[r]._node_label: off
                for r, off in router_mesh.fleet.clock_offsets().items()
                if r in by_rank
            }
            stitched = stitch_traces([rec.export_spans()], offsets)
            stitch_report.update(
                {
                    "trace_id": f"{best['trace_id']:#018x}",
                    "node_tracks": len(best["nodes"]),
                    "nodes_on_track": sorted(best["nodes"]),
                    "replication_edges": int(best["lag"]),
                    "publish_edges": int(best["publish"]),
                    "span_count": len(spans),
                    "stitched_events": len(stitched["traceEvents"]),
                    "clock_offsets_applied": len(offsets),
                }
            )
            if stitched_trace_path:
                import json as _json

                with open(stitched_trace_path, "w") as fh:
                    _json.dump(stitched, fh)
                stitch_report["stitched_artifact"] = stitched_trace_path

            # -- wire gate: traceless frames are bit-for-bit pre-PR-9 --
            base = dict(
                op_type=OplogType.INSERT,
                origin_rank=0,
                logic_id=7,
                ttl=3,
                key=np.arange(1, 9, dtype=np.int32),
                value=np.arange(8, dtype=np.int32),
                value_rank=0,
            )
            import radixmesh_tpu.cache.oplog as oplog_mod

            plain = serialize(Oplog(**base))
            traced = serialize(Oplog(**base, trace_id=0xA5A5_5A5A_DEAD_BEEF))
            # Strip the trailer + clear the flag bit: the result must be
            # BYTE-IDENTICAL to the traceless frame — i.e. tracing-off
            # frames are exactly the pre-PR-9 wire, and tracing adds
            # exactly (flag bit, 8-byte trailer) and nothing else.
            stripped = bytearray(traced[:-8])
            stripped[oplog_mod._FLAGS_OFFSET] &= ~oplog_mod._FLAG_TRACE
            wire_report = {
                "rf0_traceless_unchanged": bool(
                    bytes(stripped) == plain
                    and oplog_mod.deserialize(plain).trace_id == 0
                ),
                "trace_trailer_roundtrip": bool(
                    oplog_mod.deserialize(traced).trace_id
                    == 0xA5A5_5A5A_DEAD_BEEF
                ),
                "trailer_bytes": len(traced) - len(plain),
            }
    finally:
        set_recorder(prev_recorder)
        for p in fleet_planes:
            p.close()
        for n in nodes:
            n.close()
        InprocHub.reset_default()

    # -- leg (c): step attribution on a CPU-backed tiny engine ---------
    steps_report: dict = {"performed": False}
    if engine_steps:
        import jax

        from radixmesh_tpu.engine.engine import Engine
        from radixmesh_tpu.models.llama import ModelConfig, init_params

        mcfg = ModelConfig.tiny()
        eng = Engine(
            mcfg,
            init_params(mcfg, jax.random.PRNGKey(seed)),
            num_slots=512,
            page_size=4,
            max_batch=2,
            name="obs-steps",
            step_accounting=True,
        )
        sampling = None
        prompts = [list(range(1, 14)), list(range(1, 18)), list(range(1, 14))]
        eng.generate(prompts, sampling)
        acct = eng.step_acct.report()
        steps_report = {
            "performed": True,
            "n_params": acct["n_params"],
            "peak_tflops": acct["peak_tflops"],
            "prefill": {
                k: acct["prefill"][k]
                for k in (
                    "waves", "real_tokens", "padded_tokens", "mfu",
                    "pad_fraction",
                )
            },
            "decode": {
                k: acct["decode"][k]
                for k in (
                    "waves", "real_tokens", "padded_tokens", "mfu",
                    "pad_fraction",
                )
            },
        }

    return {
        "nodes": len(prefill) + len(decode) + len(router_addrs),
        "topology": "4 prefill + 2 decode + 1 router (inproc)",
        "replication_factor": replication_factor,
        "stitch": stitch_report,
        "heat": heat_report,
        "steps": steps_report,
        "wire": wire_report,
        "wall_s": round(_time.monotonic() - t_start, 3),
    }


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
    but gate-exempt (the CHAOS v2/v3 convention)."""
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


def run_tier_workload(
    n_prefixes: int = 16,
    prefix_tokens: int = 384,
    host_slots: int = 512,
    n_streams: int = 5,
    stream_tail_tokens: int = 48,
    stream_max_new: int = 12,
    interrupt_after: int = 4,
    seed: int = 0,
    max_steps: int = 40_000,
) -> dict:
    """Drive the durable KV spill tier (``cache/kv_tier.py``) through
    the TIER report's three claims — the data source for
    ``validate_tier`` below.

    **Capacity** (phase A): a working set of ``n_prefixes`` distinct
    ``prefix_tokens``-token prefixes — sized >= 10x the host arena —
    served once, churned through eviction (device → host → disk via the
    write-behind destager), then RE-served. With the tier, pass 2 is a
    near-pure cache hit (restores from verified extents); the no-tier
    baseline's host arena can hold only a sliver of the set, so its
    pass-2 hit-rate collapses. The artifact's headline value is the
    hit-rate ratio.

    **Restore overlap** (phase B): every prefix demoted to DISK-only
    residency, then a burst of re-serves against a live background
    decode — requests park in ``RESTORING`` behind staged extent reads
    while decode keeps stepping (``decode_steps_during_restore > 0`` is
    KVFLOW's decode-never-blocks contract extended one tier down).

    **Cold-cell resurrection** (phase C): a fresh cell serves
    ``n_streams`` seeded streams sharing a long warm prefix (already
    spilled to extents), is KILLED HARD mid-decode (every volatile tier
    destroyed with it, no flush), one committed extent is bit-flipped
    and another truncated (the power-loss corruption model), and a new
    cell boots from the extent directory alone: corrupt extents must be
    detected and dropped (never served), every interrupted stream must
    resume byte-identical to its deterministic seeded expectation
    (PR 7's replay contract), and the resumed prefills must actually
    hit disk-restored KV.

    CPU-runnable by design: the phenomena are tier transitions and
    crash recovery, not FLOPs.
    """
    import os
    import shutil
    import tempfile
    import time as _time

    import jax

    from radixmesh_tpu.engine.engine import Engine
    from radixmesh_tpu.engine.request import RequestState, SamplingParams
    from radixmesh_tpu.models.llama import ModelConfig, init_params

    cfg = ModelConfig(
        vocab_size=256, hidden=64, n_layers=2, n_heads=2, n_kv_heads=2,
        head_dim=32, intermediate=128, max_seq_len=2048,
    )
    params = init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    page_size = 4
    t_start = _time.monotonic()
    root = tempfile.mkdtemp(prefix="tierwl-")
    gen = SamplingParams(temperature=0.0, max_new_tokens=2)

    prefixes = [
        rng.integers(1, cfg.vocab_size - 1, size=prefix_tokens).astype(
            np.int32
        )
        for _ in range(n_prefixes)
    ]
    working_set = n_prefixes * prefix_tokens

    def make_engine(tier_dir: str | None, tag: str) -> Engine:
        return Engine(
            cfg,
            params,
            num_slots=max(1024, 2 * prefix_tokens + 512),
            page_size=page_size,
            max_batch=n_streams + 1,
            host_cache_slots=host_slots,
            kv_tier_dir=tier_dir,
            kv_tier_watermark=0.0,  # destage eagerly: durability first
            kv_tier_destage_budget=64,
            kv_tier_destage_interval_s=0.0,  # deterministic per-pump spills
            # Fine-grained staging: each extent restores in several
            # chunks, so the parked window is wide enough to measure
            # decode overlap against.
            kv_transfer_chunk_tokens=64,
            kv_transfer_async=tier_dir is None,  # baseline gets a plane too
            name=tag,
        )

    def settle(eng: Engine, timeout: float = 20.0) -> None:
        """Run the engine's pump until every spill has committed (the
        write-behind destager needs engine pumps to install refs)."""
        plane = eng.kv_transfer
        if plane is None:
            return
        plane.wait_host_ready()
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            eng.step()  # no work -> pure pump/destage service
            if plane.spills_idle():
                return
            plane.wait_progress(0.01)

    def churn_pass(eng: Engine, reverse: bool = False) -> tuple[int, int]:
        """Serve every prefix once, evicting between requests (the
        pressure that drives device → host → disk). Returns the pass's
        (cached, prompt) token deltas. The measured pass runs in
        REVERSE order — most-recently-evicted first — which is the
        no-tier baseline's BEST case (its arena can only retain the
        tail of the set), so the comparison is biased against the
        claim."""
        c0, p0 = eng.stats.cached_tokens, eng.stats.prompt_tokens
        for p in (reversed(prefixes) if reverse else prefixes):
            eng.generate([list(p)], gen)
            eng.tree.evict(10 * prefix_tokens)
            settle(eng)
        return (
            eng.stats.cached_tokens - c0,
            eng.stats.prompt_tokens - p0,
        )

    # ---- phase A: hit-rate at >= 10x host capacity, tier vs no tier --
    tier_dir = os.path.join(root, "tier-a")
    eng_t = make_engine(tier_dir, "tier-a")
    churn_pass(eng_t)  # pass 1: populate + spill
    t_cached, t_prompt = churn_pass(eng_t, reverse=True)
    tier_hit = t_cached / max(1, t_prompt)

    eng_b = make_engine(None, "tier-base")
    churn_pass(eng_b)
    b_cached, b_prompt = churn_pass(eng_b, reverse=True)
    base_hit = b_cached / max(1, b_prompt)
    if eng_b.kv_transfer is not None:
        eng_b.kv_transfer.close()

    tier = eng_t._kv_tier
    moves = list(tier.recent_moves)
    spill_section = {
        "spilled_tokens": int(tier._m_spilled.value),
        "extents": int(tier.extents),
        "demotes": sum(1 for m in moves if m[2] == "demote"),
        "promotes": sum(1 for m in moves if m[2] == "promote"),
        "drops": sum(1 for m in moves if m[2] == "drop"),
        "resident_bytes": int(tier.resident_bytes),
    }

    # ---- phase B: decode never blocks on disk restores ---------------
    # Demote EVERYTHING to disk-only residency: device -> host (free for
    # disk-backed nodes), then shed every host copy.
    eng_t.tree.evict(10 * working_set)
    settle(eng_t)
    eng_t.tree._evict_host(10 * working_set)
    bg_prompt = rng.integers(1, cfg.vocab_size - 1, size=64).astype(np.int32)
    bg = eng_t.add_request(
        list(bg_prompt), SamplingParams(temperature=0.0, max_new_tokens=64)
    )
    eng_t.step()  # admit + first decode for the background row
    burst = [
        eng_t.add_request(list(p), gen) for p in prefixes[: 3]
    ]
    parked: set = set()
    decode_during_restore = 0
    last_t = _time.monotonic()
    max_gap = 0.0
    for _ in range(max_steps):
        before = eng_t.stats.decode_steps
        eng_t.step()
        now = _time.monotonic()
        for r in burst:
            if r.state is RequestState.RESTORING:
                parked.add(r.rid)
        stepped = eng_t.stats.decode_steps - before
        if stepped:
            max_gap = max(max_gap, now - last_t)
            last_t = now
        if getattr(eng_t, "_restoring", ()):
            decode_during_restore += stepped
        if all(r.state is RequestState.FINISHED for r in burst):
            break
    if bg.state is not RequestState.FINISHED:
        eng_t.cancel(bg.rid)
    restore_section = {
        "parked_requests": len(parked),
        "disk_restored_tokens": int(tier._m_restored.value),
        "decode_steps_during_restore": int(decode_during_restore),
        "max_decode_gap_s": round(max_gap, 6),
        "overlap_ok": bool(parked) and decode_during_restore > 0,
    }
    eng_t.kv_transfer.close()

    # ---- phase C: whole-cell kill -> corrupt -> resurrect -> resume --
    cold_dir = os.path.join(root, "tier-cold")
    shared = rng.integers(1, cfg.vocab_size - 1, size=prefix_tokens).astype(
        np.int32
    )
    tails = [
        rng.integers(1, cfg.vocab_size - 1, size=stream_tail_tokens).astype(
            np.int32
        )
        for _ in range(n_streams)
    ]
    stream_prompts = [list(shared) + list(t) for t in tails]
    stream_samps = [
        SamplingParams(
            temperature=0.9, top_p=0.95, seed=7000 + i,
            max_new_tokens=stream_max_new,
        )
        for i in range(n_streams)
    ]

    # Deterministic expectation (the PR 7 seeded-replay contract: same
    # seed => identical continuation on any engine/row/path): each
    # stream's FULL output, computed on a pristine reference engine.
    eng_ref = make_engine(None, "tier-ref")
    expected: list[list[int]] = []
    for pr, sp in zip(stream_prompts, stream_samps):
        req = eng_ref.add_request(pr, sp)
        while eng_ref.has_work():
            eng_ref.step()
        expected.append(list(req.generated))
    if eng_ref.kv_transfer is not None:
        eng_ref.kv_transfer.close()

    eng_c = make_engine(cold_dir, "tier-c0")
    # Warm + spill the streams' prompts (the shared prefix and each
    # tail become committed extents).
    for pr in stream_prompts:
        eng_c.generate([pr], gen)
        eng_c.tree.evict(10 * prefix_tokens)
        settle(eng_c)
    # Start every stream and interrupt them mid-decode.
    reqs = [
        eng_c.add_request(pr, sp)
        for pr, sp in zip(stream_prompts, stream_samps)
    ]
    for _ in range(max_steps):
        eng_c.step()
        if all(len(r.generated) >= interrupt_after for r in reqs):
            break
    delivered = [list(r.generated) for r in reqs]
    # KILL the whole cell: no drain, no flush — the plane dies with its
    # queues, HBM and the host arena die with the process. Only
    # committed extents survive.
    eng_c.kv_transfer.close()
    del eng_c

    # Power-loss corruption model: one committed extent bit-flipped,
    # one truncated (attack the two smallest — stream tails — so the
    # shared prefix still proves disk-served hits).
    import glob as _glob

    files = sorted(
        _glob.glob(os.path.join(cold_dir, "ext-*.kv")), key=os.path.getsize
    )
    attacked = 0
    if len(files) >= 2:
        with open(files[0], "r+b") as fh:
            fh.seek(os.path.getsize(files[0]) // 2)
            b = fh.read(1)
            fh.seek(-1, 1)
            fh.write(bytes([b[0] ^ 0xFF]))
        with open(files[1], "r+b") as fh:
            fh.truncate(max(8, os.path.getsize(files[1]) - 64))
        attacked = 2

    t_restart = _time.monotonic()
    eng_r = make_engine(cold_dir, "tier-c1")
    restart_s = _time.monotonic() - t_restart
    corrupt_detected = sum(
        int(m.value) for m in eng_r._kv_tier._m_corrupt_by.values()
    )
    c0 = eng_r.stats.cached_tokens
    failed = 0
    identical = 0
    for i, (pr, sp) in enumerate(zip(stream_prompts, stream_samps)):
        try:
            req = eng_r.add_request(pr, sp, resume_tokens=delivered[i])
            for _ in range(max_steps):
                eng_r.step()
                if req.state is RequestState.FINISHED:
                    break
            if req.state is not RequestState.FINISHED:
                failed += 1
                continue
            final = delivered[i] + list(req.generated)
            if final == expected[i]:
                identical += 1
            else:
                failed += 1
        except Exception:
            failed += 1
    disk_hit_tokens = int(eng_r.stats.cached_tokens - c0)
    resumed = identical
    byte_identical = identical == len(reqs) and failed == 0
    cold_section = {
        "performed": True,
        "interrupted": len(reqs),
        "resumed": resumed,
        "byte_identical": bool(byte_identical),
        "failed": int(failed),
        "disk_hit_tokens": disk_hit_tokens,
        "grafted_nodes": int(eng_r.resurrected["grafted_nodes"]),
        "orphaned": int(eng_r.resurrected["orphaned"]),
        "corrupt_detected": int(corrupt_detected),
        # Byte-identity of EVERY resumed stream is the direct evidence
        # no corrupt KV reached decode (the dropped extents degraded to
        # recomputes instead).
        "corrupt_served": 0 if byte_identical else int(failed),
        "restart_s": round(restart_s, 4),
    }
    eng_r.kv_transfer.close()
    shutil.rmtree(root, ignore_errors=True)

    return {
        "capacity": {
            "working_set_tokens": int(working_set),
            "host_slots": int(host_slots),
            "working_set_ratio": round(working_set / host_slots, 2),
            "tier_hit_rate": round(tier_hit, 4),
            "baseline_hit_rate": round(base_hit, 4),
            # Baseline floored at 1%: a fully-cold baseline would make
            # the ratio meaningless instead of impressive.
            "hit_rate_gain": round(tier_hit / max(0.01, base_hit), 4),
            "requests": 2 * n_prefixes,
            "distinct_prefixes": n_prefixes,
        },
        "spill": spill_section,
        "restore_overlap": restore_section,
        "cold_start": cold_section,
        "corruption": {
            "extents_attacked": attacked,
            "truncated": 1 if attacked else 0,
            "bitflipped": 1 if attacked else 0,
            "detected": int(min(corrupt_detected, attacked))
            if attacked
            else 0,
            "served_corrupt": cold_section["corrupt_served"],
        },
        "page_size": page_size,
        "wall_s": round(_time.monotonic() - t_start, 3),
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
    schema-valid but gate-exempt (the CHAOS convention)."""
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


def run_blackbox_workload(
    seed: int = 0,
    replication_factor: int = 3,
    history_interval_s: float = 0.25,
    history_capacity: int = 900,
    segment_every: int = 4,
    balanced_shards: int = 16,
    zipf_keys: int = 64,
    zipf_inserts: int = 400,
    zipf_alpha: float = 1.4,
    key_len: int = 8,
    digest_interval_s: float = 0.2,
    stale_after_s: float = 0.6,
    summary_interval_s: float = 0.2,
    timeout_s: float = 60.0,
    blackbox_dir: str | None = None,
) -> dict:
    """The black-box acceptance scenario (PR 13;
    ``validate_blackbox`` below pins its report): one rf=3 inproc mesh
    (4 prefill + 2 decode + 1 router, per-node fleet digesters) plus a
    step-accounted CPU engine, with TWO telemetry histories recording —
    the ROUTER's (the observer: fleet health, shard heat, skew) and the
    hot shard's PRIMARY OWNER's (the victim) — each wired to a
    :class:`~radixmesh_tpu.obs.blackbox.BlackBox` writing incremental
    segments. The run:

    0. **Healthy.** Balanced heat + a decode-dominant engine burst; the
       live history-backed doctor must report ZERO findings with every
       rule checked.
    a. **Zipf heat storm** (the OBS leg): deterministic rank^-alpha
       insert counts drive one shard provably hottest; the observer's
       rings record the skew peak.
    b. **Kill mid-storm.** The hot shard's primary owner dies HARD:
       its fleet digester, history sampler, and black box stop with NO
       final flush (the kill -9 simulation — only its committed
       segments survive), then its mesh closes. The observer's rings
       record the victim's health score collapsing.
    c. **Post-mortem from the dumps alone.** ``obs/doctor.py::
       postmortem_report`` over the OBSERVER's flushed dump must name
       the seeded hot shard AND a crash window containing the true
       kill time; over the VICTIM's segment-only dump it must flag the
       unclean death with the truncation point within one segment of
       the kill.

    The sampler's own cost is gated in units of its own work, each
    against a stated bound (``BLACKBOX_MAX_*`` below): neither history
    sweeps more often than its interval allows, sweeps more series or
    refuses one, and no black box commits a larger segment. The
    self-accounted sweep seconds are reported as a share of the run's
    wall clock, ungated (the run is a step-accounting run —
    the engine leg has it on)."""
    import os
    import shutil
    import tempfile
    import time as _time

    import jax

    from radixmesh_tpu.cache.mesh_cache import MeshCache
    from radixmesh_tpu.cache.sharding import shard_of_tokens
    from radixmesh_tpu.comm.inproc import InprocHub
    from radixmesh_tpu.config import MeshConfig, NodeRole
    from radixmesh_tpu.engine.engine import Engine
    from radixmesh_tpu.engine.request import SamplingParams
    from radixmesh_tpu.models.llama import ModelConfig, init_params
    from radixmesh_tpu.obs.aggregator import FleetAggregator, InprocPeer
    from radixmesh_tpu.obs.attribution import ensure_attributor
    from radixmesh_tpu.obs.blackbox import BlackBox, load_blackbox
    from radixmesh_tpu.obs.doctor import (
        DoctorConfig,
        MeshDoctor,
        postmortem_report,
    )
    from radixmesh_tpu.obs.fleet_plane import FleetPlane
    from radixmesh_tpu.obs.timeseries import TelemetryHistory
    from radixmesh_tpu.obs.trace_plane import (
        FlightRecorder,
        get_recorder,
        set_recorder,
    )
    from radixmesh_tpu.slo.control import OverloadController, SLOConfig

    def wait_for(pred, timeout=timeout_s, interval=0.02):
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            if pred():
                return True
            _time.sleep(interval)
        return pred()

    def finding_for(report: dict, rule: str, detector: str | None = None):
        for f in report["findings"]:
            if f["rule"] != rule:
                continue
            if detector and f["evidence"].get("detector") != detector:
                continue
            return f
        return None

    def hist_points(hist: TelemetryHistory, name: str) -> list:
        body = hist.query(family=name, since=-1, limit=1 << 62)
        return body["series"].get(name, {}).get("points", [])

    rng = np.random.default_rng(seed)
    t_start = _time.monotonic()
    InprocHub.reset_default()
    prev_recorder = get_recorder()
    own_tmp = blackbox_dir is None
    out_root = blackbox_dir or tempfile.mkdtemp(prefix="blackbox-wl-")
    obs_dir = os.path.join(out_root, "observer")
    victim_dir = os.path.join(out_root, "victim")
    prefill = ["bp0", "bp1", "bp2", "bp3"]
    decode = ["bd0", "bd1"]
    router_addrs = ["br0"]
    nodes: list = []
    fleet_planes: list = []
    histories: list = []
    boxes: list = []
    eng = None
    try:
        for addr in prefill + decode + router_addrs:
            cfg = MeshConfig(
                prefill_nodes=prefill,
                decode_nodes=decode,
                router_nodes=router_addrs,
                local_addr=addr,
                protocol="inproc",
                tick_interval_s=0.1,
                gc_interval_s=60.0,
                failure_timeout_s=60.0,
                replication_factor=replication_factor,
                shard_summary_interval_s=summary_interval_s,
            )
            nodes.append(MeshCache(cfg, pool=None).start())
        for n in nodes:
            if not n.wait_ready(timeout=timeout_s):
                raise RuntimeError(f"node {n.rank} never passed the barrier")
        ring = [n for n in nodes if n.role is not NodeRole.ROUTER]
        router_mesh = nodes[-1]
        by_rank = {n.rank: n for n in ring}
        any_node = ring[0]
        page = max(1, any_node.page)
        ownership = any_node.ownership
        # Fast staleness verdicts: the observer must see a dead node's
        # digest go stale within a second, not the 15 s default. (Ring
        # nodes' views adopt their FleetPlane's config; the router has
        # no plane, so its view is tuned directly.)
        router_mesh.fleet.cfg.stale_after_s = stale_after_s
        for n in ring:
            fleet_planes.append(
                FleetPlane(n, interval_s=digest_interval_s).start()
            )

        # -- engine (the step-accounting leg) --------------------------
        mcfg = ModelConfig(
            vocab_size=256, hidden=64, n_layers=2, n_heads=2, n_kv_heads=2,
            head_dim=32, intermediate=128, max_seq_len=1024,
        )
        eng = Engine(
            mcfg,
            init_params(mcfg, jax.random.PRNGKey(seed)),
            num_slots=2048,
            page_size=4,
            max_batch=8,
            name="bb-eng",
            step_accounting=True,
            # CPU-tier jit compiles take seconds; a serving-tuned 50ms
            # stall threshold would attribute compile time as decode
            # stalls and trip the healthy-phase zero-findings gate.
            token_stall_threshold_s=5.0,
        )
        sampling = SamplingParams(temperature=0.0, max_new_tokens=8)

        def prompts_of(n_tokens: int, count: int) -> list[list[int]]:
            return [
                list(rng.integers(1, mcfg.vocab_size - 1, size=n_tokens))
                for _ in range(count)
            ]

        # Warm-up untraced: jit compiles land before anything is timed.
        set_recorder(FlightRecorder(capacity=2048, sample=0.0, node="warm"))
        eng.generate(prompts_of(24, 3) + prompts_of(48, 3), sampling)
        rec = FlightRecorder(capacity=1 << 15, sample=1.0, node="bb-eng")
        set_recorder(rec)
        attr = ensure_attributor(rec)
        slo = OverloadController(SLOConfig())

        # -- histories + black boxes (observer = router; victim joins
        #    after the storm names the hot owner) ----------------------
        obs_hist = TelemetryHistory(
            interval_s=history_interval_s,
            capacity=history_capacity,
            mesh=router_mesh,
            engine=eng,
            slo=slo,
            node="observer-router",
        )
        histories.append(obs_hist)
        obs_bb = BlackBox(
            obs_dir,
            history=obs_hist,
            recorder=get_recorder,
            attributor_fn=ensure_attributor,
            node="observer-router",
            segment_every=segment_every,
        )
        boxes.append(obs_bb)
        # Fleet-aggregation seam (PR 17): the observer doubles as the
        # aggregation host, pulling its own ring — the gate requires
        # the fleet rules in the healthy rules_checked, and a healthy
        # fleet must keep them silent.
        agg = FleetAggregator(
            peers=[
                InprocPeer(
                    "observer-router", obs_hist, rank=router_mesh.rank
                )
            ],
            interval_s=history_interval_s,
        )
        doctor = MeshDoctor(
            mesh=router_mesh,
            engine=eng,
            slo=slo,
            attributor=ensure_attributor,
            history=obs_hist,
            aggregator=agg,
        )
        obs_bb.doctor = doctor
        obs_t0 = _time.monotonic()
        obs_hist.start()

        # -- phase 0: healthy ------------------------------------------
        seen_shards: set[int] = set()
        attempts = 0
        while len(seen_shards) < balanced_shards and attempts < 10_000:
            attempts += 1
            key = np.concatenate([
                np.asarray([23_000 + attempts], dtype=np.int32),
                rng.integers(1, 600, size=key_len - 1).astype(np.int32),
            ])
            sid = shard_of_tokens(key[:page])
            if sid in seen_shards:
                continue
            seen_shards.add(sid)
            node = by_rank[ownership.primary(sid)]
            node.insert(key, np.arange(len(key), dtype=np.int32))
            node.match_prefix(key)
        for n in ring:
            n.broadcast_shard_summary()
        wait_for(
            lambda: router_mesh.fleet.shard_heat()["reporters"]
            >= len(ring) - 1
        )
        eng.generate(prompts_of(24, 3) + prompts_of(48, 3), sampling)
        # Health series need at least one full digest round folded.
        wait_for(
            lambda: len(router_mesh.fleet.health()) >= len(ring)
        )
        wait_for(lambda: obs_hist.stats()["seq"] >= 2)
        agg.pull_once()
        healthy_report = doctor.diagnose()
        healthy = {
            "performed": True,
            "findings": healthy_report["findings"],
            "rules_checked": healthy_report["rules_checked"],
            "inputs": healthy_report["inputs"],
            "balanced_shards": len(seen_shards),
            "skew_score": router_mesh.shard_heat_report().get("skew_score"),
            "history_samples": obs_hist.stats()["seq"] + 1,
        }

        # -- phase a: zipf heat storm ----------------------------------
        heat = _obs_zipf_heat_phase(
            ring=ring,
            router_mesh=router_mesh,
            by_rank=by_rank,
            rng=rng,
            wait_for=wait_for,
            zipf_keys=zipf_keys,
            zipf_inserts=zipf_inserts,
            zipf_alpha=zipf_alpha,
            key_len=key_len,
        )
        expected_sid = heat["expected_hot_shard"]
        expected_owners = heat["expected_hot_owners"]
        victim_rank = ownership.primary(expected_sid)
        victim = by_rank[victim_rank]
        # The victim's OWN recorder: history + black box on the node
        # about to die — only its committed segments will survive.
        victim_hist = TelemetryHistory(
            interval_s=history_interval_s,
            capacity=history_capacity,
            mesh=victim,
            node=f"victim-rank{victim_rank}",
        )
        histories.append(victim_hist)
        victim_bb = BlackBox(
            victim_dir,
            history=victim_hist,
            node=f"victim-rank{victim_rank}",
            segment_every=segment_every,
        )
        boxes.append(victim_bb)
        victim_t0 = _time.monotonic()
        victim_hist.start()
        # The observer must SAMPLE the storm at its peak before the
        # kill (the rings are the post-mortem's only evidence).
        skew_threshold = DoctorConfig().hot_shard_skew
        wait_for(
            lambda: any(
                p[2] >= skew_threshold
                for p in hist_points(obs_hist, "shard:skew_ratio")
            )
        )
        # ...and the victim must commit at least one segment.
        wait_for(lambda: victim_bb.stats()["segments"] >= 1)

        # -- phase b: kill the hot owner mid-storm ---------------------
        # To its peers a node dies when it is last heard: t_kill is
        # stamped the moment the victim's digester has stopped (close
        # joins it, so a publish racing the close lands before), and
        # the rest of the teardown, however long this machine takes
        # over it, happens to a node already dead.
        for fp in fleet_planes:
            if fp.mesh is victim:
                fp.close()
        t_kill = _time.monotonic()
        victim_hist.close()
        victim_t1 = _time.monotonic()
        victim_seq_at_kill = victim_hist.stats()["seq"]
        victim_bb.close()  # NO flush: the kill -9 simulation
        victim.close()
        detected = wait_for(
            lambda: any(
                p[2] < 0.5
                for p in hist_points(
                    obs_hist, f'fleet:health_score{{rank="{victim_rank}"}}'
                )
            ),
            timeout=max(10.0, 20.0 * stale_after_s),
        )
        crash = {
            "performed": True,
            "victim_rank": victim_rank,
            "victim_is_hot_owner": victim_rank in expected_owners,
            "t_kill": round(t_kill, 3),
            "observer_detected_live": bool(detected),
        }

        # -- flush the observer (the SIGTERM exit path) ----------------
        flush_info = obs_bb.flush("sigterm")
        obs_hist.close()
        t_obs_closed = _time.monotonic()

        # -- phase c: post-mortem from the dumps alone -----------------
        obs_dump = load_blackbox(obs_dir)
        victim_dump = load_blackbox(victim_dir)
        obs_pm = postmortem_report(obs_dump)
        victim_pm = postmortem_report(victim_dump)
        hot_f = finding_for(obs_pm, "hot_shard")
        crash_f = finding_for(obs_pm, "node_crash", detector="health_drop")
        trunc_f = finding_for(
            victim_pm, "node_crash", detector="history_truncated"
        )
        window = (crash_f or {}).get("evidence", {}).get("window")
        window_contains_kill = bool(
            window is not None and window[0] - 0.05 <= t_kill <= window[1]
        )
        # The victim's dump must end within two segments of the last
        # sample its sampler took, counted in samples: a segment commits
        # every ``segment_every`` of them, whatever their spacing was.
        trunc_slack = 2 * segment_every
        trunc_last_seq = victim_dump.get("last_seq")
        truncation_within = bool(
            victim_dump["unclean"]
            and trunc_f is not None
            and trunc_last_seq is not None
            and 0 <= victim_seq_at_kill - trunc_last_seq <= trunc_slack
        )
        postmortem = {
            "observer": {
                "findings": obs_pm["findings"],
                "rules_checked": obs_pm["rules_checked"],
                "samples": obs_pm["samples"],
                "hot_shard_named": bool(
                    hot_f is not None
                    and hot_f["evidence"].get("shard") == expected_sid
                ),
                "hot_shard_evidence": (hot_f or {}).get("evidence", {}),
                "crash_window_named": window_contains_kill,
                "crash_evidence": (crash_f or {}).get("evidence", {}),
            },
            "victim": {
                "findings": victim_pm["findings"],
                "unclean": victim_pm["unclean"],
                "segments": victim_dump["segments"],
                "last_seq": trunc_last_seq,
                "seq_at_kill": victim_seq_at_kill,
                "truncation_slack_samples": trunc_slack,
                "truncation_named": truncation_within,
                "truncation_evidence": (trunc_f or {}).get("evidence", {}),
            },
            "expected": {
                "hot_shard": expected_sid,
                "hot_owners": expected_owners,
                "t_kill": round(t_kill, 3),
            },
        }

        wall_s = _time.monotonic() - t_start
        hist_stats = [h.stats() for h in histories]
        sampler_cost = sum(st["sample_seconds_total"] for st in hist_stats)
        # What the samplers and their black boxes cost is gated in units
        # of their own work, each against its stated bound — a share of
        # a shared CPU's wall clock measures the neighbours: sweeps (no
        # sooner than the interval allows over the time each sampler
        # ran), series swept a sweep, bytes committed a segment, and no
        # series refused. The measured share is reported beside them,
        # ungated.
        samples = sum(st["seq"] + 1 for st in hist_stats)
        max_samples = sum(
            int((t1 - t0) / history_interval_s) + 1
            for t0, t1 in ((obs_t0, t_obs_closed), (victim_t0, victim_t1))
        )
        series_per_sweep = max(st["series"] for st in hist_stats)
        segment_bytes = [
            os.path.getsize(os.path.join(root, name))
            for d in (obs_dir, victim_dir)
            for root, _, names in os.walk(d)
            for name in names
            if name.startswith("segment-")
        ]
        dropped = sum(st["dropped_series"] for st in hist_stats)
        obs_stats = hist_stats[0]  # the observer's: appended first
        history = {
            "interval_s": history_interval_s,
            "capacity": history_capacity,
            "samplers": len(histories),
            "samples": obs_stats["seq"] + 1,
            "series": obs_stats["series"],
            "points": obs_stats["points"],
            "dropped_series": obs_stats["dropped_series"],
            "self_overhead": {
                "sample_seconds_total": round(sampler_cost, 6),
                "wall_s": round(wall_s, 3),
                "fraction": round(sampler_cost / max(1e-9, wall_s), 6),
                "samples": samples,
                "max_samples": max_samples,
                "series_per_sweep": series_per_sweep,
                "segments": len(segment_bytes),
                "segment_bytes_max": max(segment_bytes, default=0),
                "dropped_series": dropped,
            },
        }
        overhead = history["self_overhead"]
        overhead["under_budget"] = not _blackbox_over_budget(overhead)
        blackbox = {
            "schema_version": obs_dump["schema_version"],
            "observer": {
                "segments": obs_dump["segments"],
                "finals": obs_dump["finals"],
                "causes": obs_dump["causes"],
                "bytes_final": flush_info["bytes"],
            },
            "victim": {
                "segments": victim_dump["segments"],
                "finals": victim_dump["finals"],
                "unclean": victim_dump["unclean"],
            },
        }
    finally:
        set_recorder(prev_recorder)
        for h in histories:
            h.close()
        for bb in boxes:
            bb.close()
        for fp in fleet_planes:
            fp.close()
        for n in nodes:
            n.close()
        InprocHub.reset_default()
        if own_tmp:
            shutil.rmtree(out_root, ignore_errors=True)

    named = sum([
        postmortem["observer"]["hot_shard_named"],
        postmortem["observer"]["crash_window_named"],
        postmortem["victim"]["truncation_named"],
    ])
    return {
        "nodes": len(prefill) + len(decode) + len(router_addrs),
        "topology": "4 prefill + 2 decode + 1 router (inproc, per-node "
        "fleet digesters) + step-accounted CPU engine",
        "replication_factor": replication_factor,
        "named": named,
        "healthy": healthy,
        "storm": heat,
        "crash": crash,
        "postmortem": postmortem,
        "history": history,
        "blackbox": blackbox,
        "attribution_audited": attr.stats()["audited"],
        "wall_s": round(_time.monotonic() - t_start, 3),
    }


# ----------------------------------------------------------------------
# BLACKBOX stable schema (PR 13): the flight-recorder acceptance
# artifact. A node killed mid-zipf-storm must yield black-box dumps
# (obs/blackbox.py) from which the post-mortem doctor
# (obs/doctor.py::postmortem_report) names the seeded hot shard and the
# crash window FROM THE DUMPS ALONE, the live history-backed doctor must
# stay silent on the healthy phase, and the telemetry sampler must
# stay inside its budget of sweeps, series and segment bytes.
# ----------------------------------------------------------------------

# v2 (PR 14): the healthy-phase rules_checked gate grew the
# rebalancer_asleep rule; v3 (PR 15): tier_thrash; v4 (PR 17): the
# three fleet rules (the workload arms an in-proc FleetAggregator for
# its healthy phase); v5 (PR 18): the three token-plane rules
# (decode_stall, spec_misconfigured, goodput_regression).
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
    "sample_seconds_total", "wall_s", "fraction", "samples", "max_samples",
    "series_per_sweep", "segments", "segment_bytes_max", "dropped_series",
    "under_budget",
)
# The sampler's budget, in units of its own work. A sweep folds every
# series once and a segment serializes the points ``segment_every``
# sweeps appended, so series a sweep and bytes a segment are what a
# dearer sampler shows first. The scenario's seven nodes, one engine and
# one tenant make about 740 series and segments of 90-130 KB: the bounds
# are the next power of two above each.
BLACKBOX_MAX_SERIES_PER_SWEEP = 1024
BLACKBOX_MAX_SEGMENT_BYTES = 256 * 1024


def _blackbox_over_budget(overhead: dict) -> list[str]:
    """What of ``history.self_overhead`` is over its bound, by name: more
    sweeps than the interval allows, a series refused, more series a
    sweep or bytes a segment than the bounds above, or no segment
    committed at all (nothing was measured)."""
    over = []
    if overhead["samples"] > overhead["max_samples"]:
        over.append(
            f"{overhead['samples']} sweeps of {overhead['max_samples']} "
            "allowed"
        )
    if overhead["series_per_sweep"] > BLACKBOX_MAX_SERIES_PER_SWEEP:
        over.append(
            f"{overhead['series_per_sweep']} series a sweep of "
            f"{BLACKBOX_MAX_SERIES_PER_SWEEP}"
        )
    if not overhead["segments"]:
        over.append("no segment committed")
    if overhead["segment_bytes_max"] > BLACKBOX_MAX_SEGMENT_BYTES:
        over.append(
            f"{overhead['segment_bytes_max']} bytes a segment of "
            f"{BLACKBOX_MAX_SEGMENT_BYTES}"
        )
    if overhead["dropped_series"]:
        over.append(f"{overhead['dropped_series']} series refused")
    return over


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
    (segments, no final); and the sampler stayed inside its budget of
    sweeps, series and segment bytes. Sections with performed=False are
    schema-valid but gate-exempt (the CHAOS convention)."""
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
            r for r in RULES
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
            missing = [
                f"history.self_overhead.{f}"
                for f in BLACKBOX_OVERHEAD_FIELDS
                if f not in overhead
            ]
            problems += missing
            over = [] if missing else _blackbox_over_budget(overhead)
            if over or overhead.get("under_budget") is not True:
                over.append(f"measured share {overhead.get('fraction')}")
                problems.append(
                    "history: sampler overhead out of budget "
                    f"({'; '.join(over)})"
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
