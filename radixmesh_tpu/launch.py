"""CLI launcher: ``radixmesh-tpu <command> --config-file cfg.yaml``.

The reference's only entry points are two test modules driven by a single
``--config-file`` flag (``test_util.py:16-23``, ``README.md:33-45``). This
CLI keeps that one-YAML-per-node operational model (identical config on
every node except ``local_addr``, reference ``README.md:122-124``) and adds
real commands:

- ``node``  — run one cache-mesh node (prefill / decode / router). Router
  nodes also expose the HTTP routing API (``POST /route``).
- ``serve`` — run a single-node serving engine with the HTTP generate API
  (cache-mesh-less quickstart; the disaggregated path wires engines to
  mesh nodes programmatically, see ``engine/disagg.py``).
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading

from radixmesh_tpu.utils.logging import configure_logger, get_logger

__all__ = ["main"]


def _log_devices(log, when: str) -> None:
    """One line naming the hardware this process computes on — platform,
    kind, count as JAX reports them — and each device's peak memory so
    far, so a start-up log says what it started ON and what it took."""
    from radixmesh_tpu.obs.step_plane import describe_devices

    dev = describe_devices()
    peaks = ", ".join(
        f"dev{m['id']} peak {m['peak_bytes_in_use'] / 2**30:.2f} GiB "
        f"of {m['bytes_limit'] / 2**30:.2f} GiB"
        for m in dev["memory"]
    )
    log.info(
        "%s: platform=%s device_kind=%s count=%d%s",
        when, dev["platform"], dev["device_kind"], dev["count"],
        f" ({peaks})" if peaks else "",
    )


def _configure_tracing(args: argparse.Namespace, node: str = "") -> None:
    """Enable the request-flight tracing plane (``obs/trace_plane.py``)
    when asked: ``--trace-sample`` gates recording; ``--trace-dir`` with
    the sample UNSET implies sample=1.0 (asking for a dump of nothing is
    never intended), but an EXPLICIT ``--trace-sample 0`` wins — the
    operator said off, so off (None default distinguishes the two).
    ``node`` labels this process's spans so the cross-node stitcher
    (``trace_plane.stitch_traces``) can give it its own Perfetto
    process-track."""
    sample = args.trace_sample
    if sample is None:
        sample = 1.0 if args.trace_dir else 0.0
    if sample > 0:
        from radixmesh_tpu.obs.trace_plane import configure

        configure(capacity=args.trace_capacity, sample=sample, node=node)


def _dump_trace(args: argparse.Namespace, log) -> None:
    """Exit-time flight-recorder dump: one Chrome trace-event artifact
    under ``--trace-dir`` (the post-mortem a wedged node leaves behind)."""
    if not args.trace_dir:
        return
    import os
    import time

    from radixmesh_tpu.obs.trace_plane import get_recorder, write_trace

    if not get_recorder().enabled:
        # Explicit --trace-sample 0 beat the dir (see _configure_tracing):
        # don't litter the directory with empty artifacts that read as
        # "a trace was captured".
        return

    try:
        os.makedirs(args.trace_dir, exist_ok=True)
        path = os.path.join(args.trace_dir, f"trace-{int(time.time())}.json")
        n = write_trace(path)
        log.info("wrote %d trace spans to %s", n, path)
    except OSError:
        log.exception("trace dump failed")


def _run_node(args: argparse.Namespace) -> int:
    import jax

    from radixmesh_tpu.cache.kv_pool import PagedKVPool
    from radixmesh_tpu.cache.mesh_cache import MeshCache
    from radixmesh_tpu.config import NodeRole, load_config, parse_addr
    from radixmesh_tpu.router.cache_aware_router import CacheAwareRouter
    from radixmesh_tpu.server.http_frontend import RouterFrontend

    # Multi-router front door override: like every topology key, the
    # SAME list must be passed on every node of the cluster (the global
    # rank space is positional). Applied BEFORE validation so a router
    # added by flag can find its own membership.
    router_override = (
        [a.strip() for a in args.router_nodes.split(",") if a.strip()]
        if args.router_nodes is not None
        else None
    )
    # The --replication-factor override (prefix-ownership sharding,
    # cache/sharding.py) must be IDENTICAL on every node, same contract
    # as every other config key; applied pre-validation so the
    # rebalance/replication cross-field check judges the factor the
    # node actually runs with.
    cfg = load_config(
        args.config_file,
        router_nodes=router_override,
        replication_factor=args.replication_factor,
        # Validated WITH the factor above: --rebalance-interval without
        # sharding gets config.validate()'s refusal, the same error the
        # YAML key gives (one rule, one home).
        rebalance_interval_s=args.rebalance_interval,
    )
    role, rank, _ = cfg.local_identity()
    configure_logger(f"{role.value}@{rank}")
    log = get_logger("launch")
    _configure_tracing(args, node=f"{role.value}@{rank}")
    if cfg.replication_factor > 0:
        log.info(
            "prefix-ownership sharding ON (replication factor %d)",
            cfg.replication_factor,
        )

    # Chaos/fault-injection plane (comm/faults.py): installed BEFORE the
    # node opens any transport so every channel — ring, spine, router
    # fan-out, prefetch, repair — passes the seam. Drill/soak tooling
    # only; production configs leave it empty.
    chaos_spec = None
    if args.chaos_plan:
        import json as _json

        with open(args.chaos_plan) as fh:
            chaos_spec = _json.load(fh)
    elif cfg.chaos:
        chaos_spec = cfg.chaos
    if chaos_spec:
        from radixmesh_tpu.comm.faults import FaultPlan, install

        plan = FaultPlan.from_dict(chaos_spec)
        install(plan)
        log.warning(
            "CHAOS PLAN ARMED (seed=%d, drop_p=%.2f, %d partitions) — "
            "transports on this node will misbehave on schedule",
            plan.seed, plan.drop_p, len(plan.partitions),
        )

    # A P/D node with a ``model:`` section is a SERVING node: one shared KV
    # pool, an Engine that owns slot lifetime, and an advertisement-only
    # MeshCache (pool=None — the engine frees slots, the mesh must not)
    # wired into every publish. This is the reference's end-to-end loop
    # (radix_mesh.py:193-238): serve → publish → replicate → route back.
    serving = role is not NodeRole.ROUTER and bool(cfg.model)
    pool = None
    mcfg = None
    if serving:
        from radixmesh_tpu.models import get_config

        model = cfg.model
        mcfg = get_config(
            model.get("preset", "llama3-tiny"), **model.get("overrides", {})
        )
        # Engine page size (pow-2 paged-attention granularity) is distinct
        # from cfg.page_size (mesh replication granularity, default 1).
        page_size = int(model.get("page_size", 16))
        planes, heads, width = mcfg.kv_row
        pool = PagedKVPool(
            num_slots=int(model.get("kv_slots", cfg.num_kv_slots)),
            num_layers=mcfg.n_layers,
            num_kv_heads=heads,
            head_dim=width,
            page_size=page_size,
            dtype=mcfg.dtype,
            quant=model.get("kv_quant"),
            planes=planes,
        )
        node = MeshCache(cfg, pool=None).start()
    elif role is not NodeRole.ROUTER:
        # Standalone cache node (no model): the mesh owns the pool, like the
        # reference's model-less deployment.
        model = cfg.model or {}
        pool = PagedKVPool(
            num_slots=cfg.num_kv_slots,
            num_layers=int(model.get("n_layers", 1)),
            num_kv_heads=int(model.get("n_kv_heads", 1)),
            head_dim=int(model.get("head_dim", 128)),
            page_size=cfg.page_size,
        )
        node = MeshCache(cfg, pool=pool).start()
    else:
        node = MeshCache(cfg).start()
    log.info("node started; waiting for ring verification...")
    if not node.wait_ready(timeout=args.ready_timeout):
        log.error("startup tick barrier timed out")
        node.close()
        return 1
    log.info("ring verified (view epoch=%d)", node.view.epoch)

    # Text seam for both frontends: --tokenizer wins, else the config's
    # model.tokenizer key (must be the SAME spec on router and serving
    # nodes, or routed text prefixes won't line up with cached ones).
    tokenizer = None
    tok_spec = args.tokenizer or (cfg.model or {}).get("tokenizer")
    if tok_spec:
        from radixmesh_tpu.server.tokenizer import load_tokenizer

        tokenizer = load_tokenizer(tok_spec)

    frontend = None
    fleet_plane = None
    engine = None
    if role is NodeRole.ROUTER:
        router = CacheAwareRouter(
            node, cfg,
            health_aware=args.health_aware_routing,
            prefetch_hints=args.kv_prefetch_hints,
        )
        router.watch_topology()
        if not args.warm_up:
            router.finish_warm_up()
        host = parse_addr(cfg.local_addr)[0] or "127.0.0.1"
        # Fleet aggregation (obs/aggregator.py): with a pull cadence
        # configured, this router cursor-pulls every ring node's
        # /debug/timeseries into one fleet store. The peer list is
        # DERIVED from the topology (each node's serving HTTP address),
        # named to match the engines' node labels ("prefill0",
        # "decode2", ...) so fleet series line up with per-node ones.
        agg_interval = (
            args.agg_interval
            if args.agg_interval is not None
            else cfg.agg_interval_s
        )
        agg_peers = []
        if agg_interval > 0:
            for r in range(cfg.num_ring):
                serve = cfg.serve_addr(cfg.addr_of_rank(r))
                if serve is None:  # portless inproc address: no HTTP tier
                    continue
                agg_peers.append(
                    (f"{cfg.role_of_rank(r).value}{r}", f"http://{serve}", r)
                )
            if not agg_peers:
                log.warning(
                    "--agg-interval %.1fs set but no ring node has an "
                    "HTTP serving address — fleet aggregator stays off",
                    agg_interval,
                )
        frontend = RouterFrontend(
            router, host=host, port=args.http_port, tokenizer=tokenizer,
            aggregator_peers=agg_peers,
            aggregator_interval_s=agg_interval or 2.0,
            **_history_kwargs(args),
        )
        log.info("routing API on port %d", frontend.port)
        if frontend.aggregator is not None:
            log.info(
                "fleet aggregator ON: pulling %d peer(s) every %.1fs "
                "(GET /cluster/timeseries, /cluster/slo)",
                len(agg_peers), agg_interval,
            )
    elif serving:
        from radixmesh_tpu.engine.engine import Engine
        from radixmesh_tpu.models import init_params
        from radixmesh_tpu.server.http_frontend import ServingFrontend

        model = cfg.model
        log.info("initializing model %s...", model.get("preset", "llama3-tiny"))
        _log_devices(log, "devices")
        params = init_params(
            mcfg,
            jax.random.PRNGKey(int(model.get("seed", 0))),
            weight_quant=model.get("weight_quant"),
        )
        engine = Engine(
            mcfg,
            params,
            pool=pool,
            page_size=pool.page_size,
            max_batch=int(model.get("max_batch", 8)),
            host_cache_slots=int(model.get("host_cache_slots", 0)),
            prefill_inline_budget=int(model.get("prefill_inline_budget", 0)),
            prefill_inline_max_defer=int(
                model.get("prefill_inline_max_defer", 2)
            ),
            spec_decode_tokens=int(model.get("spec_decode_tokens", 0)),
            spec_adaptive=bool(model.get("spec_adaptive", False)),
            token_timeline_capacity=int(
                model.get("token_timeline_capacity", 4096)
            ),
            token_stall_threshold_s=float(
                model.get("token_stall_threshold_s", 0.05)
            ),
            kv_quant=model.get("kv_quant"),
            weight_quant=model.get("weight_quant"),
            mesh=node,
            name=f"{role.value}{rank}",
            kv_transfer_async=(
                args.kv_transfer_async or cfg.kv_transfer_async
            ),
            kv_transfer_chunk_tokens=(
                args.kv_transfer_chunk
                if args.kv_transfer_chunk is not None
                else cfg.kv_transfer_chunk_tokens
            ),
            kv_transfer_min_restore_tokens=(
                args.kv_transfer_min_restore
                if args.kv_transfer_min_restore is not None
                else cfg.kv_transfer_min_restore_tokens
            ),
            stream_publish_tokens=(
                args.stream_publish
                if args.stream_publish is not None
                else cfg.stream_publish_tokens
            ),
            kv_tier_dir=(args.kv_tier_dir or cfg.kv_tier_dir),
            kv_tier_capacity_bytes=(
                int(args.kv_tier_capacity_gb * (1 << 30))
                if args.kv_tier_capacity_gb is not None
                else cfg.kv_tier_capacity_bytes
            ),
            # TPU step attribution (obs/step_plane.py): per-wave MFU +
            # pad-fraction accounting, opt-in via the model config (the
            # node subcommand is config-file-driven).
            step_accounting=bool(model.get("step_accounting", False)),
            peak_tflops=model.get("peak_tflops"),
        )
        if engine.resurrected.get("grafted_nodes"):
            # Cold-cell resurrection: the transport is up (node.start()
            # above), so re-announce the disk-grafted working set
            # through the normal insert/SHARD_SUMMARY path — routers
            # and co-owners learn these prefixes exist again.
            n = engine.announce_resurrected()
            log.info(
                "resurrected %d prefix(es) / %d tokens from %s; "
                "re-announced %d",
                engine.resurrected["grafted_nodes"],
                engine.resurrected["grafted_tokens"],
                args.kv_tier_dir or cfg.kv_tier_dir, n,
            )
        if engine.kv_transfer is not None:
            # Predictive restores: PREFETCH hints received off the wire
            # land in the plane's bounded hint queue; the engine converts
            # them to no-request restores at its next pump.
            node.on_prefetch = engine.kv_transfer.note_hint
            log.info("async KV-movement plane enabled")
        host, port = parse_addr(cfg.local_addr)
        frontend = ServingFrontend(
            engine, host=host or "127.0.0.1",
            port=port + cfg.serve_port_offset, tokenizer=tokenizer,
            **_history_kwargs(args),
        )
        log.info("serving API on port %d", frontend.port)

    # Cache-only nodes (no frontend: non-router, no model: section) still
    # honor the history/black-box flags — the planes compose without an
    # HTTP surface, so a crashing cache node leaves the same dump a
    # serving node does instead of silently ignoring --blackbox-dir.
    history_plane = None
    blackbox_plane = None
    if frontend is None:
        hk = _history_kwargs(args)
        # Without an HTTP surface the dump is the ONLY reader of the
        # rings, so a default cache node doesn't pay for a sampler
        # thread (plus up to max_series retained rings) nobody can
        # read — history only spins up when --blackbox-dir arms it.
        if hk["blackbox_dir"] and hk["history_interval_s"] > 0:
            from radixmesh_tpu.obs.timeseries import TelemetryHistory

            history_plane = TelemetryHistory(
                interval_s=hk["history_interval_s"],
                mesh=node,
                node=f"{role.value}@{rank}",
            )
        if hk["blackbox_dir"]:
            from radixmesh_tpu.obs.blackbox import BlackBox
            from radixmesh_tpu.obs.doctor import MeshDoctor
            from radixmesh_tpu.obs.trace_plane import get_recorder

            blackbox_plane = BlackBox(
                hk["blackbox_dir"],
                history=history_plane,
                doctor=MeshDoctor(mesh=node, history=history_plane),
                recorder=get_recorder,
                node=f"{role.value}@{rank}",
                watchdog_timeout_s=hk["blackbox_watchdog_s"],
            )
        if history_plane is not None:
            # Started AFTER the black box installed its segment hook,
            # so the very first samples are already crash-durable.
            history_plane.start()
            log.info(
                "telemetry history sampling every %.1fs%s",
                hk["history_interval_s"],
                f" (black box: {hk['blackbox_dir']})"
                if hk["blackbox_dir"] else "",
            )

    # Fleet telemetry plane: ring nodes gossip a NodeDigest per interval
    # (serving nodes include engine occupancy/latency; cache-only nodes
    # publish mesh-only digests). Routers never send — their fleet view
    # fills from the master's fan-out. Constructed here but STARTED after
    # the lifecycle plane attaches, so the very first digest already
    # carries the node's true lifecycle state.
    digest_interval = (
        args.fleet_digest_interval
        if args.fleet_digest_interval is not None
        else cfg.digest_interval_s
    )
    if role is not NodeRole.ROUTER and digest_interval > 0:
        from radixmesh_tpu.obs.fleet_plane import FleetPlane

        fleet_plane = FleetPlane(
            node,
            engine=engine,
            # The digest's slo_tier field follows the node's overload
            # controller when one exists (SLO-enabled frontends expose
            # it as runner.ctl; plain runners have no tier to report).
            slo=getattr(getattr(frontend, "runner", None), "ctl", None),
            interval_s=digest_interval,
        )

    # Anti-entropy repair plane: every role runs one (routers probe and
    # pull; they never push) — it closes the detect→repair loop the
    # fleet digests open. Needs digest gossip to see peers: a P/D node
    # that doesn't publish still folds received digests, so repair works
    # as long as SOMEONE gossips.
    repair_plane = None
    repair_interval = (
        args.repair_interval
        if args.repair_interval is not None
        else cfg.repair_interval_s
    )
    if repair_interval > 0:
        from radixmesh_tpu.cache.repair_plane import RepairConfig, RepairPlane

        repair_plane = RepairPlane(
            node,
            RepairConfig(
                interval_s=repair_interval,
                age_threshold_s=cfg.repair_age_threshold_s,
                key_budget=cfg.repair_key_budget,
                backoff_base_s=cfg.repair_backoff_s,
                backoff_max_s=max(
                    cfg.repair_backoff_s * 30.0, cfg.repair_backoff_s
                ),
            ),
        ).start()
        log.info(
            "anti-entropy repair armed (scan %.1fs, stale after %.1fs)",
            repair_interval, cfg.repair_age_threshold_s,
        )

    # Heat-driven shard rebalancer (cache/rebalance.py): every sharded
    # P/D node runs the plane; only the current view master decides
    # (lowest-alive-rank failover, no election). Overrides gossip like
    # the view, so arming it on every node costs one idle ticker per
    # non-master.
    rebalance_plane = None
    # The CLI override already folded into cfg pre-validation (see
    # load_config above), so the rf>0 requirement was enforced there.
    rebalance_interval = cfg.rebalance_interval_s
    if (
        role is not NodeRole.ROUTER
        and cfg.replication_factor > 0
        and rebalance_interval > 0
    ):
        from radixmesh_tpu.cache.rebalance import (
            RebalanceConfig,
            RebalancePlane,
        )

        rebalance_plane = RebalancePlane(
            node, RebalanceConfig(interval_s=rebalance_interval)
        ).start()
        log.info(
            "heat-driven rebalancer armed (tick %.1fs; decider = view "
            "master)",
            rebalance_interval,
        )

    # Membership lifecycle plane (policy/lifecycle.py): ring nodes get
    # the BOOTSTRAPPING → ACTIVE → DRAINING → LEFT state machine. Warm
    # bootstrap (bulk repair from a donor + router hit-withholding) only
    # engages when the machinery it rides exists — digest gossip to see
    # donors and a repair plane to pull through; otherwise the node
    # starts ACTIVE, exactly the pre-lifecycle behavior. POST
    # /admin/drain (serving nodes) and SIGTERM both drain through it.
    lifecycle_plane = None
    if role is not NodeRole.ROUTER:
        from radixmesh_tpu.policy.lifecycle import (
            LifecycleConfig,
            LifecyclePlane,
        )

        lifecycle_plane = LifecyclePlane(
            node,
            repair=repair_plane,
            runner=getattr(frontend, "runner", None),
            fleet_plane=fleet_plane,
            cfg=LifecycleConfig(drain_timeout_s=args.drain_timeout),
            bootstrap=(repair_plane is not None and digest_interval > 0),
            # Drain step 5c flushes the black box, so a planned
            # departure always leaves a complete post-mortem dump.
            blackbox=getattr(frontend, "blackbox", None) or blackbox_plane,
        )
        if frontend is not None:
            frontend.lifecycle = lifecycle_plane
    if fleet_plane is not None:
        fleet_plane.start()
        log.info("fleet digests every %.1fs", digest_interval)
    if lifecycle_plane is not None:
        lifecycle_plane.start()
        log.info(
            "membership lifecycle plane armed (state=%s, drain timeout %.0fs)",
            lifecycle_plane.state.value, args.drain_timeout,
        )

    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        while not stop.is_set():
            stop.wait(1.0)
    finally:
        if lifecycle_plane is not None:
            # Drain on the way out when we can still talk to the ring:
            # requeue parked work, flush hot prefixes, announce LEAVE —
            # the graceful path SIGTERM is supposed to take. Already-
            # drained (POST /admin/drain) nodes fall through instantly.
            try:
                lifecycle_plane.drain(deadline_s=args.drain_timeout)
            except Exception:  # noqa: BLE001 — drain failure must not block exit
                log.exception("exit drain failed")
            lifecycle_plane.close()
        if rebalance_plane is not None:
            rebalance_plane.close()
        if repair_plane is not None:
            repair_plane.close()
        if fleet_plane is not None:
            fleet_plane.close()
        if frontend is not None:
            frontend.close()
        if blackbox_plane is not None:
            blackbox_plane.close(flush_cause="shutdown")
        if history_plane is not None:
            history_plane.close()
        node.close(graceful=True)
        _dump_trace(args, log)
    return 0


def _apply_libtpu_args(cfg, log) -> None:
    """``cfg.libtpu_init_args`` into ``LIBTPU_INIT_ARGS``, keeping what the
    caller set (a flag the caller names wins). Called before anything touches
    a device: libtpu reads the variable once, when the backend starts; a
    process held to the CPU never reads it."""
    import os

    have = os.environ.get("LIBTPU_INIT_ARGS", "")
    extra = [a for a in cfg.libtpu_init_args if a.split("=")[0] not in have]
    if extra:
        os.environ["LIBTPU_INIT_ARGS"] = " ".join([have, *extra]).strip()
        log.info("LIBTPU_INIT_ARGS for this model: %s", " ".join(extra))


def _run_serve(args: argparse.Namespace) -> int:
    import jax

    from radixmesh_tpu.engine.engine import Engine
    from radixmesh_tpu.models import get_config, init_params
    from radixmesh_tpu.obs.tracing import ProcessAccount
    from radixmesh_tpu.server.http_frontend import ServingFrontend

    configure_logger("serve")
    log = get_logger("launch")
    _configure_tracing(args, node="serve")
    # The process's collections, compilations and start-up, counted from
    # here on (/debug/state ``process``). gc.callbacks and jax.monitoring
    # are global to the process: this entry point installs them, no library
    # code does. The phases: ``imports`` (the process's start -> here),
    # ``devices`` (the backend), ``params`` (until the weights are on the
    # device), ``engine`` (pool and tables), ``listen``.
    account = ProcessAccount().install()
    account.started("imports")
    with account.phase("devices"):
        cfg = get_config(args.model)
        log.info("initializing %s (%d layers)...", args.model, cfg.n_layers)
        _apply_libtpu_args(cfg, log)
        _log_devices(log, "devices")
    with account.phase("params"):
        if args.weights:
            from radixmesh_tpu.models.hf_io import load_hf_checkpoint

            log.info("loading HF checkpoint from %s", args.weights)
            params = load_hf_checkpoint(args.weights, cfg)
        else:
            # Drawn directly in the serving form (W8A16 when asked), a layer
            # at a time: the float pytree of a 7B model never exists.
            params = init_params(
                cfg, jax.random.PRNGKey(args.seed), weight_quant=args.weight_quant
            )
        jax.block_until_ready(params)
    tokenizer = None
    if args.tokenizer:
        from radixmesh_tpu.server.tokenizer import load_tokenizer

        tokenizer = load_tokenizer(args.tokenizer)
    with account.phase("engine"):
        engine = Engine(
            cfg,
            params,
            num_slots=args.kv_slots,
            page_size=args.page_size,
            max_batch=args.max_batch,
            host_cache_slots=args.host_cache_slots,
            prefill_inline_budget=args.prefill_inline_budget,
            prefill_inline_max_defer=args.prefill_inline_max_defer,
            spec_decode_tokens=args.spec_decode_tokens,
            spec_adaptive=args.spec_adaptive,
            token_timeline_capacity=args.token_timeline_capacity,
            token_stall_threshold_s=args.token_stall_threshold_ms / 1e3,
            kv_quant=args.kv_quant,
            weight_quant=args.weight_quant,
            kv_transfer_async=args.kv_transfer_async,
            kv_transfer_chunk_tokens=args.kv_transfer_chunk or 512,
            kv_transfer_min_restore_tokens=args.kv_transfer_min_restore or 0,
            kv_tier_dir=args.kv_tier_dir,
            kv_tier_capacity_bytes=(
                int(args.kv_tier_capacity_gb * (1 << 30))
                if args.kv_tier_capacity_gb is not None
                else 1 << 30
            ),
            stream_publish_tokens=args.stream_publish or 0,
            step_accounting=args.step_accounting,
            peak_tflops=args.peak_tflops,
            process_account=account,
        )
    slo_cfg = None
    if args.slo or args.slo_ttft_ms is not None or args.slo_tenant:
        from radixmesh_tpu.slo import SLOConfig, TenantConfig

        tenants = {}
        for spec in args.slo_tenant:
            # NAME=WEIGHT[:RATE_TOKENS_PER_S]
            name, _, rest = spec.partition("=")
            if not name or not rest:
                raise SystemExit(f"--slo-tenant {spec!r}: want NAME=W[:RATE]")
            weight, _, rate = rest.partition(":")
            tenants[name] = TenantConfig(
                weight=float(weight),
                rate_tokens_per_s=float(rate) if rate else 0.0,
            )
        slo_cfg = SLOConfig(
            tenants=tenants,
            default_ttft_slo_s=(
                args.slo_ttft_ms / 1e3
                if args.slo_ttft_ms is not None
                else None
            ),
        )
        log.info("SLO control plane enabled (%d tenants)", len(tenants))
    _log_devices(log, "weights + KV pool resident")
    with account.phase("listen"):
        frontend = ServingFrontend(
            engine, host=args.host, port=args.http_port,
            profile_dir=args.profile_dir, tokenizer=tokenizer, slo=slo_cfg,
            **_history_kwargs(args),
        )
    print(f"serving {args.model} on http://{args.host}:{frontend.port}", flush=True)

    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        while not stop.is_set():
            stop.wait(1.0)
    finally:
        frontend.close()
        account.uninstall()
        _dump_trace(args, log)
    return 0


def _run_multihost_dryrun(args: argparse.Namespace) -> int:
    """One sharded train step with every process's chips in one mesh:
    the same pjit program as the single-host dryrun, with XLA emitting
    the cross-host collectives (SURVEY §5 'distributed communication
    backend' — compute plane)."""
    from radixmesh_tpu.parallel.multihost import global_mesh, init_multihost

    info = init_multihost(
        args.coordinator, args.num_processes, args.process_id,
        local_device_count=args.local_devices,
    )
    import math

    from radixmesh_tpu.parallel.sharding import MeshPlan
    from radixmesh_tpu.parallel.train import run_dryrun_train_step

    plan = None
    if args.mesh:
        dp, sp, tp = (int(x) for x in args.mesh.split(","))
        plan = MeshPlan(dp=dp, sp=sp, tp=tp)
    mesh = global_mesh(plan)
    loss = run_dryrun_train_step(mesh)
    print(
        f"multihost-dryrun: proc {info.process_index}/{info.process_count} "
        f"devices {info.local_devices} local / {info.global_devices} global "
        f"mesh={dict(mesh.shape)} loss={loss:.4f}",
        flush=True,
    )
    return 0 if math.isfinite(loss) else 1


def _add_kv_transfer_args(sub: argparse.ArgumentParser) -> None:
    """Async KV-movement plane flags (``cache/kv_transfer.py``), shared
    by node + serve."""
    sub.add_argument(
        "--kv-transfer-async", action="store_true",
        help="stage host-tier restores / eviction write-backs / disagg "
        "placement off the scheduling thread (requests with host-tier "
        "prefixes park in RESTORING while decode keeps stepping)",
    )
    sub.add_argument(
        "--kv-transfer-chunk", type=int, default=None, metavar="TOKENS",
        help="restore staging chunk size in tokens (default 512): smaller "
        "chunks interleave with decode more finely",
    )
    sub.add_argument(
        "--kv-transfer-min-restore", type=int, default=None, metavar="TOKENS",
        help="restores shorter than this stay on the synchronous "
        "in-admission path (default 0 = always staged)",
    )
    sub.add_argument(
        "--kv-tier-dir", default=None, metavar="DIR",
        help="durable KV spill tier (cache/kv_tier.py): directory for "
        "checksummed fsynced extent files below the host-RAM tier. "
        "Arms the async plane; at boot the directory is scanned and "
        "every verified prefix is resurrected (cold-cell recovery). "
        "Requires a host tier (host_cache_slots > 0)",
    )
    sub.add_argument(
        "--kv-tier-capacity-gb", type=float, default=None, metavar="GB",
        help="extent-store disk budget (default 1 GiB); oldest extents "
        "are dropped past it",
    )
    sub.add_argument(
        "--stream-publish", type=int, default=None, metavar="TOKENS",
        help="publish a request's grown prefix to the tree + ring every "
        "N generated tokens (crash recovery: bounds a resurrected "
        "request's cache-hit loss to N tokens; default 0 = publish only "
        "at finish/preempt)",
    )


def _history_kwargs(args: argparse.Namespace) -> dict:
    """Frontend kwargs for the telemetry-history + black-box planes
    (``obs/timeseries.py`` / ``obs/blackbox.py``), shared by node +
    serve so the wiring cannot drift. The watchdog default arms at
    10x the sample interval whenever a dump directory exists — an
    unclean death should leave a final artifact without the operator
    remembering a flag."""
    interval = args.telemetry_history_interval
    watchdog = args.blackbox_watchdog
    if watchdog is None:
        watchdog = 10.0 * interval if args.blackbox_dir else 0.0
    if args.blackbox_dir and interval <= 0:
        # The flag's promise ("segments land here continuously", a
        # watchdog-armed final) depends on the sampler; an armed box
        # with no history records nothing — say so instead of leaving
        # a manifest-only dir the operator will discover post-crash.
        get_logger("launch").warning(
            "--blackbox-dir %s is armed but --telemetry-history-interval "
            "is 0: no history will be recorded, no segments written, and "
            "the unclean-death watchdog stays off — only explicit "
            "flushes (SIGTERM/drain/POST /admin/blackbox) leave a dump",
            args.blackbox_dir,
        )
    return {
        "history_interval_s": interval,
        "blackbox_dir": args.blackbox_dir,
        "blackbox_watchdog_s": watchdog,
    }


def _add_history_args(sub: argparse.ArgumentParser) -> None:
    """Telemetry-history / black-box flags, shared by node + serve."""
    sub.add_argument(
        "--telemetry-history-interval", type=float, default=1.0,
        metavar="SECONDS",
        help="sample every registered metric family plus the fleet/"
        "heat/step planes into bounded in-process time-series rings "
        "every N seconds (obs/timeseries.py; ~15 min retained, served "
        "on GET /debug/timeseries with cursor pagination; also feeds "
        "the doctor's burn-rate windows). 0 disables",
    )
    sub.add_argument(
        "--blackbox-dir", default=None, metavar="DIR",
        help="arm the black box (obs/blackbox.py): incremental history "
        "segments land here continuously (atomic renames — a kill -9 "
        "keeps every completed segment), and SIGTERM / drain / the "
        "unclean-death watchdog / POST /admin/blackbox flush a full "
        "final dump (history + waterfalls + spans + doctor findings + "
        "state) for scripts/doctor.py --blackbox",
    )
    sub.add_argument(
        "--blackbox-watchdog", type=float, default=None, metavar="SECONDS",
        help="flush the black box once if the history sampler stalls "
        "this long (default: 10x the sample interval when "
        "--blackbox-dir is set; 0 disables)",
    )


def _add_trace_args(sub: argparse.ArgumentParser) -> None:
    """Request-flight tracing flags, shared by node + serve."""
    sub.add_argument(
        "--trace-capacity", type=int, default=8192,
        help="flight-recorder span bound (drop-oldest past it)",
    )
    sub.add_argument(
        "--trace-sample", type=float, default=None,
        help="fraction of requests to trace (0 disables — the default; "
        "spans surface on GET /debug/trace as Perfetto-loadable JSON)",
    )
    sub.add_argument(
        "--trace-dir", default=None,
        help="also dump the flight recorder to this directory on exit "
        "(implies --trace-sample 1.0 unless set explicitly)",
    )


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="radixmesh-tpu")
    sub = p.add_subparsers(dest="command", required=True)

    node = sub.add_parser("node", help="run one cache-mesh node")
    node.add_argument("--config-file", required=True)
    node.add_argument("--http-port", type=int, default=0, help="router API port")
    node.add_argument("--ready-timeout", type=float, default=120.0)
    node.add_argument(
        "--tokenizer", default=None,
        help="'byte' or a local HF tokenizer dir; enables text on this "
        "node's API (same spec on every node; overrides model.tokenizer)",
    )
    node.add_argument(
        "--warm-up",
        action="store_true",
        help="start the router in warm-up (spread) mode",
    )
    node.add_argument(
        "--fleet-digest-interval", type=float, default=None, metavar="SECONDS",
        help="gossip this node's fleet NodeDigest (tree fingerprint, fill, "
        "health signals) every N seconds as one oplog frame "
        "(obs/fleet_plane.py); overrides the config's digest_interval_s; "
        "0 disables origination (folding received digests stays on)",
    )
    node.add_argument(
        "--health-aware-routing", action="store_true",
        help="router role: demote nodes whose gossiped health score drops "
        "below 0.5 (stall watchdog, replication lag, eviction storm) — "
        "cache hits shed past them and the hash-ring fallback skips them",
    )
    node.add_argument(
        "--repair-interval", type=float, default=None, metavar="SECONDS",
        help="anti-entropy repair scan cadence (cache/repair_plane.py): "
        "compare this node's tree fingerprint against gossiped digests "
        "and open bounded repair sessions with stale-diverged peers; "
        "overrides the config's repair_interval_s; 0 disables (detect-"
        "only). Needs --fleet-digest-interval somewhere in the fleet",
    )
    node.add_argument(
        "--replication-factor", type=int, default=None, metavar="RF",
        help="prefix-ownership sharding (cache/sharding.py): each subtree "
        "shard is owned by RF consistent-hash successors and inserts are "
        "delivered point-to-point to the owner set only — bytes-per-"
        "insert O(RF) instead of O(ring size). Must be identical on every "
        "node. 0 (the default) = full replication, bit-for-bit the old "
        "ring wire",
    )
    node.add_argument(
        "--router-nodes", default=None, metavar="ADDR,ADDR",
        help="multi-router front door override: comma-separated router "
        "cache addresses replacing the config's router_nodes (must be "
        "IDENTICAL on every node — the rank space is positional). Every "
        "router is fed by the master fan-out; clients fail over between "
        "them (router/front_door.py)",
    )
    node.add_argument(
        "--rebalance-interval", type=float, default=None, metavar="SECONDS",
        help="heat-driven shard rebalancing (cache/rebalance.py): the "
        "view master consumes the gossiped heat map every N seconds — "
        "hot shards temporarily gain owners (reads fan out), cooled "
        "shards shrink back (hysteresis band), moves bounded per round "
        "and handed off zero-loss. Requires --replication-factor > 0; "
        "overrides the config's rebalance_interval_s; 0 disables the "
        "decider (folding gossiped overrides stays on)",
    )
    node.add_argument(
        "--chaos-plan", default=None, metavar="FILE",
        help="ARM FAULT INJECTION from a FaultPlan JSON file "
        "(comm/faults.py): seeded frame drops, delays, duplicates, "
        "reordering, scheduled partitions, channel crashes — applied to "
        "every transport this node opens. Drills and soak runs only",
    )
    node.add_argument(
        "--drain-timeout", type=float, default=30.0, metavar="SECONDS",
        help="graceful-drain deadline (policy/lifecycle.py): on POST "
        "/admin/drain or SIGTERM, in-flight decodes get this long to "
        "finish while new work sheds retriably (503 + Retry-After at "
        "the router), parked restores are requeued, hot prefixes are "
        "written back to the host tier, and the node announces LEAVE",
    )
    node.add_argument(
        "--agg-interval", type=float, default=None, metavar="SECONDS",
        help="router role: host the fleet telemetry aggregator "
        "(obs/aggregator.py) — cursor-pull every ring node's "
        "/debug/timeseries at this cadence into one node-labeled fleet "
        "store, served on GET /cluster/timeseries with true cross-node "
        "percentiles on GET /cluster/slo (and the fleet doctor rules: "
        "straggler_node, fleet_burn_slope, telemetry_gap). Overrides "
        "the config's agg_interval_s; 0 disables",
    )
    node.add_argument(
        "--kv-prefetch-hints", action="store_true",
        help="router role: fire an idempotent PREFETCH oplog at the node a "
        "cache hit routes to, so a host-tier prefix starts restoring to "
        "HBM before the request arrives (cache/kv_transfer.py)",
    )
    _add_kv_transfer_args(node)
    _add_trace_args(node)
    _add_history_args(node)
    node.set_defaults(fn=_run_node)

    serve = sub.add_parser("serve", help="run a single-node serving engine")
    serve.add_argument("--model", default="llama3-tiny")
    serve.add_argument(
        "--weights", default=None,
        help="HF-format safetensors checkpoint directory (models/hf_io.py); "
        "default: random init",
    )
    serve.add_argument(
        "--tokenizer", default=None,
        help="'byte' (lossless UTF-8 fallback) or a local HF tokenizer "
        "directory; enables text in/out on /generate",
    )
    serve.add_argument("--host", default="127.0.0.1")
    serve.add_argument("--http-port", type=int, default=8000)
    serve.add_argument("--kv-slots", type=int, default=4096)
    serve.add_argument("--page-size", type=int, default=16)
    serve.add_argument("--max-batch", type=int, default=8)
    serve.add_argument("--host-cache-slots", type=int, default=0)
    serve.add_argument("--seed", type=int, default=0)
    serve.add_argument(
        "--prefill-inline-budget", type=int, default=0,
        help="mixed compute waves (engine/waves.py): ride up to N tokens "
        "of chunked prefill from queued prompts on each decode launch "
        "instead of convoying running streams behind whole prefill waves "
        "(0 = legacy alternating schedule)",
    )
    serve.add_argument(
        "--prefill-inline-max-defer", type=int, default=2,
        help="starvation bound for mixed waves: at most N consecutive "
        "prefill-only catch-up waves before a decode-bearing wave MUST "
        "run (bound stated in wave counts, not wall-clock)",
    )
    serve.add_argument(
        "--profile-dir", default=None,
        help="enable POST /profile + GET /debug/profile?seconds=N "
        "captures into this directory",
    )
    serve.add_argument(
        "--step-accounting", action="store_true",
        help="TPU step attribution (obs/step_plane.py): per-wave token/"
        "padding accounting + analytic-FLOPs MFU estimate, exported as "
        "radixmesh_step_mfu / radixmesh_wave_pad_fraction and on "
        "/debug/state",
    )
    serve.add_argument(
        "--peak-tflops", type=float, default=None,
        help="nominal accelerator peak for the MFU estimate (default: "
        "looked up from the jax device kind — an unknown accelerator is "
        "an error; 1.0 on a CPU)",
    )
    serve.add_argument(
        "--kv-quant", choices=["int8"], default=None,
        help="store the KV pool quantized (halves decode HBM traffic)",
    )
    serve.add_argument(
        "--weight-quant", choices=["int8"], default=None,
        help="W8A16 weights: int8 storage + per-out-channel scales "
             "(halves the decode weight stream; random init draws them "
             "directly in this form, so qwen2-7b starts on one 16 GB v5e)",
    )
    serve.add_argument(
        "--spec-decode-tokens", type=int, default=0,
        help="speculative decoding: draft up to N tokens by prompt lookup "
        "and verify them in one chunked pass (greedy rows by argmax-prefix, "
        "sampled rows by exact rejection sampling)",
    )
    serve.add_argument(
        "--spec-adaptive", action="store_true",
        help="acceptance-adaptive draft width: per-(tenant, shape) γ "
        "shrinks where the speculation ledger's acceptance EWMA misses "
        "its floor and regrows where it clears the ceiling, clamped to "
        "[1, --spec-decode-tokens] (off by default; inert unless "
        "--spec-decode-tokens > 0)",
    )
    serve.add_argument(
        "--token-timeline-capacity", type=int, default=4096,
        help="bounded per-token ITL ring entries for /debug/tokens "
        "(change-compressed, drop-oldest; 0 disables the token "
        "timeline and the goodput ledger entirely)",
    )
    serve.add_argument(
        "--token-stall-threshold-ms", type=float, default=50.0,
        help="inter-token gap above which the timeline attributes a "
        "stall to a cause (restore park, prefill convoy, rebalance "
        "handoff, spec-verify miss, scheduler wait)",
    )
    serve.add_argument(
        "--slo", action="store_true",
        help="enable the overload control plane (radixmesh_tpu/slo/): "
        "per-tenant rate limits + weighted-fair admission, deadline "
        "shedding (429/503 + Retry-After), graceful degradation tiers; "
        "/generate accepts tenant / ttft_deadline_ms / deadline_ms",
    )
    serve.add_argument(
        "--slo-ttft-ms", type=float, default=None,
        help="default TTFT SLO applied to requests carrying no explicit "
        "deadline (requires --slo)",
    )
    serve.add_argument(
        "--slo-tenant", action="append", default=[], metavar="NAME=W[:RATE]",
        help="tenant entitlement: fair-share weight W and optional "
        "sustained prompt-token rate limit RATE tok/s (repeatable; "
        "requires --slo)",
    )
    _add_kv_transfer_args(serve)
    _add_trace_args(serve)
    _add_history_args(serve)
    serve.set_defaults(fn=_run_serve)

    mh = sub.add_parser(
        "multihost-dryrun",
        help="join a jax.distributed job and run ONE sharded train step "
        "over the global (cross-host) mesh — the multi-host compute proof",
    )
    mh.add_argument("--coordinator", required=True, help="host:port of process 0")
    mh.add_argument("--num-processes", type=int, required=True)
    mh.add_argument("--process-id", type=int, required=True)
    mh.add_argument(
        "--local-devices", type=int, default=None,
        help="force N virtual CPU devices per process (rehearsal mode)",
    )
    mh.add_argument(
        "--mesh", default=None, metavar="DP,SP,TP",
        help="explicit global mesh plan (default: host-aligned auto)",
    )
    mh.set_defaults(fn=_run_multihost_dryrun)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
