"""Observability: metrics registry + profiler tracing + the request-flight
tracing plane (SURVEY §5) + the fleet telemetry plane (gossiped node
digests, radix-tree convergence audit, health scoring) + the mesh-wide
plane (PR 9: cross-node trace stitching, per-shard heat/skew, TPU step
attribution) + the history axis (PR 13: bounded telemetry time-series
rings, crash-surviving black-box dumps, post-mortem doctoring)."""

from radixmesh_tpu.obs.blackbox import BlackBox, load_blackbox
from radixmesh_tpu.obs.fleet_plane import (
    FleetConfig,
    FleetPlane,
    FleetView,
    NodeDigest,
)
from radixmesh_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    get_registry,
    set_registry,
)
from radixmesh_tpu.obs.step_plane import StepAccounting
from radixmesh_tpu.obs.timeseries import TelemetryHistory
from radixmesh_tpu.obs.trace_plane import (
    FlightRecorder,
    Span,
    TraceContext,
    configure,
    get_recorder,
    new_trace_id,
    set_recorder,
    stitch_traces,
    write_trace,
)
# ``annotate`` (jax.profiler.TraceAnnotation) is taken from obs.tracing
# directly: re-exporting it here would import jax into mesh-only processes.
from radixmesh_tpu.obs.tracing import profile, recorded

__all__ = [
    "FleetConfig",
    "FleetPlane",
    "FleetView",
    "NodeDigest",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "get_registry",
    "set_registry",
    "FlightRecorder",
    "Span",
    "TraceContext",
    "configure",
    "get_recorder",
    "set_recorder",
    "write_trace",
    "new_trace_id",
    "stitch_traces",
    "StepAccounting",
    "TelemetryHistory",
    "BlackBox",
    "load_blackbox",
    "profile",
    "recorded",
]
