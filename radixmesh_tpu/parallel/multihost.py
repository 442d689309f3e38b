"""Multi-host compute initialization: ``jax.distributed`` glue.

SURVEY §5 requires a distributed communication backend that "scales to
multi-host the way the reference's NCCL/MPI backend" was meant to. The
cache/control plane already rides the C++ DCN transport (``comm/``); this
module is the COMPUTE plane's counterpart: one ``jax.distributed`` process
per host, all chips joined into one global device mesh, XLA emitting the
cross-host collectives (ICI within a slice, DCN across slices) from the
same ``pjit``/``shard_map`` programs used single-host — no NCCL/MPI port,
by design.

On TPU pods the runtime discovers the topology; on CPU (tests, localhost
rehearsal) collectives ride Gloo, so the same multi-process program is
testable anywhere — the reference's multi-node-without-a-cluster strategy
(``correctness.py:22-29``) applied to the compute plane.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["MultihostInfo", "init_multihost", "global_mesh"]


@dataclass(frozen=True)
class MultihostInfo:
    process_index: int
    process_count: int
    local_devices: int
    global_devices: int


def init_multihost(
    coordinator: str,
    num_processes: int,
    process_id: int,
    local_device_count: int | None = None,
) -> MultihostInfo:
    """Join this process into the ``jax.distributed`` job and return the
    topology. Call before ANY other jax API touches a backend.

    ``local_device_count`` forces a virtual CPU device count per process
    (rehearsal mode); on real TPU hosts leave it ``None`` and the runtime
    reports the chips attached to this host.
    """
    import os
    import re

    if local_device_count is not None:
        # Override (not merely append) any inherited device-count flag:
        # every process of the job must agree on its local device count,
        # and a stale shell export silently breaking that is worse than
        # clobbering it.
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            "",
            os.environ.get("XLA_FLAGS", ""),
        )
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{local_device_count}"
        ).strip()
        # Virtual devices exist on the CPU platform only: rehearsal mode
        # IS a CPU run unless the caller named another platform.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
        # CPU processes have no ICI; collectives ride Gloo over TCP.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    return MultihostInfo(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_devices=len(jax.local_devices()),
        global_devices=len(jax.devices()),
    )


def global_mesh(plan=None):
    """A device mesh over EVERY process's chips. The default plan keeps
    the process (host) boundary on the dp axis: sp/tp factorize ONE
    host's chips (per-layer, latency-sensitive collectives stay on
    intra-host ICI) and dp multiplies across hosts (gradient/batch
    reductions amortize over DCN). ``jax.devices()`` lists devices
    process-contiguously and dp is the outermost mesh axis, so the
    reshape lands each host's chips in their own dp rows."""
    import jax

    from radixmesh_tpu.parallel.sharding import MeshPlan, make_mesh

    if plan is None:
        local = MeshPlan.auto(len(jax.local_devices()))
        plan = MeshPlan(
            dp=jax.process_count() * local.dp, sp=local.sp, tp=local.tp
        )
    return make_mesh(plan, devices=jax.devices())
