"""Kernels: the index-scoring kernel's share of the chip's HBM bandwidth,
percent. Bound: memory (32 FLOP a byte of key, far under the ridge).

Bytes the kernel HAD to read per decode step: the index keys the window's
decode launches scored (counter ``/debug/state``
``attn.sparse.decode.index_keys_scored``: context x full layers, summed
over rows, delta over the window) x one key's bytes
(``opsbytes_dsa.index_key_bytes``: 256 B) / the window's decode steps.
Time per step: device time of the ``radixmesh_dsa_index_decode`` kernel
events in the trace / the ``jit_decode_step`` executions it holds. A
program without the counter or the kernel: nothing to read."""
from benchmarks.harness.opsbytes_dsa import index_key_bytes
from benchmarks.harness.xplane import modules_matching


def sparse_delta(ctx, site: str, key: str):
    """Delta over the window of ``attn.sparse[site][key]``, or None (a
    configuration without an indexer, a program without the counter)."""
    if "index_topk" not in ctx["config"]:
        return None
    a0, a1 = ((ctx[k]["state"].get("attn") or {}).get("sparse")
              for k in ("before", "after"))
    if not a0 or not a1:
        return None
    return a1[site][key] - a0[site][key]


def kernel_per_step(ctx, name: str):
    """``(window's decode steps, kernel seconds per traced decode step)``
    or None."""
    trace = ctx["trace"]
    planes = (trace or {}).get("planes") or {}
    if not planes:
        return None
    kernel = next(iter(planes.values()))["kernels"].get(name)
    steps_traced, _ = modules_matching(trace, "jit_decode_step")
    steps = (ctx["after"]["state"]["engine"]["decode_steps"]
             - ctx["before"]["state"]["engine"]["decode_steps"])
    if not kernel or not steps_traced or steps <= 0:
        return None
    return steps, kernel["seconds"] / steps_traced


def read(ctx):
    keys = sparse_delta(ctx, "decode", "index_keys_scored")
    got = kernel_per_step(ctx, "radixmesh_dsa_index_decode")
    if not keys or got is None or ctx["peaks"] is None:
        return None
    steps, seconds = got
    return (100.0 * keys / steps * index_key_bytes(ctx["config"]) / seconds
            / ctx["peaks"]["hbm_bytes_per_s"])
