"""Kernels, on the cell of the configuration with Mamba-1 layers beside two
multi-query attention layers: the decode attention's share of the chip's HBM
bandwidth in a decode step, percent; bound: memory (20 query heads on ONE KV
head of 128: twenty queries share a key, 512 B a token and layer). Bytes the
launches HAD to read a step: the live rows' context summed over the window's
decode launches (``/debug/state`` ``attn.decode_context_tokens``, counted by
the engine as it builds each launch: no mean context is guessed) x 1 024 B
(``opsbytes_jamba.kv_bytes_per_token``: K and V, two layers) / the window's
decode steps. Time a step: device seconds of the
``radixmesh_decode_fused_mh`` events / the ``jit_decode_step`` executions the
capture holds."""
from benchmarks.harness import jamba_trace
from benchmarks.harness.opsbytes_jamba import attn_decode_bytes_per_step
from benchmarks.harness.xplane import modules_matching


def read(ctx):
    kernel = jamba_trace.decode_kernel(ctx)
    tokens = jamba_trace.context_tokens_per_step(ctx)
    if not kernel or not kernel["seconds"] or tokens is None or ctx["peaks"] is None:
        return None
    steps_traced, _ = modules_matching(ctx["trace"], "jit_decode_step")
    if not steps_traced:
        return None
    return (100.0 * attn_decode_bytes_per_step(ctx["config"], tokens)
            / (kernel["seconds"] / steps_traced) / ctx["peaks"]["hbm_bytes_per_s"])
