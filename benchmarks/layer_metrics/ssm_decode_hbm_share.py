"""Kernels: the state-space side's share of the chip's HBM bandwidth in a
decode step, percent; bound: memory (64 rows against 2560 x 10240 and 5120
x 2560 matrices, and 3.2 MB of state a row read and written). Bytes it HAD
to move a step (``opsbytes_phi4flash.ssm_decode_bytes_per_step``): every
Mamba and GMU operator's weights once + each live row's state read and
written back (live rows a step: delta ``generated_tokens`` / delta
``decode_steps``). Time a step: device seconds under ``rm_ssm`` and
``rm_gmu`` in ``jit_decode_step`` / the executions the capture holds."""
from benchmarks.harness import phi4flash_trace
from benchmarks.harness.opsbytes_phi4flash import ssm_decode_bytes_per_step


def read(ctx):
    ssm = phi4flash_trace.of_run(ctx, "ssm")
    rows, peaks = phi4flash_trace.window_rows(ctx), ctx["peaks"]
    if ssm is None or peaks is None or rows is None:
        return None
    per_step = ssm["seconds"] / ssm["executions"]
    return (100.0 * ssm_decode_bytes_per_step(ctx["config"], rows) / per_step
            / peaks["hbm_bytes_per_s"])
