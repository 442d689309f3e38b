"""Kernels: the conv operators' share of the chip's HBM bandwidth in a
decode step, percent. Bound: memory (32 rows against 2048 x 6144 and 2048
x 2048 matrices: 0.03 FLOP a weight byte short of the ridge by 7x).

Bytes the operators HAD to move per decode step
(``opsbytes_lfm2.conv_decode_bytes_per_step``): every conv layer's three
matrices once + each live row's state read and written back (live rows a
step: delta ``generated_tokens`` / delta ``decode_steps``). Time per step:
device seconds of the ops under ``rm_shortconv`` inside ``jit_decode_step``
(``harness/lfm2_trace.py``) / the executions the capture holds."""
from benchmarks.harness import lfm2_trace
from benchmarks.harness.opsbytes_lfm2 import conv_decode_bytes_per_step


def read(ctx):
    conv, peaks = lfm2_trace.of_run(ctx), ctx["peaks"]
    a, b = ctx["before"]["state"]["engine"], ctx["after"]["state"]["engine"]
    steps = b["decode_steps"] - a["decode_steps"]
    if conv is None or peaks is None or steps <= 0:
        return None
    rows = (ctx["after"]["stats"]["generated_tokens"]
            - ctx["before"]["stats"]["generated_tokens"]) / steps
    need = conv_decode_bytes_per_step(ctx["config"], rows)
    return (100.0 * need / (conv["seconds"] / conv["executions"])
            / peaks["hbm_bytes_per_s"])
