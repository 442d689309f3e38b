"""moe_share_of_decode_step's reader on the cell of the configuration with
Mamba-2 blocks (10 expert blocks of 16 held un-gated experts of 128: two
grouped products a block), under a name of its own."""
from benchmarks.harness import ssd_trace

read = ssd_trace.pinned("moe_share_of_decode_step")
