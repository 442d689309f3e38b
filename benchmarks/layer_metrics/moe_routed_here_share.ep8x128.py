"""moe_routed_here_share's reader on the cell of the configuration with
Mamba-2 blocks (16 of 128 experts held, no groups: 12.5 with an even
router), under a name of its own."""
from benchmarks.harness import ssd_trace

read = ssd_trace.pinned("moe_routed_here_share")
