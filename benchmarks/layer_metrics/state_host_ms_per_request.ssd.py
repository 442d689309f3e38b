"""state_host_ms_per_request's reader on the cell of the configuration with
Mamba-2 blocks, under a name of its own."""
from benchmarks.harness import ssd_trace

read = ssd_trace.pinned("state_host_ms_per_request")
