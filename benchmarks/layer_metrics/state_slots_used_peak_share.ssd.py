"""state_slots_used_peak_share's reader on the cell of the configuration with
Mamba-2 blocks (321 slots of 20.4 MiB: they run full by design), under a
name of its own."""
from benchmarks.harness import ssd_trace

read = ssd_trace.pinned("state_slots_used_peak_share")
