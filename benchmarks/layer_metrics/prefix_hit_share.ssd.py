"""prefix_hit_share's reader on the cell of the configuration with Mamba-2
blocks (cached_tokens counts the positions a request RESUMED at: a hit on
rows without a state counts for nothing), under a name of its own."""
from benchmarks.harness import ssd_trace

read = ssd_trace.pinned("prefix_hit_share")
