"""state_resume_share's reader on the cell of the configuration with Mamba-2
blocks (a snapshot here is a 20.4 MiB state slot beside 3 KiB a token of K/V
rows; cached_tokens counts resumed positions), under a name of its own."""
from benchmarks.harness import ssd_trace

read = ssd_trace.pinned("state_resume_share")
