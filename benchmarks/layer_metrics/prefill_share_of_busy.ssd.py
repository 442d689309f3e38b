"""prefill_share_of_busy.docs' reader on the cell of the configuration with
Mamba-2 blocks (every prefill is a 64-528-token tail from a restored state
through radixmesh_ssd_chunk), under a name of its own."""
from benchmarks.harness import ssd_trace

read = ssd_trace.pinned("prefill_share_of_busy.docs")
