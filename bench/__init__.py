"""On-chip benchmark of the tiered-KV serving path (see PERF.md)."""
