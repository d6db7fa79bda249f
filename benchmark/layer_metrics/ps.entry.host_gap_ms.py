"""`entry.host_gap_ms` under the name that moves `ps_tokens_per_s`: a per-layer metric names
the one end-to-end metric it should move."""

from benchmark.harness.readers import reader

read = reader("entry.host_gap_ms")
