"""The benchmark's own measuring code: peaks, byte counts, the trace
reducer and the comparison with the plain references."""
