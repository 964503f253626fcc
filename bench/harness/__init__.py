"""The benchmark harness: one run of one cell, driven by the data files
beside it (configurations, traffic mixes, metric readers, work counters)."""
