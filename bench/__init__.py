"""On-chip benchmark of the serving path (see ``BENCHMARK.json``)."""
