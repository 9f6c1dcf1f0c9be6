"""On-chip benchmark of the federation loop (see ``BENCHMARK.json``)."""
