"""On-chip benchmark of the HASFL edge simulator (see BENCHMARK.json)."""
