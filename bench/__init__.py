"""On-chip benchmark of the BAFDP federated trainer (see BENCHMARK.json)."""
