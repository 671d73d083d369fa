"""On-chip serving benchmark of the DA serving stack (see BENCHMARK.json)."""
