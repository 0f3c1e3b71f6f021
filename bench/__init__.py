"""The chip benchmark of the cluster system: one cell per run, driven by
``BENCHMARK.json`` and by the files under this directory (configurations,
traffic mixes, metric readers), found by name."""
