"""Applications built on the spec API: emit → cluster → collect specs that
drive the kernels through the cluster runtime (``chip_smoke.py``, the
benchmarks and the tests run them)."""
