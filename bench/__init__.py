"""The benchmark of the RidgeWalker walk engine: configurations, traffic
mixes, per-layer metric readers, the plain reference and the harness that
runs one cell once (``bench/run.py``).  See PERF.md."""
