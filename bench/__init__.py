"""Chip benchmark of the serving path: one cell (a model configuration under
one traffic mix) per run of `bench/run.py`. Everything that belongs to one
configuration, traffic mix or per-layer metric is a file of its own, found
by the name that `BENCHMARK.json` gives it."""
