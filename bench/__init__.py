"""The benchmark: cells of BENCHMARK.json, run on a TPU by ``bench/run.py``."""
