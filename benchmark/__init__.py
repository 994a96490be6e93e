"""The benchmark of analytics-zoo-tpu: BENCHMARK.json's cells, run one at a
time by ``python3 benchmark/run.py``.  See benchmark/README.md."""
