"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module defines ``read(rec) -> float | None`` over the run's
``bench.harness.Record``.  A reader that finds nothing to read returns
None and the metric is left out of the result line.
"""
