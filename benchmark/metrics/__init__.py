"""One reader per metric, named as in BENCHMARK.json: ``read(run)``
returns the metric's value, or None where the run has nothing to read."""
