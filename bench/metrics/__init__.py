"""One reader per metric, found by the metric's name in BENCHMARK.json.

``read(run)`` takes the harness's :class:`bench.lib.harness.Run` and
returns the number, or ``None`` where the run holds nothing to read; the
harness then leaves the metric out of the result line.
"""
