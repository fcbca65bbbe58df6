"""One reader per metric, found by the metric's name in BENCHMARK.json:
``read(window)`` returns the number, or None where the window holds nothing
to read (the harness then leaves the metric out of the line)."""
